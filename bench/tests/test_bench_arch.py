"""The architecture module a configuration names
(``bench/reference/<name>.py``): how ``manifest.resolve`` finds it and what
it refuses, the weights made from its layout, and a configuration added
by new files alone, run at CPU size."""
import hashlib
import json
import os
import pathlib
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import manifest, run, weights
from bench.reference.csgd import path_names
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "granite-moe-csgd-1chip"
COPY = "lm_copy"
SEED = 2_718_281_829

# granite-moe-1b-a400m's leaves in leaf order, cut to one layer: shape,
# type and the first 16 hex digits of the sha256 of the bytes that
# weights.make gave for seed 3141592653 before the layout moved into the
# architecture module
WEIGHTS_SEED = 3_141_592_653
GRANITE_L1 = {
    "blocks.attn.wk.w": ((1, 1024, 512), "bfloat16", "a559dfc8071979e3"),
    "blocks.attn.wo.w": ((1, 1024, 1024), "bfloat16", "7ad3680899fe68c0"),
    "blocks.attn.wq.w": ((1, 1024, 1024), "bfloat16", "17134a0b75f31d04"),
    "blocks.attn.wv.w": ((1, 1024, 512), "bfloat16", "d3c3403d773b2b54"),
    "blocks.attn_norm.w": ((1, 1024), "bfloat16", "95fbbe9f3324b7d9"),
    "blocks.mlp_norm.w": ((1, 1024), "bfloat16", "95fbbe9f3324b7d9"),
    "blocks.moe.router.w": ((1, 1024, 32), "float32", "77ac165fa3e0e267"),
    "blocks.moe.wg": ((1, 32, 1024, 512), "bfloat16", "38235ba7dcdb6f65"),
    "blocks.moe.wi": ((1, 32, 1024, 512), "bfloat16", "6e7465eb6f5dccd2"),
    "blocks.moe.wo": ((1, 32, 512, 1024), "bfloat16", "a4d8b875c70adfdc"),
    "embed.w": ((49408, 1024), "bfloat16", "07982eaa1844520d"),
    "final_norm.w": ((1024,), "bfloat16", "95fbbe9f3324b7d9"),
}


def granite() -> dict:
    return json.loads((ROOT / "bench" / "configs" /
                       "granite-moe-1b-a400m.json").read_text())["model"]


def test_weights_keep_their_tree_and_bytes():
    lm = manifest.architecture("transformer_lm")
    m = granite()
    six = weights.shapes(lm, m)
    assert list(six) == list(GRANITE_L1)
    for k, (shape, _, _) in GRANITE_L1.items():
        assert six[k] == ((m["n_layers"],) + shape[1:]
                          if k.startswith("blocks.") else shape), k
    params = weights.make(lm, dict(m, n_layers=1), WEIGHTS_SEED)
    got = {k: (x.shape, str(x.dtype),
               hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16])
           for k, x in zip(path_names(params), jax.tree.leaves(params))}
    assert got == GRANITE_L1


def test_a_kind_of_the_architecture_is_drawn_by_its_init():
    def layout(m):
        return {"a": ((3, 4), "float32", "fan_in"),
                "b": ((5,), "bfloat16", "halves")}

    def init(kind, key, shape):
        assert kind == "halves"
        return jnp.full(shape, 0.5) + 0 * jax.random.normal(key, shape)

    with_init = types.SimpleNamespace(__name__="arch", layout=layout,
                                      init=init)
    p = weights.make(with_init, {}, 7)
    assert p["b"].dtype == jnp.bfloat16
    assert np.all(np.asarray(p["b"], np.float32) == 0.5)
    key = jax.random.fold_in(weights.seed_key(7), 0)
    # the built-in draw of leaf 0 (the jitted division may round apart)
    np.testing.assert_allclose(
        p["a"], jax.random.normal(key, (3, 4), jnp.float32) / np.sqrt(3),
        rtol=1e-6)
    with pytest.raises(ValueError, match="halves"):
        weights.make(types.SimpleNamespace(__name__="arch", layout=layout),
                     {}, 7)


def _with_reference(tmp, reference):
    root = tiny.make_root(tmp, CELL)
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if reference is None:
        del cfg["reference"]
    else:
        cfg["reference"] = reference
    path.write_text(json.dumps(cfg))
    return root


@pytest.mark.parametrize("case", ["no_file", "no_key", "not_a_name"]
                         + [f"lacks_{k}" for k in manifest.ARCH_NAMES])
def test_resolve_refuses_a_missing_or_incomplete_module(tmp_path, case):
    if case == "no_file":
        root = _with_reference(tmp_path, "nowhere")
    elif case == "no_key":
        root = _with_reference(tmp_path, None)
    elif case == "not_a_name":
        root = _with_reference(tmp_path, "../reference/transformer_lm")
    else:
        root = _with_reference(tmp_path, "partial")
        lacking = case.removeprefix("lacks_")
        (root / "bench" / "reference" / "partial.py").write_text(
            "".join(f"{k} = None\n" for k in manifest.ARCH_NAMES
                    if k != lacking))
    man = manifest.load(root)
    with pytest.raises(manifest.ManifestError):
        manifest.resolve(man, CELL, root)


def _files(root):
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in (root / "bench").rglob("*") if p.is_file()}


def _add_cell(root, module):
    """A configuration whose file names ``module``, a copy of the
    transformer_lm module, and a cell of it under the first cell's traffic
    and limits: new files under ``bench/`` and new entries in
    ``BENCHMARK.json``."""
    shutil.copy(ROOT / "bench" / "reference" / "transformer_lm.py",
                root / "bench" / "reference" / f"{module}.py")
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    (root / "bench" / "configs" / "tiny-copy.json").write_text(
        json.dumps(dict(cfg, name="tiny-copy", reference=module)))
    cell = f"{CELL}-copy"
    shutil.copy(root / "bench" / "limits" / f"{CELL}.json",
                root / "bench" / "limits" / f"{cell}.json")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(man["configs"][0], name="tiny-copy",
                               file="bench/configs/tiny-copy.json"))
    w = next(w for w in man["workloads"] if w["name"] == CELL)
    man["workloads"].append(dict(w, name=cell, config="tiny-copy"))
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return cell


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"), CELL)
    before = _files(root)
    cell = _add_cell(root, COPY)
    return root, cell, before, _files(root)


def test_a_configuration_is_added_by_new_files_alone(checkout):
    root, cell, before, after = checkout
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        f"bench/reference/{COPY}.py", "bench/configs/tiny-copy.json",
        f"bench/limits/{cell}.json"}
    man = manifest.load(root)
    for name, module in ((CELL, "transformer_lm"), (cell, COPY)):
        arch = manifest.resolve(man, name, root)["arch"]
        assert pathlib.Path(arch.__file__) == \
            root / "bench" / "reference" / f"{module}.py"


def _drive(root, cell):
    args = run.parse(["--workload", cell, "--seed", str(SEED),
                      "--seconds", "1", "--trace", "0"])
    with open(os.devnull, "w") as sink:
        return run.run_cell(args, require_chip=False, root=root,
                            compile_cache=False, out=sink)


def test_the_copied_module_runs_its_cell_alike(checkout):
    root, cell, _, _ = checkout
    result, prog, ref, ctx = _drive(root, CELL)
    c_result, c_prog, c_ref, c_ctx = _drive(root, cell)
    assert result["correct"] and c_result["correct"], c_result["check"]
    assert c_ctx["arch"] is not ctx["arch"]
    assert c_prog["loss"] == prog["loss"]
    assert c_ref["loss"] == ref["loss"]
    assert c_result["check"] == result["check"]
    for k in ("flops_per_token", "flash_forward_flops", "shapes"):
        assert c_ctx[k] == ctx[k], k
