"""The train step's phase names and the training loop's profiler spans.

The step names its phases with ``jax.named_scope``; the names reach the
optimized HLO's ``op_name`` metadata, which is what the benchmark reads
device time per phase from (``bench/scopes.py``).  One smoke-size step
(CSGD-ASSS, block top-k on the fused EF kernels, bucketed exchange) is
compiled on the CPU and shared by the tests of its metadata.
"""
from __future__ import annotations

import glob
import re

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
from repro.core import ArmijoConfig, Compressor
from repro.core.dcsgd import worker_compress_aggregate
from repro.kernels import dispatch
from repro.launch import spans, train
from repro.launch.mesh import make_mesh
from repro.launch.train_step import (build_train_step, init_opt_state,
                                     opt_state_shardings)
from repro.models import build_model
from repro.sharding import param_shardings

PHASES = {"csgd_grad", "csgd_armijo", "csgd_ef", "csgd_codec",
          "csgd_apply"}
TOKEN = re.compile(r"\bcsgd_\w+")


@pytest.fixture(scope="module")
def op_names():
    """The op_name of every instruction of the compiled step that runs in
    it (a reduction's region names its ops without the program's path)."""
    cfg = get_smoke_config("paper-lm-100m")
    model = build_model(cfg)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("t", 64, 2, "train"),
        optimizer=OptimizerConfig(
            kind="csgd_asss", armijo=ArmijoConfig(), transport="bucketed",
            compressor=Compressor(gamma=0.01, method="block_topk",
                                  block=1024)))
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh), dispatch.using("pallas-interpret"):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, param_shardings(params, mesh))
        state = init_opt_state(params, run, 1,
                               stacked_mask=model.stacked_mask(params))
        state = jax.device_put(
            state, opt_state_shardings(state, params, mesh, run))
        batch = {"tokens": jax.device_put(
            jnp.zeros((2, 64), jnp.int32), NamedSharding(mesh, P("data")))}
        step = build_train_step(model, run, mesh)(params, batch)
        text = step.lower(params, state, batch).compile().as_text()
    return re.findall(r'op_name="(jit\(worker_fn\)/[^"]*)"', text)


def outermost(op_name: str) -> str | None:
    m = TOKEN.search(op_name)
    return m.group(0) if m else None


def test_step_names_each_phase_and_no_other(op_names):
    assert {t for n in op_names for t in TOKEN.findall(n)} == PHASES


def test_backward_falls_under_grad(op_names):
    backward = [n for n in op_names if "transpose(" in n]
    assert backward
    assert {outermost(n) for n in backward} == {"csgd_grad"}


@pytest.mark.parametrize("kernel, phase", [
    ("ef_stats_telemetry", "csgd_ef"), ("ef_apply", "csgd_ef"),
    ("pack_words", "csgd_codec"), ("unpack_words", "csgd_codec")])
def test_kernels_fall_under_their_phase(op_names, kernel, phase):
    calls = [n for n in op_names if f"jit({kernel})" in n]
    assert calls
    assert {outermost(n) for n in calls} == {phase}


def test_armijo_loop_falls_under_armijo(op_names):
    loop = [n for n in op_names if re.search(r"csgd_armijo/while\b", n)]
    assert loop


def _scoped_eqns(jaxpr, scope: str, stack: str = "") -> list:
    """(primitive name, first operand shape) of every equation that runs
    under ``scope``, nested jaxprs (jit, shard_map, loops, Pallas kernel
    bodies) included."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if scope in here.split("/"):
            found.append((eqn.primitive.name,
                          getattr(eqn.invars[0].aval, "shape", ())
                          if eqn.invars else ()))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    found += _scoped_eqns(sub, scope, here)
    return found


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("transport", ["bucketed", "perleaf"])
def test_fused_ef_selection_has_no_sort_or_gather(transport, use_kernel):
    """On the fused path pass 1 hands out the wire pairs, so no top-k or
    sort runs in ``csgd_ef`` and nothing gathers from the (L, nb, block)
    block view — in ``select_and_encode`` (the bucketed transport) and in
    the per-leaf transport's fused branch.  The jnp path
    (``use_kernel=False``) still ranks with ``lax.top_k`` and gathers the
    values from the blocks.  (The telemetry's k-entry gather of g at the
    wire indices runs on both.)"""
    comp = Compressor(gamma=0.01, method="block_topk", block=1024,
                      min_compress_size=64, use_kernel=use_kernel)
    tree = {"w": jnp.zeros((3, 2500)), "v": jnp.zeros((4096,))}
    mask = {"w": True, "v": False}
    mesh = make_mesh((1,), ("data",))

    def worker(g, m):
        return worker_compress_aggregate(g, m, jnp.float32(0.1), comp,
                                         ("data",), stacked_mask=mask,
                                         transport=transport)[:2]

    step = jax.shard_map(worker, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)
    with dispatch.using("pallas-interpret"):
        eqns = _scoped_eqns(jax.make_jaxpr(step)(tree, tree).jaxpr,
                            "csgd_ef")
    names = {n for n, _ in eqns}
    ranks = names & {"top_k", "sort"}
    block_gathers = [s for n, s in eqns
                     if n == "gather" and len(s) == 3 and s[-1] == 1024]
    if use_kernel:
        assert "pallas_call" in names
        assert not ranks and not block_gathers
    else:
        assert "pallas_call" not in names
        assert ranks == {"top_k"} and len(block_gathers) == 2


def test_profile_dir_writes_the_loop_spans(tmp_path):
    from jax.profiler import ProfileData

    train.main(["--arch", "paper-lm-100m", "--smoke", "--steps", "3",
                "--seq-len", "32", "--global-batch", "2", "--mesh", "1x1",
                "--log-every", "1", "--ckpt-dir", str(tmp_path / "ckpt"),
                "--ckpt-every", "1", "--profile-dir", str(tmp_path / "prof"),
                "--profile-steps", "1:3"])
    paths = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    names = [e.name for plane in ProfileData.from_file(paths[0]).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events]
    for span in ("train.step", "train.make_batch", "train.put_batch",
                 "train.wait", "train.divergence_read", "train.log",
                 "train.checkpoint"):
        assert names.count(span) == 2, span


@pytest.mark.parametrize("spec, steps", [("1:3", (1, 3)), ("0:1", (0, 1))])
def test_parse_steps(spec, steps):
    assert spans.parse_steps(spec) == steps


@pytest.mark.parametrize("spec", ["3:3", "2:1", "-1:2", "1", "a:b"])
def test_parse_steps_refuses(spec):
    with pytest.raises(ValueError):
        spans.parse_steps(spec)
