"""BENCHMARK.json and the files it names: the manifest's schema, and a
loader that refuses bad names, units and keys."""
import ast
import copy
import json
import pathlib

import pytest

from bench import manifest

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def test_every_cell_resolves(man):
    for w in man["workloads"]:
        e = manifest.resolve(man, w["name"], ROOT)
        t = e["traffic"]
        for k in ("optimizer", "mesh", "seq_len", "global_batch",
                  "loss_steps", "check_steps", "trace_steps"):
            assert k in t, (w["name"], k)
        assert t["mesh"][0] * t["mesh"][1] == w["chips"]
        assert e["limits"] is not None, w["name"]
        assert not e["config"]["reduced"] or set(
            e["config"]["reduced"]) <= set(e["config"]["model"])


def test_every_metric_has_a_reader(man):
    for m in man["end_to_end"] + man["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_four_chip_cells_at_most_half(man):
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


@pytest.mark.parametrize("path, value", [
    (("workloads", 0, "name"), "has space"),
    (("workloads", 0, "name"), "a/b"),
    (("end_to_end", 0, "unit"), "tokens per second"),
    (("end_to_end", 0, "unit"), "µs"),
    (("end_to_end", 0, "better"), "more"),
    (("end_to_end", 0, "bound"), 0.3),
    (("end_to_end", 0, "source"), "program_counter"),
    (("per_layer", 0, "moves"), "latency"),
    (("workloads", 0, "chips"), 2),
    (("run_seconds",), 52),
])
def test_loader_refuses(man, path, value):
    bad = copy.deepcopy(man)
    node = bad
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_loader_refuses_extra_key_and_duplicate(man):
    bad = copy.deepcopy(man)
    bad["per_layer"][0]["why"] = "no such key"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)
    bad = copy.deepcopy(man)
    bad["per_layer"].append(dict(bad["per_layer"][0]))
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_reference_imports_nothing_of_the_program():
    for path in (ROOT / "bench" / "reference").glob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.split(".")[0] == "repro" for n in names), path


def test_configs_depart_from_published_only_in_reduced(man):
    """Every published key the configuration's architecture module maps
    (its ``published_keys``) keeps its value unless ``reduced`` names it."""
    for c in man["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        keys = manifest.architecture(f["reference"], ROOT).published_keys
        assert f["reduced"] == c["reduced"], c["name"]
        changed = {keys[k] for k, v in f["published"].items()
                   if k in keys and f["model"][keys[k]] != v}
        assert changed == set(c["reduced"]), (c["name"], changed)
