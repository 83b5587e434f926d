"""``BENCHMARK.json``: load, check against its schema, and
resolve a cell to its configuration, its architecture module, traffic and
metrics by name."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# what a configuration's architecture module provides (its optional
# ``init(kind, key, shape)`` draws weight kinds ``weights.make`` lacks)
ARCH_NAMES = ("layout", "loss", "train_flops_per_token", "matmul_params",
              "flash_forward_flops", "published_keys")


class ManifestError(ValueError):
    pass


def _line(s, what: str) -> None:
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        raise ManifestError(f"{what}: 1 to 200 characters on one line")


def _name(s, what: str) -> None:
    if not isinstance(s, str) or not NAME.match(s):
        raise ManifestError(f"{what}: bad name {s!r}")


def _keys(entry: dict, allowed: set, required: set, what: str) -> None:
    extra = set(entry) - allowed
    missing = required - set(entry)
    if extra or missing:
        raise ManifestError(f"{what}: unexpected keys {sorted(extra)}, "
                            f"missing {sorted(missing)}")


def validate(man: dict) -> dict:
    if set(man) != TOP_KEYS:
        raise ManifestError(f"top-level keys must be {sorted(TOP_KEYS)}")
    if not isinstance(man["run_seconds"], int) \
            or not 1 <= man["run_seconds"] <= 51:
        raise ManifestError("run_seconds: a whole number from 1 to 51")
    cfg_keys = {"name", "source", "file", "reduced", "why"}
    names = set()
    for c in man["configs"]:
        _keys(c, cfg_keys, cfg_keys, f"config {c.get('name')}")
        _name(c["name"], "config name")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        for k in c["reduced"]:
            _name(k, "reduced key")
        names.add(c["name"])
    if len(names) != len(man["configs"]):
        raise ManifestError("two configurations share a name")
    w_keys = {"name", "config", "traffic", "chips", "why"}
    seen = set()
    for w in man["workloads"]:
        _keys(w, w_keys, w_keys, f"workload {w.get('name')}")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _line(w["why"], "workload why")
        if w["config"] not in names:
            raise ManifestError(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in seen:
            raise ManifestError("a configuration and traffic pair repeats")
        seen.add((w["config"], w["traffic"]))
    cells = [w["name"] for w in man["workloads"]]
    if len(set(cells)) != len(cells):
        raise ManifestError("two cells share a name")
    metric_names = []
    e2e = {"name", "unit", "better", "bound", "source"}
    for m in man["end_to_end"]:
        _keys(m, e2e | {"workloads"}, e2e, f"metric {m.get('name')}")
        if m["source"] not in SOURCES_E2E:
            raise ManifestError(f"{m['name']}: end-to-end source must be "
                                f"one of {SOURCES_E2E}")
        if not 0 < m["bound"] <= 0.25:
            raise ManifestError(f"{m['name']}: bound in (0, 0.25]")
        metric_names.append(m["name"])
    pl = {"name", "unit", "better", "source", "layer", "moves"}
    e2e_names = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        _keys(m, pl | {"workloads"}, pl, f"metric {m.get('name')}")
        if m["source"] not in SOURCES:
            raise ManifestError(f"{m['name']}: unknown source")
        _line(m["layer"], "layer")
        if m["moves"] not in e2e_names:
            raise ManifestError(f"{m['name']}: moves an unknown metric")
        metric_names.append(m["name"])
    for m in man["end_to_end"] + man["per_layer"]:
        _name(m["name"], "metric name")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            raise ManifestError(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise ManifestError(f"{m['name']}: better is lower or higher")
        for w in m.get("workloads", []):
            if w not in cells:
                raise ManifestError(f"{m['name']}: unknown cell {w}")
    if len(set(metric_names)) != len(metric_names):
        raise ManifestError("two metrics share a name")
    if "setup_s" not in e2e_names:
        raise ManifestError("setup_s is missing")
    return man


def load(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return validate(json.load(f))


def metrics_of(man: dict, cell: str, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in man[kind]
            if cell in m.get("workloads", [cell])]


def architecture(name: str, root: pathlib.Path = ROOT):
    """The architecture module ``bench/reference/<name>.py`` of the
    checkout at ``root``, loaded from its file: everything of the benchmark
    that depends on the architecture (``ARCH_NAMES``)."""
    if not isinstance(name, str) or not name.isidentifier():
        raise ManifestError(f"reference: bad module name {name!r}")
    path = root / "bench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"reference {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [k for k in ARCH_NAMES if not hasattr(mod, k)]
    if missing:
        raise ManifestError(f"reference {name!r} lacks {missing}")
    return mod


def resolve(man: dict, cell: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files loaded,
    and the architecture module its configuration names (``"arch"``)."""
    try:
        w = next(w for w in man["workloads"] if w["name"] == cell)
    except StopIteration:
        raise ManifestError(f"no workload named {cell!r}") from None
    c = next(c for c in man["configs"] if c["name"] == w["config"])
    with open(root / c["file"]) as f:
        config = json.load(f)
    arch = architecture(config.get("reference"), root)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    limits_path = root / "bench" / "limits" / f"{cell}.json"
    limits = json.loads(limits_path.read_text()) \
        if limits_path.exists() else None
    return {"cell": w, "config": config, "arch": arch, "traffic": traffic,
            "limits": limits}
