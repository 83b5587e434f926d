"""A benchmark checkout at CPU size for the tests: the real manifest's
metrics, one cell cut from a real cell (its traffic with 64-token rows,
its limits), and a two-layer model of the same family and precision,
with the checkout's own copy of its architecture module."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = {"name": "tiny", "family": "moe", "n_layers": 2, "d_model": 128,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 64,
        "vocab_size": 512, "n_experts": 8, "experts_per_token": 2,
        "moe_d_ff": 64, "capacity_factor": 1.25, "router_aux_coef": 0.01,
        "rope_theta": 10000.0, "norm_eps": 1e-6, "tie_embeddings": True,
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "attn_chunk": 1024, "remat": True}


def lm100m() -> dict:
    """The program's paper-lm-100m, the model of the recorded trace and
    of the hand counts."""
    from repro.configs.paper_models import LM_100M_CONFIG
    return dataclasses.asdict(LM_100M_CONFIG)


def make_root(tmp: pathlib.Path, cell: str, rows: int = 4,
              seq: int = 64) -> pathlib.Path:
    """Write the tiny checkout under ``tmp``; the cell keeps its name,
    its mesh and its limits."""
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next(w for w in man["workloads"] if w["name"] == cell)
    for d in ("configs", "traffic", "limits", "reference"):
        (tmp / "bench" / d).mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "reference": "transformer_lm",
                    "model": TINY}))
    shutil.copy(ROOT / "bench" / "reference" / "transformer_lm.py",
                tmp / "bench" / "reference" / "transformer_lm.py")
    t = json.loads((ROOT / "bench" / "traffic" /
                    f"{w['traffic']}.json").read_text())
    t.update(seq_len=seq, global_batch=rows * t["mesh"][0], loss_steps=8,
             trace_steps=2)
    (tmp / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
        json.dumps(t))
    shutil.copy(ROOT / "bench" / "limits" / f"{cell}.json",
                tmp / "bench" / "limits" / f"{cell}.json")
    man["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "CPU size"}]
    man["workloads"] = [dict(w, config="tiny")]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [x for x in m["workloads"] if x == cell]
    man["per_layer"] = [m for m in man["per_layer"]
                        if m.get("workloads", [cell])]
    man["end_to_end"] = [m for m in man["end_to_end"]
                         if m.get("workloads", [cell])]
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp
