"""Operations and bytes counted from shapes: the yardstick of ``mfu``,
``step_mfu`` and the kernels' roofline shares.

Model FLOPs per trained token count the matmuls of the published shapes,
forward and backward (6 per weight that a token meets): attention's four
projections, the MLP's three (for a sparse-expert layer, those of the
``experts_per_token`` experts a token is routed to, plus the router) and
the output head over the true vocabulary.  The input-embedding lookup
adds none.  Attention's score and value products add ``4 * H * hd`` per
attended (query, key) pair forward, 3x that forward and backward; causal
masking halves them: a row of S positions attends S(S+1)/2 pairs.
Armijo trial forwards and recomputation are not counted.
"""
from __future__ import annotations


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def matmul_params(m: dict) -> int:
    """Weights a token is multiplied by, once each."""
    D, hd = m["d_model"], _hd(m)
    H, KV = m["n_heads"], m["n_kv_heads"]
    attn = D * (H + 2 * KV) * hd + H * hd * D
    if m["family"] == "moe":
        ffn = m["experts_per_token"] * 3 * D * m["moe_d_ff"] \
            + D * m["n_experts"]
    else:
        ffn = 3 * D * m["d_ff"]
    return m["n_layers"] * (attn + ffn) + D * m["vocab_size"]


def attention_pairs(seq: int) -> int:
    """Causal (query, key) pairs of one row of ``seq`` positions."""
    return seq * (seq + 1) // 2


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at ``seq``
    positions per row."""
    attn = 3 * 4 * m["n_heads"] * _hd(m) * attention_pairs(seq) / seq
    return 6.0 * matmul_params(m) + m["n_layers"] * attn


def flash_forward_flops(m: dict, rows: int, seq: int) -> float:
    """One causal attention forward over ``rows`` rows (all layers' calls
    are alike; this is one call)."""
    return 4.0 * rows * m["n_heads"] * _hd(m) * attention_pairs(seq)


def ef_rows(shapes: dict, opt) -> int:
    """Block rows the fused EF passes stream: every compressed layer row,
    padded to whole blocks."""
    rows = 0
    for path, shape in shapes.items():
        L, d = opt.rows(path, shape)
        if not opt.sent_whole(d):
            rows += L * -(-d // opt.block)
    return rows


def ef_pass_bytes(rows: int, block: int) -> dict[str, float]:
    """Least HBM bytes of each fused EF pass over ``rows`` block rows of
    float32 memory and gradient: pass 1 reads both and writes one
    threshold and two moments per row; pass 2 reads both and the
    thresholds and writes the sent values and the new memory."""
    n = rows * block * 4.0
    return {"stats": 2 * n + rows * 12.0, "update": 4 * n + rows * 4.0}
