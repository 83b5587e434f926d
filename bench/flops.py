"""Operations and bytes counted from shapes, where they do not depend on
the architecture: attended pairs, and the rows and bytes of the fused EF
passes (``ef_topk_roofline``).  A model's FLOPs, the yardstick of
``mfu``, ``step_mfu`` and ``flash_attn_roofline``, are counted by its
architecture module (``bench/reference/<name>.py``).
"""
from __future__ import annotations


def attention_pairs(seq: int) -> int:
    """Causal (query, key) pairs of one row of ``seq`` positions."""
    return seq * (seq + 1) // 2


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
