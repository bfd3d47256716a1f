"""Operations and bytes a kernel call needs, from the call's own shapes.

The shapes come from the HLO text that names the op event in the trace
(``%gather_scores.9 = f32[64,16,1]{...} custom-call(...)``); the counts are
what the algorithm needs, not what an implementation moves (a
kernel that fetches 8-row groups to read one row is charged one row).

gather_scores (kernels/gather_distance.py): for B queries of width d, each
scoring C candidate rows, the call needs the B*C rows (4*d bytes each) and
their norms (4 bytes), the B*C candidate ids (4 bytes), the B queries
(4*d bytes), and writes B*C scores (4 bytes); it does 2*B*C*d operations.
Its output is f32[B, C, 1] and its query operand f32[B, 1, d].
"""
from __future__ import annotations

import re

SHAPE = re.compile(r"\b(f32|s32|bf16|s8|u32)\[([0-9,]+)\]")
GATHER = "gather_scores"


def _shapes(text: str) -> list[list[int]]:
    return [[int(x) for x in dims.split(",")]
            for _, dims in SHAPE.findall(text)]


def gather_need(text: str) -> tuple[int, int] | None:
    """(bytes, operations) one gather_scores call needs, or None when its
    shapes cannot be read."""
    shapes = _shapes(text)
    out = next((s for s in shapes if len(s) == 3 and s[2] == 1), None)
    q = [s for s in shapes if len(s) == 3 and s[1] == 1]
    if out is None or not q:
        return None
    b, c, _ = out
    d = q[-1][2]
    return 4 * (b * c * (d + 1) + b * c + b * d + b * c), 2 * b * c * d


def roofline_share(events, peaks: dict) -> float | None:
    """Percent of the chip's roofline that the gather calls reach: the
    least time their bytes or operations need at the published peaks, over
    their device time. None when no call, or no call's shapes, is found."""
    need_s = busy_s = 0.0
    for e in events:
        if not e.name.lstrip("%").startswith(GATHER + "."):
            continue
        got = gather_need(e.name)
        if got is None:
            continue
        nbytes, ops = got
        need_s += max(nbytes / peaks["hbm_bytes_per_s"],
                      ops / peaks["bf16_flops_per_s"])
        busy_s += e.dur_ns * 1e-9
    return 100.0 * need_s / busy_s if busy_s > 0 else None
