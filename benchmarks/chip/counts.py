"""A step's least time on the chip from its operations and bytes.

What a step of a model must compute and move is counted by the model's
code (``code/<code>.py``: ``decode_step(m, rows, ctx_rows)``, from shapes
alone), which counts weights and its cache at ``BYTES`` each.
"""
from __future__ import annotations

BYTES = 2


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time of a step on the chip: the larger of its operations over
    the bf16 peak and its bytes over HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
