"""The chip's least time for a piece of work.

What a piece of work needs (its operations and bytes, from shapes alone)
is counted by the model's family (``families/<name>.py``); this turns it
into the time the chip's peaks allow.
"""

from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
