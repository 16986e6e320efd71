"""What every architecture's work counts share: bytes of each dtype,
and the least time the chips need for a count of operations and bytes.
The counts themselves are the architecture's (``archs/<name>.py``,
``Shapes``)."""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_seconds(flops: float, nbytes: float, chips: int,
                  peak: dict) -> tuple[float, str]:
    """The least time ``chips`` chips need for the work, and which of
    the two bounds sets it."""
    t_flops = flops / (chips * peak["bf16_flops_per_s"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
