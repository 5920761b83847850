"""The roofline of a program call from its operations and bytes.

The operations and bytes themselves depend on the architecture: each
architecture module counts them (``prefill_flops``, ``prefill_bytes`` and
``model_flops`` of ``architectures/<name>.py``) from a call's logical
shapes, with the byte sizes below.
"""
from __future__ import annotations

BF16 = 2
LOGIT_BYTES = 2       # the head's bf16 output


def roofline_seconds(flops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
