"""Shape arithmetic shared by the kernel work counts."""
from __future__ import annotations

BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
         "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
         "s64": 8, "u64": 8, "f64": 8}


def nbytes(shape) -> int:
    """Bytes of one (dtype, dims) array."""
    dt, dims = shape
    n = BYTES[dt]
    for x in dims:
        n *= x
    return n
