"""Scoped-VMEM accounting shared by the tile pickers.

Mosaic places every block of a ``pallas_call`` in VMEM twice (the pipeline
double-buffers inputs *and* outputs), pads each block to the (sublane, 128)
hardware tiling — a (bn, 1) f32 row vector costs bn * 128 * 4 bytes — and
keeps large intermediate values (logit/score tiles, iotas, masks, casts) in
VMEM too. A picker that counts only the nominal block bytes overruns the
scoped limit at real widths, which only a compile for the chip shows.

Each kernel asks for ``VMEM_LIMIT_BYTES`` of scoped VMEM (v5e/v6e hold
128 MiB, the compiler's default scope is 16 MiB on v5e) and its picker fills
at most ``VMEM_BUDGET_BYTES`` of it, counted with :func:`tile_bytes`; the
rest is headroom for Mosaic's own scratch and for estimate error. The
fused xent backward alone asks for ``WIDE_VMEM_LIMIT_BYTES`` and fills at
most ``WIDE_VMEM_BUDGET_BYTES``.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 32 << 20
VMEM_BUDGET_BYTES = 24 << 20
# the scoped-VMEM request every tile-picked kernel compiles with
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
# the fused xent backward holds a resident f32 dW tile, its dW output
# block and the w block at once; it asks for most of the chip's VMEM
WIDE_VMEM_LIMIT_BYTES = 96 << 20
WIDE_VMEM_BUDGET_BYTES = 80 << 20
WIDE_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=WIDE_VMEM_LIMIT_BYTES)


def tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one (rows, cols) block padded to the hardware tiling.

    The sublane tile is 8 rows of 32-bit words, so narrower dtypes pack
    more rows per tile (16 for bf16, 32 for int8); lanes pad to 128.
    """
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize
