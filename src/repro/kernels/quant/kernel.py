"""Fused per-channel affine int8 quantize / dequantize as Pallas kernels.

The quantizer takes each channel's ``(lo, scale)`` affine range (reduced
over all rows by ``ops.quantize_ef``) and, in one VMEM pass per
``[rows, channels]`` tile, emits the u8 codes AND the error-feedback
residual ``z - dequant(q)`` — the outputs the ``StageExecutor`` boundary
needs to ship a device-quantized activation without a single host-side
numpy pass (vs. the ~15 GIL-bound passes of the codec's tag-12 encoder).
Tiles cover a bounded block of rows, so a boundary tensor of any batch
fits the chip's scoped VMEM.

Conventions (shared with ``ref.py``, the numpy oracle, and the wire
format of ``runtime/qtensor.DeviceQuantized``). Every output (``q``,
``lo``, ``scale``, the residual and the dequantized value) is
BIT-IDENTICAL to the oracle on every backend: ``scale`` keeps at most
16 significant bits (``exact_product_scale``), so ``scale * q`` with
``q <= 255`` is exact in f32 and ``lo + scale*q`` rounds once whether or
not a compiler contracts it into an FMA. Sender residual and receiver
dequant therefore agree exactly even when the two run in separately
compiled programs.

  * channel = LAST axis; inputs arrive as 2D ``[rows, channels]`` tiles,
  * ``scale = (hi - lo) / levels`` truncated to 16 significant bits,
    with ``q in [0, levels]`` (``levels = 255`` on the wire; tests use
    coarser grids),
  * a degenerate channel (``hi == lo``, or a non-finite range) stores
    ``scale = 0`` and ``q = 0`` — it decodes to exactly ``lo``, so
    constant channels (zeros included) round-trip EXACTLY,
  * non-finite inputs are the CALLER's fallback case (``ops.quantize_ef``
    returns an ``ok`` flag); the kernel itself just propagates them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Rows per tile. Multiple of 32 (the u8 sublane tiling on TPU); at 128
# lanes a tile's f32 input + u8 codes + f32 residual, double-buffered,
# stay under 2.5 MiB of VMEM.
ROW_BLOCK = 1024


def _quant_kernel(z_ref, lo_ref, scale_ref, q_ref, res_ref, *, levels):
    z = z_ref[...].astype(jnp.float32)               # [rb, cb]
    lo = lo_ref[...]                                 # [1, cb]
    scale = scale_ref[...]
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint((z - lo) / safe), 0, levels)
    q = jnp.where(scale > 0, q, 0.0)
    # scale*q is exact (see exact_product_scale): one rounding, FMA or not
    res_ref[...] = z - (lo + scale * q)
    # Mosaic has no direct f32 <-> u8 cast; q is an exact small integer
    q_ref[...] = q.astype(jnp.int32).astype(jnp.uint8)


def tile_shape(rows: int, C: int, block: int) -> tuple[int, int]:
    """(row block, column block) for a ``[rows, C]`` operand: a full-dim
    block where the dimension is small, else the tile size (the caller
    pads to a multiple of it)."""
    return min(rows, ROW_BLOCK), min(block, C)


def _grid_specs(rows, C, block):
    rb, cb = tile_shape(rows, C, block)
    assert rows % rb == 0 and C % cb == 0, (rows, C, rb, cb)
    tile = pl.BlockSpec((rb, cb), lambda i, j: (i, j))
    chan = pl.BlockSpec((1, cb), lambda i, j: (0, j))
    return (rows // rb, C // cb), tile, chan


def quantize_kernel(z, lo, scale, *, levels: int = 255, block: int = 128,
                    interpret: bool):
    """``z``: f32 [rows, C]; ``lo``/``scale``: f32 [1, C] (from
    ``exact_product_scale``). ``rows`` and ``C`` are multiples of the
    tiles ``tile_shape`` picks (pad upstream). Returns ``(q u8 [rows, C],
    residual f32 [rows, C])``."""
    rows, C = z.shape
    grid, tile, chan = _grid_specs(rows, C, block)
    kern = functools.partial(_quant_kernel, levels=levels)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile, chan, chan],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, C), jnp.uint8),
                   jax.ShapeDtypeStruct((rows, C), jnp.float32)],
        interpret=interpret,
    )(z, lo, scale)


def _dequant_kernel(q_ref, lo_ref, scale_ref, x_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = lo_ref[...] + scale_ref[...] * q


def dequantize_kernel(q, lo, scale, *, block: int = 128, interpret: bool):
    """``q``: u8 [rows, C]; ``lo``/``scale``: f32 [1, C] (same tiling
    contract as ``quantize_kernel``). Returns f32 [rows, C]."""
    rows, C = q.shape
    grid, tile, chan = _grid_specs(rows, C, block)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[tile, chan, chan],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, C), jnp.float32),
        interpret=interpret,
    )(q, lo, scale)
