"""Traceable wrappers around the fused quantize/dequantize kernels.

``quantize_ef`` / ``dequantize`` are jit-safe: ``StageExecutor`` calls
them INSIDE its single compiled step so the boundary tensor leaves the
device already quantized (u8 codes + per-channel affine params + the
carried error-feedback residual), and the codec ships it zero-copy.

Like ``fused_sgd``, ``interpret=None`` follows ``kernels.backend``:
interpret-mode Pallas on CPU, native lowering on TPU/GPU. Arbitrary-rank
inputs are viewed as ``[rows, channels]`` with channel = last axis; the
per-channel range is reduced over all rows here, and the kernels then
run over row x channel tiles. Both axes are zero-padded to a tile
multiple (padded rows and channels are sliced away).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import default_interpret
from repro.kernels.quant.kernel import (dequantize_kernel, quantize_kernel,
                                        tile_shape)

# f32 bit mask keeping the top 16 significant bits of a scale
SCALE_MASK = 0xFFFFFF00


def exact_product_scale(lo, hi, levels: int):
    """Per-channel step ``(hi - lo) / levels`` truncated to 16 significant
    bits, so ``scale * q`` is exact in f32 for every code ``q <= 255``;
    0 for a degenerate (constant or non-finite) channel."""
    scale = (hi - lo) * (1.0 / levels)
    bits = jax.lax.bitcast_convert_type(scale, jnp.uint32)
    scale = jax.lax.bitcast_convert_type(bits & jnp.uint32(SCALE_MASK),
                                         jnp.float32)
    return jnp.where(jnp.isfinite(scale) & (scale > 0), scale, 0.0)


def _pad2(a, rows: int, cols: int):
    """Zero-pad a 2D array up to ``[rows, cols]``."""
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr or pc:
        a = jnp.pad(a, ((0, pr), (0, pc)))
    return a


def _padded(rows: int, C: int, block: int) -> tuple[int, int]:
    rb, cb = tile_shape(rows, C, block)
    return -(-rows // rb) * rb, -(-C // cb) * cb


def quantize_ef(x, res=None, *, levels: int = 255, block: int = 128,
                interpret: bool | None = None):
    """Fused per-channel affine quantize with error feedback.

    ``x``: f32 [..., C] (channel = last axis); ``res``: carried residual
    of the same shape, or None (treated as zeros — first send).

    Returns ``(q, lo, scale, res', ok, z)``:
      * ``q``     u8 [..., C] codes in ``[0, levels]``,
      * ``lo``    f32 [C] per-channel offset,
      * ``scale`` f32 [C] per-channel step (0 = degenerate channel,
        decoded exactly as ``lo``),
      * ``res'``  f32 [..., C] next residual ``z - dequant(q)``,
      * ``ok``    scalar bool — False when ``z`` has non-finite values;
        callers must then ship ``z`` exactly (and reset the residual),
      * ``z``     f32 [..., C] ``x + res``, the exact-fallback payload.
    """
    if interpret is None:
        interpret = default_interpret()
    if not 1 <= levels <= 255:
        raise ValueError(f"levels must be in [1, 255] for u8 codes, got "
                         f"{levels}")
    x = jnp.asarray(x, jnp.float32)
    if x.ndim < 1 or x.size == 0:
        raise ValueError(f"quantize_ef needs a non-empty array, got shape "
                         f"{x.shape}")
    shape = x.shape
    C = shape[-1]
    z = x if res is None else x + jnp.asarray(res, jnp.float32)
    ok = jnp.isfinite(z).all()
    z2 = z.reshape(-1, C)
    lo = jnp.min(z2, axis=0)
    scale = exact_product_scale(lo, jnp.max(z2, axis=0), levels)
    R, Cp = _padded(z2.shape[0], C, block)
    q, rout = quantize_kernel(_pad2(z2, R, Cp), _pad2(lo[None], 1, Cp),
                              _pad2(scale[None], 1, Cp), levels=levels,
                              block=block, interpret=interpret)
    rows = z2.shape[0]
    return (q[:rows, :C].reshape(shape), lo, scale,
            rout[:rows, :C].reshape(shape), ok, z)


def dequantize(q, lo, scale, *, block: int = 128,
               interpret: bool | None = None):
    """Fused dequantize: u8 codes + per-channel ``(lo, scale)`` -> f32.

    ``q``: u8 [..., C]; ``lo``/``scale``: f32 [C]. Inverse of
    ``quantize_ef`` up to scale/2 per element (exact for degenerate
    channels where ``scale == 0``).
    """
    if interpret is None:
        interpret = default_interpret()
    q = jnp.asarray(q)
    shape = q.shape
    C = shape[-1]
    q2 = q.reshape(-1, C)
    R, Cp = _padded(q2.shape[0], C, block)
    x = dequantize_kernel(
        _pad2(q2, R, Cp),
        _pad2(jnp.asarray(lo, jnp.float32).reshape(1, C), 1, Cp),
        _pad2(jnp.asarray(scale, jnp.float32).reshape(1, C), 1, Cp),
        block=block, interpret=interpret)
    return x[:q2.shape[0], :C].reshape(shape)
