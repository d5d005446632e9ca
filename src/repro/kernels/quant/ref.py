"""Numpy reference for the fused quantize/dequantize kernels.

The op ORDER and dtypes mirror ``kernel.py``/``ops.py`` exactly (all
f32, ``rint`` round-half-even, min/max reductions over rows, the scale
truncated to 16 significant bits), so tests assert EXACT equality for
every output against the Pallas path. The truncated scale makes
``scale * q`` exact, so this two-step ``lo + scale*q`` and a compiler's
FMA-contracted one round identically.
"""
from __future__ import annotations

import numpy as np


def quantize_ef_reference(x, res=None, *, levels: int = 255):
    """Per-channel affine quantization with error feedback.

    ``x``: any-rank array, channel = last axis. ``res`` is the carried
    error-feedback residual (same shape) or None. Returns
    ``(q u8, lo f32 [C], scale f32 [C], res' f32, ok bool, z f32)``
    where ``z = x + res`` is what the quantizer actually saw — the exact
    payload a caller should ship when ``ok`` is False (non-finite input).
    """
    x = np.asarray(x, np.float32)
    r = np.zeros_like(x) if res is None else np.asarray(res, np.float32)
    z = x + r
    C = z.shape[-1] if z.ndim else 1
    z2 = z.reshape(-1, C)
    lo = np.min(z2, axis=0).astype(np.float32)
    hi = np.max(z2, axis=0).astype(np.float32)
    with np.errstate(invalid="ignore", over="ignore", divide="ignore"):
        scale = (hi - lo) * np.float32(1.0 / levels)
        scale = (scale.view(np.uint32) & np.uint32(0xFFFFFF00)).view(
            np.float32)
        scale = np.where(np.isfinite(scale) & (scale > 0), scale,
                         np.float32(0)).astype(np.float32)
        safe = np.where(scale > 0, scale, np.float32(1)).astype(np.float32)
        q = np.clip(np.rint((z2 - lo[None, :]) / safe[None, :]), 0, levels)
        q = np.where(scale[None, :] > 0, q, np.float32(0))
        dq = lo[None, :] + scale[None, :] * q
        rout = (z2 - dq).astype(np.float32)
    qu8 = q.astype(np.uint8).reshape(z.shape)
    ok = bool(np.isfinite(z).all())
    return qu8, lo, scale, rout.reshape(z.shape), ok, z


def dequantize_reference(q, lo, scale):
    """``q``: u8 [..., C]; ``lo``/``scale``: f32 [C] -> f32 [..., C]."""
    q = np.asarray(q, np.uint8).astype(np.float32)
    lo = np.asarray(lo, np.float32)
    scale = np.asarray(scale, np.float32)
    return (lo + scale * q).astype(np.float32)
