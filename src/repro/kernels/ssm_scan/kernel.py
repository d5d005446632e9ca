"""Mamba2 SSD (chunked selective-state scan) as a Pallas TPU kernel.

Grid (B, H, num_chunks) with the chunk axis innermost: the inter-chunk state
h [P, N] lives in VMEM scratch and carries across sequential chunk steps —
the TPU-native replacement for the GPU kernel's warp-level scan. Intra-chunk
work is two MXU matmuls ([Q,Q] decay-weighted "attention" and the state
outer-product update); all tiles (Q x P, Q x N, P x N) are VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref, *,
            chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)         # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # [Q]
    A = a_ref[0].astype(jnp.float32)                  # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)                 # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                 # [Q, N]
    D = d_ref[0].astype(jnp.float32)

    la = dt * A                                       # [Q] log-decay
    cum = jnp.cumsum(la)

    # intra-chunk: M[t,s] = (C_t.B_s) * exp(L_t - L_s) * dt_s, s <= t
    seg = cum[:, None] - cum[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    decay = jnp.where(tri, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # [Q, Q]
    M = cb * decay * dt[None, :]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())))      # [Q, P]

    # carried state contribution: y_t += exp(L_t) * C_t . h^T
    h = h_ref[...]                                    # [P, N]
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())))              # [Q, P]

    y += D * x
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: h' = exp(L_Q) h + sum_t exp(L_Q - L_t) dt_t x_t B_t^T
    coeff = jnp.exp(cum[-1] - cum) * dt               # [Q]
    inj = jax.lax.dot_general(x, coeff[:, None] * Bm,
                              (((0,), (0,)), ((), ())))          # [P, N]
    h_ref[...] = jnp.exp(cum[-1]) * h + inj


def ssd_scan_kernel(xh, dt, A, Bm, Cm, D, *, chunk: int = 128,
                    interpret: bool):
    """xh: [B, S, H, P]; dt: [B, S, H]; A, D: [H]; Bm, Cm: [B, S, N].
    Returns y: [B, S, H, P]. S must be a multiple of `chunk`."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kern = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct(xh.shape, xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dt, A, Bm, Cm, D)
