"""Fused SGD + momentum + weight decay (the paper's optimizer) as a Pallas
kernel: one VMEM pass over flat parameter tiles, emitting updated params and
momentum together (vs. 3 separate HBM round-trips unfused).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p_ref, g_ref, m_ref, po_ref, mo_ref, *, lr, momentum,
            weight_decay):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) + weight_decay * p
    m = momentum * m_ref[...].astype(jnp.float32) + g
    po_ref[...] = (p - lr * m).astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)


def fused_sgd_kernel(params, grads, mom, *, lr: float, momentum: float = 0.9,
                     weight_decay: float = 4e-5, block: int = 65536,
                     interpret: bool):
    """params/grads/mom: flat [N] arrays (pad to a block multiple upstream)."""
    (N,) = params.shape
    assert N % block == 0 or N < block, (N, block)
    blk = min(block, N)
    kern = functools.partial(_kernel, lr=lr, momentum=momentum,
                             weight_decay=weight_decay)
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(N, blk),),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))] * 3,
        out_specs=[pl.BlockSpec((blk,), lambda i: (i,))] * 2,
        out_shape=[jax.ShapeDtypeStruct(params.shape, params.dtype),
                   jax.ShapeDtypeStruct(mom.shape, mom.dtype)],
        interpret=interpret,
    )(params, grads, mom)
