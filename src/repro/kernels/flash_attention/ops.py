"""Public jit'd wrapper: padding, GQA plumbing, custom VJP.

Forward runs the Pallas kernel; backward recomputes with the jnp reference
(flash backward kernel is future work — the recompute matches the remat'd
training configuration anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_reference


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, block_q=128, block_k=128,
                    interpret=None):
    """q: [B, H, Sq, dh]; k, v: [B, Hkv, Skv, dh]. Returns [B, H, Sq, dh].
    ``interpret=None`` follows ``kernels.backend.default_interpret``."""
    if interpret is None:
        interpret = default_interpret()
    qp, Sq = _pad_to(q, 2, block_q)
    kp, Skv = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    out = flash_attention_kernel(qp, kp, vp, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out[:, :, :Sq, :]


def _fwd(q, k, v, causal, window, block_q, block_k, interpret):
    return flash_attention(q, k, v, causal, window, block_q, block_k,
                           interpret), (q, k, v)


def _bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=causal,
                                               window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
