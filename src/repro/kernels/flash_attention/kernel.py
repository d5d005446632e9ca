"""Blocked flash attention (online softmax) as a Pallas TPU kernel.

Grid (B, H, num_q_blocks, num_kv_blocks), kv innermost so the VMEM scratch
(acc, running max m, running sum l) carries across kv blocks. Tiles are
MXU-aligned (block_q x head_dim and block_k x head_dim live in VMEM). GQA is
handled in the k/v index_maps (query head -> kv head); causal and
sliding-window masking via global position iota.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

NEG_INF = -1e30


def _kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, seq_q: int, seq_kv: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    qoff = qoff_ref[0]          # global position of q row 0 (chunked prefill)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = qoff + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)

    # skip kv blocks fully beyond the causal frontier / outside the window
    needed = ik >= 0
    if causal:
        needed &= (ik * block_k) <= (qoff + iq * block_q + block_q - 1)
    if window:
        needed &= (ik * block_k + block_k - 1) > (qoff + iq * block_q - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

        mask = kpos < seq_kv
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, q_offset=None, *, causal: bool = True,
                           window: int = 0, scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: [B, H, Sq, dh]; k, v: [B, Hkv, Skv, dh]. Returns [B, H, Sq, dh].
    q_offset: int32 scalar array — global position of q row 0 (chunked
    prefill against a longer kv cache). ``interpret=None`` follows
    ``kernels.backend.default_interpret``."""
    import jax.numpy as _jnp
    if interpret is None:
        interpret = default_interpret()
    B, H, Sq, dh = q.shape
    _, Hkv, Skv, _ = k.shape
    if q_offset is None:
        q_offset = _jnp.zeros((1,), _jnp.int32)
    else:
        q_offset = _jnp.asarray(q_offset, _jnp.int32).reshape(1)
    scale = scale if scale is not None else dh ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Skv, block_k)

    def q_map(b, h, iq, ik):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik):
        return (b, h * Hkv // H, ik, 0)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, block_q=block_q,
        block_k=block_k, seq_q=Sq, seq_kv=Skv, num_kv_blocks=nk)

    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, dh), q_map),
            pl.BlockSpec((1, 1, block_k, dh), kv_map),
            pl.BlockSpec((1, 1, block_k, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q_offset, q, k, v)
