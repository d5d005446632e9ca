"""Vocab-parallel embedding / head / cross-entropy.

The embedding table and LM head are sharded over the COMBINED model axis
(stage x tensor = 16-way) on the vocab dimension. Naive GSPMD would
all-gather the table (2 GB for llama3); these shard_map kernels do the
Megatron-style masked-local-gather + psum instead, so the only cross-device
traffic is an activation-sized psum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.pipeline.sharding import AXIS_STAGE, AXIS_TENSOR, data_axes

VOCAB_AXES = (AXIS_STAGE, AXIS_TENSOR)


def embed_tokens(mesh, table, tokens, dtype=jnp.bfloat16, data_sharded=True):
    """table: [V, d] sharded P((stage,tensor), None); tokens: [B, S] sharded
    over data. Returns x: [B, S, d] sharded over data, replicated over model."""
    dspec = data_axes(mesh) if data_sharded else None

    def body(tbl, tok):
        V_l = tbl.shape[0]
        off = jax.lax.axis_index(VOCAB_AXES) * V_l
        local = (tok >= off) & (tok < off + V_l)
        idx = jnp.clip(tok - off, 0, V_l - 1)
        x = tbl[idx] * local[..., None].astype(tbl.dtype)
        return jax.lax.psum(x.astype(jnp.float32), VOCAB_AXES).astype(dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(VOCAB_AXES, None), P(dspec, None)),
        out_specs=P(dspec, None, None))(table, tokens)


def lm_head_loss(mesh, head_w, y, labels, mask, vocab_size: int = 0,
                 z_weight: float = 0.0):
    """Fused vocab-parallel head matmul + cross-entropy.

    head_w: [d, V_padded] sharded P(None, (stage,tensor)); y: [B, S, d] over
    data; labels/mask: [B, S] over data. Pad columns beyond ``vocab_size``
    are masked to -inf. Returns scalar mean loss (replicated)."""
    dspec = data_axes(mesh)
    V_real = vocab_size or head_w.shape[-1]

    def body(w, yb, lb, mk):
        logits = (yb.astype(jnp.float32) @ w.astype(jnp.float32))
        V_l = logits.shape[-1]
        off = jax.lax.axis_index(VOCAB_AXES) * V_l
        col = off + jnp.arange(V_l)
        logits = jnp.where(col[None, None, :] < V_real, logits, -1e30)
        # stop_gradient BEFORE pmax (no pmax JVP rule; the stabilizer
        # cancels exactly in d(logsumexp) anyway)
        lmax = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(logits, axis=-1)), VOCAB_AXES)
        z = jax.lax.psum(jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1),
                         VOCAB_AXES)
        logz = jnp.log(z) + lmax
        in_rng = (lb >= off) & (lb < off + V_l)
        idx = jnp.clip(lb - off, 0, V_l - 1)
        ll_loc = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_rng, ll_loc, 0.0), VOCAB_AXES)
        nll = (logz - ll) + z_weight * logz * logz
        num = jax.lax.psum(jnp.sum(nll * mk), dspec)
        den = jax.lax.psum(jnp.sum(mk), dspec)
        return num / jnp.maximum(den, 1.0)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, VOCAB_AXES), P(dspec, None, None),
                  P(dspec, None), P(dspec, None)),
        out_specs=P())(head_w, y, labels, mask)


def lm_head_logits(mesh, head_w, y, data_sharded=True, vocab_size: int = 0):
    """Decode-time head: logits sharded over the model axis on vocab
    (pad columns masked to -inf so sampling never picks them)."""
    dspec = data_axes(mesh) if data_sharded else None
    V_real = vocab_size or head_w.shape[-1]

    def body(w, yb):
        logits = yb.astype(jnp.float32) @ w.astype(jnp.float32)
        V_l = logits.shape[-1]
        col = jax.lax.axis_index(VOCAB_AXES) * V_l + jnp.arange(V_l)
        return jnp.where(col[None, None, :] < V_real, logits, -1e30)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, VOCAB_AXES), P(dspec, None, None)),
        out_specs=P(dspec, None, VOCAB_AXES))(head_w, y)
