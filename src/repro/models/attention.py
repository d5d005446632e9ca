"""GQA multi-head attention: train/prefill (full-seq) and decode (KV cache).

Apply functions operate on *local* (possibly tensor-sharded) head counts —
they read head counts from the param shapes. GQA query→kv grouping is
computed from global head counts + the shard's offset so it is correct both
sharded and replicated (see DESIGN.md §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import modules
from repro.models.tp import TP


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": modules.dense_init(ks[0], d, H * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": modules.dense_init(ks[1], d, K * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": modules.dense_init(ks[2], d, K * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": modules.dense_init(ks[3], H * hd, d, dtype=dtype),
    }


def init_cross_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    return init_attention(key, cfg.with_overrides(qkv_bias=False), dtype)


def _split_heads(x, head_dim):
    b, s, hd_total = x.shape
    return x.reshape(b, s, hd_total // head_dim, head_dim)


def _kv_select(cfg: ModelConfig, q_heads_local: int, kv_heads_local: int, tp: TP):
    """Local kv index for each local q head (GQA grouping across shards)."""
    H, K = cfg.num_heads, cfg.num_kv_heads
    idx = tp.index()
    q_off = idx * q_heads_local
    kv_sharded = kv_heads_local < K  # kv weights were sharded over tensor axis
    kv_off = idx * kv_heads_local if kv_sharded else 0
    g = (q_off + jnp.arange(q_heads_local)) * K // H
    return g - kv_off


def _sdpa(q, k, v, mask, dtype):
    """q:[B,Sq,Hl,hd] k,v:[B,Sk,Kl,hd] mask:[B?,Sq,Sk] or [Sq,Sk]; grouped."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(dtype), v.astype(dtype))
    return out


def full_mask(seq_q: int, seq_k: int, *, causal: bool, window: int = 0,
              q_start=0):
    """[Sq, Sk] boolean mask; q positions are ``q_start + arange(Sq)``."""
    qpos = q_start + jnp.arange(seq_q)[:, None]
    kpos = jnp.arange(seq_k)[None, :]
    m = jnp.ones((seq_q, seq_k), bool)
    if causal:
        m &= kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def attention(p, x, *, cfg: ModelConfig, positions, causal: bool = True,
              window: int = 0, tp: TP = TP.none(), dtype=jnp.bfloat16,
              kv_source=None):
    """Full-sequence attention (training / prefill).

    kv_source: if given ([B, Sk, d]), cross-attention over that sequence
    (no causal mask, no rope on kv positions beyond their own indices).
    Returns [B, Sq, d]-shaped *partial* output — caller psums over tp axis.
    """
    hd = cfg.head_dim
    q = _split_heads(modules.dense(p["wq"], x, dtype), hd)
    kv_in = x if kv_source is None else kv_source.astype(x.dtype)
    k = _split_heads(modules.dense(p["wk"], kv_in, dtype), hd)
    v = _split_heads(modules.dense(p["wv"], kv_in, dtype), hd)

    if kv_source is None:
        q = modules.apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = modules.apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    kv_prop = cfg.num_kv_heads >= cfg.tensor_parallel   # shards align
    if cfg.use_flash_attention and kv_source is None and kv_prop:
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3),
                              causal, window, 128, 128)
        out = out.transpose(0, 2, 1, 3).astype(dtype)
    else:
        sel = _kv_select(cfg, q.shape[2], k.shape[2], tp)
        k = jnp.take(k, sel, axis=2)
        v = jnp.take(v, sel, axis=2)
        if kv_source is None:
            mask = full_mask(q.shape[1], k.shape[1], causal=causal,
                             window=window)
        else:
            mask = None
        out = _sdpa(q, k, v, mask, dtype)
    out = out.reshape(out.shape[0], out.shape[1], -1)
    return modules.dense(p["wo"], out, dtype)


def chunk_attention(p, x, cache, *, cfg: ModelConfig, start,
                    tp: TP = TP.none(), dtype=jnp.bfloat16, window: int = 0):
    """Chunked-prefill attention: process `L` new tokens at global positions
    ``start + [0, L)``, appending their kv to the cache and attending
    causally over everything so far. Returns (partial_out, new_cache)."""
    hd = cfg.head_dim
    L = x.shape[1]
    S_total = cache["k"].shape[1]
    q = _split_heads(modules.dense(p["wq"], x, dtype), hd)
    k = _split_heads(modules.dense(p["wk"], x, dtype), hd)
    v = _split_heads(modules.dense(p["wv"], x, dtype), hd)
    positions = start + jnp.arange(L, dtype=jnp.int32)[None, :]
    q = modules.apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = modules.apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    new_k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                         (0, start, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                         (0, start, 0, 0))

    kv_prop = cfg.num_kv_heads >= cfg.tensor_parallel
    if cfg.use_flash_attention and kv_prop:
        from repro.kernels.flash_attention.kernel import flash_attention_kernel
        out = flash_attention_kernel(
            q.transpose(0, 2, 1, 3), new_k.transpose(0, 2, 1, 3),
            new_v.transpose(0, 2, 1, 3), jnp.reshape(start, (1,)),
            causal=True, window=window)
        out = out.transpose(0, 2, 1, 3).astype(dtype)
    else:
        sel = _kv_select(cfg, q.shape[2], new_k.shape[2], tp)
        ks = jnp.take(new_k, sel, axis=2)
        vs = jnp.take(new_v, sel, axis=2)
        mask = full_mask(L, S_total, causal=True, window=window,
                         q_start=start)
        out = _sdpa(q, ks, vs, mask, dtype)
    out = out.reshape(out.shape[0], L, -1)
    return modules.dense(p["wo"], out, dtype), {"k": new_k, "v": new_v}


def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                      kv_heads_local: int, dtype=jnp.bfloat16):
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv_heads_local, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv_heads_local, hd), dtype),
    }


def decode_attention(p, x, cache, *, cfg: ModelConfig, pos,
                     tp: TP = TP.none(), dtype=jnp.bfloat16):
    """One-token decode. x: [B, 1, d]; pos: scalar int32 OR per-sequence
    [B] int32 (continuous batching: every slot at its own position).

    The cache is a ring buffer of length W (= sliding window, or max seq for
    full attention); rope is applied pre-cache, so slots need no positions.
    Returns (partial_out [B,1,d], new_cache).
    """
    hd = cfg.head_dim
    B = x.shape[0]
    W = cache["k"].shape[1]
    q = _split_heads(modules.dense(p["wq"], x, dtype), hd)
    k = _split_heads(modules.dense(p["wk"], x, dtype), hd)
    v = _split_heads(modules.dense(p["wv"], x, dtype), hd)

    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (B,))                   # [B]
    q = modules.apply_rope(q, pos_b[:, None], cfg.rope_theta,
                           cfg.rope_fraction)
    k = modules.apply_rope(k, pos_b[:, None], cfg.rope_theta,
                           cfg.rope_fraction)

    slot = jnp.mod(pos_b, W)                              # [B]
    rows = jnp.arange(B)
    new_k = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
    new_v = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))

    sel = _kv_select(cfg, q.shape[2], new_k.shape[2], tp)
    ks = jnp.take(new_k, sel, axis=2)
    vs = jnp.take(new_v, sel, axis=2)

    valid = ((jnp.arange(W)[None, :] <= pos_b[:, None])
             | (pos_b[:, None] >= W))                     # [B, W] ring
    mask = valid[:, None, None, :]                        # [B,1(H),1(Sq),W]
    out = _sdpa(q, ks, vs, mask, dtype)
    out = out.reshape(out.shape[0], 1, -1)
    return modules.dense(p["wo"], out, dtype), {"k": new_k, "v": new_v}
