"""The 1F1B pipeline-parallel execution engine (shard_map + ppermute).

Forward: microbatches enter stage 0, activations circulate stage->stage+1
via ppermute, a lax.scan runs M + S - 1 ticks. Backward is jax.grad through
the scan (reverse scan + transposed ppermute — GPipe-with-remat compute
schedule; the paper's ASYNC semantics live in the cross-step weight stash,
see DESIGN.md §2). Tensor/expert parallelism runs inside each stage over the
"tensor" axis.

Decode: same circulation with one token per microbatch and per-stage KV/SSM
caches carried through the scan.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import model as model_lib
from repro.models import modules
from repro.models.blocks import BLOCKS, BlockCtx
from repro.models.tp import TP
from repro.pipeline import losses as loss_lib
from repro.pipeline.sharding import (AXIS_STAGE, AXIS_TENSOR, block_specs,
                                     cache_specs, data_axes)


def _unstack(tree):
    """Strip the local (size-1) stage axis."""
    return jax.tree.map(lambda a: a[0], tree)


def _ring(S):
    return [(i, (i + 1) % S) for i in range(S)]


# ============================ forward (train/prefill) =====================

def pipeline_forward(mesh, cfg: ModelConfig, blocks, x, pad_mask, *,
                     layout=None, num_microbatches: int = 0, causal=True,
                     window: int = 0, kv_source=None, remat=True,
                     data_sharded=True, dtype=None, unroll=False):
    """x: [B, seq, d] (sharded over data axes). Returns (y [B, seq, d] from
    the last stage, aux scalar)."""
    layout = tuple(layout or cfg.slot_layout)
    S = cfg.pipeline_stages
    dtype = dtype or modules.dtype_of(cfg.dtype)
    dspec = data_axes(mesh)
    Bspec = dspec if data_sharded else None
    tp = TP(AXIS_TENSOR, cfg.tensor_parallel)

    def body(blocks_l, x_l, pm_l, kv_l):
        s_idx = jax.lax.axis_index(AXIS_STAGE)
        B_l, seq, d = x_l.shape
        M = min(num_microbatches or B_l, B_l)
        while B_l % M:
            M -= 1
        mb = B_l // M
        x_mb = x_l.reshape(M, mb, seq, d).astype(dtype)
        kv_mb = (None if kv_l.ndim == 0 else
                 kv_l.reshape(M, mb, *kv_l.shape[1:]).astype(dtype))
        pad_row = pm_l[0]
        slots = [_unstack(p) for p in blocks_l]
        positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                     (mb, seq))

        def stage_fn(xin, kv_in):
            aux = jnp.float32(0.0)
            xx = xin
            for j, t in enumerate(layout):
                ctx = BlockCtx(cfg=cfg, positions=positions, tp=tp,
                               dtype=dtype, causal=causal, window=window,
                               kv_source=kv_in, active=pad_row[j])
                xx, a = BLOCKS[t].apply(slots[j], xx, ctx)
                aux = aux + a
            return xx, aux

        if remat:
            stage_fn = jax.checkpoint(stage_fn)

        y_buf0 = jnp.zeros((M, mb, seq, d), dtype)

        def tick_fn(carry, t):
            x_cur, y_buf, aux = carry
            idx = t - s_idx
            valid = (idx >= 0) & (idx < M)
            idxc = jnp.clip(idx, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(x_mb, idxc, 0, keepdims=False)
            xin = jnp.where(s_idx == 0, x0, x_cur)
            kv_in = (None if kv_mb is None else
                     jax.lax.dynamic_index_in_dim(kv_mb, idxc, 0,
                                                  keepdims=False))
            y, a = stage_fn(xin, kv_in)
            aux = aux + jnp.where(valid, a, 0.0)
            upd = jax.lax.dynamic_update_index_in_dim(
                y_buf, y.astype(dtype), idxc, 0)
            y_buf = jnp.where(valid, upd, y_buf)
            y_next = jax.lax.ppermute(y.astype(dtype), AXIS_STAGE, _ring(S))
            return (y_next, y_buf, aux), None

        carry0 = (jnp.zeros((mb, seq, d), dtype), y_buf0, jnp.float32(0.0))
        (_, y_buf, aux), _ = jax.lax.scan(tick_fn, carry0,
                                          jnp.arange(M + S - 1),
                                          unroll=bool(unroll))
        y_out = y_buf.reshape(B_l, seq, d)
        return y_out[None], (aux / M)[None, None]   # mean over microbatches

    blocks_specs = [block_specs(t, cfg) for t in layout]
    in_specs = (blocks_specs, P(Bspec, None, None), P(AXIS_STAGE, None),
                P(Bspec, None, None) if kv_source is not None else P())
    out_specs = (P(AXIS_STAGE, Bspec, None, None), P(AXIS_STAGE, dspec))

    kv_arg = kv_source if kv_source is not None else jnp.zeros((), jnp.float32)
    y_all, aux_all = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(blocks, x, pad_mask, kv_arg)
    y = y_all[S - 1]
    aux = jnp.sum(jnp.mean(aux_all, axis=1))
    return y, aux


# ================================ decode ==================================

def pipeline_decode(mesh, cfg: ModelConfig, blocks, x, caches, pos,
                    pad_mask, *, layout=None, num_microbatches: int = 0,
                    window: int = 0, kv_source=None, data_sharded=True,
                    dtype=None):
    """One-token decode through the pipeline.

    x: [B, 1, d]; caches: list (per slot) of stage-stacked trees [S, B, ...];
    pos: scalar int32 (current position, same for the whole batch).
    Returns (y [B, 1, d], new caches).
    """
    layout = tuple(layout or cfg.slot_layout)
    S = cfg.pipeline_stages
    dtype = dtype or modules.dtype_of(cfg.dtype)
    dspec = data_axes(mesh)
    Bspec = dspec if data_sharded else None
    tp = TP(AXIS_TENSOR, cfg.tensor_parallel)

    def body(blocks_l, x_l, pm_l, caches_l, pos_s, kv_l):
        s_idx = jax.lax.axis_index(AXIS_STAGE)
        B_l = x_l.shape[0]
        d = x_l.shape[-1]
        M = min(num_microbatches or min(B_l, S), B_l)
        while B_l % M:
            M -= 1
        mb = B_l // M
        x_mb = x_l.reshape(M, mb, 1, d).astype(dtype)
        kv_mb = (None if kv_l.ndim == 0 else
                 kv_l.reshape(M, mb, *kv_l.shape[1:]).astype(dtype))
        slots = [_unstack(p) for p in blocks_l]
        caches0 = [_unstack(c) for c in caches_l]
        pad_row = pm_l[0]

        def slice_mb(tree, idxc):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, idxc * mb, mb, 0),
                tree)

        def put_mb(tree, upd, idxc, valid):
            def put(a, u):
                new = jax.lax.dynamic_update_slice_in_dim(
                    a, u.astype(a.dtype), idxc * mb, 0)
                return jnp.where(valid, new, a)
            return jax.tree.map(put, tree, upd)

        def stage_fn(xin, cin, kv_in):
            xx = xin
            cout = []
            for j, t in enumerate(layout):
                ctx = BlockCtx(cfg=cfg, pos=pos_s, tp=tp, dtype=dtype,
                               window=window, kv_source=kv_in,
                               active=pad_row[j])
                xx, c = BLOCKS[t].step(slots[j], xx, cin[j], ctx)
                cout.append(c)
            return xx, cout

        y_buf0 = jnp.zeros((M, mb, 1, d), dtype)

        def tick_fn(carry, t):
            x_cur, caches_c, y_buf = carry
            idx = t - s_idx
            valid = (idx >= 0) & (idx < M)
            idxc = jnp.clip(idx, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(x_mb, idxc, 0, keepdims=False)
            xin = jnp.where(s_idx == 0, x0, x_cur)
            kv_in = (None if kv_mb is None else
                     jax.lax.dynamic_index_in_dim(kv_mb, idxc, 0,
                                                  keepdims=False))
            cin = [slice_mb(c, idxc) for c in caches_c]
            y, cout = stage_fn(xin, cin, kv_in)
            caches_c = [put_mb(c, u, idxc, valid)
                        for c, u in zip(caches_c, cout)]
            upd = jax.lax.dynamic_update_index_in_dim(
                y_buf, y.astype(dtype), idxc, 0)
            y_buf = jnp.where(valid, upd, y_buf)
            y_next = jax.lax.ppermute(y.astype(dtype), AXIS_STAGE, _ring(S))
            return (y_next, caches_c, y_buf), None

        carry0 = (jnp.zeros((mb, 1, d), dtype), caches0, y_buf0)
        (_, caches_f, y_buf), _ = jax.lax.scan(tick_fn, carry0,
                                               jnp.arange(M + S - 1))
        y_out = y_buf.reshape(B_l, 1, d)
        caches_out = [jax.tree.map(lambda a: a[None], c) for c in caches_f]
        return y_out[None], caches_out

    blocks_specs = [block_specs(t, cfg) for t in layout]
    caches_sp = [cache_specs(t, cfg, Bspec) for t in layout]
    in_specs = (blocks_specs, P(Bspec, None, None), P(AXIS_STAGE, None),
                caches_sp, P(),
                P(Bspec, None, None) if kv_source is not None else P())
    out_specs = (P(AXIS_STAGE, Bspec, None, None), caches_sp)

    kv_arg = kv_source if kv_source is not None else jnp.zeros((), jnp.float32)
    y_all, new_caches = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(blocks, x, pad_mask, caches,
                         jnp.asarray(pos, jnp.int32), kv_arg)
    return y_all[S - 1], new_caches


# ======================= chunked-sequence prefill =========================

def pipeline_prefill_chunked(mesh, cfg: ModelConfig, blocks, x, caches,
                             pad_mask, *, seq_chunks: int, layout=None,
                             window: int = 0, data_sharded=True, dtype=None):
    """Sequence-dimension pipelining for prefill (beyond-paper, §Perf):
    microbatch i = tokens [i*L, (i+1)*L) of EVERY local sequence; per-stage
    KV/SSM caches carry the context between chunks, so the pipeline bubble
    shrinks from (B_loc+S-1)/B_loc to (C+S-1)/C with C = seq_chunks.

    x: [B, S_total, d]; caches: stage-stacked, cache_len == S_total.
    Returns (y_last_chunk [B, L, d], new caches).
    """
    layout = tuple(layout or cfg.slot_layout)
    S = cfg.pipeline_stages
    dtype = dtype or modules.dtype_of(cfg.dtype)
    dspec = data_axes(mesh)
    Bspec = dspec if data_sharded else None
    tp = TP(AXIS_TENSOR, cfg.tensor_parallel)

    def body(blocks_l, x_l, pm_l, caches_l):
        s_idx = jax.lax.axis_index(AXIS_STAGE)
        B_l, S_total, d = x_l.shape
        M = seq_chunks
        L = S_total // M
        x_mb = x_l.reshape(B_l, M, L, d).transpose(1, 0, 2, 3).astype(dtype)
        slots = [_unstack(p) for p in blocks_l]
        caches0 = [_unstack(c) for c in caches_l]
        pad_row = pm_l[0]

        def stage_fn(xin, cin, start):
            xx = xin
            cout = []
            for j, t in enumerate(layout):
                ctx = BlockCtx(cfg=cfg, pos=start, tp=tp, dtype=dtype,
                               window=window, active=pad_row[j])
                xx, c = BLOCKS[t].prefill_chunk(slots[j], xx, cin[j], ctx)
                cout.append(c)
            return xx, cout

        y0 = jnp.zeros((B_l, S_total // M, d), dtype)

        def tick_fn(carry, t):
            x_cur, caches_c, y_last = carry
            idx = t - s_idx
            valid = (idx >= 0) & (idx < M)
            idxc = jnp.clip(idx, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(x_mb, idxc, 0, keepdims=False)
            xin = jnp.where(s_idx == 0, x0, x_cur)
            start = idxc * L
            y, cout = stage_fn(xin, caches_c, start)
            caches_c = [jax.tree.map(
                lambda new, old: jnp.where(valid, new, old), c, o)
                for c, o in zip(cout, caches_c)]
            y_last = jnp.where(valid & (idxc == M - 1), y.astype(dtype),
                               y_last)
            y_next = jax.lax.ppermute(y.astype(dtype), AXIS_STAGE, _ring(S))
            return (y_next, caches_c, y_last), None

        carry0 = (jnp.zeros((B_l, L, d), dtype), caches0, y0)
        (_, caches_f, y_last), _ = jax.lax.scan(tick_fn, carry0,
                                                jnp.arange(M + S - 1))
        caches_out = [jax.tree.map(lambda a: a[None], c) for c in caches_f]
        return y_last[None], caches_out

    blocks_specs = [block_specs(t, cfg) for t in layout]
    caches_sp = [cache_specs(t, cfg, Bspec) for t in layout]
    in_specs = (blocks_specs, P(Bspec, None, None), P(AXIS_STAGE, None),
                caches_sp)
    out_specs = (P(AXIS_STAGE, Bspec, None, None), caches_sp)
    y_all, new_caches = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(blocks, x, pad_mask, caches)
    return y_all[S - 1], new_caches


CHUNKABLE = {"dense", "moe", "mamba", "hybrid", "mlstm", "slstm"}


# ============================ train / serve steps =========================

def _stage_window_blend(cfg, new_blocks, stash_blocks):
    """Paper weight aggregation mapped onto the depth-2 stash: stages with
    n - i >= 2 live versions average (new, stash); the last stage keeps new.
    Leaves carry a leading stage axis."""
    S = cfg.pipeline_stages

    def blend(n, s):
        alpha = jnp.where(jnp.arange(S) < S - 1, 0.5, 1.0)
        shape = (S,) + (1,) * (n.ndim - 1)
        a = alpha.reshape(shape).astype(jnp.float32)
        return (a * n.astype(jnp.float32)
                + (1 - a) * s.astype(jnp.float32)).astype(n.dtype)

    return jax.tree.map(blend, new_blocks, stash_blocks)


def make_loss_fn(mesh, cfg: ModelConfig, *, num_microbatches=0, remat=True,
                 window: int = 0, unroll=False):
    def loss_fn(params, batch):
        dtype = modules.dtype_of(cfg.dtype)
        if cfg.family == "audio":
            xe, _ = model_lib.embed_frames(cfg, batch["frames"], dtype)
            pm_e = model_lib.pad_mask(cfg)
            xe, _ = pipeline_forward(mesh, cfg, params["blocks"], xe, pm_e,
                                     layout=cfg.slot_layout, causal=False,
                                     num_microbatches=num_microbatches,
                                     remat=remat, unroll=unroll)
            x = loss_lib.embed_tokens(mesh, params["embed"]["table"],
                                      batch["tokens"], dtype)
            Sq = x.shape[1]
            pos_table = modules.sinusoidal_positions(max(Sq, 2), cfg.d_model)
            x = x + pos_table[None, :Sq].astype(dtype)
            mask = jnp.ones(batch["tokens"].shape, jnp.float32)
            pm_d = model_lib.pad_mask(cfg, model_lib.decoder_assignment(cfg),
                                      cfg.decoder_slot_layout)
            y, aux = pipeline_forward(mesh, cfg, params["dec_blocks"], x,
                                      pm_d, layout=cfg.decoder_slot_layout,
                                      kv_source=xe, remat=remat,
                                      num_microbatches=num_microbatches,
                                      unroll=unroll)
        else:
            x = loss_lib.embed_tokens(mesh, params["embed"]["table"],
                                      batch["tokens"], dtype)
            mask = jnp.ones(batch["tokens"].shape, jnp.float32)
            if "prefix" in batch:
                x = jnp.concatenate([batch["prefix"].astype(dtype), x], axis=1)
                mask = jnp.concatenate(
                    [jnp.zeros(batch["prefix"].shape[:2], jnp.float32), mask],
                    axis=1)
            pm = model_lib.pad_mask(cfg)
            y, aux = pipeline_forward(mesh, cfg, params["blocks"], x, pm,
                                      num_microbatches=num_microbatches,
                                      window=window or cfg.sliding_window,
                                      remat=remat, unroll=unroll)
        yn = (modules.layernorm if cfg.family == "audio" else modules.rmsnorm)(
            params["final_norm"], y, cfg.norm_eps)
        labels = batch["labels"]
        if labels.shape[1] < yn.shape[1]:       # vlm prefix: no loss there
            pad = yn.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.zeros((labels.shape[0], pad), labels.dtype), labels],
                axis=1)
        loss = loss_lib.lm_head_loss(mesh, params["head"]["w"], yn, labels,
                                     mask, vocab_size=cfg.vocab_size)
        total = loss + cfg.router_aux_weight * aux
        return total, {"loss": loss, "aux": aux}

    return loss_fn


def make_train_step(mesh, cfg: ModelConfig, tc: TrainConfig, *,
                    window: int = 0):
    """Returns (train_step, loss_fn). State: {params, stash, opt_state, step}.

    Forward/backward run on the STASHED weights (one step stale, PipeDream-2BW
    adaptation of weight stashing); the update lands on the newest weights;
    aggregation blends per-stage version windows (paper §III-C)."""
    from repro.optim import get_optimizer
    opt_init, opt_update = get_optimizer(tc.optimizer)
    loss_fn = make_loss_fn(mesh, cfg, num_microbatches=tc.microbatches,
                           remat=tc.remat, window=window)
    agg_every = cfg.aggregate_every

    def train_step(state, batch):
        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["stash"], batch)
        if tc.bf16_grads:
            # cast before the (GSPMD-inserted) data-parallel all-reduce:
            # halves the dominant collective payload (EXPERIMENTS.md §Perf)
            grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
        kw = dict(lr=tc.learning_rate, weight_decay=tc.weight_decay)
        if tc.optimizer == "sgd":
            kw["momentum"] = tc.momentum
        new_params, new_opt = opt_update(state["params"], grads,
                                         state["opt_state"], **kw)
        step = state["step"] + 1
        if agg_every:
            do = (step % agg_every == 0)
            blended = dict(new_params)
            blended["blocks"] = _stage_window_blend(cfg, new_params["blocks"],
                                                    state["stash"]["blocks"])
            if "dec_blocks" in new_params:
                blended["dec_blocks"] = _stage_window_blend(
                    cfg, new_params["dec_blocks"],
                    state["stash"]["dec_blocks"])
            new_params = jax.tree.map(
                lambda b, n: jnp.where(do, b, n), blended, new_params)
        new_stash = state["params"] if cfg.stash_depth > 1 else new_params
        return {"params": new_params, "stash": new_stash,
                "opt_state": new_opt, "step": step}, metrics

    def init_state(params):
        return {"params": params, "stash": params,
                "opt_state": opt_init(params),
                "step": jnp.zeros((), jnp.int32)}

    train_step.init_state = init_state
    return train_step, loss_fn


def make_prefill_step(mesh, cfg: ModelConfig, *, num_microbatches=0,
                      window: int = 0, seq_chunks: int = 0):
    """Inference prefill: full-sequence forward, logits for the LAST position.

    seq_chunks > 1 switches to chunked-sequence pipelining (fills the KV/SSM
    caches as a side effect — the production prefill path; see §Perf)."""
    if seq_chunks > 1:
        assert cfg.family != "audio" and set(cfg.slot_layout) <= CHUNKABLE, \
            (cfg.name, cfg.slot_layout)

        def prefill_chunked(params, batch, caches):
            dtype = modules.dtype_of(cfg.dtype)
            x = loss_lib.embed_tokens(mesh, params["embed"]["table"],
                                      batch["tokens"], dtype)
            if "prefix" in batch:
                x = jnp.concatenate([batch["prefix"].astype(dtype), x], axis=1)
            pm = model_lib.pad_mask(cfg)
            y, new_caches = pipeline_prefill_chunked(
                mesh, cfg, params["blocks"], x, caches, pm,
                seq_chunks=seq_chunks, window=window or cfg.sliding_window)
            yn = modules.rmsnorm(params["final_norm"], y[:, -1:, :],
                                 cfg.norm_eps)
            logits = loss_lib.lm_head_logits(mesh, params["head"]["w"], yn,
                                             vocab_size=cfg.vocab_size)
            return logits, new_caches

        return prefill_chunked

    def prefill_step(params, batch):
        dtype = modules.dtype_of(cfg.dtype)
        if cfg.family == "audio":
            xe, _ = model_lib.embed_frames(cfg, batch["frames"], dtype)
            pm_e = model_lib.pad_mask(cfg)
            xe, _ = pipeline_forward(mesh, cfg, params["blocks"], xe, pm_e,
                                     layout=cfg.slot_layout, causal=False,
                                     num_microbatches=num_microbatches,
                                     remat=False)
            x = loss_lib.embed_tokens(mesh, params["embed"]["table"],
                                      batch["tokens"], dtype)
            Sq = x.shape[1]
            pos_table = modules.sinusoidal_positions(max(Sq, 2), cfg.d_model)
            x = x + pos_table[None, :Sq].astype(dtype)
            pm_d = model_lib.pad_mask(cfg, model_lib.decoder_assignment(cfg),
                                      cfg.decoder_slot_layout)
            y, _ = pipeline_forward(mesh, cfg, params["dec_blocks"], x, pm_d,
                                    layout=cfg.decoder_slot_layout,
                                    kv_source=xe, remat=False,
                                    num_microbatches=num_microbatches)
        else:
            x = loss_lib.embed_tokens(mesh, params["embed"]["table"],
                                      batch["tokens"], dtype)
            if "prefix" in batch:
                x = jnp.concatenate([batch["prefix"].astype(dtype), x], axis=1)
            pm = model_lib.pad_mask(cfg)
            y, _ = pipeline_forward(mesh, cfg, params["blocks"], x, pm,
                                    num_microbatches=num_microbatches,
                                    window=window or cfg.sliding_window,
                                    remat=False)
        yn = (modules.layernorm if cfg.family == "audio" else modules.rmsnorm)(
            params["final_norm"], y[:, -1:, :], cfg.norm_eps)
        return loss_lib.lm_head_logits(mesh, params["head"]["w"], yn,
                                       vocab_size=cfg.vocab_size)

    return prefill_step


def make_serve_step(mesh, cfg: ModelConfig, *, window: int = 0,
                    data_sharded=True, num_microbatches: int = 0):
    dtype = modules.dtype_of(cfg.dtype)
    layout = (cfg.decoder_slot_layout if cfg.family == "audio"
              else cfg.slot_layout)
    pm = model_lib.pad_mask(
        cfg, model_lib.decoder_assignment(cfg) if cfg.family == "audio" else None,
        layout)

    def serve_step(params, token, caches, pos, kv_source=None):
        x = loss_lib.embed_tokens(mesh, params["embed"]["table"], token, dtype,
                                  data_sharded=data_sharded)
        if cfg.family == "audio":
            pos_table = modules.sinusoidal_positions(
                max(cfg.max_target_positions, 2), cfg.d_model)
            x = x + jax.lax.dynamic_index_in_dim(
                pos_table, jnp.minimum(pos, pos_table.shape[0] - 1), 0,
                keepdims=False)[None, None].astype(dtype)
        blocks = (params["dec_blocks"] if cfg.family == "audio"
                  else params["blocks"])
        y, new_caches = pipeline_decode(
            mesh, cfg, blocks, x, caches, pos, pm, layout=layout,
            window=window or cfg.sliding_window, kv_source=kv_source,
            data_sharded=data_sharded, num_microbatches=num_microbatches)
        yn = (modules.layernorm if cfg.family == "audio" else modules.rmsnorm)(
            params["final_norm"], y, cfg.norm_eps)
        logits = loss_lib.lm_head_logits(mesh, params["head"]["w"], yn,
                                         data_sharded=data_sharded,
                                         vocab_size=cfg.vocab_size)
        return logits, new_caches

    return serve_step
