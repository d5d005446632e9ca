"""Compiled per-stage hot path for the live FTPipeHD runtime.

The live runtime's unit of work is a contiguous layer slice. This module
gives each slice ONE packed representation and ONE compiled step:

``ChainLayout``
    Per-layer flat-buffer layout for a whole ``runtime/workload.LayerChain``
    (leaf treedefs/shapes/dtypes + sizes), built on the flatten helpers of
    ``kernels/fused_sgd/ops.py``. Every node in the cluster can derive the
    layout from the model definition alone, so a layer's weights travel the
    wire as a bare flat f32 array keyed by layer id.

``SliceLayout``
    A contiguous [a, e] window of a ``ChainLayout``: the slice's parameters
    (and momentum) live in one flat f32 buffer, and layer ``j``'s weights
    are the cheap array slice ``buffer[offset(j):offset(j)+size(j)]`` — the
    currency of vertical-sync stash copies, §III-E replication snapshots and
    §III-F redistribution fetches.

``StageExecutor``
    The compiled hot path: a jitted ``forward`` (activation, or loss at the
    last stage) and a jitted fused ``step`` that recomputes the forward
    under the batch's vertical-sync weight version, runs the backward, and
    applies the SGD+momentum+weight-decay update through the
    ``kernels/fused_sgd`` Pallas kernel — one compiled call per backward
    instead of an op-by-op ``jax.vjp`` + pytree update retraced every step.
    Gradients come out of the VJP already packed (the forward reads weights
    from the flat buffer, so d(loss)/d(buffer) IS the flat gradient).
    Recomputing the forward from the stored (version-buffer, input) pair
    reproduces the residuals the uncompiled path kept alive as a vjp
    closure, so vertical-sync semantics are bit-for-bit preserved. The
    momentum buffer is donated to the step on backends that support
    donation; the parameter buffers are not (the stash retains them).
    ``compiled=False`` keeps the legacy per-layer ``jax.vjp`` +
    ``optim/sgd.sgd_update`` path (same packed interface) as a reference
    and benchmark baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import default_interpret, pallas_native_backend
from repro.kernels.fused_sgd.ops import fused_sgd, pack_leaves, unpack_leaves
from repro.kernels.quant.ops import dequantize as quant_dequantize
from repro.kernels.quant.ops import quantize_ef
from repro.optim.sgd import sgd_update
from repro.runtime.qtensor import DeviceQuantized


def stage_device(dev: int):
    """The accelerator that worker ``dev`` keeps its buffers and runs its
    steps on: ``jax.devices()[dev mod n]``. Keyed by the device id, not
    the pipeline stage, so a worker stays on its chip when §III-F
    recovery renumbers the stages; with one device every worker shares
    it."""
    devices = jax.devices()
    return devices[dev % len(devices)]


def aggregate_packed(bufs) -> jnp.ndarray:
    """Mean of same-shape packed flat f32 buffers — THE weight-aggregation
    op of the runtime, shared by §III-C stash averaging
    (``runtime/live.Worker``), the semantics oracle's pluggable aggregate
    hook, and the fleet barrier (``runtime/fleet.py``): one stacked ``jnp``
    mean over the flat layout, so data-parallel averaging costs a couple of
    vector ops regardless of the layer's pytree structure."""
    return jnp.mean(jnp.stack([jnp.asarray(b) for b in bufs]), axis=0)


# ============================ packed layouts =============================

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Flat-buffer layout of one layer's parameter pytree."""
    treedef: Any
    shapes: tuple
    dtypes: tuple
    size: int                    # total elements across leaves


class ChainLayout:
    """Per-layer packed layout for a whole layer chain."""

    def __init__(self, layers: list[LayerSpec]):
        self.layers = layers

    @classmethod
    def of_params(cls, params: list) -> "ChainLayout":
        """Derive the layout from a chain's parameter list — a pure
        function of the model definition, so every node (thread, process,
        or host) computes the identical layer->offset map without
        exchanging metadata."""
        specs = []
        for p in params:
            leaves, treedef = jax.tree.flatten(p)
            shapes = tuple(l.shape for l in leaves)
            dtypes = tuple(l.dtype for l in leaves)
            size = int(sum(np.prod(s, dtype=np.int64) if s else 1
                           for s in shapes))
            specs.append(LayerSpec(treedef, shapes, dtypes, size))
        return cls(specs)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def layer_size(self, j: int) -> int:
        return self.layers[j].size

    def layer_nbytes(self, j: int) -> int:
        return 4 * self.layers[j].size          # packed f32 on the wire

    def pack_layer(self, j: int, pytree) -> jax.Array:
        """Layer pytree -> flat f32 [size(j)]."""
        return pack_leaves(jax.tree.leaves(pytree))

    def unpack_layer(self, j: int, flat) -> Any:
        """Flat f32 [size(j)] -> layer pytree (original shapes/dtypes)."""
        spec = self.layers[j]
        leaves = unpack_leaves(jnp.asarray(flat), spec.shapes, spec.dtypes)
        return jax.tree.unflatten(spec.treedef, leaves)

    def slice(self, a: int, e: int) -> "SliceLayout":
        return SliceLayout(self, a, e)


class SliceLayout:
    """Flat-buffer layout of the contiguous layer window [a, e]."""

    def __init__(self, chain_layout: ChainLayout, a: int, e: int):
        self.chain_layout = chain_layout
        self.a, self.e = a, e
        self.offsets: dict[int, int] = {}
        off = 0
        for j in range(a, e + 1):
            self.offsets[j] = off
            off += chain_layout.layer_size(j)
        self.size = off

    @property
    def layer_ids(self) -> list[int]:
        return list(range(self.a, self.e + 1))

    def view(self, buffer, j: int) -> jax.Array:
        """Layer ``j``'s flat weights: a cheap slice of the packed buffer."""
        off = self.offsets[j]
        return buffer[off:off + self.chain_layout.layer_size(j)]

    def pack(self, flats: dict[int, Any]) -> jax.Array:
        """{layer -> flat f32} covering [a, e] -> one packed buffer."""
        return jnp.concatenate(
            [jnp.ravel(jnp.asarray(flats[j])).astype(jnp.float32)
             for j in self.layer_ids]) if self.layer_ids else jnp.zeros((0,))

    def unpack_layer(self, buffer, j: int) -> Any:
        return self.chain_layout.unpack_layer(j, self.view(buffer, j))

    def unpack(self, buffer) -> dict[int, Any]:
        return {j: self.unpack_layer(buffer, j) for j in self.layer_ids}

    def zeros(self) -> jax.Array:
        return jnp.zeros((self.size,), jnp.float32)


# ============================ stage executor =============================

class StageExecutor:
    """Fused fwd/bwd/update for one stage slice on packed flat buffers.

    ``forward(buf, x, batch=None)``
        activation ``y`` (mid stage) or scalar loss (last stage).
    ``step(fwd_buf, new_buf, mom_buf, x, ct=None, batch=None)``
        -> ``(dx, new_buf', mom_buf')``: recompute forward under
        ``fwd_buf`` (the batch's vertical-sync version), backward with
        cotangent ``ct`` (1.0 at the last stage), fused SGD update applied
        to ``new_buf`` (the newest version) — the exact update order of the
        uncompiled path.
    ``forward_q`` / ``step_q``
        the fused-wire variants: same compiled call additionally runs the
        ``kernels/quant`` per-channel int8 quantizer on the outgoing
        boundary tensor with an error-feedback residual threaded like
        momentum, returning a ``DeviceQuantized`` payload the codec ships
        zero-copy (tag 13). Inbound ``DeviceQuantized`` values are
        accepted by every entry point and dequantized on-device inside
        the same call.
    """

    def __init__(self, chain, slice_layout: SliceLayout, *, last: bool,
                 lr: float, momentum: float = 0.9,
                 weight_decay: float = 4e-5, compiled: bool = True,
                 interpret: Optional[bool] = None, device=None):
        self.slice = slice_layout
        self.device = device     # inbound tensors move here (None: stay)
        self.last = last
        self.compiled = compiled
        ids = slice_layout.layer_ids
        # §III-E overlap scheduler: O(1) per-layer change counters, bumped
        # by every fused step (the whole packed slice is rewritten by
        # fused_sgd). The worker snapshots these alongside the weight
        # buffer; a counter equal to the one shadowed at the last ship
        # proves the layer unchanged WITHOUT the byte compare
        # (``Worker._delta_layers`` counters mode). Monotonic — external
        # writes that bypass the step (aggregation, install) are counted
        # by the worker on top.
        self.change_counts: dict[int, int] = {j: 0 for j in ids}
        if interpret is None:
            interpret = default_interpret()

        def dq_in(x):
            # Trace-time dispatch at the wire boundary: a device-quantized
            # input arrives as a (q, lo, scale) triple (see ``_coerce``)
            # and is dequantized INSIDE the compiled call by the fused
            # kernel; an exact input is already f32. jit caches by pytree
            # structure, so each input form gets its own trace.
            if isinstance(x, tuple):
                q, lo, scale = x
                return quant_dequantize(q, lo, scale, interpret=interpret)
            return x

        def fwd_raw(buf, x, batch):
            for j in ids:
                x = chain.apply_layer(j, slice_layout.unpack_layer(buf, j), x)
            return chain.loss(x, batch) if last else x

        def fwd_out(buf, x, batch):
            return fwd_raw(buf, dq_in(x), batch)

        def step_fn(fwd_buf, new_buf, mom_buf, x, ct, batch):
            # dequantize BEFORE the vjp: dx is then the cotangent w.r.t.
            # the f32 activation the upstream stage actually produced
            xf = dq_in(x)
            ctf = None if ct is None else dq_in(ct)
            out, vjp = jax.vjp(lambda b, xx: fwd_raw(b, xx, batch),
                               fwd_buf, xf)
            g_buf, dx = vjp(jnp.ones_like(out) if last else ctf)
            p_new, m_new = fused_sgd(new_buf, g_buf, mom_buf, lr=lr,
                                     momentum=momentum,
                                     weight_decay=weight_decay,
                                     interpret=interpret)
            return dx, p_new, m_new

        def fwd_q_fn(buf, x, res):
            # mid-stage forward + fused on-device quantization of the
            # outgoing activation, error-feedback residual threaded like
            # momentum (AccEPT): z = y + res is what gets quantized, and
            # res' = z - dequant(q) carries the noise forward. ``ok``
            # False (non-finite z) means the caller must ship ``z``
            # exactly and reset the residual.
            y = fwd_raw(buf, dq_in(x), None)
            if res is None:
                res = jnp.zeros_like(y)
            return quantize_ef(y, res, interpret=interpret)

        def step_q_fn(fwd_buf, new_buf, mom_buf, x, ct, res, batch):
            dx, p_new, m_new = step_fn(fwd_buf, new_buf, mom_buf, x, ct,
                                       batch)
            if res is None:
                res = jnp.zeros_like(dx)
            q, lo, scale, res2, ok, z = quantize_ef(dx, res,
                                                    interpret=interpret)
            return q, lo, scale, res2, ok, z, p_new, m_new

        def step_ref(fwd_buf, new_buf, mom_buf, x, ct, batch):
            # legacy hot path: eager per-layer vjp + pytree sgd_update
            x = dq_in(x)
            if ct is not None:
                ct = dq_in(ct)
            plist = [slice_layout.unpack_layer(fwd_buf, j) for j in ids]

            def sf(ps, xx):
                for j, p in zip(ids, ps):
                    xx = chain.apply_layer(j, p, xx)
                return chain.loss(xx, batch) if last else xx

            out, vjp = jax.vjp(sf, plist, x)
            g_params, dx = vjp(jnp.ones_like(out) if last else ct)
            new_flats, mom_flats = {}, {}
            for j, gp in zip(ids, g_params):
                p = slice_layout.unpack_layer(new_buf, j)
                m = slice_layout.unpack_layer(mom_buf, j)
                p_new, st = sgd_update(p, gp, {"momentum": m}, lr=lr,
                                       momentum=momentum,
                                       weight_decay=weight_decay)
                new_flats[j] = pack_leaves(jax.tree.leaves(p_new))
                mom_flats[j] = pack_leaves(jax.tree.leaves(st["momentum"]))
            return (dx, slice_layout.pack(new_flats),
                    slice_layout.pack(mom_flats))

        if compiled:
            # donate the momentum buffer (consumed every step); parameter
            # buffers stay live in the vertical-sync stash. CPU ignores
            # donation (with a warning), so only donate where it works.
            donate = (2,) if pallas_native_backend() else ()
            self._forward = jax.jit(fwd_out)
            self._step = jax.jit(step_fn, donate_argnums=donate)
            self._forward_q = jax.jit(fwd_q_fn)
            self._step_q = jax.jit(step_q_fn, donate_argnums=donate)
        else:
            self._forward = fwd_out
            self._step = step_ref
            # the fused-quantize entry points stay available uncompiled
            # (interpret-mode kernels run eagerly); the legacy step_ref
            # backward is not re-derived for them — they wrap step_fn.
            self._forward_q = fwd_q_fn
            self._step_q = step_q_fn

    def _coerce(self, x):
        """Wire value -> jit input on this stage's device. Exact tensors
        become f32 arrays; a ``DeviceQuantized`` becomes a (q, lo, scale)
        device triple that the compiled call dequantizes via the fused
        kernel — this is the dequantization boundary of the
        wire-compression tiers (``runtime/codec.py``): tags 10-12 already
        decoded to f32, tag 13 dequantizes on-device HERE, inside the
        single jitted step."""
        if isinstance(x, DeviceQuantized):
            return tuple(jax.device_put(a, self.device) for a in x.arrays())
        return jax.device_put(x, self.device).astype(jnp.float32)

    def forward(self, buf, x, batch=None):
        """Run the slice forward under packed weights ``buf``: activation
        for a mid stage, scalar loss at the last (``batch`` supplies the
        labels there). ``x`` may be an exact tensor of any wire precision
        or a ``DeviceQuantized`` (see ``_coerce``); the compiled step
        always sees f32."""
        return self._forward(buf, self._coerce(x), batch)

    def forward_q(self, buf, x, res, batch=None):
        """Mid-stage forward that emits a PRE-QUANTIZED boundary tensor:
        forward + fused per-channel int8 quantize with error feedback in
        ONE compiled call. ``res`` is the carried residual (None on the
        first send after an install). Returns ``(payload, res')`` where
        ``payload`` is a ``DeviceQuantized`` ready for zero-copy encode —
        or an exact f32 ndarray when the activation went non-finite (the
        per-tensor exact-fallback rule; the residual then resets)."""
        if self.last:
            raise ValueError("forward_q is for mid stages; the last stage "
                             "emits a loss, not an activation")
        q, lo, scale, res2, ok, z = self._forward_q(buf, self._coerce(x),
                                                    res)
        if bool(ok):
            return DeviceQuantized.from_arrays(q, lo, scale), res2
        return np.asarray(z), jnp.zeros_like(res2)

    def step(self, fwd_buf, new_buf, mom_buf, x, ct=None, batch=None):
        """One fused backward+update: recompute the forward under
        ``fwd_buf`` (the batch's vertical-sync version), backpropagate
        cotangent ``ct`` (implicit 1.0 at the last stage), and apply the
        SGD update to ``new_buf`` (the newest version). Returns
        ``(dx, new_buf', mom_buf')``; ``mom_buf`` may be donated. ``x``
        and ``ct`` go through ``_coerce`` (same wire boundary as
        ``forward``; a quantized ``x`` recomputes the forward from the
        identical dequantized tensor the send-side residual accounted
        for)."""
        x = self._coerce(x)
        if ct is not None:
            ct = self._coerce(ct)
        self._bump_counts()
        return self._step(fwd_buf, new_buf, mom_buf, x, ct, batch)

    def _bump_counts(self) -> None:
        for j in self.slice.layer_ids:
            self.change_counts[j] += 1

    def step_q(self, fwd_buf, new_buf, mom_buf, x, ct=None, batch=None,
               res=None):
        """``step`` that also quantizes the outgoing cotangent ``dx`` with
        error feedback, all inside the single compiled call (for stages
        > 0 on the fused wire tier). Returns
        ``(payload, new_buf', mom_buf', res')`` with the same
        exact-fallback rule as ``forward_q``."""
        x = self._coerce(x)
        if ct is not None:
            ct = self._coerce(ct)
        self._bump_counts()
        q, lo, scale, res2, ok, z, p_new, m_new = self._step_q(
            fwd_buf, new_buf, mom_buf, x, ct, res, batch)
        if bool(ok):
            return DeviceQuantized.from_arrays(q, lo, scale), p_new, \
                m_new, res2
        return np.asarray(z), p_new, m_new, jnp.zeros_like(res2)
