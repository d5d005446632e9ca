"""Compiled StageExecutor hot path: packed-layout round-trips, parity of the
jitted fused step with the uncompiled ``jax.vjp`` + ``optim/sgd.sgd_update``
reference over multiple steps (including the vertical-sync versioned-weights
path), and backend-aware Pallas interpret selection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.backend import default_interpret, pallas_native_backend
from repro.kernels.fused_sgd.ops import fused_sgd
from repro.optim.sgd import sgd_init, sgd_update
from repro.runtime.stage_executor import ChainLayout, StageExecutor
from repro.runtime.workload import classification_batches, mlp_chain

KEY = jax.random.PRNGKey(7)
LR, MOM, WD = 0.05, 0.9, 4e-5


def _setup(num_layers=6, a=1, e=3, width=16, in_dim=8):
    chain = mlp_chain(KEY, num_layers=num_layers, width=width, in_dim=in_dim)
    layout = chain.flat_layout()
    sl = layout.slice(a, e)
    buf = sl.pack(chain.flat_params(a, e))
    return chain, layout, sl, buf


class _Reference:
    """The pre-refactor hot path: eager per-layer vjp + pytree sgd_update."""

    def __init__(self, chain, ids, last):
        self.chain, self.ids, self.last = chain, ids, last

    def forward(self, plist, x, batch=None):
        for j, p in zip(self.ids, plist):
            x = self.chain.apply_layer(j, p, x)
        return self.chain.loss(x, batch) if self.last else x

    def step(self, fwd_plist, new_plist, opt, x, ct=None, batch=None):
        out, vjp = jax.vjp(lambda ps, xx: self.forward(ps, xx, batch),
                           fwd_plist, x)
        gps, dx = vjp(jnp.ones_like(out) if self.last else ct)
        new_out = []
        for j, p, gp in zip(self.ids, new_plist, gps):
            p_new, opt[j] = sgd_update(p, gp, opt[j], lr=LR, momentum=MOM,
                                       weight_decay=WD)
            new_out.append(p_new)
        return dx, new_out, opt


def _assert_buf_matches_plist(sl, buf, plist, ids, **tol):
    for j, p in zip(ids, plist):
        got = sl.unpack_layer(buf, j)
        for a_, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(p)):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), **tol)


# ============================== layouts ==================================

def test_pack_unpack_round_trip():
    chain, layout, sl, buf = _setup()
    assert buf.shape == (sl.size,)
    for j in sl.layer_ids:
        rt = layout.unpack_layer(j, layout.pack_layer(j, chain.params[j]))
        for a_, b_ in zip(jax.tree.leaves(rt),
                          jax.tree.leaves(chain.params[j])):
            np.testing.assert_array_equal(np.asarray(a_), np.asarray(b_))
            assert a_.dtype == b_.dtype
    # slice views are exactly the per-layer segments of the packed buffer
    off = 0
    for j in sl.layer_ids:
        n = layout.layer_size(j)
        np.testing.assert_array_equal(np.asarray(sl.view(buf, j)),
                                      np.asarray(buf[off:off + n]))
        assert layout.layer_nbytes(j) == 4 * n
        off += n


def test_flat_slice_matches_flat_params():
    chain, layout, sl, buf = _setup()
    sl2, buf2 = chain.flat_slice(1, 3)
    assert sl2.size == sl.size
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(buf2))


# ============================ step parity ================================

@pytest.mark.parametrize("compiled", [True, False])
def test_mid_stage_step_matches_reference_over_steps(compiled):
    chain, layout, sl, buf = _setup()
    ids = sl.layer_ids
    ex = StageExecutor(chain, sl, last=False, lr=LR, momentum=MOM,
                       weight_decay=WD, compiled=compiled)
    rng = np.random.default_rng(0)
    plist = [chain.params[j] for j in ids]
    opt = {j: sgd_init(chain.params[j]) for j in ids}
    mom_buf = sl.zeros()
    for _ in range(5):
        x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        ct = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        y = ex.forward(buf, x)
        y_ref = _Reference(chain, ids, last=False).forward(plist, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        dx, buf, mom_buf = ex.step(buf, buf, mom_buf, x, ct)
        dx_ref, plist, opt = _Reference(chain, ids, last=False).step(
            plist, plist, opt, x, ct)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-5, atol=1e-6)
        _assert_buf_matches_plist(sl, buf, plist, ids, rtol=1e-5, atol=1e-6)
        # momentum parity too (the fused kernel carries it)
        _assert_buf_matches_plist(
            sl, mom_buf, [opt[j]["momentum"] for j in ids], ids,
            rtol=1e-5, atol=1e-6)


def test_last_stage_step_matches_reference():
    num_layers = 4
    chain = mlp_chain(KEY, num_layers=num_layers)
    data = classification_batches("mlp", 3, batch=8, seed=1)
    sl = chain.flat_layout().slice(2, 3)
    ids = sl.layer_ids
    buf = sl.pack(chain.flat_params(2, 3))
    ex = StageExecutor(chain, sl, last=True, lr=LR, momentum=MOM,
                       weight_decay=WD)
    ref = _Reference(chain, ids, last=True)
    plist = [chain.params[j] for j in ids]
    opt = {j: sgd_init(chain.params[j]) for j in ids}
    mom_buf = sl.zeros()
    rng = np.random.default_rng(1)
    for t in range(3):
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        batch = data[t]
        loss = ex.forward(buf, x, batch)
        loss_ref = ref.forward(plist, x, batch)
        np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
        dx, buf, mom_buf = ex.step(buf, buf, mom_buf, x, None, batch)
        dx_ref, plist, opt = ref.step(plist, plist, opt, x, None, batch)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-5, atol=1e-6)
        _assert_buf_matches_plist(sl, buf, plist, ids, rtol=1e-5, atol=1e-6)


def test_versioned_weights_path_matches_reference():
    """Vertical sync: the forward/backward run on an OLDER weight version
    than the update target. The executor takes both buffers explicitly;
    parity must hold when they differ."""
    chain, layout, sl, buf = _setup()
    ids = sl.layer_ids
    ex = StageExecutor(chain, sl, last=False, lr=LR, momentum=MOM,
                       weight_decay=WD)
    ref = _Reference(chain, ids, last=False)
    rng = np.random.default_rng(2)
    versions = [buf]                       # packed version ring
    plists = [[chain.params[j] for j in ids]]
    opt = {j: sgd_init(chain.params[j]) for j in ids}
    mom_buf = sl.zeros()
    for t in range(4):
        x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        ct = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        v = max(0, t - 1)                  # pin an older version, as 1F1B does
        dx, new_buf, mom_buf = ex.step(versions[v], versions[-1], mom_buf,
                                       x, ct)
        dx_ref, new_plist, opt = ref.step(plists[v], plists[-1], opt, x, ct)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-5, atol=1e-6)
        _assert_buf_matches_plist(sl, new_buf, new_plist, ids,
                                  rtol=1e-5, atol=1e-6)
        versions.append(new_buf)
        plists.append(new_plist)


# ===================== backend-aware interpret knob ======================

def test_interpret_autodetects_backend():
    # this suite runs on CPU, where Pallas has no native lowering
    if jax.default_backend() == "cpu":
        assert not pallas_native_backend()
        assert default_interpret() is True
    p = jnp.arange(8.0)
    po, mo = fused_sgd(p, p * 0.1, jnp.zeros_like(p), lr=0.1,
                       momentum=0.0, weight_decay=0.0, interpret=None)
    np.testing.assert_allclose(np.asarray(po), np.asarray(p - 0.01 * p),
                               rtol=1e-6)
