"""Jit'd wrappers: flat-array update + whole-pytree update (flatten, pad,
single fused kernel launch, unflatten).

``interpret=None`` (the default) follows ``kernels.backend``: the kernel
is compiled natively on Pallas-capable devices (TPU/GPU) and interpreted
on CPU, where Pallas has no native lowering. The flatten
helpers (``pack_leaves`` / ``unpack_leaves``) are shared with the live
runtime's packed-buffer layer (``runtime/stage_executor.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import default_interpret
from repro.kernels.fused_sgd.kernel import fused_sgd_kernel


def pack_leaves(leaves) -> jax.Array:
    """Concatenate pytree leaves into one flat f32 buffer."""
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])


def unpack_leaves(buf, shapes, dtypes, offset: int = 0) -> list:
    """Slice ``buf`` back into leaves of the given shapes/dtypes."""
    out, off = [], offset
    for shape, dtype in zip(shapes, dtypes):
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        out.append(buf[off:off + n].reshape(shape).astype(dtype))
        off += n
    return out


def fused_sgd(p, g, m, *, lr, momentum=0.9, weight_decay=4e-5,
              block=65536, interpret=None):
    """Flat [N] update. Pads to the block size internally."""
    if interpret is None:
        interpret = default_interpret()
    (N,) = p.shape
    blk = min(block, max(256, N))
    pad = (-N) % blk
    if pad:
        p_, g_, m_ = (jnp.pad(a, (0, pad)) for a in (p, g, m))
    else:
        p_, g_, m_ = p, g, m
    po, mo = fused_sgd_kernel(p_, g_, m_, lr=lr, momentum=momentum,
                              weight_decay=weight_decay, block=blk,
                              interpret=interpret)
    return po[:N], mo[:N]


def fused_sgd_tree(params, grads, mom, *, lr, momentum=0.9,
                   weight_decay=4e-5, interpret=None):
    """Whole-pytree fused update: one kernel launch over the concatenation."""
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = jax.tree.leaves(grads)
    leaves_m = jax.tree.leaves(mom)
    shapes = [l.shape for l in leaves_p]
    dtypes = [l.dtype for l in leaves_p]
    po, mo = fused_sgd(pack_leaves(leaves_p), pack_leaves(leaves_g),
                       pack_leaves(leaves_m), lr=lr, momentum=momentum,
                       weight_decay=weight_decay, interpret=interpret)
    outs_p = unpack_leaves(po, shapes, dtypes)
    outs_m = unpack_leaves(mo, shapes, [jnp.float32] * len(shapes))
    return jax.tree.unflatten(treedef, outs_p), \
        jax.tree.unflatten(treedef, outs_m)
