"""Public jit'd wrapper for the SSD scan kernel (padding + custom VJP with
reference backward, mirroring flash_attention/ops.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import default_interpret
from repro.kernels.ssm_scan.kernel import ssd_scan_kernel
from repro.kernels.ssm_scan.ref import ssd_scan_reference


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan(xh, dt, A, Bm, Cm, D, chunk=128, interpret=None):
    """xh: [B,S,H,P]; dt: [B,S,H]; A,D: [H]; Bm,Cm: [B,S,N] -> [B,S,H,P].

    Sequences are zero-padded to a chunk multiple (zero dt => identity decay
    contribution, zero input injection: exact). ``interpret=None`` follows
    ``kernels.backend.default_interpret``."""
    if interpret is None:
        interpret = default_interpret()
    B, S, H, P = xh.shape
    pad = (-S) % chunk
    if pad:
        zf = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        xh_, dt_, Bm_, Cm_ = zf(xh), zf(dt), zf(Bm), zf(Cm)
    else:
        xh_, dt_, Bm_, Cm_ = xh, dt, Bm, Cm
    y = ssd_scan_kernel(xh_, dt_, A, Bm_, Cm_, D, chunk=chunk,
                        interpret=interpret)
    return y[:, :S]


def _fwd(xh, dt, A, Bm, Cm, D, chunk, interpret):
    return ssd_scan(xh, dt, A, Bm, Cm, D, chunk, interpret), \
        (xh, dt, A, Bm, Cm, D)


def _bwd(chunk, interpret, res, g):
    xh, dt, A, Bm, Cm, D = res
    _, vjp = jax.vjp(lambda *a: ssd_scan_reference(*a, chunk=chunk), xh, dt,
                     A, Bm, Cm, D)
    return vjp(g)


ssd_scan.defvjp(_fwd, _bwd)
