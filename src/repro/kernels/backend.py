"""How the Pallas kernels lower, decided from the JAX backend in use.

Every kernel entry point takes ``interpret=None`` and resolves it here:
native lowering on a backend that compiles Pallas (TPU, GPU), the Pallas
interpreter on CPU, which has no native lowering. A failure to reach the
backend is raised, never taken as a reason to interpret.
"""
from __future__ import annotations

import jax


def pallas_native_backend() -> bool:
    """True when the default JAX backend compiles Pallas natively."""
    return jax.default_backend() in ("tpu", "gpu")


def default_interpret() -> bool:
    """Interpret only where no native Pallas lowering exists."""
    return not pallas_native_backend()
