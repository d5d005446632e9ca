"""Test bootstrap.

The pipeline/sharding tests need a small multi-device host mesh, so we ask
the CPU platform for 8 devices (NOT 512 — the production count is set only
inside launch/dryrun.py; 8 host devices are benign for the single-device
smoke tests, which just run on device 0).
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# XLA's CPU backend contracts a*b+c into one FMA wherever a fusion holds
# both ops, so a compiled program rounds differently from the eager and
# numpy references the parity tests compare against. Capping the ISA
# below FMA makes every op round on its own, as those references do.
if "--xla_cpu_max_isa" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_cpu_max_isa=AVX"

import jax  # noqa: E402  (must import after the flag)

jax.config.update("jax_platform_name", "cpu")

# Property tests prefer real hypothesis (installed via `pip install -e
# .[dev]`, as CI does); in bare environments fall back to the seeded
# random-sampling shim so the suite still collects and runs.
try:
    import hypothesis  # noqa: F401
except ImportError:
    import importlib.util

    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        os.path.join(os.path.dirname(__file__), "_hypothesis_fallback.py"))
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod
    sys.modules["hypothesis.strategies"] = _mod.strategies
