"""The chip benchmark's tests run on the CPU: its harness is imported from
the root of the checkout, the program from ``src``."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny(cell: dict, **over) -> dict:
    """A cell cut to a size the CPU runs in seconds: the same protocol,
    cadence and checks, with the cut its model's reference states
    (``CPU_CUT``)."""
    from benchmarks.chip import registry
    config = registry.load_config(cell["config"])
    out = json.loads(json.dumps(cell))
    out.update(registry.load_reference(config["reference"]).CPU_CUT)
    out.update(over)
    return out


@pytest.fixture
def tiny_cell():
    from benchmarks.chip import registry

    def make(name, **over):
        return tiny(registry.load_cell(name), **over)
    return make


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """JAX's persistent compilation cache in a fresh directory shared by
    one test file's runs, as the harness keeps it on the chip; JAX's
    defaults come back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    old_max = jax.config.jax_compilation_cache_max_size
    yield tmp_path_factory.mktemp("jax_cache")
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
    jax.config.update("jax_compilation_cache_max_size", old_max)
    cc.reset_cache()
