"""Finds the benchmark's parts by name: a cell in ``workloads/<name>.json``,
a configuration in ``configs/<name>.json``, its plain reference in
``references/<name>.py`` and a metric's reader in ``metrics/<name>.py``,
under each directory of ``DIRS`` in turn. Which metrics a cell reports is
read from ``BENCHMARK``. Adding a cell, a configuration or a metric adds
files and entries; no file here changes.

A configuration of any model kind the program's ``WorkloadSpec`` builds
comes in through these files alone:

* the configuration's ``model`` block names the ``kind`` and may hold
  the model's sizes;
* its reference states ``SPEC_KEYS``, the ``WorkloadSpec`` fields the
  configuration sets, and takes them by keyword (ignoring those it does
  not use) in ``init_params(seed, **spec)``, ``make_batches(seed, count,
  batch, **spec)``, ``forward_flops_per_sample(**spec)`` and
  ``param_count(**spec)``, beside ``first_steps``, ``leaf_sizes`` and
  ``flat_layers``;
* ``spec_of`` reads each key from the cell, else from the ``model``
  block: the cell wins, so a test can cut a size by overriding it there.

The harness passes that one ``spec`` to ``WorkloadSpec``, to the
reference and, as ``ctx.cell["spec"]``, to the metric readers; a cell's
analytic ``profile`` gets the keys its signature takes. A reference may
state ``CPU_CUT``: what a test overrides in a cell to run it on the CPU.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DIRS = [HERE]                 # searched in order
BENCHMARK = ROOT / "BENCHMARK.json"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _find(kind: str, name: str, ext: str) -> Path:
    for d in DIRS:
        path = d / kind / f"{name}{ext}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                            f"{', '.join(map(str, DIRS))}")


def load_cell(name: str) -> dict:
    cell = _json(_find("workloads", name, ".json"))
    cell.setdefault("name", name)
    return cell


def load_config(name: str) -> dict:
    return _json(_find("configs", name, ".json"))


def _module(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{label}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    return _module(_find("references", name, ".py"), "reference")


def load_reader(metric: str):
    """The reader of one metric: a module with ``read(ctx)`` returning a
    number, or None where it finds nothing to read."""
    return _module(_find("metrics", metric, ".py"), "metric")


def spec_of(cell: dict, config: dict, reference) -> dict:
    """The model's ``WorkloadSpec`` fields: each of the reference's
    ``SPEC_KEYS`` from the cell, else from the configuration's ``model``
    block."""
    model = config["model"]
    missing = [k for k in reference.SPEC_KEYS
               if k not in cell and k not in model]
    if missing:
        raise KeyError(f"cell {cell.get('name')!r} and configuration "
                       f"{config.get('name')!r} set no {missing}")
    return {k: cell[k] if k in cell else model[k]
            for k in reference.SPEC_KEYS}


def metrics_of(cell: str, benchmark: dict | None = None) -> dict:
    """{"end_to_end": [...], "per_layer": [...]}: the metric entries of
    ``BENCHMARK.json`` that this cell reports."""
    bench = benchmark if benchmark is not None else _json(BENCHMARK)

    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    # a per-layer metric without a list goes wherever its end-to-end
    # metric is reported
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}
