"""Finds the benchmark's parts by name: a cell in ``workloads/<name>.json``,
a configuration in ``configs/<name>.json``, its plain reference in
``references/<name>.py`` and a metric's reader in ``metrics/<name>.py``.
Which metrics a cell reports is read from ``BENCHMARK.json`` at the root
of the checkout. Adding a cell, a configuration or a metric adds files and
entries; no file here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    cell = _json(HERE / "workloads" / f"{name}.json")
    cell.setdefault("name", name)
    return cell


def load_config(name: str) -> dict:
    return _json(HERE / "configs" / f"{name}.json")


def _module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {label} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{label}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    return _module(HERE / "references" / f"{name}.py", "reference")


def load_reader(metric: str):
    """The reader of one metric: a module with ``read(ctx)`` returning a
    number, or None where it finds nothing to read."""
    return _module(HERE / "metrics" / f"{metric}.py", "metric")


def metrics_of(cell: str, benchmark: dict | None = None) -> dict:
    """{"end_to_end": [...], "per_layer": [...]}: the metric entries of
    ``BENCHMARK.json`` that this cell reports."""
    bench = benchmark if benchmark is not None else _json(
        ROOT / "BENCHMARK.json")

    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    # a per-layer metric without a list goes wherever its end-to-end
    # metric is reported
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}
