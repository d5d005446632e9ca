"""The control of the check: the plain reference put in the system's
place, computed in the precision below the one the configuration states
(the configuration's ``control_precision``), and judged by the same
comparison as a run. It has to come out as not correct.

    python3 -m benchmarks.chip.control --workload <cell> --seeds 1,2,3
    python3 -m benchmarks.chip.control --workload <cell> --seeds 1,2,3 \
        --precision f32_default \
        [--fault half_batch|no_exchange|altered_loss|latest_weights]

``--precision f32_default`` puts the reference in the system's place at
the precision the configuration states: alone, it reads what rounding
gives; with ``--fault`` it reads a planted fault. Prints one JSON line per
seed with the readings beside the cell's limits. The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, registry  # noqa: E402
from benchmarks.chip.run import SEED_MOD, prepare  # noqa: E402


FAULTS = ("half_batch", "no_exchange", "altered_loss", "latest_weights")


def stage_ends(num_layers: int, stages: int) -> tuple:
    """Last layer of every stage but the last, under a uniform split."""
    base, extra = divmod(num_layers, stages)
    ends, acc = [], -1
    for i in range(stages - 1):
        acc += base + (1 if i < extra else 0)
        ends.append(acc)
    return tuple(ends)


@functools.lru_cache(maxsize=1)
def _exact(cell_json: str, seed: int):
    """The seed's weights, batches and reference steps, kept for the next
    fault on the same seed."""
    cell, ref = prepare(json.loads(cell_json))
    wseed = seed % SEED_MOD
    params = ref.init_params(wseed, **cell["spec"])
    data = ref.make_batches(wseed, cell["check_steps"], cell["batch"],
                            **cell["spec"])
    exact = ref.first_steps(params, data, lr=cell["lr"],
                            n_stages=cell["workers"],
                            steps=cell["check_steps"])
    return params, data, exact


def readings(cell: dict, seed: int, precision: str | None = None,
             fault: str | None = None) -> dict:
    """The control's readings for one seed, and the verdict on them.
    ``precision`` replaces the configuration's control precision;
    ``fault`` plants one of ``FAULTS`` in the reference in the system's
    place."""
    config = registry.load_config(cell["config"])
    ref = registry.load_reference(config["reference"])
    steps = cell["check_steps"]
    params, data, exact = _exact(json.dumps(cell, sort_keys=True), seed)
    kw = dict(lr=cell["lr"], steps=steps)
    low_data, zero_after, n_low = data, (), cell["workers"]
    if fault == "half_batch":
        half = cell["batch"] // 2
        low_data = [{k: v[:half] for k, v in b.items()} for b in data]
    elif fault == "no_exchange":
        zero_after = stage_ends(len(params), cell["workers"])
    elif fault == "latest_weights":
        n_low = 1       # every batch on the newest weights, none stashed
    elif fault not in (None, "altered_loss"):
        raise ValueError(f"unknown fault {fault!r}")
    low = ref.first_steps(params, low_data,
                          precision_name=precision
                          or config["control_precision"],
                          zero_after=zero_after, n_stages=n_low, **kw)
    if fault == "altered_loss":
        low["losses"] = [1.1 * v for v in low["losses"]]
    ref_state = {"losses": exact["losses"],
                 "grad0": ref.flat_layers(exact["grad0"]),
                 "p0": ref.flat_layers(exact["versions"][0]),
                 "pk": ref.flat_layers(exact["versions"][steps])}
    ctl_state = {"losses": low["losses"],
                 "p0": ref.flat_layers(low["versions"][0]),
                 "p1": ref.flat_layers(low["versions"][1]),
                 "pk": ref.flat_layers(low["versions"][steps])}
    values = check.readings(ctl_state, ref_state, ref.leaf_sizes(params),
                            cell["lr"])
    limits = {k: v for k, v in cell["limits"].items() if k in values}
    correct, shown = check.verdict(values, limits)
    return {"seed": seed, "fault": fault, "correct": correct,
            "checks": shown,
            "readings": {k: v for k, v in values.items()
                         if k != "leaves_left_out"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--precision", default=None,
                    help="in place of the configuration's control precision")
    ap.add_argument("--fault", default=None,
                    help="comma-separated, of " + ", ".join(FAULTS)
                    + " and none")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    faults = [None if f == "none" else f
              for f in (args.fault or "none").split(",")]
    for s in args.seeds.split(","):
        for fault in faults:
            print(json.dumps(readings(cell, int(s), args.precision, fault)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
