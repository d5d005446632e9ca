"""The comparison that decides ``correct`` for a training cell.

The run's own state after its first steps is compared with the plain
reference following the same steps from the same seed:

* ``loss_gap``: the widest relative gap of the first steps' losses;
* ``grad_gap``: the first gradient as the optimizer got it, worked out
  from the weights before and after step 1 (plain SGD: (P0 - P1) / lr);
* ``change_gap``: the change of the weights over the first k steps,
  Pk - P0, as step k + 1 reads them (k is the cell's ``check_steps``,
  at least the stage count plus 3, so that several of those steps run
  on a stashed version above 0).

Both norms are taken per leaf (one weight tensor of one layer): the gap
between the run's norm and the reference's, over the larger of the
reference leaf's norm and the median leaf's, and the worst leaf decides.
Leaves whose reference gradient is under ``NEGLIGIBLE`` of the median
leaf's move by round-off alone and are left out by that rule.

A cell with a fault adds the §III-F hand-off:

* ``redistribution_gap``: the widest gap between a layer the survivors
  installed at the recovery and the copy it has to be: the dead stage's
  weights at its last chain replication, or the old holder's newest
  weights for a layer a survivor held. The hand-off copies, so it is 0.
* ``resume_loss_ratio``: the mean loss of the survivors' first batches
  over that of the same number of batches before the restart, a whole
  cycle of the data set each.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def split_stages(ranges: list, slices: dict, layer_sizes: list) -> list:
    """Per-layer flat arrays of the whole model from per-stage packed
    slices. ``ranges[i] = (a, e)`` is stage i's inclusive layer range and
    ``slices[i]`` its packed buffer. Raises where a slice's size does not
    match its layers."""
    out = [None] * len(layer_sizes)
    for i, (a, e) in enumerate(ranges):
        buf = np.asarray(slices[i], np.float32)
        want = sum(layer_sizes[a:e + 1])
        if buf.size != want:
            raise ValueError(f"stage {i} holds {buf.size} weights, layers "
                             f"{a}..{e} have {want}")
        off = 0
        for j in range(a, e + 1):
            out[j] = buf[off:off + layer_sizes[j]]
            off += layer_sizes[j]
    return out


def leaf_norms(layers: list, leaf_sizes: list) -> list[float]:
    """L2 norm of every leaf, layer by layer, in packing order."""
    out = []
    for flat, sizes in zip(layers, leaf_sizes):
        off = 0
        for n in sizes:
            out.append(float(np.linalg.norm(flat[off:off + n])))
            off += n
    return out


def leaf_names(leaf_sizes: list) -> list[str]:
    return [f"layer{j}.leaf{k}" for j, sizes in enumerate(leaf_sizes)
            for k in range(len(sizes))]


def leaf_gaps(run_norms, ref_norms, keep) -> np.ndarray:
    """Per kept leaf, |run norm - reference norm| over the larger of the
    reference leaf's norm and the median kept leaf's."""
    ref = np.asarray(ref_norms)[keep]
    run = np.asarray(run_norms)[keep]
    return np.abs(run - ref) / np.maximum(ref, float(np.median(ref)))


def readings(run: dict, ref: dict, leaf_sizes: list, lr: float) -> dict:
    """``run``: ``losses`` (first steps) and per-layer flats ``p0``, ``p1``,
    ``pk`` (after the k compared steps). ``ref``: the same from the
    reference, with ``grad0`` per-layer flats of its first gradient."""
    steps = len(ref["losses"])
    if len(run["losses"]) < steps or not all(
            math.isfinite(v) for v in run["losses"][:steps]):
        loss_gap = math.inf
    else:
        loss_gap = max(abs(a - b) / abs(b) for a, b in
                       zip(run["losses"][:steps], ref["losses"]))
    g_ref = leaf_norms(ref["grad0"], leaf_sizes)
    keep = np.asarray(g_ref) >= NEGLIGIBLE * float(np.median(g_ref))
    g_run = leaf_norms([(a - b) / lr for a, b in zip(run["p0"], run["p1"])],
                       leaf_sizes)
    d_ref = leaf_norms([b - a for a, b in zip(ref["p0"], ref["pk"])],
                       leaf_sizes)
    d_run = leaf_norms([b - a for a, b in zip(run["p0"], run["pk"])],
                       leaf_sizes)
    names = [n for n, k in zip(leaf_names(leaf_sizes), keep) if k]
    g_gaps = leaf_gaps(g_run, g_ref, keep)
    d_gaps = leaf_gaps(d_run, d_ref, keep)
    return {"loss_gap": loss_gap,
            "grad_gap": float(g_gaps.max()),
            "change_gap": float(d_gaps.max()),
            "grad_gap_median": float(np.median(g_gaps)),
            "change_gap_median": float(np.median(d_gaps)),
            "grad_worst_leaf": names[int(g_gaps.argmax())],
            "change_worst_leaf": names[int(d_gaps.argmax())],
            "leaves_compared": int(keep.sum()),
            "leaves_left_out": [n for n, k in zip(leaf_names(leaf_sizes),
                                                  keep) if not k]}


def layer_of(points, layer: int) -> int:
    """The stage that holds ``layer`` under partition ``points`` (the
    last layer of each stage)."""
    for i, p in enumerate(points):
        if layer <= p:
            return i
    raise ValueError(f"layer {layer} is past the partition {points}")


def unpack(buf, layer_range, layer_sizes: list) -> dict:
    """{layer: flat weights} of a stage's packed buffer."""
    a, e = layer_range
    return dict(enumerate(split_stages([(a, e)], {0: buf}, layer_sizes)))


def redistribution_gap(recovery: dict, old_points, old_workers: list,
                       chain_rounds: dict, installs: list,
                       layer_sizes: list) -> float:
    """Widest absolute gap between what the survivors installed at the
    recovery ``{failed, restart}`` and the copy each layer has to be:
    the dead worker's newest weights at its last chain replication, or
    the old holder's newest weights before the refit. Infinite where a
    survivor installed nothing, or a layer has no copy to compare with."""
    dead = set(recovery["failed"])
    mine = {}
    for rec in installs:                    # the last install wins
        if rec["version"] == recovery["restart"]:
            mine[rec["dev"]] = rec
    survivors = [d for d in old_workers if d not in dead]
    if sorted(mine) != sorted(survivors):
        return float("inf")
    try:
        return _copy_gap(mine, dead, old_points, old_workers, chain_rounds,
                         layer_sizes)
    except ValueError:                  # a slice of the wrong size
        return float("inf")


def _copy_gap(mine, dead, old_points, old_workers, chain_rounds,
              layer_sizes) -> float:
    held = {}
    for rec in mine.values():
        layers = unpack(rec["old_newest"], rec["old_range"], layer_sizes)
        held[rec["dev"]] = {j: w for j, w in layers.items()
                            if w is not None}
    for dev in dead:
        if dev not in chain_rounds:
            return float("inf")
        _, rng, buf = chain_rounds[dev]
        layers = unpack(buf, rng, layer_sizes)
        held[dev] = {j: w for j, w in layers.items() if w is not None}
    gap = 0.0
    for rec in mine.values():
        got = unpack(rec["installed"], rec["range"], layer_sizes)
        a, e = rec["range"]
        for j in range(a, e + 1):
            holder = old_workers[layer_of(old_points, j)]
            want = held.get(holder, {}).get(j)
            if want is None:
                return float("inf")
            gap = max(gap, float(np.max(np.abs(got[j] - want))))
    return gap


def resume_loss_ratio(losses, restart: int, span: int) -> float:
    """Mean loss of batches ``restart .. restart + span - 1`` over that of
    the ``span`` batches before; infinite where any is missing or not
    finite."""
    pre = [float(v) for v in losses[max(0, restart - span):restart]]
    post = [float(v) for v in losses[restart:restart + span]]
    vals = pre + post
    if (len(pre) != span or len(post) != span
            or not all(math.isfinite(v) for v in vals)):
        return float("inf")
    return float(np.mean(post) / np.mean(pre))


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the limited readings.
    A reading that is not finite fails."""
    shown = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(values[k]) and values[k] <= limits[k]
             for k in limits)
    return ok, shown
