"""1F1B schedule semantics: PipeDream's three rules + paper Fig. 2."""
from hypothesis import given, settings, strategies as st

from repro.core import schedule as sc


@settings(max_examples=80, deadline=None)
@given(st.integers(1, 8), st.integers(0, 7), st.integers(1, 64))
def test_schedule_invariants(n, stage, num_batches):
    stage = min(stage, n - 1)
    ops = list(sc.stage_schedule(stage, n, num_batches))
    sc.validate_schedule(ops, stage, n)
    # every batch forwarded and backwarded exactly once, in order
    fwd = [o.batch for o in ops if o.kind == "fwd"]
    bwd = [o.batch for o in ops if o.kind == "bwd"]
    assert fwd == list(range(num_batches))
    assert bwd == list(range(num_batches))


def _one_f_one_b(stage, n, num_batches):
    """The 1F1B loop, written out: the reference ``stage_op`` computes."""
    warm = min(n - stage, num_batches)
    ops, next_f, next_b = [("fwd", b) for b in range(warm)], warm, 0
    while next_b < num_batches:
        ops.append(("bwd", next_b))
        next_b += 1
        if next_f < num_batches:
            ops.append(("fwd", next_f))
            next_f += 1
    return ops


@settings(max_examples=80, deadline=None)
@given(st.integers(1, 8), st.integers(0, 7), st.integers(0, 64))
def test_stage_op_walks_the_1f1b_loop(n, stage, num_batches):
    """``stage_op`` (and ``stage_schedule`` built on it) gives op ``idx``
    of the loop without building it; ``next_forward`` the first forward
    at or after it."""
    stage = min(stage, n - 1)
    ref = _one_f_one_b(stage, n, num_batches)
    assert [(o.kind, o.batch) for o in
            sc.stage_schedule(stage, n, num_batches)] == ref
    for idx in range(len(ref) + 1):
        if idx < len(ref):
            assert (sc.stage_op(stage, n, num_batches, idx).kind,
                    sc.stage_op(stage, n, num_batches, idx).batch) \
                == ref[idx]
        assert sc.next_forward(stage, n, num_batches, idx) == next(
            (b for k, b in ref[idx:] if k == "fwd"), None)


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 8), st.integers(0, 100))
def test_vertical_sync_version(n, b):
    v = sc.version_for_batch(b, n)
    assert v == max(0, b - n + 1)
    # version is monotone and catches up to b with lag n-1
    assert sc.version_for_batch(b + 1, n) >= v


def test_paper_fig2_walkthrough():
    """n=3: batch 3 forwards with ver 1, batch 4 ver 2, batch 5 ver 3;
    backwarding batch 0 bumps to ver 1."""
    n = 3
    assert sc.version_for_batch(0, n) == 0
    assert sc.version_for_batch(1, n) == 0
    assert sc.version_for_batch(3, n) == 1
    assert sc.version_for_batch(4, n) == 2
    assert sc.version_for_batch(5, n) == 3
    assert sc.version_after_backward(0) == 1


def test_stash_depth_matches_paper():
    # "the training in the i-th stage can be viewed as n-i independent
    # concurrent training"
    for n in range(1, 6):
        for i in range(n):
            assert sc.stash_depth(i, n) == n - i
            assert sc.warmup_forwards(i, n) == n - i


def test_aggregation_interval_is_multiple_of_window():
    for n in range(2, 6):
        for i in range(n):
            for k in range(1, 4):
                assert sc.aggregation_interval(i, n, k) % (n - i) == 0
