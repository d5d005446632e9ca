"""Benchmark driver: one benchmark per paper table/figure.

Prints ``name,value,derived`` CSV rows (values are virtual-clock seconds,
accuracies, or ratios — the paper's experiments reproduced on the simulator
and the async-semantics executor) plus a compact roofline summary derived
from the dry-run artifacts if present.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (bench_continuous_learning, bench_dynamic_partition,
                            bench_fault_recovery, bench_live_throughput,
                            bench_replication, bench_weight_aggregation)
    suites = [
        ("Fig5-dynamic-partition", bench_dynamic_partition.run),
        ("Fig4-weight-aggregation", bench_weight_aggregation.run),
        ("Fig6-TableIII-fault-recovery", bench_fault_recovery.run),
        ("Fig6-replication-overhead", bench_replication.run),
        ("Fig8-continuous-learning", bench_continuous_learning.run),
        ("Live-hot-path-throughput", bench_live_throughput.run),
    ]
    print("name,value,derived")
    failed = []
    for title, fn in suites:
        t0 = time.time()
        try:
            rows = fn()
            for n, v, d in rows:
                print(f"{n},{v},{d}")
            print(f"_meta/{title}_wall_s,{time.time()-t0:.1f},")
        except Exception as e:
            traceback.print_exc()
            print(f"_meta/{title}_FAILED,{e},")
            failed.append(title)

    # roofline summary (if the dry-run matrix has been generated)
    try:
        from benchmarks import roofline
        doms = roofline.summarize()
        for dom, pairs in doms.items():
            print(f"roofline/{dom}_pairs,{len(pairs)},")
    except Exception:
        print("roofline/skipped,0,run `python -m repro.launch.dryrun --all`")
    if failed:
        sys.exit(f"benchmark suites failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
