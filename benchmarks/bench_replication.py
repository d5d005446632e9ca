"""Paper Fig. 6 (the batch-200 spike): cost of chain vs chain+global weight
replication, and the communication-bytes accounting of §III-E.
"""
from __future__ import annotations

import numpy as np

from repro.runtime.devices import (DeviceSpec, WorkloadProfile,
                                   uniform_bandwidth)
from repro.runtime.simulator import PipelineSimulator, SimConfig


def run(num_batches: int = 220):
    prof = WorkloadProfile.mobilenetv2(batch=256)
    devs = DeviceSpec.raspberry_trio()
    bw = uniform_bandwidth(3)
    sim = PipelineSimulator(SimConfig(devs, prof, bw, num_batches=num_batches))
    r = sim.run()
    bt = r.batch_times
    base = float(np.median(bt[20:45]))
    # the chain round at 50 runs inside the segment and shows in the
    # completions right after it; the chain+global round at 100 shares
    # a drained re-partition point
    chain_cost = float(max(bt[50:52]) - base)
    both_cost = float(bt[100] - base)
    weights_mb = float(np.sum(prof.weight_bytes)) / 1e6
    return [
        ("replication/base_batch_s", base, ""),
        ("replication/chain_extra_s", chain_cost, "every 50 batches"),
        ("replication/chain_plus_global_extra_s", both_cost,
         "every 100 batches (paper: global spike > chain spike)"),
        ("replication/model_weights_mb", weights_mb, ""),
        ("replication/global_over_chain_ratio",
         both_cost / max(chain_cost, 1e-9), ""),
    ]


if __name__ == "__main__":
    for n, v, d in run():
        print(f"{n},{v},{d}")
