"""A real cell cut to a size the CPU runs in seconds, for the tests.

The cell's configuration keeps every key; only its scale shrinks: 8
devices, 3 rounds, 40 shards of 25 samples, 2 scenarios a chunk and
cheaper solver loops.  Tests steer the harness with it; the program
takes no option for it.
"""

from __future__ import annotations

import copy

from feelbench import run


def cell(name: str = "cnn-das-s8", scenarios: int = 2,
         chips: int = 1) -> dict:
    c = copy.deepcopy(run.load_cell(name))
    cfg = c["cfg"]
    cfg.update(devices=8, rounds=3)
    cfg["data"].update(samples_per_class=100, num_shards=40, shard_size=25,
                       max_shards=6)
    cfg["sub2"].update(time_bisect_iters=20, newton_iters=6, pgd_iters=30)
    c["traffic_mix"] = dict(c["traffic_mix"], scenarios_per_chunk=scenarios)
    c["chips"] = chips
    c["limits"] = dict(c["limits"], reference_scenarios=2)
    return c


def cpu_device(count: int = 1) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": count}
