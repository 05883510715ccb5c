"""packed_step_use: the share of the packed trainer's lane-steps required.

Required: the local SGD steps the window's admitted devices need,
E * ceil(size_k / B) each (``feelbench/work.py``), as in
``lane_step_use``.  Run: the program's trainer packs its ``lanes`` lanes
(``repro.core.federated.local_train_launch``) two to a trainer lane and
loops for the longest pair's total steps, the trip count of
``repro.core.federated.pack_plan``, from which the trainer plans.  A
chip runs its scenarios together, so its loop runs for the largest trip
count among them: each round, ceil(lanes / 2) x the chip's scenarios x
that count.  The traced window's chunks (``run.TRACE_CHUNKS``) are
dealt to the cell's chips in contiguous blocks.  In percent.  The
pairs' imbalance and the other scenarios' longer loops are the rest.

Nothing when the trace saw no chip, or when the program does not pack
its trainer.  Counts the cells' path, every device a lane (no
``dispatch_cap``).
"""

import numpy as np

from feelbench import run, work

LAYER = "local_train"
UNIT = "%"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    import jax
    from repro.core import federated
    plan = getattr(federated, "pack_plan", None)
    if plan is None or not trace["chips_seen"]:
        return None
    cfg = ctx["cfg"]
    sizes = np.asarray(ctx["sizes"])
    selected = np.asarray(ctx["selected"], np.float64)   # (N, R, K)
    fcfg = run.program_configs(cfg, "full")[2]
    lanes, _ = federated.local_train_launch(fcfg, sizes.shape[0],
                                            int(sizes.max()))
    per_device = cfg["local_epochs"] * np.ceil(sizes / cfg["batch_size"])
    steps = (selected * per_device).astype(np.int32)
    trips = np.asarray(jax.vmap(plan)(
        steps.reshape(-1, steps.shape[-1])).trips).reshape(steps.shape[:2])
    ran = 0
    for block in np.split(trips, run.TRACE_CHUNKS * ctx["chips"]):
        ran += -(-lanes // 2) * block.shape[0] * int(block.max(0).sum())
    if not ran:
        return None
    return 100.0 * work.train_steps(selected, sizes, cfg) / ran
