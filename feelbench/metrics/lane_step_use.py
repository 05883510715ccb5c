"""lane_step_use: the share of launched local-SGD lane-steps required.

Required: the local SGD steps the window's admitted devices need,
E * ceil(size_k / B) each (``feelbench/work.py``).  Launched: what the
program's vmapped trainer runs each scenario-round, ``lanes`` lanes of
``max_steps`` steps (``repro.core.federated.local_train_launch``, from
which the program builds its trainer), times the window's
scenario-rounds.  In percent.  Frozen lanes and a device's padded steps
are the rest.

Nothing when the trace saw no chip (nothing was launched on one), or
when the program does not say what it launches.
"""

import numpy as np

from feelbench import run, work

LAYER = "local_train"
UNIT = "%"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    from repro.core import federated
    launch = getattr(federated, "local_train_launch", None)
    if launch is None or not trace["chips_seen"]:
        return None
    cfg = ctx["cfg"]
    sizes = np.asarray(ctx["sizes"])
    # The program's FLConfig does not depend on the scheduling method.
    fcfg = run.program_configs(cfg, "full")[2]
    # The data's dense (K, cap) arrays are as wide as the largest
    # device's shard (feelbench/data.py).
    lanes, steps = launch(fcfg, sizes.shape[0], int(sizes.max()))
    launched = ctx["scenario_rounds"] * lanes * steps
    required = work.train_steps(ctx["selected"], sizes, cfg)
    return 100.0 * required / launched
