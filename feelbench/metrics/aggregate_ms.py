"""aggregate_ms: FedAvg time per scenario-round.

Device time of the operations under the program's ``repro/aggregate``
named scope (core/federated.py fedavg_aggregate, kernels/fedavg_agg.py), summed over the cell's chips, per
scenario-round of the traced window, in milliseconds.  Nothing when no
operation carries the scope.
"""

LAYER = "aggregate"
UNIT = "ms"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    s = trace["phase_s"].get("aggregate")
    if not s:
        return None
    return 1e3 * s / ctx["scenario_rounds"]
