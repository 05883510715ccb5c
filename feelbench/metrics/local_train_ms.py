"""local_train_ms: local SGD time per scenario-round.

Device time of the operations under the program's ``repro/local_train``
named scope (core/federated.py make_local_trainer, _masked_local_train), summed over the cell's chips, per
scenario-round of the traced window, in milliseconds.  Nothing when no
operation carries the scope.
"""

LAYER = "local_train"
UNIT = "ms"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    s = trace["phase_s"].get("local_train")
    if not s:
        return None
    return 1e3 * s / ctx["scenario_rounds"]
