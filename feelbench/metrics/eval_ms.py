"""eval_ms: test-set evaluation time per scenario-round.

Device time of the operations under the program's ``repro/eval`` named
scope (core/federated.py _make_sim, core/events.py: the forward pass
over the test split each round), summed over the cell's chips, per
scenario-round of the traced window, in milliseconds.  Nothing when no
operation carries the scope.
"""

LAYER = "eval"
UNIT = "ms"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    s = trace["phase_s"].get("eval")
    if not s:
        return None
    return 1e3 * s / ctx["scenario_rounds"]
