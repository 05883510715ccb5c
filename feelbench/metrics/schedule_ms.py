"""schedule_ms: DAS / Sub2 scheduling time per scenario-round.

Device time of the operations under the program's ``repro/schedule``
named scope (core/scheduler.py, core/bandwidth.py, core/allocator.py), summed over the cell's chips, per
scenario-round of the traced window, in milliseconds.  Nothing when no
operation carries the scope.
"""

LAYER = "schedule"
UNIT = "ms"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    s = trace["phase_s"].get("schedule")
    if not s:
        return None
    return 1e3 * s / ctx["scenario_rounds"]
