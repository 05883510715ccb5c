"""device_idle_share: the share of the traced window in which no
operation ran on the chip, for the chip that idled most, in percent."""

LAYER = "device"
UNIT = "%"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    if not trace["chips_seen"]:
        return None
    return 100.0 * trace["idle_share_worst"]
