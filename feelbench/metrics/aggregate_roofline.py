"""aggregate_roofline: FedAvg's share of its memory roofline.

The least time FedAvg can take is the bytes it requires at the chip's
peak HBM bandwidth: each admitted device's model read once and the
global model written, P float32 values each (``feelbench/work.py``,
admitted devices read from the traced window's ``selected``).  Divided
by the device time under ``repro/aggregate``, in percent.  The count
assumes the client models are materialised before FedAvg; a program
that fuses FedAvg into the trainer retires this metric.
"""

from feelbench import work

LAYER = "aggregate"
UNIT = "%"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    s = trace["phase_s"].get("aggregate")
    if not s:
        return None
    need = work.fedavg_bytes(ctx["selected"], ctx["cfg"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / s
