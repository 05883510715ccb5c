"""step_mfu: the whole step's share of the chips' peak FLOP/s.

Required model FLOPs of the traced window (``feelbench/work.py``): for
each admitted device E * ceil(size_k / B) local steps of B samples at 3
forward passes each, plus one forward pass over the test split per
scenario-round.  Frozen lanes and padded steps the program runs anyway
are not counted.  Divided by window x chips x peak (bf16, since f32
dots run at the TPU's default one-pass bf16 precision), in percent.
"""

from feelbench import work

LAYER = "whole step"
UNIT = "%"
MOVES = "scenario_rounds_per_s"


def reduce(trace: dict, ctx: dict):
    cfg = ctx["cfg"]
    flops = (work.train_flops(ctx["selected"], ctx["sizes"], cfg)
             + work.eval_flops(ctx["scenario_rounds"], ctx["test_samples"],
                               cfg))
    peak = ctx["chips"] * ctx["peaks"]["flops_per_s"] * trace["window_s"]
    return 100.0 * flops / peak if peak > 0 else None
