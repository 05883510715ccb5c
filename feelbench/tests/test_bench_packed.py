"""``packed_step_use``: the required local-SGD steps over the lane-steps
the program's packed trainer runs (``repro.core.federated.pack_plan``)."""

import numpy as np
import pytest

from feelbench.metrics import packed_step_use
from feelbench.tests.test_bench_lanes import paper_cfg, paper_sizes
from repro.core import federated

CHIP = {"chips_seen": 1}


def test_all_admitted_at_the_paper_deployment():
    """Every device admitted: 1,036 required of 50 packed lanes x 22
    trips, the longest pair, a round."""
    cfg = paper_cfg()
    sizes = paper_sizes(cfg)
    selected = np.ones((8, cfg["rounds"], cfg["devices"]), np.float32)
    ctx = {"cfg": cfg, "sizes": sizes, "selected": selected, "chips": 1}
    assert packed_step_use.reduce(CHIP, ctx) == pytest.approx(
        100 * 1036 / 1100)


def test_a_chip_runs_its_longest_scenario():
    cfg = paper_cfg()                         # E = 1, B = 50
    sizes = np.array([50, 100, 200, 200])     # 1, 2, 4 and 4 steps
    # Two scenarios, one round: pairs (4, 1) and (4, 2), 6 trips, 11
    # steps; then (2, 0) and (1, 0), 2 trips, 3 steps.
    selected = np.array([[[1, 1, 1, 1]], [[1, 1, 0, 0]]], np.float32)
    ctx = {"cfg": cfg, "sizes": sizes, "selected": selected}
    # One chip: both scenarios loop 6 trips over 2 packed lanes.
    assert packed_step_use.reduce(CHIP, dict(ctx, chips=1)) == \
        pytest.approx(100 * 14 / (2 * 2 * 6))
    # Two chips, a scenario each: 6 and 2 trips.
    assert packed_step_use.reduce(CHIP, dict(ctx, chips=2)) == \
        pytest.approx(100 * 14 / (2 * 6 + 2 * 2))


def test_nothing_without_a_chip_or_a_plan(monkeypatch):
    cfg = paper_cfg()
    ctx = {"cfg": cfg, "sizes": paper_sizes(cfg), "chips": 1,
           "selected": np.ones((1, 15, 100))}
    assert packed_step_use.reduce({"chips_seen": 0}, ctx) is None
    monkeypatch.delattr(federated, "pack_plan")
    assert packed_step_use.reduce(CHIP, ctx) is None
