"""``packed_step_use``: the required local-SGD steps over the lane-steps
the program's packed trainer runs (``repro.core.federated.pack_plan``)."""

import numpy as np
import pytest

from feelbench import run
from feelbench.metrics import packed_step_use
from feelbench.models import _mnist
from repro.core import federated

CHIP = {"chips_seen": 1}


def paper_cfg() -> dict:
    return run.read_json(run.ROOT, "feelbench", "configs",
                         "paper-cnn-k100.json")


def paper_sizes(cfg: dict) -> np.ndarray:
    """The paper deployment's samples per device (one draw of shard
    counts from ``counts_seed``; the order of devices does not matter)."""
    d = cfg["data"]
    n_test = max(1, int(round(d["num_shards"] * d["test_fraction"])))
    counts = _mnist._shard_counts(
        np.random.default_rng(d["counts_seed"]), cfg["devices"],
        d["num_shards"] - n_test, d["min_shards"], d["max_shards"])
    return counts * d["shard_size"]


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
