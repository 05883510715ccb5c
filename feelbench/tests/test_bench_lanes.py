"""``lane_step_use``: the required local-SGD steps over the lane-steps
the program's trainer launches (``repro.core.federated.local_train_launch``)."""

import numpy as np
import pytest

from feelbench import data, run
from feelbench.metrics import lane_step_use
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
    counts = data._shard_counts(
        np.random.default_rng(d["counts_seed"]), cfg["devices"],
        d["num_shards"] - n_test, d["min_shards"], d["max_shards"])
    return counts * d["shard_size"]


def test_all_admitted_at_the_paper_deployment():
    """Every device admitted: 1,036 of 2,000 lane-steps a round."""
    cfg = paper_cfg()
    sizes = paper_sizes(cfg)
    selected = np.ones((8, cfg["rounds"], cfg["devices"]), np.float32)
    ctx = {"cfg": cfg, "sizes": sizes, "selected": selected,
           "scenario_rounds": 8 * cfg["rounds"]}
    assert lane_step_use.reduce(CHIP, ctx) == pytest.approx(51.80)


def test_frozen_lanes_and_padded_steps_are_not_required():
    cfg = paper_cfg()                         # E = 1, B = 50
    sizes = np.array([50, 100, 200])          # 1, 2 and 4 steps; cap 4
    selected = np.array([[[1, 1, 1], [0, 0, 1]]], np.float32)
    ctx = {"cfg": cfg, "sizes": sizes, "selected": selected,
           "scenario_rounds": 2}
    # 7 + 4 required of 2 rounds x 3 lanes x 4 steps.
    assert lane_step_use.reduce(CHIP, ctx) == pytest.approx(100 * 11 / 24)


def test_nothing_without_a_chip_or_a_launch_shape(monkeypatch):
    cfg = paper_cfg()
    ctx = {"cfg": cfg, "sizes": paper_sizes(cfg),
           "selected": np.ones((1, 15, 100)), "scenario_rounds": 15}
    assert lane_step_use.reduce({"chips_seen": 0}, ctx) is None
    monkeypatch.delattr(federated, "local_train_launch")
    assert lane_step_use.reduce(CHIP, ctx) is None

