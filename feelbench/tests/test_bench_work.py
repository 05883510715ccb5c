"""Work counts of ``feelbench/work.py`` against counts made by hand."""

import copy
import json
import os

import numpy as np
import pytest

from feelbench import models, work

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


# The paper's MLP (784-200-10), which no cell runs yet.
MLP = {"model": "mlp", "params": 159_010, "batch_size": 50,
       "local_epochs": 1, "net": {"hidden": 200, "classes": 10, "image": 28}}


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_forward_flops_of_the_paper_nets():
    # CNN: conv1 24x24x10 outputs of 5x5x1 MACs, conv2 8x8x20 of 5x5x10,
    # FC 320x50 and 50x10; 2 FLOPs a MAC.
    cnn = 2 * (24 * 24 * 10 * 25 + 8 * 8 * 20 * 250 + 320 * 50 + 50 * 10)
    mlp = 2 * (784 * 200 + 200 * 10)
    assert cnn == 961_000 and mlp == 317_600
    for cfg, want in ((config("paper-cnn-k100"), cnn), (MLP, mlp)):
        assert models.load(cfg).forward_flops(cfg) == want


def test_param_counts_match_the_configs():
    for cfg in (config("paper-cnn-k100"), MLP):
        assert models.load(cfg).uploaded_params(cfg) == cfg["params"]


def test_train_steps_and_flops_at_tiny_k():
    cfg = copy.deepcopy(MLP)
    cfg.update(batch_size=50, local_epochs=2)
    sizes = np.array([50, 120, 900, 7])
    # Two rounds: devices 0 and 2 admitted, then 1, 2 and 3.
    selected = np.array([[1, 0, 1, 0], [0, 1, 1, 1]], np.float32)
    # ceil(size / 50) * E steps: 1, 3, 18, 1 -> 2, 6, 36, 2.
    steps = (2 + 36) + (6 + 36 + 2)
    assert work.train_steps(selected, sizes, cfg) == steps
    assert work.train_flops(selected, sizes, cfg) == pytest.approx(
        3 * 317_600 * 50 * steps)


def test_eval_flops_and_fedavg_bytes_at_tiny_k():
    cfg = config("paper-cnn-k100")
    assert work.eval_flops(3, 6000, cfg) == 3 * 6000 * 961_000
    selected = np.array([[[1, 0, 1], [0, 0, 1]],
                         [[1, 1, 1], [0, 1, 0]]], np.float32)
    # 7 admitted models read, one global model written per round (4).
    assert work.fedavg_bytes(selected, cfg) == 4 * 21_840 * (7 + 4)
