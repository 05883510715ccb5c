"""The work a FEEL round requires, counted from shapes and outputs.

These counts are the numerators of the benchmark's utilization
metrics.  They count what the algorithm needs, not what the program
happens to run: frozen lanes and padded local steps are left out.

- ``forward_flops``: one sample through the net, 2 FLOPs per
  multiply-add of every convolution and dense layer (biases, ReLU and
  pooling left out): 961,000 for the paper CNN, 317,600 for the MLP.
- ``train_flops``: each admitted device runs E * ceil(size_k / B) SGD
  steps of B samples, at 3 forward passes a sample (forward, and the
  backward pass's two products).
- ``eval_flops``: one forward pass over the test split each round.
- ``fedavg_bytes``: FedAvg reads each admitted device's model and
  writes the global one, P float32 values each.
"""

from __future__ import annotations

import numpy as np


def forward_flops(cfg: dict) -> int:
    net = cfg["net"]
    s = net["image"]
    if cfg["model"] == "cnn":
        total, c_in = 0, 1
        for c_out, hw in (net["conv1"], net["conv2"]):
            s = s - hw + 1
            total += 2 * s * s * c_out * c_in * hw * hw
            s //= 2
            c_in = c_out
        flat = c_in * s * s
    else:
        total, flat = 0, s * s
    return total + 2 * flat * net["hidden"] + 2 * net["hidden"] * net["classes"]


def num_params(cfg: dict) -> int:
    net = cfg["net"]
    if cfg["model"] == "cnn":
        (c1, h1), (c2, h2) = net["conv1"], net["conv2"]
        s = ((net["image"] - h1 + 1) // 2 - h2 + 1) // 2
        convs = c1 * h1 * h1 + c1 + c2 * c1 * h2 * h2 + c2
        flat = c2 * s * s
    else:
        convs, flat = 0, net["image"] ** 2
    return (convs + flat * net["hidden"] + net["hidden"]
            + net["hidden"] * net["classes"] + net["classes"])


def train_steps(selected: np.ndarray, sizes: np.ndarray, cfg: dict) -> int:
    """Local SGD steps the admitted devices require.

    ``selected``: (..., K) 0/1 masks over any leading scenario and round
    axes; ``sizes``: (K,) samples per device.
    """
    per_device = cfg["local_epochs"] * np.ceil(
        np.asarray(sizes, np.float64) / cfg["batch_size"])
    return int(np.sum(np.asarray(selected, np.float64) * per_device))


def train_flops(selected, sizes, cfg: dict) -> float:
    return (3.0 * forward_flops(cfg) * cfg["batch_size"]
            * train_steps(selected, sizes, cfg))


def eval_flops(scenario_rounds: int, test_samples: int, cfg: dict) -> float:
    return float(scenario_rounds) * test_samples * forward_flops(cfg)


def fedavg_bytes(selected, cfg: dict) -> float:
    """Bytes FedAvg requires over the rounds in ``selected`` (..., K)."""
    sel = np.asarray(selected, np.float64)
    rounds = sel.size // sel.shape[-1]
    return 4.0 * num_params(cfg) * (float(np.sum(sel)) + rounds)
