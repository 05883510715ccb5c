"""The work a FEEL round requires, counted from shapes and outputs.

These counts are the numerators of the benchmark's utilization
metrics.  They count what the algorithm needs, not what the program
happens to run: frozen lanes and padded local steps are left out.  What
one sample costs, and P, come from the configuration's model family
(``feelbench/models``).

- ``train_flops``: each admitted device runs E * ceil(size_k / B) SGD
  steps of B samples, at the family's training FLOPs a sample.
- ``eval_flops``: one forward pass over the test split each round.
- ``fedavg_bytes``: FedAvg reads each admitted device's model and
  writes the global one, P float32 values each.
"""

from __future__ import annotations

import numpy as np

from feelbench import models


def train_steps(selected: np.ndarray, sizes: np.ndarray, cfg: dict) -> int:
    """Local SGD steps the admitted devices require.

    ``selected``: (..., K) 0/1 masks over any leading scenario and round
    axes; ``sizes``: (K,) samples per device.
    """
    per_device = cfg["local_epochs"] * np.ceil(
        np.asarray(sizes, np.float64) / cfg["batch_size"])
    return int(np.sum(np.asarray(selected, np.float64) * per_device))


def train_flops(selected, sizes, cfg: dict) -> float:
    return (models.load(cfg).train_flops(cfg) * cfg["batch_size"]
            * train_steps(selected, sizes, cfg))


def eval_flops(scenario_rounds: int, test_samples: int, cfg: dict) -> float:
    return (float(scenario_rounds) * test_samples
            * models.load(cfg).forward_flops(cfg))


def fedavg_bytes(selected, cfg: dict) -> float:
    """Bytes FedAvg requires over the rounds in ``selected`` (..., K)."""
    sel = np.asarray(selected, np.float64)
    rounds = sel.size // sel.shape[-1]
    return (4.0 * models.load(cfg).uploaded_params(cfg)
            * (float(np.sum(sel)) + rounds))
