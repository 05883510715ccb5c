"""The paper's MLP (§VI-A.2) on the MNIST-shaped stand-in: FC 200 ->
ReLU -> FC 10 on the flat image.  What it shares with the CNN is in
``_paper.py``."""

from __future__ import annotations

import functools

import jax

from feelbench.models import _paper
from feelbench.models._paper import (  # noqa: F401
    classes, cut_for_cpu, data, engine_args, inputs, reference_block)


@functools.partial(jax.jit, static_argnames=("net_items",))
def _init(key, net_items: tuple) -> dict:
    net = dict(net_items)
    k1, k2 = jax.random.split(key)
    return {"fc1": _paper.dense(k1, net["image"] ** 2, net["hidden"]),
            "fc2": _paper.dense(k2, net["hidden"], net["classes"])}


def init(key, cfg: dict) -> dict:
    """Initial weights, on the device."""
    return _init(key, _paper.net_items(cfg))


def apply(params: dict, images):
    """images (B, 28, 28) in [0, 1] -> logits (B, classes)."""
    return _paper.fc_head(params, images.reshape(images.shape[0], -1))


loss, accuracy = _paper.losses(apply)


def forward_flops(cfg: dict) -> int:
    """2 FLOPs per multiply-add of both dense layers (biases and ReLU
    left out): 317,600 for the paper MLP."""
    return _paper.fc_counts(cfg["net"]["image"] ** 2, cfg["net"])[0]


def train_flops(cfg: dict) -> float:
    """3 forward passes a sample: the forward, and the backward pass's
    two products."""
    return 3.0 * forward_flops(cfg)


def uploaded_params(cfg: dict) -> int:
    """Every weight and bias: 159,010 for the paper MLP."""
    return _paper.fc_counts(cfg["net"]["image"] ** 2, cfg["net"])[1]
