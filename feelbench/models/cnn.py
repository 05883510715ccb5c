"""The paper's CNN (§VI-A.2) on the MNIST-shaped stand-in: conv 5x5
(10 channels) -> ReLU -> 2x2 max pool -> conv 5x5 (20 channels) ->
ReLU -> 2x2 max pool -> FC 50 -> ReLU -> FC 10.  What it shares with
the MLP is in ``_paper.py``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from feelbench.models import _paper
from feelbench.models._paper import (  # noqa: F401
    classes, cut_for_cpu, data, engine_args, inputs, reference_block)


def _convs(net: dict):
    """(c_in, c_out, kernel, output side) of each convolution, and the
    flat width after the last pool."""
    s, c_in, out = net["image"], 1, []
    for c_out, hw in (net["conv1"], net["conv2"]):
        out.append((c_in, c_out, hw, s - hw + 1))
        s, c_in = (s - hw + 1) // 2, c_out
    return out, c_in * s * s


def _conv(key, c_in: int, c_out: int, hw: int) -> dict:
    return {"w": jax.random.normal(key, (c_out, c_in, hw, hw), jnp.float32)
            * jnp.sqrt(2.0 / (c_in * hw * hw)),
            "b": jnp.zeros((c_out,), jnp.float32)}


@functools.partial(jax.jit, static_argnames=("net_items",))
def _init(key, net_items: tuple) -> dict:
    net = dict(net_items)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    (c1, hw1), (c2, hw2) = net["conv1"], net["conv2"]
    return {"conv1": _conv(k1, 1, c1, hw1),
            "conv2": _conv(k2, c1, c2, hw2),
            "fc1": _paper.dense(k3, _convs(net)[1], net["hidden"]),
            "fc2": _paper.dense(k4, net["hidden"], net["classes"])}


def init(key, cfg: dict) -> dict:
    """Initial weights, on the device."""
    return _init(key, _paper.net_items(cfg))


def apply(params: dict, images):
    """images (B, 28, 28) in [0, 1] -> logits (B, classes)."""
    x = images[:, None]
    for name in ("conv1", "conv2"):
        p = params[name]
        x = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        x = jax.nn.relu(x + p["b"][None, :, None, None])
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                  (1, 1, 2, 2), "VALID")
    return _paper.fc_head(params, x.reshape(images.shape[0], -1))


loss, accuracy = _paper.losses(apply)


def forward_flops(cfg: dict) -> int:
    """2 FLOPs per multiply-add of every convolution and dense layer
    (biases, ReLU and pooling left out): 961,000 for the paper CNN."""
    convs, flat = _convs(cfg["net"])
    return (sum(2 * s * s * c_out * c_in * hw * hw
                for c_in, c_out, hw, s in convs)
            + _paper.fc_counts(flat, cfg["net"])[0])


def train_flops(cfg: dict) -> float:
    """3 forward passes a sample: the forward, and the backward pass's
    two products."""
    return 3.0 * forward_flops(cfg)


def uploaded_params(cfg: dict) -> int:
    """Every weight and bias: 21,840 for the paper CNN."""
    convs, flat = _convs(cfg["net"])
    return (sum(c_out * c_in * hw * hw + c_out for c_in, c_out, hw, _ in convs)
            + _paper.fc_counts(flat, cfg["net"])[1])
