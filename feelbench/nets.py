"""The paper's two nets, written plainly for the benchmark (§VI-A.2).

- CNN: conv 5x5 (10 channels) -> ReLU -> 2x2 max pool -> conv 5x5
  (20 channels) -> ReLU -> 2x2 max pool -> FC 50 -> ReLU -> FC 10.
- MLP: FC 200 -> ReLU -> FC 10.

``init`` makes the initial weights the benchmark hands to the program
and to the reference alike: He-normal weights, zero biases, in one
jitted call from a key.  The pytree layout (``conv1``/``conv2``/``fc1``/
``fc2``, each ``{"w", "b"}``) is the one the program's nets consume.

``apply``, ``loss`` and ``accuracy`` are the reference's forward pass:
nothing here calls the program.  They compute in the dtype of their
inputs; the caller sets the matmul precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dense(key, n_in: int, n_out: int) -> dict:
    return {"w": jax.random.normal(key, (n_in, n_out), jnp.float32)
            * jnp.sqrt(2.0 / n_in),
            "b": jnp.zeros((n_out,), jnp.float32)}


def _conv(key, c_in: int, c_out: int, hw: int) -> dict:
    return {"w": jax.random.normal(key, (c_out, c_in, hw, hw), jnp.float32)
            * jnp.sqrt(2.0 / (c_in * hw * hw)),
            "b": jnp.zeros((c_out,), jnp.float32)}


def _cnn_flat(net: dict) -> int:
    s = net["image"]
    for _, hw in (net["conv1"], net["conv2"]):
        s = (s - hw + 1) // 2
    return net["conv2"][0] * s * s


@functools.partial(jax.jit, static_argnames=("model", "net_items"))
def _init(key, model: str, net_items: tuple) -> dict:
    net = dict(net_items)
    if model == "cnn":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        c1, hw1 = net["conv1"]
        c2, hw2 = net["conv2"]
        return {"conv1": _conv(k1, 1, c1, hw1),
                "conv2": _conv(k2, c1, c2, hw2),
                "fc1": _dense(k3, _cnn_flat(net), net["hidden"]),
                "fc2": _dense(k4, net["hidden"], net["classes"])}
    k1, k2 = jax.random.split(key)
    return {"fc1": _dense(k1, net["image"] ** 2, net["hidden"]),
            "fc2": _dense(k2, net["hidden"], net["classes"])}


def init(key, cfg: dict) -> dict:
    """Initial weights of the configuration's net, on the device."""
    items = tuple((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in sorted(cfg["net"].items()))
    return _init(key, cfg["model"], items)


def apply(params: dict, images, model: str):
    """images (B, 28, 28) in [0, 1] -> logits (B, classes)."""
    b = images.shape[0]
    if model == "cnn":
        x = images[:, None]
        for name in ("conv1", "conv2"):
            p = params[name]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "VALID",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            x = jax.nn.relu(x + p["b"][None, :, None, None])
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                      (1, 1, 2, 2), "VALID")
        x = x.reshape(b, -1)
    else:
        x = images.reshape(b, -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


def loss(params: dict, images, labels, mask, model: str):
    """Mean cross-entropy over the valid rows of a padded batch."""
    logits = apply(params, images, model).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def accuracy(params: dict, images, labels, model: str):
    logits = apply(params, images, model)
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))
