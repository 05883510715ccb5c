"""A toy model family that lives only in the tests: Gaussian blobs.

``test_bench_family.py`` copies this file into a copy of the harness as
``feelbench/models/<name>.py`` and runs a cell of it there, to show that
a model family is new files and nothing else.  It differs from the
paper nets where the harness used to assume them: its samples are
vectors of ``features`` u8 values, not 28 x 28 images; it has its own
class count; and its reference trains the devices in blocks of
``reference_block`` (fewer than K), so the reference's FedAvg sums
block by block.

Data: per class a centre drawn in [0.2, 0.8]^features, a sample the
centre plus Gaussian noise, clipped to [0, 1] and stored as u8; then the
paper's shard protocol (``feelbench/models/_mnist.py``).  Model: one
ReLU hidden layer and a linear read-out.  The program's loss and eval
(``engine_args``) are written apart from the reference's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from feelbench.models import _mnist

NOISE = 0.08


def data(seed: int, cfg: dict) -> dict:
    d, net = cfg["data"], cfg["net"]
    rng = np.random.default_rng(seed)
    n, c = d["samples_per_class"], net["classes"]
    centres = rng.uniform(0.2, 0.8, (c, net["features"]))
    x = centres[:, None] + NOISE * rng.standard_normal(
        (c, n, net["features"]))
    images = (np.clip(x, 0.0, 1.0) * 255.0).astype(np.uint8)
    labels = np.repeat(np.arange(c, dtype=np.int32), n)
    return _mnist.partition(
        images.reshape(c * n, -1), labels, seed + 1,
        num_devices=cfg["devices"], num_shards=d["num_shards"],
        shard_size=d["shard_size"], min_shards=d["min_shards"],
        max_shards=d["max_shards"], test_fraction=d["test_fraction"],
        counts_seed=d["counts_seed"])


def classes(cfg: dict) -> int:
    return cfg["net"]["classes"]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key, features: int, hidden: int, classes: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"hidden": {"w": jax.random.normal(k1, (features, hidden))
                       * jnp.sqrt(2.0 / features),
                       "b": jnp.zeros((hidden,))},
            "out": {"w": jax.random.normal(k2, (hidden, classes))
                    * jnp.sqrt(1.0 / hidden),
                    "b": jnp.zeros((classes,))}}


def init(key, cfg: dict) -> dict:
    net = cfg["net"]
    return _init(key, net["features"], net["hidden"], net["classes"])


def _program_logits(params, x):
    h = jnp.maximum(jnp.dot(x, params["hidden"]["w"])
                    + params["hidden"]["b"], 0.0)
    return jnp.dot(h, params["out"]["w"]) + params["out"]["b"]


def _program_loss(params, x, labels, mask):
    logits = _program_logits(params, x)
    onehot = jax.nn.one_hot(labels, logits.shape[-1])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.sum(onehot * logits, -1)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _program_accuracy(params, x, labels):
    pred = jnp.argmax(_program_logits(params, x), axis=-1)
    return jnp.mean((pred == labels).astype(jnp.float32))


def engine_args(cfg: dict) -> dict:
    return {"loss_fn": _program_loss, "eval_fn": _program_accuracy}


def inputs(rows, dt):
    return (rows.astype(jnp.float32) / 255.0).astype(dt)


def _apply(params, x):
    h = jax.nn.relu(x @ params["hidden"]["w"] + params["hidden"]["b"])
    return h @ params["out"]["w"] + params["out"]["b"]


def loss(params, x, labels, mask, cfg):
    logp = jax.nn.log_softmax(_apply(params, x).astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def accuracy(params, x, labels, cfg):
    return jnp.mean((jnp.argmax(_apply(params, x), axis=-1) == labels)
                    .astype(jnp.float32))


def reference_block(cfg: dict) -> int:
    return cfg["net"]["reference_block"]


def forward_flops(cfg: dict) -> int:
    net = cfg["net"]
    return 2 * (net["features"] * net["hidden"]
                + net["hidden"] * net["classes"])


def train_flops(cfg: dict) -> float:
    return 3.0 * forward_flops(cfg)


def uploaded_params(cfg: dict) -> int:
    net = cfg["net"]
    return ((net["features"] + 1) * net["hidden"]
            + (net["hidden"] + 1) * net["classes"])


def cut_for_cpu(cfg: dict) -> None:
    cfg["data"].update(samples_per_class=50, num_shards=20)
