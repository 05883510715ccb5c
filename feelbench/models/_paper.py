"""What the paper's two nets (§VI-A.2), the ``cnn`` and ``mlp`` model
families, share (``feelbench/models/__init__.py`` lists what a family
gives).  Each family module holds its own net: weights, forward pass
and counts.  Its sizes are the configuration's ``"net"``.

The data is the MNIST-shaped stand-in of ``_mnist.py``, u8 images that
the program and the reference both read as ``x / 255``.  The initial
weights are He-normal with zero biases, made in one jitted call from a
key; the pytree layout (``conv1``/``conv2``/``fc1``/``fc2``, each
``{"w", "b"}``) is the one the program's nets consume.  The program's
own functions, ``repro.models.paper_nets``, are what ``engine_args``
hands the engine.

A family's ``apply``, and the ``loss`` and ``accuracy`` that
:func:`losses` builds over it, are the reference's forward pass:
nothing here calls the program.  They compute in the dtype of their
inputs; the caller sets the matmul precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from feelbench.models import _mnist


def dense(key, n_in: int, n_out: int) -> dict:
    return {"w": jax.random.normal(key, (n_in, n_out), jnp.float32)
            * jnp.sqrt(2.0 / n_in),
            "b": jnp.zeros((n_out,), jnp.float32)}


def net_items(cfg: dict) -> tuple:
    """The configuration's ``"net"`` as a hashable static argument."""
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(cfg["net"].items()))


def fc_head(params: dict, x):
    """FC hidden -> ReLU -> FC classes, on flat rows."""
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


def losses(apply):
    """The reference's ``loss`` and ``accuracy`` over ``apply(params,
    images)``, images (B, 28, 28) in [0, 1] -> logits (B, classes)."""

    def loss(params: dict, images, labels, mask, cfg: dict):
        """Mean cross-entropy over the valid rows of a padded batch."""
        logits = apply(params, images).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def accuracy(params: dict, images, labels, cfg: dict):
        logits = apply(params, images)
        return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                        .astype(jnp.float32))

    return loss, accuracy


def inputs(rows, dt):
    """u8 pixels -> [0, 1] in ``dt``, as the program reads them."""
    return (rows.astype(jnp.float32) / 255.0).astype(dt)


def data(seed: int, cfg: dict) -> dict:
    return _mnist.make(seed, cfg)


def classes(cfg: dict) -> int:
    return cfg["net"]["classes"]


def engine_args(cfg: dict) -> dict:
    """The program's loss and eval for the configuration's net."""
    from repro.models import paper_nets
    net = cfg["net"]
    spec = paper_nets.PaperNetSpec(
        kind=cfg["model"], image_size=net["image"],
        num_classes=net["classes"], mlp_hidden=net["hidden"],
        cnn_hidden=net["hidden"])
    return {"loss_fn": functools.partial(paper_nets.loss_fn, spec=spec),
            "eval_fn": functools.partial(paper_nets.accuracy, spec=spec)}


def reference_block(cfg: dict) -> int:
    """All K devices at once: the nets are small."""
    return cfg["devices"]


def fc_counts(flat: int, net: dict) -> tuple:
    """(forward FLOPs, parameters) of the dense head on ``flat`` inputs."""
    h, c = net["hidden"], net["classes"]
    return 2 * flat * h + 2 * h * c, flat * h + h + h * c + c


def cut_for_cpu(cfg: dict) -> None:
    """Ten classes of 100 images, cut into 40 shards of 25."""
    cfg["data"].update(samples_per_class=100, num_shards=40, shard_size=25,
                       max_shards=6)
