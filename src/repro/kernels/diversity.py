"""Fused label-histogram -> diversity-measures kernel (paper Eq. 2/3).

Per device (FL client), compute the class histogram of its (masked) label
vector and reduce it to the two classification diversity measures in one
pass: Gini-Simpson ``1 - sum p^2`` and Shannon entropy ``-sum p log2 p``.

TPU mapping: grid over clients; each program holds one client's (N,)
labels + mask in VMEM, builds the (C,) histogram via an iota-compare
matmul-free reduction (C <= 64 classes broadcast against the label row),
then emits ``(gini, shannon, total)``.  N tiles of 8k labels x 4 B = 32 KB
VMEM — tiny; the win is fusing histogram+entropy so labels are read once
from HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _diversity_kernel(labels_ref, mask_ref, out_ref, *, num_classes: int):
    labels = labels_ref[...]                       # (1, N) int32
    mask = mask_ref[...].astype(jnp.float32)       # (1, N)
    classes = jax.lax.broadcasted_iota(jnp.int32, (num_classes, 1), 0)
    onehot = (labels == classes).astype(jnp.float32)      # (C, N)
    hist = jnp.sum(onehot * mask, axis=1)                 # (C,)
    total = jnp.sum(hist)
    p = hist / jnp.maximum(total, 1.0)
    gini = 1.0 - jnp.sum(p * p)
    logp = jnp.where(p > 0.0, jnp.log2(jnp.maximum(p, 1e-30)), 0.0)
    shannon = -jnp.sum(p * logp)
    out_ref[...] = jnp.stack([gini, shannon, total])[None, :]


def diversity_kernel(labels: jax.Array, mask: jax.Array, num_classes: int, *,
                     interpret: bool) -> jax.Array:
    """labels/mask: (K, N) -> (K, 3) [gini, shannon, count]."""
    k, n = labels.shape
    return pl.pallas_call(
        functools.partial(_diversity_kernel, num_classes=num_classes),
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 3), jnp.float32),
        interpret=interpret,
    )(labels, mask)
