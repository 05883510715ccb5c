"""FedAvg weighted aggregation kernel (Alg. 1 line 12 hot loop).

``out[p] = sum_k w[k] * updates[k, p]`` over K stacked client updates —
a memory-bound weighted reduction executed every round on every parameter
buffer.  TPU mapping: grid over parameter-dim tiles; each program loads a
(K, BLOCK_P) VMEM tile of the stacked updates and the (K,) weight vector,
reduces over K in f32 on the VPU, writes a (BLOCK_P,) tile.

VMEM budget: K <= 256 clients x BLOCK_P=2048 x 4 B = 2 MB per tile (plus
double buffering) — comfortably inside the ~16 MB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_P = 2048


def _fedavg_kernel(updates_ref, weights_ref, out_ref):
    u = updates_ref[...].astype(jnp.float32)          # (K, BP)
    w = weights_ref[...].astype(jnp.float32)          # (K, 1)
    out_ref[...] = jnp.sum(u * w, axis=0).astype(out_ref.dtype)


def fedavg_agg_kernel(updates: jax.Array, weights: jax.Array,
                      block_p: int = DEFAULT_BLOCK_P, *,
                      interpret: bool) -> jax.Array:
    """updates: (K, P) with P % block_p == 0; weights: (K,) -> (P,)."""
    k, p = updates.shape
    grid = (p // block_p,)
    return pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p,), updates.dtype),
        interpret=interpret,
    )(updates, weights[:, None])


def _fedavg_stale_kernel(updates_ref, weights_ref, mask_ref, stale_ref,
                         out_ref):
    u = updates_ref[...].astype(jnp.float32)          # (K, BP)
    w = weights_ref[...].astype(jnp.float32)          # (K, 1)
    m = mask_ref[...].astype(jnp.float32)             # (K, 1)
    s = stale_ref[...].astype(jnp.float32)            # (K, 1)
    out_ref[...] = jnp.sum(u * (w * m * s), axis=0).astype(out_ref.dtype)


def fedavg_agg_stale_kernel(updates: jax.Array, weights: jax.Array,
                            mask: jax.Array, stale_w: jax.Array,
                            block_p: int = DEFAULT_BLOCK_P, *,
                            interpret: bool) -> jax.Array:
    """Staleness-weighted masked FedAvg reduction (event subsystem,
    DESIGN.md §12).

    ``out[p] = sum_k w[k] * m[k] * s[k] * updates[k, p]`` — the masked
    reduction with a per-update staleness multiplier ``s`` fused into
    the weight load.  The buffered aggregator's flush discounts each
    arrived update by its model-version staleness ``(1 + tau)^-gamma``;
    at ``gamma = 0`` the multiplier row is exactly 1.0 and the kernel is
    bitwise :func:`fedavg_agg_masked_kernel` (the synchronous-limit
    parity contract).  No internal renormalization — callers fold the
    staleness discount into the normalizer themselves.  Same grid/VMEM
    mapping as the masked kernel; the third (K, 1) tile is noise
    against the (K, BLOCK_P) update tile.
    """
    k, p = updates.shape
    grid = (p // block_p,)
    return pl.pallas_call(
        _fedavg_stale_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p,), updates.dtype),
        interpret=interpret,
    )(updates, weights[:, None], mask[:, None], stale_w[:, None])


def _fedavg_masked_kernel(updates_ref, weights_ref, mask_ref, out_ref):
    u = updates_ref[...].astype(jnp.float32)          # (K, BP)
    w = weights_ref[...].astype(jnp.float32)          # (K, 1)
    m = mask_ref[...].astype(jnp.float32)             # (K, 1)
    out_ref[...] = jnp.sum(u * (w * m), axis=0).astype(out_ref.dtype)


def fedavg_agg_masked_kernel(updates: jax.Array, weights: jax.Array,
                             mask: jax.Array,
                             block_p: int = DEFAULT_BLOCK_P, *,
                             interpret: bool) -> jax.Array:
    """Failure-masked FedAvg reduction (fault subsystem, DESIGN.md §10).

    ``out[p] = sum_k w[k] * m[k] * updates[k, p]`` — the unmasked
    reduction with a success mask fused into the weight load.  The
    kernel does NOT renormalize over the mask: callers own the weight
    normalization, which is what makes an all-ones mask bitwise equal
    to :func:`fedavg_agg_kernel` (``w * 1.0 == w`` exactly in f32 —
    the property ``tests/test_faults.py`` pins).  Same grid/VMEM
    mapping as the unmasked kernel; the extra (K, 1) mask tile is
    noise against the (K, BLOCK_P) update tile.
    """
    k, p = updates.shape
    grid = (p // block_p,)
    return pl.pallas_call(
        _fedavg_masked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((p,), updates.dtype),
        interpret=interpret,
    )(updates, weights[:, None], mask[:, None])
