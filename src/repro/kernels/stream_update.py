"""Fused streaming-data refresh kernel (DESIGN.md §7).

One FEEL round's data evolution for every device in one launch: apply
the round's count deltas to the ``(K, C)`` class-count matrix (clamped
at zero — evictions are negative deltas), optionally rescale devices
that overflow their buffer cap, recompute both classification diversity
measures (Gini-Simpson, Shannon) plus the sample count, and advance the
staleness carry ``stale' = [selected ? 0 : decay * stale] + arrivals``.
The un-fused path reads the count matrix three times (accumulate,
normalize, entropy) through HBM; here each scenario's ``(K, C)`` block
is loaded into VMEM once and every derived statistic falls out of the
same residency.

TPU mapping: grid over the scenario axis S (the vmapped FEEL driver's
lane); each program owns one scenario — ``(K, C)`` count and delta
blocks plus ``(K,)`` staleness/selection rows.  At paper scale
(K = 100, C = 10) that is a few KB of VMEM; the per-element work is
VPU-only (multiply/accumulate plus one ``log2`` per class), so the
kernel is bandwidth-bound and fusing removes the two extra round trips.
Validated against the pure-jnp oracle ``kernels/ref.py::stream_update``
in interpret mode (CPU), like the diversity/fedavg/sub2 kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stream_update_kernel(h_ref, d_ref, arr_ref, stale_ref, sel_ref,
                          h_out, stats_out, stale_out, *,
                          decay: float, size_cap: float):
    h0 = h_ref[0]                                   # (K, C)
    d = d_ref[0]                                    # (K, C)
    arrivals = arr_ref[0, 0]                        # (K,)
    stale = stale_ref[0, 0]                         # (K,)
    sel = sel_ref[0, 0]                             # (K,)
    h = jnp.maximum(h0 + d, 0.0)
    if size_cap > 0.0:
        total = jnp.sum(h, axis=-1, keepdims=True)
        scale = jnp.where(total > size_cap,
                          size_cap / jnp.maximum(total, 1.0), 1.0)
        h = h * scale
    sizes = jnp.sum(h, axis=-1)
    p = h / jnp.maximum(sizes[:, None], 1.0)
    gini = 1.0 - jnp.sum(p * p, axis=-1)
    logp = jnp.where(p > 0.0, jnp.log2(jnp.maximum(p, 1e-30)), 0.0)
    shannon = -jnp.sum(p * logp, axis=-1)
    h_out[...] = h[None]
    stats_out[...] = jnp.stack([gini, shannon, sizes], axis=-1)[None]
    stale_out[...] = (jnp.where(sel > 0.0, 0.0, decay * stale)
                      + arrivals)[None, None]


def stream_update_kernel(hists: jax.Array, deltas: jax.Array,
                         arrivals: jax.Array, staleness: jax.Array,
                         selected: jax.Array, *,
                         decay: float, size_cap: float = 0.0,
                         interpret: bool
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched fused refresh: ``(S, K, C)`` counts/deltas + ``(S, K)``
    arrivals/staleness/selection -> ``((S, K, C) counts, (S, K, 3)
    stats, (S, K) staleness)``.  Stats pack ``[gini, shannon, size]``
    like the ``diversity`` kernel.  See
    ``kernels/ref.py::stream_update`` for the exact contract."""
    s, k, c = hists.shape
    if deltas.shape != (s, k, c):
        raise ValueError(f"deltas must be {(s, k, c)}, got {deltas.shape}")
    for name, a in (("arrivals", arrivals), ("staleness", staleness),
                    ("selected", selected)):
        if a.shape != (s, k):
            raise ValueError(f"{name} must be {(s, k)}, got {a.shape}")
    kern = functools.partial(_stream_update_kernel, decay=decay,
                             size_cap=size_cap)
    mat = pl.BlockSpec((1, k, c), lambda i: (i, 0, 0))
    # Rows travel as (S, 1, K): a TPU block's last two dims must be
    # (8, 128)-aligned or whole, and a (1, K) slice of (S, K) is neither.
    row = pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0))
    h, stats, stale = pl.pallas_call(
        kern,
        grid=(s,),
        in_specs=[mat, mat, row, row, row],
        out_specs=[mat, pl.BlockSpec((1, k, 3), lambda i: (i, 0, 0)), row],
        out_shape=[jax.ShapeDtypeStruct((s, k, c), jnp.float32),
                   jax.ShapeDtypeStruct((s, k, 3), jnp.float32),
                   jax.ShapeDtypeStruct((s, 1, k), jnp.float32)],
        interpret=interpret,
    )(hists, deltas, arrivals[:, None], staleness[:, None],
      selected[:, None])
    return h, stats, stale[:, 0]
