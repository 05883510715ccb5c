"""Fused uplink-compression kernel (DESIGN.md §9).

One FEEL round's lossy uplink for every device in one launch: accumulate
the error-feedback residual onto the raw model updates (``v = u + r``),
compress each device's ``(P,)`` update row — stochastic b-bit
quantization or magnitude top-k sparsification — immediately dequantize
(the server aggregates *values*, so the decode is part of the round),
and advance the residual carry ``r' = selected ? v - c : r``.  The
un-fused path streams the ``(K, P)`` update matrix through HBM four
times (accumulate, row-max/threshold, quantize, residual); here each
scenario's block is loaded into VMEM once and every derived quantity
falls out of the same residency.

TPU mapping: grid over the scenario axis S (the vmapped FEEL driver's
lane); each program owns one scenario — ``(K, P)`` update / residual
blocks plus ``(K,)`` width and selection rows (quant additionally
streams a ``(K, P)`` noise block; topk takes a ``(K,)`` placeholder row
instead — it never reads noise, and a dead full block would cost real
VMEM traffic).  Whole ``(K, P)`` blocks do not fit a TPU's VMEM at the
paper's widths: for a v5e the compiler refuses the quant launch at
K = 100 with P = 21,840 (CNN; a 31 MB scoped allocation against the
16 MB scoped limit) and with P = 159,010 (MLP; 505 MB of the 128 MB
VMEM).  On a TPU that refusal is the error a caller of
``CompressionConfig(use_kernel=True)`` gets; the kernel runs only in
interpret mode, as the CPU oracle tests do.  A TPU launch needs a
P-blocked variant carrying the row max / threshold in SMEM across
P-tiles (ROADMAP A3).
The per-element work is VPU-only (abs/floor/compare), so the kernel is
bandwidth-bound and fusing removes the three extra round trips.

Quantization is *stochastically rounded*: the caller supplies the
uniform ``noise`` block (drawn with ``jax.random`` outside the launch),
so the kernel stays deterministic per input and bit-for-bit equal to
the pure-jnp oracle ``kernels/ref.py::compress_update`` — the same
pattern every kernel in this repo uses for its property tests.  Top-k
selects by a fixed-trip threshold bisection on ``count(|v| >= t)``
(monotone in ``t``) rather than a sort — sorts don't lower inside TPU
Pallas (see the Duchi projection note in DESIGN.md §6); float ties at
the threshold can keep marginally fewer/more than ``keep`` entries,
identically in kernel and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MODES = ("quant", "topk")
DEFAULT_THRESH_ITERS = 32


def _compress_update_kernel(u_ref, r_ref, w_ref, sel_ref, n_ref,
                            c_out, r_out, *, mode: str, keep: int,
                            thresh_iters: int):
    u = u_ref[0]                                    # (K, P)
    r = r_ref[0]                                    # (K, P)
    widths = w_ref[0]                               # (K,)
    sel = sel_ref[0]                                # (K,)
    v = u + r                                       # residual accumulate
    av = jnp.abs(v)
    if mode == "quant":
        noise = n_ref[0]                            # (K, P)
        m = jnp.max(av, axis=-1, keepdims=True)     # per-device scale
        levels = jnp.maximum(jnp.exp2(widths[:, None]) - 1.0, 1.0)
        scaled = av / jnp.maximum(m, 1e-12) * levels
        fl = jnp.floor(scaled)
        q = fl + (noise < (scaled - fl)).astype(jnp.float32)
        c = jnp.sign(v) * q / levels * m
    else:                                           # topk
        lo = jnp.zeros(av.shape[:-1] + (1,), jnp.float32)
        hi = jnp.max(av, axis=-1, keepdims=True)

        def body(_, lohi):
            tlo, thi = lohi
            mid = 0.5 * (tlo + thi)
            cnt = jnp.sum((av >= mid).astype(jnp.float32), axis=-1,
                          keepdims=True)
            over = cnt > keep
            return jnp.where(over, mid, tlo), jnp.where(over, thi, mid)

        lo, hi = jax.lax.fori_loop(0, thresh_iters, body, (lo, hi))
        c = jnp.where(av >= hi, v, 0.0)
    c_out[...] = c[None]
    r_out[...] = jnp.where(sel[:, None] > 0.0, v - c, r)[None]


def compress_update_kernel(updates: jax.Array, residual: jax.Array,
                           widths: jax.Array, selected: jax.Array,
                           noise: jax.Array, *, mode: str, keep: int = 0,
                           thresh_iters: int = DEFAULT_THRESH_ITERS,
                           interpret: bool
                           ) -> tuple[jax.Array, jax.Array]:
    """Batched fused compress: ``(S, K, P)`` updates/residual/noise +
    ``(S, K)`` widths/selection -> ``((S, K, P) decoded values,
    (S, K, P) new residual)``.  ``mode`` picks stochastic ``widths``-bit
    quantization or magnitude top-``keep`` sparsification.  See
    ``kernels/ref.py::compress_update`` for the exact contract."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    s, k, p = updates.shape
    for name, a, want in (("residual", residual, (s, k, p)),
                          ("widths", widths, (s, k)),
                          ("selected", selected, (s, k))):
        if a.shape != want:
            raise ValueError(f"{name} must be {want}, got {a.shape}")
    # quant consumes per-coordinate noise; topk never reads it, so a
    # (S, K) placeholder row avoids streaming a dead (K, P) block into
    # the launch (a full block is still accepted for oracle sweeps).
    if mode == "quant" and noise.shape != (s, k, p):
        raise ValueError(f"noise must be {(s, k, p)}, got {noise.shape}")
    if noise.shape not in ((s, k, p), (s, k)):
        raise ValueError(f"noise must be {(s, k, p)} or {(s, k)}, got "
                         f"{noise.shape}")
    if mode == "topk" and not (0 < keep <= p):
        raise ValueError(f"topk keep must be in (0, {p}], got {keep}")
    kern = functools.partial(_compress_update_kernel, mode=mode,
                             keep=keep, thresh_iters=thresh_iters)
    mat = pl.BlockSpec((1, k, p), lambda i: (i, 0, 0))
    row = pl.BlockSpec((1, k), lambda i: (i, 0))
    noise_spec = mat if noise.ndim == 3 else row
    return pl.pallas_call(
        kern,
        grid=(s,),
        in_specs=[mat, mat, row, row, noise_spec],
        out_specs=[mat, mat],
        out_shape=[jax.ShapeDtypeStruct((s, k, p), jnp.float32),
                   jax.ShapeDtypeStruct((s, k, p), jnp.float32)],
        interpret=interpret,
    )(updates, residual, widths, selected, noise)
