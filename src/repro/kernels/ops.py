"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, layout (B, S, H, hd) <-> kernel
(BH, S, hd), GQA head expansion, and the interpret-mode switch.

This module is the one place that decides interpret mode: every wrapper
defaults ``interpret=None``, which compiles the kernel when the default
backend is a TPU and interprets it everywhere else (the CPU tests).  The
raw kernel entries under ``repro.kernels`` take ``interpret`` as a
required keyword, so no caller runs the interpreter on a TPU by default.
A caller that pins work to the host CPU on a TPU machine
(``jax.default_device``) still sees ``default_backend() == "tpu"`` here,
so it keeps the kernel lanes off rather than relying on this switch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compress as _compress
from repro.kernels import diversity as _div
from repro.kernels import fedavg_agg as _agg
from repro.kernels import flash_attention as _fa
from repro.kernels import stream_update as _stream
from repro.kernels import sub2_pgd as _pgd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def fedavg_agg(updates: jax.Array, weights: jax.Array,
               block_p: int = _agg.DEFAULT_BLOCK_P,
               interpret: bool | None = None) -> jax.Array:
    """FedAvg weighted aggregation: (K, P) x (K,) -> (P,)."""
    interpret = _default_interpret() if interpret is None else interpret
    k, p = updates.shape
    bp = min(block_p, max(128, 1 << (p - 1).bit_length()))
    padded, pad = _pad_to(updates, 1, bp)
    out = _agg.fedavg_agg_kernel(padded, weights, block_p=bp,
                                 interpret=interpret)
    return out[:p] if pad else out


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def fedavg_agg_masked(updates: jax.Array, weights: jax.Array,
                      mask: jax.Array,
                      block_p: int = _agg.DEFAULT_BLOCK_P,
                      interpret: bool | None = None) -> jax.Array:
    """Success-masked FedAvg aggregation: (K, P) x (K,) x (K,) -> (P,).

    The fault subsystem's degraded-aggregation lane (DESIGN.md §10):
    same padding/tiling as :func:`fedavg_agg`, with the upload-success
    mask folded into the weights inside the kernel.  No internal
    renormalization — an all-ones mask is bitwise the unmasked kernel.
    """
    interpret = _default_interpret() if interpret is None else interpret
    k, p = updates.shape
    bp = min(block_p, max(128, 1 << (p - 1).bit_length()))
    padded, pad = _pad_to(updates, 1, bp)
    out = _agg.fedavg_agg_masked_kernel(padded, weights, mask, block_p=bp,
                                        interpret=interpret)
    return out[:p] if pad else out


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def fedavg_agg_stale(updates: jax.Array, weights: jax.Array,
                     mask: jax.Array, stale_w: jax.Array,
                     block_p: int = _agg.DEFAULT_BLOCK_P,
                     interpret: bool | None = None) -> jax.Array:
    """Staleness-weighted masked FedAvg: (K, P) x (K,) x3 -> (P,).

    The event subsystem's buffered-flush lane (DESIGN.md §12): the
    masked aggregation with each update additionally discounted by its
    model-version staleness multiplier ``stale_w``.  Same padding and
    tiling as :func:`fedavg_agg_masked`; an all-ones ``stale_w`` is
    bitwise the masked kernel (synchronous-limit contract).
    """
    interpret = _default_interpret() if interpret is None else interpret
    k, p = updates.shape
    bp = min(block_p, max(128, 1 << (p - 1).bit_length()))
    padded, pad = _pad_to(updates, 1, bp)
    out = _agg.fedavg_agg_stale_kernel(padded, weights, mask, stale_w,
                                       block_p=bp, interpret=interpret)
    return out[:p] if pad else out


# Test/observability hook: counts how many times the batched-lane vmap
# rule below was traced.  A vmap of the single-instance `sub2_pgd` entry
# (the batched FEEL driver) is wired straight onto the kernel's (S, K)
# grid through jax.custom_batching — this counter is how tests assert
# the direct lane, not Pallas's generic batching rule, handled the map.
BATCHED_LANE_TRACES = 0


@functools.lru_cache(maxsize=32)
def _sub2_pgd_entry(rho: float, lr: float, tau: float, iters: int,
                    bandwidth_hz: float, min_alpha: float,
                    proj_iters: int, interpret: bool):
    """Single-instance kernel entry with a custom vmap rule.

    The plain path launches the kernel with a length-1 grid.  Under
    ``jax.vmap`` (one level — the scenario axis of
    ``federated.run_federated_batch``), the custom rule broadcasts any
    unbatched operands and launches the batched ``(S, K)`` grid
    directly, so the scenario axis maps 1:1 onto kernel grid steps
    instead of being reconstructed by the generic pallas batching rule.
    Cached per static-parameter tuple so repeat solves reuse one
    custom-vmap object (and jax's trace cache).  Payload bits ride as a
    ``(K,)`` operand row (not a static), so per-device compressed
    payloads keep this fused lane.
    """
    kern = functools.partial(
        _pgd.sub2_pgd_kernel, rho=rho, lr=lr, tau=tau, iters=iters,
        bandwidth_hz=bandwidth_hz, min_alpha=min_alpha,
        proj_iters=proj_iters, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def single(selected, t_train, c, tx_power, bits, alpha0):
        alpha, obj = kern(selected[None], t_train[None], c[None],
                          tx_power[None], bits[None], alpha0[None])
        return alpha[0], obj[0]

    @single.def_vmap
    def _batched_lane(axis_size, in_batched, selected, t_train, c,
                      tx_power, bits, alpha0):
        global BATCHED_LANE_TRACES
        BATCHED_LANE_TRACES += 1
        args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, b in zip((selected, t_train, c, tx_power, bits,
                                 alpha0), in_batched)]
        alpha, obj = kern(*args)
        return (alpha, obj), (True, True)

    return single


def sub2_pgd(selected: jax.Array, t_train: jax.Array, gains: jax.Array,
             tx_power: jax.Array, alpha0: jax.Array, *, rho: float,
             lr: float, tau: float, iters: int, bandwidth_hz: float,
             noise_psd: float, model_bits, min_alpha: float,
             proj_iters: int = _pgd.DEFAULT_PROJ_ITERS,
             interpret: bool | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Fused Sub2 PGD solve: whole descent in one kernel launch.

    Single instance: ``selected``/``t_train``/``gains``/``tx_power`` of
    (K,) with ``alpha0`` (2, K) -> ((K,) alpha, () objective).  Batched
    scenario lane: (S, K) rows with ``alpha0`` (S, 2, K) -> ((S, K),
    (S,)).  ``alpha0`` stacks the two starting points (water-filling, uniform); gains/power fold into the SNR coefficient
    c = g*P/(B*N0) here so the kernel sees one coefficient row.

    ``model_bits`` may be a Python/0-d scalar (nominal model size) or a
    per-device ``(K,)`` / ``(S, K)`` payload-bits array (compressed
    uplinks, DESIGN.md §9) — either way it is materialized to a bits
    row and fed to the kernel as an operand, so the fused lane survives
    per-device payloads.

    The single-instance entry carries a custom vmap rule: a ``vmap``
    over it (the batched FEEL driver) launches the (S, K) kernel grid
    directly (see :func:`_sub2_pgd_entry`).
    """
    interpret = _default_interpret() if interpret is None else interpret
    c = gains * tx_power / (bandwidth_hz * noise_psd)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    bits = jnp.broadcast_to(f32(model_bits), selected.shape)
    args = (f32(selected), f32(t_train), f32(c), f32(tx_power), bits,
            f32(alpha0))
    if selected.ndim == 2:
        return _pgd.sub2_pgd_kernel(
            *args, rho=rho, lr=lr, tau=tau, iters=iters,
            bandwidth_hz=bandwidth_hz, min_alpha=min_alpha,
            proj_iters=proj_iters, interpret=interpret)
    entry = _sub2_pgd_entry(rho, lr, tau, iters, bandwidth_hz,
                            min_alpha, proj_iters, interpret)
    return entry(*args)


# Observability hook mirroring BATCHED_LANE_TRACES: counts traces of the
# compress kernel's direct batched-vmap lane (tests assert the scenario
# vmap hit the (S,)-grid launch, not pallas's generic batching rule).
COMPRESS_LANE_TRACES = 0


@functools.lru_cache(maxsize=32)
def _compress_entry(mode: str, keep: int, thresh_iters: int,
                    interpret: bool):
    """Single-instance compress entry with a custom vmap rule.

    The plain path launches the kernel with a length-1 grid.  Under
    ``jax.vmap`` (the scenario axis of ``federated.run_federated_batch``)
    the custom rule broadcasts any unbatched operands and launches the
    batched ``(S,)`` grid directly — same pattern as
    :func:`_sub2_pgd_entry`.
    """
    kern = functools.partial(_compress.compress_update_kernel, mode=mode,
                             keep=keep, thresh_iters=thresh_iters,
                             interpret=interpret)

    @jax.custom_batching.custom_vmap
    def single(updates, residual, widths, selected, noise):
        c, r = kern(updates[None], residual[None], widths[None],
                    selected[None], noise[None])
        return c[0], r[0]

    @single.def_vmap
    def _batched_lane(axis_size, in_batched, updates, residual, widths,
                      selected, noise):
        global COMPRESS_LANE_TRACES
        COMPRESS_LANE_TRACES += 1
        args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, b in zip((updates, residual, widths, selected,
                                 noise), in_batched)]
        c, r = kern(*args)
        return (c, r), (True, True)

    return single


def compress_update(updates: jax.Array, residual: jax.Array,
                    widths: jax.Array, selected: jax.Array,
                    noise: jax.Array, *, mode: str, keep: int = 0,
                    thresh_iters: int = _compress.DEFAULT_THRESH_ITERS,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Fused uplink compression: residual accumulate -> quantize/top-k
    -> dequantize-for-FedAvg in one launch.

    Single instance: ``(K, P)`` updates/residual/noise + ``(K,)``
    widths/selection -> ``((K, P) decoded, (K, P) residual)``.  Batched
    scenario lane: ``(S, K, P)`` / ``(S, K)`` — the grid runs over S.
    The single-instance entry carries a custom vmap rule so the vmapped
    FEEL driver lands on the batched grid directly
    (:func:`_compress_entry`).  Exact contract in
    ``kernels/ref.py::compress_update``.  Not jitted here: the caller
    is the FEEL round body, which is already tracing.
    """
    interpret = _default_interpret() if interpret is None else interpret
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    args = (f32(updates), f32(residual), f32(widths), f32(selected),
            f32(noise))
    if updates.ndim == 3:
        return _compress.compress_update_kernel(
            *args, mode=mode, keep=keep, thresh_iters=thresh_iters,
            interpret=interpret)
    entry = _compress_entry(mode, keep, thresh_iters, interpret)
    return entry(*args)


@functools.partial(jax.jit, static_argnames=("num_classes", "interpret"))
def diversity_stats(labels: jax.Array, mask: jax.Array, num_classes: int,
                    interpret: bool | None = None) -> jax.Array:
    """(K, N) labels/mask -> (K, 3) [gini-simpson, shannon, count]."""
    interpret = _default_interpret() if interpret is None else interpret
    return _div.diversity_kernel(labels, mask, num_classes,
                                 interpret=interpret)


def stream_update(hists: jax.Array, deltas: jax.Array,
                  arrivals: jax.Array, staleness: jax.Array,
                  selected: jax.Array, *,
                  decay: float, size_cap: float = 0.0,
                  interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused streaming refresh (one round of in-scan data evolution).

    Count-delta accumulation -> Gini/Shannon/size refresh -> staleness
    decay in one launch (``kernels/stream_update.py``; exact contract in
    ``kernels/ref.py::stream_update``).  Single instance: ``(K, C)``
    counts/deltas + ``(K,)`` arrivals/staleness/selection.  Batched
    scenario lane: ``(S, K, C)`` / ``(S, K)`` — the grid runs over S.
    Not jitted here: the caller is the FEEL round body, which is
    already tracing.
    """
    interpret = _default_interpret() if interpret is None else interpret
    batched = hists.ndim == 3
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    hists, deltas, arrivals, staleness, selected = (
        f32(hists), f32(deltas), f32(arrivals), f32(staleness),
        f32(selected))
    if not batched:
        hists, deltas, arrivals, staleness, selected = (
            x[None] for x in (hists, deltas, arrivals, staleness,
                              selected))
    h, stats, stale = _stream.stream_update_kernel(
        hists, deltas, arrivals, staleness, selected, decay=decay,
        size_cap=size_cap, interpret=interpret)
    if not batched:
        return h[0], stats[0], stale[0]
    return h, stats, stale


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Batched GQA flash attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) -> (B, Sq, H, hd).
    Sequences are zero-padded to block multiples; the causal mask plus the
    `k_pos < seq_len` guard inside the kernel keeps padding inert.
    """
    interpret = _default_interpret() if interpret is None else interpret
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, v.shape[1], hd)
    bq = min(block_q, sq)
    bk = min(block_k, kf.shape[1])
    kv_len = kf.shape[1]
    qf, qpad = _pad_to(qf, 1, bq)
    kf, _ = _pad_to(kf, 1, bk)
    vf, _ = _pad_to(vf, 1, bk)
    out = _fa.flash_attention_kernel(qf, kf, vf, causal=causal,
                                     window=window, block_q=bq, block_k=bk,
                                     kv_len=kv_len, interpret=interpret)
    out = out[:, :sq]
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
