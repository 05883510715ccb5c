"""Flash attention (forward) Pallas TPU kernel: causal + sliding window.

Online-softmax blocked attention (Dao et al.), adapted to the TPU memory
hierarchy: the kv loop is the innermost *grid* dimension (TPU grids
execute sequentially per core, so VMEM scratch carries the running
(m, l, acc) statistics across kv steps); q/k/v tiles stream HBM->VMEM via
BlockSpecs sized to the MXU (block_q x head_dim and block_k x head_dim,
multiples of 128).

Grid: (batch * q_heads, num_q_blocks, num_kv_blocks).  GQA is handled in
the index maps: q head ``h`` reads kv head ``h // group_size``.  Causal /
sliding-window masking is applied inside the block; fully-masked blocks
are skipped with ``pl.when`` (they still occupy grid steps — the TPU
cost is the skipped DMA, which XLA elides per-block).

The pure-jnp oracle lives in ``ref.py``; ``ops.py`` wraps the kernel with
padding + (B, S, H, hd) layout handling.  Validated with interpret=True
(CPU) across shape/dtype sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_len: int,
                  causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    visible = k_pos < seq_len
    if causal:
        visible &= k_pos <= q_pos
    if window > 0:
        visible &= k_pos > q_pos - window

    # Whole-block skip: any work in this (q, kv) block?
    q_lo = qi * block_q
    k_lo = ki * block_k
    block_live = jnp.bool_(True)
    if causal:
        block_live = jnp.logical_and(block_live,
                                     k_lo <= (q_lo + block_q - 1))
    if window > 0:
        block_live = jnp.logical_and(
            block_live, (k_lo + block_k - 1) > (q_lo - window))

    @pl.when(block_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = q @ k.T                                       # (bq, bk)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_scr[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           kv_len: int | None = None,
                           interpret: bool) -> jax.Array:
    """q: (BH, Sq, hd); k, v: (BH, Skv, hd) — flattened batch*head rows.

    Sq/Skv must be multiples of the block sizes (ops.py pads); ``kv_len``
    is the true (pre-padding) KV length used for the validity mask.
    Returns (BH, Sq, hd) in q.dtype.
    """
    bh, sq, hd = q.shape
    skv = k.shape[1]
    grid = (bh, sq // block_q, skv // block_k)
    scale = hd ** -0.5
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=kv_len if kv_len is not None else skv, causal=causal,
        window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
