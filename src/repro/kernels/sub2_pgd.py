"""Fused Sub2 projected-gradient kernel (paper Eq. 15 inner solve).

One Pallas launch runs the *entire* PGD descent for a bandwidth
allocation instance: analytic gradient of the smoothed objective ->
tangent projection (mean removal on the simplex) -> normalized
cosine-decayed step -> Duchi simplex projection -> exact-objective best
tracking, iterated ``pgd_iters`` times over two starting points, all
without leaving VMEM.  The un-fused path materializes every step's
intermediates through HBM; here the (K,) problem state lives in
registers/VMEM for the whole descent.

TPU mapping: grid over the scenario axis S; each program owns one
instance — mask/t_train/SNR-coefficient/power/payload-bits rows of (K,)
plus a (2, K)
block of starting points (water-filling, uniform).  K <= 1024 devices x a handful of (2, K) f32 temps is a few
KB of VMEM — the kernel is compute-bound on the VPU transcendentals
(log1p per rate eval), which is exactly what fusing is for.  The simplex
projection uses a fixed-trip theta-bisection (sum(max(v - theta, 0)) = 1
is monotone in theta) rather than a sort — sorts don't lower inside TPU
Pallas, and 32 halvings put theta well below float32 resolution.

The batched (S, K) lane is the vmapped scenario driver's shape; the
single-instance (K,) entry in ``kernels/ops.py`` adds the leading axis.
Validated against the pure-jnp oracle ``kernels/ref.py::sub2_pgd`` in
interpret mode (CPU), like the diversity/fedavg kernels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_STARTS = 2          # water-filling + (warm start | uniform)
DEFAULT_PROJ_ITERS = 32


def _sub2_pgd_kernel(sel_ref, tt_ref, c_ref, pw_ref, bits_ref, a0_ref,
                     alpha_ref, obj_ref, *, rho: float, lr: float,
                     tau: float, iters: int, bandwidth_hz: float,
                     min_alpha: float, proj_iters: int):
    mask = sel_ref[0, 0]                               # (K,)
    tt = tt_ref[0, 0]
    c = c_ref[0, 0]
    pw = pw_ref[0, 0]
    bits = bits_ref[0, 0]                              # (K,) payload bits
    a0 = a0_ref[0]                                     # (N_STARTS, K)
    n_act = jnp.maximum(jnp.sum(mask), 1.0)
    any_act = jnp.sum(mask) > 0.5
    scale = bandwidth_hz / math.log(2.0)

    def upload(av):
        """t_up for selected devices (alpha floored), 0 for unselected."""
        ae = jnp.maximum(av, min_alpha)
        rate = scale * ae * jnp.log1p(c / ae)
        return jnp.where(mask > 0.0,
                         bits / jnp.maximum(rate, 1e-12), 0.0)

    def exact_obj(av):                                 # (n, K) -> (n,)
        tu = upload(av)
        tot = jnp.where(mask > 0.0, tt + tu, 0.0)
        return (rho * jnp.sum(pw * tu, axis=-1)
                + (1.0 - rho) * jnp.max(tot, axis=-1))

    def tangent_grad(av):
        """Mean-removed gradient of the logsumexp-smoothed objective.

        Mirrors ``bandwidth.sub2_objective(smooth_tau=tau)`` under
        ``jax.grad``: unselected coords enter the softmax with total 0
        (they sit in the reference logsumexp too) and the result is
        masked to the selected set.
        """
        ae = jnp.maximum(av, min_alpha)
        l = jnp.log1p(c / ae)
        rate = jnp.maximum(scale * ae * l, 1e-12)
        slope = scale * (l - c / (ae + c))
        tu = jnp.where(mask > 0.0, bits / rate, 0.0)
        dtu = -bits * slope / (rate * rate)
        tot = jnp.where(mask > 0.0, tt + tu, 0.0)
        w = jax.nn.softmax(tot / tau, axis=-1)
        g = (rho * pw + (1.0 - rho) * w) * dtu * mask
        return (g - jnp.sum(g, axis=-1, keepdims=True) / n_act) * mask

    def project(v):
        """Rows of v onto {a >= 0, sum a = 1, a_i = 0 off-mask}.

        Theta-bisection form of the Duchi projection: the unique theta
        with sum(max(v - theta, 0)) = 1 over active coords.  Bracket:
        at min(v) - 1 every active term is >= 1 (sum >= n_act >= 1); at
        max(v) the sum is 0.
        """
        vm = jnp.where(mask > 0.0, v, 0.0)
        act = mask > 0.0
        lo = jnp.min(jnp.where(act, vm, jnp.inf), axis=-1,
                     keepdims=True) - 1.0
        hi = jnp.max(jnp.where(act, vm, -jnp.inf), axis=-1, keepdims=True)

        def pbody(_, lohi):
            plo, phi = lohi
            mid = 0.5 * (plo + phi)
            s = jnp.sum(jnp.where(act, jnp.maximum(vm - mid, 0.0), 0.0),
                        axis=-1, keepdims=True)
            over = s >= 1.0
            return jnp.where(over, mid, plo), jnp.where(over, phi, mid)

        lo, hi = jax.lax.fori_loop(0, proj_iters, pbody, (lo, hi))
        out = jnp.maximum(vm - 0.5 * (lo + hi), 0.0)
        out = jnp.where(act, out, 0.0)
        return jnp.where(any_act, out, jnp.zeros_like(out))

    def body(i, carry):
        a, best_a, best_o = carry
        gt = tangent_grad(a)
        gmax = jnp.max(jnp.abs(gt), axis=-1, keepdims=True)
        frac = i.astype(jnp.float32) / iters
        lr_i = lr * (0.5 * (1.0 + jnp.cos(jnp.pi * frac)))
        a = project(a - lr_i * gt / jnp.maximum(gmax, 1e-12))
        o = exact_obj(a)
        better = o < best_o
        return (a, jnp.where(better[:, None], a, best_a),
                jnp.where(better, o, best_o))

    a = project(a0)
    a, best_a, best_o = jax.lax.fori_loop(0, iters, body,
                                          (a, a, exact_obj(a)))
    pick = best_o[0] <= best_o[1]
    alpha_ref[...] = jnp.where(pick, best_a[0], best_a[1])[None, None, :]
    obj_ref[...] = jnp.where(pick, best_o[0], best_o[1])[None, None, None]


def sub2_pgd_kernel(selected: jax.Array, t_train: jax.Array,
                    snr_coeff: jax.Array, tx_power: jax.Array,
                    payload_bits: jax.Array,
                    alpha0: jax.Array, *, rho: float, lr: float,
                    tau: float, iters: int, bandwidth_hz: float,
                    min_alpha: float,
                    proj_iters: int = DEFAULT_PROJ_ITERS,
                    interpret: bool
                    ) -> tuple[jax.Array, jax.Array]:
    """Batched fused PGD: (S, K) instance rows -> ((S, K) alpha, (S,) obj).

    ``snr_coeff`` is c = g*P / (B*N0); ``payload_bits`` is the per-device
    (S, K) uplink payload (the scalar ``model_bits`` broadcast when no
    codec reshapes it); ``alpha0`` is (S, N_STARTS, K).
    """
    s, k = selected.shape
    if alpha0.shape != (s, N_STARTS, k):
        raise ValueError(f"alpha0 must be (S, {N_STARTS}, K), got "
                         f"{alpha0.shape}")
    kern = functools.partial(
        _sub2_pgd_kernel, rho=rho, lr=lr, tau=tau, iters=iters,
        bandwidth_hz=bandwidth_hz, min_alpha=min_alpha,
        proj_iters=proj_iters)
    # Rows travel as (S, 1, K): a TPU block's last two dims must be
    # (8, 128)-aligned or whole, and a (1, K) slice of (S, K) is neither.
    row = pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0))
    alpha, obj = pl.pallas_call(
        kern,
        grid=(s,),
        in_specs=[row, row, row, row, row,
                  pl.BlockSpec((1, N_STARTS, k), lambda i: (i, 0, 0))],
        out_specs=[row, pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((s, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((s, 1, 1), jnp.float32)],
        interpret=interpret,
    )(*(x[:, None, :] for x in (selected, t_train, snr_coeff, tx_power,
                                payload_bits)), alpha0)
    return alpha[:, 0], obj[:, 0, 0]
