"""Plain reference of one FEEL scenario: the paper's Algorithms 1 and 2.

It imports nothing of the program.  From the configuration file, the
benchmark's own data and initial weights and a scenario's index it
recomputes what the program's sweep engine returns for that scenario:
per round the admitted set, every device's upload energy, the round
time, the test accuracy and the DAS iteration count, and the final
global model.

Random streams follow the simulator's documented seed contract
(``repro.sweep`` module docstring): scenario ``i`` draws its network
from ``fold_in(fold_in(key(base_seed), 0), i)`` and its simulation
stream from ``fold_in(fold_in(key(base_seed), 1), i)``; each round
splits the stream into (next, fading, scheduling, training) keys, and
device ``k`` trains on ``split(training key, K)[k]``, one key per local
step.  The schedule is Algorithm 2 as the configuration states it: Sub1
by the exact relaxation over the breakpoints and rounding at 1/2, Sub2
by projected gradient descent from the min-time (water-filling) start
and the uniform start, with the iteration counts of ``sub2``.

Written for one scenario at a time, round by round, with only the
admitted devices training.  Every matmul and convolution runs at the
configuration's ``matmul_precision``.  ``dtype`` is the precision of
the whole scenario and ``train_dtype`` that of local training, FedAvg
and evaluation alone; float32 is the reference, bfloat16 the controls.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from feelbench import nets

ALPHA_CEIL = 4.0


class _Radio:
    """Eq. 6-10 for one cell; every array is (K,)."""

    def __init__(self, w: dict):
        self.w = w

    def c(self, gains, power):
        return gains * power / (self.w["bandwidth_hz"] * self.w["noise_psd"])

    def rate(self, alpha, gains, power):
        w = self.w
        a = jnp.maximum(alpha, w["min_alpha"])
        snr = gains * power / (a * w["bandwidth_hz"] * w["noise_psd"])
        r = a * w["bandwidth_hz"] * jnp.log2(1.0 + snr)
        return jnp.where(alpha > 0.0, r, 0.0)

    def upload_time(self, alpha, gains, power):
        r = self.rate(alpha, gains, power)
        return jnp.where(r > 0.0, self.w["model_bits"] / jnp.maximum(r, 1e-12),
                         jnp.inf)


def _newton(a, r_req, c, w, steps):
    """Newton steps on rate(a) = r_req; rate is concave increasing."""
    scale = w["bandwidth_hz"] / math.log(2.0)

    def body(_, a):
        l = jnp.log1p(c / a)
        r, slope = scale * a * l, scale * (l - c / (a + c))
        return jnp.clip(a - (r - r_req) / jnp.maximum(slope, 1e-20),
                        w["min_alpha"], ALPHA_CEIL)

    a = jnp.clip(a, w["min_alpha"], ALPHA_CEIL)
    return jax.lax.fori_loop(0, steps, body, a)


def _min_time(radio, sub2, x, t_train, gains, power, alpha0):
    """Smallest common deadline: bisection on T, Newton on each share."""
    w = radio.w
    any_sel = jnp.sum(x) > 0.0
    n = jnp.maximum(jnp.sum(x), 1.0)
    equal = jnp.where(x > 0.0, 1.0 / n, 0.0)
    t_eq = radio.upload_time(equal, gains, power)
    hi = jnp.max(jnp.where(x > 0.0, t_train + t_eq, 0.0))
    lo = jnp.max(jnp.where(x > 0.0, t_train, 0.0))
    c = radio.c(gains, power)
    carry = jnp.clip(equal if alpha0 is None else alpha0, w["min_alpha"],
                     ALPHA_CEIL)

    def probe(deadline, carry, steps):
        slack = deadline - t_train
        r_req = jnp.where(slack > 0.0,
                          w["model_bits"] / jnp.maximum(slack, 1e-9), jnp.inf)
        finite = jnp.isfinite(r_req)
        a = _newton(carry, jnp.where(finite, r_req, 1.0), c, w, steps)
        a_eval = jnp.where(x > 0.0, jnp.where(finite, a, ALPHA_CEIL), 0.0)
        return a_eval, jnp.where(finite, a, carry)

    def body(_, s):
        lo, hi, carry = s
        mid = 0.5 * (lo + hi)
        a_eval, carry = probe(mid, carry, sub2["joint_newton_steps"])
        ok = jnp.sum(a_eval) <= 1.0
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi), carry

    lo, hi, carry = jax.lax.fori_loop(0, sub2["time_bisect_iters"], body,
                                      (lo, hi, carry))
    alpha, _ = probe(hi, carry, sub2["newton_iters"])
    total = jnp.sum(alpha)
    alpha = jnp.where(total > 1.0, alpha / total, alpha)
    return jnp.where(any_sel, alpha, jnp.zeros_like(alpha))


def _simplex(v, mask):
    """Euclidean projection onto {a >= 0, sum a = 1, a = 0 off mask}."""
    big = -1e30
    vm = jnp.where(mask > 0.0, v, big)
    u = jnp.sort(vm)[::-1]
    css = jnp.cumsum(u)
    k = jnp.arange(1, v.shape[0] + 1, dtype=v.dtype)
    cond = (u * k > (css - 1.0)) & (u > big / 2)
    i = jnp.clip(jnp.sum(cond) - 1, 0, v.shape[0] - 1)
    theta = (css[i] - 1.0) / (i + 1.0)
    out = jnp.where(mask > 0.0, jnp.maximum(v - theta, 0.0), 0.0)
    return jnp.where(jnp.sum(mask) > 0.5, out, jnp.zeros_like(out))


def _sub2(radio, sub2, x, t_train, gains, power, alpha0):
    """Eq. 15: min rho * sum E_k + (1 - rho) * T over the simplex."""
    rho, tau = sub2["rho"], sub2["smooth_tau"]
    mask = (x > 0.0).astype(t_train.dtype)
    n = jnp.maximum(jnp.sum(mask), 1.0)

    def objective(a, smooth):
        t_up = jnp.where(x > 0.0, radio.upload_time(a, gains, power), 0.0)
        energy = jnp.where(x > 0.0, power * t_up, 0.0)
        total = jnp.where(x > 0.0, t_train + t_up, 0.0)
        t = tau * jax.nn.logsumexp(total / tau) if smooth else jnp.max(total)
        return rho * jnp.sum(energy) + (1.0 - rho) * t

    grad = jax.grad(lambda a: objective(a, True))
    iters = sub2["pgd_iters"]

    def descend(a0):
        a0 = _simplex(a0, mask)

        def body(i, s):
            a, best_a, best_o = s
            g = grad(a) * mask
            g_t = (g - jnp.sum(g) / n) * mask
            lr = sub2["pgd_lr"] * (0.5 * (1 + jnp.cos(
                jnp.pi * i.astype(a.dtype) / iters)))
            a = _simplex(a - lr * g_t / jnp.maximum(jnp.max(jnp.abs(g_t)),
                                                    1e-12), mask)
            o = objective(a, False)
            better = o < best_o
            return a, jnp.where(better, a, best_a), jnp.where(better, o,
                                                              best_o)

        _, a, o = jax.lax.fori_loop(0, iters, body,
                                    (a0, a0, objective(a0, False)))
        return a, o

    a1, o1 = descend(_min_time(radio, sub2, x, t_train, gains, power, alpha0))
    a2, o2 = descend(mask / n)
    return jnp.where(o1 <= o2, a1, a2)


def _sub1(sched, energy, times, index):
    """Eq. 16 relaxed exactly over the breakpoints, rounded at 1/2."""
    c = sched["lambda_e"] * energy - sched["lambda_i"] * index
    good = c < 0.0
    t = jnp.maximum(times, 1e-9)
    cand = jnp.concatenate([jnp.zeros((1,), t.dtype), t])
    frac = jnp.minimum(1.0, cand[:, None] / t[None, :])
    j = sched["lambda_t"] * cand + jnp.sum(
        jnp.where(good[None, :], c[None, :] * frac, 0.0), axis=1)
    t_star = cand[jnp.argmin(j)]
    x_rel = jnp.where(good, jnp.minimum(1.0, t_star / t), 0.0)
    x = (x_rel >= 0.5).astype(t.dtype)
    prio = x_rel + 1e-4 * index / jnp.maximum(jnp.max(index), 1e-12)
    _, top = jax.lax.top_k(prio, sched["n_min"])
    forced = jnp.zeros_like(x).at[top].set(1.0)
    return jnp.where(jnp.sum(x) < sched["n_min"], jnp.maximum(x, forced), x)


def _schedule(cfg, method, index, sizes, gains, net, dt):
    """(selected, energy, round time, iterations) of one round."""
    w, sched, sub2 = cfg["wireless"], cfg["scheduler"], cfg["sub2"]
    radio = _Radio(w)
    power = net["tx_power"]
    k = sizes.shape[0]
    t_train = (cfg["local_epochs"] * sizes.astype(dt) * w["bits_per_sample"]
               * net["cycles_per_bit"] / net["cpu_freq"])
    if method == "full":
        x = jnp.ones((k,), dt)
        alpha = _sub2(radio, sub2, x, t_train, gains, power, None)
        iters = jnp.asarray(0, jnp.int32)
    elif method == "das":
        def live(s):
            x, a, xp, ap, it = s
            return (it == 0) | (jnp.sum(jnp.abs(x - xp)) >= sched["x_tol"]) \
                | (jnp.max(jnp.abs(a - ap)) >= sched["alpha_tol"])

        def body(s):
            x, a, _, _, it = s
            t_up = radio.upload_time(jnp.maximum(a, w["min_alpha"]), gains,
                                     power)
            x_new = _sub1(sched, power * t_up, t_train + t_up, index)
            a_new = _sub2(radio, sub2, x_new, t_train, gains, power, a)
            return x_new, a_new, x, a, it + 1

        init = (jnp.ones((k,), dt), jnp.full((k,), 1.0 / k, dt),
                jnp.zeros((k,), dt), jnp.zeros((k,), dt),
                jnp.asarray(0, jnp.int32))
        x, alpha, _, _, iters = jax.lax.while_loop(
            lambda s: (s[4] < sched["iterations_max"]) & live(s), body, init)
    else:
        raise ValueError(f"the reference has no scheduler {method!r}")
    t_up = jnp.where(x > 0.0, radio.upload_time(alpha, gains, power), 0.0)
    t_up = jnp.where(jnp.isinf(t_up), 0.0, t_up)
    energy = jnp.where(x > 0.0, power * t_up, 0.0)
    t_round = jnp.max(jnp.where(x > 0.0, t_train + t_up, 0.0))
    return x, energy, t_round, iters


def _index(cfg, hists, sizes, ages, dt):
    """Eq. 2 and Eq. 4: Gini-Simpson, size and log(1 + age), max-scaled."""
    sched = cfg["scheduler"]
    p = hists / jnp.maximum(jnp.sum(hists, axis=-1, keepdims=True), 1.0)
    terms = (1.0 - jnp.sum(p * p, axis=-1), sizes.astype(dt),
             jnp.log1p(ages.astype(dt)))
    out = None
    for v, g in zip(terms, sched["index_weights"]):
        m = jnp.max(v)
        t = jnp.where(m > 0.0, v / jnp.maximum(m, 1e-12), 0.0) * g
        out = t if out is None else out + t
    return out


def _network(cfg, key, k, dt):
    w = cfg["wireless"]
    k_pos, k_pow, k_cpu, k_cyc = jax.random.split(key, 4)
    side = w["cell_side_m"]
    pos = jax.random.uniform(k_pos, (k, 2), minval=0.0, maxval=side)
    d = jnp.maximum(jnp.linalg.norm(pos - side / 2.0, axis=-1), 1.0)

    def u(key, lo_hi):
        return jax.random.uniform(key, (k,), minval=lo_hi[0],
                                  maxval=lo_hi[1]).astype(dt)

    return {"pathloss": (d ** (-w["pathloss_exp"])).astype(dt),
            "tx_power": u(k_pow, w["tx_power_range"]),
            "cpu_freq": u(k_cpu, w["cpu_freq_range"]),
            "cycles_per_bit": u(k_cyc, w["cycles_per_bit_range"])}


def _local_train(cfg, params, images, labels, mask, n_steps, key, dt):
    """``n_steps`` plain SGD steps of one device (0 leaves it unchanged)."""
    cap = images.shape[0]
    b, lr = cfg["batch_size"], cfg["learning_rate"]
    max_steps = cfg["local_epochs"] * max(1, -(-cap // b))
    keys = jax.random.split(key, max_steps)

    def step(j, p):
        idx = jax.random.randint(keys[j], (b,), 0, cap)
        x = (images[idx].astype(jnp.float32) / 255.0).astype(dt)
        g = jax.grad(nets.loss)(p, x, labels[idx], mask[idx].astype(dt),
                                cfg["model"])
        return jax.tree_util.tree_map(lambda w, gi: w - lr * gi, p, g)

    return jax.lax.fori_loop(0, n_steps, step, params)


@functools.lru_cache(maxsize=None)
def _round_fn(cfg_key: str, method: str, dtype: str, train_dtype: str):
    import json
    cfg = json.loads(cfg_key)
    dt, tdt = jnp.dtype(dtype), jnp.dtype(train_dtype)

    @jax.jit
    def round_fn(params, ages, key, images, labels, mask, sizes, hists,
                 test_x, test_labels, net):
        key, k_fade, _, k_train = jax.random.split(key, 4)
        index = _index(cfg, hists, sizes, ages, dt)
        gains = net["pathloss"] * jax.random.exponential(
            k_fade, sizes.shape).astype(dt)
        x, energy, t_round, iters = _schedule(cfg, method, index, sizes,
                                              gains, net, dt)
        k = sizes.shape[0]
        steps = cfg["local_epochs"] * jnp.ceil(
            sizes.astype(jnp.float32) / cfg["batch_size"]).astype(jnp.int32)
        steps = jnp.where(x > 0.0, steps, 0)
        trained = jax.vmap(
            lambda im, lb, m, n, kk: _local_train(cfg, params, im, lb, m, n,
                                                  kk, tdt)
        )(images, labels, mask, steps, jax.random.split(k_train, k))
        wts = sizes.astype(dt) * x
        wts = (wts / jnp.maximum(jnp.sum(wts), 1.0)).astype(tdt)
        avg = jax.tree_util.tree_map(
            lambda st: jnp.einsum("k,k...->...", wts, st), trained)
        any_sel = jnp.sum(x) > 0.0
        params = jax.tree_util.tree_map(
            lambda a, p: jnp.where(any_sel, a, p), avg, params)
        ages = jnp.where(x > 0.0, 0, ages + 1)
        acc = nets.accuracy(params, test_x, test_labels, cfg["model"])
        return params, ages, key, (x, energy, t_round, acc, iters)

    return round_fn


def scenario_keys(base_seed: int, index: int):
    """(network key, simulation key) of global scenario ``index``."""
    root = jax.random.key(base_seed)
    i = jnp.uint32(index)
    return (jax.random.fold_in(jax.random.fold_in(root, 0), i),
            jax.random.fold_in(jax.random.fold_in(root, 1), i))


def simulate(cfg: dict, method: str, data: dict, params0, base_seed: int,
             index: int, dtype: str = "float32",
             train_dtype: str = "") -> dict:
    """One scenario, round by round.  Returns host arrays:
    ``selected`` (R, K), ``energy`` (R, K), ``round_time`` (R,),
    ``accuracy`` (R,), ``iterations`` (R,) and ``params`` (pytree)."""
    import json
    train_dtype = train_dtype or dtype
    dt, tdt = jnp.dtype(dtype), jnp.dtype(train_dtype)
    fn = _round_fn(json.dumps(cfg, sort_keys=True), method, dtype,
                   train_dtype)
    k = cfg["devices"]
    net_key, key = scenario_keys(base_seed, index)
    net = _network(cfg, net_key, k, dt)
    images = jnp.asarray(data["images"])
    labels = jnp.asarray(data["labels"])
    mask = jnp.asarray(data["mask"])
    sizes = jnp.asarray(data["sizes"])
    hists = jnp.sum(jax.nn.one_hot(labels, 10, dtype=jnp.float32)
                    * mask[..., None], axis=1).astype(dt)
    test_x = (jnp.asarray(data["test_images"]).astype(jnp.float32)
              / 255.0).astype(tdt)
    test_labels = jnp.asarray(data["test_labels"])
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(tdt),
                                    params0)
    ages = jnp.zeros((k,), jnp.int32)
    rows = []
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for _ in range(cfg["rounds"]):
            params, ages, key, row = fn(params, ages, key, images, labels,
                                        mask, sizes, hists, test_x,
                                        test_labels, net)
            rows.append(row)
    rows = jax.device_get(rows)
    return {"selected": np.stack([r[0] for r in rows]).astype(np.float32),
            "energy": np.stack([r[1] for r in rows]).astype(np.float64),
            "round_time": np.array([r[2] for r in rows], np.float64),
            "accuracy": np.array([r[3] for r in rows], np.float64),
            "iterations": np.array([r[4] for r in rows], np.int64),
            "params": jax.tree_util.tree_map(
                lambda a: np.asarray(a, np.float64), jax.device_get(params))}
