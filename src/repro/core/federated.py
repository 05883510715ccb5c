"""FEEL orchestration — the paper's Algorithm 1 (FedAvg + scheduling).

Each round:

1. Devices report (transmit power, |D_k|, diversity index) — here the
   index is computed from on-device label histograms
   (``core.diversity.diversity_index``), sizes and ages.
2. Fresh channel fading is drawn; the scheduler (``core.scheduler``)
   returns the selected set and bandwidth allocation.
3. Selected devices run ``E`` local epochs of SGD from the global model
   (vmapped over the *entire* client axis, masked by selection — static
   shapes, one jit).
4. The server aggregates with FedAvg weights ``|D_k| / D_r`` (Alg. 1
   line 12) — optionally through the ``fedavg_agg`` Pallas kernel path.
5. Ages update (selected -> 0, others += 1); energy/time accumulate.

Drivers (DESIGN.md §3):

* :func:`run_federated` — the device-resident driver: the entire
  ``num_rounds`` simulation (diversity index, fading draw, scheduling,
  masked local training, FedAvg, age update, folded evaluation, metric
  accumulation) is ONE ``jax.lax.scan`` over rounds inside one jit.
  Per-round metrics come back as stacked arrays (:class:`RoundMetrics`)
  and a thin host adapter converts them to the historical
  :class:`RoundRecord` list, so callers of the old per-round loop keep
  working unchanged.
* :func:`run_federated_batch` — ``vmap`` of the scanned simulation over
  a leading scenario axis (PRNG key x :class:`wireless.NetworkState`
  realization): S independent FEEL runs execute as one SPMD program.
  Every scheduling policy is vmap-deterministic (``core.scheduler``),
  so scenario ``i`` of a batch is bit-for-bit the single run with
  ``nets[i]``/``keys[i]``.
* :func:`run_federated_loop` — the legacy host-side Python loop (two
  jit dispatches + >=5 host syncs per round), kept as the reference
  implementation for the parity tests and the ``fl_e2e`` old-vs-new
  benchmark.

The client axis is shardable: on a pod, ``client_batch_spec`` places
clients over the ``data`` mesh axis so K local trainings run as one SPMD
program — the cross-silo mapping described in DESIGN.md §3.

Streaming data (``FLConfig.stream``, DESIGN.md §7): when set, a
:class:`repro.core.streaming.StreamState` joins the scan carry — each
round samples data arrivals, refreshes per-device class counts /
diversity stats / staleness in one fused pass, and schedules + trains on
the refreshed statistics.  Both drivers and the legacy loop share the
sequence, so every parity contract above extends to streaming runs.

Compressed uplink (``FLConfig.compression``, DESIGN.md §9): when set,
devices upload codec-compressed updates — the codec's per-device
payload bits flow into scheduling and Sub2 (Eq. 6/9/10 price the
*effective* post-compression bits), the round's FedAvg aggregates the
dequantized values, and the ``(K, P)`` error-feedback residual joins
the scan carry so lossy rounds stay bit-for-bit reproducible across
drivers (scan == legacy loop, batch == S independent runs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bandwidth, compression, diversity, faults, \
    scheduler, streaming, wireless
from repro.core import events as events_lib
from repro.data import partition as partition_lib
from repro.data import synthetic
from repro import telemetry as telemetry_lib
from repro.telemetry import health as telemetry_health
from repro.telemetry import record as telemetry_record

Array = jax.Array
Params = Any


@dataclasses.dataclass(frozen=True)
class FLConfig:
    num_rounds: int = 15                  # paper: 15 rounds
    local_epochs: int = 1                 # E
    batch_size: int = 50                  # one shard per step
    learning_rate: float = 0.05
    momentum: float = 0.0
    num_classes: int = 10
    measure: str = "gini_simpson"
    index_weights: diversity.IndexWeights = diversity.IndexWeights()
    use_kernel_agg: bool = False          # route FedAvg through Pallas
    # Streaming-data subsystem (DESIGN.md §7): when set, per-device data
    # evolves round by round inside the scan carry and the scheduler
    # re-ranks on the refreshed statistics.  None = static data,
    # bit-for-bit the pre-streaming behavior.
    stream: Optional[streaming.StreamConfig] = None
    # Compressed-uplink subsystem (DESIGN.md §9): when set, devices
    # upload codec-compressed updates — per-device payload bits price
    # scheduling and Sub2, the lossy round trip shapes the aggregate,
    # and the error-feedback residual joins the scan carry.  None =
    # full-precision uploads, bit-for-bit the pre-compression behavior.
    compression: Optional[compression.CompressionConfig] = None
    # Unreliable-edge subsystem (DESIGN.md §10): when set, per-round
    # fault processes (outages, deep fades, stragglers, dropouts) are
    # drawn inside the scan, uploads retry with exponential backoff,
    # FedAvg aggregates over the success mask only, and the scheduler
    # discounts priorities by a per-device reliability EMA carried in
    # the scan state.  None = perfectly reliable edge, bit-for-bit the
    # pre-fault behavior.
    faults: Optional[faults.FaultConfig] = None
    # Admitted-set dense-block dispatch (DESIGN.md §11): static capacity
    # of the training block.  When set, each round gathers the admitted
    # devices into a fixed ``(n_cap, ...)`` block (stable argsort on the
    # selection mask), runs the vmapped local trainer over only those
    # lanes, and scatters the results back for FedAvg.  Admitted devices
    # beyond the capacity are dropped deterministically by schedule rank
    # and counted in ``RoundMetrics.n_dropped``.  None = today's
    # masked-all-K path, bitwise unchanged.
    dispatch_cap: Optional[int] = None
    # Scan-carry memory diet (DESIGN.md §11): storage dtype for the
    # ``(K, P)`` error-feedback residual and the ``(K, C)`` stream
    # stats between rounds ("bfloat16"/"float16").  Arithmetic stays
    # float32 — state is downcast on carry write and upcast on read, in
    # helpers shared by both drivers so the scan==legacy parity holds at
    # reduced precision too.  None (or "float32") = full-precision
    # carry, bitwise unchanged.
    carry_dtype: Optional[str] = None
    # Event-driven asynchronous FEEL (DESIGN.md §12): when set, the
    # simulation runs as a scan over scheduling *events* instead of
    # synchronous rounds — per-device availability processes gate
    # admission, uploads land after their compute + channel time, and
    # the server applies staleness-weighted buffered aggregation
    # (``core.events``).  ``make_feel_sim``/``make_feel_sim_batch``
    # delegate to the event drivers, so the sweep engine and batch
    # driver compose unchanged.  None = synchronous rounds; the event
    # scan's synchronous limit reproduces them bitwise
    # (``tests/test_events.py``).
    events: Optional[events_lib.EventConfig] = None
    # In-scan telemetry subsystem (DESIGN.md §13): when set, the scan
    # bodies of both drivers (and the legacy loop) emit a per-round
    # telemetry frame — scheduler score decompositions, admission/
    # dispatch/delivery outcomes, Sub2 solver traces, per-device
    # transport accounting, fault events by type, event-mode
    # availability state — as an extra stacked output alongside
    # RoundMetrics.  The frame only observes (no extra PRNG draws,
    # nothing feeds back into the round), so the primary outputs stay
    # bitwise identical to a disabled run.  None = no telemetry,
    # bitwise today's program (the faults.active inert-config pattern).
    telemetry: Optional[telemetry_lib.TelemetryConfig] = None


def sim_length(fcfg: FLConfig) -> int:
    """Rows in the simulation's metrics: ``num_rounds`` for the
    synchronous drivers, ``events.num_events`` (when set) for the event
    drivers — the one place that resolves the default, so the sweep
    engine's Welford aggregates and checkpoint shapes stay in step with
    whichever driver ``make_feel_sim`` delegates to."""
    if fcfg.events is not None and fcfg.events.num_events is not None:
        return fcfg.events.num_events
    return fcfg.num_rounds


@dataclasses.dataclass
class RoundRecord:
    round: int
    accuracy: float
    n_selected: int
    round_time: float
    energy_total: float
    energy_per_device: float
    selected: np.ndarray
    # Devices whose upload actually landed; equals n_selected on a
    # reliable edge (faults=None).  Defaulted so pre-fault positional
    # constructors keep working; the -1 sentinel is normalized to
    # n_selected in __post_init__ so it never reaches users.
    n_success: int = -1
    # Admitted devices dropped by the dispatch capacity this round
    # (always 0 with ``dispatch_cap=None``).  Defaulted like n_success.
    n_dropped: int = 0

    def __post_init__(self):
        if self.n_success < 0:
            self.n_success = self.n_selected


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RoundMetrics:
    """Per-round simulation outputs as stacked device arrays.

    Leaves carry a leading ``(num_rounds,)`` axis — and an additional
    leading scenario axis when produced by :func:`run_federated_batch`.
    ``accuracy`` is NaN on rounds where evaluation was skipped
    (``eval_every`` stride), matching the legacy record semantics.
    """

    accuracy: Array      # (R,)
    n_selected: Array    # (R,) int32
    round_time: Array    # (R,)
    energy: Array        # (R, K) per-device joules (0 if unselected)
    energy_total: Array  # (R,)
    selected: Array      # (R, K) {0,1}
    iterations: Array    # (R,) int32 DAS outer iterations
    n_success: Array     # (R,) int32 uploads that landed (= n_selected
                         # on a reliable edge)
    n_dropped: Array     # (R,) int32 admitted devices dropped by the
                         # dispatch capacity (0 with dispatch_cap=None)

    def tree_flatten(self):
        return ((self.accuracy, self.n_selected, self.round_time,
                 self.energy, self.energy_total, self.selected,
                 self.iterations, self.n_success, self.n_dropped), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


# ---------------------------------------------------------------------------
# Local training (vmapped over clients)
# ---------------------------------------------------------------------------

def make_local_trainer(loss_fn: Callable[[Params, Array, Array, Array],
                                         Array],
                       cfg: FLConfig) -> Callable:
    """Build the vmapped multi-epoch local-SGD update.

    Every client runs ``steps_k = E * ceil(size_k / B)`` gradient steps;
    clients are padded to the max step count and masked, so one
    ``lax.scan`` covers the heterogeneous dataset sizes (the wireless time
    model separately charges each device for its true workload, Eq. 8).
    """

    def local_sgd(params: Params, images: Array, labels: Array,
                  mask: Array, steps_active: Array, key: Array) -> Params:
        cap = images.shape[0]

        def step(carry, inp):
            p, vel = carry
            k, active = inp
            idx = jax.random.randint(k, (cfg.batch_size,), 0, cap)
            bx = synthetic.to_float(images[idx])
            by = labels[idx]
            bm = mask[idx]
            g = jax.grad(loss_fn)(p, bx, by, bm)
            vel = jax.tree_util.tree_map(
                lambda v, gi: cfg.momentum * v + gi, vel, g)
            p_new = jax.tree_util.tree_map(
                lambda w, v: w - cfg.learning_rate * v, p, vel)
            p = jax.tree_util.tree_map(
                lambda new, old: jnp.where(active > 0.0, new, old),
                p_new, p)
            return (p, vel), None

        vel0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        keys = jax.random.split(key, steps_active.shape[0])
        (params, _), _ = jax.lax.scan(step, (params, vel0),
                                      (keys, steps_active))
        return params

    return jax.vmap(local_sgd, in_axes=(None, 0, 0, 0, 0, 0))


def fedavg_aggregate(client_params: Params, weights: Array,
                     use_kernel: bool = False) -> Params:
    """g <- sum_k (D_k / D_r) w_k (Alg. 1 line 12) over stacked params.

    ``weights`` must already be normalized over the selected set (zeros
    for unselected clients).

    The kernel path flattens the whole pytree once — every leaf reshaped
    to ``(K, -1)`` and concatenated — so the Pallas ``fedavg_agg`` kernel
    launches once per round instead of once per parameter leaf (leaves
    must share a dtype, which stacked model params do).
    """
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        leaves, treedef = jax.tree_util.tree_flatten(client_params)
        dtypes = {leaf.dtype for leaf in leaves}
        if len(dtypes) != 1:
            # concatenate would silently promote mixed-dtype leaves,
            # diverging from the dtype-preserving tensordot path.
            raise TypeError(
                f"kernel FedAvg path needs uniform leaf dtype, got "
                f"{sorted(map(str, dtypes))}")
        k = leaves[0].shape[0]
        sizes = [int(np.prod(leaf.shape[1:])) for leaf in leaves]
        flat = jnp.concatenate(
            [leaf.reshape(k, -1) for leaf in leaves], axis=1)
        agg = kernel_ops.fedavg_agg(flat, weights)
        outs, offset = [], 0
        for leaf, size in zip(leaves, sizes):
            outs.append(agg[offset:offset + size].reshape(leaf.shape[1:]))
            offset += size
        return jax.tree_util.tree_unflatten(treedef, outs)
    return jax.tree_util.tree_map(
        lambda stacked: jnp.tensordot(weights, stacked, axes=1),
        client_params)


# ---------------------------------------------------------------------------
# Admitted-set dense-block dispatch (DESIGN.md §11)
# ---------------------------------------------------------------------------

def dispatch_plan(selected: Array, n_cap: int
                  ) -> Tuple[Array, Array, Array]:
    """Gather plan for the dense training block: ``(idx, sel_eff, n_dropped)``.

    ``idx`` is the ``(min(n_cap, K),)`` device indices that occupy the
    block's lanes, ``sel_eff`` the ``(K,)`` realized selection mask after
    capacity drops, and ``n_dropped`` the int32 count of admitted devices
    that did not fit.

    Schedule rank: ``jnp.argsort`` is stable, so ``argsort(-selected)``
    lists the admitted devices first *in device-index order*, then the
    rest.  The rank is a pure function of the selection mask — no
    data-dependent shapes, no host sync, identical under ``vmap`` — which
    is what makes overflow drops deterministic across the batch/shard_map
    drivers (the batch == singles contract).  Admitted devices with rank
    ``>= n_cap`` are dropped for the round.
    """
    k = selected.shape[0]
    n_lanes = min(int(n_cap), k)                    # static
    order = jnp.argsort(-selected)
    idx = order[:n_lanes]
    sel_eff = jnp.zeros_like(selected).at[idx].set(selected[idx])
    n_dropped = (jnp.sum(selected) - jnp.sum(sel_eff)).astype(jnp.int32)
    return idx, sel_eff, n_dropped


def _dispatch_accounting(result, sel_eff: Array) -> Tuple[Array, Array]:
    """Re-price a scheduled round on the *realized* (post-drop) set.

    The scheduler already charged energy/airtime for every admitted
    device; capacity-dropped devices never train or transmit, so their
    energy is zeroed and the round's wall clock is the max over the
    surviving set only.  Shared by the scan body and the (jitted) legacy
    loop so both drivers price drops identically.
    """
    energy = result.energy * sel_eff
    t_up = jnp.where(jnp.isinf(result.t_up), 0.0, result.t_up)
    return energy, wireless.round_time(sel_eff, result.t_train, t_up)


# Legacy-loop entries: jitted (not eager) on purpose, mirroring
# ``faults.fault_step`` — the scan driver compiles the same arithmetic
# fused, and op-at-a-time eager scheduling is the one way the loop could
# drift off the scan bitwise.
_dispatch_plan_jit = jax.jit(dispatch_plan, static_argnums=(1,))
_dispatch_accounting_jit = jax.jit(_dispatch_accounting)
_signal_update_jit = jax.jit(telemetry_health.signal_update)


def _carry_dtype(fcfg: FLConfig):
    """Storage dtype for the dieted scan-carry state, or None.

    ``float32`` normalizes to None (the storage dtype already is f32, so
    emitting casts would only change the jaxpr, not the values).
    """
    if fcfg.carry_dtype is None:
        return None
    dt = jnp.dtype(fcfg.carry_dtype)
    if dt == jnp.dtype(jnp.float32):
        return None
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        raise ValueError(
            f"carry_dtype must be one of bfloat16/float16/float32, got "
            f"{fcfg.carry_dtype!r}")
    return dt


# ---------------------------------------------------------------------------
# One federated round (shared by the scan driver and the legacy loop)
# ---------------------------------------------------------------------------

def _masked_local_train(trainer: Callable, max_steps: int, cfg: FLConfig,
                        params: Params, images: Array, labels: Array,
                        mask: Array, sizes: Array, selected: Array,
                        key: Array,
                        dispatch_idx: Optional[Array] = None
                        ) -> Tuple[Params, Array]:
    """Masked local SGD for all K clients -> (stacked params, FedAvg w).

    The single definition of the per-client step schedule and the
    ``D_k / D_r`` weight normalization — the plain and compressed round
    bodies both call it, so the scan==legacy parity contracts cannot be
    broken by editing one copy.

    ``dispatch_idx`` (DESIGN.md §11) switches on the dense-block path:
    the per-device operands are gathered into a ``(n_cap, ...)`` block,
    the vmapped trainer runs over only those lanes, and the trained
    params scatter back into the ``(K, ...)`` layout with the global
    model as filler.  Two invariants make ``dispatch_cap >= K`` bitwise
    equal to the masked path: (a) device ``d``'s PRNG key is
    ``split(key, K)[d]`` gathered by lane — a device's SGD noise never
    depends on which lane it lands in — and (b) the scatter restores
    device order *before* FedAvg, so the aggregation's float reduction
    order is the same one the masked path uses.
    """
    k = images.shape[0]
    # Per-client active step schedule: E * ceil(size_k / B) steps.
    steps_k = cfg.local_epochs * jnp.ceil(
        sizes.astype(jnp.float32) / cfg.batch_size)
    step_idx = jnp.arange(max_steps, dtype=jnp.float32)[None, :]
    active = (step_idx < steps_k[:, None]).astype(jnp.float32)
    active = active * selected[:, None]             # frozen if unselected
    keys = jax.random.split(key, k)
    with telemetry_lib.phase_scope("local_train"):
        if dispatch_idx is None:
            client_params = trainer(params, images, labels, mask, active,
                                    keys)
        else:
            idx = dispatch_idx
            block = trainer(params, images[idx], labels[idx], mask[idx],
                            active[idx], keys[idx])
            # Scatter the trained lanes back to device order; every
            # off-block device is frozen at the global model (exactly
            # what its masked-path lane would have returned).
            client_params = jax.tree_util.tree_map(
                lambda p, b: jnp.broadcast_to(p[None], (k,) + p.shape)
                .at[idx].set(b),
                params, block)
    # FedAvg weights D_k / D_r over the selected set.
    w = sizes.astype(jnp.float32) * selected
    w = w / jnp.maximum(jnp.sum(w), 1.0)
    return client_params, w


def _train_round(trainer: Callable, max_steps: int, cfg: FLConfig,
                 params: Params, images: Array, labels: Array, mask: Array,
                 sizes: Array, selected: Array, key: Array,
                 dispatch_idx: Optional[Array] = None,
                 sig_fn: Optional[Callable] = None) -> Params:
    """Masked local training for all K clients + FedAvg. Pure, traceable.

    An empty admitted set (possible when ``n_min == 0`` and every device
    misses the deadline) must carry the previous model forward — the
    all-zero weights would otherwise *replace* the global model with
    zeros.  The guard is a scalar select, so any non-empty round keeps
    the aggregated value bitwise unchanged.  Under dispatch the guard
    still works: an all-dropped/all-unselected round scatters nothing
    but frozen lanes and the zero-weight aggregate is discarded.

    ``sig_fn`` (telemetry signals group, DESIGN.md §14) is the
    learning-signal observer from :func:`_make_sig_fn`: when set, the
    return value grows a trailing ``(loss_delta, update_norm)`` pair
    computed from the stacked client params *before* aggregation.  A
    pure observer — the aggregate itself is untouched.
    """
    client_params, w = _masked_local_train(trainer, max_steps, cfg, params,
                                           images, labels, mask, sizes,
                                           selected, key,
                                           dispatch_idx=dispatch_idx)
    obs = sig_fn(params, client_params, None, images, labels, mask) \
        if sig_fn is not None else None
    with telemetry_lib.phase_scope("aggregate"):
        agg = fedavg_aggregate(client_params, w, cfg.use_kernel_agg)
        any_sel = jnp.sum(selected) > 0.0
        new_params = jax.tree_util.tree_map(
            lambda a, p: jnp.where(any_sel, a, p), agg, params)
    if sig_fn is not None:
        return new_params, obs
    return new_params


def fedavg_aggregate_masked(params: Params, client_params: Params,
                            weights: Array, mask: Array,
                            use_kernel: bool = False) -> Params:
    """Failure-aware FedAvg in update form (fault subsystem, DESIGN.md §10).

    ``g' = g + sum_k w_k m_k (w^k - g)`` with ``weights`` normalized by
    the caller over the success set and ``mask`` the upload-success
    indicator.  The update form is the graceful-degradation guarantee:
    all-zero masked weights leave ``g`` exactly unchanged (the server
    carries the previous model when every upload fails), with no branch.
    The kernel path flattens the per-client deltas once and runs the
    masked ``fedavg_agg`` Pallas lane.
    """
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        leaves, _ = jax.tree_util.tree_flatten(client_params)
        p_leaves, p_treedef = jax.tree_util.tree_flatten(params)
        dtypes = {leaf.dtype for leaf in p_leaves}
        if len(dtypes) != 1:
            raise TypeError(
                f"kernel FedAvg path needs uniform leaf dtype, got "
                f"{sorted(map(str, dtypes))}")
        k = leaves[0].shape[0]
        deltas = jnp.concatenate(
            [(cl - p[None]).reshape(k, -1)
             for cl, p in zip(leaves, p_leaves)], axis=1)
        agg = kernel_ops.fedavg_agg_masked(deltas, weights, mask)
        outs, offset = [], 0
        for p in p_leaves:
            size = int(np.prod(p.shape))
            outs.append(p + agg[offset:offset + size].reshape(p.shape)
                        .astype(p.dtype))
            offset += size
        return jax.tree_util.tree_unflatten(p_treedef, outs)
    # Broadcast-multiply-reduce, NOT tensordot: a batched dot_general
    # lowers through a different CPU matmul tiling than the single-lane
    # one, so the vmapped batch driver would drift a few ULP off the
    # per-scenario runs.  The explicit sum keeps one reduction order in
    # every context (the batch == singles bitwise contract).
    wm = weights * mask
    return jax.tree_util.tree_map(
        lambda p, st: p + jnp.sum(
            wm.reshape(wm.shape + (1,) * (st.ndim - 1)) * (st - p[None]),
            axis=0).astype(p.dtype),
        params, client_params)


def _train_round_faulty(trainer: Callable, max_steps: int, cfg: FLConfig,
                        params: Params, images: Array, labels: Array,
                        mask: Array, sizes: Array, selected: Array,
                        ok: Array, key: Array,
                        dispatch_idx: Optional[Array] = None,
                        sig_fn: Optional[Callable] = None) -> Params:
    """Fault-aware round: train the *selected* set, aggregate the *ok* set.

    Every admitted device runs its local epochs (the failure happens at
    upload time, after the compute was spent), but only devices whose
    upload landed contribute to FedAvg — weights are renormalized over
    the success set, so the aggregate stays a convex combination and an
    all-fail round degrades to carrying the previous model
    (:func:`fedavg_aggregate_masked`).

    ``sig_fn``: see :func:`_train_round` — appends the observer's
    ``(loss_delta, update_norm)`` pair to the return value.
    """
    client_params, _ = _masked_local_train(trainer, max_steps, cfg, params,
                                           images, labels, mask, sizes,
                                           selected, key,
                                           dispatch_idx=dispatch_idx)
    obs = sig_fn(params, client_params, None, images, labels, mask) \
        if sig_fn is not None else None
    with telemetry_lib.phase_scope("aggregate"):
        w = sizes.astype(jnp.float32) * ok
        w = w / jnp.maximum(jnp.sum(w), 1.0)
        new_params = fedavg_aggregate_masked(params, client_params, w, ok,
                                             cfg.use_kernel_agg)
    if sig_fn is not None:
        return new_params, obs
    return new_params


def _max_local_steps(cfg: FLConfig, capacity: int) -> int:
    steps_per_epoch = max(1, -(-capacity // cfg.batch_size))
    return cfg.local_epochs * steps_per_epoch


# ---------------------------------------------------------------------------
# Compressed uplink (DESIGN.md §9): lossy updates + error feedback
# ---------------------------------------------------------------------------

def flat_param_size(params: Params) -> int:
    """Total flattened coordinate count — the error-feedback residual's
    trailing dimension (static from the param shapes)."""
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(params))


def _comp_setup(fcfg: FLConfig) -> compression.Codec:
    """Codec instance for a compressed run (shared by the scan driver
    and the legacy loop so their uplink sequence cannot drift apart)."""
    return compression.get_codec(fcfg.compression.codec)


def _train_round_compressed(trainer: Callable, max_steps: int,
                            fcfg: FLConfig, codec: compression.Codec,
                            params: Params, images: Array, labels: Array,
                            mask: Array, sizes: Array, selected: Array,
                            key: Array, residual: Array, gains: Array,
                            index: Array,
                            success: Optional[Array] = None,
                            dispatch_idx: Optional[Array] = None,
                            sig_fn: Optional[Callable] = None
                            ) -> Tuple[Params, Array]:
    """Masked local training + compressed-uplink FedAvg.  Pure, traceable.

    Local SGD is identical to :func:`_train_round`; the aggregation
    differs: client *updates* (``w_k - g``) are flattened to one
    ``(K, P)`` matrix, pushed through the codec's fused
    residual-accumulate -> compress -> dequantize pass
    (``compression.apply_codec``), and the decoded values are averaged
    with the FedAvg weights onto the global model (``g' = g + sum_k
    (D_k / D_r) c_k``).  Returns the new params and the advanced
    error-feedback residual (only selected devices consume backlog).
    Unselected clients are frozen, so their raw update is exactly zero
    and their decoded row is multiplied by a zero weight.

    ``success`` (fault subsystem, DESIGN.md §10) is the upload-landed
    mask: FedAvg weights renormalize over the *successful* set, the
    codec consumes backlog only for devices that delivered, and a
    failed device's whole update folds back into its error-feedback
    residual (``compression.apply_codec``).  The update-form aggregate
    means an all-fail round carries the previous model unchanged.
    ``None`` is the reliable-edge path, bitwise the pre-fault behavior.

    ``dispatch_idx`` (DESIGN.md §11): the dense block trains ``n_cap``
    lanes and the trained params scatter back to the ``(K, ...)`` layout
    *before* the updates are flattened — off-block rows equal the global
    model bitwise, so their raw update is exactly zero, the codec sees
    them as untransmitted, and the ``(K, P)`` EF residual carry keeps
    its population shape under dispatch.

    With ``fcfg.carry_dtype`` set the residual is *stored* at reduced
    precision between rounds: upcast to f32 here on entry, advanced in
    f32 by the codec, and downcast on return.  Both drivers call this
    one body, so the cast points cannot drift apart.
    """
    k = images.shape[0]
    cdt = _carry_dtype(fcfg)
    if cdt is not None:
        residual = residual.astype(jnp.float32)
    k_sgd, k_comp = jax.random.split(key)
    client_params, w = _masked_local_train(trainer, max_steps, fcfg,
                                           params, images, labels, mask,
                                           sizes, selected, k_sgd,
                                           dispatch_idx=dispatch_idx)
    leaves, _ = jax.tree_util.tree_flatten(client_params)
    p_leaves, p_treedef = jax.tree_util.tree_flatten(params)
    dtypes = {leaf.dtype for leaf in p_leaves}
    if len(dtypes) != 1:
        # the flattened (K, P) update matrix would silently promote
        # mixed-dtype leaves; same guard as the kernel FedAvg path.
        raise TypeError(f"compressed uplink needs uniform leaf dtype, "
                        f"got {sorted(map(str, dtypes))}")
    updates = jnp.concatenate(
        [(cl - p[None]).reshape(k, -1)
         for cl, p in zip(leaves, p_leaves)], axis=1)
    obs = sig_fn(params, client_params, updates, images, labels, mask) \
        if sig_fn is not None else None
    if success is not None:
        w = sizes.astype(jnp.float32) * selected * success
        w = w / jnp.maximum(jnp.sum(w), 1.0)
    with telemetry_lib.phase_scope("aggregate"):
        c, residual = compression.apply_codec(
            codec, updates, residual, selected, k_comp, fcfg.compression,
            gains, index, success=success)
        if cdt is not None:
            residual = residual.astype(cdt)
        agg = jnp.tensordot(w, c, axes=1)           # (P,)
        outs, offset = [], 0
        for p in p_leaves:
            size = int(np.prod(p.shape))
            outs.append(p + agg[offset:offset + size].reshape(p.shape)
                        .astype(p.dtype))
            offset += size
        new_params = jax.tree_util.tree_unflatten(p_treedef, outs)
    if sig_fn is not None:
        return new_params, residual, obs
    return new_params, residual


def _sched_cfg(scfg: scheduler.SchedulerConfig,
               fcfg: FLConfig) -> scheduler.SchedulerConfig:
    """Round-time scheduler config shared by the scan driver and the
    legacy loop (the parity contract depends on both deriving it
    identically).  Syncs ``local_epochs`` and — with faults enabled —
    applies the overprovisioning bump: Sub1 admits ``overprovision``
    extra devices so the *expected* surviving set still meets the
    original floor (DESIGN.md §10)."""
    sch = dataclasses.replace(scfg, local_epochs=fcfg.local_epochs)
    flt = faults.active(fcfg.faults)
    if flt is not None and flt.overprovision > 0:
        sch = dataclasses.replace(
            sch, n_min=sch.n_min + flt.overprovision,
            n_fixed=None if sch.n_fixed is None
            else sch.n_fixed + flt.overprovision)
    return sch


def _make_sig_fn(loss_fn: Callable, fcfg: FLConfig,
                 capacity: int) -> Callable:
    """Learning-signal observer for the telemetry ``signals`` group.

    Returns ``sig_fn(params0, client_params, updates, images, labels,
    mask) -> (loss_delta, update_norm)``, both ``(K,) f32``.  The
    compressed round passes its existing flattened ``(K, P)`` update
    matrix; the plain/faulty rounds pass ``None`` and the matrix is
    built here with the same ravel order, so every driver path shares
    one norm reduction.  The loss probe evaluates a fixed leading
    window of each shard (no PRNG), so enabling signals cannot perturb
    the round (DESIGN.md §14 purity contract).  The window is capped
    at ``health.PROBE_CAP`` samples: the probe costs two forward
    passes per device per round, and an uncapped batch-size window
    prices at ~25% of the whole round body — the cap keeps the
    signals group inside the <1.10 telemetry overhead budget.
    """
    probe = telemetry_health.make_signal_probe(
        loss_fn, min(fcfg.batch_size, capacity,
                     telemetry_health.PROBE_CAP))

    def sig_fn(params0, client_params, updates, images, labels, mask):
        if updates is None:
            updates = telemetry_health.flatten_updates(client_params,
                                                       params0)
        return (probe(params0, client_params, images, labels, mask),
                telemetry_health.update_norms(updates))

    return sig_fn


def _sig_enabled(fcfg: FLConfig) -> bool:
    tel = telemetry_lib.active(fcfg.telemetry)
    return tel is not None and tel.signals


def make_round_fn(loss_fn: Callable, cfg: FLConfig,
                  capacity: int,
                  sig_fn: Optional[Callable] = None) -> Callable:
    """Returns jit'd ``round_fn(params, data, selected, weights, key)``.

    ``selected``/``weights`` come from the scheduler (host side); the round
    body — local training for all K clients, masked FedAvg — is one SPMD
    program.  Used by the legacy per-round loop; the scan driver inlines
    the same :func:`_train_round` body.  With ``cfg.compression`` set the
    returned function is the compressed-uplink round
    (:func:`_train_round_compressed`): it additionally takes
    ``(residual, gains, index)`` and returns ``(params, residual)``.
    With ``cfg.faults`` set (and no compression) it is the fault-aware
    round (:func:`_train_round_faulty`), taking the upload-success mask
    ``ok`` after ``selected``; the compressed round takes the mask as
    its ``success`` keyword either way.  Every variant accepts a
    ``dispatch_idx`` keyword (the dense-block gather indices from
    :func:`dispatch_plan`; None = masked all-K path).
    """
    trainer = make_local_trainer(loss_fn, cfg)
    max_steps = _max_local_steps(cfg, capacity)
    if cfg.compression is not None:
        codec = _comp_setup(cfg)
        return jax.jit(functools.partial(_train_round_compressed, trainer,
                                         max_steps, cfg, codec,
                                         sig_fn=sig_fn))
    if faults.active(cfg.faults) is not None:
        return jax.jit(functools.partial(_train_round_faulty, trainer,
                                         max_steps, cfg, sig_fn=sig_fn))
    return jax.jit(functools.partial(_train_round, trainer, max_steps, cfg,
                                     sig_fn=sig_fn))


# ---------------------------------------------------------------------------
# Device-resident simulation: scan over rounds, one jit
# ---------------------------------------------------------------------------

def _eval_mask(num_rounds: int, eval_every: int) -> np.ndarray:
    """Static per-round evaluate-or-skip schedule (legacy semantics)."""
    mask = np.zeros((num_rounds,), np.bool_)
    mask[::max(eval_every, 1)] = True
    mask[-1] = True
    return mask


def _stream_size_cap(stream: streaming.StreamConfig, capacity: int) -> float:
    """Effective per-device count cap for a streaming run.

    Streamed sizes drive the local step counts and FedAvg weights, so
    they must stay within the padded sample buffers; the configured cap
    (if any) is additionally clipped to the physical capacity.
    """
    if stream.size_cap <= 0.0:
        return float(capacity)
    return min(float(stream.size_cap), float(capacity))


def _stream_setup(fcfg: FLConfig, capacity: int):
    """(process, size_cap, stats column of ``fcfg.measure``).

    Shared by the scan driver and the legacy loop so their streaming
    setup cannot drift apart (the parity contract depends on it).
    """
    process = streaming.get_process(fcfg.stream.process)
    size_cap = _stream_size_cap(fcfg.stream, capacity)
    if fcfg.measure not in ("gini_simpson", "shannon"):
        raise ValueError(f"unknown diversity measure: {fcfg.measure!r}")
    return process, size_cap, 0 if fcfg.measure == "gini_simpson" else 1


def _stream_round(process, fcfg: FLConfig, size_cap: float,
                  measure_col: int, k_arr: Array,
                  st: streaming.StreamState, ages: Array):
    """One round's data evolution: sample -> fused refresh -> index.

    Returns ``(index, sizes, staleness, refreshed hists, state)``.  The
    single definition of the streaming round sequence — the scan body
    and the legacy loop both call it, so the bit-for-bit parity between
    them cannot be broken by editing one copy.

    With ``fcfg.carry_dtype`` set the ``(K, C)`` hists and ``(K,)``
    staleness arrive at storage precision (see :func:`_stream_advance`);
    they are upcast here before any arithmetic so the whole refresh runs
    in f32 and only the carried state pays the diet.
    """
    with telemetry_lib.phase_scope("stream_refresh"):
        cdt = _carry_dtype(fcfg)
        if cdt is not None:
            st = dataclasses.replace(
                st, hists=st.hists.astype(jnp.float32),
                staleness=st.staleness.astype(jnp.float32))
        deltas, arrivals, st = process.sample(k_arr, st, fcfg.stream)
        hists_r, stats, stale = streaming.refresh(
            st.hists, deltas, arrivals, st.staleness, st.selected_prev,
            fcfg.stream, size_cap=size_cap)
        sizes_r = stats[..., 2]
        index = diversity.diversity_index_from_stats(
            div=stats[..., measure_col], data_sizes=sizes_r, ages=ages,
            weights=fcfg.index_weights)
        return index, sizes_r, stale, hists_r, st


def _stream_advance(st: streaming.StreamState, hists_r: Array,
                    stale: Array, selected: Array,
                    cdt=None) -> streaming.StreamState:
    """Post-decision carry update (driver-owned StreamState fields).

    ``cdt`` (from :func:`_carry_dtype`) is the storage dtype of the
    dieted carry: the refreshed hists/staleness are downcast on write
    and :func:`_stream_round` upcasts them on the next read.
    """
    if cdt is not None:
        hists_r = hists_r.astype(cdt)
        stale = stale.astype(cdt)
    return dataclasses.replace(st, hists=hists_r, staleness=stale,
                               selected_prev=selected,
                               round=st.round + 1)


def _diet_stream_state(st: streaming.StreamState,
                       cdt) -> streaming.StreamState:
    """Cast a fresh StreamState's carried stats to storage precision so
    the round-0 carry structure matches what :func:`_stream_advance`
    writes (scan carries must be dtype-stable)."""
    if cdt is None:
        return st
    return dataclasses.replace(st, hists=st.hists.astype(cdt),
                               staleness=st.staleness.astype(cdt))


def _make_sim(loss_fn: Callable, eval_fn: Callable, wcfg, scfg, fcfg,
              capacity: int, eval_every: int) -> Callable:
    """Build the traceable whole-simulation function (no jit applied).

    The returned ``sim(params, images, labels, mask, sizes, hists,
    test_x, test_labels, net, key)`` runs all ``fcfg.num_rounds`` rounds
    as a single ``lax.scan`` and returns ``(final_params, RoundMetrics)``.
    Evaluation is folded into the scan at the static ``eval_every``
    stride via ``lax.cond`` on a per-round flag carried as scan inputs —
    the flag is un-batched under the scenario vmap, so skipped rounds
    skip the eval computation in the batched program too.

    With ``fcfg.stream`` set, the scan carry additionally holds a
    :class:`streaming.StreamState`: each round samples count deltas from
    the arrival process, refreshes the class-count matrix / diversity
    stats / staleness in one fused pass (``streaming.refresh``), and
    feeds the *refreshed* sizes and index — plus the staleness signal —
    into scheduling and training (DESIGN.md §7).

    With ``fcfg.compression`` set, the carry additionally holds the
    ``(K, P)`` error-feedback residual (DESIGN.md §9): each round the
    codec's per-device payload bits price scheduling and Sub2, the
    round's updates go through the fused residual-accumulate ->
    compress -> dequantize pass, and the residual advances for the
    devices that transmitted.  Streaming and compression compose — the
    carry simply holds both extras.
    """
    trainer = make_local_trainer(loss_fn, fcfg)
    max_steps = _max_local_steps(fcfg, capacity)
    sch = _sched_cfg(scfg, fcfg)
    do_eval = jnp.asarray(_eval_mask(fcfg.num_rounds, eval_every))
    n_cap = fcfg.dispatch_cap
    if n_cap is not None and n_cap < 1:
        raise ValueError(f"dispatch_cap must be >= 1, got {n_cap}")
    cdt = _carry_dtype(fcfg)
    stream = fcfg.stream
    if stream is not None:
        process, size_cap, measure_col = _stream_setup(fcfg, capacity)
    comp = fcfg.compression
    if comp is not None:
        codec = _comp_setup(fcfg)
    flt = faults.active(fcfg.faults)
    exp_mult = faults.expected_time_mult(flt) if flt is not None else 1.0
    tel = telemetry_lib.active(fcfg.telemetry)
    sig_fn = _make_sig_fn(loss_fn, fcfg, capacity) \
        if (tel is not None and tel.signals) else None

    def sim(params: Params, images: Array, labels: Array, mask: Array,
            sizes: Array, hists: Array, test_x: Array, test_labels: Array,
            net: wireless.NetworkState, key: Array
            ) -> Tuple[Params, RoundMetrics]:
        k_dev = sizes.shape[0]
        # Chronic per-device drop rates (DESIGN.md §10): drawn once per
        # scenario off the *pristine* scenario key (folded, before the
        # streaming init split, so every other stream is untouched) and
        # held fixed across rounds.  None unless chronic_spread > 0 —
        # the i.i.d. fault path stays bitwise identical.
        drop_rates = faults.chronic_rates(
            jax.random.fold_in(key, 0xC407), k_dev, flt) \
            if flt is not None else None
        if stream is not None:
            key, k_init = jax.random.split(key)
            state0 = _diet_stream_state(
                process.init(k_init, hists, stream), cdt)
        if comp is not None:
            residual0 = jnp.zeros((k_dev, flat_param_size(params)),
                                  cdt or jnp.float32)

        def body(carry, do_ev):
            params, ages, key = carry[:3]
            pos = 3
            if stream is not None:
                st = carry[pos]
                pos += 1
            if comp is not None:
                residual = carry[pos]
                pos += 1
            if flt is not None:
                rel = carry[pos]
                pos += 1
            if sig_fn is not None:
                sigst = carry[pos]
            # One extra split for streaming, appended at the end; the
            # fault stream is *folded* off the carried key instead of
            # widening the split, because ``split(key, n)`` re-keys every
            # output when ``n`` changes — folding keeps every other
            # stream bitwise identical, so an inert FaultConfig (all
            # probabilities zero) reproduces ``faults=None`` exactly
            # (``tests/test_faults.py``).
            n_keys = 4 + (stream is not None)
            subkeys = jax.random.split(key, n_keys)
            key, k_fade, k_sched, k_train = subkeys[:4]
            if stream is not None:
                k_arr = subkeys[4]
            if flt is not None:
                k_fault = jax.random.fold_in(key, 0xFA17)
            if stream is None:
                index = diversity.diversity_index(
                    label_hists=hists, data_sizes=sizes, ages=ages,
                    weights=fcfg.index_weights, measure=fcfg.measure)
                sizes_r, stale = sizes, None
            else:
                index, sizes_r, stale, hists_r, st = _stream_round(
                    process, fcfg, size_cap, measure_col, k_arr, st, ages)
            gains = wireless.sample_fading(k_fade, net)
            payload = codec.payload_bits(comp, wcfg, gains, index) \
                if comp is not None else None
            # Scheduling prices retry-inflated bits (expected airtime
            # multiplier, a static constant) so Sub2's deadline reserves
            # the retransmission window before it happens.
            payload_sched = bandwidth.effective_payload_bits(
                payload, exp_mult, wcfg, gains) if flt is not None \
                else payload
            with telemetry_lib.phase_scope("schedule"):
                result = scheduler.schedule_impl(
                    k_sched, index, ages, sizes_r, gains, net, wcfg, sch,
                    staleness=stale, payload_bits=payload_sched,
                    reliability=rel if flt is not None else None)
            selected = result.selected
            admitted = selected
            # Dense-block dispatch (DESIGN.md §11): the plan runs right
            # after scheduling so faults, training, ages, reliability
            # and metrics all see the *realized* (post-drop) selection.
            if n_cap is None:
                didx = None
                n_dropped = jnp.zeros((), jnp.int32)
            else:
                didx, selected, n_dropped = dispatch_plan(selected, n_cap)
            if flt is None:
                ok = selected
                draw = None
                if n_cap is None:
                    energy = result.energy
                    round_time = result.round_time
                else:
                    energy, round_time = _dispatch_accounting(result,
                                                              selected)
            else:
                draw = faults.sample_faults(k_fault, gains, net, flt,
                                            drop_rates)
                ok, energy, round_time = faults.apply_faults(
                    draw, selected, result.alpha, result.t_train, gains,
                    net, wcfg, payload, flt)
            if comp is None:
                if flt is None:
                    out = _train_round(trainer, max_steps, fcfg, params,
                                       images, labels, mask, sizes_r,
                                       selected, k_train,
                                       dispatch_idx=didx, sig_fn=sig_fn)
                else:
                    out = _train_round_faulty(
                        trainer, max_steps, fcfg, params, images, labels,
                        mask, sizes_r, selected, ok, k_train,
                        dispatch_idx=didx, sig_fn=sig_fn)
                if sig_fn is not None:
                    params, obs = out
                else:
                    params = out
            else:
                out = _train_round_compressed(
                    trainer, max_steps, fcfg, codec, params, images,
                    labels, mask, sizes_r, selected, k_train, residual,
                    gains, index,
                    success=draw.success if flt is not None else None,
                    dispatch_idx=didx, sig_fn=sig_fn)
                if sig_fn is not None:
                    params, residual, obs = out
                else:
                    params, residual = out
            # Learning-signal carry (DESIGN.md §14): fold this round's
            # delivered observations in *before* the frame is built, so
            # the frame snapshots the exact post-round state a
            # learning-signal scheduler would rank on next round.
            if sig_fn is not None:
                loss_delta, upd_norm = obs
                sigst = telemetry_health.signal_update(
                    sigst, ok, loss_delta, upd_norm, energy)
            # Telemetry frame (DESIGN.md §13): built *before* the
            # ages/reliability carry updates so the trace records the
            # signals the scheduler actually saw.  Pure observer — no
            # PRNG draws, nothing feeds back — and statically absent
            # with telemetry=None (the bitwise contract).
            if tel is not None:
                frame = telemetry_record.round_frame(
                    tel, result=result, admitted=admitted,
                    sel_eff=selected, ok=ok, energy=energy,
                    payload_bits=payload, gains=gains, net=net,
                    wcfg=wcfg, sch=sch, key_sched=k_sched, index=index,
                    ages=ages, staleness=stale,
                    reliability=rel if flt is not None else None,
                    draw=draw,
                    signals=telemetry_health.signals_frame(
                        sigst, ok, loss_delta, upd_norm)
                    if sig_fn is not None else None)
            # Participation = delivered: ages reset and streaming
            # backlog clears only for uploads that landed.
            ages = jnp.where(ok > 0.0, 0, ages + 1)
            if flt is not None:
                rel = faults.reliability_update(rel, selected, ok, flt)
            acc = jax.lax.cond(
                do_ev,
                lambda p: jnp.asarray(eval_fn(p, test_x, test_labels),
                                      jnp.float32),
                lambda p: jnp.full((), jnp.nan, jnp.float32),
                params)
            met = RoundMetrics(
                accuracy=acc,
                n_selected=jnp.sum(selected).astype(jnp.int32),
                round_time=round_time,
                energy=energy,
                energy_total=jnp.sum(energy),
                selected=selected,
                iterations=result.iterations,
                n_success=jnp.sum(ok).astype(jnp.int32),
                n_dropped=n_dropped,
            )
            out = (params, ages, key)
            if stream is not None:
                out += (_stream_advance(st, hists_r, stale, ok, cdt),)
            if comp is not None:
                out += (residual,)
            if flt is not None:
                out += (rel,)
            if sig_fn is not None:
                out += (sigst,)
            if tel is not None:
                return out, (met, frame)
            return out, met

        ages0 = jnp.zeros((k_dev,), jnp.int32)
        carry0 = (params, ages0, key)
        if stream is not None:
            carry0 += (state0,)
        if comp is not None:
            carry0 += (residual0,)
        if flt is not None:
            carry0 += (jnp.ones((k_dev,), jnp.float32),)
        if sig_fn is not None:
            carry0 += (telemetry_health.signal_init(k_dev),)
        if tel is not None:
            out_carry, (metrics, frames) = jax.lax.scan(body, carry0,
                                                        do_eval)
            return out_carry[0], metrics, frames
        out_carry, metrics = jax.lax.scan(body, carry0, do_eval)
        return out_carry[0], metrics

    return sim


def make_feel_sim(*, loss_fn: Callable, eval_fn: Callable,
                  wcfg: wireless.WirelessConfig,
                  scfg: scheduler.SchedulerConfig, fcfg: FLConfig,
                  capacity: int, eval_every: int = 1,
                  donate_params: bool = False) -> Callable:
    """Jitted single-scenario simulation (see :func:`_make_sim`).

    ``donate_params=True`` donates the initial-params argument to the
    scan carry, letting XLA alias the global model's input buffer with
    the returned final params instead of holding both across the whole
    scan — at paper scale (CNN params x K client replicas inside the
    round body) that is the difference between 2x and 1x of the global
    model at peak.  The caller must not reuse the donated arrays after
    the call (pass a fresh copy per invocation in sweeps); CPU-backend
    JAX may decline the donation with a warning, which is harmless.
    """
    if fcfg.events is not None:
        sim = events_lib._make_event_sim(loss_fn, eval_fn, wcfg, scfg,
                                         fcfg, capacity, eval_every)
    else:
        sim = _make_sim(loss_fn, eval_fn, wcfg, scfg, fcfg, capacity,
                        eval_every)
    return jax.jit(sim, donate_argnums=(0,) if donate_params else ())


def scenario_keys(base_key: Array, start: int, count: int) -> Array:
    """Per-scenario PRNG keys from *global* scenario indices.

    ``key_i = fold_in(base_key, i)`` for ``i in [start, start + count)``:
    scenario ``i``'s stream depends only on ``(base_key, i)``, never on
    how a sweep is chunked or how many devices execute the chunk — the
    seed-derivation contract the sweep engine (``repro.sweep``) and the
    benchmark harness rely on (``tests/test_sweep.py``).  Contrast
    ``jax.random.split(key, S)``, whose streams change with ``S``.
    """
    idx = jnp.arange(start, start + count, dtype=jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)


def tile_params(params: Params, num_scenarios: int) -> Params:
    """Stack ``num_scenarios`` copies of ``params`` along a new axis 0.

    Produces the fresh ``(S, ...)`` buffers the donating batch driver
    consumes (see :func:`make_feel_sim_batch`): the caller's original
    params stay untouched, and the tiled copies are safe to hand over.
    """
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (num_scenarios,) + a.shape),
        params)


def make_feel_sim_batch(*, loss_fn: Callable, eval_fn: Callable,
                        wcfg: wireless.WirelessConfig,
                        scfg: scheduler.SchedulerConfig, fcfg: FLConfig,
                        capacity: int, eval_every: int = 1,
                        donate_params: bool = False,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        scenario_axis: str = "scenario") -> Callable:
    """Jitted S-scenario simulation: vmap over (net, key) only.

    Dataset and initial params broadcast; each scenario sees its own
    network realization and PRNG stream — the paper's Monte-Carlo
    averaging (Figs. 2-6) as one SPMD program.

    ``donate_params=True`` changes the params contract: pass leaves with
    a leading ``(S,)`` axis (:func:`tile_params`) and they are donated
    into the vmapped scan carry.  A *broadcast* input cannot be donated
    — XLA declines aliasing a ``(P,)`` buffer against the stacked
    ``(S, P)`` carry/output and silently copies — whereas the pre-tiled
    buffer is exactly the carry's shape, so the donation is actually
    usable (asserted in ``tests/test_federated.py``).  The batched carry
    materializes either way; donating it avoids holding a second copy
    across the whole scan.

    ``mesh`` is the spec-in/spec-out entry (DESIGN.md §8): pass a mesh
    carrying ``scenario_axis`` and the vmapped sim is wrapped in
    ``shard_map`` with the scenario axis of ``nets``/``keys`` (and the
    tiled params, when donating) partitioned over it and everything else
    replicated — each device runs the same vmapped scan on its
    ``S / mesh.shape[scenario_axis]`` local scenarios, with no
    cross-device communication (scenarios are independent by
    construction).  The batched ``fused_pgd`` / ``stream_update`` kernel
    lanes and ``donate_params`` compose unchanged: both operate on the
    per-shard local batch.  ``S`` must be divisible by the mesh axis
    size (the sweep engine falls back to ``mesh=None`` otherwise).
    """
    if fcfg.events is not None:
        sim = events_lib._make_event_sim(loss_fn, eval_fn, wcfg, scfg,
                                         fcfg, capacity, eval_every)
    else:
        sim = _make_sim(loss_fn, eval_fn, wcfg, scfg, fcfg, capacity,
                        eval_every)
    vsim = jax.vmap(sim, in_axes=(0 if donate_params else None,
                                  None, None, None, None,
                                  None, None, None, 0, 0))
    if mesh is not None:
        sharded = jax.sharding.PartitionSpec(scenario_axis)
        rep = jax.sharding.PartitionSpec()
        # Telemetry adds a third (frames) output with the same leading
        # scenario axis as params/metrics — sharded identically.
        n_out = 3 if telemetry_lib.active(fcfg.telemetry) is not None \
            else 2
        vsim = jax.shard_map(
            vsim, mesh=mesh,
            in_specs=(sharded if donate_params else rep,
                      rep, rep, rep, rep, rep, rep, rep,
                      sharded, sharded),
            out_specs=(sharded,) * n_out,
            check_vma=False)
    return jax.jit(vsim, donate_argnums=(0,) if donate_params else ())


# ---------------------------------------------------------------------------
# Host-side adapters: stacked metrics -> RoundRecord list
# ---------------------------------------------------------------------------

def metrics_to_records(metrics: RoundMetrics) -> List[RoundRecord]:
    """One device->host transfer for the whole run's records."""
    m = jax.device_get(metrics)
    history: List[RoundRecord] = []
    for r in range(m.selected.shape[0]):
        n_sel = int(m.n_selected[r])
        e_total = float(m.energy_total[r])
        history.append(RoundRecord(
            round=r, accuracy=float(m.accuracy[r]), n_selected=n_sel,
            round_time=float(m.round_time[r]),
            energy_total=e_total,
            energy_per_device=e_total / max(n_sel, 1),
            selected=np.asarray(m.selected[r]),
            n_success=int(m.n_success[r]),
            n_dropped=int(m.n_dropped[r]),
        ))
    return history


def batch_metrics_to_records(metrics: RoundMetrics
                             ) -> List[List[RoundRecord]]:
    """Per-scenario record lists from (S, R, ...) stacked metrics.

    One device->host transfer for the whole batch; scenario slicing
    happens on the host copies.
    """
    host = jax.device_get(metrics)
    num_scenarios = host.selected.shape[0]
    return [
        metrics_to_records(jax.tree_util.tree_map(lambda a, s=s: a[s],
                                                  host))
        for s in range(num_scenarios)
    ]


def client_histograms(data: partition_lib.ClientDataset,
                      num_classes: int) -> Array:
    """On-device statistics reported to the server (Alg. 1 line 5).

    Public because sweep harnesses (``benchmarks/fl_e2e.py``) need the
    same histograms to feed ``make_feel_sim(_batch)`` directly.
    """
    return jax.vmap(
        lambda lab, m: diversity.label_histogram(lab, m, num_classes)
    )(data.labels, data.mask)


# ---------------------------------------------------------------------------
# Full training drivers (Alg. 1)
# ---------------------------------------------------------------------------

def run_federated(
    *,
    init_params: Params,
    loss_fn: Callable,
    eval_fn: Callable[[Params, Array, Array], Array],
    data: partition_lib.ClientDataset,
    net: wireless.NetworkState,
    wcfg: wireless.WirelessConfig,
    scfg: scheduler.SchedulerConfig,
    fcfg: FLConfig,
    key: Array,
    eval_every: int = 1,
    donate_params: bool = False,
) -> tuple[Params, List[RoundRecord]]:
    """Run ``num_rounds`` of FEEL; returns final params + per-round records.

    Scan-over-rounds driver: the whole simulation compiles to one XLA
    program (no per-round dispatch or host syncs).  Bit-for-bit
    consistent with :func:`run_federated_loop` for the same key.
    ``donate_params=True`` hands ``init_params`` to the scan carry (the
    caller must not reuse those arrays afterwards — see
    :func:`make_feel_sim`).

    With ``fcfg.telemetry`` set (DESIGN.md §13) the return grows a
    third element: the stacked per-round telemetry frame dict from
    ``repro.telemetry.record`` — callers with telemetry off see the
    historical 2-tuple unchanged.
    """
    sim = make_feel_sim(loss_fn=loss_fn, eval_fn=eval_fn, wcfg=wcfg,
                        scfg=scfg, fcfg=fcfg, capacity=data.capacity,
                        eval_every=eval_every, donate_params=donate_params)
    hists = client_histograms(data, fcfg.num_classes)
    test_x = synthetic.to_float(data.test_images)
    out = sim(init_params, data.images, data.labels, data.mask,
              data.sizes, hists, test_x, data.test_labels, net, key)
    if len(out) == 3:
        params, metrics, frames = out
        return params, metrics_to_records(metrics), frames
    params, metrics = out
    return params, metrics_to_records(metrics)


def run_federated_batch(
    *,
    init_params: Params,
    loss_fn: Callable,
    eval_fn: Callable[[Params, Array, Array], Array],
    data: partition_lib.ClientDataset,
    nets: wireless.NetworkState,
    wcfg: wireless.WirelessConfig,
    scfg: scheduler.SchedulerConfig,
    fcfg: FLConfig,
    keys: Array,
    eval_every: int = 1,
    donate_params: bool = False,
) -> tuple[Params, RoundMetrics]:
    """Run S independent FEEL scenarios as one vmapped scan.

    Args:
      nets: stacked :class:`wireless.NetworkState` with leading ``(S,)``
        leaf axis (see :func:`wireless.sample_networks`).
      keys: ``(S,)`` PRNG keys, one stream per scenario.
      donate_params: donate the initial params into the vmapped scan
        carry.  The caller's ``init_params`` stay valid: fresh ``(S,
        ...)`` tiled buffers (:func:`tile_params`) are built here and
        those are donated (see :func:`make_feel_sim_batch`).

    Returns:
      (params, metrics): final params stacked ``(S, ...)`` per leaf and
      :class:`RoundMetrics` with leading ``(S, R, ...)`` axes.  Use
      :func:`batch_metrics_to_records` for per-scenario record lists.
      With ``fcfg.telemetry`` set a third element joins: the stacked
      frame dict with leading ``(S, R, ...)`` axes — scenario ``i`` of
      the batch is bitwise the single run's frames (batch == singles,
      ``tests/test_telemetry.py``).
    """
    sim = make_feel_sim_batch(loss_fn=loss_fn, eval_fn=eval_fn, wcfg=wcfg,
                              scfg=scfg, fcfg=fcfg, capacity=data.capacity,
                              eval_every=eval_every,
                              donate_params=donate_params)
    hists = client_histograms(data, fcfg.num_classes)
    test_x = synthetic.to_float(data.test_images)
    if donate_params:
        init_params = tile_params(init_params, keys.shape[0])
    return sim(init_params, data.images, data.labels, data.mask,
               data.sizes, hists, test_x, data.test_labels, nets, keys)


def run_federated_loop(
    *,
    init_params: Params,
    loss_fn: Callable,
    eval_fn: Callable[[Params, Array, Array], Array],
    data: partition_lib.ClientDataset,
    net: wireless.NetworkState,
    wcfg: wireless.WirelessConfig,
    scfg: scheduler.SchedulerConfig,
    fcfg: FLConfig,
    key: Array,
    eval_every: int = 1,
) -> tuple[Params, List[RoundRecord]]:
    """Legacy host-side per-round loop (reference implementation).

    Dispatches two jits and forces several host syncs per round; kept for
    the scan-parity tests and the ``fl_e2e`` old-vs-new benchmark.
    Honors ``fcfg.stream`` with the same per-round sequence (and key
    splits) as the scan driver, so streaming runs stay bit-for-bit
    comparable (``tests/test_streaming.py``).  With ``fcfg.telemetry``
    set the return grows a third element — the stacked per-round frame
    dict (host numpy), same field set as the scan driver's.
    """
    if fcfg.events is not None:
        raise ValueError(
            "FLConfig.events is set: the event-driven drivers have no "
            "legacy per-round loop (their reference is the synchronous-"
            "limit parity contract, tests/test_events.py) — use "
            "make_feel_sim / make_feel_sim_batch")
    k_dev = data.num_devices
    sig_fn = _make_sig_fn(loss_fn, fcfg, data.capacity) \
        if _sig_enabled(fcfg) else None
    round_fn = make_round_fn(loss_fn, fcfg, data.capacity, sig_fn=sig_fn)
    hists = client_histograms(data, fcfg.num_classes)
    n_cap = fcfg.dispatch_cap
    if n_cap is not None and n_cap < 1:
        raise ValueError(f"dispatch_cap must be >= 1, got {n_cap}")
    cdt = _carry_dtype(fcfg)
    flt = faults.active(fcfg.faults)
    # Chronic rates off the pristine scenario key, before the streaming
    # init split — same derivation as the scan driver (parity contract).
    drop_rates = faults.chronic_rates(
        jax.random.fold_in(key, 0xC407), k_dev, flt) \
        if flt is not None else None
    stream = fcfg.stream
    if stream is not None:
        process, size_cap, measure_col = _stream_setup(fcfg, data.capacity)
        key, k_init = jax.random.split(key)
        st = _diet_stream_state(process.init(k_init, hists, stream), cdt)
    comp = fcfg.compression
    if comp is not None:
        codec = _comp_setup(fcfg)
        residual = jnp.zeros((k_dev, flat_param_size(init_params)),
                             cdt or jnp.float32)
    exp_mult = faults.expected_time_mult(flt) if flt is not None else 1.0
    rel = jnp.ones((k_dev,), jnp.float32) if flt is not None else None
    sch = _sched_cfg(scfg, fcfg)
    tel = telemetry_lib.active(fcfg.telemetry)
    frames_host: List[dict] = []
    if tel is not None:
        # Jitted (not eager) on purpose, like ``faults.fault_step`` and
        # ``_dispatch_plan_jit``: the scan driver compiles the frame
        # fused, and op-at-a-time eager arithmetic is the one way the
        # loop's recorded floats could drift off the scan's.
        @jax.jit
        def _frame_fn(result, admitted, sel_eff, ok, energy, payload,
                      gains, net_, k_sched, index, ages_, stale, rel_,
                      draw, sigst, loss_delta, upd_norm):
            return telemetry_record.round_frame(
                tel, result=result, admitted=admitted, sel_eff=sel_eff,
                ok=ok, energy=energy, payload_bits=payload, gains=gains,
                net=net_, wcfg=wcfg, sch=sch, key_sched=k_sched,
                index=index, ages=ages_, staleness=stale,
                reliability=rel_, draw=draw,
                signals=telemetry_health.signals_frame(
                    sigst, ok, loss_delta, upd_norm)
                if sigst is not None else None)

    ages = jnp.zeros((k_dev,), jnp.int32)
    params = init_params
    sigst = telemetry_health.signal_init(k_dev) \
        if sig_fn is not None else None
    history: List[RoundRecord] = []
    test_x = synthetic.to_float(data.test_images)

    for r in range(fcfg.num_rounds):
        # Same split counts and order as the scan body (parity contract):
        # base 4, +1 streaming arrivals; the fault draw folds off the
        # carried key (never widens the split — inert-config identity).
        n_keys = 4 + (stream is not None)
        subkeys = jax.random.split(key, n_keys)
        key, k_fade, k_sched, k_train = subkeys[:4]
        if flt is not None:
            k_fault = jax.random.fold_in(key, 0xFA17)
        if stream is None:
            index = diversity.diversity_index(
                label_hists=hists, data_sizes=data.sizes, ages=ages,
                weights=fcfg.index_weights, measure=fcfg.measure)
            sizes_r, stale = data.sizes, None
        else:
            index, sizes_r, stale, hists_r, st = _stream_round(
                process, fcfg, size_cap, measure_col, subkeys[4], st, ages)
        gains = wireless.sample_fading(k_fade, net)
        payload = codec.payload_bits(comp, wcfg, gains, index) \
            if comp is not None else None
        payload_sched = bandwidth.effective_payload_bits(
            payload, exp_mult, wcfg, gains) if flt is not None else payload
        result = scheduler.schedule(k_sched, index, ages, sizes_r,
                                    gains, net, wcfg, sch, stale,
                                    payload_sched, rel)
        selected = result.selected
        admitted = selected
        # Same dispatch plan + re-pricing as the scan body, through the
        # jitted entries (parity: fused == loop bitwise).
        if n_cap is None:
            didx = None
            n_dropped = jnp.zeros((), jnp.int32)
        else:
            didx, selected, n_dropped = _dispatch_plan_jit(selected, n_cap)
        if flt is None:
            ok = selected
            draw = None
            if n_cap is None:
                energy = result.energy
                round_time = result.round_time
            else:
                energy, round_time = _dispatch_accounting_jit(result,
                                                              selected)
        else:
            # Jitted (not eager) on purpose: the scan driver compiles
            # the same arithmetic fused, and CPU XLA's FMA contraction
            # rounds differently from the op-at-a-time eager schedule.
            draw, ok, energy, round_time = faults.fault_step(
                k_fault, selected, result.alpha, result.t_train, gains,
                net, wcfg, payload, flt, drop_rates)
        if comp is None:
            if flt is None:
                out = round_fn(params, data.images, data.labels,
                               data.mask, sizes_r, selected, k_train,
                               dispatch_idx=didx)
            else:
                out = round_fn(params, data.images, data.labels,
                               data.mask, sizes_r, selected, ok,
                               k_train, dispatch_idx=didx)
            if sig_fn is not None:
                params, obs = out
            else:
                params = out
        else:
            out = round_fn(
                params, data.images, data.labels, data.mask, sizes_r,
                selected, k_train, residual, gains, index,
                success=draw.success if flt is not None else None,
                dispatch_idx=didx)
            if sig_fn is not None:
                params, residual, obs = out
            else:
                params, residual = out
        # Signal carry folds in before the frame, same as the scan.
        loss_delta = upd_norm = None
        if sig_fn is not None:
            loss_delta, upd_norm = obs
            sigst = _signal_update_jit(sigst, ok, loss_delta, upd_norm,
                                       energy)
        # Frame before the ages/reliability updates — the trace records
        # the signals the scheduler saw (same placement as the scan).
        if tel is not None:
            frames_host.append(jax.device_get(_frame_fn(
                result, admitted, selected, ok, energy, payload, gains,
                net, k_sched, index, ages, stale, rel, draw,
                sigst, loss_delta, upd_norm)))
        ages = jnp.where(ok > 0.0, 0, ages + 1)
        if flt is not None:
            rel = faults.reliability_update(rel, selected, ok, flt)
        if stream is not None:
            st = _stream_advance(st, hists_r, stale, ok, cdt)

        if (r % eval_every) == 0 or r == fcfg.num_rounds - 1:
            acc = float(eval_fn(params, test_x, data.test_labels))
        else:
            acc = float("nan")
        n_sel = int(jnp.sum(selected))
        e_total = float(jnp.sum(energy))
        history.append(RoundRecord(
            round=r, accuracy=acc, n_selected=n_sel,
            round_time=float(round_time),
            energy_total=e_total,
            energy_per_device=e_total / max(n_sel, 1),
            selected=np.asarray(selected),
            n_success=int(jnp.sum(ok)),
            n_dropped=int(n_dropped),
        ))
    if tel is not None:
        frames = {name: np.stack([f[name] for f in frames_host])
                  for name in (frames_host[0] if frames_host else ())}
        return params, history, frames
    return params, history
