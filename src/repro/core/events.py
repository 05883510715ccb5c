"""Event-driven asynchronous FEEL (DESIGN.md §12).

The synchronous drivers (``core.federated``) advance the world one
*round* at a time: every admitted device trains, uploads, and the
server aggregates before anything else happens.  Real edge fleets are
not synchronous — devices come and go (charging, diurnal usage,
connectivity churn), uploads land whenever compute + channel time
elapses, and an asynchronous server applies updates as they arrive.
This module reframes the simulation as a jitted ``lax.scan`` over
*events* (scheduling ticks):

1. **Availability** — a per-device availability process gates which
   devices the scheduler may admit this tick: ``always`` (the
   synchronous limit), ``churn`` (i.i.d. Bernoulli presence), and
   ``diurnal`` (a correlated day/night activity wave whose shared phase
   and per-device jitter are drawn once per scenario off the scenario
   seed).  Processes register by name (:func:`register_availability`),
   mirroring the arrival-process and allocator registries.
2. **Dispatch** — free (available, not in-flight) devices are ranked
   and admitted by the *same* scheduling stack as the synchronous
   drivers (``scheduler.schedule_impl`` with staleness / payload /
   reliability signals), composed with the dense-block dispatch cap,
   the fault subsystem's retransmission pricing, and the compressed
   uplink's per-device payload bits.  Admitted devices train
   immediately on the current global model; their (flattened) updates
   enter a per-device pending slot with an *arrival time* of
   ``now + t_train + t_up`` (retry-stretched under faults) and a
   *birth version* (the global model version they trained from).
3. **Buffered aggregation** — uploads whose arrival time has elapsed
   join the server buffer; once the buffer holds ``buffer_size``
   updates the server flushes: a staleness-weighted FedAvg in update
   form, ``g' = g + sum_k w_k s(tau_k) (w^k - g)``, where
   ``s(tau) = (1 + tau)^-gamma`` discounts an update by how many model
   versions elapsed since its dispatch (the FedBuff rule, Nguyen et
   al.; 2305.01238's async-vs-sync probe).  ``gamma`` is
   ``EventConfig.staleness_decay`` — the update-weighting
   generalization of the scheduler's ``staleness_boost`` *priority*
   machinery.  The flush optionally runs through the Pallas
   ``fedavg_agg_stale`` kernel lane (``use_kernel_agg``).

**Synchronous-limit parity contract** (``tests/test_events.py``): with
every device always available (``availability="always"``), whole-cohort
ticks (``tick_horizon=0``), zero staleness decay, and a ``buffer_size``
no larger than the per-tick cohort, every dispatched update arrives and
flushes within its own tick with staleness 0 — and the event scan
reproduces the synchronous scan driver **bitwise**.  The contract is
stated against the update-form aggregation path (the fault-aware
synchronous round and the compressed round both use it); the key
discipline is copied from ``federated._make_sim`` exactly — the same
``split`` widths, the same ``fold_in`` salts for the fault and chronic
streams, and a *folded* (never split) availability stream — so every
PRNG draw matches the synchronous trajectory draw for draw.

The event drivers hang off ``FLConfig.events``: ``federated.
make_feel_sim`` / ``make_feel_sim_batch`` delegate here when the field
is set, so the sweep engine, the batch driver's vmap/shard_map lanes,
buffer donation, and the ``async`` sweep axis all compose without any
caller change.  ``batch == S singles`` holds bitwise like every other
subsystem (the availability draws are keyed off the per-scenario
stream).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bandwidth, compression, diversity, faults, \
    scheduler, streaming, wireless
from repro import telemetry as telemetry_lib
from repro.telemetry import health as telemetry_health
from repro.telemetry import record as telemetry_record

Array = jax.Array
Params = Any

# fold_in salts for the event-only PRNG streams: folded off the carried
# (availability) / pristine scenario (phases) key, never a widened
# split, so the synchronous drivers' streams stay bitwise untouched.
_AVAIL_SALT = 0xA7A1
_PHASE_SALT = 0xD1A7


@dataclasses.dataclass(frozen=True)
class EventConfig:
    """Static event-scan knobs (hashable; rides on ``FLConfig.events``).

    ``tick_horizon`` is the wall-clock length of one scheduling tick in
    seconds: ``0.0`` (default) means whole-cohort ticks — the clock
    advances by the dispatched cohort's makespan, so every upload lands
    within its own tick (the synchronous limit).  A positive horizon
    caps the tick length instead: slow devices stay in flight across
    ticks, arrive late, and their updates carry genuine model-version
    staleness into the buffered flush.

    ``num_events`` is the scan length (``None`` = ``fcfg.num_rounds``);
    under a short horizon one synchronous round's work spreads over
    several events, so async sweeps typically raise it.
    """

    availability: str = "always"   # availability-process registry name
    avail_prob: float = 0.9        # churn: per-tick presence probability
    period: float = 24.0           # diurnal: ticks per activity cycle
    phase_spread: float = 0.5      # diurnal: per-device phase jitter (rad)
    duty: float = 0.5              # diurnal: mean availability fraction
    buffer_size: int = 1           # arrived updates needed to flush
    staleness_decay: float = 0.0   # gamma of the (1+tau)^-gamma weight
    tick_horizon: float = 0.0      # 0 = whole-cohort ticks (sync limit)
    num_events: Optional[int] = None


# ---------------------------------------------------------------------------
# Availability processes
# ---------------------------------------------------------------------------

@runtime_checkable
class AvailabilityProcess(Protocol):
    """Per-device availability gate consumed by the event drivers."""

    def init(self, key: Array, k: int, cfg: EventConfig) -> Array:
        """Once-per-scenario state (e.g. diurnal phases), shape (K,).

        ``key`` is folded off the *pristine* scenario key, so a
        process that ignores it (``always``) leaves every other stream
        bitwise untouched."""
        ...

    def sample(self, key: Array, state: Array, tick: Array,
               cfg: EventConfig) -> Array:
        """(K,) {0, 1} availability mask for one tick (traceable)."""
        ...


@dataclasses.dataclass(frozen=True)
class AlwaysOn:
    """Every device available every tick — the synchronous limit."""

    def init(self, key: Array, k: int, cfg: EventConfig) -> Array:
        del key, cfg
        return jnp.zeros((k,), jnp.float32)

    def sample(self, key: Array, state: Array, tick: Array,
               cfg: EventConfig) -> Array:
        del key, tick, cfg
        return jnp.ones_like(state)


@dataclasses.dataclass(frozen=True)
class Churn:
    """I.i.d. Bernoulli presence: each device is reachable with
    probability ``avail_prob`` each tick, independently."""

    def init(self, key: Array, k: int, cfg: EventConfig) -> Array:
        del key, cfg
        return jnp.zeros((k,), jnp.float32)

    def sample(self, key: Array, state: Array, tick: Array,
               cfg: EventConfig) -> Array:
        del tick
        u = jax.random.uniform(key, state.shape)
        return (u < cfg.avail_prob).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class Diurnal:
    """Correlated day/night activity keyed off the scenario seed.

    One shared cycle phase per scenario plus Gaussian per-device jitter
    (``phase_spread``) — the fleet wakes and sleeps *together*, which
    is what starves a scheduler in ways independent churn cannot.  The
    per-tick availability probability is the sinusoidal activity level
    rescaled so its cycle mean is ``duty`` (exact for
    ``duty <= 0.5``; clipped above).
    """

    def init(self, key: Array, k: int, cfg: EventConfig) -> Array:
        k_shared, k_dev = jax.random.split(key)
        shared = jax.random.uniform(k_shared, (),
                                    maxval=2.0 * jnp.pi)
        jitter = cfg.phase_spread * jax.random.normal(k_dev, (k,))
        return shared + jitter

    def sample(self, key: Array, state: Array, tick: Array,
               cfg: EventConfig) -> Array:
        t = tick.astype(jnp.float32)
        level = 0.5 * (1.0 + jnp.sin(
            2.0 * jnp.pi * t / cfg.period + state))
        p = jnp.clip(2.0 * cfg.duty * level, 0.0, 1.0)
        u = jax.random.uniform(key, state.shape)
        return (u < p).astype(jnp.float32)


_PROCESSES: Dict[str, Callable[[], AvailabilityProcess]] = {}


def register_availability(name: str,
                          factory: Callable[[], AvailabilityProcess],
                          overwrite: bool = False) -> None:
    """Register an availability-process factory (zero-arg -> process)."""
    if name in _PROCESSES and not overwrite:
        raise ValueError(f"availability process {name!r} already "
                         f"registered")
    _PROCESSES[name] = factory


def availability_names() -> tuple[str, ...]:
    return tuple(sorted(_PROCESSES))


def get_availability(name: str) -> AvailabilityProcess:
    """Build the named availability process."""
    try:
        factory = _PROCESSES[name]
    except KeyError:
        raise ValueError(
            f"unknown availability process {name!r}; registered: "
            f"{availability_names()}") from None
    return factory()


register_availability("always", AlwaysOn)
register_availability("churn", Churn)
register_availability("diurnal", Diurnal)


# ---------------------------------------------------------------------------
# Staleness-weighted buffered flush
# ---------------------------------------------------------------------------

def staleness_multiplier(staleness: Array, decay: float) -> Array:
    """FedBuff-style update discount ``(1 + tau)^-gamma``.

    ``decay == 0`` returns exact ones (no pow in the program), which is
    what makes the zero-decay flush weights bitwise identical to the
    synchronous FedAvg weights (the parity contract)."""
    if decay == 0.0:
        return jnp.ones_like(staleness)
    return jnp.power(1.0 + staleness, -decay)


def buffered_flush(params: Params, rows: Array, weights: Array,
                   arrived: Array, stale_mult: Array,
                   use_kernel: bool = False) -> Params:
    """Apply one buffer flush in update form over flattened rows.

    ``g' = g + sum_k (w_k * m_k * s_k) row_k`` with ``weights`` already
    normalized by the caller, ``arrived`` the buffer-membership mask
    and ``stale_mult`` the per-update staleness discount.  The
    reduction is the broadcast-multiply-reduce of
    ``federated.fedavg_aggregate_masked`` on the concatenated layout —
    per-coordinate arithmetic identical to the per-leaf form, which is
    what the synchronous-limit contract leans on.  The kernel path is
    the ``fedavg_agg_stale`` Pallas lane.
    """
    p_leaves, p_treedef = jax.tree_util.tree_flatten(params)
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        agg = kernel_ops.fedavg_agg_stale(rows, weights, arrived,
                                          stale_mult)
    else:
        wm = weights * arrived * stale_mult
        agg = jnp.sum(wm[:, None] * rows, axis=0)
    outs, offset = [], 0
    for p in p_leaves:
        size = int(np.prod(p.shape))
        outs.append(p + agg[offset:offset + size].reshape(p.shape)
                    .astype(p.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(p_treedef, outs)


# ---------------------------------------------------------------------------
# The event scan
# ---------------------------------------------------------------------------

def _make_event_sim(loss_fn: Callable, eval_fn: Callable, wcfg, scfg,
                    fcfg, capacity: int, eval_every: int) -> Callable:
    """Build the traceable event simulation (no jit applied).

    Same signature as ``federated._make_sim``'s product — ``sim(params,
    images, labels, mask, sizes, hists, test_x, test_labels, net, key)
    -> (final_params, RoundMetrics)`` — so the batch driver's vmap /
    shard_map wrappers, buffer donation and the sweep engine reuse it
    unchanged.  One metrics row per *event*; ``round_time`` is the wall
    clock the tick consumed and ``n_success`` the uploads that landed.

    Every synchronous-round helper is reused, not reimplemented: the
    local trainer, the masked/dense-block training body, the streaming
    round, the codec pass, the fault draw + accounting, the scheduler
    config derivation.  The event machinery wraps them with the
    pending/buffer carry — and reduces to the identity in the
    synchronous limit (see the module docstring contract).
    """
    from repro.core import federated as fed

    ecfg = fcfg.events
    if ecfg is None:
        raise ValueError("FLConfig.events is None — use the synchronous "
                         "drivers (federated.make_feel_sim)")
    if ecfg.buffer_size < 1:
        raise ValueError(f"buffer_size must be >= 1, got "
                         f"{ecfg.buffer_size}")
    if ecfg.tick_horizon < 0.0:
        raise ValueError(f"tick_horizon must be >= 0, got "
                         f"{ecfg.tick_horizon}")
    avail_proc = get_availability(ecfg.availability)
    num_events = ecfg.num_events or fcfg.num_rounds

    trainer = fed.make_local_trainer(loss_fn, fcfg)
    sch = fed._sched_cfg(scfg, fcfg)
    do_eval = jnp.asarray(fed._eval_mask(num_events, eval_every))
    ticks = jnp.arange(num_events, dtype=jnp.int32)
    n_cap = fcfg.dispatch_cap
    if n_cap is not None and n_cap < 1:
        raise ValueError(f"dispatch_cap must be >= 1, got {n_cap}")
    cdt = fed._carry_dtype(fcfg)
    stream = fcfg.stream
    if stream is not None:
        process, size_cap, measure_col = fed._stream_setup(fcfg, capacity)
    comp = fcfg.compression
    if comp is not None:
        codec = fed._comp_setup(fcfg)
    flt = faults.active(fcfg.faults)
    exp_mult = faults.expected_time_mult(flt) if flt is not None else 1.0
    tel = telemetry_lib.active(fcfg.telemetry)
    sig_fn = fed._make_sig_fn(loss_fn, fcfg, capacity) \
        if (tel is not None and tel.signals) else None
    gamma = ecfg.staleness_decay
    buf_target = float(ecfg.buffer_size)
    horizon = float(ecfg.tick_horizon)

    def sim(params: Params, images: Array, labels: Array, mask: Array,
            sizes: Array, hists: Array, test_x: Array, test_labels: Array,
            net: wireless.NetworkState, key: Array
            ) -> Tuple[Params, "fed.RoundMetrics"]:
        k_dev = sizes.shape[0]
        lanes, max_steps = fed.local_train_launch(fcfg, k_dev, capacity)
        p_flat = fed.flat_param_size(params)
        # Once-per-scenario draws off the *pristine* scenario key —
        # folded before the streaming init split, exactly like the
        # synchronous driver, so every shared stream stays bitwise
        # identical between the two drivers.
        drop_rates = faults.chronic_rates(
            jax.random.fold_in(key, 0xC407), k_dev, flt) \
            if flt is not None else None
        avail_state = avail_proc.init(
            jax.random.fold_in(key, _PHASE_SALT), k_dev, ecfg)
        if stream is not None:
            key, k_init = jax.random.split(key)
            state0 = fed._diet_stream_state(
                process.init(k_init, hists, stream), cdt)
        if comp is not None:
            residual0 = jnp.zeros((k_dev, p_flat), cdt or jnp.float32)

        def body(carry, xs):
            do_ev, tick = xs
            (params, ages, key, clock, version, pend_rows, pend_mask,
             pend_size, pend_birth, pend_arrival) = carry[:10]
            pos = 10
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
            if cdt is not None:
                pend_rows = pend_rows.astype(jnp.float32)
            # Key discipline copied from the synchronous scan body:
            # same split widths, fault stream folded off the carried
            # key; the availability stream folds too (a widened split
            # would re-key everything and break the parity contract).
            n_keys = 4 + (stream is not None)
            subkeys = jax.random.split(key, n_keys)
            key, k_fade, k_sched, k_train = subkeys[:4]
            if stream is not None:
                k_arr = subkeys[4]
            if flt is not None:
                k_fault = jax.random.fold_in(key, 0xFA17)
            k_avail = jax.random.fold_in(key, _AVAIL_SALT)
            if stream is None:
                index = diversity.diversity_index(
                    label_hists=hists, data_sizes=sizes, ages=ages,
                    weights=fcfg.index_weights, measure=fcfg.measure)
                sizes_r, stale = sizes, None
            else:
                index, sizes_r, stale, hists_r, st = fed._stream_round(
                    process, fcfg, size_cap, measure_col, k_arr, st, ages)
            gains = wireless.sample_fading(k_fade, net)
            # Availability x in-flight gate.  Busy devices (update
            # pending or buffered-unapplied) cannot be re-dispatched;
            # unavailable devices rank at zero priority and are
            # hard-masked out of the admitted set.  In the synchronous
            # limit both masks are all-ones and every expression below
            # passes its input through bitwise unchanged.
            avail = avail_proc.sample(k_avail, avail_state, tick, ecfg)
            free = avail * (1.0 - pend_mask)
            index_g = jnp.where(free > 0.0, index, 0.0)
            payload = codec.payload_bits(comp, wcfg, gains, index_g) \
                if comp is not None else None
            payload_sched = bandwidth.effective_payload_bits(
                payload, exp_mult, wcfg, gains) if flt is not None \
                else payload
            with telemetry_lib.phase_scope("schedule"):
                result = scheduler.schedule_impl(
                    k_sched, index_g, ages, sizes_r, gains, net, wcfg,
                    sch, staleness=stale, payload_bits=payload_sched,
                    reliability=rel if flt is not None else None)
            selected = result.selected * free
            admitted = selected
            if n_cap is None:
                didx = None
                n_dropped = jnp.zeros((), jnp.int32)
            else:
                didx, selected, n_dropped = fed.dispatch_plan(selected,
                                                              lanes)
            # Fault draw + realized accounting, plus the per-device
            # completion times the arrival queue needs (recomputed with
            # the synchronous drivers' own expressions, so the cohort
            # makespan and each device's arrival agree bitwise).
            if flt is None:
                ok = selected
                draw = None
                if n_cap is None:
                    energy = result.energy
                    round_time = result.round_time
                else:
                    energy, round_time = fed._dispatch_accounting(
                        result, selected)
                t_up = jnp.where(jnp.isinf(result.t_up), 0.0,
                                 result.t_up)
                t_done = jnp.where(selected > 0.0,
                                   result.t_train + t_up, 0.0)
            else:
                draw = faults.sample_faults(k_fault, gains, net, flt,
                                            drop_rates)
                ok, energy, round_time = faults.apply_faults(
                    draw, selected, result.alpha, result.t_train, gains,
                    net, wcfg, payload, flt)
                t_up = wireless.upload_time(
                    result.alpha, gains, net.tx_power, wcfg, payload,
                    airtime_mult=faults.time_mult(draw.attempts, flt))
                t_up = jnp.where((selected > 0.0) & jnp.isfinite(t_up),
                                 t_up, 0.0)
                t_done = jnp.where(
                    selected > 0.0,
                    result.t_train * draw.compute_mult + t_up, 0.0)
            # Local training happens at dispatch time on the *current*
            # model — the channel delay only decides when the server
            # sees the update, so the update itself is computed now and
            # parked in the device's pending slot.
            if comp is None:
                client_params, _ = fed._masked_local_train(
                    trainer, max_steps, fcfg, params, images, labels,
                    mask, sizes_r, selected, k_train, dispatch_idx=didx)
                leaves, _ = jax.tree_util.tree_flatten(client_params)
                p_leaves = jax.tree_util.tree_leaves(params)
                rows = jnp.concatenate(
                    [(cl - p[None]).reshape(k_dev, -1)
                     for cl, p in zip(leaves, p_leaves)], axis=1)
            else:
                k_sgd, k_comp = jax.random.split(k_train)
                client_params, _ = fed._masked_local_train(
                    trainer, max_steps, fcfg, params, images, labels,
                    mask, sizes_r, selected, k_sgd, dispatch_idx=didx)
                leaves, _ = jax.tree_util.tree_flatten(client_params)
                p_leaves = jax.tree_util.tree_leaves(params)
                updates = jnp.concatenate(
                    [(cl - p[None]).reshape(k_dev, -1)
                     for cl, p in zip(leaves, p_leaves)], axis=1)
                if cdt is not None:
                    residual = residual.astype(jnp.float32)
                rows, residual = compression.apply_codec(
                    codec, updates, residual, selected, k_comp,
                    fcfg.compression, gains, index_g,
                    success=draw.success if flt is not None else None)
                if cdt is not None:
                    residual = residual.astype(cdt)
            # Learning-signal observations (DESIGN.md §14), taken on the
            # *raw* pre-codec updates against the pre-flush global model
            # the devices actually trained from — same matrix/reduction
            # the synchronous driver observes, so signals agree in the
            # synchronous limit.  Pure observer; nothing feeds back.
            if sig_fn is not None:
                loss_delta, upd_norm = sig_fn(
                    params, client_params,
                    rows if comp is None else updates,
                    images, labels, mask)
                sigst = telemetry_health.signal_update(
                    sigst, ok, loss_delta, upd_norm, energy)
            # Enqueue the uploads that will land (a failed upload never
            # arrives; its energy is already charged and — under
            # compression — its update already folded back into the
            # error-feedback residual, exactly as in the synchronous
            # fault path).
            enq = ok
            pend_rows = jnp.where(enq[:, None] > 0.0, rows, pend_rows)
            pend_mask = jnp.where(enq > 0.0, 1.0, pend_mask)
            pend_size = jnp.where(enq > 0.0, sizes_r, pend_size)
            pend_birth = jnp.where(enq > 0.0, version, pend_birth)
            pend_arrival = jnp.where(enq > 0.0, clock + t_done,
                                     pend_arrival)
            # Clock advance: whole-cohort ticks in the synchronous
            # limit (dt = the cohort makespan, so every upload lands
            # in-tick), fixed-length ticks under a positive horizon.
            dt = round_time if horizon <= 0.0 \
                else jnp.full((), horizon, jnp.float32)
            clock = clock + dt
            arrived = pend_mask * (pend_arrival <= clock)
            buf_n = jnp.sum(arrived)
            do_flush = buf_n >= buf_target
            # Flush weights: FedAvg sizes over the arrived set, times
            # the staleness discount.  At gamma = 0 the discount is
            # exact ones and the whole expression is the synchronous
            # success-set normalization bitwise.
            tau = (version - pend_birth).astype(jnp.float32)
            s_mult = staleness_multiplier(tau, gamma)
            base = pend_size.astype(jnp.float32) * arrived
            # The effective per-update weight is base * s(tau) over its
            # own sum; at gamma = 0 the discount drops out of the
            # *program* (static branch), leaving the synchronous
            # success-set normalization bitwise.
            num = base * s_mult if gamma != 0.0 else base
            denom = jnp.maximum(jnp.sum(num), 1.0)
            with telemetry_lib.phase_scope("aggregate"):
                if comp is None:
                    # ``buffered_flush`` multiplies the discount in per
                    # row (the kernel lane's fused ``s`` operand), so
                    # only the normalizer is folded here.
                    flushed = buffered_flush(params, pend_rows,
                                             base / denom, arrived,
                                             s_mult, fcfg.use_kernel_agg)
                else:
                    # Mirror the compressed synchronous round's
                    # aggregation (tensordot over the decoded rows) so
                    # the compressed sync-limit parity is also bitwise.
                    agg = jnp.tensordot(num / denom, pend_rows, axes=1)
                    p_leaves2, p_treedef2 = jax.tree_util.tree_flatten(
                        params)
                    outs, offset = [], 0
                    for p in p_leaves2:
                        size = int(np.prod(p.shape))
                        outs.append(
                            p + agg[offset:offset + size]
                            .reshape(p.shape).astype(p.dtype))
                        offset += size
                    flushed = jax.tree_util.tree_unflatten(p_treedef2,
                                                           outs)
                params = jax.tree_util.tree_map(
                    lambda f, p: jnp.where(do_flush, f, p), flushed,
                    params)
            version = version + do_flush.astype(jnp.int32)
            # Applied updates leave the buffer; un-flushed arrivals
            # stay buffered (and their devices stay busy) until the
            # buffer fills.
            cleared = arrived * do_flush.astype(jnp.float32)
            pend_mask = pend_mask * (1.0 - cleared)
            if tel is not None:
                frame = telemetry_record.round_frame(
                    tel, result=result, admitted=admitted,
                    sel_eff=selected, ok=ok, energy=energy,
                    payload_bits=payload, gains=gains, net=net,
                    wcfg=wcfg, sch=sch, key_sched=k_sched, index=index_g,
                    ages=ages, staleness=stale,
                    reliability=rel if flt is not None else None,
                    draw=draw,
                    signals=telemetry_health.signals_frame(
                        sigst, ok, loss_delta, upd_norm)
                    if sig_fn is not None else None)
                if tel.events:
                    frame.update(telemetry_record.event_frame(
                        avail=avail, free=free, in_flight=pend_mask,
                        buffer_fill=buf_n, flushed=do_flush, tau=tau,
                        clock=clock, version=version))
            # Participation = delivered, exactly as in the synchronous
            # drivers: ages reset and the streaming backlog clears for
            # uploads that landed this tick.
            ages = jnp.where(ok > 0.0, 0, ages + 1)
            if flt is not None:
                rel = faults.reliability_update(rel, selected, ok, flt)
            with telemetry_lib.phase_scope("eval"):
                acc = jax.lax.cond(
                    do_ev,
                    lambda p: jnp.asarray(eval_fn(p, test_x, test_labels),
                                          jnp.float32),
                    lambda p: jnp.full((), jnp.nan, jnp.float32),
                    params)
            met = fed.RoundMetrics(
                accuracy=acc,
                n_selected=jnp.sum(selected).astype(jnp.int32),
                round_time=dt,
                energy=energy,
                energy_total=jnp.sum(energy),
                selected=selected,
                iterations=result.iterations,
                n_success=jnp.sum(ok).astype(jnp.int32),
                n_dropped=n_dropped,
            )
            if cdt is not None:
                pend_rows = pend_rows.astype(cdt)
            out = (params, ages, key, clock, version, pend_rows,
                   pend_mask, pend_size, pend_birth, pend_arrival)
            if stream is not None:
                out += (fed._stream_advance(st, hists_r, stale, ok,
                                            cdt),)
            if comp is not None:
                out += (residual,)
            if flt is not None:
                out += (rel,)
            if sig_fn is not None:
                out += (sigst,)
            if tel is not None:
                return out, (met, frame)
            return out, met

        carry0 = (params,
                  jnp.zeros((k_dev,), jnp.int32),          # ages
                  key,
                  jnp.zeros((), jnp.float32),              # clock
                  jnp.zeros((), jnp.int32),                # model version
                  jnp.zeros((k_dev, p_flat), cdt or jnp.float32),
                  jnp.zeros((k_dev,), jnp.float32),        # pending mask
                  jnp.zeros((k_dev,), jnp.float32),        # pending sizes
                  jnp.zeros((k_dev,), jnp.int32),          # birth version
                  jnp.zeros((k_dev,), jnp.float32))        # arrival time
        if stream is not None:
            carry0 += (state0,)
        if comp is not None:
            carry0 += (residual0,)
        if flt is not None:
            carry0 += (jnp.ones((k_dev,), jnp.float32),)
        if sig_fn is not None:
            carry0 += (telemetry_health.signal_init(k_dev),)
        if tel is not None:
            out_carry, (metrics, frames) = jax.lax.scan(
                body, carry0, (do_eval, ticks))
            return out_carry[0], metrics, frames
        out_carry, metrics = jax.lax.scan(body, carry0, (do_eval, ticks))
        return out_carry[0], metrics

    return sim


def make_event_sim(*, loss_fn: Callable, eval_fn: Callable,
                   wcfg: wireless.WirelessConfig,
                   scfg: scheduler.SchedulerConfig, fcfg,
                   capacity: int, eval_every: int = 1,
                   donate_params: bool = False) -> Callable:
    """Jitted single-scenario event simulation (see
    :func:`_make_event_sim`).  Same donation contract as
    ``federated.make_feel_sim``."""
    sim = _make_event_sim(loss_fn, eval_fn, wcfg, scfg, fcfg, capacity,
                          eval_every)
    return jax.jit(sim, donate_argnums=(0,) if donate_params else ())


__all__ = ["EventConfig", "AvailabilityProcess", "AlwaysOn", "Churn",
           "Diurnal", "register_availability", "availability_names",
           "get_availability", "staleness_multiplier", "buffered_flush",
           "make_event_sim"]
