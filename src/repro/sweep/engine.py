"""Sharded Monte-Carlo execution + online Welford aggregation.

The engine turns a :class:`repro.sweep.grid.SweepSpec` into compiled
work: for each grid point it builds the batched FEEL sim
(``federated.make_feel_sim_batch``) — sharding the scenario axis over a
``scenario`` mesh axis via ``shard_map`` when a mesh is available — and
executes the point's scenarios in chunks of ``S``, folding every
chunk's ``(S, R)`` metrics into an **online Welford aggregate** carried
across chunks.  Host (and checkpoint) state is O(R) per grid point no
matter how many scenarios run: per-round mean/variance/min/max of
accuracy, energy and completion time, plus the per-scenario summary
scalars the paper figures need (final accuracy, totals, rounds to a
target accuracy).

Numerics: the fold uses the Chan et al. parallel-merge form — a chunk's
batch statistics (count/mean/M2 over the scenario axis) merge into the
carry in one step — with NaN-masking so eval-stride rounds (NaN
accuracy) simply don't count toward that round's statistics.  The fold
runs jitted on device; only the O(R) carry ever reaches the host.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import federated, wireless
from repro.data import partition as partition_lib
from repro.data import synthetic
from repro.launch import mesh as mesh_lib
from repro.sweep import grid as grid_lib

Array = jax.Array

# Salts separating the two per-scenario fold_in streams.
_NET_STREAM = 0
_SIM_STREAM = 1


def stream_bases(base_seed: int) -> Tuple[Array, Array]:
    """(net_base, sim_base) keys for a sweep's two per-scenario streams.

    Scenario ``i`` draws its network from ``fold_in(net_base, i)`` and
    its simulation stream from ``fold_in(sim_base, i)``.  Public so the
    unsharded driver path (``benchmarks.common.run_fl_batch``) derives
    the *same* scenarios as the engine — the sharded/unsharded parity
    contract compares like with like.
    """
    root = jax.random.key(base_seed)
    return (jax.random.fold_in(root, _NET_STREAM),
            jax.random.fold_in(root, _SIM_STREAM))


# ---------------------------------------------------------------------------
# Online Welford aggregation (masked, batched merge)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Welford:
    """Running mean/variance/min/max over the scenario population.

    Leaves share a broadcastable shape (``(R,)`` for per-round metrics,
    ``()`` for per-scenario scalars).  ``count`` is per-element because
    masking (NaN accuracy on eval-stride rounds, never-reached targets)
    makes the effective sample size element-dependent.
    """

    count: Array
    mean: Array
    m2: Array
    min: Array
    max: Array

    def tree_flatten(self):
        return ((self.count, self.mean, self.m2, self.min, self.max),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def variance(self) -> Array:
        """Population variance (ddof=0), matching ``jnp.var``."""
        return jnp.where(self.count > 0, self.m2
                         / jnp.maximum(self.count, 1.0), jnp.nan)

    @property
    def std(self) -> Array:
        return jnp.sqrt(self.variance)


def welford_init(shape: Tuple[int, ...]) -> Welford:
    return Welford(count=jnp.zeros(shape, jnp.float32),
                   mean=jnp.zeros(shape, jnp.float32),
                   m2=jnp.zeros(shape, jnp.float32),
                   min=jnp.full(shape, jnp.inf, jnp.float32),
                   max=jnp.full(shape, -jnp.inf, jnp.float32))


def welford_fold(state: Welford, batch: Array,
                 mask: Optional[Array] = None) -> Welford:
    """Merge a ``(S, ...)`` batch into the carry (Chan et al. merge).

    ``mask`` (same shape, optional) excludes entries; NaNs are always
    excluded so eval-stride rounds never poison the fold.
    """
    batch = batch.astype(jnp.float32)
    valid = jnp.isfinite(batch)
    if mask is not None:
        valid = jnp.logical_and(valid, mask)
    x = jnp.where(valid, batch, 0.0)
    n_b = jnp.sum(valid, axis=0).astype(jnp.float32)
    safe_n = jnp.maximum(n_b, 1.0)
    mean_b = jnp.sum(x, axis=0) / safe_n
    m2_b = jnp.sum(jnp.where(valid, (x - mean_b) ** 2, 0.0), axis=0)
    n = state.count + n_b
    delta = mean_b - state.mean
    has = n_b > 0
    mean = jnp.where(has, state.mean + delta * n_b / jnp.maximum(n, 1.0),
                     state.mean)
    m2 = jnp.where(has, state.m2 + m2_b
                   + delta ** 2 * state.count * n_b / jnp.maximum(n, 1.0),
                   state.m2)
    mn = jnp.minimum(state.min, jnp.min(jnp.where(valid, batch, jnp.inf),
                                        axis=0))
    mx = jnp.maximum(state.max, jnp.max(jnp.where(valid, batch, -jnp.inf),
                                        axis=0))
    return Welford(count=n, mean=mean, m2=m2, min=mn, max=mx)


# ---------------------------------------------------------------------------
# Per-point aggregate: per-round Welford + per-scenario scalar Welford
# ---------------------------------------------------------------------------

ROUND_METRICS = ("accuracy", "round_time", "energy_total", "n_selected",
                 "n_success", "n_dropped")
SCALAR_METRICS = ("final_accuracy", "time_total", "energy_total",
                  "energy_per_device", "mean_selected", "rounds_to_target",
                  "reached_target")


def aggregate_init(num_rounds: int) -> Dict[str, Dict[str, Welford]]:
    return {
        "round": {m: welford_init((num_rounds,)) for m in ROUND_METRICS},
        "scalar": {m: welford_init(()) for m in SCALAR_METRICS},
    }


def _scenario_scalars(metrics: federated.RoundMetrics, target: float):
    """Per-scenario (S,) summary scalars + validity masks from (S, R)
    stacked metrics — the quantities ``benchmarks.common.totals`` and
    ``rounds_to_accuracy`` derive per scenario, computed on device."""
    acc = metrics.accuracy                       # (S, R), NaN on skipped
    n_sel = metrics.n_selected.astype(jnp.float32)
    e_tot = jnp.sum(metrics.energy_total, axis=1)
    t_tot = jnp.sum(metrics.round_time, axis=1)
    sel_tot = jnp.sum(n_sel, axis=1)
    hit = jnp.where(jnp.isnan(acc), False, acc >= target)   # (S, R)
    reached = jnp.any(hit, axis=1)
    first = jnp.argmax(hit, axis=1).astype(jnp.float32) + 1.0
    out = {
        "final_accuracy": acc[:, -1],
        "time_total": t_tot,
        "energy_total": e_tot,
        "energy_per_device": e_tot / jnp.maximum(sel_tot, 1.0),
        "mean_selected": jnp.mean(n_sel, axis=1),
        "rounds_to_target": first,
        "reached_target": reached.astype(jnp.float32),
    }
    masks = {m: None for m in out}
    masks["rounds_to_target"] = reached   # only scenarios that got there
    return out, masks


def aggregate_fold(agg: Dict[str, Dict[str, Welford]],
                   metrics: federated.RoundMetrics,
                   target: float) -> Dict[str, Dict[str, Welford]]:
    """Fold one chunk's ``(S, R)`` metrics into the O(R) carry."""
    per_round = {
        "accuracy": metrics.accuracy,
        "round_time": metrics.round_time,
        "energy_total": metrics.energy_total,
        "n_selected": metrics.n_selected.astype(jnp.float32),
        "n_success": metrics.n_success.astype(jnp.float32),
        "n_dropped": metrics.n_dropped.astype(jnp.float32),
    }
    scalars, masks = _scenario_scalars(metrics, target)
    return {
        "round": {m: welford_fold(agg["round"][m], per_round[m])
                  for m in ROUND_METRICS},
        "scalar": {m: welford_fold(agg["scalar"][m], scalars[m],
                                   masks[m])
                   for m in SCALAR_METRICS},
    }


def aggregate_summary(agg) -> Dict[str, Dict[str, np.ndarray]]:
    """Host-side view: ``{"round.accuracy": {mean, var, std, min, max,
    count}, ...}`` — everything the figure suites consume."""
    host = jax.device_get(agg)
    out: Dict[str, Dict[str, np.ndarray]] = {}
    for group, metrics in host.items():
        for name, w in metrics.items():
            count = np.asarray(w.count)
            valid = count > 0
            out[f"{group}.{name}"] = {
                "count": count,
                "mean": np.where(valid, np.asarray(w.mean), np.nan),
                "var": np.asarray(w.variance),
                "std": np.asarray(w.std),
                "min": np.where(valid, np.asarray(w.min), np.nan),
                "max": np.where(valid, np.asarray(w.max), np.nan),
            }
    return out


# -- adaptive scenario counts (SweepSpec.ci_target) -----------------------

def final_accuracy_ci_halfwidth(agg) -> float:
    """95% CI half-width of the final-accuracy mean from the Welford
    carry: ``1.96 * sqrt(m2 / (n-1)) / sqrt(n)`` (sample std / sqrt n).
    ``inf`` below two scenarios — a single draw has no spread estimate.
    One O(1) host transfer; callers are the chunk loops, which already
    sync per chunk for checkpointing.
    """
    w = agg["scalar"]["final_accuracy"]
    n = float(jax.device_get(w.count))
    if n < 2.0:
        return float("inf")
    m2 = max(float(jax.device_get(w.m2)), 0.0)
    return 1.96 * np.sqrt(m2 / (n - 1.0)) / np.sqrt(n)


def point_converged(agg, ci_target: float) -> bool:
    """True when adaptive stopping is on and the point's final-accuracy
    CI half-width is at or below the target."""
    if ci_target <= 0.0:
        return False
    return bool(final_accuracy_ci_halfwidth(agg) <= ci_target)


# -- checkpoint (de)serialization: Welford pytree <-> plain array tree ----

def aggregate_to_tree(agg) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    return {
        group: {
            name: {field: np.asarray(getattr(w, field))
                   for field in ("count", "mean", "m2", "min", "max")}
            for name, w in metrics.items()
        }
        for group, metrics in jax.device_get(agg).items()
    }


def aggregate_from_tree(tree) -> Dict[str, Dict[str, Welford]]:
    return {
        group: {
            name: Welford(**{f: jnp.asarray(leaves[f])
                             for f in ("count", "mean", "m2", "min",
                                       "max")})
            for name, leaves in metrics.items()
        }
        for group, metrics in tree.items()
    }


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class SweepEngine:
    """Executes a :class:`SweepSpec` chunk by chunk.

    One instance owns the problem data (dataset, model init, loss/eval)
    and a compiled-sim cache keyed by ``(grid point, chunk size,
    sharded?)`` — re-running a chunk size reuses the jit.  The mesh is
    built lazily from the present devices (``launch.mesh
    .make_scenario_mesh``); chunks whose size the mesh does not divide
    fall back to the unsharded vmap program transparently, so a sweep
    never fails on an awkward remainder chunk.
    """

    def __init__(self, spec: grid_lib.SweepSpec, *,
                 data: partition_lib.ClientDataset,
                 loss_fn: Callable, eval_fn: Callable,
                 init_params, target_accuracy: float = 0.85,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 use_sharding: bool = True,
                 donate_params: bool = False,
                 telemetry_dir: Optional[str] = None):
        self.spec = spec
        self.data = data
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.init_params = init_params
        self.target_accuracy = float(target_accuracy)
        self.donate_params = donate_params
        # Per-scenario telemetry streams (DESIGN.md §13): grid points
        # whose FLConfig.telemetry is set return stacked frames from the
        # batch sim; when a directory is given each scenario's frames
        # land in their own JSONL file keyed by the fold_in-derived
        # global scenario index, so re-running a chunk (resume) simply
        # overwrites the same files with the same bytes.
        self.telemetry_dir = telemetry_dir
        self._manifest_written = False
        if mesh is None and use_sharding:
            mesh = mesh_lib.make_scenario_mesh()
        self.mesh = mesh
        self.points = spec.expand()
        self._sims: Dict[Tuple[int, int, bool], Callable] = {}
        self._hists: Dict[int, Array] = {}
        self._fold = jax.jit(aggregate_fold, static_argnums=(2,))
        # Problem-wide constants, computed once.
        self._test_x = synthetic.to_float(data.test_images)
        self._net_base, self._sim_base = stream_bases(spec.base_seed)

    # -- plumbing --------------------------------------------------------

    def _shard_count(self) -> int:
        if self.mesh is None:
            return 1
        return mesh_lib.scenario_shard_count(self.mesh)

    def _sim_for(self, point: grid_lib.GridPoint, size: int) -> Callable:
        sharded = self.mesh is not None and size % self._shard_count() == 0
        cache_key = (point.index, size, sharded)
        sim = self._sims.get(cache_key)
        if sim is None:
            sim = federated.make_feel_sim_batch(
                loss_fn=self.loss_fn, eval_fn=self.eval_fn,
                wcfg=point.wireless, scfg=point.sched, fcfg=point.fl,
                capacity=self.data.capacity,
                eval_every=self.spec.eval_every,
                donate_params=self.donate_params,
                mesh=self.mesh if sharded else None)
            self._sims[cache_key] = sim
        return sim

    def _hists_for(self, point: grid_lib.GridPoint) -> Array:
        # Constant per num_classes — cached so chunked runs don't rebuild
        # the (K, C) histogram scan every dispatch.
        c = point.fl.num_classes
        if c not in self._hists:
            self._hists[c] = federated.client_histograms(self.data, c)
        return self._hists[c]

    # -- execution -------------------------------------------------------

    def chunk_outputs(self, point: grid_lib.GridPoint, global_start: int,
                      size: int):
        """The batch sim's raw outputs for scenarios [global_start,
        global_start + size): ``(params, metrics[, frames])`` with a
        leading ``(size,)`` axis, from the program :meth:`run_chunk`
        folds — per-scenario values the O(R) aggregate no longer holds.

        Host spans, shown by a running profiler on its host plane
        (``jax.profiler.TraceAnnotation``): ``sweep.inputs`` holds the
        network draw, the scenario keys, the params tiling and the
        histograms; ``sweep.launch`` the sim's call, with its first
        trace, lowering and compile.
        """
        data = self.data
        with jax.profiler.TraceAnnotation("sweep.inputs"):
            indices = jnp.arange(global_start, global_start + size)
            nets = wireless.sample_networks_indexed(
                self._net_base, indices, data.num_devices, point.wireless)
            keys = federated.scenario_keys(self._sim_base, global_start,
                                           size)
            params = federated.tile_params(self.init_params, size) \
                if self.donate_params else self.init_params
            hists = self._hists_for(point)
        with jax.profiler.TraceAnnotation("sweep.launch"):
            sim = self._sim_for(point, size)
            return sim(params, data.images, data.labels, data.mask,
                       data.sizes, hists, self._test_x, data.test_labels,
                       nets, keys)

    def run_chunk(self, point: grid_lib.GridPoint, global_start: int,
                  size: int, agg):
        """Run scenarios [global_start, global_start + size) of a grid
        point and fold their metrics into ``agg``."""
        out = self.chunk_outputs(point, global_start, size)
        if len(out) == 3:
            _, metrics, frames = out
            self._sink_frames(point, global_start, size, metrics, frames)
        else:
            _, metrics = out
        return self._fold(agg, metrics, self.target_accuracy)

    def _sink_frames(self, point: grid_lib.GridPoint, global_start: int,
                     size: int, metrics, frames) -> None:
        """One JSONL round-event file per scenario in the chunk, named
        by grid-point index and global scenario index (the same fold_in
        index that derives the scenario's streams, so a resumed re-run
        rewrites identical bytes), plus one run manifest per sweep."""
        if self.telemetry_dir is None:
            return
        from repro.telemetry import sinks
        os.makedirs(self.telemetry_dir, exist_ok=True)
        if not self._manifest_written:
            sinks.write_manifest(
                os.path.join(self.telemetry_dir, "manifest.json"),
                self.spec, extra={"kind": "sweep",
                                  "fingerprint": self.spec.fingerprint()})
            self._manifest_written = True
        host_frames = sinks.frames_to_host(frames)
        host_met = jax.device_get(metrics)
        for s in range(size):
            scn = global_start + s
            path = os.path.join(
                self.telemetry_dir,
                f"point{point.index:03d}_scn{scn:05d}.jsonl")
            sinks.write_round_frames(
                path,
                {k: v[s] for k, v in host_frames.items()},
                metrics=jax.tree_util.tree_map(lambda a, s=s: a[s],
                                               host_met),
                scenario=scn)

    def run_point(self, point: grid_lib.GridPoint, agg=None):
        """All chunks of one grid point folded into one fresh aggregate
        (mid-point resume is the runner's job — it drives
        :meth:`run_chunk` directly from its checkpointed cursor).
        With ``spec.ci_target > 0`` the chunk loop stops early once the
        final-accuracy CI half-width reaches the target."""
        if agg is None:
            agg = aggregate_init(federated.sim_length(point.fl))
        base = self.spec.scenario_start(point.index)
        for off, size in self.spec.point_chunks():
            if off > 0 and point_converged(agg, self.spec.ci_target):
                break
            agg = self.run_chunk(point, base + off, size, agg)
        return agg

    def run(self) -> List[Tuple[grid_lib.GridPoint,
                                Dict[str, Dict[str, np.ndarray]]]]:
        """The whole grid, no checkpointing (use ``runner.SweepRunner``
        for resumable execution).  Returns per-point summaries."""
        return [(p, aggregate_summary(self.run_point(p)))
                for p in self.points]


__all__ = ["Welford", "welford_init", "welford_fold", "aggregate_init",
           "aggregate_fold", "aggregate_summary", "aggregate_to_tree",
           "aggregate_from_tree", "SweepEngine", "ROUND_METRICS",
           "SCALAR_METRICS", "stream_bases",
           "final_accuracy_ci_halfwidth", "point_converged"]
