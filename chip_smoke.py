"""Run the FEEL simulator's main path on a TPU and check what comes out.

    python3 chip_smoke.py              # one chip: phases 1-5
    python3 chip_smoke.py --chips 4    # four chips: phases 1 and 6

The deployment is the paper's Table-I setup as
``examples/federated_mnist.py --full-data --devices 100 --model cnn
--method das`` builds it (``repro.launch.paper``), cut from 15 rounds
to 3: K = 100 devices over 1,200 shards of 50 synthetic MNIST-shaped
samples drawn from the seed (50-900 samples per device), the paper's
CNN (21,840 parameters, random init), DAS with 6 outer iterations,
E = 1, B = 50.

Phases, all in this one process:

1. device     the first device must be a TPU, else exit non-zero.
2. single     ``federated.run_federated`` (the scan driver) on the chip.
3. reference  the same inputs pinned to the host CPU with every kernel
              lane off, compared with phase 2.
4. kernels    phase 2 with the Pallas lanes on (``use_kernel_agg``,
              ``fused_pgd``); the compiled program must hold
              ``tpu_custom_call``.  Compared with phase 2.
5. sweep      8 scenarios through ``sweep.SweepEngine``; scenario 0
              against a single run with the same keys.
6. sharded    (``--chips 4`` only) one chunk of 16 scenarios sharded
              over a 4-chip scenario mesh against the same chunk on
              one chip.

Each phase prints one JSON line: its wall seconds and, separately, its
compile seconds (tracing, lowering and XLA compilation, summed from
JAX's monitoring events) and persistent-cache hits.  A failed check
raises, so the exit code is non-zero; the last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import sweep  # noqa: E402
from repro.core import federated, wireless  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch import cache, paper  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.models import paper_nets  # noqa: E402
from repro.sweep import engine as sweep_engine  # noqa: E402

PAPER = dict(model="cnn", method="das", devices=100, full_data=True,
             rounds=3, seed=0)

# Chip against host CPU, same inputs (phases 3 and 5).  f32 matmuls and
# convolutions run at the TPU's default precision (bf16 passes), so the
# trained weights drift from the CPU's f32 ones: accuracy may move by a
# few test images per round (0.02 is 120 of the 6,000).  Energy and round
# time do not depend on the weights; they come out of f32 bisection and
# Newton solves whose last-ulp differences between backends grow to
# ~1e-4 relative, so 1e-3 leaves a decade of room.
ACC_ATOL = 0.02
COST_RTOL = 1e-3
# Kernel lanes against phase 2 (phase 4).  ``fused_pgd`` runs the same
# descent as the default ``pgd`` allocator but projects by bisection and
# floors alpha differently, so DAS sees other bandwidth splits: on this
# deployment (interpret mode, host CPU) the two differ by up to 6.9% in
# round energy and 10.3% in round time, with DAS admitting 2 of 92
# devices differently in round 1.  Accuracy sees the admitted data only.
KERNEL_ACC_ATOL = 0.05
KERNEL_COST_RTOL = 0.25
# Sharded against unsharded per-round means over the scenarios
# (phase 6).  The scenarios are the same; the two programs differ only
# in the vmapped batch width per device (4 vs 16), which may reorder f32
# sums, so the phase-3 tolerances apply to the means.
SHARD_TOL = {"round.accuracy": ("abs", ACC_ATOL),
             "round.round_time": ("rel", COST_RTOL),
             "round.energy_total": ("rel", COST_RTOL),
             "round.n_selected": ("rel", COST_RTOL)}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_clock = {"compile_s": 0.0, "cache_hits": 0, "listening": False}


def _listen() -> None:
    if _clock["listening"]:
        return

    def on_duration(event, duration, **_):
        if event in _COMPILE_EVENTS:
            _clock["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _clock["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _clock["listening"] = True


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


@contextlib.contextmanager
def _phase(name: str):
    """Time a phase; print its JSON line only if it passes."""
    _listen()
    info: dict = {}
    c0, h0 = _clock["compile_s"], _clock["cache_hits"]
    t0 = time.perf_counter()
    yield info
    _emit({"phase": name, "wall_s": time.perf_counter() - t0,
           "compile_s": _clock["compile_s"] - c0,
           "cache_hits": _clock["cache_hits"] - h0, **info})


def check_device(chips: int = 1) -> dict:
    """Phase 1: a TPU must be the default device, with ``chips`` of them."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{d.platform!r} ({len(devices)} device(s) of "
                         f"kind {d.device_kind!r})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, found {len(devices)}")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    _emit({"phase": "device", **device})
    return device


# -- checks ------------------------------------------------------------------

def _rounds(hist) -> dict:
    return {"accuracy": [r.accuracy for r in hist],
            "n_selected": [r.n_selected for r in hist],
            "energy_total": [r.energy_total for r in hist],
            "round_time": [r.round_time for r in hist]}


def check_records(hist, setup: paper.PaperSetup) -> None:
    """Finite metrics, a legal admitted count, positive costs, learning."""
    k = setup.data.num_devices
    chance = 1.0 / setup.fcfg.num_classes
    bad = []
    for r in hist:
        vals = (r.accuracy, r.round_time, r.energy_total)
        if not all(np.isfinite(vals)):
            bad.append(f"round {r.round}: non-finite metric {vals}")
        if not 1 <= r.n_selected <= k:
            bad.append(f"round {r.round}: n_selected={r.n_selected} "
                       f"outside [1, {k}]")
        if not (r.energy_total > 0.0 and r.round_time > 0.0):
            bad.append(f"round {r.round}: energy={r.energy_total} "
                       f"time={r.round_time} not positive")
    if len(hist) != setup.fcfg.num_rounds:
        bad.append(f"{len(hist)} rounds, expected {setup.fcfg.num_rounds}")
    elif not hist[-1].accuracy > chance:
        bad.append(f"final accuracy {hist[-1].accuracy} not above chance "
                   f"{chance}")
    if bad:
        raise AssertionError("; ".join(bad))


def compare(got, want, *, acc_atol: float, cost_rtol: float, label: str,
            same_round0: bool = True) -> dict:
    """Per-round comparison of two record lists under stated tolerances.

    With ``same_round0`` the round-0 admitted sets must be identical:
    they depend on no trained weights, only on the data, the channel
    draw and the solver.  On a mismatch every differing round and device
    is printed before the assertion fails.
    """
    diffs = []
    sel0_got, sel0_want = got[0].selected > 0, want[0].selected > 0
    if same_round0 and not np.array_equal(sel0_got, sel0_want):
        diffs.append(
            f"round 0 admitted sets differ: devices "
            f"{np.flatnonzero(sel0_got & ~sel0_want).tolist()} only in "
            f"the run, {np.flatnonzero(sel0_want & ~sel0_got).tolist()} "
            f"only in the reference")
    worst = {"accuracy_abs": 0.0, "energy_rel": 0.0, "time_rel": 0.0}
    sel_diff = []
    for g, w in zip(got, want, strict=True):
        d_acc = abs(g.accuracy - w.accuracy)
        d_e = abs(g.energy_total - w.energy_total) / abs(w.energy_total)
        d_t = abs(g.round_time - w.round_time) / abs(w.round_time)
        worst["accuracy_abs"] = max(worst["accuracy_abs"], d_acc)
        worst["energy_rel"] = max(worst["energy_rel"], d_e)
        worst["time_rel"] = max(worst["time_rel"], d_t)
        moved = np.flatnonzero((g.selected > 0) != (w.selected > 0))
        sel_diff.append(len(moved))
        if not d_acc <= acc_atol:
            diffs.append(f"round {g.round}: accuracy {g.accuracy} vs "
                         f"{w.accuracy} (|d| {d_acc} > {acc_atol})")
        for name, d, a, b in (("energy", d_e, g.energy_total,
                               w.energy_total),
                              ("round time", d_t, g.round_time,
                               w.round_time)):
            if not d <= cost_rtol:
                diffs.append(f"round {g.round}: {name} {a} vs {b} "
                             f"(rel {d} > {cost_rtol}); admission "
                             f"differs on devices {moved.tolist()}")
    if diffs:
        for line in diffs:
            print(f"chip_smoke: {label}: {line}", file=sys.stderr)
        raise AssertionError(f"{label}: {len(diffs)} mismatch(es), "
                             f"first: {diffs[0]}")
    return {"worst": worst, "admission_diffs": sel_diff,
            "tolerance": {"accuracy_abs": acc_atol, "cost_rel": cost_rtol}}


# -- phases ------------------------------------------------------------------

def _run(setup: paper.PaperSetup, **over):
    kw = dict(init_params=setup.params, loss_fn=setup.loss_fn,
              eval_fn=setup.eval_fn, data=setup.data, net=setup.net,
              wcfg=setup.wcfg, scfg=setup.scfg, fcfg=setup.fcfg,
              key=setup.key)
    kw.update(over)
    return federated.run_federated(**kw)


def run_single(setup: paper.PaperSetup):
    """Phase 2: the scan driver on the default device."""
    with _phase("single") as info:
        _, hist = _run(setup)
        check_records(hist, setup)
        info["rounds"] = _rounds(hist)
    return hist


def _pinned(setup: paper.PaperSetup, device) -> paper.PaperSetup:
    """The same inputs, committed to ``device``."""
    data = setup.data
    data = dataclasses.replace(data, **{
        f.name: jax.device_put(getattr(data, f.name), device)
        for f in dataclasses.fields(data)})
    return dataclasses.replace(
        setup, data=data, params=jax.device_put(setup.params, device),
        net=jax.device_put(setup.net, device),
        key=jax.device_put(setup.key, device))


def run_reference(setup: paper.PaperSetup, hist) -> dict:
    """Phase 3: the plain reference on the host CPU, compared with 2.

    Kernel lanes must be off here: ``kernels/ops.py`` picks interpret
    mode from the default backend, which stays ``tpu`` while this run
    is pinned to the CPU.
    """
    fc, sc = setup.fcfg, setup.scfg
    if fc.use_kernel_agg or sc.allocator == "fused_pgd" or \
            fc.stream is not None or fc.compression is not None:
        raise ValueError("the CPU reference runs with every kernel lane "
                         "off")
    cpu = jax.devices("cpu")[0]
    with _phase("reference") as info:
        with jax.default_device(cpu):
            params, ref = _run(_pinned(setup, cpu))
        ran_on = {d.platform for leaf in jax.tree_util.tree_leaves(params)
                  for d in leaf.devices()}
        if ran_on != {"cpu"}:
            raise AssertionError(f"reference ran on {ran_on}, not the CPU")
        check_records(ref, setup)
        info["rounds"] = _rounds(ref)
        info.update(compare(hist, ref, acc_atol=ACC_ATOL,
                            cost_rtol=COST_RTOL, label="chip vs CPU"))
    return info


def run_kernels(setup: paper.PaperSetup, hist) -> dict:
    """Phase 4: the Pallas lanes compiled into the scan program."""
    fcfg = dataclasses.replace(setup.fcfg, use_kernel_agg=True)
    scfg = dataclasses.replace(setup.scfg, allocator="fused_pgd")
    data = setup.data
    with _phase("kernels") as info:
        sim = federated.make_feel_sim(
            loss_fn=setup.loss_fn, eval_fn=setup.eval_fn, wcfg=setup.wcfg,
            scfg=scfg, fcfg=fcfg, capacity=data.capacity)
        args = (setup.params, data.images, data.labels, data.mask,
                data.sizes, federated.client_histograms(data,
                                                        fcfg.num_classes),
                synthetic.to_float(data.test_images), data.test_labels,
                setup.net, setup.key)
        compiled = sim.lower(*args).compile()
        n_kernels = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        if jax.default_backend() == "tpu" and n_kernels == 0:
            raise AssertionError("the kernel lanes were not compiled: no "
                                 "tpu_custom_call in the program")
        _, metrics = compiled(*args)
        got = federated.metrics_to_records(metrics)
        check_records(got, setup)
        info["tpu_custom_calls"] = n_kernels
        info["rounds"] = _rounds(got)
        info.update(compare(got, hist, acc_atol=KERNEL_ACC_ATOL,
                            cost_rtol=KERNEL_COST_RTOL,
                            label="kernel lanes vs plain",
                            same_round0=False))
    return info


def _engine(setup, scenarios: int, chunk: int = 0,
            **kw) -> sweep.SweepEngine:
    spec = sweep.SweepSpec(fl=setup.fcfg, sched=setup.scfg,
                           wireless=setup.wcfg,
                           scenarios_per_point=scenarios,
                           chunk_scenarios=chunk or scenarios,
                           base_seed=PAPER["seed"])
    return sweep.SweepEngine(spec, data=setup.data, loss_fn=setup.loss_fn,
                             eval_fn=setup.eval_fn,
                             init_params=setup.params, **kw)


def run_sweep(setup: paper.PaperSetup, scenarios: int = 8) -> dict:
    """Phase 5: a Monte-Carlo batch through the sweep engine."""
    with _phase("sweep") as info:
        engine = _engine(setup, scenarios)
        point = engine.points[0]
        summary = sweep.aggregate_summary(engine.run_point(point))
        for name in ("round.accuracy", "round.energy_total",
                     "round.round_time", "round.n_selected"):
            for field in ("mean", "std", "min", "max"):
                if not np.all(np.isfinite(summary[name][field])):
                    raise AssertionError(f"{name}.{field} not finite: "
                                         f"{summary[name][field]}")
        metrics = engine.chunk_outputs(point, 0, scenarios)[1]
        acc = np.asarray(metrics.accuracy)
        np.testing.assert_allclose(summary["round.accuracy"]["mean"],
                                   acc.mean(axis=0), rtol=1e-5,
                                   err_msg="aggregate vs scenarios")
        batch0 = federated.batch_metrics_to_records(metrics)[0]
        net_base, sim_base = sweep_engine.stream_bases(PAPER["seed"])
        net0 = jax.tree_util.tree_map(
            lambda a: a[0], wireless.sample_networks_indexed(
                net_base, jnp.arange(1), setup.data.num_devices,
                setup.wcfg))
        key0 = federated.scenario_keys(sim_base, 0, 1)[0]
        _, single = _run(setup, net=net0, key=key0)
        info["scenarios"] = scenarios
        info["mean"] = {k: summary[f"round.{k}"]["mean"].tolist()
                        for k in ("accuracy", "energy_total",
                                  "round_time", "n_selected")}
        info.update(compare(batch0, single, acc_atol=ACC_ATOL,
                            cost_rtol=COST_RTOL,
                            label="sweep scenario 0 vs single run"))
    return info


def run_sharded(setup: paper.PaperSetup, chips: int = 4,
                scenarios: int = 16) -> dict:
    """Phase 6: one chunk sharded over the scenario mesh vs one chip.

    The one-chip side runs the same scenarios unsharded in chunks of
    ``scenarios / chips``, the width each chip holds in the sharded
    program: at the paper deployment one 16-wide program needs 18.4 GB
    of temporaries, more than a v5e's 16 GB.  A scenario's streams
    depend only on its index, never on the chunking (``repro.sweep``).
    """
    with _phase("sharded") as info:
        sharded = _engine(setup, scenarios,
                          mesh=mesh_lib.make_scenario_mesh(chips))
        point = sharded.points[0]
        if sharded.mesh.devices.size != chips:
            raise AssertionError(f"scenario mesh has "
                                 f"{sharded.mesh.devices.size} devices, "
                                 f"expected {chips}")
        many = sharded.chunk_outputs(point, 0, scenarios)[1]
        out_devices = many.accuracy.sharding.device_set
        if len(out_devices) != chips:
            raise AssertionError(f"the chunk ran on {len(out_devices)} "
                                 f"device(s): not the sharded program")
        plain = _engine(setup, scenarios, chunk=scenarios // chips,
                        use_sharding=False)
        parts = [plain.chunk_outputs(point, off, size)[1]
                 for off, size in plain.spec.point_chunks()]
        if any(len(m.accuracy.sharding.device_set) != 1 for m in parts):
            raise AssertionError("an unsharded chunk spans devices")
        one = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
            *parts)
        # Per-scenario outputs, before the Welford fold reduces them in
        # a layout-dependent order.
        scenarios_bitwise = all(
            np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
            zip(jax.tree_util.tree_leaves(many),
                jax.tree_util.tree_leaves(one), strict=True))
        got = sweep.aggregate_summary(sharded.run_point(point))
        want = sweep.aggregate_summary(plain.run_point(point))
        worst, bitwise = {}, True
        for name, (kind, tol) in SHARD_TOL.items():
            a, b = got[name]["mean"], want[name]["mean"]
            bitwise &= bool(np.array_equal(a, b))
            d = np.abs(a - b) if kind == "abs" else np.abs(a - b) / b
            worst[name] = float(np.max(d))
            if not worst[name] <= tol:
                raise AssertionError(
                    f"{name} per-round mean, sharded {a.tolist()} vs one "
                    f"chip {b.tolist()}: {kind} diff {worst[name]} > {tol}")
        info.update(mesh_devices=int(sharded.mesh.devices.size),
                    sharded_output_devices=len(out_devices),
                    scenarios=scenarios, means_bitwise=bitwise,
                    scenarios_bitwise=scenarios_bitwise, worst=worst,
                    tolerance=SHARD_TOL,
                    mean={k: got[f"round.{k}"]["mean"].tolist()
                          for k in ("accuracy", "energy_total",
                                    "round_time", "n_selected")})
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the scenario-sharded sweep on a "
                         "four-chip mesh and its one-chip comparison")
    args = ap.parse_args(argv)
    device = check_device(args.chips)
    cache.enable_compile_cache()
    with _phase("setup") as info:
        setup = paper.paper_setup(**PAPER)
        info.update(devices=setup.data.num_devices,
                    capacity=setup.data.capacity,
                    params=paper_nets.num_params(setup.params),
                    rounds=setup.fcfg.num_rounds)
    if args.chips == 4:
        run_sharded(setup, chips=4)
    else:
        hist = run_single(setup)
        run_reference(setup, hist)
        run_kernels(setup, hist)
        run_sweep(setup)
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
