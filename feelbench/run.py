"""Run one benchmark cell once: Monte-Carlo sweep throughput of FEEL.

    python3 feelbench/run.py --workload cnn-das-s8 --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``feelbench/configs/<config>.json``: the deployment, the model, the
wireless constants) and a traffic mix (``feelbench/traffic/<traffic>.json``:
the scheduling method and the scenarios per chunk).  Nothing here is
particular to one cell.

1. Set-up: check the device, make the data and the initial weights from
   ``--seed`` by the configuration's model family
   (``feelbench/models/<model>.py``), build the program's sweep engine,
   with the family's loss and eval, over a ``scenario`` mesh of the
   cell's chips,
   and run one chunk through it so that every program compiles or is
   read from the persistent cache.  ``setup_s`` ends here.
2. Window: chunk after chunk of S new scenarios through
   ``SweepEngine.chunk_outputs`` and the engine's fold, with at most one
   chunk enqueued ahead of the one running, until ``--seconds`` have
   passed; the window closes when the last chunk started has folded.
   The second chunk is enqueued before the first has run, so a window
   holds at least two chunks whatever ``--seconds`` says: for a cell
   whose chunk outlasts ``--seconds``, its length is two chunks' time.
   A compile inside the window fails the run.  With ``--trace 1`` the
   profiler records one chunk instead, and the per-layer metrics are
   read from its trace (``feelbench/metrics``).
3. Check: the fold against the window's per-scenario outputs, and a
   sample of the window's scenarios drawn from the seed against the
   plain reference (``feelbench/reference.py``), each number beside its
   limit (``feelbench/limits/<workload>.json``).

The last line of standard output is one JSON object; the numbers
compared are the last lines on standard error and the ``checks`` key of
that object.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

TRACE_CHUNKS = 1        # chunks the profiler records with --trace 1
WARM_START = 1 << 24    # scenario index of the warm-up chunk


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell, its configuration, traffic mix and limits, by name:
    ``BENCHMARK.json`` at ``root``, the rest in files of their own."""
    bench = read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"feelbench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    home = os.path.join(root, bench["paths"][0])
    cell["cfg"] = read_json(root, configs[cell["config"]]["file"])
    cell["traffic_mix"] = read_json(home, "traffic", cell["traffic"] + ".json")
    cell["limits"] = read_json(home, "limits", name + ".json")
    cell["end_to_end"] = bench["end_to_end"]
    cell["per_layer"] = bench["per_layer"]
    return cell


def check_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" or len(devices) < chips:
        raise NoChip(f"feelbench: needs {chips} TPU chip(s), found "
                     f"{len(devices)} device(s) of platform {d.platform!r}, "
                     f"kind {d.device_kind!r}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for data, weights, streams and sampling."""
    w = np.random.SeedSequence(seed).generate_state(4, dtype=np.uint32)
    return {"data": int(w[0] >> 1), "init": int(w[1] >> 1),
            "base": int(w[2] >> 1), "sample": int(w[3] >> 1)}


class CompileCounter:
    """Counts JAX compiles and persistent-cache reads while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax
        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def _event(self, event, **kw):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


@dataclasses.dataclass
class Bench:
    """The program's sweep engine for one cell, and what built it."""

    cell: dict
    seeds: dict
    data: dict            # host arrays from the model family
    params: object        # initial weights (device)
    engine: object
    point: object
    fold: object
    scenarios: int        # per chunk


def program_configs(cfg: dict, method: str):
    """The program's config objects, field by field from the file."""
    from feelbench import models
    from repro.core import bandwidth, diversity, federated, scheduler, \
        selection, wireless
    w, s, s2 = cfg["wireless"], cfg["scheduler"], cfg["sub2"]
    wcfg = wireless.WirelessConfig(
        bandwidth_hz=w["bandwidth_hz"], noise_psd=w["noise_psd"],
        pathloss_exp=w["pathloss_exp"], cell_side_m=w["cell_side_m"],
        model_bits=w["model_bits"],
        cpu_freq_range=tuple(w["cpu_freq_range"]),
        cycles_per_bit_range=tuple(w["cycles_per_bit_range"]),
        tx_power_range=tuple(w["tx_power_range"]),
        bits_per_sample=w["bits_per_sample"], min_alpha=w["min_alpha"])
    scfg = scheduler.SchedulerConfig(
        method=method, n_min=s["n_min"], n_fixed=None,
        iterations_max=s["iterations_max"], local_epochs=cfg["local_epochs"],
        sub1=selection.Sub1Params(lambda_e=s["lambda_e"],
                                  lambda_t=s["lambda_t"],
                                  lambda_i=s["lambda_i"], n_min=s["n_min"]),
        sub2=bandwidth.Sub2Params(
            rho=s2["rho"], time_bisect_iters=s2["time_bisect_iters"],
            newton_iters=s2["newton_iters"],
            joint_newton_steps=s2["joint_newton_steps"],
            pgd_iters=s2["pgd_iters"], pgd_lr=s2["pgd_lr"],
            smooth_tau=s2["smooth_tau"]),
        allocator="pgd", x_tol=s["x_tol"], alpha_tol=s["alpha_tol"],
        reentry=s["reentry"])
    fcfg = federated.FLConfig(
        num_rounds=cfg["rounds"], local_epochs=cfg["local_epochs"],
        batch_size=cfg["batch_size"], learning_rate=cfg["learning_rate"],
        momentum=cfg["momentum"],
        num_classes=models.load(cfg).classes(cfg),
        measure=s["measure"],
        index_weights=diversity.IndexWeights(*s["index_weights"]))
    return wcfg, scfg, fcfg


def build(cell: dict, seed: int, mesh=None) -> Bench:
    """Data, weights and the program's engine for one cell and seed."""
    import jax
    import jax.numpy as jnp
    from feelbench import models
    from repro import sweep
    from repro.data import partition
    from repro.launch import mesh as mesh_lib
    from repro.sweep import engine as sweep_engine

    cfg, mix = cell["cfg"], cell["traffic_mix"]
    family = models.load(cfg)
    sd = seeds(seed)
    host = family.data(sd["data"], cfg)
    params = family.init(jax.random.key(sd["init"]), cfg)
    dataset = partition.ClientDataset(
        **{k: jnp.asarray(v) for k, v in host.items()})
    wcfg, scfg, fcfg = program_configs(cfg, mix["method"])
    s = mix["scenarios_per_chunk"]
    spec = sweep.SweepSpec(fl=fcfg, sched=scfg, wireless=wcfg,
                           scenarios_per_point=s, chunk_scenarios=s,
                           base_seed=sd["base"])
    if mesh is None:
        mesh = mesh_lib.make_scenario_mesh(cell["chips"])
    engine = sweep.SweepEngine(spec, data=dataset, init_params=params,
                               mesh=mesh, **family.engine_args(cfg))
    fold = jax.jit(sweep_engine.aggregate_fold, static_argnums=(2,))
    return Bench(cell=cell, seeds=sd, data=host, params=params,
                 engine=engine, point=engine.points[0], fold=fold,
                 scenarios=s)


@contextlib.contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def run_chunks(bench: Bench, start: int, seconds: float,
               max_chunks: int = 0) -> dict:
    """Chunks of new scenarios from global index ``start`` on.

    Enqueues chunk n + 1 before waiting for chunk n, starts no chunk
    after ``seconds`` (or past ``max_chunks``) but always the first, and
    returns once the last one started has folded.
    """
    import jax
    from repro.core import federated
    from repro.sweep import engine as sweep_engine
    s = bench.scenarios
    agg = sweep_engine.aggregate_init(federated.sim_length(bench.point.fl))
    outs, pending = [], []
    t0 = time.perf_counter()
    while True:
        n = len(outs)
        if (max_chunks and n >= max_chunks) or (
                not max_chunks and n and time.perf_counter() - t0 >= seconds):
            break
        with span("chunk.enqueue"):
            params, metrics = bench.engine.chunk_outputs(
                bench.point, start + n * s, s)[:2]
        with span("chunk.fold"):
            agg = bench.fold(agg, metrics, bench.engine.target_accuracy)
        outs.append((start + n * s, params, metrics))
        pending.append(agg)
        if len(pending) > 1:
            with span("chunk.wait"):
                jax.block_until_ready(pending.pop(0))
    with span("chunk.wait"):
        jax.block_until_ready(pending)
    t1 = time.perf_counter()
    return {"outs": outs, "agg": agg, "t0": t0, "t1": t1,
            "scenarios": len(outs) * s}


def warm_up(bench: Bench) -> None:
    """Run every program the window runs once, with the window's shapes
    and shardings: one chunk of scenarios the window never uses (a
    nonzero start, as every window chunk has), and the fold of a fresh
    and of an already folded aggregate."""
    import jax
    w = run_chunks(bench, WARM_START, 0.0, max_chunks=1)
    metrics = w["outs"][0][2]
    jax.block_until_ready(bench.fold(w["agg"], metrics,
                                     bench.engine.target_accuracy))


def memory_peak(chips: int) -> int:
    """Peak device memory of the fullest chip: what the allocator held
    (``peak_bytes_in_use``) plus what it reserved for the programs'
    temporaries (``peak_bytes_reserved``), which the first leaves out."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def to_host(window: dict) -> dict:
    """The window's per-scenario outputs, stacked on the host."""
    import jax
    starts, params, metrics = zip(*window["outs"])
    host = jax.device_get((params, metrics, window["agg"]))
    cat = lambda *xs: np.concatenate([np.asarray(x) for x in xs])
    return {"start": starts[0],
            "params": jax.tree_util.tree_map(cat, *host[0]),
            "metrics": jax.tree_util.tree_map(cat, *host[1]),
            "agg": host[2]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default="",
                    help="keep the profiler's trace here (default: a "
                         "temporary directory, deleted)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        device = check_device(cell["chips"])
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(f"feelbench: {args.workload} on {device['count']} x "
          f"{device['kind']} ({device['platform']})", file=sys.stderr)
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 args.trace_dir, device)
    from feelbench import check
    check.report(result)
    return 0


def run(cell: dict, seed: int, seconds: float, traced: bool,
        trace_dir: str, device: dict) -> dict:
    """One run of a cell: set-up, window, check.  Returns the result."""
    import jax
    from repro.launch import cache
    from feelbench import check

    cache.enable_compile_cache()
    counter = CompileCounter()
    bench = build(cell, seed)
    s = bench.scenarios
    warm_up(bench)
    setup_s = time.perf_counter() - T_START

    counter.armed = True
    tmp = None
    if traced:
        tmp = trace_dir or tempfile.mkdtemp(prefix="feelbench-trace-")
        jax.profiler.start_trace(tmp)
        window = run_chunks(bench, s, 0.0, max_chunks=TRACE_CHUNKS)
        jax.profiler.stop_trace()
    else:
        window = run_chunks(bench, s, seconds)
    counter.armed = False
    out = {"device": dict(device), "compiles_in_window": counter.count,
           "window_s": window["t1"] - window["t0"],
           "attempted": window["scenarios"], "setup_s": setup_s}
    out["device"]["memory_peak_bytes"] = memory_peak(cell["chips"])
    host = to_host(window)
    # The program's state goes before the reference runs on the chip.
    del window, bench.engine
    out.update(cell=cell, seed=seed, seeds=bench.seeds, data=bench.data,
               params0=jax.device_get(bench.params), host=host,
               rounds=cell["cfg"]["rounds"])
    if traced:
        from feelbench import trace as trace_lib
        out["trace"] = trace_lib.reduce_dir(tmp, cell["chips"])
        if not trace_dir:
            shutil.rmtree(tmp, ignore_errors=True)
    check.verify(out)
    return out


if __name__ == "__main__":
    sys.exit(main())
