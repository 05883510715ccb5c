"""Benchmark orchestrator: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig2,...]
                                            [--host-tuned]

Prints ``name,value,derived`` CSV rows.  Default (quick) mode shrinks the
FL scale so the whole suite runs on the CPU container; ``--full`` is the
paper's K=100 / 1200x50-shard / 15-round configuration.

Suites: fig2 (limited devices, scenario-averaged via the vmapped batch
driver), fig3 (local epochs), fig45 (model size), fig67 (energy/time vs
baseline+ABS), divergence (selected-fraction probe), fl_e2e (legacy loop
vs scan vs batch vs sharded-sweep simulation throughput; writes
BENCH_fl_e2e.json), sched (scheduler latency, includes sweep/* rows),
sweep (sweep engine rows only — the CI shard_map smoke), dispatch
(dense-block dispatch smoke — the CI gather/scatter regression guard),
async (event-driver smoke — sync scan vs event-scan sync limit vs
buffered async under diurnal churn), telemetry (in-scan frame overhead,
inert vs enabled; ``--telemetry-log`` sinks the enabled run's JSONL
round-event log for ``python -m repro.telemetry.report``),
kernels (Pallas micro), roofline (requires dryrun_results.json from
repro.launch.dryrun).

``--profile DIR`` wraps the selected suites in ``jax.profiler.trace``;
the trace's device ops carry the drivers' ``repro/*`` named scopes
(DESIGN.md §13).

``--host-tuned`` re-execs the process with the host-tuning idioms the
related training repos bake into their launchers (SNIPPETS.md §1-2):
``LD_PRELOAD`` tcmalloc when the library is present on the box,
``--xla_force_host_platform_device_count=<cores>`` so the sharded sweep
rows get real host devices, and quieted TF logging.  Env applied before
jax is imported (the re-exec happens before any suite import); a guard
variable prevents exec loops, and existing ``XLA_FLAGS``/``LD_PRELOAD``
settings are extended, never clobbered.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
import time

_TUNED_GUARD = "REPRO_HOST_TUNED"

_TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/*/libtcmalloc*.so*",
    "/usr/lib64/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
)


def _host_tuned_env() -> dict:
    """Tuned environment for the re-exec (pure; tested separately)."""
    env = dict(os.environ)
    env[_TUNED_GUARD] = "1"
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    cores = os.cpu_count() or 1
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = (f"{flags} " if flags else "") + \
            f"--xla_force_host_platform_device_count={cores}"
        env["XLA_FLAGS"] = flags
    tcmalloc = sorted(p for pat in _TCMALLOC_GLOBS
                      for p in glob.glob(pat))
    if tcmalloc and "tcmalloc" not in env.get("LD_PRELOAD", ""):
        preload = env.get("LD_PRELOAD", "")
        env["LD_PRELOAD"] = (f"{preload} {tcmalloc[0]}".strip())
        # Silence tcmalloc's large-alloc spam for the big scan buffers.
        env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                       "10000000000")
    return env


def _reexec_host_tuned() -> None:
    env = _host_tuned_env()
    has_tcm = "tcmalloc" in env.get("LD_PRELOAD", "")
    print(f"# host-tuned re-exec: devices={os.cpu_count() or 1}, "
          f"tcmalloc={'yes' if has_tcm else 'absent'}",
          file=sys.stderr)
    os.execve(sys.executable,
              [sys.executable, "-m", "benchmarks.run"] + sys.argv[1:],
              env)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="explicit quick mode (the default; used by the "
                         "CI smoke step)")
    ap.add_argument("--only", default="")
    ap.add_argument("--dryrun-json", default="dryrun_results.json")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the selected suites in jax.profiler.trace"
                         "(DIR)")
    ap.add_argument("--telemetry-log", default=None, metavar="PATH",
                    help="with the telemetry suite: sink the enabled "
                         "run's round frames to this JSONL file (the CI "
                         "report smoke reads it back)")
    ap.add_argument("--metrics-store", default=None, metavar="PATH",
                    help="append run summaries (final acc, energy, "
                         "fairness, timings) to this cross-run JSONL "
                         "store (repro.telemetry.store) — the "
                         "regression-gate input")
    ap.add_argument("--host-tuned", action="store_true",
                    help="re-exec with tcmalloc LD_PRELOAD (if present) "
                         "and one forced XLA host device per core "
                         "before importing jax")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    if args.host_tuned and os.environ.get(_TUNED_GUARD) != "1":
        _reexec_host_tuned()
    from repro.launch import cache
    cache.enable_compile_cache()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    print("name,value,derived")
    t0 = time.time()

    profile_ctx = None
    if args.profile is not None:
        import jax
        profile_ctx = jax.profiler.trace(args.profile)
        profile_ctx.__enter__()

    def run_suites() -> None:
        if want("fig2") or want("fig3") or want("fig45") or want("fig67") \
                or want("divergence"):
            from benchmarks import paper_figs
            if want("fig2"):
                for r in paper_figs.fig2_limited_devices(quick):
                    _emit(r)
            if want("fig3"):
                for r in paper_figs.fig3_local_epochs(quick):
                    _emit(r)
            if want("fig45"):
                for r in paper_figs.fig45_model_size(quick):
                    _emit(r)
            if want("fig67"):
                for r in paper_figs.fig67_energy_time(quick):
                    _emit(r)
            if want("divergence"):
                for r in paper_figs.selection_fraction_sweep(quick):
                    _emit(r)

        if want("fl_e2e"):
            from benchmarks import fl_e2e
            for r in fl_e2e.run(quick, store_path=args.metrics_store):
                _emit(r)

        if want("sched"):
            from benchmarks import sched_micro
            for r in sched_micro.run(quick):
                _emit(r)
        elif want("sweep"):
            # Standalone sweep smoke (CI runs this under
            # XLA_FLAGS=--xla_force_host_platform_device_count=4 so the
            # sharded row exercises the real shard_map partitioning).
            from benchmarks import sched_micro
            for r in sched_micro.sweep_rows(quick):
                _emit(r)

        if want("async") and not want("sched"):
            # Standalone event-driver smoke (CI runs this under 4 forced
            # host devices): sync scan vs event-scan sync limit vs full
            # buffered async, without paying the full sched suite.
            from benchmarks import sched_micro
            for r in sched_micro.async_rows(quick):
                _emit(r)

        if want("telemetry") and not want("sched"):
            # Standalone telemetry smoke (CI runs this under 4 forced
            # host devices): inert vs enabled frame overhead, plus the
            # enabled run's JSONL round-event log for the report-CLI
            # check.
            from benchmarks import sched_micro
            for r in sched_micro.telemetry_rows(
                    quick, log_path=args.telemetry_log,
                    store_path=args.metrics_store):
                _emit(r)

        if want("dispatch") and not want("fl_e2e"):
            # Standalone dispatch smoke (CI runs this under 4 forced
            # host devices): masked vs dense-block scan + a batched
            # dispatched run, without paying the full fl_e2e suite.
            from benchmarks import fl_e2e
            for r in fl_e2e.dispatch_rows(quick):
                _emit(r)

        if want("kernels"):
            from benchmarks import kernel_bench
            for r in kernel_bench.run(quick):
                _emit(r)

        if want("roofline"):
            if os.path.exists(args.dryrun_json):
                from benchmarks import roofline
                for row in roofline.analyze(
                        __import__("json").load(open(args.dryrun_json))):
                    _emit((f"roofline/{row['arch']}/{row['shape']}/"
                           f"{row['dominant']}",
                           round(max(row['compute_s'], row['memory_s'],
                                     row['collective_s']), 4),
                           f"useful={row['useful_ratio']:.3f}"))
            else:
                print(f"# roofline skipped: {args.dryrun_json} not found "
                      f"(run repro.launch.dryrun first)", file=sys.stderr)

    # try/finally so a suite raising mid-run still finalizes the
    # profiler trace directory — a half-written trace dir with no
    # closing __exit__ is unreadable by the viewer.
    try:
        run_suites()
    finally:
        if profile_ctx is not None:
            profile_ctx.__exit__(None, None, None)
            print(f"# profiler trace written to {args.profile}",
                  file=sys.stderr)

    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


def _emit(row) -> None:
    name, value, derived = row
    print(f"{name},{value},{derived}")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
