"""End-to-end driver: the paper's evaluation, runnable at paper scale.

    PYTHONPATH=src python examples/federated_mnist.py \
        [--model cnn|mlp] [--method das|abs|random|full] [--rounds 15]
        [--devices 100] [--n-fixed 7] [--epochs 1] [--full-data]
        [--scenarios 1] [--stream poisson|drift|shift|evict]

Reproduces the §VI setup: K devices with shard-partitioned synthetic
MNIST-like data, DAS/ABS/random/full scheduling, FedAvg training, and
per-round accuracy/energy/time reporting (the numbers behind Figs 2-11).

The whole multi-round simulation runs as one compiled scan
(``federated.run_federated``); with ``--scenarios S > 1`` it reproduces
the paper's Monte-Carlo averaging through the sharded sweep engine
(``repro.sweep``, DESIGN.md §8): scenarios execute in shard_map'd
chunks over the present devices (``--chunk-scenarios`` bounds the
scenarios per dispatch) with online Welford aggregation, so host memory
stays O(rounds) however many scenarios run.  ``--sweep-ckpt PATH``
checkpoints the aggregate + grid cursor after every chunk — a killed
run re-invoked with the same arguments resumes bit-for-bit.

``--stream <process>`` turns the scenario non-stationary: per-device
data arrives/drifts/evicts round by round inside the scan carry and the
scheduler re-ranks on the refreshed statistics (streaming subsystem,
DESIGN.md §7).  Combine with ``--scenarios`` to run S independent
streaming realizations through the batch driver.

``--codec <name>`` compresses the uplink (compressed-uplink subsystem,
DESIGN.md §9): devices upload quantized/sparsified updates with error
feedback, the scheduler and Sub2 price the per-device post-compression
payload bits, and the reported energy/time reflect the smaller uploads.
``--sweep-jsonl PATH`` streams per-chunk aggregates as JSON lines for
live dashboards while a ``--scenarios`` sweep runs.

``--dispatch-cap N`` trains only a dense N-lane block of the admitted
devices instead of masking all K lanes (dense-block dispatch,
DESIGN.md §11) — the steady-state win at the paper's small-admitted-set
regime; admitted devices beyond the cap are dropped by schedule rank
and reported per round.  ``--carry-dtype bfloat16`` stores the large
scan-carry tensors (EF residual, stream stats) at reduced precision.
"""

import argparse

from repro import sweep
from repro.core import compression, federated, streaming
from repro.launch import cache, paper
from repro.models import paper_nets


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--method", default="das",
                    choices=["das", "abs", "random", "full"])
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--devices", type=int, default=40)
    ap.add_argument("--n-fixed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--model-bits", type=float, default=100e3)
    ap.add_argument("--full-data", action="store_true",
                    help="paper scale: 1200 shards x 50 (else 300x50)")
    ap.add_argument("--scenarios", type=int, default=1,
                    help="Monte-Carlo scenarios through the sharded "
                         "sweep engine")
    ap.add_argument("--chunk-scenarios", type=int, default=0,
                    help="scenarios per compiled chunk (0: all in one)")
    ap.add_argument("--sweep-ckpt", default="",
                    help="checkpoint path for resumable sweeps")
    ap.add_argument("--sweep-jsonl", default="",
                    help="stream per-chunk aggregates to this JSONL "
                         "file (live-dashboard feed; resume-safe)")
    ap.add_argument("--codec", default="",
                    choices=["", "none", "quant", "topk", "adaptive"],
                    help="uplink compression codec (default: "
                         "uncompressed full-precision uploads)")
    ap.add_argument("--bit-width", type=int, default=8,
                    help="quantization bit width for --codec quant")
    ap.add_argument("--stream", default="",
                    choices=["", "static", "poisson", "drift", "shift",
                             "evict"],
                    help="streaming-data arrival process (default: "
                         "static data, the paper's frozen partition)")
    ap.add_argument("--stream-rate", type=float, default=25.0,
                    help="mean arrivals per device per round")
    ap.add_argument("--staleness-weight", type=float, default=0.25,
                    help="gamma_s staleness boost for streaming runs")
    ap.add_argument("--dispatch-cap", type=int, default=0,
                    help="dense-block training lanes (0: masked all-K "
                         "path; see DESIGN.md §11)")
    ap.add_argument("--carry-dtype", default="",
                    choices=["", "float32", "bfloat16", "float16"],
                    help="storage dtype for the big scan-carry tensors")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache.enable_compile_cache()

    stream_cfg = streaming.StreamConfig(
        process=args.stream, rate=args.stream_rate) if args.stream \
        else None
    comp_cfg = compression.CompressionConfig(
        codec=args.codec, bit_width=args.bit_width) if args.codec \
        else None
    setup = paper.paper_setup(
        model=args.model, method=args.method, rounds=args.rounds,
        devices=args.devices, n_fixed=args.n_fixed, epochs=args.epochs,
        model_bits=args.model_bits, full_data=args.full_data,
        seed=args.seed,
        staleness_weight=args.staleness_weight if args.stream else 0.0,
        stream=stream_cfg, compression=comp_cfg,
        dispatch_cap=args.dispatch_cap or None,
        carry_dtype=args.carry_dtype or None)
    print(f"[feel] {args.model} ({paper_nets.num_params(setup.params):,} "
          f"params), K={args.devices}, method={args.method}, "
          f"E={args.epochs}, s={args.model_bits / 1e3:.0f} kbit, "
          f"S={args.scenarios}"
          + (f", stream={args.stream}@{args.stream_rate:g}/round"
             if args.stream else "")
          + (f", codec={args.codec}" if args.codec else ""))

    if args.scenarios > 1:
        spec = sweep.SweepSpec(
            fl=setup.fcfg, sched=setup.scfg, wireless=setup.wcfg,
            scenarios_per_point=args.scenarios,
            chunk_scenarios=args.chunk_scenarios,
            base_seed=args.seed)
        results = sweep.run_sweep(
            spec, data=setup.data, loss_fn=setup.loss_fn,
            eval_fn=setup.eval_fn, init_params=setup.params,
            ckpt_path=args.sweep_ckpt or None,
            jsonl_path=args.sweep_jsonl or None)
        _, summary = results[0]
        acc = summary["round.accuracy"]
        sel = summary["round.n_selected"]
        t = summary["round.round_time"]
        for r in range(args.rounds):
            print(f"round {r:3d}: acc={acc['mean'][r]:.4f} "
                  f"[{acc['min'][r]:.4f},{acc['max'][r]:.4f}] "
                  f"sel={sel['mean'][r]:5.1f} "
                  f"T={t['mean'][r]:7.3f}s")
        final = summary["scalar.final_accuracy"]
        print(f"[feel] S={args.scenarios} final acc "
              f"mean={float(final['mean']):.4f} "
              f"min={float(final['min']):.4f} "
              f"max={float(final['max']):.4f} "
              f"(std={float(final['std']):.4f})")
        return

    _, hist = federated.run_federated(
        init_params=setup.params, loss_fn=setup.loss_fn,
        eval_fn=setup.eval_fn, data=setup.data, net=setup.net,
        wcfg=setup.wcfg, scfg=setup.scfg, fcfg=setup.fcfg, key=setup.key)

    e_tot = t_tot = 0.0
    for r in hist:
        e_tot += r.energy_total
        t_tot += r.round_time
        drop = f" drop={r.n_dropped:2d}" if args.dispatch_cap else ""
        print(f"round {r.round:3d}: acc={r.accuracy:.4f} "
              f"sel={r.n_selected:3d} T={r.round_time:7.3f}s "
              f"E/dev={r.energy_per_device:7.3f}J{drop}")
    print(f"[feel] total: time={t_tot:.1f}s energy={e_tot:.1f}J "
          f"final acc={hist[-1].accuracy:.4f}")


if __name__ == "__main__":
    main()
