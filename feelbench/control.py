"""The control and the planted faults, put in the program's place.

    python3 feelbench/control.py --workload cnn-das-s8 --seeds 11 12 13 \
        --controls bf16 bf16train frozen halfbatch

The configuration states float32 (matmuls at the backend's default
precision).  For each seed this recomputes the scenarios a run of the
cell checks (the same data, weights, streams and sampled indices, drawn
from the seed as ``run.py`` draws them) with the reference, and once
more with each of ``--controls`` in the program's place, and reads
every number ``check.py`` compares, as a run reads the program's:

- ``bf16``: the control, the whole reference in bfloat16; its fold is
  the same statistics taken in bfloat16;
- ``bf16train``: the reference with local training, FedAvg and
  evaluation alone in bfloat16, the schedule in float32;
- ``frozen``: the float32 reference with every local step returning
  its state unchanged;
- ``halfbatch``: the float32 reference with half of every local batch
  left out, the loss a mean over the rest.

Each must fail at least one of the cell's limits; the smallest readings
over the seeds are the upper readings the limits in
``feelbench/limits`` were set under (``PERF.md``).  The benchmark's own
runs do not run this.  Prints one JSON line per seed and control, then
one with the smallest reading of each number over the seeds.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402


def plain_fold(per_scenario: dict, dtype) -> dict:
    """Mean, variance, min and max over scenarios, computed in ``dtype``."""
    import jax.numpy as jnp
    out = {}
    for name, x in per_scenario.items():
        x = jnp.asarray(np.asarray(x, np.float32)).astype(dtype)
        mean = jnp.mean(x, axis=0)
        out[name] = {"mean": mean, "var": jnp.mean((x - mean) ** 2, axis=0),
                     "min": jnp.min(x, axis=0), "max": jnp.max(x, axis=0)}
    return {k: {f: np.asarray(v.astype(jnp.float32), np.float64)
                for f, v in d.items()} for k, d in out.items()}


@contextlib.contextmanager
def planted(fault: str):
    """The reference with ``fault`` planted in its local step, or as it
    is for any other name."""
    import jax.numpy as jnp
    from feelbench import nets, reference
    if fault == "frozen":
        orig = reference._local_train

        def patched(cfg, params, images, labels, mask, n_steps, key, dt):
            return orig(cfg, params, images, labels, mask, 0 * n_steps, key,
                        dt)
        module, name = reference, "_local_train"
    elif fault == "halfbatch":
        orig = nets.loss

        def patched(params, images, labels, mask, model):
            b = mask.shape[0]
            keep = (jnp.arange(b) < b // 2).astype(mask.dtype)
            return orig(params, images, labels, mask * keep, model)
        module, name = nets, "loss"
    else:
        yield
        return
    setattr(module, name, patched)
    reference._round_fn.cache_clear()
    try:
        yield
    finally:
        setattr(module, name, orig)
        reference._round_fn.cache_clear()


# control name -> (dtype, train dtype) of the reference in its place
PRECISION = {"bf16": ("bfloat16", "bfloat16"),
             "bf16train": ("float32", "bfloat16"),
             "frozen": ("float32", "float32"),
             "halfbatch": ("float32", "float32")}


def control_numbers(cell: dict, seed: int, controls=("bf16",),
                    windows: int = 2) -> dict:
    """Every compared number of each control in ``controls`` for one
    seed: ``{control: {number: reading}}``."""
    import jax
    from feelbench import check, data, nets, reference, run
    cfg, limits = cell["cfg"], cell["limits"]
    method = cell["traffic_mix"]["method"]
    s = cell["traffic_mix"]["scenarios_per_chunk"]
    sd = run.seeds(seed)
    host = data.make(sd["data"], cfg)
    params0 = jax.device_get(nets.init(jax.random.key(sd["init"]), cfg))
    out = {"seeds": sd, "attempted": windows * s}
    indices = [s + i for i in check.sample(out, limits["reference_scenarios"])]
    wants = [reference.simulate(cfg, method, host, params0, sd["base"], i)
             for i in indices]
    worst = {}
    low = {c: [] for c in controls}
    for c in controls:
        dtype, train_dtype = PRECISION[c]
        numbers = []
        with planted(c):
            for index, want in zip(indices, wants):
                got = reference.simulate(cfg, method, host, params0,
                                         sd["base"], index, dtype=dtype,
                                         train_dtype=train_dtype)
                print(c, check.describe(index, got, want), file=sys.stderr)
                numbers.append(check.compare(got, want,
                                             limits["early_rounds"]))
                low[c].append(got)
        worst[c] = check.combine(numbers)
    for c in controls:
        per = {"accuracy": np.stack([g["accuracy"] for g in low[c]]),
               "round_time": np.stack([g["round_time"] for g in low[c]]),
               "energy_total": np.stack([g["energy"].sum(axis=1)
                                         for g in low[c]]),
               "n_selected": np.stack([g["selected"].sum(axis=1)
                                       for g in low[c]])}
        worst[c]["fold_rel"] = check.fold_gap(
            plain_fold(per, PRECISION[c][0]), per)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", choices=sorted(PRECISION),
                    default=["bf16"])
    args = ap.parse_args(argv)
    from feelbench import run
    from repro.launch import cache
    cache.enable_compile_cache()
    cell = run.load_cell(args.workload)
    smallest = {c: {} for c in args.controls}
    for seed in args.seeds:
        got = control_numbers(cell, seed, args.controls)
        for c, numbers in got.items():
            print(json.dumps({"seed": seed, "control": c,
                              "numbers": numbers}), flush=True)
            for k, v in numbers.items():
                smallest[c][k] = min(smallest[c].get(k, np.inf), v)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "smallest": smallest}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
