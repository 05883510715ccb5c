"""What decides ``correct``, and the run's result line.

Every number compared but ``acc_early_mean`` is a worst case over
what the window produced:

- ``admit_diff``: (round, device) entries where a sampled scenario's
  admitted set differs from the reference's (schedule layer).
- ``energy_rel``: largest gap of one device's upload energy in a round,
  as a share of the reference's round energy (schedule layer).
- ``time_rel``: largest relative gap of a round's completion time
  (schedule layer).
- ``acc_abs``: largest gap of a round's test accuracy (local_train and
  aggregate layers).
- ``acc_early_mean``: the mean gap of test accuracy over the first
  ``early_rounds`` rounds and over the sampled scenarios.  Rounding
  differences grow through the rounds of SGD, so a worst case over
  15 rounds reads sound runs about as high as a wrong local step; a
  mean over the early rounds is steady from seed to seed
  (local_train and aggregate layers).
- ``model_rel``: worst leaf of ||final model - reference|| / ||reference||
  (local_train and aggregate layers).
- ``fold_rel``: largest gap between the sweep engine's aggregate (mean,
  variance, min, max per round of accuracy, round time, energy and
  admitted count) and the same statistics computed exactly from the
  window's per-scenario outputs, as a share of the metric's scale.
- ``compiles``: compiles and cache reads inside the window.
- ``bad_outputs``: window scenarios with a non-finite metric or final
  weight, or an admitted count outside [1, K].

The sampled scenarios are drawn from the seed among all of the window's
scenarios and run through ``feelbench/reference.py`` once the program's
state is freed.  Each number is held to its limit in
``feelbench/limits/<workload>.json``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

import jax
import numpy as np

from feelbench import reference

ROUND_FOLD = ("accuracy", "round_time", "energy_total", "n_selected")
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def scenario(metrics, params, i: int) -> dict:
    """Scenario ``i`` of stacked host outputs, named as the reference's."""
    pick = lambda a: np.asarray(a)[i]
    return {"selected": pick(metrics.selected).astype(np.float32),
            "energy": pick(metrics.energy).astype(np.float64),
            "round_time": pick(metrics.round_time).astype(np.float64),
            "accuracy": pick(metrics.accuracy).astype(np.float64),
            "params": jax.tree_util.tree_map(
                lambda a: np.asarray(a[i], np.float64), params)}


FAR = 1e300   # a NaN or infinite gap, kept a finite JSON number


def _worst(x) -> float:
    """Largest entry, with NaN or infinity read as ``FAR``."""
    x = np.asarray(x, np.float64)
    return float(np.max(np.where(np.isfinite(x), x, FAR)))


MEAN_OVER_SAMPLE = ("acc_early_mean",)


def compare(got: dict, ref: dict, early: int) -> dict:
    """The per-scenario numbers of one sampled scenario; ``early`` is
    the number of rounds ``acc_early_mean`` covers."""
    e_ref = np.maximum(ref["energy"].sum(axis=1), 1e-30)
    gaps = [np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
            for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                            jax.tree_util.tree_leaves(ref["params"]))]
    return {
        "admit_diff": float(np.sum(got["selected"] != ref["selected"])),
        "energy_rel": _worst(
            np.max(np.abs(got["energy"] - ref["energy"]), axis=1) / e_ref),
        "time_rel": _worst(np.abs(got["round_time"] - ref["round_time"])
                           / np.maximum(ref["round_time"], 1e-30)),
        "acc_abs": _worst(np.abs(got["accuracy"] - ref["accuracy"])),
        "acc_early_mean": _worst(np.mean(np.abs(got["accuracy"][:early]
                                                - ref["accuracy"][:early]))),
        "model_rel": _worst(gaps),
    }


def combine(per_scenario: list) -> dict:
    """The sampled scenarios' numbers as one: the mean for
    ``MEAN_OVER_SAMPLE``, the worst case for the rest."""
    return {k: float(np.mean([g[k] for g in per_scenario]))
            if k in MEAN_OVER_SAMPLE else max(g[k] for g in per_scenario)
            for k in per_scenario[0]}


def describe(index: int, got: dict, ref: dict) -> str:
    """One line per sampled scenario: per-round accuracy and admitted
    count, program / reference."""
    acc = " ".join(f"{a:.4f}/{b:.4f}" for a, b in
                   zip(got["accuracy"], ref["accuracy"]))
    n = " ".join(f"{int(a)}/{int(b)}" for a, b in
                 zip(got["selected"].sum(axis=1), ref["selected"].sum(axis=1)))
    return f"scenario {index}: accuracy {acc}; admitted {n}"


def exact_stats(x: np.ndarray) -> dict:
    x = np.asarray(x, np.float64)
    return {"mean": x.mean(axis=0), "var": x.var(axis=0),
            "min": x.min(axis=0), "max": x.max(axis=0)}


def welford_stats(w) -> dict:
    count = np.maximum(np.asarray(w.count, np.float64), 1.0)
    return {"mean": np.asarray(w.mean, np.float64),
            "var": np.asarray(w.m2, np.float64) / count,
            "min": np.asarray(w.min, np.float64),
            "max": np.asarray(w.max, np.float64)}


def fold_gap(stats: dict, per_scenario: dict) -> float:
    """Largest gap of ``stats[metric][field]`` from the exact statistics
    of ``per_scenario[metric]`` (S, R), over each metric's scale."""
    worst = 0.0
    for name in ROUND_FOLD:
        x = np.asarray(per_scenario[name], np.float64)
        scale = max(float(np.max(np.abs(x))), 1e-30)
        exact = exact_stats(x)
        for field in ("mean", "var", "min", "max"):
            s = scale ** 2 if field == "var" else scale
            worst = max(worst, _worst(
                np.abs(np.asarray(stats[name][field], np.float64)
                       - exact[field]) / s))
    return worst


def per_scenario_metrics(metrics) -> dict:
    return {"accuracy": np.asarray(metrics.accuracy),
            "round_time": np.asarray(metrics.round_time),
            "energy_total": np.asarray(metrics.energy_total),
            "n_selected": np.asarray(metrics.n_selected)}


def sample(out: dict, n: int) -> list:
    """Window positions of the scenarios checked against the reference."""
    rng = np.random.default_rng(out["seeds"]["sample"])
    total = out["attempted"]
    return sorted(rng.choice(total, size=min(n, total), replace=False)
                  .tolist())


def bad_outputs(metrics, params, k: int) -> int:
    m = per_scenario_metrics(metrics)
    ok = np.ones(m["accuracy"].shape[0], bool)
    for name in ("accuracy", "round_time", "energy_total"):
        ok &= np.all(np.isfinite(m[name]), axis=1)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf = np.asarray(leaf)
        ok &= np.all(np.isfinite(leaf.reshape(leaf.shape[0], -1)), axis=1)
    ok &= np.all((m["n_selected"] >= 1) & (m["n_selected"] <= k), axis=1)
    return int(np.sum(~ok))


def verify(out: dict) -> None:
    """Fill ``checks``, ``correct`` and ``failed`` of a run's result."""
    cell, host = out["cell"], out["host"]
    cfg, limits = cell["cfg"], cell["limits"]
    metrics = host["metrics"]
    numbers = {"compiles": float(out["compiles_in_window"]),
               "bad_outputs": float(bad_outputs(metrics, host["params"],
                                                cfg["devices"]))}
    stats = {name: welford_stats(host["agg"]["round"][name])
             for name in ROUND_FOLD}
    numbers["fold_rel"] = fold_gap(stats, per_scenario_metrics(metrics))
    t0 = time.perf_counter()
    failed = 0
    got_all = []
    for i in sample(out, limits["reference_scenarios"]):
        ref = reference.simulate(cfg, cell["traffic_mix"]["method"],
                                 out["data"], out["params0"],
                                 out["seeds"]["base"], host["start"] + i)
        mine = scenario(metrics, host["params"], i)
        got = compare(mine, ref, limits["early_rounds"])
        print(describe(host["start"] + i, mine, ref), file=sys.stderr)
        if any(got[k] > limits[k] for k in got
               if k not in MEAN_OVER_SAMPLE):
            failed += 1
        got_all.append(got)
    numbers.update(combine(got_all))
    out["reference_s"] = time.perf_counter() - t0
    out["checks"] = {k: {"value": v, "limit": float(limits[k])}
                     for k, v in numbers.items()}
    out["correct"] = all(c["value"] <= c["limit"]
                         for c in out["checks"].values())
    out["failed"] = max(failed, int(numbers["bad_outputs"]),
                        0 if out["correct"] else 1)


def peaks(kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def per_layer(out: dict) -> dict:
    """The cell's per-layer metrics, each from its own reader."""
    cell, host = out["cell"], out["host"]
    ctx = {"cfg": cell["cfg"], "chips": cell["chips"],
           "scenario_rounds": out["attempted"] * out["rounds"],
           "selected": np.asarray(host["metrics"].selected),
           "sizes": np.asarray(out["data"]["sizes"]),
           "test_samples": int(out["data"]["test_labels"].shape[0]),
           "peaks": peaks(out["device"]["kind"])}
    found = {}
    for m in cell["per_layer"]:
        mod = importlib.import_module(f"feelbench.metrics.{m['name']}")
        value = mod.reduce(out["trace"], ctx)
        if value is not None:
            found[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return found


def report(out: dict) -> None:
    """Print the compared numbers (stderr) and the result line (stdout)."""
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if "trace" in out:
        tr = out["trace"]
        result["metrics"] = per_layer(out)
        device = dict(out["device"], busy_s=tr["busy_s"],
                      window_s=tr["window_s"])
        result["device"] = device
        result["breakdown"] = tr["breakdown"]
    else:
        rate = out["attempted"] * out["rounds"] / out["window_s"]
        result["metrics"] = {
            "scenario_rounds_per_s": {"value": rate, "unit": "scn-rounds/s"},
            "setup_s": {"value": out["setup_s"], "unit": "s"}}
        result["device"] = out["device"]
    result["checks"] = out["checks"]
    print(f"window_s {out['window_s']!r} setup_s {out['setup_s']!r} "
          f"reference_s {out['reference_s']!r}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
