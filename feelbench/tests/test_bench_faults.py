"""A run with the timed path broken underneath comes out not correct.

Each test plants one fault in the program (by monkeypatching, at the
tests' tiny size on the CPU), skips the harness's look for a chip, and
drives the rest of a run: set-up, window, check.  ``correct`` has to
come out false, and the tests name the number that catches the fault.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from feelbench import run
from feelbench.tests import tiny
from repro import sweep
from repro.core import federated
from repro.models import paper_nets
from repro.sweep import engine as sweep_engine


def tiny_run(cell=None, seed=21):
    cell = cell or tiny.cell()
    # Every scenario of the window is checked at this size.
    cell["limits"] = dict(cell["limits"], reference_scenarios=64)
    return run.run(cell, seed, 0.0, False, "", tiny.cpu_device())


def failing(out) -> set:
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    out = tiny_run()
    assert out["correct"], out["checks"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    def frozen(trainer, max_steps, cfg, params, *args, **kw):
        return params
    monkeypatch.setattr(federated, "_train_round", frozen)
    out = tiny_run()
    assert not out["correct"]
    assert failing(out) & {"acc_abs", "acc_early_mean", "model_rel"}


def test_half_of_the_batch_left_out(monkeypatch):
    orig = paper_nets.loss_fn

    def half(params, images, labels, mask, spec):
        b = mask.shape[0]
        keep = (jnp.arange(b) < b // 2).astype(mask.dtype)
        return orig(params, images, labels, mask * keep, spec)
    monkeypatch.setattr(paper_nets, "loss_fn", half)
    out = tiny_run()
    assert not out["correct"]
    assert failing(out) & {"acc_abs", "acc_early_mean", "model_rel"}


def test_half_of_the_devices_left_out_of_fedavg(monkeypatch):
    orig = federated.fedavg_aggregate

    def half(client_params, weights, use_kernel=False):
        k = weights.shape[0]
        w = jnp.where(jnp.arange(k) < k // 2, weights, 0.0)
        return orig(client_params, w / jnp.maximum(jnp.sum(w), 1e-12),
                    use_kernel)
    monkeypatch.setattr(federated, "fedavg_aggregate", half)
    out = tiny_run()
    assert not out["correct"]
    assert failing(out) & {"acc_abs", "model_rel"}


def first_of(metrics, share: int):
    """The metrics with all but the first 1/share of the scenarios
    blanked out (NaN is what the fold leaves out)."""
    s = metrics.accuracy.shape[0]
    keep = jnp.arange(s) < s // share
    return jax.tree_util.tree_map(
        lambda a: jnp.where(keep.reshape((s,) + (1,) * (a.ndim - 1)),
                            a.astype(jnp.float32), jnp.nan), metrics)


def test_half_of_the_scenarios_left_out_of_the_fold(monkeypatch):
    orig = sweep_engine.aggregate_fold

    def half(agg, metrics, target):
        return orig(agg, first_of(metrics, 2), target)
    monkeypatch.setattr(sweep_engine, "aggregate_fold", half)
    out = tiny_run()
    assert not out["correct"]
    assert failing(out) == {"fold_rel"}


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    orig = sweep.SweepEngine.chunk_outputs

    def altered(self, point, start, size):
        params, metrics = orig(self, point, start, size)
        first = jnp.arange(size) == 0
        metrics.energy = metrics.energy * jnp.where(first, 1.01, 1.0)[
            :, None, None]
        return params, metrics
    monkeypatch.setattr(sweep.SweepEngine, "chunk_outputs", altered)
    out = tiny_run()
    assert not out["correct"]
    assert "energy_rel" in failing(out)


CROSS_CHIP = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from feelbench import run
from feelbench.tests import tiny
from feelbench.tests.test_bench_faults import first_of
from repro.sweep import engine as sweep_engine
orig = sweep_engine.aggregate_fold
def local(agg, metrics, target):
    # Each chip folds its own quarter; the reduction across chips is
    # left out and chip 0's partial stands for the whole.
    return orig(agg, first_of(metrics, 4), target)
sweep_engine.aggregate_fold = local
cell = tiny.cell("cnn-das-s8", scenarios=4, chips=4)
out = run.run(cell, 31, 0.0, False, "", tiny.cpu_device(4))
bad = {{k for k, c in out["checks"].items() if c["value"] > c["limit"]}}
print("RESULT", out["correct"], sorted(bad))
"""


def test_the_exchange_between_chips_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CROSS_CHIP.format(root=run.ROOT,
                             src=os.path.join(run.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=run.ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == "RESULT False ['fold_rel']"
