"""Model families (``feelbench/models``), at the tests' tiny size.

- The paper nets behind the family lookup reproduce, bit for bit, what
  the harness made before the lookup existed: ``data/harness_fixture.json``
  holds digests, recorded with that harness, of each cell's data and
  initial weights, of one reference scenario per method, of one chunk
  of the program's outputs, and the work counts of both nets.
- The reference trains the devices in blocks as it does in one.
- A new family (``toy_family.py``) runs a whole cell from new files
  alone, in a copy of the harness that no existing file of which names
  it.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from feelbench import models, reference, run, work
from feelbench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "harness_fixture.json")) as _f:
    FIXTURE = json.load(_f)
CELLS = sorted(FIXTURE["cells"])


def digest(tree) -> str:
    """sha256 over a pytree's structure and each leaf's dtype, shape and
    bytes."""
    h = hashlib.sha256()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h.update(str(treedef).encode())
    for a in leaves:
        a = np.asarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def made(name: str):
    cell = tiny.cell(name)
    cfg = cell["cfg"]
    sd = run.seeds(FIXTURE["seed"])
    family = models.load(cfg)
    host = family.data(sd["data"], cfg)
    params = jax.device_get(family.init(jax.random.key(sd["init"]), cfg))
    return cell, sd, host, params


@pytest.mark.parametrize("name", CELLS)
def test_data_and_weights_are_the_recorded_ones(name):
    _, _, host, params = made(name)
    want = FIXTURE["cells"][name]
    assert {k: digest(v) for k, v in sorted(host.items())} == want["data"]
    assert digest(params) == want["params"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_scenario_is_the_recorded_one(name):
    cell, sd, host, params = made(name)
    method = cell["traffic_mix"]["method"]
    ref = reference.simulate(cell["cfg"], method, host, params, sd["base"], 5)
    assert ({k: digest(v) for k, v in sorted(ref.items())}
            == FIXTURE["reference"][method])


@pytest.mark.parametrize("name", CELLS)
def test_program_chunk_is_the_recorded_one(name):
    """The same functions handed to the program: the same outputs."""
    bench = run.build(tiny.cell(name), FIXTURE["seed"])
    h = run.to_host(run.run_chunks(bench, 2, 0.0, max_chunks=1))
    assert {"params": digest(h["params"]), "metrics": digest(h["metrics"])} \
        == FIXTURE["program"][name]


def paper_config(model: str) -> dict:
    cfg = run.load_cell("cnn-das-s8")["cfg"]
    if model == "mlp":
        cfg = dict(cfg, model="mlp", params=159_010,
                   net={"hidden": 200, "classes": 10, "image": 28})
    return cfg


@pytest.mark.parametrize("model", ["cnn", "mlp"])
def test_work_counts_are_the_recorded_ones(model):
    cfg = paper_config(model)
    want = FIXTURE["work"][model]
    sel = np.array([[[1, 0, 1, 1], [0, 1, 1, 0]]], np.float32)
    sizes = np.array([50, 120, 900, 7])
    family = models.load(cfg)
    got = {"forward_flops": family.forward_flops(cfg),
           "num_params": family.uploaded_params(cfg),
           "train_flops": work.train_flops(sel, sizes, cfg),
           "eval_flops": work.eval_flops(3, 6000, cfg),
           "fedavg_bytes": work.fedavg_bytes(sel, cfg),
           "init_params": digest(jax.device_get(family.init(
               jax.random.key(7), cfg)))}
    assert got == want


@pytest.mark.parametrize("block", [3, 4])
def test_reference_trains_in_blocks_as_in_one(monkeypatch, block):
    """Blocks of 3 (8 devices padded to 9) or 4 devices against one
    block of all 8: the same schedules, the model to float32 rounding."""
    cell, sd, host, params = made("cnn-das-s8")
    cfg = cell["cfg"]
    whole = reference.simulate(cfg, "das", host, params, sd["base"], 5)
    monkeypatch.setattr(models.load(cfg), "reference_block",
                        lambda cfg: block)
    reference._round_fn.cache_clear()
    try:
        parts = reference.simulate(cfg, "das", host, params, sd["base"], 5)
    finally:
        reference._round_fn.cache_clear()
    for k in ("selected", "energy", "round_time", "iterations"):
        np.testing.assert_array_equal(parts[k], whole[k])
    for a, b in zip(jax.tree_util.tree_leaves(parts["params"]),
                    jax.tree_util.tree_leaves(whole["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


TOY = "blobs"          # the family's name in the copied harness only

TOY_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from feelbench import check, models, run
from feelbench.tests import tiny
cell = tiny.cell({workload!r})
cfg = cell["cfg"]
family = models.load(cfg)
out = run.run(cell, 2**31 + 777, 0.0, False, "", tiny.cpu_device())
check.peaks = lambda kind: {{"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
traced = run.run(cell, 2**31 + 778, 0.0, True, "", tiny.cpu_device())
print(json.dumps({{
    "file": family.__file__, "correct": out["correct"],
    "checks": out["checks"], "traced_correct": traced["correct"],
    "per_layer": sorted(check.per_layer(traced)),
    "images": list(out["data"]["images"].shape),
    "labels": sorted(set(out["data"]["labels"].ravel().tolist())),
    "forward_flops": family.forward_flops(cfg),
    "num_params": family.uploaded_params(cfg)}}))
"""


def toy_tree(dest) -> str:
    """A copy of the harness with the toy family added as new files:
    its module, a configuration, limits and the entries in
    ``BENCHMARK.json``.  Returns the new workload's name."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), dest)
    home = os.path.join(dest, "feelbench")
    shutil.copytree(os.path.join(run.ROOT, "feelbench"), home,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                os.path.join(home, "models", TOY + ".py"))
    cfg = run.read_json(run.ROOT, "feelbench", "configs",
                        "paper-cnn-k100.json")
    cfg.update(name=f"{TOY}-k12", model=TOY, devices=12, rounds=4,
               net={"features": 12, "hidden": 16, "classes": 4,
                    "reference_block": 3},
               data={"samples_per_class": 60, "num_shards": 24,
                     "shard_size": 10, "min_shards": 1, "max_shards": 4,
                     "test_fraction": 0.25, "counts_seed": 0})
    cfg["params"] = (12 + 1) * 16 + (16 + 1) * 4
    with open(os.path.join(home, "configs", cfg["name"] + ".json"),
              "w") as f:
        json.dump(cfg, f)
    workload = f"{TOY}-das"
    shutil.copy(os.path.join(home, "limits", "cnn-das-s8.json"),
                os.path.join(home, "limits", workload + ".json"))
    path = os.path.join(dest, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": cfg["name"], "source": "toy",
                             "file": f"feelbench/configs/{cfg['name']}.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": workload, "config": cfg["name"],
                               "traffic": "das-s8", "chips": 1,
                               "why": "toy"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return workload


def test_a_new_family_runs_from_new_files_alone(tmp_path):
    harness = [os.path.join(d, f)
               for d, _, fs in os.walk(os.path.join(run.ROOT, "feelbench"))
               if os.sep + "tests" not in d for f in fs if f.endswith(".py")]
    harness.append(os.path.join(run.ROOT, "BENCHMARK.json"))
    for p in harness:
        with open(p) as f:
            assert TOY not in f.read(), p
    workload = toy_tree(tmp_path)
    code = TOY_RUN.format(root=str(tmp_path),
                          src=os.path.join(run.ROOT, "src"),
                          workload=workload)
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["file"] == str(tmp_path / "feelbench" / "models"
                              / (TOY + ".py"))
    assert got["correct"] and got["traced_correct"], got["checks"]
    # 8 devices (tiny.py), 12 u8 features a sample, 4 classes.
    assert got["images"][0] == 8 and got["images"][2:] == [12]
    assert got["labels"] == [0, 1, 2, 3]
    assert got["forward_flops"] == 2 * (12 * 16 + 16 * 4)
    assert got["num_params"] == 276
    assert got["per_layer"] == ["step_mfu"]
