"""CPU rehearsal of the harness (``feelbench/run.py``) at a tiny size.

    JAX_PLATFORMS=cpu python3 -m pytest -q -p no:cacheprovider feelbench/tests

Outside the repository's ``testpaths``: run by hand, as ``PERF.md``
says.  The tests steer the harness with ``tiny.py``; the program takes
no option for it.
"""

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys

from feelbench import check, run
from feelbench.tests import tiny

ROOT = run.ROOT
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def result_line(out: dict) -> dict:
    buf, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(err):
        check.report(out)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_no_chip_exits_nonzero_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "feelbench/run.py", "--workload", "cnn-das-s8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_tiny_run_prints_the_result_line():
    out = run.run(tiny.cell(), 2**31 + 12345, 1.0, False, "",
                  tiny.cpu_device())
    line = result_line(out)
    assert list(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {"scenario_rounds_per_s", "setup_s"}
    assert line["metrics"]["scenario_rounds_per_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    assert line["checks"]["compiles"]["value"] == 0


def test_traced_tiny_run_reports_per_layer_metrics(monkeypatch, tmp_path):
    monkeypatch.setattr(check, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    out = run.run(tiny.cell(), 5, 0.0, True, str(tmp_path),
                  tiny.cpu_device())
    line = result_line(out)
    assert list(line) == RESULT_KEYS[:5] + ["breakdown", "checks"]
    assert line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0
    # No device plane on the CPU: only the whole step's share is read.
    assert set(line["metrics"]) == {"step_mfu"}


def test_a_new_workload_is_found_by_its_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "feelbench"), tmp_path / "feelbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "cnn-das-s4",
                               "config": "paper-cnn-k100",
                               "traffic": "das-s4", "chips": 1,
                               "why": "a smaller chunk"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "feelbench/traffic/das-s4.json").write_text(
        json.dumps({"method": "das", "scenarios_per_chunk": 4}))
    shutil.copy(tmp_path / "feelbench/limits/cnn-das-s8.json",
                tmp_path / "feelbench/limits/cnn-das-s4.json")
    cell = run.load_cell("cnn-das-s4", root=str(tmp_path))
    assert cell["traffic_mix"] == {"method": "das", "scenarios_per_chunk": 4}
    assert cell["cfg"]["name"] == "paper-cnn-k100"
    assert [m["name"] for m in cell["per_layer"]] == [
        m["name"] for m in bench["per_layer"]]


FOUR_CHIPS = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from feelbench import run
from feelbench.tests import tiny
cell = tiny.cell("cnn-das-s8", scenarios=4, chips=4)
cell["limits"] = dict(cell["limits"], reference_scenarios=4)
bench = run.build(cell, 9)
assert bench.engine.mesh.devices.size == 4, bench.engine.mesh
w = run.run_chunks(bench, 4, 0.0, max_chunks=1)
metrics = w["outs"][0][2]
assert len(metrics.accuracy.sharding.device_set) == 4
out = run.run(cell, 9, 0.0, False, "", tiny.cpu_device(4))
assert out["correct"], out["checks"]
print("OK", out["attempted"])
"""


def test_four_chip_cell_runs_sharded_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(root=ROOT, src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1].startswith("OK")
