"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The script's phase functions run in this process on the CPU (Pallas in
interpret mode) with K = 8 devices, 2 rounds and the MLP, so the paths,
arguments and checks the chip run takes are exercised here for free.
The entry point itself must refuse a process without a TPU.
"""

import dataclasses
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.launch import paper

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def setup():
    return paper.paper_setup(model="mlp", method="das", devices=8,
                             rounds=2, seed=0)


@pytest.fixture(scope="module")
def single(smoke, setup):
    return smoke.run_single(setup)


def test_entry_point_refuses_a_process_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "found platform 'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_single_phase(single, setup, capsys):
    assert len(single) == setup.fcfg.num_rounds
    assert all(1 <= r.n_selected <= 8 for r in single)


def test_reference_phase_matches_on_the_same_backend(smoke, setup, single):
    info = smoke.run_reference(setup, single)
    assert info["worst"] == {"accuracy_abs": 0.0, "energy_rel": 0.0,
                             "time_rel": 0.0}


def test_reference_refuses_kernel_lanes(smoke, setup, single):
    on = dataclasses.replace(
        setup, fcfg=dataclasses.replace(setup.fcfg, use_kernel_agg=True))
    with pytest.raises(ValueError, match="kernel lane"):
        smoke.run_reference(on, single)


def test_kernel_phase(smoke, setup, single):
    info = smoke.run_kernels(setup, single)
    # Interpret mode on the CPU: the kernels lower to plain ops.
    assert info["tpu_custom_calls"] == 0
    assert len(info["admission_diffs"]) == setup.fcfg.num_rounds


def test_sweep_phase(smoke, setup):
    info = smoke.run_sweep(setup, scenarios=4)
    assert info["scenarios"] == 4
    assert np.all(np.isfinite(info["mean"]["accuracy"]))


def test_sharded_phase_on_present_devices(smoke, setup):
    chips = 4 if len(jax.devices()) >= 4 else 1
    info = smoke.run_sharded(setup, chips=chips, scenarios=4)
    assert info["mesh_devices"] == chips
    assert info["sharded_output_devices"] == chips


def test_compare_names_what_differs(smoke, single, capsys):
    other = [dataclasses.replace(r) for r in single]
    sel = other[0].selected.copy()
    sel[0] = 1.0 - sel[0]
    other[0] = dataclasses.replace(other[0], selected=sel)
    other[1] = dataclasses.replace(other[1],
                                   energy_total=single[1].energy_total * 2)
    with pytest.raises(AssertionError, match="mismatch"):
        smoke.compare(single, other, acc_atol=0.02, cost_rtol=1e-3,
                      label="test")
    err = capsys.readouterr().err
    assert "round 0 admitted sets differ" in err
    assert "round 1: energy" in err


def test_check_records_rejects_a_bad_round(smoke, setup, single):
    bad = list(single)
    bad[-1] = dataclasses.replace(bad[-1], accuracy=float("nan"))
    with pytest.raises(AssertionError, match="non-finite"):
        smoke.check_records(bad, setup)
