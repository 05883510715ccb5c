"""In-scan telemetry tests (DESIGN.md §13): the inert-dispatch bitwise
contract across subsystem compositions, frame contents, batch==singles
parity on telemetry leaves, the legacy loop's host-collected frames,
JSONL sink round-trips through the report CLI, the shared rewind
contract, and the RoundRecord sentinel fix."""

import contextlib
import dataclasses
import functools
import re

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import (compression, events, faults, federated,
                        scheduler, streaming, wireless)
from repro.data import partition, synthetic
from repro.models import paper_nets
from repro.telemetry import record as record_lib
from repro.telemetry import report as report_lib
from repro.telemetry import sinks


# ---------------------------------------------------------------------------
# Fixtures: one tiny world shared module-wide (compiles dominate runtime)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def world():
    imgs, labs = synthetic.generate(0, samples_per_class=200)
    data = partition.partition(
        imgs, labs, seed=1,
        spec=partition.PartitionSpec(num_devices=8, num_shards=36,
                                     shard_size=50))
    mspec = paper_nets.PaperNetSpec(kind="mlp", mlp_hidden=8)
    params = paper_nets.init(jax.random.key(3), mspec)
    loss = functools.partial(paper_nets.loss_fn, spec=mspec)
    ev = functools.partial(paper_nets.accuracy, spec=mspec)
    return data, params, loss, ev


WCFG = wireless.WirelessConfig()
SCFG = scheduler.SchedulerConfig(method="das", n_min=2, iterations_max=3,
                                 reliability_weight=0.4)
FL = federated.FLConfig(num_rounds=3, batch_size=50, learning_rate=0.1)
TEL = telemetry.TelemetryConfig()

# Subsystem compositions the bitwise contract must hold across.
COMPOSITIONS = {
    "plain": {},
    "faulty": {"faults": faults.FaultConfig(drop_prob=0.3, max_retries=2,
                                            reliability_ema=0.3)},
    "compressed": {"compression": compression.CompressionConfig(
        codec="quant", bit_width=8)},
    "streaming": {"stream": streaming.StreamConfig()},
    "dispatch": {"dispatch_cap": 4},
    "async": {"events": events.EventConfig(availability="churn",
                                           buffer_size=2,
                                           tick_horizon=0.5,
                                           num_events=4),
              "faults": faults.FaultConfig(reliability_ema=0.3)},
}


def _run_kwargs(world):
    data, params, loss, ev = world
    net = wireless.sample_network(jax.random.key(0), data.num_devices,
                                  WCFG)
    return dict(init_params=params, loss_fn=loss, eval_fn=ev, data=data,
                net=net, wcfg=WCFG, scfg=SCFG, key=jax.random.key(42))


def _same_tree(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# Config normalization (the faults.py inert-dispatch pattern)
# ---------------------------------------------------------------------------

def test_inert_config_normalizes_to_none():
    assert telemetry.active(None) is None
    inert = telemetry.TelemetryConfig(scores=False, sub2=False,
                                      transport=False, faults=False,
                                      events=False, signals=False)
    assert telemetry.is_inert(inert)
    assert telemetry.active(inert) is None
    assert telemetry.active(TEL) is TEL
    assert not telemetry.is_inert(TEL)


def test_inert_config_builds_two_tuple_sim(world):
    # An all-False TelemetryConfig compiles the no-telemetry program:
    # same return arity, same values.
    inert = telemetry.TelemetryConfig(scores=False, sub2=False,
                                      transport=False, faults=False,
                                      events=False, signals=False)
    kw = _run_kwargs(world)
    out_none = federated.run_federated(fcfg=FL, **kw)
    out_inert = federated.run_federated(
        fcfg=dataclasses.replace(FL, telemetry=inert), **kw)
    assert len(out_none) == 2 and len(out_inert) == 2
    assert _same_tree(out_none[0], out_inert[0])


# ---------------------------------------------------------------------------
# The bitwise contract: telemetry only observes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", sorted(COMPOSITIONS))
def test_primary_outputs_bitwise_with_telemetry(world, comp):
    kw = _run_kwargs(world)
    fcfg = dataclasses.replace(FL, **COMPOSITIONS[comp])
    p0, h0 = federated.run_federated(fcfg=fcfg, **kw)
    p1, h1, frames = federated.run_federated(
        fcfg=dataclasses.replace(fcfg, telemetry=TEL), **kw)
    assert _same_tree(p0, p1)
    for a, b in zip(h0, h1):
        assert a.accuracy == b.accuracy
        assert a.round_time == b.round_time
        assert a.energy_total == b.energy_total
        assert a.n_selected == b.n_selected
        assert a.n_success == b.n_success
        assert np.array_equal(a.selected, b.selected)
    # Frames exist and carry one row per round.
    n = federated.sim_length(fcfg)
    assert all(np.asarray(v).shape[0] == n for v in frames.values())


def test_frame_contents_faulty(world):
    kw = _run_kwargs(world)
    fcfg = dataclasses.replace(FL, faults=COMPOSITIONS["faulty"]["faults"],
                               telemetry=TEL)
    _, hist, frames = federated.run_federated(fcfg=fcfg, **kw)
    k = kw["data"].num_devices
    expect = {"admitted", "dispatched", "delivered", "score_base",
              "score_boosted", "score_final", "score_rank", "alpha",
              "sub2_iters", "sub2_obj", "sub2_obj_eq", "sub2_gain",
              "payload_bits", "t_up", "energy_up", "fault_outage",
              "fault_dropout", "fault_straggler", "fault_attempts"}
    assert expect <= set(frames)
    for r, rec in enumerate(hist):
        # The realized set in the frame is the history's selected row,
        # and delivered counts match n_success.
        assert np.array_equal(np.asarray(frames["dispatched"][r]),
                              rec.selected)
        assert int(np.asarray(frames["delivered"][r]).sum()) \
            == rec.n_success
        assert int(np.asarray(frames["sub2_iters"][r])) >= 0
    # Score rank is a permutation of 0..K-1 each round.
    for row in np.asarray(frames["score_rank"]):
        assert sorted(row.tolist()) == list(range(k))
    # Fault events are disjoint classifications within the admitted set.
    outage = np.asarray(frames["fault_outage"])
    dropout = np.asarray(frames["fault_dropout"])
    assert ((outage + dropout) <= 1.0 + 1e-6).all()


def test_event_frames_include_event_state(world):
    kw = _run_kwargs(world)
    fcfg = dataclasses.replace(FL, **COMPOSITIONS["async"],
                               telemetry=TEL)
    _, _, frames = federated.run_federated(fcfg=fcfg, **kw)
    expect = {"avail", "free", "in_flight", "buffer_fill", "flushed",
              "staleness_tau", "clock", "model_version"}
    assert expect <= set(frames)
    clock = np.asarray(frames["clock"])
    assert (np.diff(clock) >= 0.0).all()       # time moves forward
    avail = np.asarray(frames["avail"])
    assert ((avail == 0.0) | (avail == 1.0)).all()


def test_streaming_frames_include_staleness(world):
    kw = _run_kwargs(world)
    fcfg = dataclasses.replace(FL, stream=streaming.StreamConfig(),
                               telemetry=TEL)
    _, _, frames = federated.run_federated(fcfg=fcfg, **kw)
    assert "staleness" in frames
    assert np.asarray(frames["staleness"]).shape \
        == (FL.num_rounds, kw["data"].num_devices)


# ---------------------------------------------------------------------------
# Batch == singles on every telemetry leaf
# ---------------------------------------------------------------------------

# The raw score surfaces re-expose the diversity index, whose (S, K, C)
# reduction lowers with a different vectorization under vmap than the
# single-scenario (K, C) program — a <=1-ULP float difference that the
# drivers' decision outputs (rank, admission, Sub2, energy) provably
# absorb (they ARE bitwise below).  Every other leaf is exact.
_ULP_LEAVES = ("score_base", "score_boosted", "score_final")


def test_batch_matches_singles_on_frames(world):
    data, params, loss, ev = world
    s = 3
    fcfg = dataclasses.replace(
        FL, faults=COMPOSITIONS["faulty"]["faults"], telemetry=TEL)
    nets = wireless.sample_networks(jax.random.key(5), s,
                                    data.num_devices, WCFG)
    keys = federated.scenario_keys(jax.random.key(11), 0, s)
    _, _, frames_b = federated.run_federated_batch(
        init_params=params, loss_fn=loss, eval_fn=ev, data=data,
        nets=nets, wcfg=WCFG, scfg=SCFG, fcfg=fcfg, keys=keys)
    for i in range(s):
        net_i = jax.tree_util.tree_map(lambda a, i=i: a[i], nets)
        _, _, frames_i = federated.run_federated(
            init_params=params, loss_fn=loss, eval_fn=ev, data=data,
            net=net_i, wcfg=WCFG, scfg=SCFG, fcfg=fcfg, key=keys[i])
        assert set(frames_b) == set(frames_i)
        for name in frames_i:
            a = np.asarray(frames_b[name][i])
            b = np.asarray(frames_i[name])
            if name in _ULP_LEAVES:
                np.testing.assert_allclose(a, b, rtol=2e-7, atol=0.0,
                                           err_msg=name)
            else:
                assert np.array_equal(a, b), name


# ---------------------------------------------------------------------------
# Legacy loop: host-collected frames, same field set
# ---------------------------------------------------------------------------

def test_loop_frames_match_scan(world):
    kw = _run_kwargs(world)
    fcfg = dataclasses.replace(
        FL, faults=COMPOSITIONS["faulty"]["faults"], telemetry=TEL)
    _, h_scan, f_scan = federated.run_federated(fcfg=fcfg, **kw)
    _, h_loop, f_loop = federated.run_federated_loop(fcfg=fcfg, **kw)
    assert set(f_scan) == set(f_loop)
    for a, b in zip(h_scan, h_loop):
        assert a.accuracy == b.accuracy
        assert np.array_equal(a.selected, b.selected)
    # Same <=1-ULP story as batch==singles: the loop's separately-jitted
    # round program fuses the diversity-index reduction differently
    # than the scan body, so the raw score surfaces may differ in the
    # last bit; every decision leaf is exact.
    for name in f_scan:
        a, b = np.asarray(f_scan[name]), np.asarray(f_loop[name])
        if name in _ULP_LEAVES:
            np.testing.assert_allclose(a, b, rtol=2e-7, atol=0.0,
                                       err_msg=name)
        else:
            assert np.array_equal(a, b), name


# ---------------------------------------------------------------------------
# Sinks: JSONL round-trip, report CLI, shared rewind contract
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_and_report(world, tmp_path, capsys):
    data, params, loss, ev = world
    fcfg = dataclasses.replace(
        FL, faults=COMPOSITIONS["faulty"]["faults"], telemetry=TEL)
    net = wireless.sample_network(jax.random.key(0), data.num_devices,
                                  WCFG)
    sim = federated.make_feel_sim(loss_fn=loss, eval_fn=ev, wcfg=WCFG,
                                  scfg=SCFG, fcfg=fcfg,
                                  capacity=data.capacity)
    hists = federated.client_histograms(data, fcfg.num_classes)
    test_x = synthetic.to_float(data.test_images)
    _, metrics, frames = sim(params, data.images, data.labels, data.mask,
                             data.sizes, hists, test_x,
                             data.test_labels, net, jax.random.key(42))
    log = tmp_path / "run.jsonl"
    man = sinks.run_manifest(fcfg, WCFG, SCFG)
    n = sinks.write_round_frames(str(log), frames, metrics=metrics,
                                 manifest=man)
    assert n == fcfg.num_rounds
    recs = sinks.read_jsonl(str(log))
    assert recs[0]["type"] == "manifest"
    rounds = [r for r in recs if r.get("type") == "round"]
    assert len(rounds) == n
    # Field round-trip: the JSON line holds the device-resolved frame.
    for r, rec in enumerate(rounds):
        assert rec["round"] == r
        assert rec["dispatched"] \
            == np.asarray(frames["dispatched"][r]).tolist()
        assert "accuracy" in rec and "n_success" in rec
        assert len(rec["score_final"]) == data.num_devices
        assert "sub2_iters" in rec and "fault_outage" in rec
    # Report CLI renders it and exits 0.
    assert report_lib.main([str(log)]) == 0
    out = capsys.readouterr().out
    for block in ("Run summary", "Round table", "Admission heatmap",
                  "Energy / fault breakdown", "Sub2 convergence"):
        assert block in out
    # Empty/absent logs exit non-zero.
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report_lib.main([str(empty)]) == 1
    assert report_lib.main([str(tmp_path / "missing.jsonl")]) == 2


def test_jsonl_rewind_contract(tmp_path):
    path = tmp_path / "stream.jsonl"
    with open(path, "w") as f:
        f.write('{"cursor": 1, "v": "a"}\n')
        f.write('{"cursor": 2, "v": "b"}\n')
        f.write('{"cursor": 3, "v": "c"}\n')
        f.write('{"cursor": 4, "v": "torn')      # no newline, torn tail
    sinks.jsonl_rewind(str(path), 2)
    recs = sinks.read_jsonl(str(path))
    assert [r["cursor"] for r in recs] == [1, 2]
    # Appending after rewind continues the stream.
    sinks.jsonl_append(str(path), {"cursor": 3, "v": "c2"})
    recs = sinks.read_jsonl(str(path))
    assert [r["v"] for r in recs] == ["a", "b", "c2"]
    # Rewinding a missing file is a no-op, not an error.
    sinks.jsonl_rewind(str(tmp_path / "nope.jsonl"), 0)


def test_manifest_identity(tmp_path):
    man = sinks.write_manifest(str(tmp_path / "m.json"), FL, WCFG, SCFG)
    assert man["config_fingerprint"] \
        == sinks.config_fingerprint(FL, WCFG, SCFG)
    assert man["jax_version"] == jax.__version__
    assert man["device_count"] >= 1
    # Same configs -> same fingerprint; different -> different.
    assert sinks.config_fingerprint(FL) == sinks.config_fingerprint(FL)
    assert sinks.config_fingerprint(FL) != sinks.config_fingerprint(
        dataclasses.replace(FL, num_rounds=99))


# ---------------------------------------------------------------------------
# Sweep integration: per-scenario JSONL streams
# ---------------------------------------------------------------------------

def test_sweep_telemetry_dir(world, tmp_path):
    from repro.sweep import grid as grid_lib
    from repro.sweep import runner as runner_lib

    data, params, loss, ev = world
    fl = dataclasses.replace(FL, num_rounds=2, telemetry=TEL)
    spec = grid_lib.SweepSpec(
        fl=fl, sched=SCFG, wireless=WCFG,
        axes=(grid_lib.Axis("sched", "method", ("das", "random")),),
        scenarios_per_point=2, base_seed=0)
    tel_dir = tmp_path / "tel"
    out = runner_lib.run_sweep(spec, data=data, loss_fn=loss, eval_fn=ev,
                               init_params=params, use_sharding=False,
                               telemetry_dir=str(tel_dir))
    assert len(out) == 2
    logs = sorted(p.name for p in tel_dir.glob("*.jsonl"))
    assert logs == ["point000_scn00000.jsonl", "point000_scn00001.jsonl",
                    "point001_scn00000.jsonl", "point001_scn00001.jsonl"]
    assert (tel_dir / "manifest.json").exists()
    for name in logs:
        recs = sinks.read_jsonl(str(tel_dir / name))
        assert len(recs) == fl.num_rounds
        scn = int(name.split("_scn")[1].split(".")[0])
        assert all(r["scenario"] == scn for r in recs)
    assert report_lib.main([str(tel_dir / n) for n in logs]) == 0


# ---------------------------------------------------------------------------
# Satellites: RoundRecord sentinel, phase scopes
# ---------------------------------------------------------------------------

def test_round_record_sentinel_normalized():
    rec = federated.RoundRecord(
        round=0, accuracy=0.5, n_selected=4, round_time=1.0,
        energy_total=2.0, energy_per_device=0.5,
        selected=np.ones(4))
    assert rec.n_success == 4                  # -1 sentinel never leaks
    rec2 = federated.RoundRecord(
        round=0, accuracy=0.5, n_selected=4, round_time=1.0,
        energy_total=2.0, energy_per_device=0.5,
        selected=np.ones(4), n_success=3)
    assert rec2.n_success == 3                 # explicit value kept


def test_reliable_edge_history_n_success(world):
    kw = _run_kwargs(world)
    _, hist = federated.run_federated(fcfg=FL, **kw)
    for rec in hist:
        assert rec.n_success == rec.n_selected
        assert rec.n_success >= 0


DRIVERS = {
    "sync": {},
    "streaming": {"stream": streaming.StreamConfig()},
    "events": {"events": events.EventConfig(availability="churn",
                                            buffer_size=2)},
}


def _sim_and_args(world, fcfg):
    """The single-scenario sim of ``fcfg`` and its arguments."""
    kw = _run_kwargs(world)
    data = kw["data"]
    sim = federated.make_feel_sim(
        loss_fn=kw["loss_fn"], eval_fn=kw["eval_fn"], wcfg=WCFG, scfg=SCFG,
        fcfg=fcfg, capacity=data.capacity)
    args = (kw["init_params"], data.images, data.labels, data.mask,
            data.sizes, federated.client_histograms(data, fcfg.num_classes),
            synthetic.to_float(data.test_images), data.test_labels,
            kw["net"], kw["key"])
    return sim, args


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_phase_scopes_name_the_compiled_ops(world, driver):
    """Every scope is in the op metadata of the compiled program, where
    the profiler reads each device op's scope path from; the gradient
    part covers the backward pass too, inside ``local_train``."""
    fcfg = dataclasses.replace(FL, **DRIVERS[driver])
    sim, args = _sim_and_args(world, fcfg)
    text = sim.lower(*args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    want = {f"repro/{p}" for p in telemetry.PHASES
            if p != "stream_refresh" or driver == "streaming"}
    want |= {"repro.batch", "repro.grad"}
    assert "repro/eval" in want
    assert {s for s in want if any(s in n for n in names)} == want
    assert any("repro/local_train" in n and "repro.grad/transpose(" in n
               for n in names)


@pytest.mark.parametrize("driver", ["sync", "events"])
def test_scopes_change_no_value(world, driver, monkeypatch):
    """The phase and part scopes name ops and change none: the program
    built with them as no-ops gives bitwise the same outputs."""
    fcfg = dataclasses.replace(FL, **DRIVERS[driver])
    sim, args = _sim_and_args(world, fcfg)
    scoped = jax.device_get(sim(*args))
    monkeypatch.setattr(telemetry, "phase_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(telemetry, "part_scope",
                        lambda name: contextlib.nullcontext())
    sim, args = _sim_and_args(world, fcfg)
    text = sim.lower(*args).as_text(debug_info=True)
    scopes = [f"repro/{p}" for p in telemetry.PHASES]
    assert not [s for s in scopes + ["repro.batch", "repro.grad"]
                if s in text]
    assert _same_tree(scoped, jax.device_get(sim(*args)))


def _whiles(jaxpr):
    """Every ``while`` equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _whiles(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _whiles(sub)


@pytest.mark.parametrize("driver,dispatch_cap", [
    ("sync", None), ("sync", 3), ("events", None)])
def test_local_train_launch_is_what_the_trainer_runs(world, driver,
                                                     dispatch_cap):
    """``local_train_launch`` names the grid the trainer packs: ``lanes``
    lanes — K, or the dispatch cap — of ``max_steps`` local steps.  The
    trainer runs them two to a lane: a ``while`` loop whose carry holds
    the params of ``ceil(lanes / 2)`` packed lanes, and no other width."""
    data, params = world[0], world[1]
    fcfg = dataclasses.replace(FL, dispatch_cap=dispatch_cap,
                               **DRIVERS[driver])
    lanes, max_steps = federated.local_train_launch(
        fcfg, data.num_devices, data.capacity)
    assert lanes == (data.num_devices if dispatch_cap is None
                     else dispatch_cap)
    assert max_steps == fcfg.local_epochs * -(-data.capacity
                                               // fcfg.batch_size)
    sim, args = _sim_and_args(world, fcfg)
    jaxpr = jax.make_jaxpr(sim)(*args).jaxpr
    shapes = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(params)}
    widths = set()
    for eqn in _whiles(jaxpr):
        c0 = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
        carry = [v.aval.shape for v in eqn.invars[c0:] if v.aval.shape]
        for w in {c[0] for c in carry}:
            if shapes <= {c[1:] for c in carry if c[0] == w}:
                widths.add(w)
    assert widths == {-(-lanes // 2)}
