"""FEEL integration tests: Algorithm 1 end-to-end at small scale."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federated, scheduler, wireless
from repro.data import partition, synthetic
from repro.models import paper_nets


@pytest.fixture(scope="module")
def small_world():
    imgs, labs = synthetic.generate(0, samples_per_class=600)
    pspec = partition.PartitionSpec(num_devices=12, num_shards=100,
                                    shard_size=50)
    data = partition.partition(imgs, labs, seed=1, spec=pspec)
    wcfg = wireless.WirelessConfig()
    net = wireless.sample_network(jax.random.key(0), 12, wcfg)
    return data, net, wcfg


def _run(data, net, wcfg, method, rounds=4, model="mlp", **sch_kw):
    mspec = paper_nets.PaperNetSpec(kind=model)
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method=method, n_min=2,
                                     iterations_max=4, **sch_kw)
    fcfg = federated.FLConfig(num_rounds=rounds, batch_size=50,
                              learning_rate=0.1)
    return federated.run_federated(
        init_params=params,
        loss_fn=functools.partial(paper_nets.loss_fn, spec=mspec),
        eval_fn=functools.partial(paper_nets.accuracy, spec=mspec),
        data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
        key=jax.random.key(4))


def test_fl_learns(small_world):
    data, net, wcfg = small_world
    _, hist = _run(data, net, wcfg, "das")
    assert hist[-1].accuracy > 0.5, \
        f"FL failed to learn: {hist[-1].accuracy}"
    assert hist[-1].accuracy > hist[0].accuracy


def test_round_accounting(small_world):
    data, net, wcfg = small_world
    _, hist = _run(data, net, wcfg, "das", rounds=3)
    for rec in hist:
        assert rec.n_selected >= 2              # n_min
        assert rec.round_time > 0.0
        assert rec.energy_total > 0.0
        assert rec.energy_per_device <= rec.energy_total + 1e-9


def test_full_baseline_selects_everyone(small_world):
    data, net, wcfg = small_world
    _, hist = _run(data, net, wcfg, "full", rounds=2)
    assert all(r.n_selected == data.num_devices for r in hist)


def test_ages_reset_on_selection(small_world):
    data, net, wcfg = small_world
    _, hist = _run(data, net, wcfg, "random", rounds=3,
                   n_fixed=3)
    # With n_fixed=3, every round selects exactly 3.
    assert all(r.n_selected == 3 for r in hist)


def test_fedavg_aggregate_weighted():
    stacked = {"w": jnp.stack([jnp.ones((4,)), 3.0 * jnp.ones((4,))])}
    weights = jnp.asarray([0.25, 0.75])
    out = federated.fedavg_aggregate(stacked, weights)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.5)


def test_fedavg_aggregate_kernel_path():
    key = jax.random.key(5)
    stacked = {"w": jax.random.normal(key, (6, 37))}
    weights = jax.nn.softmax(jax.random.normal(key, (6,)))
    ref_out = federated.fedavg_aggregate(stacked, weights,
                                         use_kernel=False)
    krn_out = federated.fedavg_aggregate(stacked, weights,
                                         use_kernel=True)
    np.testing.assert_allclose(np.asarray(krn_out["w"]),
                               np.asarray(ref_out["w"]), rtol=1e-5,
                               atol=1e-5)


def test_fedavg_aggregate_kernel_multi_leaf_pytree():
    """Kernel path flattens the whole pytree into ONE launch; parity with
    the tensordot path across heterogeneous leaf shapes."""
    key = jax.random.key(6)
    k = 5
    stacked = {
        "fc1": {"w": jax.random.normal(key, (k, 7, 11)),
                "b": jax.random.normal(jax.random.key(7), (k, 11))},
        "fc2": {"w": jax.random.normal(jax.random.key(8), (k, 11, 3))},
    }
    weights = jax.nn.softmax(jax.random.normal(jax.random.key(9), (k,)))
    ref = federated.fedavg_aggregate(stacked, weights, use_kernel=False)
    krn = federated.fedavg_aggregate(stacked, weights, use_kernel=True)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(krn)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5)


def test_kernel_agg_driver_parity(small_world):
    """use_kernel_agg=True runs the whole scan driver through the Pallas
    aggregation and must match the tensordot path."""
    data, net, wcfg = small_world
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method="random", n_min=2, n_fixed=2,
                                     iterations_max=2)
    outs = {}
    for use_kernel in (False, True):
        fcfg = federated.FLConfig(num_rounds=2, batch_size=50,
                                  learning_rate=0.1,
                                  use_kernel_agg=use_kernel)
        p, hist = federated.run_federated(
            init_params=params,
            loss_fn=functools.partial(paper_nets.loss_fn, spec=mspec),
            eval_fn=functools.partial(paper_nets.accuracy, spec=mspec),
            data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
            key=jax.random.key(4))
        outs[use_kernel] = (p, hist)
    for a, b in zip(jax.tree_util.tree_leaves(outs[False][0]),
                    jax.tree_util.tree_leaves(outs[True][0])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)
    assert all(np.array_equal(x.selected, y.selected)
               for x, y in zip(outs[False][1], outs[True][1]))


# ---------------------------------------------------------------------------
# Scan driver vs legacy loop; vmapped scenario batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["das", "random", "full"])
def test_scan_driver_matches_legacy_loop(small_world, method):
    """The device-resident scan driver must be bit-for-bit consistent
    with the legacy per-round loop: selection masks, round times,
    per-device energies, accuracies and final params (energy *totals*
    are compared at float tolerance — the fused in-scan reduction may
    sum in a different order than the legacy eager ``jnp.sum``)."""
    data, net, wcfg = small_world
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method=method, n_min=2,
                                     iterations_max=4)
    fcfg = federated.FLConfig(num_rounds=3, batch_size=50,
                              learning_rate=0.1)
    kw = dict(init_params=params,
              loss_fn=functools.partial(paper_nets.loss_fn, spec=mspec),
              eval_fn=functools.partial(paper_nets.accuracy, spec=mspec),
              data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
              key=jax.random.key(4))
    p_scan, h_scan = federated.run_federated(**kw)
    p_loop, h_loop = federated.run_federated_loop(**kw)
    assert len(h_scan) == len(h_loop)
    for a, b in zip(h_scan, h_loop):
        assert np.array_equal(a.selected, b.selected)
        assert a.n_selected == b.n_selected
        assert a.round_time == b.round_time
        np.testing.assert_allclose(a.energy_total, b.energy_total,
                                   rtol=1e-6)
        if b.accuracy == b.accuracy:        # not NaN
            assert a.accuracy == b.accuracy
        else:
            assert a.accuracy != a.accuracy
    for a, b in zip(jax.tree_util.tree_leaves(p_scan),
                    jax.tree_util.tree_leaves(p_loop)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scan_eval_stride(small_world):
    """eval_every > 1 skips evaluation (NaN accuracy) on the same rounds
    as the legacy loop: multiples of the stride plus the final round."""
    data, net, wcfg = small_world
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method="random", n_min=2, n_fixed=2)
    fcfg = federated.FLConfig(num_rounds=4, batch_size=50,
                              learning_rate=0.1)
    _, hist = federated.run_federated(
        init_params=params,
        loss_fn=functools.partial(paper_nets.loss_fn, spec=mspec),
        eval_fn=functools.partial(paper_nets.accuracy, spec=mspec),
        data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
        key=jax.random.key(4), eval_every=3)
    want_eval = [True, False, False, True]   # rounds 0, 3(final)
    got_eval = [r.accuracy == r.accuracy for r in hist]
    assert got_eval == want_eval


def test_batch_matches_independent_runs(small_world):
    """S=3 scenarios through run_federated_batch reproduce, scenario by
    scenario and bit-for-bit, three independent run_federated calls with
    the matching (net, key) pair — shape check + determinism."""
    data, net, wcfg = small_world
    del net
    num_scenarios, rounds = 3, 3
    nets = wireless.sample_networks(jax.random.key(21),
                                    num_scenarios, data.num_devices,
                                    wireless.WirelessConfig())
    keys = jax.random.split(jax.random.key(22), num_scenarios)
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method="das", n_min=2,
                                     iterations_max=3)
    fcfg = federated.FLConfig(num_rounds=rounds, batch_size=50,
                              learning_rate=0.1)
    loss = functools.partial(paper_nets.loss_fn, spec=mspec)
    ev = functools.partial(paper_nets.accuracy, spec=mspec)
    p_b, metrics = federated.run_federated_batch(
        init_params=params, loss_fn=loss, eval_fn=ev, data=data,
        nets=nets, wcfg=wcfg, scfg=scfg, fcfg=fcfg, keys=keys)
    assert metrics.selected.shape == (num_scenarios, rounds,
                                      data.num_devices)
    assert metrics.accuracy.shape == (num_scenarios, rounds)
    hists_b = federated.batch_metrics_to_records(metrics)
    for s in range(num_scenarios):
        net_s = jax.tree_util.tree_map(lambda a, s=s: a[s], nets)
        p_s, hist_s = federated.run_federated(
            init_params=params, loss_fn=loss, eval_fn=ev, data=data,
            net=net_s, wcfg=wcfg, scfg=scfg, fcfg=fcfg, key=keys[s])
        for a, b in zip(hists_b[s], hist_s):
            assert np.array_equal(a.selected, b.selected)
            assert a.round_time == b.round_time
            if b.accuracy == b.accuracy:
                assert a.accuracy == b.accuracy
        for a, b in zip(jax.tree_util.tree_leaves(p_b),
                        jax.tree_util.tree_leaves(p_s)):
            np.testing.assert_array_equal(np.asarray(a[s]), np.asarray(b))


def test_donated_params_scan_matches_undonated(small_world):
    """donate_params=True hands the init-params buffers to the scan carry
    (peak-memory open item): results must be identical, and the caller's
    obligation is only to not reuse the donated arrays afterwards."""
    data, net, wcfg = small_world
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method="random", n_min=2, n_fixed=2)
    fcfg = federated.FLConfig(num_rounds=2, batch_size=50,
                              learning_rate=0.1)
    kw = dict(loss_fn=functools.partial(paper_nets.loss_fn, spec=mspec),
              eval_fn=functools.partial(paper_nets.accuracy, spec=mspec),
              data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
              key=jax.random.key(4))
    p_ref, h_ref = federated.run_federated(init_params=params, **kw)
    donated = jax.tree_util.tree_map(jnp.array, params)  # fresh buffers
    p_don, h_don = federated.run_federated(init_params=donated,
                                           donate_params=True, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_don)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r.accuracy for r in h_ref] == [r.accuracy for r in h_don]


def _donation_fixture(small_world, rounds=2):
    data, net, wcfg = small_world
    mspec = paper_nets.PaperNetSpec(kind="mlp")
    params = paper_nets.init(jax.random.key(3), mspec)
    scfg = scheduler.SchedulerConfig(method="random", n_min=2, n_fixed=2)
    fcfg = federated.FLConfig(num_rounds=rounds, batch_size=50,
                              learning_rate=0.1)
    loss = functools.partial(paper_nets.loss_fn, spec=mspec)
    ev = functools.partial(paper_nets.accuracy, spec=mspec)
    hists = federated.client_histograms(data, fcfg.num_classes)
    test_x = synthetic.to_float(data.test_images)
    return data, net, wcfg, params, scfg, fcfg, loss, ev, hists, test_x


def _assert_donated(donated, warn_records):
    """The donation must actually be used: every initial-params buffer
    handed to the compiled sim is consumed (no aliasing copy), and XLA
    did not warn that it declined any donated buffer."""
    for leaf in jax.tree_util.tree_leaves(donated):
        assert leaf.is_deleted(), "donated buffer survived the call"
    declined = [str(w.message) for w in warn_records
                if "donated" in str(w.message).lower()]
    assert not declined, f"XLA declined the donation: {declined}"


def test_make_feel_sim_donates_params_buffer(small_world):
    data, net, wcfg, params, scfg, fcfg, loss, ev, hists, test_x = \
        _donation_fixture(small_world)
    sim = federated.make_feel_sim(loss_fn=loss, eval_fn=ev, wcfg=wcfg,
                                  scfg=scfg, fcfg=fcfg,
                                  capacity=data.capacity,
                                  donate_params=True)
    donated = jax.tree_util.tree_map(jnp.array, params)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = sim(donated, data.images, data.labels, data.mask,
                  data.sizes, hists, test_x, data.test_labels, net,
                  jax.random.key(4))
        jax.block_until_ready(out)
    _assert_donated(donated, rec)


def test_make_feel_sim_batch_donates_tiled_params(small_world):
    """The batch driver's donate contract: params pre-tiled to (S, ...)
    (tile_params) are donated into the vmapped scan carry — the tiled
    buffers are consumed and XLA does not fall back to an aliasing
    copy.  (A broadcast input cannot be donated; see
    make_feel_sim_batch.)"""
    data, _, wcfg, params, scfg, fcfg, loss, ev, hists, test_x = \
        _donation_fixture(small_world)
    s = 2
    nets = wireless.sample_networks(jax.random.key(21), s,
                                    data.num_devices, wcfg)
    keys = jax.random.split(jax.random.key(22), s)
    sim = federated.make_feel_sim_batch(loss_fn=loss, eval_fn=ev,
                                        wcfg=wcfg, scfg=scfg, fcfg=fcfg,
                                        capacity=data.capacity,
                                        donate_params=True)
    donated = federated.tile_params(params, s)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = sim(donated, data.images, data.labels, data.mask,
                  data.sizes, hists, test_x, data.test_labels, nets, keys)
        jax.block_until_ready(out)
    _assert_donated(donated, rec)


def test_run_federated_batch_donated_matches_undonated(small_world):
    """run_federated_batch(donate_params=True) tiles internally, leaves
    the caller's params intact, and returns identical results."""
    data, _, wcfg, params, scfg, fcfg, loss, ev, _, _ = \
        _donation_fixture(small_world)
    s = 2
    nets = wireless.sample_networks(jax.random.key(21), s,
                                    data.num_devices, wcfg)
    keys = jax.random.split(jax.random.key(22), s)
    kw = dict(init_params=params, loss_fn=loss, eval_fn=ev, data=data,
              nets=nets, wcfg=wcfg, scfg=scfg, fcfg=fcfg, keys=keys)
    p_ref, m_ref = federated.run_federated_batch(**kw)
    p_don, m_don = federated.run_federated_batch(donate_params=True, **kw)
    for leaf in jax.tree_util.tree_leaves(params):
        assert not leaf.is_deleted()       # caller's buffers untouched
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_don)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_ref.selected),
                                  np.asarray(m_don.selected))


def test_das_beats_random_on_noniid(small_world):
    """The paper's core claim at miniature scale: with few devices
    schedulable, data-aware selection reaches higher accuracy in equal
    rounds.  Averaged over 3 seeds to damp run-to-run noise."""
    data, net, wcfg = small_world
    gaps = []
    for seed in range(3):
        mspec = paper_nets.PaperNetSpec(kind="mlp")
        params = paper_nets.init(jax.random.key(seed), mspec)
        accs = {}
        for method in ("das", "random"):
            scfg = scheduler.SchedulerConfig(method=method, n_min=2,
                                             n_fixed=2,
                                             iterations_max=4)
            fcfg = federated.FLConfig(num_rounds=4, batch_size=50,
                                      learning_rate=0.1)
            _, hist = federated.run_federated(
                init_params=params,
                loss_fn=functools.partial(paper_nets.loss_fn,
                                          spec=mspec),
                eval_fn=functools.partial(paper_nets.accuracy,
                                          spec=mspec),
                data=data, net=net, wcfg=wcfg, scfg=scfg, fcfg=fcfg,
                key=jax.random.key(seed + 40))
            accs[method] = hist[-1].accuracy
        gaps.append(accs["das"] - accs["random"])
    assert float(np.mean(gaps)) > -0.02, \
        f"DAS under-performs random: gaps={gaps}"


# ---------------------------------------------------------------------------
# Packed local trainer: bitwise the padded one, lane by lane
# ---------------------------------------------------------------------------

def _padded_trainer(loss_fn, cfg):
    """The plain reference: every lane runs all ``max_steps`` steps of
    its own masked scan, vmapped over the lanes."""

    def local_sgd(params, images, labels, mask, steps_active, key):
        cap = images.shape[0]

        def step(carry, inp):
            p, vel = carry
            k, active = inp
            idx = jax.random.randint(k, (cfg.batch_size,), 0, cap)
            bx = synthetic.to_float(images[idx])
            by = labels[idx]
            bm = mask[idx]
            g = jax.grad(loss_fn)(p, bx, by, bm)
            vel = jax.tree_util.tree_map(
                lambda v, gi: cfg.momentum * v + gi, vel, g)
            p_new = jax.tree_util.tree_map(
                lambda w, v: w - cfg.learning_rate * v, p, vel)
            p = jax.tree_util.tree_map(
                lambda new, old: jnp.where(active > 0.0, new, old),
                p_new, p)
            return (p, vel), None

        vel0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        keys = jax.random.split(key, steps_active.shape[0])
        (params, _), _ = jax.lax.scan(step, (params, vel0),
                                      (keys, steps_active))
        return params

    return jax.vmap(local_sgd, in_axes=(None, 0, 0, 0, 0, 0))


# name: (lanes, steps of each lane or None for its own size's, selected,
# momentum)
PACK_CASES = {
    "unbalanced_some_unselected": (12, None, [1, 0, 1, 1, 0, 1] * 2, 0.0),
    "odd_lanes": (7, None, [1] * 7, 0.0),
    "nothing_selected": (12, None, [0] * 12, 0.0),
    "all_at_max_steps": (6, "max", [1] * 6, 0.0),
    "momentum": (9, None, [1, 1, 0, 1, 1, 1, 0, 1, 1], 0.9),
}


@pytest.mark.parametrize("model", ["mlp", "cnn"])
@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_packed_trainer_matches_padded_bitwise(small_world, case, model):
    data = small_world[0]
    lanes, steps, selected, momentum = PACK_CASES[case]
    cfg = federated.FLConfig(batch_size=50, learning_rate=0.1,
                             momentum=momentum)
    mspec = paper_nets.PaperNetSpec(kind=model)
    params = paper_nets.init(jax.random.key(3), mspec)
    loss = functools.partial(paper_nets.loss_fn, spec=mspec)
    max_steps = federated._max_local_steps(cfg, data.capacity)
    if steps is None:
        steps = cfg.local_epochs * np.ceil(
            np.asarray(data.sizes[:lanes], np.float32) / cfg.batch_size)
    elif steps == "max":
        steps = np.full(lanes, max_steps)
    active = (np.arange(max_steps)[None, :] < np.asarray(steps)[:, None]) \
        * np.asarray(selected, np.float32)[:, None]
    args = (params, data.images[:lanes], data.labels[:lanes],
            data.mask[:lanes], jnp.asarray(active, jnp.float32),
            jax.random.split(jax.random.key(7), lanes))
    packed = jax.jit(federated.make_local_trainer(loss, cfg))(*args)
    padded = jax.jit(_padded_trainer(loss, cfg))(*args)
    for got, want, init in zip(jax.tree_util.tree_leaves(packed),
                               jax.tree_util.tree_leaves(padded),
                               jax.tree_util.tree_leaves(params)):
        assert got.shape == want.shape == (lanes,) + init.shape
        for lane in range(lanes):
            np.testing.assert_array_equal(np.asarray(got[lane]),
                                          np.asarray(want[lane]))
            if not active[lane].any():
                np.testing.assert_array_equal(np.asarray(got[lane]),
                                              np.asarray(init))


@pytest.mark.parametrize("lanes", [1, 2, 5, 8, 100])
def test_pack_plan_pairs_every_lane_once(lanes):
    rng = np.random.default_rng(lanes)
    for draw in range(20):
        steps = rng.integers(0, 21, lanes) * (rng.random(lanes) < 0.7)
        if draw == 0:
            steps[:] = 0
        plan = jax.device_get(federated.pack_plan(jnp.asarray(steps)))
        half = -(-lanes // 2)
        assert plan.first.shape == plan.second.shape == (half,)
        runs = np.concatenate([plan.first, plan.second[:lanes // 2]])
        assert sorted(runs.tolist()) == list(range(lanes))
        np.testing.assert_array_equal(plan.r_first, steps[plan.first])
        want_second = steps[plan.second]
        if lanes % 2:
            assert want_second.shape and plan.r_second[-1] == 0
            want_second[-1] = 0
        np.testing.assert_array_equal(plan.r_second, want_second)
        assert np.all(plan.r_first >= plan.r_second)
        assert int(plan.trips) == int(np.max(plan.r_first + plan.r_second))
        assert int(plan.trips) <= 2 * int(steps.max())
        if not steps.any():
            assert int(plan.trips) == 0
