"""Compile the FEEL path's kernels and its paper-scale scan for a TPU v5e.

Nothing runs here.  Each test compiles for a v5e chip that JAX describes
from its topology name, with no chip attached: the kernels with
``interpret=False`` at the paper's widths (K = 100 devices; P = 21,840
CNN and 159,010 MLP parameters; C = 10 classes), and the whole
single-scenario scan of the paper's CNN deployment.  The compiler
refuses what a chip would refuse (misaligned blocks, VMEM overflow, a
program larger than the chip's memory), and each kernel must reach the
program as a ``tpu_custom_call`` rather than as interpreted ops.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.  The tests skip where it cannot be described.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bandwidth, federated, scheduler, wireless
from repro.data import partition
from repro.kernels import ops
from repro.models import paper_nets

K = 100
CNN_P, MLP_P = 21_840, 159_010
V5E_HBM = 16 * 1024 ** 3
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compile cache off
    (a described chip's executables cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_kernels(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count(CUSTOM_CALL)


@pytest.mark.parametrize("p", [CNN_P, MLP_P])
@pytest.mark.parametrize("lane", ["plain", "masked", "stale"])
def test_fedavg_agg_compiles(one_chip, lane, p):
    u, w = _f32(one_chip, K, p), _f32(one_chip, K)
    if lane == "plain":
        fn, args = functools.partial(ops.fedavg_agg, interpret=False), (u, w)
    elif lane == "masked":
        fn = functools.partial(ops.fedavg_agg_masked, interpret=False)
        args = (u, w, w)
    else:
        fn = functools.partial(ops.fedavg_agg_stale, interpret=False)
        args = (u, w, w, w)
    assert _compiled_kernels(fn, *args) == 1


def _sub2(selected, t_train, gains, tx_power, alpha0):
    p, wcfg = bandwidth.Sub2Params(), wireless.WirelessConfig()
    return ops.sub2_pgd(selected, t_train, gains, tx_power, alpha0,
                        rho=p.rho, lr=p.pgd_lr, tau=p.smooth_tau,
                        iters=p.pgd_iters, bandwidth_hz=wcfg.bandwidth_hz,
                        noise_psd=wcfg.noise_psd,
                        model_bits=wcfg.model_bits,
                        min_alpha=wcfg.min_alpha, interpret=False)


@pytest.mark.parametrize("scenarios", [0, 8])
def test_sub2_pgd_compiles(one_chip, scenarios):
    """The single instance, and the (S, K) lane the batched driver
    reaches through the entry's vmap rule."""
    lead = (scenarios,) if scenarios else ()
    row = _f32(one_chip, *lead, K)
    fn = jax.vmap(_sub2) if scenarios else _sub2
    assert _compiled_kernels(fn, row, row, row, row,
                             _f32(one_chip, *lead, 2, K)) == 1


@pytest.mark.parametrize("scenarios", [0, 8])
def test_stream_update_compiles(one_chip, scenarios):
    lead = (scenarios,) if scenarios else ()
    fn = functools.partial(ops.stream_update, decay=0.75, size_cap=900.0,
                           interpret=False)
    counts, row = _f32(one_chip, *lead, K, 10), _f32(one_chip, *lead, K)
    assert _compiled_kernels(fn, counts, counts, row, row, row) == 1


def test_paper_cnn_scan_compiles_and_fits(one_chip):
    """The single-scenario scan of the paper deployment: K = 100 devices
    holding up to 900 samples, 6,000 test images, the CNN, DAS."""
    cap, n_test, c = 900, 6000, 10
    spec = paper_nets.PaperNetSpec(kind="cnn")
    wcfg = wireless.WirelessConfig()
    fcfg = federated.FLConfig(num_rounds=3, learning_rate=0.05)
    scfg = scheduler.SchedulerConfig(method="das", n_min=1,
                                     iterations_max=6)
    sim = federated.make_feel_sim(
        loss_fn=functools.partial(paper_nets.loss_fn, spec=spec),
        eval_fn=functools.partial(paper_nets.accuracy, spec=spec),
        wcfg=wcfg, scfg=scfg, fcfg=fcfg, capacity=cap)
    labels = jax.ShapeDtypeStruct((K, cap), jnp.int32)
    mask = jax.ShapeDtypeStruct((K, cap), jnp.float32)

    def hists(lab, m):
        data = partition.ClientDataset(images=None, labels=lab, mask=m,
                                       sizes=None, test_images=None,
                                       test_labels=None)
        return federated.client_histograms(data, c)

    args = (
        jax.eval_shape(lambda: paper_nets.init(jax.random.key(0), spec)),
        jax.ShapeDtypeStruct((K, cap, 28, 28), jnp.uint8), labels, mask,
        jax.ShapeDtypeStruct((K,), jnp.int32),
        jax.eval_shape(hists, labels, mask),
        jax.ShapeDtypeStruct((n_test, 28, 28), jnp.float32),
        jax.ShapeDtypeStruct((n_test,), jnp.int32),
        jax.eval_shape(lambda: wireless.sample_network(jax.random.key(0),
                                                       K, wcfg)),
        jax.eval_shape(lambda: jax.random.key(0)))
    compiled = sim.lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM
