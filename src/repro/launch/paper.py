"""The paper's §VI evaluation setup (Table I), built in one place.

Synthetic MNIST-shaped data from a seed, shard-partitioned over K
devices; the paper's MLP or CNN; Table-I wireless constants; the
scheduler and FedAvg configurations the evaluation uses.  The example
driver (``examples/federated_mnist.py``) and the chip smoke test
(``chip_smoke.py``) both build their deployments here.

``full_data=True`` is the paper's scale: 1,200 shards of 50 samples
(6,000 per class), so at K = 100 each device holds 50-900 samples.
The default is the quick 300 x 50 split.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax

from repro.core import federated, scheduler, wireless
from repro.data import partition, synthetic
from repro.models import paper_nets


@dataclasses.dataclass(frozen=True)
class PaperSetup:
    """Everything one FEEL run of the paper's evaluation needs."""

    data: partition.ClientDataset
    spec: paper_nets.PaperNetSpec
    params: Any
    loss_fn: Callable
    eval_fn: Callable
    wcfg: wireless.WirelessConfig
    scfg: scheduler.SchedulerConfig
    fcfg: federated.FLConfig
    net: wireless.NetworkState     # the single-scenario network draw
    key: jax.Array                 # the single-scenario PRNG stream


def paper_setup(*, model: str = "mlp", method: str = "das",
                rounds: int = 15, devices: int = 40, n_fixed: int = 0,
                epochs: int = 1, model_bits: float = 100e3,
                full_data: bool = False, seed: int = 0,
                staleness_weight: float = 0.0, **fl_fields) -> PaperSetup:
    """Build the §VI setup.

    ``fl_fields`` pass through to :class:`federated.FLConfig`
    (``stream``, ``compression``, ``dispatch_cap``, ``carry_dtype``).
    """
    shards = 1200 if full_data else 300
    spc = 6000 if full_data else 2000
    imgs, labels = synthetic.generate(seed, samples_per_class=spc)
    data = partition.partition(
        imgs, labels, seed=seed + 1,
        spec=partition.PartitionSpec(num_devices=devices,
                                     num_shards=shards, shard_size=50))
    wcfg = wireless.WirelessConfig(model_bits=model_bits)
    spec = paper_nets.PaperNetSpec(kind=model)
    scfg = scheduler.SchedulerConfig(
        method=method, n_min=1, n_fixed=n_fixed or None, iterations_max=6,
        staleness_weight=staleness_weight)
    fcfg = federated.FLConfig(
        num_rounds=rounds, local_epochs=epochs, batch_size=50,
        learning_rate=0.1 if model == "mlp" else 0.05, **fl_fields)
    return PaperSetup(
        data=data, spec=spec,
        params=paper_nets.init(jax.random.key(seed + 3), spec),
        loss_fn=functools.partial(paper_nets.loss_fn, spec=spec),
        eval_fn=functools.partial(paper_nets.accuracy, spec=spec),
        wcfg=wcfg, scfg=scfg, fcfg=fcfg,
        net=wireless.sample_network(jax.random.key(seed + 2), devices,
                                    wcfg),
        key=jax.random.key(seed + 4))
