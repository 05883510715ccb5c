"""Model families: everything the harness knows of a model, one module each.

A configuration names its family by its ``"model"`` key, and
:func:`load` imports ``feelbench/models/<model>.py``.  Adding a family
takes that file and nothing else in the harness.  A family module gives:

- data: ``data(seed, cfg)``, the host arrays of the program's
  ``ClientDataset`` (``images``, ``labels``, ``mask``, ``sizes``,
  ``test_images``, ``test_labels``) from one seed; ``classes(cfg)``, the
  class count of the labels, which the program's ``FLConfig`` and the
  reference's class histograms use;
- weights: ``init(key, cfg)``, the initial parameters that the devices
  train and FedAvg averages, on the device;
- engine arguments: ``engine_args(cfg)``, the ``SweepEngine`` keyword
  arguments the model owns (``loss_fn``, ``eval_fn``, and any operand
  the program takes beside ``init_params``), handed on unread;
- reference maths, which import nothing of the program:
  ``inputs(rows, dt)``, rows of ``images`` or ``test_images`` as the
  reference's inputs in dtype ``dt``; ``loss(params, x, labels, mask,
  cfg)``, the mean loss over a padded batch's valid rows;
  ``accuracy(params, x, labels, cfg)``; ``reference_block(cfg)``, how
  many devices the reference trains at once;
- work counts: ``forward_flops(cfg)`` and ``train_flops(cfg)``, the
  FLOPs one sample takes through the forward pass and through a
  training step; ``uploaded_params(cfg)``, the parameters a device
  uploads and FedAvg averages;
- CPU cut: ``cut_for_cpu(cfg)``, shrinks in place the model's own
  scale keys (its data) for the tests' tiny cells.

Modules whose names start with ``_`` hold shared code and are no family.
"""

from __future__ import annotations

import importlib


def load(cfg: dict):
    """The family module of the configuration's ``"model"``."""
    return importlib.import_module(f"{__name__}.{cfg['model']}")
