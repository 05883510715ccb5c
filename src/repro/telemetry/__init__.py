"""In-scan telemetry subsystem (DESIGN.md §13).

The paper's claims are argued from *per-round, per-device* quantities —
diversity ranks, admission decisions, Sub2 allocations, the energy
split — but the drivers only surface the nine aggregate
:class:`repro.core.federated.RoundMetrics` leaves; everything else is
computed inside the jit and thrown away.  This package makes those
internals observable without giving up the compiled drivers:

* :class:`TelemetryConfig` rides on ``FLConfig.telemetry``.  When set,
  the scan bodies of both FEEL drivers (synchronous and event-driven)
  and the legacy loop emit a per-round *frame* — a flat dict of stacked
  arrays (``repro.telemetry.record``) holding scheduler score
  decompositions, admission/drop/dispatch outcomes, Sub2 solver traces,
  per-device payload bits and realized upload energy/time, fault events
  by type, and (event mode) availability/staleness state.  Frames ride
  the scan's ``ys`` output, so telemetry costs zero host syncs.
* ``telemetry=None`` (the default) statically dispatches today's
  program **bitwise** — the same ``is_inert``/:func:`active` pattern as
  ``core.faults``: every frame computation sits behind a Python-level
  ``if tel is not None`` so the disabled jaxpr is literally unchanged.
* Host-side durability lives in ``repro.telemetry.sinks`` (fsync-safe
  JSONL round-event writer + the resume-safe rewind shared with the
  sweep runner, and a run manifest), and ``python -m
  repro.telemetry.report`` renders a run summary from a JSONL log.
* :func:`phase_scope` wraps the driver phases (:data:`PHASES`) in
  ``jax.named_scope("repro/<phase>")``.  The scope lands in the op
  metadata of the compiled program, so a profiler trace attributes
  device time to each phase; the chip benchmark reads it from there
  (``feelbench/trace.py``).  Inside ``local_train`` the local step
  splits into ``repro.batch`` (the minibatch draw, gather and
  conversion) and ``repro.grad`` (forward, backward and the update);
  the dot keeps them out of the ``repro/<phase>`` pattern, so a phase
  keeps the time of its parts.
* ``SweepEngine.chunk_outputs`` marks its host work with profiler
  annotations, ``sweep.inputs`` and ``sweep.launch``, which a profile
  shows on its host plane beside the device ops.

Contracts (``tests/test_telemetry.py``): with telemetry enabled the
*primary* outputs (params, metrics) are bitwise identical to the
``telemetry=None`` run across every subsystem composition (frames only
observe — no extra PRNG splits, no op feeding back into the round), and
``batch == S singles`` holds bitwise on every telemetry leaf.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

# The profiled driver phases.  ``stream_refresh`` only
# appears in streaming runs; the others are always present.
PHASES = ("schedule", "local_train", "aggregate", "stream_refresh", "eval")


def phase_scope(name: str):
    """``jax.named_scope("repro/<name>")`` for one driver phase.

    The scope is pure trace-time metadata (it names HLO ops for the
    profiler; no op changes), so the drivers enter it unconditionally —
    the ``telemetry=None`` bitwise contract is unaffected.
    """
    return jax.named_scope(f"repro/{name}")


def part_scope(name: str):
    """``jax.named_scope("repro.<name>")`` for one part of a phase.

    The dot, not a slash, keeps a part out of the ``repro/<phase>``
    pattern the trace reduction attributes device time by, so the
    enclosing phase keeps its time and the part is read on its own.
    """
    return jax.named_scope(f"repro.{name}")


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static telemetry knobs (hashable; rides on ``FLConfig.telemetry``).

    Each flag gates one frame group; the all-``False`` instance is
    *inert* — it records nothing, so :func:`active` normalizes it to
    ``None`` and the drivers compile the identical no-telemetry
    program (the ``core.faults`` disabled-means-identical pattern).
    Admission outcomes (``admitted``/``dispatched``/``delivered``) are
    recorded whenever any group is on — they are the backbone every
    report view joins against.
    """

    scores: bool = True     # per-device scheduler score decomposition
    sub2: bool = True       # Sub2 allocation vector + objective trace
    transport: bool = True  # payload bits, realized upload time/energy
    faults: bool = True     # fault events by type (needs FLConfig.faults)
    events: bool = True     # event-mode availability/staleness state
    signals: bool = True    # per-device learning signals + fairness health


def is_inert(cfg: TelemetryConfig) -> bool:
    """True when the config records nothing at all."""
    return not (cfg.scores or cfg.sub2 or cfg.transport or cfg.faults
                or cfg.events or cfg.signals)


def active(cfg: Optional[TelemetryConfig]) -> Optional[TelemetryConfig]:
    """Normalize an inert config to ``None`` (the no-telemetry path).

    Every driver dispatches through this, so an all-``False``
    :class:`TelemetryConfig` compiles the *same program* as
    ``telemetry=None`` — bitwise, because it is the identical
    computation.
    """
    if cfg is None or is_inert(cfg):
        return None
    return cfg


__all__ = ["TelemetryConfig", "is_inert", "active", "phase_scope",
           "part_scope", "PHASES"]
