"""``eval_ms`` and the local step's part scopes against the trace
reduction (``feelbench/trace.py``).

A hand-made trace whose op paths are shaped as the program's compiled
ops name them: the local step's ``repro.batch`` and ``repro.grad``
parts nest inside ``repro/local_train`` and, being dotted, leave its
time whole; eval ops sit under ``repro/eval``.  And a small trace
recorded on one TPU v5e with those scopes
(``data/tiny_trace_parts.xplane.pb.gz``: ``record_trace.py``, the
``cnn-das-s8`` path at the tests' tiny size).
"""

import collections
import gzip
import os
import re

import pytest

from feelbench import trace, xplane
from feelbench.metrics import eval_ms, local_train_ms

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_trace.xplane.pb.gz")
RECORDED_PARTS = os.path.join(DATA, "tiny_trace_parts.xplane.pb.gz")
_PART = re.compile(r"repro\.([A-Za-z0-9_]+)")

LT = "jit(sim)/while/body/closed_call/repro/local_train/vmap()/while/body"
# Device: batch gather (1-3), gradient (3-9), eval (10-12).  Host: one
# chunk.enqueue (0-13).
HAND = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 2 offset_ps: 3000000 duration_ps: 6000000 }}
    events {{ metadata_id: 3 offset_ps: 10000000 duration_ps: 2000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = u8[] fusion()"
    stats {{ metadata_id: 10 str_value: "custom fusion" }}
    stats {{ metadata_id: 11 str_value: "{LT}/closed_call/repro.batch/gather" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%convolution.2 = f32[] convolution()"
    stats {{ metadata_id: 10 str_value: "convolution" }}
    stats {{ metadata_id: 11 str_value: "{LT}/closed_call/repro.grad/transpose(jvp())/conv" }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.3 = f32[] fusion()"
    stats {{ metadata_id: 10 str_value: "convolution fusion" }}
    stats {{ metadata_id: 11 str_value: "jit(sim)/while/body/closed_call/repro/eval/cond/branch_1_fun/conv" }} }} }}
  stat_metadata {{ key: 10 value {{ id: 10 name: "hlo_category" }} }}
  stat_metadata {{ key: 11 value {{ id: 11 name: "tf_op" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 1 name: "python3" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 13000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "chunk.enqueue" }} }}
}}
"""


def reduced():
    from jax.profiler import ProfileData
    return trace.reduce_xspace(ProfileData.text_proto_to_serialized_xspace(
        HAND), chips=1)


def test_parts_stay_in_local_train_and_eval_is_its_own_phase():
    r = reduced()
    assert r["phase_s"] == pytest.approx({"local_train": 8e-6,
                                          "eval": 2e-6})
    ctx = {"scenario_rounds": 2}
    assert local_train_ms.reduce(r, ctx) == pytest.approx(4e-3)
    assert eval_ms.reduce(r, ctx) == pytest.approx(1e-3)


def test_eval_ms_reads_nothing_from_a_trace_without_the_scope():
    with gzip.open(RECORDED, "rb") as f:
        r = trace.reduce_xspace(f.read(), chips=1)
    assert eval_ms.reduce(r, {"scenario_rounds": 6}) is None


def part_times(xspace: bytes) -> tuple:
    """Device time of the leaf ops by the innermost ``repro.<part>`` of
    their scope path, inside the harness's window, and each such op's
    phase."""
    from jax.profiler import ProfileData
    plane = "/device:TPU:0"
    table = xplane.event_metadata(xspace, {plane})[plane]
    prof = ProfileData.from_serialized_xspace(xspace)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in prof.planes if p.name != plane for line in p.lines
            for ev in line.events if ev.name in trace.HOST_SPANS]
    lo, hi = min(s for s, _ in host), max(e for _, e in host)
    parts, phases = collections.Counter(), set()
    for p in prof.planes:
        if p.name != plane:
            continue
        for line in p.lines:
            if line.name != trace.OP_LINE:
                continue
            for ev in line.events:
                st = table.get(ev.name, {})
                scope = str(st.get("tf_op", ""))
                found = _PART.findall(scope)
                d = min(ev.start_ns + ev.duration_ns, hi) - max(ev.start_ns,
                                                                 lo)
                if (not found or d <= 0
                        or st.get("hlo_category") in trace.CONTAINERS):
                    continue
                parts[found[-1]] += d * 1e-9
                phases.add(trace.phase_of(scope))
    return parts, phases


def test_recorded_parts_fall_under_local_train():
    with gzip.open(RECORDED_PARTS, "rb") as f:
        xspace = f.read()
    r = trace.reduce_xspace(xspace, chips=1)
    assert set(r["phase_s"]) == {"schedule", "local_train", "aggregate",
                                 "eval"}
    parts, phases = part_times(xspace)
    assert set(parts) == {"batch", "grad"} and phases == {"local_train"}
    lt = r["phase_s"]["local_train"]
    assert 0.9 * lt <= parts["batch"] + parts["grad"] <= lt
    assert parts["grad"] > parts["batch"]
    assert eval_ms.reduce(r, {"scenario_rounds": 6}) > 0
