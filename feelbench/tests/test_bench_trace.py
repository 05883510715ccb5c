"""The trace reduction (``feelbench/trace.py``) checked as code.

A hand-made trace with known answers, and a small trace recorded on one
TPU v5e chip (``data/tiny_trace.xplane.pb.gz``: one chunk of the
``cnn-das-s8`` path cut to 8 devices, 3 rounds and 2 scenarios, recorded
by ``record_trace.py``).
"""

import gzip
import os

import pytest

from feelbench import trace, xplane

# One chip, one host thread; times in microseconds (ps in the proto).
# Device: a while op (1-11) holding a schedule op (1-4) and a
# local_train op (5-11); an aggregate op (14-15).  Host: chunk.enqueue
# (0-12) then chunk.wait (12-20).
HAND = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 14000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = f32[] while()"
    stats { metadata_id: 10 str_value: "while" }
    stats { metadata_id: 11 str_value: "jit(sim)/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[] fusion()"
    stats { metadata_id: 10 str_value: "loop fusion" }
    stats { metadata_id: 11 str_value: "jit(sim)/while/body/repro/schedule/add:" } } }
  event_metadata { key: 3 value { id: 3 name: "%convolution.3 = f32[] convolution()"
    stats { metadata_id: 10 str_value: "convolution" }
    stats { metadata_id: 11 str_value: "jit(sim)/while/body/repro/local_train/conv:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[] fusion()"
    stats { metadata_id: 10 str_value: "loop fusion" }
    stats { metadata_id: 11 str_value: "jit(sim)/repro/aggregate/dot_general:" } } }
  stat_metadata { key: 10 value { id: 10 name: "hlo_category" } }
  stat_metadata { key: 11 value { id: 11 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chunk.enqueue" } }
  event_metadata { key: 2 value { id: 2 name: "chunk.wait" } }
}
"""

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_trace.xplane.pb.gz")


def hand_trace():
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(HAND)


def test_metadata_reader_finds_scope_and_category():
    meta = xplane.event_metadata(hand_trace(), {"/device:TPU:0"})
    table = meta["/device:TPU:0"]
    assert table["%fusion.2 = f32[] fusion()"] == {
        "hlo_category": "loop fusion",
        "tf_op": "jit(sim)/while/body/repro/schedule/add:"}
    assert set(meta) == {"/device:TPU:0"}


def test_hand_trace_reduces_to_known_times():
    r = trace.reduce_xspace(hand_trace(), chips=1)
    assert r["window_s"] == pytest.approx(20e-6)
    assert r["busy_s"] == pytest.approx(11e-6)        # [1, 11] + [14, 15]
    assert r["idle_share_worst"] == pytest.approx(9 / 20)
    assert r["phase_s"] == pytest.approx(
        {"schedule": 3e-6, "local_train": 6e-6, "aggregate": 1e-6})
    assert r["leaf_s"] == pytest.approx(10e-6)        # the while left out
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [0, 1] under enqueue; [11, 14] mostly under wait; [15, 20] wait.
    assert gaps == pytest.approx({"chunk.enqueue": 1e-6,
                                  "chunk.wait": 8e-6})
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"convolution.3 (convolution)": 6e-6,
                                 "fusion.2 (loop fusion)": 3e-6,
                                 "fusion.4 (loop fusion)": 1e-6})


def test_chips_beyond_the_cell_are_left_out():
    r = trace.reduce_xspace(hand_trace(), chips=0)
    assert r["chips_seen"] == 0 and r["phase_s"] == {}


def test_recorded_chip_trace():
    with gzip.open(RECORDED, "rb") as f:
        r = trace.reduce_xspace(f.read(), chips=1)
    assert r["chips_seen"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert set(r["phase_s"]) == {"schedule", "local_train", "aggregate"}
    assert sum(r["phase_s"].values()) <= r["leaf_s"] * (1 + 1e-9)
    assert len(r["breakdown"]["device_ops"]) == 10
    # The values this reduction gave when the trace was recorded.
    assert r["window_s"] == pytest.approx(0.05833356)
    assert r["busy_s"] == pytest.approx(0.017730795)
    assert r["phase_s"] == pytest.approx({"schedule": 0.000817013,
                                          "local_train": 0.016632046,
                                          "aggregate": 2.8026e-05})
