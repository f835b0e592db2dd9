"""The program spans in a traced run (``bench/spans.py``): read from a
small trace built here, idle gaps split among the innermost program
spans inside the harness's step span, and the host-time reducers on
fixed records."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path[:0] = [str(BENCH)]

import devtrace  # noqa: E402
import harness  # noqa: E402
import spans  # noqa: E402

# One device runs ops at 0-8 us and 20-28 us. The host wrote a window
# (0-40 us), the harness's step spans (0-12, 19.5-40) and an arrival wait
# (13-19.5). Inside them the program marked a decode step (0.5-11.5 us:
# sync 1-9, emit 9-11.5) and a chunk step (20-39 us: sync 20.5-28.5, emit
# 28.5-39), so the gap at 28-40 us lies mostly in the chunk's emit, a
# little in its sync, and its last microsecond between the program's
# spans.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(%x)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 11000000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 6 offset_ps: 9000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 6500000 }
    events { metadata_id: 2 offset_ps: 19500000 duration_ps: 20500000 }
    events { metadata_id: 7 offset_ps: 20000000 duration_ps: 19000000 }
    events { metadata_id: 5 offset_ps: 20500000 duration_ps: 8000000 }
    events { metadata_id: 6 offset_ps: 28500000 duration_ps: 10500000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "arrival_wait" } }
  event_metadata { key: 4 value { id: 4 name: "engine.decode" } }
  event_metadata { key: 5 value { id: 5 name: "engine.sync" } }
  event_metadata { key: 6 value { id: 6 name: "engine.emit" } }
  event_metadata { key: 7 value { id: 7 name: "engine.chunk" } }
}
"""


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(XSPACE)


@pytest.fixture(scope="module")
def tr(pd):
    return devtrace.reduce_profile(pd)


def test_read_keeps_only_program_spans(pd):
    sp = spans.read(pd)
    assert set(sp) == {"engine.decode", "engine.chunk", "engine.sync",
                       "engine.emit"}
    assert sp["engine.sync"] == [(1_000, 9_000), (20_500, 28_500)]


def test_gaps_named_by_innermost_program_span(pd, tr):
    sp = spans.read(pd)
    # the arrival wait holds most of the first gap: the harness names all
    assert spans.split_gap(tr, sp, 8_000, 20_000) == {"arrival_wait": 12_000}
    # inside the harness's step span, each phase its overlap, and the
    # rest outside the program's spans keeps the harness's name
    assert spans.split_gap(tr, sp, 28_000, 40_000) == {
        "engine.sync": 500, "engine.emit": 10_500, "engine.step": 1_000}
    assert spans.split_gap(tr, sp, 11_600, 11_900) == {"engine.step": 300}
    # a step's own time outside its phases goes to the step
    assert spans.split_gap(tr, sp, 0, 1_000) == {
        "engine.decode": 500, "engine.step": 500}
    split = spans.idle_split(tr, sp)
    assert dict(split["idle_gaps"]) == pytest.approx(
        {"arrival_wait": 12e-6, "engine.emit": 10.5e-6,
         "engine.sync": 0.5e-6, "engine.step": 1e-6})
    # the total stays what the harness's breakdown gives
    assert sum(v for _, v in split["idle_gaps"]) == pytest.approx(
        sum(v for _, v in devtrace.breakdown(tr)["idle_gaps"]))
    assert split["named_share"] == pytest.approx(11 / 12)
    slow = spans.slowest(tr, sp)
    assert slow["engine.chunk"] == pytest.approx(
        {"ms": 0.019, "engine.sync": 0.008, "engine.emit": 0.0105})
    assert slow["engine.decode"]["ms"] == pytest.approx(0.011)


def test_host_time_reducers_on_fixed_records(pd, tr):
    rec = {"trace": tr, "program_spans": spans.read(pd)}
    # each step less the sync inside it: 11 - 8 us and 19 - 8 us
    assert harness.reducer("decode_host_ms")(rec) == pytest.approx(0.003)
    assert harness.reducer("chunk_host_ms")(rec) == pytest.approx(0.011)
    # no frames in this trace
    assert harness.reducer("frame_host_ms")(rec) is None


def test_host_time_reducers_without_their_source_return_nothing(tr):
    # untraced: no profile to read, and it is not looked for
    rec = {"trace": None}
    for name in ("decode_host_ms", "chunk_host_ms", "frame_host_ms"):
        assert harness.reducer(name)(rec) is None, name
    assert rec["program_spans"] is None
    # a program that marks no spans leaves nothing to read
    rec = {"trace": tr, "program_spans": {}}
    for name in ("decode_host_ms", "chunk_host_ms", "frame_host_ms"):
        assert harness.reducer(name)(rec) is None, name
