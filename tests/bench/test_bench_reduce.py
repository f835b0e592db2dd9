"""The benchmark's yardstick on the CPU: trace reduction on a small trace
built here, every metric reducer on fixed records, the work counts
against hand counts, the traffic generator, and discovery of cells,
configurations and metrics by name."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path[:0] = [str(BENCH)]

import devtrace  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
DH = "h2o-danube-1.8b-mxfp4.decode-heavy"

# A one-device trace: one decode program (0-10 us) whose layer scan (a
# while op, 0-9 us) runs two mxfp4_matmul kernels (1-3, 4-5 us) and a
# fusion that consumes a kernel's output (6-8 us: its HLO text names the
# kernel, and it is no kernel event); one prefill program (20-30 us) with
# one kernel (21-29 us). The host wrote a window
# span (0-40 us), two engine steps and one arrival wait (12-18 us).
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 21000000 duration_ps: 8000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%mxfp4_matmul_kernel.62 = bf16[32,6912]{1,0} custom-call(bf16[32,2560]{1,0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = bf16[32,2560]{1,0} fusion(bf16[32,6912]{1,0} %mxfp4_matmul_kernel.62), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%while.50 = (s32[]) while(%t), body=%body" } }
  event_metadata { key: 6 value { id: 6 name: "%mxfp4_matmul_kernel.63 = bf16[32,2560]{1,0} custom-call(%y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode(11)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_prefill(12)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10500000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 19000000 duration_ps: 11000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "arrival_wait" } }
}
"""


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData

    return devtrace.reduce_profile(ProfileData.from_text_proto(XSPACE))


def test_trace_reduction_reads_ops_programs_and_spans(tr):
    assert tr.n_devices == 1
    assert tr.window == (0, 40_000)
    assert [o.name for o in tr.ops] == [
        "while.50", "mxfp4_matmul_kernel.62", "mxfp4_matmul_kernel.63",
        "fusion.7", "mxfp4_matmul_kernel.62"]
    assert [o.module.split("(")[0] for o in tr.ops] == [
        "jit_decode"] * 4 + ["jit_prefill"]
    assert devtrace.kernel_ns(tr, "mxfp4_matmul", "decode") == (3_000, 2)
    assert devtrace.kernel_ns(tr, "mxfp4_matmul", "prefill") == (8_000, 1)
    assert devtrace.kernel_ns(tr, "mxfp4_matmul") == (11_000, 3)
    assert tr.busy_by_device == [17_000]


def test_idle_gaps_and_their_owners(tr):
    gaps = devtrace.idle_gaps(tr, 0, 40_000)
    assert gaps == [(9_000, 21_000), (29_000, 40_000)]
    assert devtrace.gap_owner(tr, 9_000, 21_000) == "arrival_wait"
    assert devtrace.gap_owner(tr, 29_000, 40_000) == "engine.step"
    bd = devtrace.breakdown(tr)
    # kernels of one kind together; the while op holds them, and is left out
    assert [k for k, _ in bd["device_ops"]] == ["mxfp4_matmul_kernel",
                                                "fusion"]
    assert dict(bd["device_ops"]) == pytest.approx(
        {"mxfp4_matmul_kernel": 11e-6, "fusion": 2e-6})
    assert dict(bd["idle_gaps"]) == pytest.approx(
        {"arrival_wait": 12e-6, "engine.step": 11e-6})


def test_union_clips_and_merges():
    iv = [(0, 10), (5, 15), (20, 30)]
    assert devtrace.union_ns(iv) == 25
    assert devtrace.union_ns(iv, 8, 25) == 12
    spans = devtrace.Spans([(10, 20), (0, 5)])
    assert devtrace.overlap_ns(spans, 3, 12) == 4


def lm_record(trace=None):
    """Two requests in a 2 s window at t0 = 100 s."""
    reqs = [
        {"due": 100.0, "admitted": 100.1, "n_prompt": 4, "done": True,
         "times": [100.2, 100.3, 100.5]},
        {"due": 101.0, "admitted": 101.4, "n_prompt": 8, "done": True,
         "times": [101.5, 101.6, 102.1, 102.2]},
    ]
    steps = [("prefill", 256, 1, 100.15, 100.2),
             ("decode", 32, 1, 100.25, 100.3),
             ("prefill", 256, 1, 101.45, 101.5),
             ("decode", 32, 2, 101.55, 101.6)]
    return {"requests": reqs, "steps": steps, "t0": 100.0, "end": 102.0,
            "t_last": 102.2, "seconds": 2.0, "setup_s": 42.5,
            "peaks": PEAKS, "trace": trace,
            "config": harness.load_json(
                BENCH / "configs" / "h2o-danube-1.8b-mxfp4.json"),
            "cell": harness.load_json(BENCH / "cells" / f"{DH}.json")}


def test_end_to_end_reducers_on_fixed_records():
    rec = lm_record()
    red = {n: harness.reducer(n) for n in (
        "ttft_p95_ms.prompt", "itl_p99_ms", "output_tokens_per_s",
        "setup_s")}
    assert red["setup_s"](rec) == 42.5
    # TTFT from the due time: 0.2 s and 0.5 s; p95 of two is the larger
    assert red["ttft_p95_ms.prompt"](rec) == pytest.approx(500.0)
    # gaps 0.1, 0.2 | 0.1, 0.5, 0.1: nearest-rank p99 of five is the max
    assert red["itl_p99_ms"](rec) == pytest.approx(500.0)
    # tokens stamped at or before the window's end (102.0): 3 + 2
    assert red["output_tokens_per_s"](rec) == pytest.approx(2.5)


def test_unanswered_request_counts_its_whole_wait():
    rec = lm_record()
    rec["requests"].append({"due": 101.9, "times": [], "done": False,
                            "n_prompt": 4, "admitted": None})
    # 3 requests: 0.2, 0.5 and 102.2 - 101.9 = 0.3; nearest-rank p95 is 0.5
    assert harness.reducer("ttft_p95_ms.prompt")(rec) == pytest.approx(500.0)
    rec["t_last"] = 103.0
    assert harness.reducer("ttft_p95_ms.prompt")(rec) == pytest.approx(1100.0)


def test_program_span_reducers_on_fixed_records():
    rec = lm_record()
    assert harness.reducer("queue_wait_p95_ms")(rec) == pytest.approx(400.0)
    assert harness.reducer("prefill_step_ms")(rec) == pytest.approx(50.0)
    assert harness.reducer("decode_step_ms")(rec) == pytest.approx(50.0)
    lm = harness.work("decoder_lm")
    m = rec["config"]["model"]
    want = (lm.prompt_flops(m, 4) + lm.prompt_flops(m, 8)) / 0.1 / 197e12
    assert harness.reducer("prefill_mfu")(rec) == pytest.approx(100 * want)
    dec = (sum(lm.token_flops(m, 4 + j) for j in (1, 2))
           + sum(lm.token_flops(m, 8 + j) for j in (1, 2, 3)))
    assert harness.reducer("decode_mfu")(rec) == pytest.approx(
        100 * dec / 0.1 / 197e12)


def test_trace_reducers_on_fixed_records(tr):
    rec = lm_record(tr)
    mm = harness.work("mxfp4_matmul")
    lm = harness.work("decoder_lm")
    m = rec["config"]["model"]
    calls = lm.matmul_calls(m, 32, 32)
    per = mm.min_seconds(calls, 197e12, 819e9) / len(calls)
    assert harness.reducer("mxfp4_matmul_roofline.decode")(rec) == \
        pytest.approx(100 * per * 2 / 3e-6)
    calls = lm.matmul_calls(m, 256, 16)
    per = mm.min_seconds(calls, 197e12, 819e9) / len(calls)
    assert harness.reducer("mxfp4_matmul_roofline.prefill")(rec) == \
        pytest.approx(100 * per / 8e-6)
    # idle 23 us of 40, of which the 6 us arrival wait is not the engine's
    assert harness.reducer("device_idle.lm")(rec) == pytest.approx(
        100 * 17 / 34)


def test_reducers_without_their_source_return_nothing():
    rec = lm_record()
    del rec["peaks"]
    for name in ("prefill_mfu", "decode_mfu", "mxfp4_matmul_roofline.decode",
                 "mxfp4_matmul_roofline.prefill", "device_idle.lm"):
        assert harness.reducer(name)(rec) is None, name


def test_every_benchmark_metric_has_a_reducer():
    doc = harness.benchmark_doc()
    for kind in ("end_to_end", "per_layer"):
        for m in doc[kind]:
            assert callable(harness.reducer(m["name"])), m["name"]


def test_work_counts_match_hand_counts():
    mm = harness.work("mxfp4_matmul")
    # M=32, K=2560, N=6912: 2*M*K*N FLOPs; K*N/2 code bytes, K/32*N
    # exponent bytes, bf16 activations in and out
    f, b = mm.work(32, 2560, 6912)
    assert f == 2 * 32 * 2560 * 6912
    assert b == 2560 * 6912 / 2 + 80 * 6912 + 2 * 32 * 2560 + 2 * 32 * 6912
    assert [mm.rows(m) for m in (1, 16, 32, 197, 256)] == [16, 16, 32, 256, 256]
    lm = harness.work("decoder_lm")
    m = harness.load_json(
        BENCH / "configs" / "h2o-danube-1.8b-mxfp4.json")["model"]
    # per layer: q,o 2560x2560; k,v 2560x640; three 2560x6912; plus head
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert lm.linear_params(m) == 24 * per_layer + 2560 * 32000
    assert lm.token_flops(m, 100) == 2 * lm.linear_params(m) \
        + 4 * 24 * 2560 * 100
    # the window caps the keys a token attends to
    assert lm.token_flops(m, 10_000) == lm.token_flops(m, 4096)
    assert lm.prompt_flops(m, 3) == 3 * 2 * 24 * per_layer \
        + 4 * 24 * 2560 * (1 + 2 + 3) + 2 * 2560 * 32000
    assert len(lm.matmul_calls(m, 32, 32)) == 24 * 7 + 1


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite") == PEAKS
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def test_generator_gives_every_seed_the_same_work():
    mix = harness.load_json(BENCH / "traffic" / "decode-heavy.json")
    a = gen.requests(mix, 4.0, 30.0, 7, 32000)
    b = gen.requests(mix, 4.0, 30.0, 2**31 + 12345, 32000)
    assert len(a) == len(b) == 120
    # the same sizes and arrivals in the same order; the seed draws tokens
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert min(len(r.prompt) for r in a) >= 32
    assert max(len(r.prompt) for r in a) <= 256
    assert all(128 <= r.max_new <= 512 for r in a)
    # exponential gaps at the rate: their mean is close to 1 / rate
    assert np.mean(np.diff([0.0] + [r.due for r in a])) == \
        pytest.approx(0.25, rel=0.05)
    # balanced in time: each 4 consecutive requests hold one output length
    # from each quarter of the sorted lengths
    q = np.searchsorted(sorted(r.max_new for r in a),
                        [r.max_new for r in a], side="right") - 1
    assert all(sorted(q[i:i + 4] * 4 // 120) == [0, 1, 2, 3]
               for i in range(0, 120, 4))
    again = gen.requests(mix, 4.0, 30.0, 7, 32000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, again))


def test_generator_bursts_and_shared_prefixes():
    mix = {"arrivals": "burst", "burst": 4,
           "prompt_len": {"dist": "fixed", "value": 10},
           "output_len": {"dist": "uniform", "lo": 2, "hi": 6},
           "shared_prefix": {"n_system": 1, "len": 16, "p": 1.0}}
    rs = gen.requests(mix, 8.0, 2.0, 3, 100)
    dues = [r.due for r in rs]
    assert len(rs) == 16 and len(set(dues)) == 4
    assert all(len(r.prompt) == 26 for r in rs)
    assert all(np.array_equal(r.prompt[:16], rs[0].prompt[:16]) for r in rs)


def test_an_added_cell_file_is_found_without_code(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cell = harness.load_json(bench / "cells" / f"{DH}.json")
    cell.update(traffic="burst-chat", rate_rps=3.0)
    (bench / "cells" / "h2o-danube-1.8b-mxfp4.burst-chat.json").write_text(
        json.dumps(cell))
    mix = harness.load_json(bench / "traffic" / "decode-heavy.json")
    mix.update(arrivals="burst", burst=16)
    (bench / "traffic" / "burst-chat.json").write_text(json.dumps(mix))
    files = harness.cell_files("h2o-danube-1.8b-mxfp4.burst-chat", bench)
    assert files["traffic"]["burst"] == 16
    assert files["config"]["name"] == "h2o-danube-1.8b-mxfp4"
    assert callable(files["ref"].logits_at)
    rs = gen.requests(files["traffic"], cell["rate_rps"], 16.0, 1, 32000)
    assert len({r.due for r in rs}) == 3
    doc = harness.benchmark_doc()
    doc["per_layer"][0]["workloads"].append("h2o-danube-1.8b-mxfp4.burst-chat")
    names = [m["name"] for m in harness.metrics_for(
        doc, "h2o-danube-1.8b-mxfp4.burst-chat", "per_layer")]
    assert names == [doc["per_layer"][0]["name"]]


def test_benchmark_cells_and_configs_have_their_files():
    doc = harness.benchmark_doc()
    for w in doc["workloads"]:
        files = harness.cell_files(w["name"])
        assert files["cell"]["config"] == w["config"]
        assert files["cell"]["traffic"] == w["traffic"]
        assert files["cell"]["chips"] == w["chips"]
    for c in doc["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
        assert (BENCH / "configs" / f"{c['name']}.ref.py").is_file()
