"""The vision cell's whole run on the CPU at a tiny size (the look for a
chip skipped): a sound stream reads correct, the float8 control and an
altered label read not correct, and the reducers read its records."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import run  # noqa: E402
import vision_stream  # noqa: E402

CELL = "tiny-vit.stream"
GAP_LIMIT = 0.15
MODEL = {"image_size": 32, "patch_size": 8, "num_channels": 3,
         "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_hidden_layers": 2, "num_classes": 32}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    b = root / "bench"
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns("__pycache__"))
    (b / "configs" / "tiny-vit.json").write_text(json.dumps(
        {"name": "tiny-vit", "system": "vision_stream", "model": MODEL,
         "min_n": 32}))
    shutil.copy(b / "configs" / "vit-b16-mxfp4.ref.py",
                b / "configs" / "tiny-vit.ref.py")
    (b / "traffic" / "tiny-stream.json").write_text(json.dumps(
        {"arrivals": "closed", "chunk": 4, "pool": 8}))
    (b / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"config": "tiny-vit", "traffic": "tiny-stream", "chips": 1,
         "check": {"min_frames": 8, "gap_limit": GAP_LIMIT}}))
    doc = harness.benchmark_doc()
    for m in doc["end_to_end"]:
        if m["name"] == "frames_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return b


def one_run(bench, seed, hooks=None):
    args = run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "1.0", "--trace", "0"])
    return run.run(args, bench=bench, gate=False, t_start=time.perf_counter(),
                   hooks=hooks)


def test_sound_stream_is_correct(bench):
    result, checks = one_run(bench, 2**31 + 5)
    assert result["correct"], checks
    assert result["attempted"] >= 8 and result["failed"] == 0
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    assert checks["label_logit_gap"]["value"] < GAP_LIMIT / 3


def test_control_reads_above_the_limit(bench):
    files = harness.cell_files(CELL, bench)
    system = vision_stream.build(files, 3)
    win = vision_stream.window(system, 0.5)
    out = vision_stream.outputs(system, win)
    vision_stream.free(system)
    sound = vision_stream.gaps(files["ref"], 3, MODEL, out["pool"],
                               out["labels"], 4).max()
    ctrl = vision_stream.gaps(files["ref"], 3, MODEL, out["pool"], [], 4,
                              control=True).max()
    assert sound < GAP_LIMIT < ctrl


def label_altered(system):
    """Every label comes back off by one where the engine makes it."""
    eng = system.engine
    step = eng.classify_frame
    eng.classify_frame = lambda image: (step(image) + 1) % 32


def test_an_altered_label_reads_not_correct(bench):
    result, checks = one_run(bench, 11, hooks=label_altered)
    assert not result["correct"], checks


def test_vision_reducers_on_fixed_records():
    m = harness.load_json(BENCH / "configs" / "vit-b16-mxfp4.json")["model"]
    rec = {"t0": 10.0, "chunks": [(10.5, 16), (11.0, 16), (12.0, 16)],
           "config": {"model": m},
           "peaks": harness.peaks("TPU v5 lite")}
    assert harness.reducer("frames_per_s")(rec) == pytest.approx(24.0)
    assert harness.reducer("vit_mfu")(rec) == pytest.approx(
        100 * 35.127656448e9 * 24 / 197e12)
    assert harness.reducer("device_idle.vision")(rec) is None
    assert harness.reducer("mxfp4_matmul_roofline.vision")(rec) is None
