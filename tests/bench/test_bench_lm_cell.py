"""The LM cell's whole run on the CPU at a tiny size (the look for a chip
skipped): a sound run reads correct; the float8 control and each fault
planted in the timed path read not correct; the command refuses a CPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import lm_serve  # noqa: E402
import run  # noqa: E402

CELL = "tiny-lm.tiny"
# a sound run at this size reads up to ~0.03; the float8 control ~0.35+
GAP_LIMIT = 0.15
MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
         "vocab_size": 256, "sliding_window": 4096, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-6, "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A copy of bench/ with a tiny decoder cell: the danube reference's
    architecture at width 64, 2 layers, 4 lanes."""
    root = tmp_path_factory.mktemp("checkout")
    b = root / "bench"
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns("__pycache__"))
    (b / "configs" / "tiny-lm.json").write_text(json.dumps(
        {"name": "tiny-lm", "system": "lm_paged", "model": MODEL,
         "min_n": 32}))
    shutil.copy(b / "configs" / "h2o-danube-1.8b-mxfp4.ref.py",
                b / "configs" / "tiny-lm.ref.py")
    (b / "traffic" / "tiny.json").write_text(json.dumps(
        {"arrivals": "poisson",
         "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 24},
         "output_len": {"dist": "loguniform", "lo": 4, "hi": 16}}))
    (b / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"config": "tiny-lm", "traffic": "tiny", "chips": 1, "rate_rps": 40.0,
         "engine": {"lanes": 4, "num_slots": 6, "page_len": 64,
                    "prefill_len": 32},
         "check": {"requests": 12, "min_tokens": 10, "gap_limit": GAP_LIMIT}}))
    doc = harness.benchmark_doc()
    for kind in ("end_to_end", "per_layer"):
        for m in doc[kind]:
            if "workloads" in m:
                m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return b


def one_run(bench, seed, hooks=None):
    args = run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "1.5", "--trace", "0"])
    return run.run(args, bench=bench, gate=False, t_start=time.perf_counter(),
                   hooks=hooks)


def test_sound_run_is_correct_and_reports_its_metrics(bench):
    result, checks = one_run(bench, 2**31 + 77)
    assert result["correct"], checks
    assert result["attempted"] == 60 and result["failed"] == 0
    assert set(result["metrics"]) == {"itl_p99_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}
    assert checks["served_logit_gap"]["value"] < GAP_LIMIT / 3
    assert list(checks) == ["served_logit_gap", "served_tokens_compared",
                            "requests_unfinished"]


def test_control_reads_above_the_limit(bench):
    files = harness.cell_files(CELL, bench)
    system = lm_serve.build(files, 5)
    win = lm_serve.serve_window(system.engine,
                                lm_serve.schedule(files, 5, 1.5), 1.5)
    served = lm_serve.sample(5, win["requests"], system.engine, 4)
    lm_serve.free(system)
    sound = lm_serve.gaps(files["ref"], 5, MODEL, served).max()
    ctrl = lm_serve.gaps(files["ref"], 5, MODEL, served, control=True).max()
    assert sound < GAP_LIMIT < ctrl


def token_altered(system):
    """Every decoded token is off by one where the step produces it."""
    import jax.numpy as jnp

    eng = system.engine
    dec = eng._decode
    vocab = eng.cfg.vocab_size

    def bad(params, pool, rows, ids, pos):
        nxt, pool = dec(params, pool, rows, ids, pos)
        return (nxt + 1) % vocab, pool

    eng._decode = bad


def state_unchanged(system):
    """The decode step returns the KV pool it was given."""
    import jax
    import jax.numpy as jnp

    eng = system.engine

    @jax.jit
    def bad(params, pool, rows, ids, pos):
        logits, _ = eng.decode_logits(params, pool, rows, ids, pos)
        return jnp.argmax(logits.astype(jnp.float32), -1), pool

    eng._decode = bad


def half_lanes_left_out(system):
    """The upper half of the decode lanes is not computed: those lanes
    hand back the token they were fed."""
    import jax.numpy as jnp

    eng = system.engine
    dec = eng._decode
    half = eng.ecfg.lanes // 2

    def bad(params, pool, rows, ids, pos):
        nxt, pool = dec(params, pool, rows, ids, pos)
        keep = jnp.arange(nxt.shape[0]) < half
        return jnp.where(keep, nxt, ids[:, 0]), pool

    eng._decode = bad


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_lanes_left_out],
                         ids=lambda f: f.__name__)
def test_a_broken_timed_path_reads_not_correct(bench, fault):
    result, checks = one_run(bench, 9, hooks=fault)
    assert not result["correct"], checks
    assert not checks["served_logit_gap"]["ok"]


def test_command_refuses_a_cpu_and_prints_no_result(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark's
    paths, on a machine without a TPU, the run exits non-zero."""
    doc = harness.benchmark_doc()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in doc["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        doc["command"] + ["--workload", doc["workloads"][0]["name"],
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
