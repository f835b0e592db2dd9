"""Serving-engine invariants.

The two crux checks:

- *Isolation*: continuous batching with staggered arrivals produces the
  same per-request completions as running each request alone — both
  against a second engine (same compiled steps => bit-identical lanes)
  and against greedy full-sequence ``lm.forward`` (same backend).
- *Pipeline fidelity*: the discrete-event FWS pipeline model's
  steady-state FPS reproduces the Table-7 figures for the paper's
  encoder shapes within 5%.

Plus the satellite decode-path guarantee: ``lm.decode_step`` over the
paged cache matches full-sequence ``lm.forward`` logits token-for-token
under the mxfp4 and cim backends.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core import cim as cimlib
from repro.hwmodel import perf, specs as S
from repro.layers import attention as attn_mod
from repro.layers.common import RunCtx, ShardingCtx, convert_params_mxfp4
from repro.models import calibrate, lm
from repro.serving import Engine, EngineConfig
from repro.serving import pipeline as pipe
from repro.serving.kvcache import (
    PagedKVCache,
    PoolExhausted,
    SlotAllocator,
    gather_rows,
    scatter_rows,
)
from repro.serving.scheduler import Request, Scheduler, static_batching_plan

CFG = C.tiny(C.ARCHS["starcoder2-7b"])  # full attention, dense


@pytest.fixture(scope="module")
def float_model():
    params, _ = lm.init_model(jax.random.PRNGKey(0), CFG)
    return params, RunCtx(shd=ShardingCtx(), dense_attn_max=256)


@pytest.fixture(scope="module")
def mxfp4_model(float_model):
    params, ctx = float_model
    return (
        convert_params_mxfp4(params),
        dataclasses.replace(ctx, quant="mxfp4_wonly"),
    )


@pytest.fixture(scope="module")
def cim_model(float_model):
    params, ctx = float_model
    cim_cfg = cimlib.CIMConfig()
    batches = calibrate.calibration_batches(CFG, n_batches=2, batch=2, seq=16)
    conv, _ = calibrate.convert_model_cim(
        params, CFG, ctx, batches, cim_cfg=cim_cfg, min_n=32
    )
    return conv, dataclasses.replace(ctx, quant="cim", cim=cim_cfg)


# ------------------------------------------------------------ unit pieces

def test_slot_allocator():
    a = SlotAllocator(3)
    got = [a.alloc() for _ in range(3)]
    assert sorted(got) == [0, 1, 2]
    # satellite regression: alloc() used to return None on exhaustion,
    # which flowed straight into the jitted step as a row index
    assert a.try_alloc() is None and a.num_free == 0
    with pytest.raises(PoolExhausted):
        a.alloc()
    a.free(got[1])
    assert a.num_free == 1 and a.alloc() == got[1]
    with pytest.raises(ValueError):
        a.free(99)


def test_slot_allocator_refcounts():
    a = SlotAllocator(2)
    s = a.alloc()
    assert a.refcount(s) == 1
    a.retain(s)
    assert a.refcount(s) == 2
    a.release(s)  # one owner left -> still allocated
    assert a.refcount(s) == 1 and s in a.in_use and a.num_free == 1
    a.release(s)  # last owner -> back on the free list
    assert a.refcount(s) == 0 and a.num_free == 2
    with pytest.raises(ValueError):
        a.release(s)  # double-free
    with pytest.raises(ValueError):
        a.retain(s)  # retain of a free slot


def test_paged_pool_gather_scatter_roundtrip():
    kv = PagedKVCache(CFG, num_slots=3, lanes=2, page_len=8)
    key = jax.random.PRNGKey(0)
    pool = []
    for seg in kv.pool:
        seg2 = {}
        for k, v in seg.items():
            key, sub = jax.random.split(key)
            seg2[k] = jax.random.normal(sub, v.shape, jnp.float32).astype(
                v.dtype
            )
        pool.append(seg2)
    rows = jnp.asarray([2, 0], jnp.int32)
    got = gather_rows(pool, kv.specs, rows)
    back = scatter_rows(pool, kv.specs, rows, got)
    for seg_a, seg_b in zip(pool, back):
        for k in seg_a:
            np.testing.assert_array_equal(np.asarray(seg_a[k]),
                                          np.asarray(seg_b[k]))
    # a scatter of fresh values lands on exactly the addressed rows
    fresh = jax.tree.map(lambda x: jnp.ones_like(x), got)
    out = scatter_rows(pool, kv.specs, rows, fresh)
    for seg_o, seg_p, spec in zip(out, pool, kv.specs):
        for k in seg_o:
            ax = spec[k].index("batch")
            o = np.moveaxis(np.asarray(seg_o[k]), ax, 0)
            p = np.moveaxis(np.asarray(seg_p[k]), ax, 0)
            assert (o[np.asarray(rows)] == 1).all()
            keep = [i for i in range(o.shape[0]) if i not in (0, 2)]
            np.testing.assert_array_equal(o[keep], p[keep])


def test_scatter_rows_rejects_lossy_dtype():
    """Regression: scatter used to silently ``.astype`` values into the
    pool dtype — f32 pages written into a bf16 pool lost mantissa bits
    with no signal. Lossy writes now raise; widening writes still pass."""
    kv = PagedKVCache(CFG, num_slots=2, lanes=1, page_len=8)
    rows = jnp.asarray([0], jnp.int32)
    good = gather_rows(kv.pool, kv.specs, rows)
    bad = jax.tree.map(lambda x: x.astype(jnp.float32)
                       if x.dtype == jnp.bfloat16 else x, good)
    with pytest.raises(TypeError, match="lossy"):
        scatter_rows(kv.pool, kv.specs, rows, bad)
    # same-dtype and widening (f16 -> f32 would promote) writes still work
    scatter_rows(kv.pool, kv.specs, rows, good)


def test_paged_pool_rejects_recurrent_and_narrow_window():
    with pytest.raises(NotImplementedError, match="attention-only"):
        PagedKVCache(C.tiny(C.ARCHS["zamba2-1.2b"]), 2, 2, 8)
    with pytest.raises(NotImplementedError, match="full pages"):
        PagedKVCache(C.tiny(C.ARCHS["h2o-danube-1.8b"]), 2, 2, 32)


def test_decode_vector_pos_matches_scalar(float_model):
    """Per-lane positions (all equal) are bitwise the scalar-pos decode."""
    params, ctx = float_model
    b, p = 2, 6
    prompt = jax.random.randint(jax.random.PRNGKey(1), (b, p), 0,
                                CFG.vocab_size)
    caches = lm.init_cache(CFG, b, 16)
    _, caches = lm.forward(params, CFG, ctx, {"ids": prompt}, caches=caches)
    ids = prompt[:, -1:]
    lg_s, _ = lm.decode_step(params, CFG, ctx, ids, jnp.int32(p), caches)
    lg_v, _ = lm.decode_step(
        params, CFG, ctx, ids, jnp.full((b,), p, jnp.int32), caches
    )
    np.testing.assert_array_equal(np.asarray(lg_s, np.float32),
                                  np.asarray(lg_v, np.float32))


def test_kv_pad_positions_never_attended(float_model):
    """Right-padded prefill with KV_PAD positions == unpadded prefill."""
    params, ctx = float_model
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, CFG.vocab_size)
    lg_ref, _ = lm.forward(params, CFG, ctx, {"ids": ids})
    pad_ids = jnp.pad(ids, ((0, 0), (0, 3)))
    positions = jnp.concatenate(
        [jnp.arange(5)[None], jnp.full((1, 3), attn_mod.KV_PAD)], axis=1
    )
    lg_pad, _ = lm.forward(
        params, CFG, ctx, {"ids": pad_ids, "positions": positions}
    )
    np.testing.assert_allclose(
        np.asarray(lg_pad[:, :5], np.float32),
        np.asarray(lg_ref, np.float32), rtol=0, atol=0,
    )


# -------------------------------------------------------------- scheduler

def _req(rid, n=4, max_new=3, **kw):
    return Request(rid=rid, prompt=list(range(1, n + 1)), max_new=max_new,
                   **kw)


def test_scheduler_policies_and_eviction():
    s = Scheduler(lanes=2, policy="prefill")
    assert s.plan(free_slots=3) == "idle"
    s.add(_req(0))
    s.add(_req(1))
    s.add(_req(2))
    assert s.plan(3) == "prefill"
    r0 = s.admit(slot=0, step=1)
    assert (r0.rid, r0.pos) == (0, 4) and s.num_active == 1
    assert s.plan(2) == "prefill"  # prefill-prioritized: fill the batch
    r1 = s.admit(slot=1, step=2)
    assert s.plan(1) == "decode"  # lanes full -> decode
    assert s.plan(0) == "decode"
    s.finish(r0, step=5)
    assert r0.done and s.num_active == 1
    assert s.plan(1) == "prefill"  # freed lane backfills immediately

    d = Scheduler(lanes=2, policy="decode")
    d.add(_req(0))
    d.add(_req(1))
    assert d.plan(2) == "prefill"  # nothing running yet
    d.admit(slot=0, step=1)
    assert d.plan(1) == "decode"  # decode-prioritized: never stall decodes
    with pytest.raises(ValueError):
        Scheduler(2, policy="fifo")


def test_stop_conditions():
    r = _req(0, n=4, max_new=2)
    r.pos = 4
    r.out = [7]
    assert not Scheduler.stopped(r, page_len=16)
    r.out = [7, 7]
    assert Scheduler.stopped(r, page_len=16)
    r2 = _req(1, n=4, max_new=8, stop_token=5)
    r2.out = [3, 5]
    assert Scheduler.stopped(r2, page_len=16)
    r3 = _req(2, n=4, max_new=100)
    r3.out = [1]
    r3.pos = 16
    assert Scheduler.stopped(r3, page_len=16)  # page exhausted


# ------------------------------------------------- continuous batching ==

def _ref_greedy(params, ctx, prompt, max_new):
    toks = list(prompt)
    outs = []
    for _ in range(max_new):
        logits, _ = lm.forward(params, CFG, ctx, {"ids": jnp.asarray([toks])})
        t = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        outs.append(t)
        toks.append(t)
    return outs


def _staggered_run(params, ctx, reqs, policy="prefill"):
    ecfg = EngineConfig(lanes=3, num_slots=4, page_len=24, prefill_len=8,
                        policy=policy)
    eng = Engine(params, CFG, ctx, ecfg)
    rids = []
    for i, (prompt, max_new) in enumerate(reqs):
        rids.append(eng.add_request(prompt, max_new=max_new))
        eng.step()  # arrivals interleave with engine progress
        if i % 2:
            eng.step()
    return eng, {r: eng.requests[r] for r in rids}, eng.run()


@pytest.mark.parametrize("backend", ["float", "mxfp4"])
def test_continuous_batching_matches_single_request(
    backend, float_model, mxfp4_model
):
    params, ctx = float_model if backend == "float" else mxfp4_model
    rng = np.random.default_rng(3)
    reqs = [
        (rng.integers(0, CFG.vocab_size, size=rng.integers(2, 9)).tolist(),
         int(rng.integers(2, 7)))
        for _ in range(6)
    ]
    eng, _, out = _staggered_run(params, ctx, reqs)
    assert eng.slot_utilization > 0.5
    # (a) same compiled steps, one request at a time -> bit-identical lanes
    solo = Engine(params, CFG, ctx, eng.ecfg)
    for rid, (prompt, max_new) in enumerate(reqs):
        srid = solo.add_request(prompt, max_new=max_new)
        assert solo.run()[srid] == out[rid], f"lane isolation broke rid {rid}"
    # (b) greedy full-sequence lm.forward, same backend
    for rid, (prompt, max_new) in enumerate(reqs):
        assert _ref_greedy(params, ctx, prompt, max_new) == out[rid], (
            f"decode path diverged from lm.forward for rid {rid}"
        )


def test_continuous_batching_isolation_cim(cim_model):
    """Under the hybrid analog backend, staggered continuous batching is
    still bit-identical to solo runs through the same compiled steps
    (lanes are independent; fixed shapes -> one executable). The greedy
    lm.forward cross-check is omitted for cim: cross-graph 1-ulp ties
    flip MXFP4/INT5 codes (see test_backends.py docstring)."""
    params, ctx = cim_model
    rng = np.random.default_rng(5)
    reqs = [
        (rng.integers(0, CFG.vocab_size, size=rng.integers(2, 9)).tolist(),
         int(rng.integers(2, 6)))
        for _ in range(3)
    ]
    eng, _, out = _staggered_run(params, ctx, reqs)
    solo = Engine(params, CFG, ctx, eng.ecfg)
    for rid, (prompt, max_new) in enumerate(reqs):
        srid = solo.add_request(prompt, max_new=max_new)
        assert solo.run()[srid] == out[rid], f"lane isolation broke rid {rid}"


def test_decode_priority_policy_runs(float_model):
    params, ctx = float_model
    rng = np.random.default_rng(4)
    reqs = [
        (rng.integers(0, CFG.vocab_size, size=5).tolist(), 3)
        for _ in range(4)
    ]
    _, _, out = _staggered_run(params, ctx, reqs, policy="decode")
    for rid, (prompt, max_new) in enumerate(reqs):
        assert _ref_greedy(params, ctx, prompt, max_new) == out[rid]


# ----------------------------------- satellite: admission regression fixes

def test_page_exhaustion_evicts_and_readmits(float_model):
    """Satellite regression: ``add_request`` used to reject any request
    with ``len(prompt) + max_new > page_len`` up front, which made the
    scheduler's "page_exhausted" stop arm dead code. The page budget is
    runtime state now: the request decodes until its page fills, finishes
    with reason page_exhausted, and its freed slot re-admits the next
    waiting request."""
    params, ctx = float_model
    ecfg = EngineConfig(lanes=1, num_slots=1, page_len=8, prefill_len=4)
    eng = Engine(params, CFG, ctx, ecfg)
    rid = eng.add_request([1, 2, 3, 4], max_new=100)  # page caps it at 4
    rid2 = eng.add_request([5, 6], max_new=2)  # must wait for the slot
    out = eng.run()
    req = eng.requests[rid]
    # prefill emits one token "for free"; each decode then burns a page
    # row until pos hits page_len
    assert len(out[rid]) == ecfg.page_len - 4 + 1
    assert req.pos == ecfg.page_len
    span = next(r for r in eng.obs.finished if r.rid == rid)
    assert span.finish_reason == "page_exhausted"
    # the evicted request's slot (the only one) was recycled for rid2
    assert eng.requests[rid2].slot == 0
    assert len(out[rid2]) == 2
    assert eng.kv.allocator.num_free == 1


def test_prefill_billing_uses_executed_width(float_model):
    """Satellite regression: prefill was billed at ``len(req.prompt)``,
    but the engine always executes a fixed ``[1, prefill_len]`` window —
    a 3-token prompt occupies the pipeline exactly as long as an 8-token
    one. Occupancy accounting now records the executed width; the span
    keeps the real prompt length for TTFT attribution."""
    params, ctx = float_model
    ecfg = EngineConfig(lanes=1, num_slots=1, page_len=16, prefill_len=8)
    reps = []
    for n in (3, 8):  # padded vs exact-width prompt
        eng = Engine(params, CFG, ctx, ecfg)
        eng.add_request(list(range(1, n + 1)), max_new=3)
        eng.run()
        pre = [e for e in eng.obs.steps if e.kind == "prefill"]
        assert [e.n_tokens for e in pre] == [ecfg.prefill_len]
        assert eng.obs.finished[0].n_prompt == n
        reps.append(eng.trace_report())
    assert reps[0].pipeline.makespan == pytest.approx(
        reps[1].pipeline.makespan
    )


def test_static_plan_optional_executed_width():
    reqs = [Request(rid=0, prompt=[1, 2], max_new=2),
            Request(rid=1, prompt=[1, 2, 3, 4], max_new=2)]
    exact = static_batching_plan(reqs, lanes=2)
    padded = static_batching_plan(reqs, lanes=2, prefill_len=8)
    assert [e for e in exact if e[0] == "prefill"] == [
        ("prefill", (0,), 2), ("prefill", (1,), 4)]
    assert [e for e in padded if e[0] == "prefill"] == [
        ("prefill", (0,), 8), ("prefill", (1,), 8)]
    assert [e for e in exact if e[0] == "decode"] == [
        e for e in padded if e[0] == "decode"]


# ------------------------------------------- satellite: paged decode path

def _paged_and_legacy_decode(params, ctx, ids, pre, t, prefill_len=12):
    """Run the serving decode path (padded fixed-shape prefill -> slot
    scatter -> gather -> per-lane-pos decode) and the legacy monolithic
    decode (unpadded prefill-into-cache, scalar pos) side by side.
    Returns per-step (paged_logits, legacy_logits) [V] arrays."""
    kv = PagedKVCache(CFG, num_slots=2, lanes=1, page_len=16)
    slot = kv.allocator.alloc()
    rows = jnp.asarray([slot], jnp.int32)
    n = pre
    pad_ids = np.zeros((1, prefill_len), np.int32)
    pad_ids[0, :n] = np.asarray(ids[0, :n])
    positions = np.full((1, prefill_len), attn_mod.KV_PAD, np.int32)
    positions[0, :n] = np.arange(n)
    caches = lm.init_cache(CFG, 1, kv.page_len)
    _, caches = lm.forward(
        params, CFG, ctx,
        {"ids": jnp.asarray(pad_ids), "positions": jnp.asarray(positions)},
        caches=caches,
    )
    kv.scatter(rows, caches)
    legacy = lm.init_cache(CFG, 1, kv.page_len)
    _, legacy = lm.forward(params, CFG, ctx, {"ids": ids[:, :pre]},
                           caches=legacy)
    out = []
    for p in range(pre, t):
        lg_p, new = lm.decode_step(
            params, CFG, ctx, ids[:, p:p + 1],
            jnp.full((1,), p, jnp.int32), kv.gather(rows),
        )
        kv.scatter(rows, new)
        lg_l, legacy = lm.decode_step(
            params, CFG, ctx, ids[:, p:p + 1], jnp.int32(p), legacy
        )
        out.append((np.asarray(lg_p, np.float32)[0],
                    np.asarray(lg_l, np.float32)[0]))
    return out


def test_paged_decode_matches_forward_logits_mxfp4(mxfp4_model):
    """Satellite: teacher-forced decode over the paged cache reproduces
    the full-sequence ``lm.forward`` logits token-for-token under the
    serving mxfp4 backend (weight-only resident MXFP4 — no activation
    quantization, so decode is length-causal and the full forward is a
    valid fixture; cf. the cim variant below)."""
    params, ctx = mxfp4_model
    t, pre = 10, 4
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, t), 0, CFG.vocab_size)
    full, _ = lm.forward(params, CFG, ctx, {"ids": ids})
    full = np.asarray(full, np.float32)
    steps = _paged_and_legacy_decode(params, ctx, ids, pre, t)
    for i, (got, leg) in enumerate(steps):
        p = pre + i
        want = full[0, p]
        assert got.argmax() == want.argmax(), f"token mismatch at pos {p}"
        np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
        np.testing.assert_array_equal(got, leg)


def test_paged_decode_matches_legacy_decode_cim(cim_model):
    """Satellite, hybrid-analog half: the paged serving decode is
    *bitwise* the legacy monolithic-cache decode, and its deviation from
    the full-sequence forward is bounded.

    Exact equality with ``lm.forward`` is unattainable for the hybrid
    SDPA by construction: the digital-MXFP4 datapath (paper §4.5)
    re-quantizes V in shared-exponent blocks along the key axis, so a
    full forward's block exponents see tokens that had not arrived when
    the decode cache froze each K/V row — appending a token perturbs
    *earlier* positions' layer>=1 hidden states (encoder-tile semantics;
    measured ~10-13 dB logit SQNR on this random-init worst case, which
    near-uniform random logits turn into argmax near-ties).

    So top-1 agreement is checked only where it is decidable: at every
    step the paged token must score within ``tie_tol`` of the reference's
    top-1, and wherever the reference's own top-1 margin exceeds
    ``tie_tol`` it must be that token. ``tie_tol`` is a quarter of the
    logit spread (std ~1 here). The regret this bounds (reference top-1
    minus the reference's score of the paged top-1) measures at most
    0.156 over these 6 steps: three flips, of 0.016, 0.031 and 0.156.
    One step (margin 0.484) is decisive."""
    from repro.core.metrics import sqnr_db

    tie_tol = 0.25
    params, ctx = cim_model
    ctx = dataclasses.replace(ctx, unroll_layers=True)
    t, pre = 10, 4
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, t), 0, CFG.vocab_size)
    full, _ = lm.forward(params, CFG, ctx, {"ids": ids})
    full = np.asarray(full, np.float32)
    steps = _paged_and_legacy_decode(params, ctx, ids, pre, t)
    decisive = 0
    for i, (got, leg) in enumerate(steps):
        p = pre + i
        np.testing.assert_array_equal(
            got, leg, err_msg=f"paged != legacy decode at pos {p}"
        )
        want = full[0, p]
        assert sqnr_db(want, got) > 10.0, f"unbounded drift at pos {p}"
        regret = want.max() - want[got.argmax()]
        assert regret <= tie_tol, f"pos {p}: top-1 off by {regret:.3f}"
        second, first = np.sort(want)[-2:]
        if first - second > tie_tol:
            decisive += 1
            assert got.argmax() == want.argmax(), f"pos {p}: top-1 flipped"
    assert decisive >= 1, "no decidable step left to check"


# ----------------------------------------------- sharded paged decode step

def test_make_paged_decode_step_executes(float_model):
    """The sharded serving bundle compiles and one paged step matches the
    plain (unsharded) gather -> decode -> scatter composition."""
    from repro import configs as C2
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_local_mesh

    params, ctx = float_model
    mesh = make_local_mesh()
    lanes, num_slots, page = 2, 3, 8
    bundle = steps_mod.make_paged_decode_step(
        CFG, mesh, C2.Shape(page, lanes, "decode"), num_slots, quant="none"
    )
    pool = lm.init_cache(CFG, num_slots + lanes, page)
    rows = jnp.asarray([1, num_slots + 1], jnp.int32)  # lane0 slot1, lane1 parked
    ids = jax.random.randint(jax.random.PRNGKey(7), (lanes, 1), 0,
                             CFG.vocab_size)
    pos = jnp.asarray([0, 0], jnp.int32)
    # reference before the jitted call: bundle.fn donates the pool buffers
    ref_caches = gather_rows(pool, lm.cache_specs(CFG), rows)
    logits, ref_caches = lm.decode_step(
        params, CFG, bundle.ctx, ids, pos, ref_caches
    )
    ref_pool = scatter_rows(pool, lm.cache_specs(CFG), rows, ref_caches)

    next_ids, new_pool = bundle.fn(params, pool, rows, ids, pos)
    assert next_ids.shape == (lanes,)
    np.testing.assert_array_equal(
        np.asarray(next_ids),
        np.asarray(jnp.argmax(logits.astype(jnp.float32), -1), np.int32),
    )
    for seg_a, seg_b in zip(new_pool, ref_pool):
        for k in seg_a:
            np.testing.assert_array_equal(np.asarray(seg_a[k], np.float32),
                                          np.asarray(seg_b[k], np.float32))


# --------------------------------------------------- FWS pipeline fidelity

def test_pipeline_steady_state_fps_matches_table7():
    for name, n_tokens, d in (("vit-b16", 197, 768), ("bert-base", 512, 768)):
        paper_fps = S.PAPER_TABLE7[name][1]
        jobs = [pipe.Job(0.0, n_tokens) for _ in range(240)]
        rep = pipe.simulate(jobs, d_model=d)
        assert rep.steady_state_fps == pytest.approx(paper_fps, rel=0.05), name
        assert rep.steady_state_fps == pytest.approx(
            perf.steady_state_fps(n_tokens, d), rel=1e-6
        )
        # pipeline full from a deep queue -> the bottleneck stage saturates
        assert rep.stage_utilization > 0.9


def test_steady_state_fps_is_public_and_consistent():
    assert perf.steady_state_fps(197) == pytest.approx(
        1.0 / perf.stage_time(197, 768)
    )
    w = S.WORKLOADS["vit-b16"]
    assert perf.steady_state_fps(w.seq, w.d) == pytest.approx(perf.fps(w))


def test_pipeline_latency_and_warmup():
    # a single job's latency is n_stages * stage_time after an empty pipe
    rep = pipe.simulate([pipe.Job(0.0, 64)], d_model=768)
    t = perf.stage_time(64, 768)
    assert rep.timings[0].latency == pytest.approx(pipe.N_STAGES * t)
    # back-to-back jobs: one drains per stage_time in steady state
    rep = pipe.simulate([pipe.Job(0.0, 64) for _ in range(40)], d_model=768)
    drains = [x.finish for x in rep.timings]
    gaps = np.diff(drains[pipe.N_STAGES:])
    np.testing.assert_allclose(gaps, t, rtol=1e-9)


def test_trace_report_continuous_vs_static(float_model):
    params, ctx = float_model
    rng = np.random.default_rng(6)
    reqs = [
        (rng.integers(0, CFG.vocab_size, size=rng.integers(2, 8)).tolist(),
         int(rng.integers(2, 8)))
        for _ in range(6)
    ]
    eng, _, out = _staggered_run(params, ctx, reqs)
    rep = eng.trace_report()
    assert set(rep.request_latency) == set(out)
    assert all(v > 0 for v in rep.request_latency.values())
    assert 0 < rep.pipeline.stage_utilization <= 1.0
    n_tok = sum(len(v) for v in out.values())
    assert rep.tokens_per_s == pytest.approx(n_tok / rep.pipeline.makespan)

    static = pipe.simulate_trace(
        static_batching_plan(
            [Request(rid=i, prompt=p, max_new=m)
             for i, (p, m) in enumerate(reqs)], lanes=3),
        CFG.d_model, lanes=3,
    )
    # static batching wastes lanes on the tail of every group
    assert static.lane_utilization < 1.0
    assert eng.slot_utilization > static.lane_utilization


# ----------------------------------------------- simulate_trace edge cases

def test_simulate_trace_empty():
    rep = pipe.simulate_trace([], CFG.d_model, lanes=3)
    assert rep.request_latency == {}
    assert rep.tokens_per_s == 0.0
    assert rep.pipeline.makespan == 0.0
    assert rep.pipeline.bubble_fraction == 1.0
    assert rep.pipeline.fill_latency_s == 0.0
    assert rep.lane_utilization == 1.0  # no decode steps -> vacuous


def test_simulate_trace_single_event():
    rep = pipe.simulate_trace([("prefill", (0,), 8)], CFG.d_model, lanes=3)
    assert set(rep.request_latency) == {0}
    # one job alone: latency == full pipe traversal == fill latency
    assert rep.request_latency[0] == pytest.approx(
        rep.pipeline.fill_latency_s
    )
    assert rep.tokens_per_s > 0


def test_simulate_trace_evicted_before_finish():
    # rid 1 is evicted after one decode step (no further events); its
    # latency still closes at the drain of the last job that carried it
    events = [
        ("prefill", (0,), 8),
        ("prefill", (1,), 8),
        ("decode", (0, 1), 2),
        ("decode", (0,), 1),
        ("decode", (0,), 1),
    ]
    rep = pipe.simulate_trace(events, CFG.d_model, lanes=3)
    assert set(rep.request_latency) == {0, 1}
    assert rep.request_latency[1] < rep.request_latency[0]
    assert all(v > 0 for v in rep.request_latency.values())


def test_simulate_trace_accepts_step_events():
    from repro.obs import StepEvent

    tuples = [("prefill", (0,), 4), ("decode", (0,), 1)]
    typed = [StepEvent(k, r, n, 0.0, 0.0) for k, r, n in tuples]
    a = pipe.simulate_trace(tuples, CFG.d_model, lanes=2)
    b = pipe.simulate_trace(typed, CFG.d_model, lanes=2)
    assert a.request_latency == b.request_latency
    assert a.tokens_per_s == b.tokens_per_s


# ------------------------------------------------------- engine telemetry

def test_engine_emits_request_spans_and_metrics(float_model):
    from repro import obs as obs_lib

    params, ctx = float_model
    rng = np.random.default_rng(7)
    reqs = [
        (rng.integers(0, CFG.vocab_size, size=rng.integers(2, 8)).tolist(),
         int(rng.integers(2, 6)))
        for _ in range(4)
    ]
    eng, _, out = _staggered_run(params, ctx, reqs)
    o = eng.obs
    # every request finished with a full span: ttft < e2e, tokens counted
    assert len(o.finished) == len(reqs)
    for r in o.finished:
        assert r.t_admitted is not None and r.ttft_s > 0
        assert r.e2e_s >= r.ttft_s
        assert r.n_generated == len(out[r.rid])
    # the derived legacy view matches Engine.trace and feeds the pipeline
    assert eng.trace == o.legacy_trace()
    assert {e.kind for e in o.steps} == {"prefill", "decode"}
    reg = o.registry
    assert reg.counter("serve_requests_total").value == len(reqs)
    assert reg.histogram("serve_ttft_seconds").count == len(reqs)
    n_tok = sum(len(v) for v in out.values())
    assert reg.counter("serve_tokens_generated_total").value == n_tok
    finished = reg.counter("serve_requests_finished_total",
                           labels={"reason": "max_new"})
    assert finished.value == len(reqs)
    # trace_report still works off the typed record
    rep = eng.trace_report()
    assert set(rep.request_latency) == set(out)


def test_engine_disabled_obs_matches_default_trace(float_model):
    from repro import obs as obs_lib

    params, ctx = float_model
    ecfg = EngineConfig(lanes=2, num_slots=2, page_len=16, prefill_len=8)
    prompt = [3, 1, 4, 1, 5]
    eng_on = Engine(params, CFG, ctx, ecfg)
    eng_off = Engine(params, CFG, ctx, ecfg,
                     obs=obs_lib.Obs(enabled=False))
    for eng in (eng_on, eng_off):
        eng.add_request(list(prompt), max_new=3)
    assert eng_on.run()[0] == eng_off.run()[0]
    # the step record (pipeline-model input) is identical either way...
    assert eng_off.trace == eng_on.trace
    assert eng_off.slot_utilization == eng_on.slot_utilization
    # ...but the disabled side did no registry or span work
    assert eng_off.obs.registry.families() == []
    assert eng_off.obs.finished == []
    assert len(eng_on.obs.finished) == 1
