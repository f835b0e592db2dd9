"""The LM cells: the paged continuous-batching ``Engine`` under an open
loop, and the check of its greedy tokens against the plain reference.

Set-up makes the weights on the device in one jitted call from the seed
(each layer drawn and converted to MXFP4 inside a ``lax.map``), builds the
engine, and warms exactly the steps the cell's traffic uses. The window
sends each request when it is due, steps the engine in between, and stamps
every token on the host clock when the step that made it returns.
Arrivals stop when the window closes; admitted requests are drained.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

import gen
import harness

DRAIN_LIMIT_S = 120.0


def arch(name: str, m: dict):
    from repro.models.lm import ArchConfig

    window = m.get("sliding_window") or 0
    return ArchConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m.get("head_dim", 0),
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        attn_pattern="swa" if window else "full", window=window or 4096,
        rope_theta=m["rope_theta"], ffn_kind="swiglu", norm="rmsnorm",
        tie_embeddings=bool(m.get("tie_word_embeddings", False)),
    )


def served_params(ref, m: dict, seed: int, min_n: int):
    """The served tree (packed MXFP4 linears, bf16 rest) in one jitted call:
    each layer's float weights are drawn and converted inside a lax.map, so
    no more than one float layer is live at a time."""
    import jax
    import jax.numpy as jnp

    from repro.layers.common import convert_params_mxfp4

    d = m["hidden_size"]

    def layer(key, i):
        w = ref.layer_weights(key, m, i)
        ones = jnp.ones((d,), jnp.float32)
        tree = {
            "attn": {"ln": {"gamma": ones},
                     **{k: {"w": w[k]} for k in ("wq", "wk", "wv", "wo")}},
            "ffn": {"ln": {"gamma": ones},
                    **{k: {"w": w[k]} for k in ("w1", "w3", "w2")}},
        }
        return convert_params_mxfp4(tree, min_n=min_n)

    def build(key):
        layers = jax.lax.map(lambda i: layer(key, i),
                             jnp.arange(m["num_hidden_layers"]))
        rest = convert_params_mxfp4({
            "embed": {"emb": ref.embed_weights(key, m)},
            "final_ln": {"gamma": jnp.ones((d,), jnp.float32)},
            "lm_head": {"w": ref.head_weights(key, m)},
        }, min_n=min_n)
        return {**rest, "segments": [layers]}

    return jax.jit(build)(ref.base_key(seed))


@dataclasses.dataclass
class System:
    engine: object
    files: dict
    seed: int
    phases: dict
    reqs: list = dataclasses.field(default_factory=list)


def build(files: dict, seed: int, seconds: float = 0.0) -> System:
    """Weights, engine and warm-up; with ``seconds``, the open-loop
    schedule of a window that long too."""
    import jax

    from repro.layers.common import RunCtx, ShardingCtx
    from repro.serving.engine import Engine, EngineConfig

    cfg = files["config"]
    m = cfg["model"]
    phases = {}
    t = time.perf_counter()
    params = served_params(files["ref"], m, seed, cfg.get("min_n", 256))
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t
    ctx = RunCtx(shd=ShardingCtx(), quant="mxfp4_wonly", dense_attn_max=256)
    eng = Engine(params, arch(cfg["name"], m), ctx,
                 EngineConfig(**files["cell"]["engine"]))
    t = time.perf_counter()
    warm(eng)
    phases["warmup_s"] = time.perf_counter() - t
    return System(eng, files, seed, phases,
                  schedule(files, seed, seconds) if seconds else [])


def warm(eng) -> None:
    """Compile every step this engine's traffic uses, and no other: the
    single-shot prefill or the chunk and page-clone steps, and decode."""
    e = eng.ecfg
    n = 2 * e.chunk_len if e.chunk_len else min(8, e.prefill_len)
    n = min(n, e.page_len - 2)
    eng.add_request([1] * n, max_new=2)
    eng.run()


def schedule(files: dict, seed: int, seconds: float,
             rate: float | None = None) -> list:
    cell = files["cell"]
    return gen.requests(files["traffic"], rate or cell["rate_rps"], seconds,
                        seed, files["config"]["model"]["vocab_size"])


def serve_window(eng, reqs, seconds: float, annotate=None,
                 drain_limit: float = DRAIN_LIMIT_S) -> dict:
    """Open loop: send each request at its due time, step the engine in
    between. Returns the per-request records and the window bounds
    (``time.perf_counter`` seconds)."""
    span = annotate or (lambda name: contextlib.nullcontext())
    clock = time.perf_counter
    t0 = clock()
    end = t0 + seconds
    recs, live = [], {}
    queue = []  # (time, waiting requests) after each step
    i = 0
    with span("window"):
        while True:
            now = clock()
            while i < len(reqs) and t0 + reqs[i].due <= min(now, end):
                r = reqs[i]
                with span("add_request"):
                    rid = eng.add_request(r.prompt.tolist(), r.max_new)
                rec = {"rid": rid, "due": t0 + r.due, "n_prompt": len(r.prompt),
                       "max_new": r.max_new, "times": [], "done": False}
                recs.append(rec)
                live[rid] = rec
                i += 1
            if eng.sched.has_work:
                with span("engine.step"):
                    eng.step()
                t = clock()
                for rid in list(live):
                    req = eng.requests[rid]
                    rec = live[rid]
                    k = len(req.out) - len(rec["times"])
                    if k:
                        rec["times"].extend([t] * k)
                    if req.done:
                        rec["done"] = True
                        del live[rid]
                queue.append((t, len(eng.sched.waiting)))
            elif i < len(reqs) and now < end:
                with span("arrival_wait"):
                    time.sleep(max(0.0, min(t0 + reqs[i].due, end) - now))
            else:
                break
            if clock() > end + drain_limit:
                break
    return {"t0": t0, "end": end, "t_last": clock(), "requests": recs,
            "queue": queue, "seconds": seconds}


def window(system: System, seconds: float, annotate=None) -> dict:
    win = serve_window(system.engine, system.reqs, seconds, annotate)
    win["attempted"] = len(win["requests"])
    win["failed"] = sum(1 for r in win["requests"] if not r["done"])
    harness.log(f"latency percentiles (ms): {percentiles(win)}")
    return win


def percentiles(win: dict) -> dict:
    """TTFT and inter-token gaps at several percentiles, with counts."""
    reqs = win["requests"]
    ttft = [r["times"][0] - r["due"] for r in reqs if r["times"]]
    itl = [b - a for r in reqs for a, b in zip(r["times"], r["times"][1:])]
    out = {}
    for name, vals in (("ttft", ttft), ("itl", itl)):
        out[name] = {"n": len(vals), **{
            f"p{q}": round(1e3 * (harness.percentile(vals, q) or 0), 3)
            for q in (50, 90, 95, 99)}}
    return out


def program_records(system: System, win: dict) -> dict:
    """The engine's own spans and counters over the window: step events
    and each request's admission time."""
    eng = system.engine
    steps = [(e.kind, e.n_tokens, len(e.rids), e.t_start, e.t_end)
             for e in eng.obs.steps if e.t_start >= win["t0"]]
    admitted = {r.rid: r.t_admitted for r in eng.obs.finished}
    admitted.update({rid: r.t_admitted for rid, r in eng.obs.live.items()})
    for rec in win["requests"]:
        rec["admitted"] = admitted.get(rec["rid"])
    return {"steps": steps}


def outputs(system: System, win: dict) -> list:
    return sample(system.seed, win["requests"], system.engine,
                  system.files["cell"]["check"]["requests"])


def sample(seed: int, recs: list, eng, k: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among
    them: [(prompt, served tokens)]."""
    done = [r for r in recs if r["done"]]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda j: done[j]["n_prompt"] + len(done[j]["times"]))
    rest = [j for j in range(len(done)) if j != longest]
    pick = gen.rng_for(seed, 99).permutation(rest)[: max(0, k - 1)]
    out = []
    for j in [longest, *pick]:
        req = eng.requests[done[j]["rid"]]
        out.append((np.asarray(req.prompt, np.int32),
                    np.asarray(req.out, np.int32)))
    return out


def free(system: System) -> None:
    """Drop the engine's device state before the reference runs."""
    eng = system.engine
    eng.kv.pool = None
    eng.params = None
    system.engine = None
    gc.collect()


def gaps(ref, seed: int, m: dict, served: list, control: bool = False
         ) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best, in units of that position's logit standard
    deviation. With ``control``, the token is the one the lower-precision
    reference puts first, at each position of the same prompts and
    tokens."""
    if not served:
        return np.zeros(0)
    seqs = [np.concatenate([p, t[:-1]]) for p, t in served]
    reads = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in served]
    z = ref.logits_at(seed, m, seqs, reads)
    if control:
        zc = ref.logits_at(seed, m, seqs, reads, control=True)
        toks = [c.argmax(-1) for c in zc]
    else:
        toks = [t for _, t in served]
    out = []
    for zj, tj in zip(z, toks):
        best = zj.max(-1)
        got = np.take_along_axis(zj, np.asarray(tj)[:, None], -1)[:, 0]
        out.append((best - got) / zj.std(-1))
    return np.concatenate(out)


def output_gaps(system: System, served: list, control: bool = False):
    files = system.files
    return gaps(files["ref"], system.seed, files["config"]["model"], served,
                control)


def check(system: System, served: list, win: dict) -> dict:
    n_failed = win["failed"]
    files = system.files
    limit = files["cell"]["check"]["gap_limit"]
    least = files["cell"]["check"]["min_tokens"]
    g = output_gaps(system, served)
    widest = float(g.max()) if len(g) else None
    return {
        "served_logit_gap": {"value": widest, "limit": limit,
                             "ok": widest is not None and widest <= limit},
        "served_tokens_compared": {"value": int(len(g)), "limit": least,
                                   "ok": len(g) >= least},
        "requests_unfinished": {"value": n_failed, "limit": 0,
                                "ok": n_failed == 0},
    }
