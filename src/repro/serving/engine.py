"""Continuous-batching inference engine over the backend registry.

``Engine`` glues the pieces together: a :class:`PagedKVCache` pool, a
:class:`Scheduler`, an optional :class:`PrefixCache`, and a set of
*fixed-shape* jitted steps —

  prefill  [1, prefill_len]   one padded prompt into its allocated slot
  chunk    [1, chunk_len]     one window of a longer prompt into its slot
                              (looped; lifts the prompt cap to page_len
                              without recompiles)
  decode   [lanes, 1]         one token per lane at per-lane positions

so XLA compiles each shape exactly once regardless of how requests come
and go. Prompts are right-padded with ``KV_PAD`` positions (masked out of
attention by ``layers.attention._mask``); decode lanes without an active
request park on their scratch row and their outputs are discarded on the
host. Works under any linear-execution backend (float / mxfp4 / cim)
because the steps just call ``lm.forward``/``lm.decode_step`` with
whatever converted params + RunCtx the caller built (see
``launch/serve.py::build_backend``).

Chunked prefill (``chunk_len``) feeds long prompts through the fixed
``[1, chunk_len]`` step one window at a time; under the ``chunked``
scheduler policy those windows interleave with decode steps so a long
prompt no longer stalls live lanes. Admission always starts by cloning /
resetting the request's page (``kvcache.clone_prefix``): with the prefix
cache on (``prefix_cache=True``) the longest chunk-aligned cached prefix
is copied from the donor page — copy-on-write at the divergence point —
and only the suffix chunks run; on a miss the clone degenerates to a
page zeroing (reused slots carry stale rows that would otherwise corrupt
shared-exponent blocks of the quantized-resident mirrors).

Telemetry: the engine emits typed lifecycle events through a
``repro.obs.Obs`` handle — enqueue -> admitted -> prefill/first-token ->
per-decode-step -> finish/evict — yielding per-request TTFT, queue-wait,
per-token latency, occupancy and eviction metrics. Prefill step events
bill the *executed* width (``prefill_len`` or ``chunk_len``), not the
prompt length: the jitted step pushes the full padded window through the
FWS pipeline whether or not the tail is padding, and the pipeline model
should see that. The old ad-hoc ``(kind, rids, n_tokens)`` tuple trace
survives as the derived ``Engine.trace`` view, which
``serving/pipeline.py`` maps onto the twelve-stage FWS pipeline for
simulated latency/throughput reporting.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers import attention as attn_mod
from repro.models import lm
from repro.obs import Obs
from repro.serving import pipeline as pipe_mod
from repro.serving.kvcache import (
    PagedKVCache,
    PoolExhausted,
    clone_prefix,
    gather_rows,
    scatter_rows,
)
from repro.serving.prefix import PrefixCache
from repro.serving.scheduler import Request, Scheduler


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    lanes: int = 4  # decode batch width
    num_slots: int = 6  # KV pages in the pool (>= lanes to be useful)
    page_len: int = 48  # positions per page (prompt + generated)
    prefill_len: int = 16  # fixed prefill shape; prompts pad up to this
    policy: str = "prefill"  # admission policy (see scheduler.py)
    # "fused" switches the pool to the head-interleaved paged layout and
    # decodes in place through the ragged paged flash-decode path —
    # RunCtx.paged_rows maps lanes to pool rows inside the jitted step,
    # so a decode step does O(lanes) KV writes instead of gathering and
    # scattering full pages
    kv_layout: str = "legacy"  # legacy | fused
    # chunked prefill: prompts run through a fixed [1, chunk_len] step in
    # absolute-position windows, lifting the prompt cap from prefill_len
    # to page_len. None keeps the single-shot padded prefill (and its
    # exact numerics — chunked attention quantizes over page-width keys,
    # so cim outputs differ statistically, not bitwise, from single-shot)
    chunk_len: int | None = None
    # radix prefix cache over the page pool (requires chunk_len: hits are
    # chunk-aligned so cached pages drop into the same chunk grid)
    prefix_cache: bool = False


class Engine:
    def __init__(self, params, cfg, ctx, ecfg: EngineConfig = EngineConfig(),
                 obs: Obs | None = None):
        if ecfg.prefill_len > ecfg.page_len:
            raise ValueError("prefill_len must fit in a page")
        if ecfg.chunk_len is not None and not (
                2 <= ecfg.chunk_len <= ecfg.page_len):
            # >= 2: the fixed-shape chunk step must take attention's
            # multi-token prefill branch, not the decode branch
            raise ValueError("chunk_len must be in [2, page_len]")
        if ecfg.prefix_cache and ecfg.chunk_len is None:
            raise ValueError("prefix_cache requires chunk_len (hits are "
                             "chunk-aligned)")
        self.params = params
        self.cfg = cfg
        self.ctx = ctx
        self.ecfg = ecfg
        self.obs = obs if obs is not None else Obs()
        # hybrid / fully-digital MXFP4 SDPA: the pool keeps K/V codes
        # resident so decode quantization is O(1) in cache length
        self.kv = PagedKVCache(cfg, ecfg.num_slots, ecfg.lanes, ecfg.page_len,
                               mx_digital=ctx.hybrid_digital_sdpa,
                               layout=ecfg.kv_layout)
        self.sched = Scheduler(ecfg.lanes, ecfg.policy, obs=self.obs)
        self.prefix: PrefixCache | None = None
        if ecfg.prefix_cache:
            self.prefix = PrefixCache(ecfg.chunk_len, self.kv.allocator,
                                      obs=self.obs)
        self.requests: dict[int, Request] = {}
        self._next_rid = 0
        self._step_idx = 0
        self._prefill, self._decode, self._chunk, self._clone = \
            self._build_steps()

    # ------------------------------------------------------- jitted steps

    def _build_steps(self):
        cfg, ctx, ecfg = self.cfg, self.ctx, self.ecfg
        specs = self.kv.specs

        def prefill(params, pool, ids, positions, row, last):
            caches = lm.init_cache(cfg, 1, ecfg.page_len,
                                   mx_digital=self.kv.mx_digital,
                                   fused=self.kv.fused)
            hidden, caches = lm.forward(
                params, cfg, ctx, {"ids": ids, "positions": positions},
                caches=caches, return_hidden=True,
            )
            # head over the real last position only (padded tail discarded).
            # Pad rows of the written page are already zero: attn_apply
            # zeroes K/V at KV_PAD positions and init_cache zero-fills
            # beyond the prefill width.
            logits = lm._head(ctx, cfg, params, hidden[:, last][:, None])
            pool = scatter_rows(pool, specs, row, caches)
            return jnp.argmax(logits[0, 0].astype(jnp.float32)), pool

        def decode_legacy(params, pool, rows, ids, pos):
            caches = gather_rows(pool, specs, rows)
            logits, caches = lm.decode_step(params, cfg, ctx, ids, pos, caches)
            return logits, scatter_rows(pool, specs, rows, caches)

        def decode_fused(params, pool, rows, ids, pos):
            # in-place paged decode: lanes address their pool rows through
            # RunCtx.paged_rows (threaded inside the trace — never closed
            # over), so no page gather/scatter brackets the step
            dctx = dataclasses.replace(ctx, paged_rows=rows)
            return lm.decode_step(params, cfg, dctx, ids, pos, pool)

        # the un-jitted decode body: (params, pool, rows, ids, pos) ->
        # ([lanes, V] logits, pool); the jitted step keeps only the argmax
        decode_logits = decode_fused if self.kv.fused else decode_legacy
        self.decode_logits = decode_logits

        def decode(params, pool, rows, ids, pos):
            logits, pool = decode_logits(params, pool, rows, ids, pos)
            return jnp.argmax(logits.astype(jnp.float32), -1), pool

        def chunk(params, pool, row, ids, positions, offset, last):
            # one [1, chunk_len] window of a longer prompt, written into
            # the request's page at absolute positions (attn_apply's
            # chunked-prefill branch, selected by pos=offset). The page
            # was cloned/zeroed at admission, so rows beyond the written
            # prefix are deterministic zeros.
            caches = gather_rows(pool, specs, row)
            hidden, caches = lm.forward(
                params, cfg, ctx, {"ids": ids, "positions": positions},
                caches=caches, pos=offset, return_hidden=True,
            )
            logits = lm._head(ctx, cfg, params, hidden[:, last][:, None])
            pool = scatter_rows(pool, specs, row, caches)
            return jnp.argmax(logits[0, 0].astype(jnp.float32)), pool

        def clone(pool, src, dst, n):
            return clone_prefix(pool, specs, src, dst, n)

        chunked = ecfg.chunk_len is not None
        return (
            jax.jit(prefill, donate_argnums=(1,)),
            jax.jit(decode, donate_argnums=(1,)),
            jax.jit(chunk, donate_argnums=(1,)) if chunked else None,
            jax.jit(clone, donate_argnums=(0,)) if chunked else None,
        )

    # --------------------------------------------------------- public API

    def add_request(self, prompt, max_new: int, stop_token: int | None = None
                    ) -> int:
        prompt = [int(t) for t in prompt]
        limit = (self.ecfg.page_len if self.ecfg.chunk_len is not None
                 else self.ecfg.prefill_len)
        if not prompt or len(prompt) > limit:
            raise ValueError(
                f"prompt length {len(prompt)} not in [1, {limit}]"
            )
        if max_new < 1:
            raise ValueError("max_new must be >= 1 (prefill emits a token)")
        # NOTE: len(prompt) + max_new may exceed page_len. The request
        # then finishes with reason "page_exhausted" once its page fills
        # — the eviction path. (An older guard rejected these up front,
        # which made the scheduler's page_exhausted arm dead code.)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      stop_token=stop_token, arrival=self._step_idx)
        self.requests[rid] = req
        self.sched.add(req)
        self.obs.request_enqueued(rid, n_prompt=len(prompt))
        return rid

    def step(self) -> list:
        """One scheduled unit of work (a prefill / prefill chunk or a
        decode step). Returns the requests that finished during it."""
        avail = self.kv.num_free + (
            self.prefix.n_evictable if self.prefix is not None else 0
        )
        action = self.sched.plan(avail)
        if action == "idle":
            return []
        self._step_idx += 1
        if action == "prefill":
            done = (self._run_prefill_chunk()
                    if self.ecfg.chunk_len is not None
                    else self._run_prefill())
        else:
            done = self._run_decode()
        self.obs.lanes_state(len(self.sched.waiting), self.sched.num_active,
                             self.kv.num_free)
        return done

    def run(self, max_steps: int = 100_000) -> dict:
        """Drive until every queued request completes. Returns
        {rid: generated token list}."""
        for _ in range(max_steps):
            if not self.sched.has_work:
                break
            self.step()
        if self.sched.has_work:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return {rid: list(r.out) for rid, r in self.requests.items()}

    @property
    def trace(self) -> list:
        """Derived view: the classic (kind, rids, n_tokens) tuple list
        the pipeline model consumes, rebuilt from the typed step events
        (``self.obs.steps``)."""
        return self.obs.legacy_trace()

    def trace_report(self) -> pipe_mod.TraceReport:
        """Map the recorded schedule onto the FWS pipeline model."""
        return pipe_mod.simulate_trace(
            self.obs.steps, self.cfg.d_model, self.ecfg.lanes
        )

    @property
    def slot_utilization(self) -> float:
        """Mean fraction of decode lanes doing live work (vs parked)."""
        decodes = [len(e.rids) for e in self.obs.steps
                   if e.kind == "decode"]
        if not decodes:
            return 1.0
        return sum(decodes) / (self.ecfg.lanes * len(decodes))

    def prefix_stats(self) -> dict:
        return self.prefix.stats() if self.prefix is not None else {}

    # ----------------------------------------------------------- internals

    def _alloc_slot(self) -> int:
        """A page slot for an admission, evicting LRU prefix-cache pages
        if the free list is dry. Raises :class:`PoolExhausted` when the
        scheduler mis-planned (every slot referenced by a live request)
        — never feeds a non-slot into the jitted step."""
        while True:
            slot = self.kv.allocator.try_alloc()
            if slot is not None:
                return slot
            if self.prefix is None or not self.prefix.evict_lru():
                raise PoolExhausted(
                    "no free KV page slots and no evictable cached pages "
                    f"(num_slots={self.ecfg.num_slots})"
                )

    def _admit_chunked(self) -> Request:
        """Admission for the chunked path: prefix-cache lookup, slot
        allocation (with LRU eviction), and the page clone/reset."""
        nxt = self.sched.waiting[0]
        hit = (self.prefix.match(nxt.prompt, self.kv)
               if self.prefix is not None else None)
        if hit is not None:
            # pin the donor page: allocation below may need an LRU
            # eviction, which must not pick the page we are cloning from
            self.kv.allocator.retain(hit.slot)
        try:
            slot = self._alloc_slot()
        except PoolExhausted:
            if hit is None:
                raise
            # the donor was the only evictable page — give it up for the
            # admission itself; the hit degrades to a miss
            self.kv.allocator.release(hit.slot)
            hit = None
            slot = self._alloc_slot()
        req = self.sched.begin_prefill(slot, self._step_idx)
        self.obs.request_admitted(req.rid)
        src = hit.slot if hit is not None else slot
        n = hit.n_tokens if hit is not None else 0
        # always clone: n=0 zeroes the (possibly reused, stale) page so
        # the quantized-resident mirror invariant survives; n>0 is the
        # prefix copy-on-write
        self.kv.pool = self._clone(
            self.kv.pool, jnp.int32(src), jnp.int32(slot), jnp.int32(n)
        )
        if hit is not None:
            req.prefilled = req.prefix_hit = hit.n_tokens
            self.kv.allocator.release(hit.slot)
        return req

    def _run_prefill(self) -> list:
        t0 = self.obs.clock()
        slot = self._alloc_slot()
        req = self.sched.admit(slot, self._step_idx)
        self.obs.request_admitted(req.rid)
        n = len(req.prompt)
        p = self.ecfg.prefill_len
        ids = np.zeros((1, p), np.int32)
        ids[0, :n] = req.prompt
        positions = np.full((1, p), attn_mod.KV_PAD, np.int32)
        positions[0, :n] = np.arange(n)
        tok, self.kv.pool = self._prefill(
            self.params, self.kv.pool, jnp.asarray(ids),
            jnp.asarray(positions), jnp.asarray([slot], jnp.int32),
            jnp.int32(n - 1),
        )
        req.out.append(int(tok))  # device sync: the step is complete here
        t1 = self.obs.clock()
        # bill the executed width: the fixed-shape step pushes all
        # prefill_len positions through the pipeline, padding included.
        # The request span keeps the real prompt length (request_enqueued)
        # for TTFT/queue accounting.
        self.obs.step_recorded("prefill", (req.rid,), p, t0, t1)
        self.obs.token_emitted(req.rid, t1)  # prefill emits the first token
        return self._retire([req])

    def _run_prefill_chunk(self) -> list:
        t0 = self.obs.clock()
        L = self.ecfg.chunk_len
        req = self.sched.prefilling
        if req is None:
            req = self._admit_chunked()
        offs = req.prefilled
        take = min(L, len(req.prompt) - offs)
        ids = np.zeros((1, L), np.int32)
        ids[0, :take] = req.prompt[offs:offs + take]
        positions = np.full((1, L), attn_mod.KV_PAD, np.int32)
        positions[0, :take] = np.arange(offs, offs + take)
        tok, self.kv.pool = self._chunk(
            self.params, self.kv.pool, jnp.asarray([req.slot], jnp.int32),
            jnp.asarray(ids), jnp.asarray(positions), jnp.int32(offs),
            jnp.int32(take - 1),
        )
        tok = int(tok)  # device sync: the step is complete here
        t1 = self.obs.clock()
        self.obs.step_recorded("prefill", (req.rid,), L, t0, t1)
        req.prefilled = offs + take
        if req.prefilled < len(req.prompt):
            return []
        # prompt fully resident: the last chunk's logits are the first
        # generated token, and the finished prefix becomes donatable
        self.sched.finish_prefill(req)
        req.out.append(tok)
        self.obs.token_emitted(req.rid, t1)
        if self.prefix is not None:
            self.prefix.insert(req.prompt, req.slot, self.kv)
        return self._retire([req])

    def _run_decode(self) -> list:
        t0 = self.obs.clock()
        ecfg = self.ecfg
        rows = np.asarray(
            [self.kv.scratch_row(i) for i in range(ecfg.lanes)], np.int32
        )
        ids = np.zeros((ecfg.lanes, 1), np.int32)
        pos = np.zeros((ecfg.lanes,), np.int32)
        active = sorted(self.sched.running.items())
        for lane, req in active:
            rows[lane] = req.slot
            ids[lane, 0] = req.out[-1]
            pos[lane] = req.pos
        next_ids, self.kv.pool = self._decode(
            self.params, self.kv.pool, jnp.asarray(rows), jnp.asarray(ids),
            jnp.asarray(pos),
        )
        next_ids = np.asarray(next_ids)  # device sync
        t1 = self.obs.clock()
        for lane, req in active:
            req.out.append(int(next_ids[lane]))
            req.pos += 1
            self.obs.token_emitted(req.rid, t1)
        self.obs.step_recorded(
            "decode", tuple(r.rid for _, r in active), len(active), t0, t1,
            lanes=ecfg.lanes,
        )
        return self._retire([r for _, r in active])

    def _retire(self, reqs) -> list:
        done = []
        for req in reqs:
            reason = Scheduler.stop_reason(req, self.ecfg.page_len)
            if reason is not None:
                self.sched.finish(req, self._step_idx)
                # drop the engine's reference; the prefix cache may still
                # hold its own (insert at prefill-complete), keeping the
                # page warm for future shared-prefix admissions
                self.kv.allocator.free(req.slot)
                self.obs.request_finished(req.rid, reason)
                done.append(req)
        return done
