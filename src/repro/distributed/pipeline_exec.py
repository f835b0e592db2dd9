"""Real multi-device FWS pipeline execution (shard_map stage parallelism).

``serving/pipeline.py`` *models* the paper's §5.3 twelve-stage fully-
weight-stationary pipeline as discrete events; this module makes the
dataflow real on a jax device mesh:

- ``stage_partition`` maps contiguous layer ranges onto a ``stage`` mesh
  axis; each stage's (possibly CIM-converted) trunk weights are placed
  **once** with ``jax.device_put(..., NamedSharding(mesh, P("stage")))``
  and never move again — the FWS premise. A transfer guard
  (:meth:`StagePipeline.collectives`) proves it from the compiled HLO:
  the steady-state step contains only ``collective-permute`` ops whose
  wire traffic is activation-sized.
- Activations stream stage-to-stage with ``jax.lax.ppermute`` over a
  rotating GPipe-style microbatch schedule: one jitted ``shard_map`` body
  unrolls the ``T = n_microbatches + n_stages - 1`` fill/steady/drain
  steps, so at steady state all stages compute concurrently on
  consecutive microbatches.
- A leading ``replica`` mesh axis runs data-parallel pipeline replicas
  (microbatch groups block-partitioned over replicas inside the same
  step); :class:`ReplicaRouter` is the trivial round-robin front door.

Stage cuts come from ``sharding.stage_partition`` — equal layer counts by
default, or cost-balanced (``mode="balanced"``) from
``blockwise.serve_layer_costs``. Unequal cuts pad every stage's slice to
the longest stage (repeating the last layer's params) and mask the padded
scan steps out with the per-stage layer count; the equal-cut path skips
the mask entirely so it stays op-for-op identical to the single-device
``lm._run_segment`` scan (bitwise parity, see tests/test_pipeline_exec.py).

Only single-homogeneous-attention-segment models (dense LMs, ViTs) are
supported: heterogeneous segment chains (local/global runs, hybrid SSM)
have per-segment block signatures that cannot share one scanned stage
body. Everything else — float / packed-MXFP4 / CIM-converted trees —
works unchanged because every stacked leaf (weights, codes, exps,
per-layer calib) carries the layer axis first.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.layers import rope as ropelib
from repro.layers.common import RunCtx, ShardingCtx, norm_apply
from repro.models import lm

__all__ = [
    "StagePipeline",
    "ReplicaRouter",
    "MeasuredReport",
    "make_pipeline_mesh",
    "build_lm_pipeline",
    "build_vit_pipeline",
]


def make_pipeline_mesh(stages: int, replicas: int = 1) -> Mesh:
    """(replica, stage) mesh over the first ``replicas * stages`` devices.

    On CPU-only machines force a multi-device platform first, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = stages * replicas
    have = jax.device_count()
    if n > have:
        raise ValueError(
            f"pipeline mesh needs {replicas}x{stages} = {n} devices, have "
            f"{have} (hint: XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n} on CPU)"
        )
    devs = np.array(jax.devices()[:n]).reshape(replicas, stages)
    return Mesh(devs, ("replica", "stage"))


def _local_ctx(ctx: RunCtx) -> RunCtx:
    """The stage body runs *inside* shard_map: per-device execution with no
    further mesh to constrain against, so drop any sharding rules."""
    if ctx.shd.mesh is None:
        return ctx
    return dataclasses.replace(ctx, shd=ShardingCtx())


def _make_stage_fn(cfg, ctx: RunCtx, seg: lm.Segment, masked: bool):
    """One pipeline stage: scan the local layer slice over the microbatch.

    Mirrors ``lm._run_segment`` exactly on the equal-cut path (hoisted RoPE
    tables, same scan body, same remat wrapper) so the pipelined forward
    stays bitwise-comparable to the single-device one; ``masked`` adds the
    padded-layer passthrough for unequal (cost-balanced) cuts.
    """
    sctx = _local_ctx(ctx)
    remat = bool(getattr(cfg, "remat", False))

    def stage_fn(p_stack, n_local, x):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        rope_tables = None
        if seg.attn is not None and seg.attn.use_rope and not seg.attn.mrope:
            rope_tables = ropelib.rope_tables(
                positions, seg.attn.head_dim, seg.attn.rope_theta
            )

        def body(carry, xs):
            if masked:
                j, pl = xs
            else:
                pl = xs
            y, _ = lm._block_apply(sctx, cfg, seg, pl, carry, positions,
                                   None, None, None, None, rope_tables)
            if masked:
                y = jnp.where(j < n_local, y, carry)
            return y, None

        if remat:
            body = jax.checkpoint(body)
        max_l = jax.tree.leaves(p_stack)[0].shape[0]
        xs = (jnp.arange(max_l), p_stack) if masked else p_stack
        x, _ = jax.lax.scan(body, x, xs)
        return x

    return stage_fn


def _stack_stages(trunk, bounds):
    """Layer-stacked trunk [L, ...] -> per-stage stack [S, max_L, ...].

    Stages shorter than the longest one are padded by repeating their last
    layer's params (the padded scan steps are masked out in the stage
    body), so every leaf keeps one uniform shape shardable as P("stage").
    """
    max_l = max(hi - lo for lo, hi in bounds)

    def leaf(a):
        slabs = []
        for lo, hi in bounds:
            s = a[lo:hi]
            if hi - lo < max_l:
                pad = jnp.repeat(a[hi - 1:hi], max_l - (hi - lo), axis=0)
                s = jnp.concatenate([s, pad], axis=0)
            slabs.append(s)
        return jnp.stack(slabs)

    return jax.tree.map(leaf, trunk), max_l


def _pad_rows(tree, cap: int):
    """Pad every leaf's leading (batch) axis to ``cap`` rows by repeating
    the last row — the ragged-final-microbatch filler."""

    def f(a):
        n = a.shape[0]
        if n == cap:
            return a
        return jnp.concatenate(
            [a, jnp.repeat(a[-1:], cap - n, axis=0)], axis=0
        )

    return jax.tree.map(f, tree)


@dataclasses.dataclass(frozen=True)
class MeasuredReport:
    """Pipeline health measured from real multi-device runs (the measured
    counterpart of the simulated ``serving.pipeline.PipelineReport``)."""

    name: str
    n_stages: int
    n_replicas: int
    microbatches: int  # per replica
    mb_size: int
    step_wall_s: float  # one full fill+steady+drain step (min over reps)
    stage_walls_s: tuple  # one microbatch through each stage, isolated
    throughput_items_per_s: float  # rows per step wall (fill included)
    steady_items_per_s: float  # drain rate implied by the bottleneck stage
    bubble_fraction: float  # mean stage idle fraction over the step wall
    fill_latency_s: float  # first microbatch through all stages (estimate)

    @property
    def stage_occupancy(self) -> tuple:
        """Busy fraction of each stage over the step wall."""
        if not self.step_wall_s:
            return tuple(0.0 for _ in self.stage_walls_s)
        return tuple(
            min(1.0, self.microbatches * w / self.step_wall_s)
            for w in self.stage_walls_s
        )

    def publish(self, registry, prefix: str = "pipeline_measured") -> None:
        """Export measured gauges next to the simulated ``pipeline_*``
        family so ``scripts/metrics_summary.py`` renders both."""
        g = registry.gauge
        for i, (w, occ) in enumerate(
            zip(self.stage_walls_s, self.stage_occupancy)
        ):
            g(f"{prefix}_stage_wall_seconds",
              "one microbatch through this stage (measured, isolated)",
              labels={"stage": str(i)}).set(w)
            g(f"{prefix}_stage_occupancy",
              "measured busy fraction of this stage over the step wall",
              labels={"stage": str(i)}).set(occ)
        g(f"{prefix}_bubble_fraction",
          "measured mean stage idle fraction over the step wall").set(
            self.bubble_fraction)
        g(f"{prefix}_fill_latency_seconds",
          "measured first-microbatch traversal of the stage chain").set(
            self.fill_latency_s)
        g(f"{prefix}_step_wall_seconds",
          "one fill+steady+drain pipeline step").set(self.step_wall_s)
        g(f"{prefix}_throughput_items_per_s",
          "rows per step wall, fill included").set(
            self.throughput_items_per_s)
        g(f"{prefix}_steady_state_fps",
          "drain rate implied by the measured bottleneck stage").set(
            self.steady_items_per_s)
        g(f"{prefix}_stages", "pipeline depth").set(float(self.n_stages))
        g(f"{prefix}_replicas", "data-parallel pipeline replicas").set(
            float(self.n_replicas))


class StagePipeline:
    """Stage-parallel executor: resident per-stage weights, overlapping
    microbatches, one jitted shard_map step.

    Built via :func:`build_lm_pipeline` / :func:`build_vit_pipeline`. The
    embed front and the final-norm/head back run outside the trunk step,
    replicated on every device: the trunk step's HLO then contains *only*
    the stage-to-stage ``collective-permute`` — the transfer guard that
    pins the weights-never-move invariant.
    """

    def __init__(self, *, mesh: Mesh, bounds, trunk, front, back,
                 embed_fn: Callable, stage_fn: Callable, head_fn: Callable,
                 microbatches: int, mb_size: int, name: str = "model"):
        if set(mesh.axis_names) != {"replica", "stage"}:
            raise ValueError(f"need a (replica, stage) mesh, got "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.bounds = list(bounds)
        self.name = name
        self.n_stages = mesh.shape["stage"]
        self.n_replicas = mesh.shape["replica"]
        if len(self.bounds) != self.n_stages:
            raise ValueError(
                f"{len(self.bounds)} stage cuts for a {self.n_stages}-stage "
                f"mesh"
            )
        self.microbatches = int(microbatches)
        self.mb_size = int(mb_size)
        if self.microbatches < 1 or self.mb_size < 1:
            raise ValueError("need microbatches >= 1 and mb_size >= 1")
        self.lengths = [hi - lo for lo, hi in self.bounds]

        stacked, self.max_layers = _stack_stages(trunk, self.bounds)
        stage_sh = NamedSharding(mesh, P("stage"))
        rep_sh = NamedSharding(mesh, P())
        # resident placement: done once, never repeated (FWS premise)
        self.trunk = jax.device_put(stacked, stage_sh)
        self.n_locals = jax.device_put(
            jnp.asarray(self.lengths, jnp.int32), stage_sh
        )
        self.front = jax.device_put(front, rep_sh)
        self.back = jax.device_put(back, rep_sh)

        S_ = self.n_stages
        M = self.microbatches
        T = M + S_ - 1
        perm = [(i, (i + 1) % S_) for i in range(S_)]

        def body(tr, nl, xg):
            # tr: this stage's params [1, max_L, ...]; nl: [1] local layer
            # count; xg: this replica's microbatches [M, mb, s, d]
            tr = jax.tree.map(lambda a: a[0], tr)
            n_local = nl[0]
            sidx = jax.lax.axis_index("stage")
            carry = jnp.zeros_like(xg[0])
            out = jnp.zeros_like(xg)
            for t in range(T):  # unrolled GPipe fill/steady/drain schedule
                x = jnp.where(sidx == 0, xg[min(t, M - 1)], carry)
                y = stage_fn(tr, n_local, x)
                o = t - (S_ - 1)
                if o >= 0:
                    out = out.at[o].set(
                        jnp.where(sidx == S_ - 1, y, out[o])
                    )
                if S_ > 1 and t < T - 1:
                    carry = jax.lax.ppermute(y, "stage", perm)
            return out[None]

        self._step = jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(P("stage"), P("stage"), P("replica")),
                out_specs=P("stage", "replica"),
                check_rep=False,
            )
        )
        # front and back compute redundantly on every device, each in its
        # own shard_map: XLA cannot partition a Pallas kernel (a cim or
        # mxfp4 linear) over the mesh the replicated params live on
        def replicated(fn):
            return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                                     out_specs=P(), check_rep=False))

        self._embed = replicated(embed_fn)
        self._head = replicated(head_fn)
        self._stage_fn = stage_fn
        self._last_report: MeasuredReport | None = None

    # --------------------------------------------------------- execution

    @property
    def capacity(self) -> int:
        """Rows one step processes: replicas x microbatches x mb_size."""
        return self.n_replicas * self.microbatches * self.mb_size

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def trunk_bytes(self) -> int:
        return sum(a.nbytes for a in jax.tree.leaves(self.trunk))

    def forward_raw(self, batch: dict):
        """Exactly ``capacity`` rows -> outputs for every row."""
        x = self._embed(self.front, batch)
        g = self.n_replicas * self.microbatches
        buf = self._step(
            self.trunk, self.n_locals,
            x.reshape(g, self.mb_size, *x.shape[1:]),
        )
        y = buf[-1]  # last stage's drain buffer holds the results
        y = y.reshape(self.capacity, *y.shape[2:])
        return self._head(self.back, y)

    def forward(self, batch: dict):
        """Any 1..capacity rows: pads the ragged final microbatch (row
        repeats), runs one pipeline step, slices the real rows back out."""
        n = jax.tree.leaves(batch)[0].shape[0]
        if not 1 <= n <= self.capacity:
            raise ValueError(f"batch of {n} rows exceeds pipeline capacity "
                             f"{self.capacity}")
        out = self.forward_raw(_pad_rows(batch, self.capacity))
        return out[:n]

    def timed_forward(self, batch: dict):
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.forward(batch))
        wall = time.perf_counter() - t0
        return out, wall

    # ------------------------------------------------------ transfer guard

    def step_hlo(self, batch: dict) -> str:
        """Compiled HLO of the steady-state trunk step (weights resident —
        everything crossing devices shows up here as a collective)."""
        x = self._embed(self.front, _pad_rows(batch, self.capacity))
        g = self.n_replicas * self.microbatches
        lowered = self._step.lower(
            self.trunk, self.n_locals,
            x.reshape(g, self.mb_size, *x.shape[1:]),
        )
        return lowered.compile().as_text()

    def collectives(self, batch: dict):
        """CollectiveStats of the trunk step. The FWS invariant: only
        ``collective-permute`` (the activation hop) may appear, and its
        wire traffic is activation-sized — far below the trunk bytes."""
        from repro.distributed import roofline as rl

        return rl.parse_collectives(self.step_hlo(batch), self.n_devices)

    def trunk_resident(self) -> bool:
        """Every trunk leaf is sharded over the stage axis, and stage
        ``s``'s slice sits only on the devices of mesh column ``s`` (placed
        once at construction; nothing below ever re-places it)."""
        def ok(a):
            spec = a.sharding.spec
            if not (len(spec) > 0 and spec[0] == "stage"):
                return False
            for sh in a.addressable_shards:
                s = sh.index[0].start or 0
                if (sh.data.shape[0] != 1
                        or sh.device not in self.mesh.devices[:, s]):
                    return False
            return True

        return all(ok(a) for a in jax.tree.leaves(self.trunk))

    # -------------------------------------------------------- measurement

    def measure_stage_walls(self, batch: dict, reps: int = 3) -> list[float]:
        """Wall time of one microbatch through each stage in isolation,
        chaining each stage's true input activations (measurement-only
        host copies; the resident placement is untouched)."""
        x = self._embed(self.front, _pad_rows(batch, self.capacity))
        x = jax.device_get(x[: self.mb_size])
        walls = []
        for i in range(self.n_stages):
            p_i = jax.tree.map(lambda a: jax.device_get(a[i]), self.trunk)
            n_i = jnp.int32(self.lengths[i])
            fn = jax.jit(lambda p, xx, n=n_i: self._stage_fn(p, n, xx))
            y = jax.block_until_ready(fn(p_i, x))  # compile + warm
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                y = jax.block_until_ready(fn(p_i, x))
                best = min(best, time.perf_counter() - t0)
            walls.append(best)
            x = y
        return walls

    def measure_step_wall(self, batch: dict, reps: int = 3) -> float:
        """Min wall of the trunk shard_map step alone (embed/head and the
        host-side pad/slice excluded) — exactly the T-step GPipe schedule
        the ``serving.pipeline`` discrete-event model predicts, so this is
        the measured side of the cross-validation in
        ``benchmarks/run.py::pipeline_multidevice``."""
        x = self._embed(self.front, _pad_rows(batch, self.capacity))
        g = self.n_replicas * self.microbatches
        xg = x.reshape(g, self.mb_size, *x.shape[1:])
        jax.block_until_ready(self._step(self.trunk, self.n_locals, xg))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(self._step(self.trunk, self.n_locals, xg))
            best = min(best, time.perf_counter() - t0)
        return best

    def measure(self, batch: dict, reps: int = 3) -> MeasuredReport:
        """Measured pipeline health for one representative batch: full-step
        wall (min over ``reps``), isolated per-stage walls, and the
        occupancy / bubble / fill figures they imply."""
        self.forward(batch)  # compile + warm
        best = float("inf")
        for _ in range(reps):
            _, wall = self.timed_forward(batch)
            best = min(best, wall)
        stage_walls = self.measure_stage_walls(batch, reps=reps)
        t_stage = max(stage_walls)
        m = self.microbatches
        occ = [min(1.0, m * w / best) for w in stage_walls] if best else []
        bubble = max(0.0, 1.0 - sum(occ) / len(occ)) if occ else 0.0
        fill = sum(stage_walls)
        steady = (
            self.n_replicas * self.mb_size / t_stage if t_stage else 0.0
        )
        rep = MeasuredReport(
            name=self.name,
            n_stages=self.n_stages,
            n_replicas=self.n_replicas,
            microbatches=m,
            mb_size=self.mb_size,
            step_wall_s=best,
            stage_walls_s=tuple(stage_walls),
            throughput_items_per_s=self.capacity / best if best else 0.0,
            steady_items_per_s=steady,
            bubble_fraction=bubble,
            fill_latency_s=fill,
        )
        self._last_report = rep
        return rep

    def publish(self, registry, prefix: str = "pipeline_measured") -> None:
        if self._last_report is None:
            raise ValueError("call measure() before publish()")
        self._last_report.publish(registry, prefix=prefix)


class ReplicaRouter:
    """Trivial round-robin front door over the pipeline's data-parallel
    replicas: each submitted batch claims the next replica slot (at most
    ``microbatches * mb_size`` rows); ``flush`` packs full replica groups
    into single pipeline steps and returns per-ticket outputs."""

    def __init__(self, runner: StagePipeline):
        self.runner = runner
        self._pending: list = []  # (ticket, batch, n_rows)
        self._next_ticket = 0
        self.dispatched = [0] * runner.n_replicas  # batches per replica

    @property
    def slot_rows(self) -> int:
        return self.runner.microbatches * self.runner.mb_size

    def submit(self, batch: dict) -> int:
        n = jax.tree.leaves(batch)[0].shape[0]
        if not 1 <= n <= self.slot_rows:
            raise ValueError(
                f"batch of {n} rows exceeds replica slot ({self.slot_rows})"
            )
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, batch, n))
        return ticket

    def flush(self) -> dict:
        """Run all pending batches; returns {ticket: output rows}."""
        out: dict = {}
        r = self.runner.n_replicas
        pending, self._pending = self._pending, []
        for g0 in range(0, len(pending), r):
            group = pending[g0:g0 + r]
            slots = [
                _pad_rows(b, self.slot_rows) for _, b, _ in group
            ]
            while len(slots) < r:  # idle replicas replay slot 0
                slots.append(slots[0])
            packed = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *slots
            )
            ys = self.runner.forward_raw(packed)
            for si, (ticket, _, n) in enumerate(group):
                out[ticket] = ys[si * self.slot_rows:si * self.slot_rows + n]
                self.dispatched[si] += 1
        return out


# ---------------------------------------------------------------- builders

def _resolve_bounds(cfg, stages: int, mode: str, costs, seq_len: int):
    from repro.distributed import blockwise
    from repro.distributed.sharding import stage_partition

    if mode == "balanced" and costs is None:
        costs = blockwise.serve_layer_costs(cfg, seq_len)
    return stage_partition(cfg.n_layers, stages, mode=mode, costs=costs)


def _finish(cfg, ctx, seg, *, mesh, stages, replicas, bounds, trunk, front,
            back, embed_fn, head_fn, microbatches, mb_size, name):
    mesh = mesh or make_pipeline_mesh(stages, replicas)
    masked = len({hi - lo for lo, hi in bounds}) > 1
    stage_fn = _make_stage_fn(cfg, ctx, seg, masked)
    return StagePipeline(
        mesh=mesh, bounds=bounds, trunk=trunk, front=front, back=back,
        embed_fn=embed_fn, stage_fn=stage_fn, head_fn=head_fn,
        microbatches=microbatches, mb_size=mb_size, name=name,
    )


def build_lm_pipeline(params, cfg, ctx: RunCtx, *, stages: int,
                      replicas: int = 1, microbatches: int = 2,
                      mb_size: int = 1, seq_len: int = 512,
                      mesh: Mesh | None = None, mode: str = "equal",
                      costs=None) -> StagePipeline:
    """Stage-parallel pipelined forward for a dense LM (prefill/scoring
    path — the per-token decode step stays on the existing engine).

    Works on float, packed-MXFP4 and CIM-converted param trees alike; the
    ``ctx`` selects the backend exactly as for ``lm.forward``.
    """
    segs = lm.build_segments(cfg)
    if len(segs) != 1 or segs[0].kind != "attn":
        raise NotImplementedError(
            "stage-parallel pipeline needs a single homogeneous attention "
            f"trunk; {cfg.name} has segments "
            f"{[(s.kind, s.n) for s in segs]}"
        )
    seg = segs[0]
    trunk = params["segments"][0]
    if seg.n == 1:
        # n==1 segments store unstacked block params; give them the layer
        # axis every stacked leaf carries
        trunk = jax.tree.map(lambda a: a[None], trunk)
    front = {"embed": params["embed"]}
    back = {"final_ln": params["final_ln"]}
    if cfg.tie_embeddings:
        back["embed"] = params["embed"]
    else:
        back["lm_head"] = params["lm_head"]
    lctx = _local_ctx(ctx)

    def embed_fn(front_p, batch):
        return lm.embed_inputs(lctx, cfg, front_p, batch)

    def head_fn(back_p, x):
        x = norm_apply(cfg.norm, back_p["final_ln"], x)
        return lm._head(lctx, cfg, back_p, x)

    bounds = _resolve_bounds(cfg, stages, mode, costs, seq_len)
    return _finish(
        cfg, ctx, seg, mesh=mesh, stages=stages, replicas=replicas,
        bounds=bounds, trunk=trunk, front=front, back=back,
        embed_fn=embed_fn, head_fn=head_fn, microbatches=microbatches,
        mb_size=mb_size, name=cfg.name,
    )


def build_vit_pipeline(params, cfg, ctx: RunCtx, *, stages: int,
                       replicas: int = 1, microbatches: int = 2,
                       mb_size: int = 1, mesh: Mesh | None = None,
                       mode: str = "equal", costs=None) -> StagePipeline:
    """Stage-parallel pipelined ViT forward: images in, class logits out.

    The executable realization of the paper's multi-chip FWS deployment
    (vit-l32 24 blocks over stages) that ``serving/vision.py`` previously
    only chained sequentially chip-by-chip.
    """
    from repro.models import vit

    seg = vit.build_segments(cfg)[0]
    trunk = params["segments"][0]  # vit trunks are always layer-stacked
    front = {k: params[k] for k in ("patch", "cls", "pos")}
    back = {"final_ln": params["final_ln"], "head": params["head"]}
    lctx = _local_ctx(ctx)

    def embed_fn(front_p, batch):
        return vit.embed_images(lctx, cfg, front_p, batch["images"])

    def head_fn(back_p, x):
        return vit.head(lctx, cfg, back_p, x)

    bounds = _resolve_bounds(cfg, stages, mode, costs, cfg.seq_len)
    return _finish(
        cfg, ctx, seg, mesh=mesh, stages=stages, replicas=replicas,
        bounds=bounds, trunk=trunk, front=front, back=back,
        embed_fn=embed_fn, head_fn=head_fn, microbatches=microbatches,
        mb_size=mb_size, name=cfg.name,
    )
