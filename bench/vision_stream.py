"""The vision cells: ``VisionEngine.stream`` as a closed-loop single
stream, and the check of every label it returned against the plain
reference.

Set-up makes the weights on the device in one jitted call from the seed,
builds the engine and warms its step; the frames are a seeded pool on the
device, handed to ``stream`` in chunks until the window closes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np


def vit_config(name: str, m: dict):
    from repro.models.vit import ViTConfig

    return ViTConfig(
        name=name, image_size=m["image_size"], patch_size=m["patch_size"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], d_ff=m["intermediate_size"],
        n_classes=m["num_classes"], in_channels=m["num_channels"],
        ffn_kind="gelu", norm="layernorm", use_bias=True)


def served_params(ref, m: dict, seed: int, min_n: int):
    """The served tree (packed MXFP4 linears, bf16 rest) in one jitted
    call, from the reference's seeded float weights."""
    import jax
    import jax.numpy as jnp

    from repro.layers.common import convert_params_mxfp4

    d = m["hidden_size"]

    def layer(key, i):
        w = ref.layer_weights(key, m, i)
        ln = lambda: {"gamma": jnp.ones((d,)), "beta": jnp.zeros((d,))}  # noqa
        lin = lambda n: {"w": w["w" + n], "b": w["b" + n]}  # noqa: E731
        tree = {"attn": {"ln": ln(), **{f"w{n}": lin(n) for n in "qkvo"}},
                "ffn": {"ln": ln(), "w1": lin("1"), "w2": lin("2")}}
        return convert_params_mxfp4(tree, min_n=min_n)

    def build(key):
        o = ref.outer_weights(key, m)
        layers = jax.lax.map(lambda i: layer(key, i),
                             jnp.arange(m["num_hidden_layers"]))
        rest = convert_params_mxfp4({
            "patch": {"w": o["patch_w"], "b": o["patch_b"]},
            "cls": o["cls"], "pos": o["pos"],
            "final_ln": {"gamma": jnp.ones((d,)), "beta": jnp.zeros((d,))},
            "head": {"w": o["head_w"], "b": o["head_b"]},
        }, min_n=min_n)
        # biases stay float32 in the converted tree; serve them as bf16
        for k in ("patch", "head"):
            rest[k]["b"] = rest[k]["b"].astype(jnp.bfloat16)
        return {**rest, "segments": [layers]}

    return jax.jit(build)(ref.base_key(seed))


@dataclasses.dataclass
class System:
    engine: object
    files: dict
    seed: int
    phases: dict
    chunks: list  # per chunk, a list of [H, W, C] device frames
    pool: object  # [n, H, W, C] device frames


def build(files: dict, seed: int, seconds: float = 0.0) -> System:
    import jax

    from repro.layers.common import RunCtx, ShardingCtx
    from repro.serving.vision import VisionEngine

    cfg, cell = files["config"], files["cell"]
    m, ref = cfg["model"], files["ref"]
    phases = {}
    t = time.perf_counter()
    params = served_params(ref, m, seed, cfg.get("min_n", 256))
    pool = ref.frames(seed, m, files["traffic"]["pool"])
    jax.block_until_ready((params, pool))
    phases["weights_s"] = time.perf_counter() - t
    ctx = RunCtx(shd=ShardingCtx(), quant="mxfp4_wonly", dense_attn_max=256)
    eng = VisionEngine(params, vit_config(cfg["name"], m), ctx)
    c = files["traffic"]["chunk"]
    frames = [pool[i] for i in range(pool.shape[0])]
    chunks = [frames[i:i + c] for i in range(0, len(frames), c)]
    t = time.perf_counter()
    eng.stream(chunks[0][:1])
    phases["warmup_s"] = time.perf_counter() - t
    return System(eng, files, seed, phases, chunks, pool)


def window(system: System, seconds: float, annotate=None) -> dict:
    """Closed loop: stream chunks of the frame pool back to back until the
    window closes; a chunk started inside the window is finished and
    counted."""
    eng = system.engine
    span = annotate or (lambda name: contextlib.nullcontext())
    clock = time.perf_counter
    t0 = clock()
    end = t0 + seconds
    done, labels, k = [], [], 0
    with span("window"):
        while clock() < end:
            chunk = system.chunks[k % len(system.chunks)]
            with span("stream"):
                out = eng.stream(chunk)
            done.append((clock(), len(out)))
            labels.append((k % len(system.chunks), out))
            k += 1
    n = sum(c[1] for c in done)
    return {"t0": t0, "end": end, "t_last": clock(), "chunks": done,
            "labels": labels, "seconds": seconds, "attempted": n,
            "failed": 0}


def program_records(system: System, win: dict) -> dict:
    eng = system.engine
    steps = [(e.kind, e.n_tokens, len(e.rids), e.t_start, e.t_end)
             for e in eng.obs.steps if e.t_start >= win["t0"]]
    return {"steps": steps}


def outputs(system: System, win: dict) -> dict:
    return {"labels": win["labels"], "pool": system.pool}


def free(system: System) -> None:
    eng = system.engine
    eng.params = None
    eng._chain = []
    system.chunks = []
    system.engine = None
    gc.collect()


def gaps(ref, seed: int, m: dict, pool, labels: list, chunk: int,
         control: bool = False, z=None) -> np.ndarray:
    """Per returned label: how far its reference logit lies below the
    reference's best, in that frame's logit standard deviations. With
    ``control`` the label is what the float8 reference puts first."""
    if z is None:
        z = ref.logits(seed, m, pool)
    if control:
        zc = ref.logits(seed, m, pool, control=True)
        labels = [(0, list(zc.argmax(-1)))]
        chunk = len(zc)
    out = []
    for ci, got in labels:
        idx = ci * chunk + np.arange(len(got))
        zz = z[idx]
        pick = np.take_along_axis(zz, np.asarray(got)[:, None], -1)[:, 0]
        out.append((zz.max(-1) - pick) / zz.std(-1))
    return np.concatenate(out) if out else np.zeros(0)


def output_gaps(system: System, out: dict, control: bool = False):
    files = system.files
    return gaps(files["ref"], system.seed, files["config"]["model"],
                out["pool"], out["labels"], files["traffic"]["chunk"],
                control)


def check(system: System, out: dict, win: dict) -> dict:
    files = system.files
    limit = files["cell"]["check"]["gap_limit"]
    g = output_gaps(system, out)
    widest = float(g.max()) if len(g) else None
    least = files["cell"]["check"]["min_frames"]
    return {
        "label_logit_gap": {"value": widest, "limit": limit,
                            "ok": widest is not None and widest <= limit},
        "labels_compared": {"value": int(len(g)), "limit": least,
                            "ok": len(g) >= least},
    }
