"""Sharding-rule resolution logic (single-device mesh: pure logic tests;
the 512-device behaviour is exercised by the dry-run sweep)."""

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.distributed import sharding as shd
from repro.launch.mesh import make_local_mesh
from repro.layers.common import ShardingCtx


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


def test_rules_head_shardability(mesh):
    r = shd.make_rules(C.ARCHS["nemotron-4-15b"], mesh, "train")
    assert r["heads"] == "model"  # 48 % 1 == 0 trivially
    r2 = shd.make_rules(C.ARCHS["starcoder2-7b"], mesh, "train")
    assert r2["qkv_fused"] == "model"


def test_decode_rules_flash_decoding(mesh):
    r = shd.make_rules(C.ARCHS["starcoder2-7b"], mesh, "decode",
                       batch_size=128)
    assert r["cache_seq"] == "model"
    assert r["heads"] is None
    r1 = shd.make_rules(C.ARCHS["zamba2-1.2b"], mesh, "decode", batch_size=1)
    assert r1["cache_seq"] == ("data", "model")  # small batch frees `data`


def test_moe_shard_modes(mesh):
    r = shd.make_rules(C.ARCHS["qwen3-moe-235b-a22b"], mesh, "train")
    assert r["experts"] == "model" and r["expert_mlp"] is None  # EP
    r2 = shd.make_rules(C.ARCHS["mixtral-8x22b"], mesh, "train")
    assert r2["experts"] is None and r2["expert_mlp"] == "model"  # TP


def test_resolve_no_axis_reuse(mesh):
    ctx = ShardingCtx(mesh=mesh, rules={"a": "model", "b": "model"})
    spec = ctx.resolve(("a", "b"))
    assert spec[0] == "model" and spec[1] is None  # second use dropped


def test_divisibility_drops_axis(mesh):
    big = make_local_mesh()
    ctx = ShardingCtx(mesh=big, rules={"batch": ("data",), "x": "model"})
    out = shd.resolve_with_divisibility(
        ("batch", "x"), jax.ShapeDtypeStruct((1, 7), jnp.float32), ctx, big
    )
    # both dims divisible by 1 -> kept; logic exercised at 16x16 in dryrun
    assert out.spec[0] in (("data",), "data")


def test_fsdp_param_rules(mesh):
    from repro.launch.steps import param_rules

    r = shd.make_rules(C.ARCHS["h2o-danube-1.8b"], mesh, "train")
    pr = param_rules(r, mesh, fsdp=True)
    assert pr["embed"] == ("data",)
    assert r["embed"] is None  # activation rules untouched


def test_opt_state_zero_specs():
    specs = {"w": ("embed", "mlp"), "g": ("embed",)}
    m = make_local_mesh()
    z = shd.opt_state_specs(specs, None, m, zero1=True)
    assert z["w"] == ("zero", "mlp")


def test_skipped_cells_match_design():
    sk = {(a, s) for a, s, _ in C.skipped_cells()}
    assert ("hubert-xlarge", "decode_32k") in sk
    assert ("hubert-xlarge", "long_500k") in sk
    for a in ("starcoder2-7b", "nemotron-4-15b", "qwen3-moe-235b-a22b",
              "qwen2-vl-7b"):
        assert (a, "long_500k") in sk
    assert len(C.all_cells()) == 34
    assert len(sk) == 6


# ----------------------------------------------------- stage partitioning

def test_stage_partition_default_equal_split_unchanged():
    # vit-l32 / bert-large: 24 blocks, 2 chips -> the paper's 12+12 split
    assert shd.stage_partition(24, 2) == [(0, 12), (12, 24)]
    assert shd.stage_partition(7, 3) == [(0, 3), (3, 5), (5, 7)]


def test_stage_partition_balanced_uniform_matches_equal():
    # uniform costs: cost-balancing reduces to the equal split
    assert shd.stage_partition(
        24, 2, mode="balanced", costs=[1.0] * 24
    ) == [(0, 12), (12, 24)]
    # no costs given: balanced falls back to the equal split
    assert shd.stage_partition(24, 2, mode="balanced") == [(0, 12), (12, 24)]


def test_stage_partition_balanced_unequal_counts():
    # one expensive layer pulls the cut: stage 0 takes fewer layers
    costs = [10.0, 1.0, 1.0, 1.0]
    bounds = shd.stage_partition(4, 2, mode="balanced", costs=costs)
    assert bounds == [(0, 1), (1, 4)]
    lens = [hi - lo for lo, hi in bounds]
    assert len(set(lens)) > 1  # genuinely unequal layer counts
    # bottleneck is optimal: no contiguous 2-split beats max(10, 3)
    assert max(sum(costs[lo:hi]) for lo, hi in bounds) == 10.0


def test_stage_partition_balanced_from_blockwise_costs():
    from repro.distributed import blockwise

    cfg = C.ARCHS["starcoder2-7b"]
    costs = blockwise.serve_layer_costs(cfg, 512)
    assert len(costs) == cfg.n_layers
    assert all(c > 0 for c in costs)
    # homogeneous dense trunk: balanced cuts == equal cuts
    assert shd.stage_partition(
        cfg.n_layers, 4, mode="balanced", costs=costs
    ) == shd.stage_partition(cfg.n_layers, 4)


def test_stage_partition_validation():
    with pytest.raises(ValueError):
        shd.stage_partition(4, 5)
    with pytest.raises(ValueError):
        shd.stage_partition(4, 2, mode="weird")
    with pytest.raises(ValueError):
        shd.stage_partition(4, 2, mode="balanced", costs=[1.0, 2.0])
