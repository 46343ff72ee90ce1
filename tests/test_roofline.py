"""Roofline extraction: HLO collective parsing + cost-analysis semantics."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import llm_cost as lc
from repro.launch import roofline as rl


SAMPLE_HLO = """
  %all-gather = f32[16,64]{0,1} all-gather(%copy), channel_id=1, replica_groups=[4,2]<=[8], dimensions={1}
  %ar = bf16[1024]{0} all-reduce(%x), replica_groups=[2,4]<=[8], to_apply=%sum
  %rs = f32[8,8]{1,0} reduce-scatter(%y), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}
  %cp = f32[32]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = f32[4,4]{1,0} all-to-all(%w), replica_groups=[2,4]<=[8], dimensions={0}
  %not_coll = f32[10]{0} add(%a, %b)
"""


def test_collective_parser_kinds_and_bytes():
    out = rl.collective_bytes(SAMPLE_HLO)
    assert out["all-gather"]["count"] == 1
    # result 16*64*4 = 4096B, group 2 -> operand 2048, wire 2048
    assert out["all-gather"]["bytes"] == pytest.approx(2048)
    assert out["all-gather"]["wire_bytes"] == pytest.approx(2048)
    # all-reduce bf16[1024] = 2048B, g=4: wire = 2*2048*3/4 = 3072
    assert out["all-reduce"]["bytes"] == pytest.approx(2048)
    assert out["all-reduce"]["wire_bytes"] == pytest.approx(3072)
    # reduce-scatter f32[64]=256B result, g=8 -> operand 2048, wire 1792
    assert out["reduce-scatter"]["bytes"] == pytest.approx(2048)
    assert out["collective-permute"]["bytes"] == pytest.approx(128)
    assert out["all-to-all"]["count"] == 1
    assert out["total_bytes"] > 0


def test_roofline_terms_bottleneck():
    v5e = dict(device_kind="TPU v5 lite")
    t = rl.roofline_terms(flops=197e12, hbm_bytes=0, coll_bytes=0, **v5e)
    assert t["bottleneck"] == "compute"
    assert t["compute_s"] == pytest.approx(1.0)
    t = rl.roofline_terms(flops=0, hbm_bytes=819e9, coll_bytes=0, **v5e)
    assert t["bottleneck"] == "memory"
    t = rl.roofline_terms(flops=0, hbm_bytes=0, coll_bytes=150e9, **v5e)
    assert t["bottleneck"] == "collective"


def test_roofline_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        rl.roofline_terms(flops=1.0, hbm_bytes=1.0, coll_bytes=0.0,
                          device_kind="cpu")


def test_cost_analysis_is_per_partition():
    """The roofline treats cost_analysis() flops as per-chip: verify that
    partitioning a matmul over k devices divides reported flops by ~k."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh1 = Mesh(np.array(devs[:1]).reshape(1, 1), ("data", "model"))

    def f(x, w):
        return x @ w

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32,
                             sharding=NamedSharding(mesh1, P(None, None)))
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32,
                             sharding=NamedSharding(mesh1, P(None, None)))
    with mesh1:
        c = jax.jit(f).lower(x, w).compile()
    flops1 = rl.cost_analysis(c).get("flops")
    assert flops1 == pytest.approx(2 * 256**3, rel=0.2)


def test_model_flops_counts():
    from repro.configs import get_config, SHAPES
    cfg = get_config("qwen2-72b")
    tot, act = lc.param_counts(cfg)
    assert tot == act
    assert 70e9 < tot < 76e9  # ~72.7B
    cfg = get_config("llama3-405b")
    tot, _ = lc.param_counts(cfg)
    assert 400e9 < tot < 412e9
    cfg = get_config("grok-1-314b")
    tot, act = lc.param_counts(cfg)
    assert 300e9 < tot < 330e9
    assert act < 0.4 * tot  # top-2 of 8 experts
    cfg = get_config("mamba2-2.7b")
    tot, _ = lc.param_counts(cfg)
    assert 2.2e9 < tot < 3.2e9
    # train flops dominate prefill dominate decode
    q = get_config("qwen2-72b")
    f_train = lc.model_flops(q, SHAPES["train_4k"])
    f_pre = lc.model_flops(q, SHAPES["prefill_32k"])
    f_dec = lc.model_flops(q, SHAPES["decode_32k"])
    assert f_train > f_pre > f_dec
