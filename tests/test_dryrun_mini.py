"""Dry-run machinery on a small mesh (the 512-device run is the deliverable;
this validates the lowering path + roofline extraction in-process)."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch import roofline as rl
from repro.launch.dryrun import lower_cell

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh():
    d = jax.devices()
    return Mesh(np.array(d[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2-72b").scaled(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab=512, head_dim=32, loss_chunk=128, attn_chunk=128)


@pytest.mark.parametrize("kind,seq,batch", [("train", 256, 4),
                                            ("prefill", 256, 2),
                                            ("decode", 256, 2)])
def test_lower_compile_and_analyse(mesh, cfg, kind, seq, batch):
    shape = ShapeSpec(f"{kind}_t", seq, batch, kind)
    lowered = lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    cost = rl.cost_analysis(compiled)
    assert cost.get("flops", 0) > 0
    mem = compiled.memory_analysis()
    assert rl.peak_memory_bytes(mem) > 0
    coll = rl.collective_bytes(compiled.as_text())
    assert coll["total_bytes"] >= 0  # no collectives on 1x1 mesh is fine
    terms = rl.roofline_terms(cost["flops"], cost.get("bytes accessed", 0),
                              coll["total_wire_bytes"],
                              device_kind="TPU v5 lite")
    assert terms["bottleneck"] in ("compute", "memory", "collective")


def test_long500k_skip_logic():
    from repro.configs import SHAPES
    assert not get_config("qwen2-72b").supports(SHAPES["long_500k"])
    assert get_config("mamba2-2.7b").supports(SHAPES["long_500k"])
    assert get_config("recurrentgemma-9b").supports(SHAPES["long_500k"])
    assert get_config("qwen2-72b").supports(SHAPES["train_4k"])
