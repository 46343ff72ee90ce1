"""Every Pallas kernel compiles for a TPU v5e chip, at real widths.

Interpret mode checks the kernels' arithmetic; only the TPU compiler checks
their block tiling, VMEM use and lowering.  These tests compile each kernel
for one chip of a described (not attached) ``v5e:2x2`` topology, at the
paper's SIFT width d = 128 and at the GIST width d = 960, with a real batch
(B = 1024 rows, C = 50 candidates), and assert that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  The IVF scans are also
compiled for a large serving batch (NQ_LARGE queries), which they must
split into query tiles that fit VMEM.

The TPU library may be loaded by one process at a time, so the topology is
described inside a fixture, never while the module is imported or
collected, and every test of this kind stays in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import centroid_assign as ca
from repro.kernels import gather_score as gs
from repro.kernels import ivf_scan as iv
from repro.kernels import ivf_scan_adc as adc
from repro.kernels import pairwise_topk as pt
from repro.kernels import refine_merge as rm

B, C, KAPPA = 1024, 50, 50          # engine batch, candidates, list width
K = 16384                           # clusters (10,000 rounded up to 2^14)
NQ, BLOCK_ROWS, T = 256, 128, 24    # query batch, list tile, probed tiles
NQ_LARGE = 4096                     # serving batch beyond one VMEM tile
N_PAD = BLOCK_ROWS * 4096           # packed index rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cases(d, NQ=NQ):
    """kernel -> (fn, [(shape, dtype), ...]) at feature width d and query
    batch NQ."""
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    cr = 2 * 64 + 8                 # refine candidates: cap_factor*xi + spill
    return {
        "gather_score": (
            lambda x, u, c, D, n: gs.gather_score(x, u, c, D, n, bB=0),
            [((B, d), f32), ((B,), i32), ((B, C), i32), ((K, d), f32),
             ((K,), f32)]),
        "refine_merge": (
            lambda x, r, ci, oi, od, X: rm.refine_merge(x, r, ci, oi, od, X,
                                                        bB=0),
            [((B, d), f32), ((B, cr), i32), ((B, cr), i32), ((B, KAPPA), i32),
             ((B, KAPPA), f32), ((65536, d), f32)]),
        "assign_centroids": (
            lambda X, Cc: ca.assign_centroids_padded(X, Cc),
            [((65536, d), f32), ((10000, d), f32)]),
        "probe_centroids": (
            lambda X, Cc: ca.probe_centroids_padded(X, Cc, 8),
            [((NQ, d), f32), ((K, d), f32)]),
        "pairwise_sq": (
            lambda X: pt.pairwise_sq(X, bB=0),
            [((256, 128, d), f32)]),
        "ivf_scan": (
            lambda Q, v, p, tm: iv.ivf_scan(Q, v, p, tm,
                                            block_rows=BLOCK_ROWS, topk=10),
            [((NQ, d), f32), ((N_PAD, d), f32), ((N_PAD,), i32),
             ((NQ, T), i32)]),
        "ivf_scan_grouped": (
            lambda Q, v, p, ut, m: iv.ivf_scan_grouped(
                Q, v, p, ut, m, block_rows=BLOCK_ROWS, topk=10),
            [((NQ, d), f32), ((N_PAD, d), f32), ((N_PAD,), i32),
             ((NQ // 8, 8 * T), i32), ((NQ, 8 * T), i32)]),
        "ivf_scan_adc_pq": (
            lambda lut, qc, vn, co, p, tm: adc.ivf_scan_adc(
                lut, qc, vn, co, p, tm, block_rows=BLOCK_ROWS, topk=40),
            [((NQ, 8, 256), f32), ((NQ,), f32), ((N_PAD,), f32),
             ((N_PAD, 8), u8), ((N_PAD,), i32), ((NQ, T), i32)]),
        "ivf_scan_adc_int8": (
            lambda lut, qc, vn, co, p, tm: adc.ivf_scan_adc(
                lut, qc, vn, co, p, tm, block_rows=BLOCK_ROWS, topk=40),
            [((NQ, d, 1), f32), ((NQ,), f32), ((N_PAD,), f32),
             ((N_PAD, d), u8), ((N_PAD,), i32), ((NQ, T), i32)]),
    }


KERNELS = sorted(_cases(128))
SCANS = ["ivf_scan", "ivf_scan_adc_int8", "ivf_scan_adc_pq"]


@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, d, one_chip, no_persistent_cache):
    _compile(_cases(d)[kernel], one_chip)


@pytest.mark.parametrize("kernel", SCANS)
def test_large_query_batch_compiles_for_v5e(kernel, one_chip,
                                            no_persistent_cache):
    _compile(_cases(960, NQ=NQ_LARGE)[kernel], one_chip)


def _compile(case, one_chip):
    fn, args = case
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
