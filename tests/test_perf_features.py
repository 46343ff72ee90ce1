"""Beyond-paper perf features (§Perf): must preserve exact semantics."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import scaled_config
from repro.models import attention as attn
from repro.models import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_causal_skip_matches_baseline():
    key = jax.random.PRNGKey(0)
    B, S, Hq, Hkv, hd = 2, 256, 4, 2, 16
    q = jax.random.normal(key, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    a = attn.flash_attention(q, k, v, causal=True, kv_chunk=32, q_chunk=64)
    b = attn.flash_attention(q, k, v, causal=True, kv_chunk=32, q_chunk=64,
                             causal_skip=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


def test_vocab_padding_semantics():
    key = jax.random.PRNGKey(1)
    cfg = scaled_config("qwen2-72b", "smoke").scaled(
        vocab=500, pad_vocab_multiple=256, loss_chunk=64, attn_chunk=64)
    assert cfg.vocab_padded == 512
    m = build_model(cfg)
    p = m.init(key)
    assert p["embed"].shape[0] == 512
    batch = {"tokens": jax.random.randint(key, (2, 128), 0, 500),
             "labels": jax.random.randint(key, (2, 128), 0, 500)}
    loss = m.loss(p, batch)
    assert bool(jnp.isfinite(loss))
    lg, cache = m.prefill(p, batch, cache_len=136)
    assert int(jnp.argmax(lg, -1).max()) < 500  # phantom ids never sampled
    lg2, _ = m.decode_step(p, jnp.argmax(lg, -1)[:, None].astype(jnp.int32),
                           cache)
    assert int(jnp.argmax(lg2, -1).max()) < 500


CODE_SPARSE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import build_knn_graph, two_means_tree, init_state, distortion
from repro.core.distributed import make_sharded_epoch
key = jax.random.PRNGKey(0)
n, d, k = 4096, 16, 32
X = gmm_blobs(key, n, d, 32)
g = build_knn_graph(X, 8, xi=32, tau=3, key=key)
a0 = two_means_tree(X, k, key)
from repro.launch.mesh import data_mesh
mesh = data_mesh(8)
G = jnp.maximum(g.ids, 0)
res = {}
for mode in (False, True):
    ep = make_sharded_epoch(mesh, batch_size=128, sparse_updates=mode)
    st = init_state(X, a0, k)
    assign, D, cnt = st.assign, st.D, st.cnt
    for t in range(5):
        assign, D, cnt, _ = ep(X, G, assign, D, cnt,
                               jax.random.fold_in(key, t))
    res[mode] = (np.asarray(assign), float(distortion(X, assign, k)))
np.testing.assert_array_equal(res[False][0], res[True][0])
print("SPARSE_DENSE_IDENTICAL", res[True][1])
"""


@pytest.mark.slow
def test_sparse_updates_bit_identical_8dev():
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", CODE_SPARSE],
                       capture_output=True, text=True, env=env, timeout=900)
    assert "SPARSE_DENSE_IDENTICAL" in r.stdout, r.stderr[-2000:]
