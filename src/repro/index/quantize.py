"""List-payload codecs: PQ codebooks and int8 affine, kernel-ready packing.

Two ways to compress the packed (n_rows, d) slab down to u8 codes that the
fused `ivf_scan_adc` kernel can score without decoding:

- ``int8``: per-dimension affine ``x ~ zero[j] + scale[j] * c[j]`` with
  ``c in [0, 255]``.  Codes are (n_rows, d) u8; the query-side constant
  ``-2 q . zero`` is the same for every candidate of a query (rank-
  invariant), so it rides OUTSIDE the kernel as ``qconst`` and is added to
  the selected partials — keeping the kernel's contraction length exactly
  ``d``, the same alignment the f32 scan's bitwise kernel/ref parity
  already relies on.
- ``pq``: product quantization — d splits into ``nsub`` subspaces, each with
  a 256-entry codebook trained by `engine.run` (the paper's own
  "k-means builds the index for k-means" trick, mode='lloyd').  Codes are
  (n_rows, nsub) u8; the per-query LUT holds ``-2 q_m . codebook[m, v]``.

Both codecs score with the same partial-distance convention as `ivf_scan`
(``||v||^2 - 2 q.v`` feeding `finalize_d2`): `pack_codes` precomputes
``vnorm = ||decode(c)||^2`` per row, and `build_lut` emits a per-query table
``(lut (q, M, W), qconst (q,))`` such that
``part = vnorm + sum_m lut[m, code[m]] + qconst``.  The int8 path is just
the W=1 degenerate case (the "lookup" is a multiply, qconst the affine
constant), so one kernel serves both (pq's qconst is zero).

Packing is a pure function of the f32 slab: ``codes == encode(vecs)`` holds
through add/remove/repack (holes encode the zero vector; the scan masks them
by id, so their values never surface).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref

PQ_VOCAB = 256          # codebook entries per subspace (one u8 code)
ENCODE_ROWS = 65536     # pq encode chunk: bounds the (rows, nsub, 256) f32
#                         distance block (8 GiB unchunked at 1M x 8 subs)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Int8Codec:
    """Per-dimension affine codec: ``x ~ zero + scale * code``."""
    kind: ClassVar[str] = "int8"
    scale: jax.Array          # (d,) f32, strictly positive
    zero: jax.Array           # (d,) f32

    def tree_flatten(self):
        return (self.scale, self.zero), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PqCodec:
    """Product quantizer: ``x ~ concat_m codebook[m, code[m]]``."""
    kind: ClassVar[str] = "pq"
    codebook: jax.Array       # (nsub, PQ_VOCAB, dsub) f32

    def tree_flatten(self):
        return (self.codebook,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def nsub(self) -> int:
        return self.codebook.shape[0]

    @property
    def dsub(self) -> int:
        return self.codebook.shape[2]


Codec = Int8Codec | PqCodec


def train_int8(X: jax.Array) -> Int8Codec:
    """Fit per-dimension [min, max] -> [0, 255] affine over training rows."""
    X = jnp.asarray(X, dtype=jnp.float32)
    mn = jnp.min(X, axis=0)
    mx = jnp.max(X, axis=0)
    # strictly positive scale keeps encode monotone even on constant dims
    scale = jnp.maximum((mx - mn) / 255.0, jnp.float32(1e-12))
    return Int8Codec(scale=scale, zero=mn)


def train_pq(X: jax.Array, nsub: int, *, key: jax.Array | None = None,
             iters: int = 8, batch_size: int = 1024) -> PqCodec:
    """Train one 256-entry codebook per subspace with the engine's k-means.

    Each subspace reuses `engine.run` (mode='lloyd') as the
    sub-k-means, seeded from a random draw of distinct training rows.  When
    fewer than 256 training rows exist the codebook is padded by repeating
    row 0 — exact duplicates, so `encode`'s stable argmin can never emit a
    padded code.
    """
    from repro.core import engine

    X = jnp.asarray(X, dtype=jnp.float32)
    n, d = X.shape
    assert nsub >= 1 and d % nsub == 0, (nsub, d)
    dsub = d // nsub
    ksub = min(PQ_VOCAB, n)
    key = jax.random.PRNGKey(0) if key is None else key
    cfg = engine.EngineConfig(batch_size=min(batch_size, n), mode="lloyd",
                              iters=iters)
    books = []
    from repro.core.permute import epoch_order

    for m in range(nsub):
        km = jax.random.fold_in(key, m)
        Xm = X[:, m * dsub:(m + 1) * dsub]
        # Feistel PRP, not random.permutation: O(n) seed draw, no full sort
        seeds = Xm[epoch_order(km, n)[:ksub]]
        assign0, _ = kref.assign_centroids(Xm, seeds)
        state = engine.init_state(Xm, assign0, ksub)
        state, *_ = engine.run(Xm, state, engine.dense_source(),
                               jax.random.fold_in(km, 1), cfg)
        book = state.D / jnp.maximum(state.cnt, 1)[:, None].astype(jnp.float32)
        if ksub < PQ_VOCAB:
            book = jnp.concatenate(
                [book, jnp.broadcast_to(book[:1], (PQ_VOCAB - ksub, dsub))])
        books.append(book)
    return PqCodec(codebook=jnp.stack(books))


# --------------------------------------------------------------------------
# encode / decode
# --------------------------------------------------------------------------

def code_width(codec: Codec, d: int) -> int:
    """Stored code columns per row (the kernel's contraction length M)."""
    return d if codec.kind == "int8" else codec.nsub


def lut_width(codec: Codec) -> int:
    """LUT entries per code column W: 256 for pq, 1 for int8 (direct dot)."""
    return 1 if codec.kind == "int8" else PQ_VOCAB


def encode(codec: Codec, X: jax.Array) -> jax.Array:
    """f32 rows (n, d) -> kernel-ready u8 codes (n, code_width)."""
    X = jnp.asarray(X, dtype=jnp.float32)
    if codec.kind == "int8":
        c = jnp.round((X - codec.zero[None, :]) / codec.scale[None, :])
        return jnp.clip(c, 0.0, 255.0).astype(jnp.uint8)
    n = X.shape[0]
    if n <= ENCODE_ROWS:
        return _encode_pq(codec.codebook, X)
    pad = -n % ENCODE_ROWS
    Xc = jnp.pad(X, ((0, pad), (0, 0))).reshape(-1, ENCODE_ROWS, X.shape[1])
    codes = jax.lax.map(lambda xc: _encode_pq(codec.codebook, xc), Xc)
    return codes.reshape(-1, codec.nsub)[:n]


def _encode_pq(codebook: jax.Array, X: jax.Array) -> jax.Array:
    """Nearest codebook entry per subspace; rows are independent, so the
    chunking in ``encode`` never changes a code."""
    nsub, _, dsub = codebook.shape
    Xs = X.reshape(X.shape[0], nsub, dsub)
    # ||x_m - book_m||^2 up to the x^2 term, argmin ties -> lowest code
    d2 = (jnp.sum(codebook ** 2, axis=-1)[None]
          - 2.0 * jnp.einsum("nmd,mvd->nmv", Xs, codebook,
                             precision=kref.HIGHEST))
    return jnp.argmin(d2, axis=-1).astype(jnp.uint8)


def decode(codec: Codec, codes: jax.Array) -> jax.Array:
    """u8 codes (n, code_width) -> reconstructed f32 rows (n, d)."""
    if codec.kind == "int8":
        c = codes.astype(jnp.float32)
        return codec.zero[None, :] + codec.scale[None, :] * c
    gathered = jnp.take_along_axis(
        codec.codebook[None], codes.astype(jnp.int32)[:, :, None, None],
        axis=2)                                       # (n, nsub, 1, dsub)
    return gathered[:, :, 0, :].reshape(codes.shape[0], -1)


def pack_codes(codec: Codec, vecs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Encode the whole packed slab: (codes (n_rows, M) u8, vnorm (n_rows,)).

    ``vnorm[i] = ||decode(codes[i])||^2`` — the reconstruction's own norm,
    so ADC partials are exact distances *to the reconstruction* and the
    codec's only error is quantization, never a norm mismatch.
    """
    codes = encode(codec, vecs)
    rec = decode(codec, codes)
    return codes, jnp.sum(rec * rec, axis=-1)


def build_lut(codec: Codec, Q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-query ADC table: (lut (q, M, W), qconst (q,)) with
    ``part = vnorm + sum_m lut[m, c[m]] + qconst``.

    ``qconst`` is the per-query term that is identical for every candidate
    (int8's affine constant ``-2 q . zero``; zero for pq) — rank-invariant,
    so the scan kernel never sees it: it is added to the SELECTED partials,
    after the top-k, on every exit path identically.  Pure jnp — safe inside
    the sharded search trace (computed once per query batch, replicated;
    codes stay sharded).
    """
    Q = jnp.asarray(Q, dtype=jnp.float32)
    if codec.kind == "int8":
        lut = (-2.0 * Q * codec.scale[None, :])[:, :, None]  # (q, d, 1)
        return lut, -2.0 * jnp.matmul(Q, codec.zero, precision=kref.HIGHEST)
    Qs = Q.reshape(Q.shape[0], codec.nsub, codec.dsub)
    lut = -2.0 * jnp.einsum("qmd,mvd->qmv", Qs, codec.codebook,
                            precision=kref.HIGHEST)
    return lut, jnp.zeros((Q.shape[0],), dtype=jnp.float32)


def bytes_per_row(codec: Codec | str, d: int) -> int:
    """HBM bytes a scan streams per candidate row (codes + vnorm | f32)."""
    kind = codec if isinstance(codec, str) else codec.kind
    if kind == "f32":
        return 4 * d
    if kind == "int8":
        return d + 4
    if kind == "pq":
        assert not isinstance(codec, str), "pq bytes need the codec's nsub"
        return codec.nsub + 4
    raise ValueError(f"unknown codec kind: {kind!r}")
