"""Plain references that decide ``correct``: straightforward jnp, float32,
every dot at ``Precision.HIGHEST`` through ``jax.lax.dot_general`` itself.

Nothing here imports the program.  Inputs are the benchmark's own data and
what the timed call was handed; outputs of the timed call are only ever
compared, never reused.  Everything is blockwise so that it runs on the
chip at the cell's own size after the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot_t(a, b):
    """a (m, d) · b (n, d)ᵀ at HIGHEST."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def brute_topk(Q, X, qids, *, k: int, block: int):
    """Exact top-k ids of Q's rows among X's rows, X streamed in blocks.
    Where ``qids[i] >= 0``, row ``qids[i]`` of X (the query itself) is
    excluded."""
    n = X.shape[0]
    nb = -(-n // block)
    Xb = jnp.pad(X, ((0, nb * block - n), (0, 0))).reshape(nb, block, -1)
    qsq = jnp.sum(Q * Q, axis=1, keepdims=True)

    def body(carry, i):
        bd, bi = carry
        xb = Xb[i]
        d2 = qsq + jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * _dot_t(Q, xb)
        ids = i * block + jnp.arange(block, dtype=jnp.int32)
        d2 = jnp.where((ids[None, :] < n) & (ids[None, :] != qids[:, None]),
                       d2, jnp.inf)
        d = jnp.concatenate([bd, d2], axis=1)
        cand = jnp.concatenate([bi, jnp.broadcast_to(ids, d2.shape)], axis=1)
        neg, pos = jax.lax.top_k(-d, k)
        return (-neg, jnp.take_along_axis(cand, pos, axis=1)), None

    init = (jnp.full((Q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((Q.shape[0], k), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(body, init, jnp.arange(nb))
    return ids


@jax.jit
def pair_sqdist(A, X, ids):
    """Exact squared distances ||A[i] - X[ids[i, j]]||², difference form
    (no cancellation); +inf where ``ids < 0``."""
    rows = X[jnp.maximum(ids, 0)]                       # (m, L, d)
    d2 = jnp.sum(jnp.square(rows - A[:, None, :]), axis=-1)
    return jnp.where(ids < 0, jnp.inf, d2)


def recall(ids, gt) -> float:
    """Mean share of each row's true neighbours that ``ids`` holds."""
    ids, gt = np.asarray(ids), np.asarray(gt)
    return float(np.mean([len(set(a) & set(b)) / gt.shape[1]
                          for a, b in zip(ids.tolist(), gt.tolist())]))


def bad_slots(ids, self_ids=None) -> int:
    """List entries that are empty (-1), the row itself, or a repeat."""
    ids = np.asarray(ids)
    bad = int(np.sum(ids < 0))
    if self_ids is not None:
        bad += int(np.sum(ids == np.asarray(self_ids)[:, None]))
    s = np.sort(ids, axis=1)
    bad += int(np.sum((s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)))
    return bad


@jax.jit
def list_faults(G):
    """Entries of the neighbour lists G (n, κ) that lie outside [0, n),
    name the row itself, or repeat an entry of their list."""
    n = G.shape[0]
    s = jnp.sort(G, axis=1)
    return (jnp.sum((G < 0) | (G >= n)) + jnp.sum(G == jnp.arange(n)[:, None])
            + jnp.sum(s[:, 1:] == s[:, :-1]))


def rel_err(got, want, stat: str = "max") -> float:
    """Relative gaps |got - want| / want over the finite entries of want,
    reduced by ``stat`` ("max" or "median"); an entry where exactly one
    side is finite counts as a gap of 1."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    fw, fg = np.isfinite(want), np.isfinite(got)
    both = fw & fg
    err = np.abs(got[both] - want[both]) / np.maximum(np.abs(want[both]),
                                                      1e-30)
    err = np.concatenate([err, np.ones(int(np.sum(fw != fg)))])
    if not err.size:
        return 0.0
    return float(np.max(err) if stat == "max" else np.median(err))


# ---------------------------------------------------------------------------
# clustering
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def segment_stats(X, assign, *, k: int):
    """(D (k, d) row sums, cnt (k,)) of an assignment, by segment sums."""
    D = jax.ops.segment_sum(X, assign, num_segments=k)
    cnt = jax.ops.segment_sum(jnp.ones(assign.shape, jnp.float32), assign,
                              num_segments=k)
    return D, cnt


@functools.partial(jax.jit, static_argnames=("block",))
def distortion(X, assign, D, cnt, *, block: int):
    """Mean squared distance of each row to its cluster's centroid,
    difference form, in row blocks."""
    n, d = X.shape
    C = D / jnp.maximum(cnt, 1.0)[:, None]
    nb = -(-n // block)
    pad = nb * block - n
    Xb = jnp.pad(X, ((0, pad), (0, 0))).reshape(nb, block, d)
    ab = jnp.pad(assign, (0, pad)).reshape(nb, block)
    valid = (jnp.arange(nb * block) < n).reshape(nb, block)

    def body(acc, i):
        e = jnp.sum(jnp.square(Xb[i] - C[ab[i]]), axis=-1)
        return acc + jnp.sum(jnp.where(valid[i], e, 0.0)), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(nb))
    return tot / n


_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35


def visit_order(key, n: int):
    """The engine's epoch visit order: a 4-round Feistel permutation of
    ``arange(n)`` with cycle walking, subkeys from ``random.bits(key)`` —
    the published construction the engine documents, written out here."""
    if n <= 1:
        return jnp.zeros((n,), jnp.int32)
    bits = max(1, (n - 1).bit_length())
    sub = jax.random.bits(key, (4,), jnp.uint32)
    m1, m2 = jnp.uint32(_M1), jnp.uint32(_M2)

    def mix(h):
        h = h ^ (h >> 16)
        h = h * m1
        h = h ^ (h >> 13)
        h = h * m2
        return h ^ (h >> 16)

    def prp(x):
        lo_b, hi_b = bits // 2, bits - bits // 2
        for r in range(4):
            lo = x & jnp.uint32((1 << lo_b) - 1)
            hi = x >> lo_b
            f = mix(lo ^ sub[r]) & jnp.uint32((1 << hi_b) - 1)
            x = (lo << hi_b) | (hi ^ f)
            lo_b, hi_b = hi_b, lo_b
        return x

    x = prp(jnp.arange(n, dtype=jnp.uint32))
    x = jax.lax.while_loop(lambda x: jnp.any(x >= n),
                           lambda x: jnp.where(x >= n, prp(x), x), x)
    return x.astype(jnp.int32)


def _delta_I(x, u, cand, D, cnt):
    """ΔI of moving each row x from cluster u to each candidate (paper
    Eqn. 3), written from the objective I = Σ_c ||D_c||² / n_c with every
    norm taken in difference form."""
    Du, nu = D[u], cnt[u]
    Dv, nv = D[cand], cnt[cand]                          # (B, C, d), (B, C)
    gain = (jnp.sum(jnp.square(Dv + x[:, None, :]), -1) / (nv + 1.0)
            - jnp.where(nv > 0, jnp.sum(jnp.square(Dv), -1)
                        / jnp.maximum(nv, 1.0), 0.0))
    loss = (jnp.where(nu > 1, jnp.sum(jnp.square(Du - x), -1)
                      / jnp.maximum(nu - 1.0, 1.0), 0.0)
            - jnp.sum(jnp.square(Du), -1) / jnp.maximum(nu, 1.0))
    return gain + loss[:, None]


@functools.partial(jax.jit, static_argnames=("k", "epochs", "batch"))
def bkm_epochs(X, G, assign, key, *, k: int, epochs: int, batch: int):
    """Graph-guided boost k-means (paper Alg. 2) for a fixed number of
    epochs: rows visited in mini-batches in each epoch's order, each
    row's candidates the clusters of its graph neighbours as assigned at
    the start of the epoch, the best candidate taken where its ΔI is
    positive, a cluster never emptied (all its leavers in a batch are held
    back when they would take its last row), statistics updated after each
    batch.  Returns the final assignment."""
    n = X.shape[0]
    bs = min(batch, n)
    nb = max(n // bs, 1)
    D, cnt = segment_stats(X, assign, k=k)
    Gc = jnp.maximum(G, 0)

    def epoch(t, carry):
        a, D, cnt = carry
        order = visit_order(jax.random.fold_in(key, t), n)
        lookup = a

        def step(i, carry):
            a, D, cnt = carry
            idx = jax.lax.dynamic_slice(order, (i * bs,), (bs,))
            x = X[idx]
            u = a[idx]
            cand = lookup[Gc[idx]]
            score = jnp.where(cand == u[:, None], -jnp.inf,
                              _delta_I(x, u, cand, D, cnt))
            best = jnp.argmax(score, axis=1)
            gain = jnp.take_along_axis(score, best[:, None], 1)[:, 0]
            moved = gain > 0.0
            leav = jax.ops.segment_sum(moved.astype(jnp.float32), u,
                                       num_segments=k)
            moved = moved & ((cnt - leav) >= 1.0)[u]
            v = jnp.where(moved, jnp.take_along_axis(cand, best[:, None],
                                                     1)[:, 0], u)
            w = moved.astype(jnp.float32)
            gx = x * w[:, None]
            D = D.at[u].add(-gx).at[v].add(gx)
            cnt = cnt.at[u].add(-w).at[v].add(w)
            return a.at[idx].set(v), D, cnt

        return jax.lax.fori_loop(0, nb, step, (a, D, cnt))

    a, _, _ = jax.lax.fori_loop(0, epochs, epoch, (assign, D, cnt))
    return a
