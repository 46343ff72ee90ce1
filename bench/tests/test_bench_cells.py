"""Each cell's runner at a tiny size on the CPU, Pallas kernels in
interpret mode, called directly (the command itself refuses a CPU): the
run reports its metrics and ``correct`` is true; with the timed path
broken underneath — each fault the cell can have — and under the
lower-precision control, ``correct`` comes out false.

The tiny sizes read other numbers than the full cells do, so the limits
here are this file's own, set from tiny-size readings; the cells' limits
are in ``bench/limits/`` and ``PERF.md`` gives the readings behind them.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402

SEED = 2**31 + 11
TINY = {"n": 2048, "d": 128, "k": 32, "components": 32, "lists": 32}
TRAFFIC = {"graph": {"sample_rows": 64},
           "cluster": {"epochs": 2},
           "serve": {"pool_batches": 2, "batch": 32, "checked_batches": 2}}
# tiny-size limits: readings at these sizes (program / control) in the
# comments; the exact ones are the cells' own
LIMITS = {
    "sift1m.graph": {"dist_err": 2.5e-5,        # 9.7e-6 / 7.4e-5
                     "recall_short": 0.5,       # 0.16
                     "bad_slots": 0},
    "sift1m.cluster": {"assign_mismatch": 0.05,  # ~0.005; unchanged ~0.4
                       "stats_err": 1e-5, "distortion_gap": 1e-4,
                       "bad_rows": 0, "graph_bad": 0},
    "sift1m.serve-f32": {"recall_short": 0.05,
                         "dist_err": 2.5e-5,    # 4.9e-6 / 3.9e-5
                         "dist_err_median": 1e-5,
                         "bad_ids": 0},
}


def tiny_cell(name, force="interpret"):
    cell = harness.resolve(harness.load_spec(ROOT), name)
    cell.config.update(TINY)
    cell.traffic.update(TRAFFIC[cell.phase])
    cell.limits = dict(LIMITS[name])
    cell.force = force
    return cell


def run(cell, **kw):
    return harness.run_cell(cell, SEED, 0.3, False, t_process=0.0,
                            require_tpu=False, log=lambda m: None, **kw)


CELLS = sorted(LIMITS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    out = run(cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.limits)
    assert out["attempted"] >= 1 and out["failed"] == 0


# ---------------------------------------------------------------------------
# faults planted in the timed path
# ---------------------------------------------------------------------------

def _graph_altered(monkeypatch):
    from repro.core import graph_build

    build = graph_build.GraphBuilder.build

    def altered(self, X, key):
        g, diag = build(self, X, key)
        return g._replace(ids=jnp.roll(g.ids, 1, axis=0)), diag
    monkeypatch.setattr(graph_build.GraphBuilder, "build", altered)


def _graph_unchanged(monkeypatch):
    from repro.core import graph_build

    build = graph_build.GraphBuilder.build

    def unchanged(self, X, key):
        g, diag = build(self, X, key)
        return g._replace(ids=jnp.full_like(g.ids, -1),
                          dist=jnp.full_like(g.dist, jnp.inf)), diag
    monkeypatch.setattr(graph_build.GraphBuilder, "build", unchanged)


def _cluster_unchanged(monkeypatch):
    from repro.core import engine

    run_ = engine.run

    def unchanged(X, state, source, key, cfg, valid=None):
        out = run_(X, state, source, key, cfg, valid)
        return (state,) + tuple(out[1:])
    monkeypatch.setattr(engine, "run", unchanged)


def _cluster_half_batch(monkeypatch):
    from repro.core import engine

    step = engine._move_step

    def half(X, assign, D, cnt, moves, idx, *a, **k):
        h = idx.shape[0] // 2
        return step(X, assign, D, cnt, moves,
                    jnp.concatenate([idx[:h], idx[:h]]), *a, **k)
    monkeypatch.setattr(engine, "_move_step", half)


def _serve_altered(monkeypatch):
    from repro import index

    search = index.search

    def altered(*a, **k):
        ids, d2 = search(*a, **k)
        return ids.at[:, 0].set(ids[:, -1]), d2
    monkeypatch.setattr(index, "search", altered)


def _serve_half_batch(monkeypatch):
    from repro import index

    search = index.search

    def half(idx, Q, **k):
        h = Q.shape[0] // 2
        ids, d2 = search(idx, Q[:h], **k)
        pad = Q.shape[0] - h
        return (jnp.concatenate([ids, jnp.full((pad, ids.shape[1]), -1,
                                               ids.dtype)]),
                jnp.concatenate([d2, jnp.full((pad, d2.shape[1]), jnp.inf)]))
    monkeypatch.setattr(index, "search", half)


FAULTS = [("sift1m.graph", "answer altered", _graph_altered),
          ("sift1m.graph", "state unchanged", _graph_unchanged),
          ("sift1m.cluster", "state unchanged", _cluster_unchanged),
          ("sift1m.cluster", "half of each batch left out",
           _cluster_half_batch),
          ("sift1m.serve-f32", "answer altered", _serve_altered),
          ("sift1m.serve-f32", "half of each batch left out",
           _serve_half_batch)]


@pytest.mark.parametrize("name,fault,plant", FAULTS,
                         ids=[f"{c}:{f}" for c, f, _ in FAULTS])
def test_fault_makes_correct_false(name, fault, plant, monkeypatch):
    plant(monkeypatch)
    out = run(tiny_cell(name, force=None))
    assert not out["correct"], (fault, out["checks"])
    jax.clear_caches()


# ---------------------------------------------------------------------------
# the lower-precision control
# ---------------------------------------------------------------------------

PRECISION_NUMBER = {"sift1m.graph": "dist_err",
                    "sift1m.cluster": "stats_err",
                    "sift1m.serve-f32": "dist_err"}


@pytest.mark.parametrize("name", sorted(PRECISION_NUMBER))
def test_control_fails_its_number(name):
    """The control — every HIGHEST float32 dot computed as HIGH, and the
    engine's bf16 move payload — reads the number at least three times the
    program's and over its limit."""
    num = PRECISION_NUMBER[name]
    sound = run(tiny_cell(name, force=None))["checks"][num]["value"]
    out = run(tiny_cell(name, force=None), control="precision")
    ctl = out["checks"][num]["value"]
    assert not out["correct"]
    assert ctl >= 3.0 * sound, (sound, ctl)


def test_dots_alone_leave_the_cluster_statistics():
    """``--control dots`` lowers the float32 dots alone: in the cluster
    cell they only choose among moves, so the returned statistics read as
    a sound run's do, and the bf16 payload of ``--control precision`` is
    what reaches them."""
    err = {c: run(tiny_cell("sift1m.cluster", force=None), control=c)
           ["checks"]["stats_err"]["value"]
           for c in ("none", "dots", "precision")}
    assert err["dots"] <= 2.0 * err["none"] + 1e-7, err
    assert err["precision"] >= 10.0 * err["dots"], err
