"""The obs layer: telemetry slots, sync counting, emit schema, obs_report.

The two load-bearing guarantees:
  * telemetry ON never changes clustering results (bit-exact assign/stats)
    and still costs exactly one host sync;
  * telemetry OFF adds ZERO HLO — the compiled program contains no
    accumulator buffers.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, two_means_tree
from repro.data import gmm_blobs
from repro.obs import (emit, run_record, sync_counter, span, validate_record,
                       write_json)
from repro.obs import telemetry as obs_tel
from repro.obs import timing


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    n, d, k = 1024, 8, 16
    X = gmm_blobs(key, n, d, 16)
    a0 = two_means_tree(X, k, key)
    G = jax.random.randint(key, (n, 8), 0, n)
    return X, a0, G, k, key


# ---------------------------------------------------------------------------
# telemetry pytree
# ---------------------------------------------------------------------------

def test_record_and_column_roundtrip():
    tel = obs_tel.init(3)
    tel = obs_tel.record(tel, 1, moves=7, distortion=2.5)
    np.testing.assert_array_equal(obs_tel.column(tel, "moves"), [0, 7, 0])
    np.testing.assert_allclose(obs_tel.column(tel, "distortion"),
                               [0.0, 2.5, 0.0])


def test_record_rows_whole_columns():
    tel = obs_tel.record_rows(obs_tel.init(2), overflow=jnp.array([3, 4]),
                              graph_mean_dist=jnp.array([1.0, 0.5]))
    np.testing.assert_array_equal(obs_tel.column(tel, "overflow"), [3, 4])
    np.testing.assert_allclose(obs_tel.column(tel, "graph_mean_dist"),
                               [1.0, 0.5])


def test_record_unknown_slot_raises_none_passes():
    with pytest.raises(KeyError):
        obs_tel.record(obs_tel.init(1), 0, nonsense=1)
    assert obs_tel.record(None, 0, moves=1) is None
    assert obs_tel.record_rows(None, moves=jnp.zeros(1)) is None
    assert obs_tel.to_dict(None) == {}


def test_to_dict_truncates_and_selects():
    tel = obs_tel.record(obs_tel.init(4), 0, moves=9, hit_rate=0.5)
    d = obs_tel.to_dict(tel, rows=2, slots=["moves", "hit_rate"])
    assert d == {"moves": [9, 0], "hit_rate": [0.5, 0.0]}
    assert set(obs_tel.to_dict(tel)) == (set(obs_tel.I32_SLOTS)
                                         | set(obs_tel.F32_SLOTS))


# ---------------------------------------------------------------------------
# engine telemetry: on/off bit-exactness, one sync, zero HLO when off
# ---------------------------------------------------------------------------

def _run_cfg(telemetry):
    return engine.EngineConfig(batch_size=256, iters=5, min_move_frac=-1.0,
                               telemetry=telemetry)


def test_telemetry_on_off_bit_exact(setup):
    X, a0, G, k, key = setup
    source = engine.graph_source(G)
    st_on, hist_on, mh_on, ep_on, fin_on, tel = engine.run(
        X, engine.init_state(X, a0, k), source, key, _run_cfg(True))
    st_off, hist_off, mh_off, ep_off, fin_off, tel_off = engine.run(
        X, engine.init_state(X, a0, k), source, key, _run_cfg(False))
    assert tel_off is None and tel is not None
    np.testing.assert_array_equal(np.asarray(st_on.assign),
                                  np.asarray(st_off.assign))
    np.testing.assert_array_equal(np.asarray(st_on.D), np.asarray(st_off.D))
    np.testing.assert_array_equal(np.asarray(st_on.cnt),
                                  np.asarray(st_off.cnt))
    np.testing.assert_array_equal(np.asarray(hist_on), np.asarray(hist_off))
    np.testing.assert_array_equal(np.asarray(mh_on), np.asarray(mh_off))
    assert int(ep_on) == int(ep_off)
    np.testing.assert_array_equal(np.asarray(fin_on), np.asarray(fin_off))


def test_telemetry_slots_consistent_with_histories(setup):
    X, a0, G, k, key = setup
    source = engine.graph_source(G)
    with sync_counter() as sc:
        out = engine.run(X, engine.init_state(X, a0, k), source, key,
                         _run_cfg(True))
        st, hist, mhist, epochs, final, tel = sc.get(out)  # the ONE sync
    assert sc.syncs == 1
    np.testing.assert_array_equal(obs_tel.column(tel, "moves"), mhist)
    np.testing.assert_array_equal(obs_tel.column(tel, "distortion"), hist)
    prop = obs_tel.column(tel, "proposed")
    assert np.all(prop >= obs_tel.column(tel, "moves"))
    hr = obs_tel.column(tel, "hit_rate")
    assert np.all((hr >= 0.0) & (hr <= 1.0))
    empt = obs_tel.column(tel, "empty_clusters")
    assert np.all((empt >= 0) & (empt <= k))


def test_telemetry_off_adds_zero_hlo(setup):
    """enabled=False compiles the accumulators away entirely: the (iters, 8)
    i32 / (iters, 4) f32 slot buffers appear nowhere in the compiled HLO."""
    X, a0, G, k, key = setup
    source = engine.graph_source(G)
    i32_shape = f"s32[5,{obs_tel.N_I32}]"
    f32_shape = f"f32[5,{obs_tel.N_F32}]"

    def compiled_text(telemetry):
        f = jax.jit(lambda X, a0, key: engine.run(
            X, engine.init_state(X, a0, k), source, key,
            _run_cfg(telemetry)))
        return f.lower(X, a0, key).compile().as_text()

    txt_off = compiled_text(False)
    assert i32_shape not in txt_off and f32_shape not in txt_off
    txt_on = compiled_text(True)
    assert i32_shape in txt_on and f32_shape in txt_on


def test_gk_means_surfaces_telemetry(setup):
    from repro.core import gk_means
    X, _, _, k, key = setup
    res = gk_means(X, k, kappa=8, xi=32, tau=2, iters=3, key=key,
                   telemetry=True)
    assert res.telemetry is not None
    assert len(obs_tel.column(res.telemetry, "moves")) == 3
    assert set(res.seconds) == {"total"} and res.seconds["total"] > 0
    res0 = gk_means(X, k, kappa=8, xi=32, tau=2, iters=3, key=key)
    assert res0.telemetry is None
    np.testing.assert_array_equal(np.asarray(res.assign),
                                  np.asarray(res0.assign))


# ---------------------------------------------------------------------------
# sync counter + span
# ---------------------------------------------------------------------------

def test_sync_counter_counts_gets_and_blocks():
    """Counting semantics (the raise-on-stray-transfer half of the guard is
    backend-dependent: CPU device->host is zero-copy and never trips it, so
    only the explicit-sync tally is asserted here)."""
    x = jnp.arange(8.0)
    with sync_counter() as sc:
        y = x * 2
        got = sc.get(y)
        assert sc.syncs == 1
        sc.block(y)
        assert sc.syncs == 2
    np.testing.assert_allclose(got, np.arange(8.0) * 2)


def test_span_times_and_files():
    with span("mul") as sp:
        sp.result = jnp.ones((128, 128)) @ jnp.ones((128, 128))
    rec = timing.recent("mul", 1)[-1]
    assert sp.seconds > 0 and rec.end_ns - rec.start_ns == \
        pytest.approx(sp.seconds * 1e9)


class _Clock:
    """A fake ``perf_counter_ns``: each read returns the next given time."""

    def __init__(self, *ns):
        self.ns = list(ns)

    def __call__(self):
        return self.ns.pop(0)


def test_span_ring_records_parent_self_time_and_counts(monkeypatch):
    timing.clear()
    # outer 0..100, inner 10..40 and 50..60: outer's self time is 60
    monkeypatch.setattr(timing.time, "perf_counter_ns",
                        _Clock(0, 10, 40, 50, 60, 100))
    with span("outer") as outer:
        outer.count(rows=3)
        for _ in range(2):
            with span("inner") as inner:
                inner.count(rows=1, tiles=2)
                inner.count(rows=1)
        outer.count(rows=4)
    (o,) = timing.recent("outer", 5)
    i1, i2 = timing.recent("inner", 5)
    assert (o.start_ns, o.end_ns, o.parent, o.self_ns) == (0, 100, None, 60)
    assert o.counts == {"rows": 7}
    assert (i1.start_ns, i1.end_ns, i1.parent, i1.self_ns) == (10, 40,
                                                               "outer", 30)
    assert i2.start_ns == 50 and i1.counts == i2.counts == {"rows": 2,
                                                            "tiles": 2}
    assert outer.seconds == pytest.approx(100e-9)


def test_span_ring_is_bounded_and_reads_the_last():
    timing.clear()
    for i in range(timing.RING_SIZE + 5):
        with span("tick") as sp:
            sp.count(i=i)
    with span("other"):
        pass
    got = timing.recent("tick", 2 * timing.RING_SIZE)
    assert len(got) == timing.RING_SIZE - 1      # "other" took a slot
    assert [r.counts["i"] for r in timing.recent("tick", 3)] == [
        timing.RING_SIZE + 2, timing.RING_SIZE + 3, timing.RING_SIZE + 4]
    assert timing.recent("tick", 0) == [] and timing.recent("none", 3) == []


def test_span_records_nothing_when_its_block_raises():
    timing.clear()
    with pytest.raises(RuntimeError):
        with span("boom"):
            raise RuntimeError("x")
    with span("after") as sp:
        pass
    assert timing.recent("boom", 1) == []
    assert timing.recent("after", 1)[0].parent is None and sp.parent is None


def test_kernel_scope_names_land_in_hlo():
    from repro.kernels import ops
    txt = jax.jit(ops.pairwise_sq).lower(
        jnp.ones((2, 8, 4))).compile().as_text()
    assert "repro.kernels.pairwise_sq" in txt


# ---------------------------------------------------------------------------
# layer scopes: where each part of the graph build and the engine epoch runs
# ---------------------------------------------------------------------------

LAYER_SCOPES = {"graph": ("repro.graph.tree", "repro.graph.members",
                          "repro.graph.candidates"),
                "engine": ("repro.engine.candidates", "repro.engine.move")}


def _compiled_text(program, setup):
    from repro.core import GraphBuildConfig
    from repro.core.graph_build import _build_single
    X, a0, G, k, key = setup
    if program == "graph":
        return _build_single.lower(
            X, key, GraphBuildConfig(kappa=8, xi=32, tau=1)).compile().as_text()
    return engine.run.lower(X, engine.init_state(X, a0, k),
                            engine.graph_source(G), key,
                            _run_cfg(False)).compile().as_text()


@pytest.mark.parametrize("program,scope", [
    (p, s) for p, scopes in LAYER_SCOPES.items() for s in scopes])
def test_layer_scope_names_land_in_hlo(program, scope, setup):
    """Each layer scope marks ops of its program, and every non-kernel
    ``repro.*`` scope in the program is one of the layer scopes."""
    txt = _compiled_text(program, setup)
    assert scope in txt
    named = set(re.findall(r"repro\.[A-Za-z0-9_]+\.[A-Za-z0-9_]+", txt))
    assert {n for n in named if not n.startswith("repro.kernels.")} == \
        set(LAYER_SCOPES[program])


def test_layer_scope_refuses_the_kernel_prefix():
    with pytest.raises(ValueError):
        timing.layer_scope("kernels", "refine_merge")
    assert all(not s.startswith(timing.SCOPE_PREFIX)
               for scopes in LAYER_SCOPES.values() for s in scopes)


# ---------------------------------------------------------------------------
# index.search: one span a call, its grid count, and no host sync
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_ivf():
    from repro import index as ivf
    from repro.kernels import ref
    key = jax.random.PRNGKey(3)
    X = gmm_blobs(key, 1024, 16, 16)
    C = gmm_blobs(jax.random.fold_in(key, 1), 16, 16, 16)
    a, _ = ref.assign_centroids(X, C)

    class Result:
        assign, centroids, k = a, C, 16

    return ivf.build_ivf(X, Result, block_rows=32), X[:40]


@pytest.mark.parametrize("qgroup,rows_q", [(None, 40), (8, 40), (16, 48)])
def test_search_records_one_span_with_its_grid(small_ivf, qgroup, rows_q):
    """grid_rows = q x nprobe x max_list_tiles x block_rows, q rounded up to
    a whole number of groups in the grouped layout."""
    from repro import index as ivf
    index, Q = small_ivf
    timing.clear()
    for _ in range(3):
        ivf.search(index, Q, topk=5, nprobe=4, qgroup=qgroup)
    spans = timing.recent("repro.search", 10)
    assert len(spans) == 3 and all(s.parent is None for s in spans)
    rows = rows_q * 4 * index.max_list_tiles * index.block_rows
    group = qgroup or 1
    assert spans[-1].counts == {"grid_rows": rows,
                                "grid_flops": 2 * rows * group * 16}


def test_warm_search_makes_no_host_sync(small_ivf):
    """Pinned: a warm ``index.search`` dispatches without a host sync (on
    a TPU the guard raises on any implicit device->host transfer; on the
    CPU it cannot trip, see above, and only the tally is checked)."""
    from repro import index as ivf
    index, Q = small_ivf
    jax.block_until_ready(ivf.search(index, Q, topk=5, nprobe=4))
    with sync_counter() as sc:
        out = ivf.search(index, Q, topk=5, nprobe=4)
    assert sc.syncs == 0
    jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# emit schema
# ---------------------------------------------------------------------------

def test_emit_roundtrip(tmp_path):
    rec = run_record("unit", shapes={"n": 4}, config={"x": 1},
                     metrics={"t_s": 0.5}, telemetry={"moves": [1, 2]})
    p = str(tmp_path / "BENCH_unit.json")
    write_json(p, rec)
    back = emit.load_records(p)
    assert back == [rec]
    assert back[0]["schema"] == emit.SCHEMA
    assert back[0]["telemetry"] == {"moves": [1, 2]}

    jl = str(tmp_path / "runs.jsonl")
    emit.append_jsonl(jl, rec)
    emit.append_jsonl(jl, run_record("unit2", metrics={"a": 1}))
    assert [r["name"] for r in emit.load_records(jl)] == ["unit", "unit2"]

    byname = emit.load_dir(str(tmp_path))
    assert set(byname) == {"unit"}


def test_emit_rejects_drift(tmp_path):
    with pytest.raises(ValueError):
        validate_record({"name": "x"})
    bad = run_record("x")
    bad["schema"] = "repro.bench.v0"
    with pytest.raises(ValueError):
        validate_record(bad)
    p = str(tmp_path / "BENCH_bad.json")
    with open(p, "w") as f:
        json.dump({"name": "bad", "metrics": {}}, f)
    with pytest.raises(ValueError):
        emit.load_records(p)


# ---------------------------------------------------------------------------
# obs_report
# ---------------------------------------------------------------------------

def _kernels_record(device_kind="TPU v5 lite"):
    rec = run_record("kernels", metrics={"kernels": [
        {"kernel": "pairwise_sq", "us": 100.0,
         "shape": {"B": 256, "m": 64, "d": 128}},
        {"kernel": "refine_merge", "us": 50.0,
         "shape": {"B": 4096, "C": 64, "d": 128, "kappa": 16}},
    ]})
    rec["env"]["device_kind"] = device_kind      # as measured on the chip
    return rec


def test_obs_report_renders_tables(tmp_path, capsys):
    from repro.launch import obs_report
    write_json(str(tmp_path / "BENCH_kernels.json"), _kernels_record())
    write_json(str(tmp_path / "BENCH_engine.json"), run_record(
        "engine", metrics={"speedup": 2.0},
        telemetry={"moves": [5, 3], "distortion": [1.5, 1.25]}))
    assert obs_report.main(["--dir", str(tmp_path),
                            "--require", "kernels", "engine"]) == 0
    out = capsys.readouterr().out
    assert "kernel roofline" in out
    assert "pairwise_sq" in out and "refine_merge" in out
    assert "achieved_frac" in out
    assert "per-phase telemetry" in out
    assert "distortion" in out and "moves" in out


def test_obs_report_fails_on_missing_inventory(tmp_path, capsys):
    from repro.launch import obs_report
    rec = _kernels_record()
    rec["metrics"]["kernels"][0]["kernel"] = "not_a_kernel"
    write_json(str(tmp_path / "BENCH_kernels.json"), rec)
    assert obs_report.main(["--dir", str(tmp_path)]) != 0
    assert "KERNEL_INVENTORY" in capsys.readouterr().err


def test_obs_report_fails_on_unknown_device_kind(tmp_path, capsys):
    """A kernels record from a device without published peaks (a CPU run):
    its roofline fails — no chip's peaks are assumed for it — while the
    report still prints its timings and passes, as the CPU CI job needs."""
    from repro.launch import obs_report
    from repro.launch.roofline import peaks
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
    write_json(str(tmp_path / "BENCH_kernels.json"), _kernels_record("cpu"))
    assert obs_report.main(["--dir", str(tmp_path),
                            "--require", "kernels", "pairwise_sq"]) == 0
    out = capsys.readouterr().out
    assert "no published peaks for 'cpu'" in out
    row = next(ln for ln in out.splitlines() if ln.startswith("pairwise_sq"))
    assert "100.0" in row and "compute" not in row and "memory" not in row


def test_obs_report_fails_on_drift_and_missing_required(tmp_path, capsys):
    from repro.launch import obs_report
    assert obs_report.main(["--dir", str(tmp_path)]) != 0   # no records
    write_json(str(tmp_path / "BENCH_kernels.json"), _kernels_record())
    assert obs_report.main(["--dir", str(tmp_path),
                            "--require", "engine"]) != 0    # missing record
    with open(tmp_path / "BENCH_drifted.json", "w") as f:
        json.dump({"schema": "repro.bench.v0", "name": "drifted"}, f)
    assert obs_report.main(["--dir", str(tmp_path)]) != 0   # schema drift
