"""The readers of the program's layer scopes, span and scan counter, on a
synthetic trace and a recorded span ring: each gives the hand-worked
value, the graph's four split ``nonkernel_ms.graph`` whole, and each reads
nothing where the program marks nothing."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace  # noqa: E402
from bench.layer import Context  # noqa: E402
from bench.trace import HloRow, Op, Span  # noqa: E402
from repro.obs import span, timing  # noqa: E402

MS = 1e6   # ns per ms
NEW = {"sift1m.graph": ["tree_ms.graph", "members_ms.graph",
                        "candidates_ms.graph", "unscoped_ms.graph"],
       "sift1m.cluster": ["candidates_ms.cluster", "move_ms.cluster",
                          "unscoped_ms.cluster"],
       "sift1m.serve-f32": ["search_host_ms.serve", "scan_useful.serve"]}
CELL_OF = {m: c for c, ms in NEW.items() for m in ms}
B = "jit(_build_single)/while/body/"
E = "jit(run)/while/body/while/body/closed_call/"


def reader(metric):
    cell = harness.resolve(harness.load_spec(ROOT), CELL_OF[metric])
    assert metric in [m["name"] for m in cell.per_layer]
    return harness.metric_reader(cell, metric)


def reduction(rows):
    ops = [Op("fusion.1", 0.0, 50 * MS)]
    return trace.reduce(ops, [Span(trace.WINDOW, 0.0, 100 * MS)],
                        [HloRow(t, f"op.{i}", s)
                         for i, (t, s) in enumerate(rows)])


def graph_trace():
    # two rounds: tree 14 ms, members 2, candidates 3, unscoped 1.5 (a
    # tree op whose key _short cut to its last three parts, and one op
    # outside every scope), refine_merge 20 ms (a kernel under candidates)
    return reduction([
        (B + "repro.graph.tree/while/body/dot_general:", 0.010),
        (B + "repro.graph.tree/_radix_left/scatter-add:", 0.004),
        (B + "repro.graph.members/vmap()/sort:", 0.002),
        (B + "repro.graph.candidates/gather:", 0.003),
        (B + "repro.graph.candidates/while/body/repro.kernels.refine_merge/"
             "pallas_call:", 0.020),
        (B + "repro.graph.tree/f/g/h/gather:", 0.001),
        ("jit(_build_single)/slice:", 0.0005)])


def cluster_trace():
    # two epochs: candidates 6 ms, move 3 (two keys), unscoped 1,
    # gather_score 30 ms
    return reduction([
        (E + "repro.engine.candidates/gather:", 0.006),
        (E + "repro.engine.move/scatter-add:", 0.002),
        (E + "repro.engine.move/select_n:", 0.001),
        (E + "repro.kernels.gather_score/pallas_call:", 0.030),
        ("jit(run)/while/body/while/body/dynamic_slice:", 0.001)])


def ctx(r, **counts):
    return Context(reduction=r, counts=counts,
                   work={"ivf_scan": (2.0 * 128 * 3000, 0.0)},
                   device_kind="TPU v5 lite")


@pytest.mark.parametrize("metric,want", [
    ("tree_ms.graph", 7.0), ("members_ms.graph", 1.0),
    ("candidates_ms.graph", 1.5), ("unscoped_ms.graph", 0.75)])
def test_graph_readers_hand_worked(metric, want):
    assert reader(metric)(ctx(graph_trace(), rounds=2)) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("candidates_ms.cluster", 3.0), ("move_ms.cluster", 1.5),
    ("unscoped_ms.cluster", 0.5)])
def test_cluster_readers_hand_worked(metric, want):
    assert reader(metric)(ctx(cluster_trace(), epochs=2)) == \
        pytest.approx(want)


@pytest.mark.parametrize("cell,trace_of,per,nonkernel", [
    ("sift1m.graph", graph_trace, "rounds", "nonkernel_ms.graph"),
    ("sift1m.cluster", cluster_trace, "epochs", "nonkernel_ms.cluster")])
def test_scope_readers_split_nonkernel_whole(cell, trace_of, per, nonkernel):
    c = ctx(trace_of(), **{per: 2})
    whole = harness.metric_reader(
        harness.resolve(harness.load_spec(ROOT), cell), nonkernel)(c)
    assert sum(reader(m)(c) for m in NEW[cell]) == pytest.approx(whole)


def record_window(monkeypatch, durations_ms, grid_flops):
    """Spans of ``index.search`` as the program records them, on a fake
    clock: one per duration, each counting ``grid_flops``."""
    timing.clear()
    t, ticks = 0, []
    for d in durations_ms:
        ticks += [t, t + int(d * MS)]
        t += int(d * MS) + 1000
    ticks.reverse()
    monkeypatch.setattr(timing.time, "perf_counter_ns", ticks.pop)
    for _ in durations_ms:
        with span("repro.search") as sp:
            sp.count(grid_rows=grid_flops // 256, grid_flops=grid_flops)


def test_span_reader_takes_the_windows_last_spans(monkeypatch):
    # two warm-up calls (50 ms) before a window of three (1, 3, 2 ms)
    record_window(monkeypatch, [50, 50, 1, 3, 2], 2 * 128 * 10000)
    c = ctx(trace.Reduction(1.0, 1.0, {}, 0.0), calls=3, batches=3)
    assert reader("search_host_ms.serve")(c) == pytest.approx(2.0)
    # live rows 3,000 of the window's 30,000 grid rows
    assert reader("scan_useful.serve")(c) == pytest.approx(10.0)
    # a window longer than the ring's record reads nothing
    assert reader("search_host_ms.serve")(ctx(c.reduction, calls=6)) is None


@pytest.mark.parametrize("metric", sorted(CELL_OF))
def test_every_reader_reads_nothing_on_an_empty_trace(metric):
    timing.clear()
    empty = trace.reduce([], [Span(trace.WINDOW, 0.0, 10 * MS)], [])
    assert reader(metric)(ctx(empty, rounds=1, epochs=1, calls=1,
                              batches=1)) is None


@pytest.mark.parametrize("metric", sorted(m for m in CELL_OF
                                          if m.endswith(("graph", "cluster"))))
def test_scope_readers_read_nothing_without_the_programs_scopes(metric):
    """A trace of a program that marks no layer (the kernels alone)."""
    r = reduction([("jit(f)/while/body/dot_general:", 0.01),
                   ("jit(f)/repro.kernels.refine_merge/pallas_call:", 0.02)])
    assert reader(metric)(ctx(r, rounds=1, epochs=1)) is None
