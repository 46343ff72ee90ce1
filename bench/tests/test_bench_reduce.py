"""The trace reduction on a small synthetic trace, and the work counts
against hand-worked shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import peaks, trace, work  # noqa: E402
from bench.trace import Op, Span  # noqa: E402

MS = 1e6   # ns per ms


def _trace():
    # window 0..100 ms; device busy 10-30, 25-40 (overlaps), 45-70, idle
    # 70-100; one op after the window.  The per-HLO-op table gives the
    # ops' self times and scopes: 20 ms of refine_merge, 10 ms of
    # gather_score, 30 ms outside every kernel scope.
    ops = [Op("custom-call.1", 10 * MS, 20 * MS),
           Op("fusion.7", 25 * MS, 15 * MS),
           Op("while.3", 45 * MS, 25 * MS),
           Op("custom-call.2", 50 * MS, 10 * MS),
           Op("copy.9", 120 * MS, 5 * MS)]                # after the window
    spans = [Span(trace.WINDOW, 0.0, 100 * MS),
             Span("bench.call", 0.0, 100 * MS),
             Span("host_prep", 72 * MS, 20 * MS)]
    hlo = [trace.HloRow("jit(f)/repro.kernels.refine_merge/pallas_call",
                        "custom-call.1", 0.020),
           trace.HloRow("jit(f)/add", "fusion.7", 0.015),
           trace.HloRow("jit(f)/while", "while.3", 0.015),
           trace.HloRow("jit(f)/while/body/repro.kernels.gather_score/"
                        "pallas_call", "custom-call.2", 0.010)]
    return ops, spans, hlo


def test_busy_is_a_union_and_idle_share():
    r = trace.reduce(*_trace())
    assert r.window_s == pytest.approx(0.100)
    # union: 10-40 (30) + 45-70 (25) = 55 ms
    assert r.busy_s == pytest.approx(0.055)
    assert 1.0 - r.busy_s / r.window_s == pytest.approx(0.45)


def test_kernel_time_by_scope_and_ops_outside_every_scope():
    r = trace.reduce(*_trace())
    assert r.kernel_s == pytest.approx({"refine_merge": 0.020,
                                        "gather_score": 0.010})
    # fusion 15 ms + the while op's own 25 - 10 = 15 ms
    assert r.nonkernel_s == pytest.approx(0.030)


def test_gaps_labelled_by_innermost_host_span():
    r = trace.reduce(*_trace())
    assert r.gaps[0][0] == "host_prep" and r.gaps[0][1] == pytest.approx(0.030)
    assert [(n, round(s, 6)) for n, s in r.gaps[1:]] == [
        ("bench.call", 0.010), ("bench.call", 0.005)]   # 0-10, 40-45
    bd = r.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert bd["device_ops"][0][0] == "repro.kernels.refine_merge"
    assert all(len(e) == 2 for e in bd["device_ops"] + bd["idle_gaps"])


def test_breakdown_keeps_ten_entries():
    ops = [Op(f"op{i}.{i}", i * 2 * MS, MS) for i in range(30)]
    ops += [Op(f"k{i}", 70 * MS + i * MS, 0.5 * MS) for i in range(12)]
    hlo = [trace.HloRow(f"jit(f)/op{i}", f"op{i}.{i}", 1e-3)
           for i in range(30)]
    hlo += [trace.HloRow(f"jit(f)/repro.kernels.k{i}/pallas_call", f"k{i}",
                         5e-4) for i in range(12)]
    r = trace.reduce(ops, [Span(trace.WINDOW, 0.0, 100 * MS)], hlo)
    bd = r.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


def test_no_ops_reads_nothing():
    r = trace.reduce([], [Span(trace.WINDOW, 0.0, 10 * MS)], [])
    assert r.busy_s == 0.0 and r.kernel_s == {}


def test_device_ops_without_the_table_are_an_error():
    """Kernel time is never read as 0 for want of the per-HLO-op table."""
    ops, spans, _ = _trace()
    with pytest.raises(ValueError, match="per-HLO-op table"):
        trace.reduce(ops, spans, [])


@pytest.mark.parametrize("texts,want", [
    (["fusion.1", "jit(f)/repro.kernels.refine_merge/pallas_call"],
     "refine_merge"),
    (["jit(run)/while/body/repro.kernels.gather_score/dot_general"],
     "gather_score"),
    (["jit(f)/outer/repro.kernels.a/repro.kernels.ivf_scan/x"], "ivf_scan"),
    (["jit(f)/sort", "copy.2"], None),
])
def test_scope_of(texts, want):
    assert trace.scope_of(texts) == want


def test_union_and_gaps_helpers():
    iv = [(0, 10), (5, 15), (20, 30)]
    assert trace.union_ns(iv, 0, 40) == 25
    assert trace.gaps_ns(iv, 0, 40) == [(15, 20), (30, 40)]
    assert trace.union_ns(iv, 8, 22) == 9


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

def test_refine_work_hand_worked():
    # 1,000 rows x 136 candidates x d 128, kappa 50
    f, b = work.refine(1000, 136, 128, 50)
    assert f == 2 * 1000 * 136 * 128
    assert b == (1000 * 136 * 128 * 4 + 1000 * 128 * 4 + 1000 * 136 * 8
                 + 2 * 1000 * 50 * 8)


def test_graph_build_work_is_init_plus_rounds():
    f, b = work.graph_build(n=10, d=4, kappa=3, tau=2, cap=8, spill=2)
    f0, b0 = work.refine(10, 3, 4, 3)
    f1, b1 = work.refine(10, 10, 4, 3)
    assert (f, b) == (f0 + 2 * f1, b0 + 2 * b1)


def test_engine_and_scan_work_hand_worked():
    f, b = work.engine_scoring(rows=1024, cands=50, d=128)
    assert f == 2 * 1024 * 51 * 128
    assert b == 1024 * 51 * 128 * 4 + 1024 * 128 * 4 + 1024 * 51 * 8 \
        + 1024 * 50 * 8
    f, b = work.ivf_scan(scanned_rows=5000, queries=2, d=960, topk=10)
    assert f == 2 * 5000 * 960
    assert b == 5000 * (960 * 4 + 4) + 2 * 960 * 4 + 2 * 10 * 8
    f, b = work.ivf_scan_adc(scanned_rows=5000, queries=2, nsub=8, width=256,
                             depth=100)
    assert f == 2 * 5000 * 8
    assert b == 5000 * 16 + 2 * 8 * 256 * 4 + 2 * 100 * 12


def test_roofline_share_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    # 819 MB in 2 ms at 819 GB/s: 1 ms least time -> 50 %, memory bound
    share, bound = work.roofline_share(1.0, 819e6, 2e-3, pk)
    assert share == pytest.approx(50.0) and bound == "memory"
    share, bound = work.roofline_share(197e9, 1.0, 4e-3, pk)
    assert share == pytest.approx(25.0) and bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_kernel_time_from_the_per_hlo_op_table():
    """On a TPU the op events carry no metadata: the per-HLO-op table's
    framework op names and self times give the kernel time, summed over
    every op of a scope."""
    ops, spans, _ = _trace()
    hlo = [trace.HloRow("jit(_build_single)/while/body/repro.kernels."
                        "refine_merge/pallas_call", "refine_merge.13", 0.012),
           trace.HloRow("jit(run)/repro.kernels.gather_score/dot_general",
                        "fusion.2", 0.004),
           trace.HloRow("jit(run)/repro.kernels.gather_score/pallas_call",
                        "gather_score.1", 0.003),
           trace.HloRow("jit(_build_single)/while/body/sort", "sort.5", 0.020),
           # a kernel run as a program of its own: no scope, its Pallas
           # call named after it; the wrapper's own ops are not the kernel
           trace.HloRow("jit(ivf_scan)/pallas_call:", "ivf_scan.1", 0.002),
           trace.HloRow("jit(ivf_scan)/reduce_sum:", "multiply_reduce_fusion",
                        0.001)]
    r = trace.reduce(ops, spans, hlo)
    assert r.kernel_s == pytest.approx({"refine_merge": 0.012,
                                        "gather_score": 0.007,
                                        "ivf_scan": 0.002})
    assert r.nonkernel_s == pytest.approx(0.021)
    assert r.busy_s == pytest.approx(0.055)       # still from the op events
    assert r.ops[0] == ("sort", pytest.approx(0.020))
    assert trace.kernel_of(trace.HloRow("jit(f)/fusion", "fusion.3", 1.0)) \
        is None
