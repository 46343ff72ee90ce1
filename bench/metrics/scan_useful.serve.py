"""Share of the scan grid's rows that hold live rows of the probed lists,
%: the live rows that ``bench/work.py`` counts for the window, over the
``grid_rows`` that the window's ``repro.search`` spans count (every probed
list walked to the longest list's tiles).  Both sides are taken as
operations, 2·d per row scored against a query (the work's flops, the
spans' ``grid_flops``), so the ratio is rows over rows.  None for a program
whose spans carry no such counts."""


def read(ctx):
    from repro.obs import timing

    recent = getattr(timing, "recent", None)
    n = ctx.counts.get("calls", 0)
    spans = recent("repro.search", n) if recent and n > 0 else []
    grid = sum(s.counts.get("grid_flops", 0) for s in spans)
    if len(spans) < max(n, 1) or grid <= 0 or "ivf_scan" not in ctx.work:
        return None
    return 100.0 * ctx.work["ivf_scan"][0] / grid
