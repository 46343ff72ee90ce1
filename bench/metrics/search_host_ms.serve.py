"""Median host ms of the window's ``repro.search`` spans: how long
``index.search`` holds the host to dispatch one batch (the span records
dispatch only; the batch's wait for the device lies outside it).  The
window's spans are the last ``calls`` the program's span ring recorded
(``repro.obs.timing.recent``); None for a program without that ring."""
import statistics


def read(ctx):
    from repro.obs import timing

    recent = getattr(timing, "recent", None)
    n = ctx.counts.get("calls", 0)
    spans = recent("repro.search", n) if recent and n > 0 else []
    if len(spans) < max(n, 1):
        return None
    return 1e-6 * statistics.median(s.end_ns - s.start_ns for s in spans)
