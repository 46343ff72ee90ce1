"""Device ms per graph-build round under the ``repro.graph.candidates``
scope: candidate rows and ids from the member table, the self mask, and
the chunking of the refine step round the ``refine_merge`` kernel."""
import re

# an op group's key (bench/trace.py) names the scopes its ops ran under;
# the group belongs to the innermost repro.<layer>.<part> that is not a
# kernel's
PART = re.compile(r"repro\.(?!kernels\.)[A-Za-z0-9_]+\.[A-Za-z0-9_]+")


def read(ctx):
    n = ctx.counts.get("rounds", 0)
    ms = [s for key, s in ctx.reduction.ops
          if PART.findall(key)[-1:] == ["repro.graph.candidates"]]
    if n <= 0 or not ms:
        return None
    return 1e3 * sum(ms) / n
