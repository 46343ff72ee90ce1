"""Device ms per graph-build round outside every kernel scope and every
``repro.<layer>.<part>`` scope: what the ``repro.graph.*`` scopes leave
unsplit.  None where no op ran under a ``repro.graph.*`` scope."""
import re

# an op group's key (bench/trace.py) names the scopes its ops ran under;
# the group belongs to the innermost repro.<layer>.<part> that is not a
# kernel's
PART = re.compile(r"repro\.(?!kernels\.)[A-Za-z0-9_]+\.[A-Za-z0-9_]+")


def read(ctx):
    n = ctx.counts.get("rounds", 0)
    parts = [(PART.findall(key)[-1:], s) for key, s in ctx.reduction.ops
             if not key.startswith("repro.kernels.")]
    if n <= 0 or not any(p[0].startswith("repro.graph.")
                         for p, _ in parts if p):
        return None
    return 1e3 * sum(s for p, s in parts if not p) / n
