"""Device ms per engine epoch under the ``repro.engine.move`` scope: the
best move from the kernel's scores, the leaver guard, the D/cnt scatters
and the assignment update."""
import re

# an op group's key (bench/trace.py) names the scopes its ops ran under;
# the group belongs to the innermost repro.<layer>.<part> that is not a
# kernel's
PART = re.compile(r"repro\.(?!kernels\.)[A-Za-z0-9_]+\.[A-Za-z0-9_]+")


def read(ctx):
    n = ctx.counts.get("epochs", 0)
    ms = [s for key, s in ctx.reduction.ops
          if PART.findall(key)[-1:] == ["repro.engine.move"]]
    if n <= 0 or not ms:
        return None
    return 1e3 * sum(ms) / n
