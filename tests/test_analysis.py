"""Fixture tests for the repro.analysis static-analysis pass.

The linter and baseline are exercised on *planted-violation* trees built in
tmp_path (the same rule code CI runs on the real tree), the contract
auditor's assertions on a tiny shard_map program in a 2-virtual-device
subprocess.  The last test runs the real linter over the real repo so the
shipped tree can never drift from its zero-entry lint baseline without a
test failing locally too.
"""
import os
import subprocess
import sys
import textwrap

from repro.analysis import baseline as bl
from repro.analysis.astlint import (Finding, LintConfig, RegistryConfig,
                                    lint_file, run_lint)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))
    return rel


def _cfg(root, registry=None):
    # template exemption off in fixtures: every pattern must match a file,
    # and the planted trees don't carry the LLM scaffolding
    return LintConfig(root=str(root), template_exempt=(), registry=registry)


# --------------------------------------------------------------------------
# layer 1: idiom rules on planted violations
# --------------------------------------------------------------------------


def test_planted_item_flagged_at_line(tmp_path):
    rel = _write(tmp_path, "src/repro/core/engine.py", """\
        import jax.numpy as jnp

        def step(x):
            total = jnp.sum(x)
            return total.item()
        """)
    findings, _ = run_lint(_cfg(tmp_path))
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("sync-idiom", rel, 5)]


def test_planted_sync_idioms_all_fire(tmp_path):
    _write(tmp_path, "src/repro/core/engine.py", """\
        import jax
        import numpy as np

        def bad(x):
            a = jax.device_get(x)
            b = np.asarray(x)
            c = float(x)
            d = float(3.0)        # constant: no traced value, no sync
            return a, b, c, d
        """)
    findings, _ = run_lint(_cfg(tmp_path))
    assert all(f.rule == "sync-idiom" for f in findings)
    assert sorted(f.line for f in findings) == [5, 6, 7]


def test_boundary_waiver_suppresses(tmp_path):
    _write(tmp_path, "src/repro/core/engine.py", """\
        def ok(x):
            a = x.item()  # lint: boundary(trace-edge readback)
            # lint: boundary(host diagnostic)
            b = float(x)
            return a, b

        def still_bad(x):
            return x.item()
        """)
    findings, _ = run_lint(_cfg(tmp_path))
    assert [(f.rule, f.line) for f in findings] == [("sync-idiom", 8)]


def test_sync_idiom_only_in_device_modules(tmp_path):
    # the same .item() outside the device-resident set is fine
    _write(tmp_path, "src/repro/data/loader.py", """\
        def host_side(x):
            return x.item()
        """)
    findings, _ = run_lint(_cfg(tmp_path))
    assert findings == []


def test_permute_and_wallclock_rules(tmp_path):
    _write(tmp_path, "src/repro/core/shuffle.py", """\
        import time
        import jax

        def shuffle(key, n):
            t0 = time.perf_counter()
            return jax.random.permutation(key, n), t0
        """)
    # the sanctioned homes stay quiet
    _write(tmp_path, "src/repro/core/permute.py", """\
        import jax

        def feistel(key, n):
            return jax.random.permutation(key, n)  # transitional fallback
        """)
    _write(tmp_path, "src/repro/obs/timing.py", """\
        import time

        def now():
            return time.perf_counter()
        """)
    findings, _ = run_lint(_cfg(tmp_path))
    assert sorted((f.rule, f.path) for f in findings) == [
        ("permute-in-core", "src/repro/core/shuffle.py"),
        ("wallclock", "src/repro/core/shuffle.py")]


def test_parse_error_is_a_finding(tmp_path):
    findings = lint_file("src/repro/core/engine.py", "def broken(:\n",
                         _cfg(tmp_path))
    assert [f.rule for f in findings] == ["parse-error"]


# --------------------------------------------------------------------------
# layer 1: kernel-registry cross-reference on a planted tree
# --------------------------------------------------------------------------

_REGISTRY_FILES = {
    "src/repro/kernels/ref.py": """\
        def good_kernel(x):
            return x
        """,
    "src/repro/launch/roofline.py": """\
        KERNEL_INVENTORY = {
            "good_kernel": {"flops": lambda n, d: 2 * n * d},
        }
        """,
    "benchmarks/kernels_bench.py": """\
        def cases(bench):
            bench(kernel="good_kernel", shape={"n": 8, "d": 4}, make=None)
        """,
    "src/repro/kernels/autotune.py": """\
        SWEEP_TILES = {}
        """,
}


def _registry_tree(tmp_path, kernel_src):
    for rel, text in _REGISTRY_FILES.items():
        _write(tmp_path, rel, text)
    _write(tmp_path, "src/repro/kernels/fake.py", kernel_src)
    return _cfg(tmp_path, registry=RegistryConfig())


def test_unregistered_kernel_all_four_findings(tmp_path):
    cfg = _registry_tree(tmp_path, """\
        import pallas as pl

        def fake_kernel(x):
            return pl.pallas_call(None)(x)
        """)
    findings, _ = run_lint(cfg)
    msgs = [f.message for f in findings]
    assert len(findings) == 4 and all(
        f.rule == "kernel-registry" and f.path == "src/repro/kernels/fake.py"
        for f in findings)
    for want in ("no src/repro/kernels/ref.py oracle",
                 "no KERNEL_INVENTORY entry",
                 "no benchmarks/kernels_bench.py case",
                 "neither in SWEEP_TILES"):
        assert any(want in m for m in msgs), (want, msgs)


def test_registered_kernel_with_exempt_comment_is_clean(tmp_path):
    cfg = _registry_tree(tmp_path, """\
        # autotune: exempt(good_kernel): fixture has no tile knob
        import pallas as pl

        def good_kernel(x):
            return pl.pallas_call(None)(x)
        """)
    findings, _ = run_lint(cfg)
    assert findings == []


def test_bench_shape_keys_must_match_flop_model(tmp_path):
    cfg = _registry_tree(tmp_path, """\
        # autotune: exempt(good_kernel): fixture
        import pallas as pl

        def good_kernel(x):
            return pl.pallas_call(None)(x)
        """)
    _write(tmp_path, "benchmarks/kernels_bench.py", """\
        def cases(bench):
            bench(kernel="good_kernel", shape={"n": 8, "k": 2}, make=None)
        """)
    findings, _ = run_lint(cfg)
    assert [f.rule for f in findings] == ["kernel-registry"]
    assert "shape keys ('n', 'k') != inventory flop-model args ('n', 'd')" \
        in findings[0].message


def test_private_def_pallas_call_flagged(tmp_path):
    cfg = _registry_tree(tmp_path, """\
        import pallas as pl

        def _hidden(x):
            return pl.pallas_call(None)(x)
        """)
    findings, _ = run_lint(cfg)
    assert len(findings) == 1
    assert "not inside a public top-level entry point" in findings[0].message


# --------------------------------------------------------------------------
# baseline: add -> suppress -> regress -> stale
# --------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    path = str(tmp_path / "baseline.json")
    key = Finding("sync-idiom", "src/repro/core/engine.py", 5,
                  ".item() forces a device->host sync").key()

    # 1. a new finding against an empty baseline fails as NEW
    assert bl.load(path)["lint"] == []
    probs = bl.compare([key], bl.load(path)["lint"], section="lint")
    assert probs and "NEW" in probs[0]

    # 2. baselining it suppresses exactly that key
    bl.save({"lint": [key]}, path)
    assert bl.compare([key], bl.load(path)["lint"], section="lint") == []

    # 3. a second (regressed) finding still fails, with the new key named
    key2 = key.replace("engine", "graph_build")
    probs = bl.compare([key, key2], bl.load(path)["lint"], section="lint")
    assert len(probs) == 1 and key2 in probs[0] and "NEW" in probs[0]

    # 4. fixing the violation makes the baseline entry STALE -> also fails
    probs = bl.compare([], bl.load(path)["lint"], section="lint")
    assert len(probs) == 1 and "STALE" in probs[0] and key in probs[0]


def test_baseline_rejects_wrong_schema(tmp_path):
    path = str(tmp_path / "baseline.json")
    with open(path, "w") as f:
        f.write('{"schema": "something.else", "lint": []}')
    try:
        bl.load(path)
        assert False, "expected ValueError"
    except ValueError as e:
        assert "schema" in str(e)


# --------------------------------------------------------------------------
# layer 2: audit_trace assertions on a tiny program (2-device subprocess)
# --------------------------------------------------------------------------

_AUDIT_FIXTURE = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.contracts import audit_trace

from repro.launch.mesh import data_mesh
mesh = data_mesh(2)
prog = jax.jit(shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P()))
low = prog.lower(jnp.zeros((8,), jnp.float32))

# the psum IS an all-reduce: an empty declared budget must fail ...
bad = audit_trace("fixture", low, collectives={})
assert bad.collectives.get("all-reduce"), bad.collectives
assert not bad.ok and any("collective counts" in p for p in bad.problems), \\
    bad.problems

# ... and declaring the measured count passes every other assertion too
ok = audit_trace("fixture", low, collectives=bad.collectives)
assert ok.ok, ok.problems

# f64 in the trace violates the no-f64 contract
jax.config.update("jax_enable_x64", True)
low64 = jax.jit(lambda x: x * 2.0).lower(jnp.zeros((4,), jnp.float64))
r64 = audit_trace("fixture64", low64, collectives={})
assert any("f64" in p for p in r64.problems), r64.problems

print("AUDIT_FIXTURE_OK")
"""


def test_audit_trace_collective_and_f64_contracts():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", _AUDIT_FIXTURE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "AUDIT_FIXTURE_OK" in proc.stdout


# --------------------------------------------------------------------------
# the real tree stays clean (same invocation CI runs)
# --------------------------------------------------------------------------


def test_real_tree_lints_clean_against_baseline():
    findings, exempt = run_lint(LintConfig(root=REPO))
    base = bl.load()
    assert bl.compare(sorted({f.key() for f in findings}),
                      base.get("lint", []), section="lint") == [], \
        [str(f) for f in findings]
    assert exempt, "template exemption list should match real files"
