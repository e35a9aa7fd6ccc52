"""repro.analysis — the lint engine and every registered rule.

Layout: one TP/TN pair per rule (fixture trees written under tmp_path so
path-scoped rules see realistic ``src/repro/...`` layouts), then the
engine mechanics (suppressions, baseline add/expire, CLI exit codes),
then the meta checks: the real tree runs clean, and the migrated rules
agree with the ad-hoc scans they replaced on a mixed fixture tree.

Fixture sources live in strings — string constants are not code, so the
rules scanning *this* file (they mostly skip tests anyway) never see
them.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import all_rules, analyze_paths
from repro.analysis.suppress import (apply_baseline, load_baseline,
                                     write_baseline)

ROOT = Path(__file__).resolve().parent.parent


def write_tree(base: Path, files):
    for rel, source in files.items():
        p = base / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(source))
    return base


def run_rule(base: Path, rule: str):
    findings, errors, _ = analyze_paths([base], select=[rule], root=base)
    assert not errors, [e.render() for e in errors]
    return findings


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_has_the_contracted_rules():
    ids = {r.id for r in all_rules()}
    assert len(ids) >= 8
    assert {"facade-boundary", "runtime-placement", "shardmap-sort",
            "prng-key-reuse", "prng-literal-key", "trace-purity",
            "lock-discipline", "deprecation-stacklevel", "deprecated-call",
            "pallas-kernel"} <= ids
    for r in all_rules():
        assert r.summary and r.rationale


# ---------------------------------------------------------------------------
# per-rule TP/TN fixtures
# ---------------------------------------------------------------------------

def test_facade_boundary(tmp_path):
    write_tree(tmp_path, {
        "src/repro/data/sel.py": "from repro.sampling import SpectralCache\n",
        "src/repro/serving/fe.py": "from ..learning import engine\n",
        "src/repro/data/ok.py": "from repro import dpp\n",
        "src/repro/sampling/internal.py":
            "from repro.sampling import batched\n",   # engine-internal: fine
        "tests/test_x.py": "from repro.sampling import SpectralCache\n",
    })
    found = {f.path for f in run_rule(tmp_path, "facade-boundary")}
    assert found == {"src/repro/data/sel.py", "src/repro/serving/fe.py"}


def test_runtime_placement(tmp_path):
    dev, host = "dev" + "ice", "ho" + "st"   # keep this file self-clean
    flag = "--dist" + "ributed"
    write_tree(tmp_path, {
        "src/repro/data/a.py":
            f'def f(m, k):\n    return m.sample(k, 4, backend="{dev}")\n',
        "src/repro/data/b.py":
            f'FLAG = "{flag}"\n',
        "src/repro/launch/learn.py":
            f'FLAG = "{flag}"  # the shim definition itself\n',
        "src/repro/data/ok.py":
            f'def f(m, k):\n'
            f'    return m.sample(k, 4, backend="pallas")  # kernel axis\n',
        "src/repro/data/prose.py":
            f'"""Long docstring mentioning {host} placement in prose."""\n',
    })
    found = {(f.path, f.line)
             for f in run_rule(tmp_path, "runtime-placement")}
    assert found == {("src/repro/data/a.py", 2), ("src/repro/data/b.py", 1)}


def test_shardmap_sort(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/bad.py": """\
            import jax

            def make(mesh, specs):
                def body(x, key):
                    pick = jax.random.choice(key, x.shape[0], (3,),
                                             replace=False)
                    return jax.numpy.sort(x[pick])
                return shard_map_unchecked(body, mesh, specs, specs)
            """,
        "src/repro/core/ok.py": """\
            import jax

            def outside(x):
                return jax.numpy.sort(x)     # not inside a shard_map

            def make(mesh, specs, fn):
                def body(x):
                    return x - x.mean()
                shard_map_unchecked(body, mesh, specs, specs)
                return shard_map_unchecked(fn, mesh, specs, specs)  # opaque: skip
            """,
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "shardmap-sort")]
    assert found == [("src/repro/core/bad.py", 5),
                     ("src/repro/core/bad.py", 7)]


def test_prng_key_reuse(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/bad.py": """\
            import jax

            def draw(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
            """,
        "src/repro/core/ok.py": """\
            import jax

            def draw(key):
                key, sub = jax.random.split(key)
                a = jax.random.normal(sub, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b

            def streams(key, n):
                # fold_in derives, it does not consume (TenantKeyring)
                return [jax.random.normal(jax.random.fold_in(key, i), (2,))
                        for i in range(n)]

            def branches(key, flip):
                if flip:
                    return jax.random.normal(key, (2,))
                else:
                    return jax.random.uniform(key, (2,))
            """,
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "prng-key-reuse")]
    assert found == [("src/repro/core/bad.py", 5)]


def test_prng_literal_key(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/bad.py":
            "import jax\nK = jax.random.PRNGKey(0)\n",
        "src/repro/core/ok.py":
            "import jax\ndef f(seed):\n    return jax.random.PRNGKey(seed)\n",
        "tests/test_x.py":
            "import jax\nK = jax.random.PRNGKey(0)\n",   # tests pin seeds
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "prng-literal-key")]
    assert found == [("src/repro/core/bad.py", 2)]


def test_trace_purity(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/bad.py": """\
            import time
            import jax

            @jax.jit
            def step(x):
                print("tracing", x)
                t0 = time.perf_counter()
                tracker.counter("steps", 1)
                return x * 2

            def sweep(xs):
                def body(c, x):
                    tracker.gauge("c", c)
                    return c + x, x
                return jax.lax.scan(body, 0.0, xs)
            """,
        "src/repro/core/ok.py": """\
            import time
            import jax

            def run(x):
                t0 = time.perf_counter()       # host side: fine
                y = jax.jit(lambda v: v * 2)(x)
                print("done", time.perf_counter() - t0)
                return y
            """,
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "trace-purity")]
    assert found == [("src/repro/core/bad.py", 6),
                     ("src/repro/core/bad.py", 7),
                     ("src/repro/core/bad.py", 8),
                     ("src/repro/core/bad.py", 13)]


def test_lock_discipline(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/svc.py": """\
            import threading

            class Service:
                def __init__(self):
                    self._pending = []        #: guarded-by: _lock
                    self._lock = threading.RLock()

                def bad(self):
                    return len(self._pending)

                def good(self):
                    with self._lock:
                        return len(self._pending)

                def _peek_locked(self):
                    return self._pending[-1]

                def unrelated(self):
                    return 7
            """,
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "lock-discipline")]
    assert found == [("src/repro/core/svc.py", 9)]


def test_deprecation_stacklevel(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/shims.py": """\
            import warnings

            def bad():
                warnings.warn("old api", DeprecationWarning)

            def bad_level():
                warnings.warn("old api", DeprecationWarning, stacklevel=1)

            def good():
                warnings.warn("old api", DeprecationWarning, stacklevel=2)

            def good_var(depth):
                warnings.warn("old api", DeprecationWarning, stacklevel=depth)

            def unrelated():
                warnings.warn("heads up", UserWarning)
            """,
    })
    found = [(f.path, f.line)
             for f in run_rule(tmp_path, "deprecation-stacklevel")]
    assert found == [("src/repro/core/shims.py", 4),
                     ("src/repro/core/shims.py", 7)]


def test_deprecated_call(tmp_path):
    write_tree(tmp_path, {
        "src/repro/data/bad.py": "from repro.core import fit_em\n",
        "src/repro/learning/ok.py":
            "from repro.core.em import fit_em  # defining submodule: fine\n",
        "src/repro/core/em.py": "def fit_em():\n    pass\n",
        "tests/test_x.py":
            "from repro.core import fit_em  # tests pin shim behavior\n",
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "deprecated-call")]
    assert found == [("src/repro/data/bad.py", 1)]


def test_pallas_kernel(tmp_path):
    write_tree(tmp_path, {
        "src/repro/kernels/bad.py": """\
            from jax.experimental import pallas as pl

            def wrapper(x):
                scale = x.mean()

                def _kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...] * scale
                    if x_ref[0] > 0:
                        o_ref[0] = 0.0

                return pl.pallas_call(_kernel, grid=(1,))(x)
            """,
        "src/repro/kernels/ok.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref, *, n_tiles, scale):
                for t in range(n_tiles):      # static unroll: fine
                    o_ref[t] = x_ref[t] * scale

            def wrapper(x, n_tiles):
                kern = functools.partial(_kernel, n_tiles=n_tiles, scale=2.0)
                return pl.pallas_call(kern, grid=(1,))(x)
            """,
    })
    found = [(f.path, f.line) for f in run_rule(tmp_path, "pallas-kernel")]
    assert found == [("src/repro/kernels/bad.py", 7),
                     ("src/repro/kernels/bad.py", 8)]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line_and_line_above(tmp_path):
    write_tree(tmp_path, {
        "src/repro/core/a.py":
            "import jax\n"
            "K = jax.random.PRNGKey(0)  # repro: ignore[prng-literal-key]\n",
        "src/repro/core/b.py":
            "import jax\n"
            "# justified exception  # repro: ignore[prng-literal-key]\n"
            "K = jax.random.PRNGKey(0)\n",
        "src/repro/core/c.py":
            "import jax\n"
            "# repro: ignore[some-other-rule]\n"
            "K = jax.random.PRNGKey(0)\n",
    })
    found = {f.path for f in run_rule(tmp_path, "prng-literal-key")}
    assert found == {"src/repro/core/c.py"}   # wrong id does not suppress


def test_suppression_only_counts_comment_lines_above(tmp_path):
    # code on the line above carrying an unrelated trailing suppression
    # must not leak onto the next line
    write_tree(tmp_path, {
        "src/repro/core/a.py":
            "import jax\n"
            "x = 1  # repro: ignore[prng-literal-key]\n"
            "K = jax.random.PRNGKey(0)\n",
    })
    assert len(run_rule(tmp_path, "prng-literal-key")) == 1


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_grandfathers_then_goes_stale(tmp_path):
    tree = write_tree(tmp_path / "t", {
        "src/repro/core/a.py": "import jax\nK = jax.random.PRNGKey(0)\n",
    })
    findings = run_rule(tree, "prng-literal-key")
    assert len(findings) == 1
    bl = tmp_path / "bl.json"
    write_baseline(bl, findings)
    entries = load_baseline(bl)
    new, stale = apply_baseline(findings, entries)
    assert new == [] and stale == []          # grandfathered
    # fix the finding -> the entry is now stale and must be reported
    (tree / "src/repro/core/a.py").write_text(
        "import jax\ndef f(seed):\n    return jax.random.PRNGKey(seed)\n")
    new, stale = apply_baseline(run_rule(tree, "prng-literal-key"), entries)
    assert new == [] and len(stale) == 1
    # and expiring rewrites it away
    write_baseline(bl, [])
    assert load_baseline(bl) == []


def test_baseline_missing_is_empty_and_corrupt_raises(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == []
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError):
        load_baseline(bad)


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------

def _cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=cwd, capture_output=True, text=True,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"})


def test_cli_exit_codes(tmp_path):
    clean = write_tree(tmp_path / "clean", {
        "src/repro/core/ok.py": "import jax\n"})
    dirty = write_tree(tmp_path / "dirty", {
        "src/repro/core/a.py": "import jax\nK = jax.random.PRNGKey(0)\n"})
    broken = write_tree(tmp_path / "broken", {
        "src/repro/core/a.py": "def f(:\n"})
    bl = str(tmp_path / "bl.json")

    r = _cli([str(clean), "--baseline", bl], cwd=tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _cli([str(dirty), "--baseline", bl], cwd=tmp_path)
    assert r.returncode == 1 and "prng-literal-key" in r.stdout
    r = _cli([str(broken), "--baseline", bl], cwd=tmp_path)
    assert r.returncode == 2 and "internal:parse" in r.stderr
    r = _cli([str(clean), "--select", "no-such-rule", "--baseline", bl],
             cwd=tmp_path)
    assert r.returncode == 2


def test_cli_update_baseline_roundtrip_and_stale_gate(tmp_path):
    dirty = write_tree(tmp_path / "d", {
        "src/repro/core/a.py": "import jax\nK = jax.random.PRNGKey(0)\n"})
    bl = str(tmp_path / "bl.json")
    assert _cli([str(dirty), "--baseline", bl],
                cwd=tmp_path).returncode == 1
    assert _cli([str(dirty), "--baseline", bl, "--update-baseline"],
                cwd=tmp_path).returncode == 0
    assert _cli([str(dirty), "--baseline", bl],
                cwd=tmp_path).returncode == 0    # grandfathered
    # fix the finding: the stale entry must fail the run until expired
    (dirty / "src/repro/core/a.py").write_text("import jax\n")
    r = _cli([str(dirty), "--baseline", bl], cwd=tmp_path)
    assert r.returncode == 1 and "stale baseline" in r.stdout


def test_cli_json_report(tmp_path):
    dirty = write_tree(tmp_path / "d", {
        "src/repro/core/a.py": "import jax\nK = jax.random.PRNGKey(0)\n"})
    out = tmp_path / "report.json"
    r = _cli([str(dirty), "--baseline", str(tmp_path / "bl.json"),
              "--json", str(out)], cwd=tmp_path)
    assert r.returncode == 1
    report = json.loads(out.read_text())
    assert report["files"] == 1
    assert report["findings"][0]["rule"] == "prng-literal-key"


def test_cli_list_rules(tmp_path):
    r = _cli(["--list-rules"], cwd=tmp_path)
    assert r.returncode == 0
    for rule_id in ("facade-boundary", "pallas-kernel", "trace-purity"):
        assert rule_id in r.stdout


# ---------------------------------------------------------------------------
# internal errors must not green-light the tree
# ---------------------------------------------------------------------------

def test_rule_exception_is_an_internal_error(tmp_path):
    from repro.analysis import registry
    from repro.analysis.registry import Rule

    def boom(ctx):
        raise RuntimeError("rule bug")

    rule = Rule(id="boom-rule", summary="s", rationale="r", check=boom)
    tree = write_tree(tmp_path, {"src/repro/core/a.py": "x = 1\n"})
    registry._REGISTRY[rule.id] = rule
    try:
        findings, errors, _ = analyze_paths([tree], select=["boom-rule"],
                                            root=tree)
    finally:
        del registry._REGISTRY[rule.id]
    assert findings == []
    assert len(errors) == 1 and errors[0].rule == "boom-rule"
    assert "rule bug" in errors[0].detail


# ---------------------------------------------------------------------------
# the real tree is clean, and the migrated rules agree with the old scans
# ---------------------------------------------------------------------------

def test_repo_tree_runs_clean():
    """`python -m repro.analysis src tests` exits 0 — the acceptance gate.
    Run in-process for speed; the CLI contract is covered above."""
    findings, errors, n_files = analyze_paths(
        [ROOT / "src", ROOT / "tests"], root=ROOT)
    assert not errors, [e.render() for e in errors]
    assert not findings, [f.render() for f in findings]
    assert n_files > 100


def test_examples_and_benchmarks_run_clean():
    findings, errors, _ = analyze_paths(
        [ROOT / "examples", ROOT / "benchmarks"], root=ROOT)
    assert not errors, [e.render() for e in errors]
    assert not findings, [f.render() for f in findings]


def test_parity_with_the_migrated_adhoc_scans(tmp_path):
    """The facade-boundary and runtime-placement rules flag exactly the
    files the old test_dpp_facade/test_runtime AST scans would have, on a
    fixture tree containing both kinds of violation and clean decoys."""
    import ast as ast_mod
    dev = "dev" + "ice"
    tree = write_tree(tmp_path, {
        "src/repro/data/viol_import.py": "import repro.sampling.batched\n",
        "src/repro/launch/viol_from.py": "from repro.learning import fit\n",
        "src/repro/data/viol_backend.py":
            f'def f(m, k):\n    return m.sample(k, 1, backend="{dev}")\n',
        "src/repro/data/clean.py": "from repro import dpp\n",
        "examples/clean2.py": "from repro import dpp\n",
    })

    # --- the old ad-hoc logic, verbatim in spirit ---
    old_facade, old_placement = set(), set()
    for path in sorted(tree.rglob("*.py")):
        mod_tree = ast_mod.parse(path.read_text())
        rel = path.relative_to(tree).as_posix()
        for node in ast_mod.walk(mod_tree):
            if isinstance(node, ast_mod.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast_mod.ImportFrom):
                mods = [("." * node.level) + (node.module or "")]
            else:
                mods = []
            for mod in mods:
                flat = mod.lstrip(".")
                if flat.startswith(("sampling", "learning")) \
                        or "repro.sampling" in mod or "repro.learning" in mod:
                    old_facade.add(rel)
            if isinstance(node, ast_mod.Call):
                for kw in node.keywords:
                    if kw.arg == "backend" \
                            and isinstance(kw.value, ast_mod.Constant) \
                            and kw.value.value in ("dev" + "ice", "ho" + "st"):
                        old_placement.add(rel)

    new_facade = {f.path for f in run_rule(tree, "facade-boundary")}
    new_placement = {f.path for f in run_rule(tree, "runtime-placement")}
    assert new_facade == old_facade == {"src/repro/data/viol_import.py",
                                        "src/repro/launch/viol_from.py"}
    assert new_placement == old_placement == {
        "src/repro/data/viol_backend.py"}
