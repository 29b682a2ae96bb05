"""AST lint (Pass 3): synthetic offending snippets + the baseline gate.

Each rule is exercised against a minimal offending snippet AND a minimal
clean one; the tier-1 gate test asserts the real package produces zero
findings outside the committed baseline (zero-NEW, not zero — accepted
host-boundary syncs stay baselined with a justification each).
"""

import textwrap

import pytest

from hetu_galvatron_tpu.analysis.lint import (
    lint_file,
    lint_package,
    load_baseline,
    new_findings,
    stale_baseline,
)

pytestmark = [pytest.mark.staticcheck, pytest.mark.utils]


def lint_src(tmp_path, src, rel="runtime/trainer.py", hot_path=True):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p), rel, hot_path=hot_path)


def rules(findings):
    return [f.rule for f in findings]


def test_gal001_host_sync_in_hot_path(tmp_path):
    src = """
    import numpy as np
    def step(metrics, arr):
        a = metrics["loss"].item()
        b = np.asarray(arr)
        c = jax.device_get(arr)
        return a, b, c
    """
    fs = lint_src(tmp_path, src)
    assert rules(fs) == ["GAL001", "GAL001", "GAL001"]
    # the same code OUTSIDE a hot-path module is not a finding
    assert lint_src(tmp_path, src, rel="cli/summarize.py",
                    hot_path=False) == []


def test_gal002_jit_inside_loop(tmp_path):
    bad = """
    import jax
    def train(fns):
        for m in range(4):
            fns[m] = jax.jit(lambda x: x)
    """
    good = """
    import jax
    def build():
        return jax.jit(lambda x: x)
    """
    assert rules(lint_src(tmp_path, bad, hot_path=False)) == ["GAL002"]
    assert lint_src(tmp_path, good, hot_path=False) == []


def test_gal003_axis_name_canon(tmp_path):
    bad = """
    import jax
    from jax.sharding import PartitionSpec as P
    def f(x):
        y = jax.lax.psum(x, "tp")          # not a mesh axis name
        spec = P("stage", None)
        return jax.lax.ppermute(y, "model", [(0, 1)])
    """
    good = """
    import jax
    from jax.sharding import PartitionSpec as P
    def f(x, axes):
        y = jax.lax.psum(x, "d0")
        spec = P("pp", ("d0", "d1"), None)
        return jax.lax.ppermute(y, axes, [(0, 1)])
    """
    assert rules(lint_src(tmp_path, bad, hot_path=False)) == \
        ["GAL003", "GAL003", "GAL003"]
    assert lint_src(tmp_path, good, hot_path=False) == []


def test_gal004_dynamic_named_scope(tmp_path):
    bad = """
    import jax
    def f(i):
        with jax.named_scope(f"layer{i}/ring"):
            pass
        with jax.named_scope("ring" + str(i)):
            pass
    """
    good = """
    import jax
    SCOPE = "tp_ring"
    def f():
        with jax.named_scope(SCOPE):
            pass
        with jax.named_scope("cp_ring"):
            pass
    """
    assert rules(lint_src(tmp_path, bad, hot_path=False)) == \
        ["GAL004", "GAL004"]
    assert lint_src(tmp_path, good, hot_path=False) == []
    # a call is a computed name whatever it returns
    called = """
    import jax
    def f(i, B):
        with jax.named_scope(scope_of("tp_ring", i, B)):
            pass
    """
    assert rules(lint_src(tmp_path, called, hot_path=False)) == ["GAL004"]


def test_gal005_exception_swallowing(tmp_path):
    bad = """
    def f():
        try:
            g()
        except:
            pass
    def h():
        try:
            g()
        except Exception:
            pass
    """
    good = """
    def f(log):
        try:
            g()
        except ValueError:
            pass
        except Exception as e:
            log(f"swallowed: {e}")
    """
    assert rules(lint_src(tmp_path, bad, hot_path=False)) == \
        ["GAL005", "GAL005"]
    assert lint_src(tmp_path, good, hot_path=False) == []


def test_gal002_str_lower_is_not_a_lowering(tmp_path):
    """str.lower() in a loop (zero-arg by definition) must not read as
    jit AOT lowering; fn.lower(avals) in a loop must."""
    strings = """
    def norm(keys):
        out = []
        for k in keys:
            out.append(k.lower())
        return out
    """
    aot = """
    def costs(fn, shapes):
        for s in shapes:
            fn.lower(s)
    """
    assert lint_src(tmp_path, strings, hot_path=False) == []
    assert rules(lint_src(tmp_path, aot, hot_path=False)) == ["GAL002"]


def test_gal002_def_inside_loop_is_not_flagged(tmp_path):
    """A def nested in a loop runs only when called — the enclosing loop
    must not taint it; a jit INSIDE a comprehension is a per-element
    construction and IS flagged."""
    nested_def = """
    import jax
    def build(buckets):
        for b in buckets:
            def make():
                return jax.jit(lambda x: x)
    """
    comprehension = """
    import jax
    def build(fs):
        return [jax.jit(f) for f in fs]
    """
    assert lint_src(tmp_path, nested_def, hot_path=False) == []
    assert rules(lint_src(tmp_path, comprehension,
                          hot_path=False)) == ["GAL002"]


def test_fingerprints_are_line_number_free(tmp_path):
    a = lint_src(tmp_path, """
    def step(m):
        return m.item()
    """)
    b = lint_src(tmp_path, """
    # a comment pushing everything down


    def step(m):
        return m.item()
    """)
    assert a[0].fingerprint == b[0].fingerprint
    assert a[0].line != b[0].line


def test_duplicate_snippets_get_distinct_occurrences(tmp_path):
    fs = lint_src(tmp_path, """
    def step(a, b):
        x = a.item()
        x += 1
        x = a.item()
        return x
    """)
    assert len(fs) == 2
    assert fs[0].fingerprint != fs[1].fingerprint


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    fs = lint_src(tmp_path, "def broken(:\n", hot_path=False)
    assert rules(fs) == ["GAL000"]


def test_package_has_zero_new_findings():
    """THE tier-1 gate: every current finding is baselined (with a
    justification) and no baselined finding went stale without pruning."""
    findings = lint_package()
    baseline = load_baseline()
    new = new_findings(findings, baseline)
    assert new == [], (
        "new lint findings — fix them or baseline with a justification "
        "(python -m hetu_galvatron_tpu.cli.check --update-baseline):\n"
        + "\n".join(str(f) for f in new))
    stale = stale_baseline(findings, baseline)
    assert stale == [], (
        "baselined findings no longer occur; prune with --update-baseline: "
        f"{stale}")
    # every accepted finding carries a real justification
    assert all(j and not j.startswith("TODO") for j in baseline.values())


def test_gal006_env_read_outside_schema(tmp_path):
    """Every os.environ read form is flagged outside the schema/CLI
    boundary — and exempt inside it."""
    src = """
    import os
    def conf():
        a = os.environ.get("MY_KNOB")
        b = os.environ["MY_KNOB"]
        c = os.getenv("MY_KNOB", "1")
        return a, b, c
    """
    fs = lint_src(tmp_path, src, rel="runtime/newmod.py", hot_path=False)
    assert rules(fs) == ["GAL006", "GAL006", "GAL006"]
    # the schema and the CLI boundary are exempt
    for exempt in ("core/args_schema.py", "cli/serve.py"):
        assert lint_src(tmp_path, src, rel=exempt, hot_path=False) == []


def test_prune_baseline_roundtrip(tmp_path):
    """--prune-baseline: stale fingerprints are removed IN PLACE, live
    justifications survive untouched, and new findings are never
    auto-accepted — the committed baseline round-trips."""
    import json

    from hetu_galvatron_tpu.analysis.lint import prune_baseline

    src = """
    import os
    def conf():
        return os.getenv("X")
    """
    fs = lint_src(tmp_path, src, rel="runtime/m.py", hot_path=False)
    assert len(fs) == 1
    live = fs[0].fingerprint
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": {
        live: "audited: reason",
        "GAL006:runtime/gone.py:f:os.getenv('Y')#0": "stale entry",
    }}))
    removed = prune_baseline(fs, str(bl))
    assert removed == ["GAL006:runtime/gone.py:f:os.getenv('Y')#0"]
    after = json.loads(bl.read_text())["findings"]
    assert after == {live: "audited: reason"}
    # idempotent: nothing stale left, file untouched
    assert prune_baseline(fs, str(bl)) == []
    assert json.loads(bl.read_text())["findings"] == after
    # a NEW finding (not in the baseline) is NOT added by pruning
    assert live in after and len(after) == 1


def test_injected_hot_path_item_fails_the_gate(tmp_path):
    """The acceptance drill: an injected .item() in step code is a NEW
    finding naming the file."""
    src = """
    def train_step(sp, opt, batch, metrics):
        loss = metrics["loss"].item()
        return loss
    """
    fs = lint_src(tmp_path, src, rel="runtime/trainer.py", hot_path=True)
    baseline = load_baseline()
    new = new_findings(fs, baseline)
    assert len(new) == 1
    assert new[0].rule == "GAL001"
    assert "runtime/trainer.py" in str(new[0])
    assert ".item()" in new[0].message
