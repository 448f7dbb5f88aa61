"""graftlint: analyzer unit tests on synthetic fixtures + the tree gate.

Each analyzer gets positive (true-positive catch), negative (idiomatic
clean code) and suppressed (`# graftlint: disable=...`) cases, then
`test_tree_is_clean` runs the full suite over the serving tree so CI
fails on any new violation or baseline drift, and the CLI contract
(--json shape, --rule filter, exit codes) is pinned.

Fixtures lint with ``LintConfig(force_hot=True)`` so throwaway snippet
names count as hot-path modules; the glossary is overridden per test so
the metrics-name cases don't depend on docs/serving.md.
"""

import json
import textwrap

import pytest

from ray_tpu._private.lint import (LintConfig, default_rules,
                                   diff_baseline, lint_paths, lint_source,
                                   load_baseline)

pytestmark = pytest.mark.lint

TREE = ["ray_tpu/models", "ray_tpu/serve", "ray_tpu/util"]


def _lint(src, *, glossary=None, force_hot=True, path="<memory>.py"):
    cfg = LintConfig(force_hot=force_hot)
    if glossary is not None:
        cfg.glossary = frozenset(glossary)
    return lint_source(textwrap.dedent(src), path=path, config=cfg)


def _open(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def _suppressed(findings, rule):
    return [f for f in findings if f.rule == rule and f.suppressed]


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


class TestHostSync:
    def test_positive_asarray_on_device_value(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def hot(x):
                y = jnp.argmax(x, axis=-1)
                return np.asarray(y)
        """)
        hits = _open(findings, "host-sync")
        assert len(hits) == 1
        assert "device->host" in hits[0].message
        assert hits[0].symbol == "hot"

    def test_positive_jitted_result_through_tuple_unpack(self):
        findings = _lint("""
            import functools, jax, numpy as np

            @functools.partial(jax.jit, static_argnames=("n",))
            def fused(a, n):
                return a, a

            def hot(a):
                toks, extra = fused(a, 4)
                return float(toks)
        """)
        assert len(_open(findings, "host-sync")) == 1

    def test_positive_item_and_truthiness_and_device_get(self):
        findings = _lint("""
            import jax, jax.numpy as jnp

            def hot(x):
                y = jnp.sum(x)
                if y > 0:
                    return y.item()
                return jax.device_get(y)
        """)
        msgs = " | ".join(f.message for f in _open(findings, "host-sync"))
        assert len(_open(findings, "host-sync")) == 3
        assert "truthiness" in msgs and ".item()" in msgs and "device_get" in msgs

    def test_negative_host_values_and_metadata(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def hot(rows, x):
                a = np.asarray(rows, np.int32)   # host list: fine
                y = jnp.cumsum(x)
                n = y.shape[0]                   # metadata: no sync
                if n > 4:
                    a = a[:4]
                if y is None:
                    return None
                return int(a.max())              # numpy, untainted
        """)
        assert _open(findings, "host-sync") == []

    def test_negative_allowed_choke_point(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def _device_get(x):
                return np.asarray(jnp.asarray(x))
        """)
        assert _open(findings, "host-sync") == []

    def test_suppressed_with_reason(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def hot(x):
                y = jnp.argmax(x)
                return np.asarray(y)  # graftlint: disable=host-sync -- deliberate solo pull
        """)
        assert _open(findings, "host-sync") == []
        sup = _suppressed(findings, "host-sync")
        assert len(sup) == 1 and sup[0].reason == "deliberate solo pull"

    def test_cold_module_not_checked(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def cold(x):
                return np.asarray(jnp.argmax(x))
        """, force_hot=False, path="tooling.py")
        assert _open(findings, "host-sync") == []


# ---------------------------------------------------------------------------
# trace-guard
# ---------------------------------------------------------------------------


class TestTraceGuard:
    def test_positive_unguarded_span(self):
        findings = _lint("""
            class E:
                def step(self, t0):
                    self.trace.add("decode", t0, 1.0)
        """)
        hits = _open(findings, "trace-guard")
        assert len(hits) == 1
        assert "enabled" in hits[0].message

    def test_negative_if_guard_ternary_and_early_return(self):
        findings = _lint("""
            class E:
                def step(self, tr):
                    t0 = tr.now() if tr.enabled else 0.0
                    if self.trace.enabled:
                        self.trace.add("decode", t0, 1.0)

                def drain(self, etr):
                    if etr is None or not etr.enabled:
                        return
                    etr.instant("drain", 1)

                def cheap(self, tr):
                    tr.enabled and tr.mark("seam")
        """)
        assert _open(findings, "trace-guard") == []

    def test_negative_non_span_methods_and_non_tracers(self):
        findings = _lint("""
            class E:
                def go(self, history):
                    history.add("not a tracer", 1)
                    self.trace.dump("/tmp/out.json")
        """)
        assert _open(findings, "trace-guard") == []

    def test_negative_lane_helper_is_unguarded_by_design(self):
        findings = _lint("""
            class E:
                def drain(self, entry):
                    with self.trace.lane("host_drain", "drain",
                                         horizon=entry.H) as span:
                        with self.trace.lane("device_wait", "drain"):
                            block = entry.toks
                        span.note(bytes=block.nbytes)
        """)
        assert _open(findings, "trace-guard") == []

    def test_positive_ring_call_inside_a_lane_still_needs_its_guard(self):
        findings = _lint("""
            class E:
                def drain(self, t0):
                    with self.trace.lane("host_drain", "drain"):
                        self.trace.add("spec_verify", t0, 1.0, lane="drain")
        """)
        assert len(_open(findings, "trace-guard")) == 1

    def test_suppressed(self):
        findings = _lint("""
            class E:
                def step(self):
                    self.trace.instant("boot", 0)  # graftlint: disable=trace-guard -- one-shot boot span
        """)
        assert _open(findings, "trace-guard") == []
        assert len(_suppressed(findings, "trace-guard")) == 1


# ---------------------------------------------------------------------------
# jit-hygiene
# ---------------------------------------------------------------------------


class TestJitHygiene:
    def test_positive_jit_in_loop(self):
        findings = _lint("""
            import jax

            def build(fns):
                out = []
                for f in fns:
                    out.append(jax.jit(f))
                return out
        """)
        hits = _open(findings, "jit-hygiene")
        assert len(hits) == 1 and "loop" in hits[0].message

    def test_positive_donated_buffer_reused(self):
        findings = _lint("""
            import functools, jax

            @functools.partial(jax.jit, donate_argnames=("cache",))
            def fused(params, cache):
                return cache

            def hot(params, cache):
                new_cache = fused(params, cache)
                return cache.sum()
        """)
        hits = _open(findings, "jit-hygiene")
        assert len(hits) == 1 and "donated" in hits[0].message

    def test_positive_static_fed_len(self):
        findings = _lint("""
            import functools, jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def fused(a, n):
                return a

            def hot(a, items):
                return fused(a, len(items))
        """)
        hits = _open(findings, "jit-hygiene")
        assert len(hits) == 1 and "recompile" in hits[0].message

    def test_negative_rebind_on_call_line_and_bounded_static(self):
        findings = _lint("""
            import functools, jax

            @functools.partial(jax.jit, donate_argnames=("cache", "logits"),
                               static_argnames=("cfg",))
            def fused(params, cache, logits, cfg):
                return cache, logits

            def hot(self, params, cfg):
                self.cache, self.logits = fused(params, self.cache,
                                                self.logits, cfg)
                return self.cache
        """)
        assert _open(findings, "jit-hygiene") == []

    def test_suppressed(self):
        findings = _lint("""
            import functools, jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def fused(a, n):
                return a

            def hot(a, items):
                return fused(a, len(items))  # graftlint: disable=jit-hygiene -- bucketed upstream
        """)
        assert _open(findings, "jit-hygiene") == []
        assert len(_suppressed(findings, "jit-hygiene")) == 1


# ---------------------------------------------------------------------------
# metrics-name
# ---------------------------------------------------------------------------


class TestMetricsName:
    GLOSSARY = {"llm_engine_steps_total", "llm_fleet_*", "serve_llm_engine_*"}

    def test_positive_unconventional_prefix(self):
        findings = _lint("""
            from ray_tpu.util.metrics import Counter
            c = Counter("llm_widget_spins_total", "spins")
        """, glossary=self.GLOSSARY)
        hits = _open(findings, "metrics-name")
        assert len(hits) == 1 and "convention prefix" in hits[0].message

    def test_positive_undocumented_name(self):
        findings = _lint("""
            from ray_tpu.util.metrics import Counter
            c = Counter("llm_engine_undocumented_total", "mystery")
        """, glossary=self.GLOSSARY)
        hits = _open(findings, "metrics-name")
        assert len(hits) == 1 and "glossary" in hits[0].message

    def test_positive_dynamic_head_without_family(self):
        findings = _lint("""
            def g(field):
                return f"llm_engine_dyn_{field}"
        """, glossary=self.GLOSSARY)
        hits = _open(findings, "metrics-name")
        assert len(hits) == 1 and "glossary" in hits[0].message

    def test_negative_documented_wildcard_and_exact(self):
        findings = _lint("""
            from ray_tpu.util.metrics import Counter, Gauge

            __all__ = ["llm_helper"]

            def build(field):
                c = Counter("llm_engine_steps_total", "steps")
                g = Gauge(f"llm_fleet_{field}", "fleet stat")
                return c, g

            def report(stats, prefix="serve_llm_engine"):
                return prefix
        """, glossary=self.GLOSSARY)
        assert _open(findings, "metrics-name") == []

    def test_suppressed(self):
        findings = _lint("""
            NAME = "llm_deployment"  # graftlint: disable=metrics-name -- deployment id, not a metric
        """, glossary=self.GLOSSARY)
        assert _open(findings, "metrics-name") == []
        assert len(_suppressed(findings, "metrics-name")) == 1


# ---------------------------------------------------------------------------
# suppression parser v2
# ---------------------------------------------------------------------------


class TestSuppressionParserV2:
    def test_multi_rule_directive_suppresses_each_listed_rule(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def hot(x):
                y = jnp.argmax(x)
                return np.asarray(y)  # graftlint: disable=host-sync,trace-guard -- deliberate pull
        """)
        assert _open(findings, "host-sync") == []
        sup = _suppressed(findings, "host-sync")
        assert len(sup) == 1 and sup[0].reason == "deliberate pull"
        assert _open(findings, "suppression-syntax") == []

    def test_missing_reason_is_inert_and_flagged(self):
        findings = _lint("""
            import numpy as np, jax.numpy as jnp

            def hot(x):
                y = jnp.argmax(x)
                return np.asarray(y)  # graftlint: disable=host-sync
        """)
        # the underlying finding stays OPEN: a keep without a why is no keep
        assert len(_open(findings, "host-sync")) == 1
        assert _suppressed(findings, "host-sync") == []
        syn = _open(findings, "suppression-syntax")
        assert len(syn) == 1 and "reason" in syn[0].message

    def test_unknown_rule_name_flagged(self):
        findings = _lint("""
            n = 1  # graftlint: disable=hots-ync -- typo'd rule name
        """)
        syn = _open(findings, "suppression-syntax")
        assert len(syn) == 1 and "hots-ync" in syn[0].message

    def test_wildcard_with_reason_still_fine(self):
        findings = _lint("""
            import jax.numpy as jnp

            def hot(x):
                return float(jnp.sum(x))  # graftlint: disable=all -- bench harness line
        """)
        assert _open(findings, "host-sync") == []
        assert _open(findings, "suppression-syntax") == []


# ---------------------------------------------------------------------------
# v2 analyzers: path gating (full behavior is pinned by tests/lint_corpus/)
# ---------------------------------------------------------------------------


KV_LEAK = """
    class Engine:
        def leak(self, n):
            ids = self.kv_pool.alloc(n)
            if not ids:
                raise RuntimeError("oom")
            self.row_blocks[0] = ids
"""


def test_kv_refcount_gated_to_kv_modules():
    """The ownership analyzer runs only on the block-pool-touching files;
    an identical snippet under another name is out of scope."""
    hot = _lint(KV_LEAK, path="engine.py", force_hot=False)
    assert len(_open(hot, "kv-refcount")) == 1
    cold = _lint(KV_LEAK, path="router.py", force_hot=False)
    assert _open(cold, "kv-refcount") == []


def test_sharding_pin_gated_on_sharding_machinery():
    src = """
        class Engine:
            def swap(self, row):
                self._pool_k = self.host_pool[row]
    """
    # force_hot opts the snippet in even without `_shardings` in source
    assert len(_open(_lint(src), "sharding-pin")) == 1
    cold = _lint(src, force_hot=False, path="engine.py")
    assert _open(cold, "sharding-pin") == []


# ---------------------------------------------------------------------------
# the tree gate + baseline
# ---------------------------------------------------------------------------


def test_tree_is_clean():
    """CI contract: the serving tree has zero unsuppressed findings and
    the inline suppressions exactly match the checked-in baseline."""
    report = lint_paths(TREE)
    assert report.errors == []
    assert report.open == [], "\n" + report.format_text()
    assert diff_baseline(report, load_baseline()) == []


def test_baseline_drift_detected():
    report = lint_paths(TREE)
    baseline = load_baseline()
    assert baseline, "baseline should record the deliberate suppressions"
    tampered = baseline[:-1]  # drop one entry -> drift both directions
    assert diff_baseline(report, tampered)


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


BAD_SNIPPET = textwrap.dedent("""
    import numpy as np, jax.numpy as jnp

    def hot(x):
        return np.asarray(jnp.argmax(x))
""")


def test_cli_exit_codes_and_rule_filter(tmp_path, capsys):
    from tools.graft_lint import main

    bad = tmp_path / "engine.py"       # hot-path name triggers host-sync
    bad.write_text(BAD_SNIPPET)
    assert main([str(bad)]) == 1
    capsys.readouterr()
    # filtered to an unrelated rule the file passes
    assert main([str(bad), "--rule", "metrics-name"]) == 0
    capsys.readouterr()
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("host-sync", "trace-guard", "jit-hygiene", "metrics-name",
                 "kv-refcount", "flush-order", "sharding-pin",
                 "suppression-syntax"):
        assert rule in out


def test_cli_json_shape(tmp_path, capsys):
    from tools.graft_lint import main

    bad = tmp_path / "engine.py"
    bad.write_text(BAD_SNIPPET)
    assert main([str(bad), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["open_count"] == 1
    assert payload["files_scanned"] == 1
    (finding,) = payload["findings"]
    assert finding["rule"] == "host-sync"
    assert finding["symbol"] == "hot"
    assert not finding["suppressed"]


def test_cli_default_tree_clean(capsys):
    """The ISSUE acceptance command: exit 0 over the final tree."""
    from tools.graft_lint import main

    assert main(TREE) == 0


def test_cli_changed_mode(tmp_path, capsys, monkeypatch):
    """--changed lints exactly the git-reported files inside scope; an
    empty diff short-circuits to success without the drift check."""
    import tools.graft_lint as gl

    bad = tmp_path / "engine.py"
    bad.write_text(BAD_SNIPPET)
    clean = tmp_path / "router.py"
    clean.write_text("VERSION = 3\n")
    elsewhere = tmp_path / "outside" / "engine.py"
    elsewhere.parent.mkdir()
    elsewhere.write_text(BAD_SNIPPET)

    scope = tmp_path  # pass the scope dir positionally; outside/ is excluded

    monkeypatch.setattr(gl, "_changed_files",
                        lambda base, root: [bad, clean, elsewhere])
    # `elsewhere` is filtered out by scope, `bad` still fails the run
    assert gl.main(["--changed", str(scope / "engine.py"),
                    str(scope / "router.py")]) == 1
    out = capsys.readouterr().out
    assert "engine.py" in out and "outside" not in out

    monkeypatch.setattr(gl, "_changed_files", lambda base, root: [])
    assert gl.main(["--changed", str(scope)]) == 0
    assert "no changed python files" in capsys.readouterr().out


def test_changed_files_sees_worktree_state():
    """_changed_files vs HEAD returns a (possibly empty) list of existing
    .py paths — the live-repo smoke check for the git plumbing."""
    from tools.graft_lint import _REPO_ROOT, _changed_files

    files = _changed_files("HEAD", _REPO_ROOT)
    assert all(f.suffix == ".py" and f.exists() for f in files)


def test_unknown_rule_rejected():
    with pytest.raises(KeyError):
        default_rules(["no-such-rule"])
