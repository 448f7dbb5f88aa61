"""graftlint v2 regression corpus: per-analyzer positive/negative/suppressed
snippets under tests/lint_corpus/ (never imported — linted as AST).

Each corpus file carries an expectation row below: exact open/suppressed
finding counts for the rule it exercises, plus the invariant that NO rule
reports an unexpected open finding on any corpus file (the corpus is the
executable spec for analyzer precision — false positives here are bugs in
the analyzer, not the snippet).
"""

from pathlib import Path

import pytest

from ray_tpu._private.lint import LintConfig, lint_paths

pytestmark = pytest.mark.lint

CORPUS = Path(__file__).parent / "lint_corpus"

# file -> {rule: (expected_open, expected_suppressed)}
EXPECTATIONS = {
    "kv_refcount_pos.py": {"kv-refcount": (7, 0)},
    "kv_refcount_neg.py": {"kv-refcount": (0, 0)},
    "kv_refcount_sup.py": {"kv-refcount": (0, 1)},
    "flush_order_pos.py": {"flush-order": (3, 0)},
    "flush_order_neg.py": {"flush-order": (0, 0)},
    "flush_order_sup.py": {"flush-order": (0, 1)},
    "sharding_pin_pos.py": {"sharding-pin": (4, 0)},
    "sharding_pin_neg.py": {"sharding-pin": (0, 0)},
    "sharding_pin_sup.py": {"sharding-pin": (0, 1)},
    "host_sync_interproc_pos.py": {"host-sync": (2, 0)},
    "host_sync_interproc_neg.py": {"host-sync": (0, 0)},
    # The inert (reason-less) directive leaves its host-sync finding OPEN.
    "suppression_syntax_pos.py": {"suppression-syntax": (2, 0),
                                  "host-sync": (1, 0)},
    "suppression_syntax_neg.py": {"suppression-syntax": (0, 0),
                                  "host-sync": (0, 2)},
}


def _lint_file(name):
    cfg = LintConfig(force_hot=True)
    report = lint_paths([CORPUS / name], config=cfg)
    assert report.errors == [], report.errors
    return report


def test_corpus_is_complete():
    """Every corpus file has an expectation row and vice versa."""
    on_disk = {p.name for p in CORPUS.glob("*.py")}
    assert on_disk == set(EXPECTATIONS)


@pytest.mark.parametrize("name", sorted(EXPECTATIONS))
def test_corpus_file(name):
    report = _lint_file(name)
    expected = EXPECTATIONS[name]
    for rule, (want_open, want_sup) in expected.items():
        got_open = [f for f in report.open if f.rule == rule]
        got_sup = [f for f in report.suppressed if f.rule == rule]
        assert len(got_open) == want_open, (
            f"{name}: {rule} open findings\n"
            + "\n".join(f.format() for f in got_open)
        )
        assert len(got_sup) == want_sup, (
            f"{name}: {rule} suppressed findings\n"
            + "\n".join(f.format() for f in got_sup)
        )
    # No OTHER analyzer may report an open finding on a corpus file:
    # cross-rule noise here means an analyzer lost precision.
    strays = [f for f in report.open if f.rule not in expected]
    assert strays == [], "\n".join(f.format() for f in strays)


def test_corpus_positives_name_the_leak_site():
    """kv-refcount findings anchor to the acquire, not the exit — the
    baseline keys on the owning symbol, so entries survive line drift in
    unrelated code."""
    report = _lint_file("kv_refcount_pos.py")
    symbols = {f.symbol for f in report.open if f.rule == "kv-refcount"}
    assert "Engine.leak_on_raise" in symbols
    assert "Engine.leak_through_helper" in symbols
