"""Per-rule fixture tests for fslint.

Every rule ships with a paired fixture: ``*_bug.py`` reproduces the
historical defect the rule was distilled from (PR-5 aliasing, PR-9
gauge-key substring matching, PR-8 vacuous gates, ...) in the shape it
actually shipped in, and ``*_fixed.py`` is the shape of the landed fix.
The rule must fire on the former and stay silent on the latter — that
pair is the rule's executable specification, and it pins the engine's
scope-override path (``ignore_scope=True``) the fixtures rely on.
"""

from pathlib import Path

import pytest

from repro.analysis.engine import run

FIXTURES = Path(__file__).resolve().parent / "fixtures"

# case -> (rule, bug fixture, expected finding count, fixed fixture)
CASES = {
    "aliasing": ("aliasing", "aliasing_bug.py", 1, "aliasing_fixed.py"),
    "determinism": ("determinism", "determinism_bug.py", 3, "determinism_fixed.py"),
    "donation": ("donation", "donation_bug.py", 1, "donation_fixed.py"),
    "gauge-keys": ("gauge-keys", "gauges_bug.py", 2, "gauges_fixed.py"),
    "vacuous-gate": ("vacuous-gate", "gates_bug.py", 4, "gates_fixed.py"),
    "wire-format": ("wire-format", "wire_bug.py", 3, "wire_fixed.py"),
    "frozen-stats": ("frozen-stats", "stats_bug.py", 1, "stats_fixed.py"),
    "format": ("format", "format_bug.py", 3, "format_fixed.py"),
    "format-fstring": (
        "format", "format_fstring_bug.py", 1, "format_fstring_fixed.py"
    ),
}


def _run(rule: str, filename: str):
    return run(
        [str(FIXTURES / filename)],
        select=[rule],
        ignore_scope=True,
        baseline=None,
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_rule_fires_on_historical_bug(case):
    rule, bug, expected, _ = CASES[case]
    result = _run(rule, bug)
    assert len(result.findings) == expected, [
        f.render() for f in result.findings
    ]
    assert all(f.rule == rule for f in result.findings)
    assert all(f.line > 0 for f in result.findings)


@pytest.mark.parametrize("case", sorted(CASES))
def test_rule_silent_on_shipped_fix(case):
    rule, _, _, fixed = CASES[case]
    result = _run(rule, fixed)
    assert result.findings == [], [f.render() for f in result.findings]
    assert result.clean


def test_every_registered_rule_has_a_fixture_pair():
    from repro.analysis.registry import RULES
    from repro.analysis import rules as _rules  # noqa: F401 - registration

    assert set(RULES) == {rule for rule, *_ in CASES.values()}


# -- pinned messages: the finding must name the defect, not just point ------


def test_aliasing_finding_names_the_container_sink():
    result = _run("aliasing", "aliasing_bug.py")
    (finding,) = result.findings
    assert "defensive copy" in finding.message
    assert ".append()" in finding.message


def test_gauge_finding_names_the_substring_trap():
    result = _run("gauge-keys", "gauges_bug.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "segment" in messages
    assert "endswith" in messages


def test_wire_finding_flags_the_undispatched_magic():
    result = _run("wire-format", "wire_bug.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "ACK_MAGIC" in messages
    assert "byte-order" in messages


def test_donation_finding_names_donor_and_line():
    result = _run("donation", "donation_bug.py")
    (finding,) = result.findings
    assert "merge_at_slots" in finding.message
    assert "donate_argnums" in finding.message
