"""repro.analysis swarmlint: rule registry, the four rule families on
known-bad/known-good fixtures, the justified baseline, and the shipped
tree's own guarantees (ISSUE 6 acceptance surface; obs family from
ISSUE 10)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (AnalysisContext, AnalyzerRule, Baseline,
                            Finding, collect_findings, register_rule,
                            rule_ids, scorecard, slotview_tiers,
                            split_by_baseline, write_baseline)
from repro.analysis.registry import _REGISTRY

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "static_fixtures"


def run_on(files, families=None, assume_library=True):
    ctx = AnalysisContext(REPO, assume_library=assume_library)
    ctx.add_paths([FIXTURES / f for f in files])
    assert not ctx.errors, ctx.errors
    return collect_findings(ctx, families)


def fired(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# registry mirrors register_policy
# ---------------------------------------------------------------------------

def test_registry_rejects_non_rule_and_anonymous_rule():
    with pytest.raises(TypeError):
        register_rule(object)

    class NoId(AnalyzerRule):
        family = "rng"
    with pytest.raises(ValueError, match="non-empty"):
        register_rule(NoId)

    class BadFamily(AnalyzerRule):
        rule = "XXX999"
        family = "nope"
    with pytest.raises(ValueError, match="family"):
        register_rule(BadFamily)


def test_registry_rejects_duplicate_rule_id():
    class Clash(AnalyzerRule):
        rule = "RNG001"                     # already taken
        family = "rng"
    with pytest.raises(ValueError, match="duplicate"):
        register_rule(Clash)
    assert _REGISTRY["RNG001"] is not Clash


def test_all_four_families_registered():
    ids = rule_ids()
    assert ids == tuple(sorted(ids))
    assert {"RNG001", "RNG002", "RNG003", "RNG004", "RNG005", "RNG006",
            "RNG007", "VIS001", "JIT101", "JIT102", "JIT103",
            "OBS001", "OBS002"} <= set(ids)
    from repro.analysis import FAMILIES
    assert FAMILIES == ("rng", "visibility", "jit", "obs")


# ---------------------------------------------------------------------------
# family 1: rng discipline
# ---------------------------------------------------------------------------

def test_rng_rules_fire_on_known_bad():
    found = run_on(["rng_bad.py"], families=("rng",))
    assert fired(found) == {"RNG001", "RNG002", "RNG003", "RNG004",
                            "RNG005", "RNG006", "RNG007"}
    # the two RNG005 shapes: tainted set variable and set literal
    details = {f.detail for f in found if f.rule == "RNG005"}
    assert details == {"peers", "set-literal"}
    # the constant-seed shadow names the threaded-param function
    (shadow,) = [f for f in found if f.rule == "RNG004"]
    assert shadow.scope == "shadowed_fallback"


def test_rng_rules_silent_on_known_good():
    assert run_on(["rng_good.py"], families=("rng",)) == []


# ---------------------------------------------------------------------------
# family 2: visibility escape
# ---------------------------------------------------------------------------

def test_visibility_flags_never_executed_over_reaching_policy():
    """The acceptance fixture: PeekingFlooder is registered nowhere and
    executed never — only the lint pass can catch it, via all three
    escape routes (direct door, self-method, two-hop module helper)."""
    found = run_on(["vis_bad.py"], families=("visibility",))
    by_scope = {}
    for f in found:
        by_scope.setdefault(f.scope, set()).add(f.detail)
    assert by_scope == {
        "PeekingFlooder": {"_engine_state", "candidate_columns",
                           "supply"},
        "NosyNeighborhood": {"state"},
    }
    assert all(f.rule == "VIS001" and f.severity == "error"
               for f in found)


def test_visibility_silent_on_tier_honest_policies():
    assert run_on(["vis_good.py"], families=("visibility",)) == []


def test_slotview_tier_table_derived_from_policy_source():
    src = (REPO / "src/repro/core/policy.py").read_text()
    tiers = slotview_tiers(src)
    assert tiers["supply"] == "full"
    assert tiers["state"] == "full"
    assert tiers["candidate_columns"] == "full"
    assert tiers["_engine_state"] == "full"      # the audited door
    assert tiers["availability_union"] == "neighborhood"
    # ungated protocol facts stay at the bottom tier
    assert tiers["rng"] == "none"
    assert tiers["receivers_open"] == "none"
    assert tiers["resolve_requests"] == "none"


# ---------------------------------------------------------------------------
# family 3: jit readiness
# ---------------------------------------------------------------------------

def test_jit_rules_fire_on_known_bad():
    found = run_on(["jit_bad.py"], families=("jit",))
    assert fired(found) == {"JIT101", "JIT102", "JIT103"}
    assert all(f.severity == "warning" for f in found)
    kinds = {f.detail.split(":", 1)[0] for f in found
             if f.rule == "JIT103"}
    assert kinds == {"while", "for"}


def test_jit_rules_silent_on_known_good():
    assert run_on(["jit_good.py"], families=("jit",)) == []


def test_scorecard_separates_ready_from_worklist():
    ctx = AnalysisContext(REPO, assume_library=True)
    ctx.add_paths([FIXTURES / "jit_bad.py", FIXTURES / "jit_good.py"])
    rows = scorecard(ctx, collect_findings(ctx, ("jit",)))
    status = {(Path(p).name, q): ready for p, q, _c, ready in rows}
    assert status[("jit_bad.py", "transport")] is False
    assert status[("jit_good.py", "transport")] is True


# ---------------------------------------------------------------------------
# family 4: observability discipline
# ---------------------------------------------------------------------------

def test_obs_rules_fire_on_known_bad():
    found = run_on(["obs_bad.py"], families=("obs",))
    assert fired(found) == {"OBS001", "OBS002"}
    assert all(f.severity == "error" for f in found)
    assert len([f for f in found if f.rule == "OBS001"]) == 2
    hits = [f for f in found if f.rule == "OBS002"]
    # OBS002 reaches past RNG007's wall-clock set: sleep and strftime
    # count as inline host-time use too.
    assert {f.detail for f in hits} == {
        "time.perf_counter", "time.sleep", "time.strftime"}
    assert len(hits) == 4


def test_obs_rules_silent_on_known_good():
    """The obs-routed twins of every bad shape — including a *reference*
    to ``time.perf_counter`` (the ``obs.recording(clock=...)`` injection
    idiom), which must not be mistaken for a call."""
    assert run_on(["obs_good.py"], families=("obs",)) == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def _finding(**kw):
    base = dict(rule="RNG001", severity="error", path="a.py", line=3,
                message="m", scope="f", detail="random.random")
    base.update(kw)
    return Finding(**base)


def test_finding_key_is_line_stable():
    assert _finding(line=3).key == _finding(line=99).key


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps(
        {"version": 1,
         "entries": [{"key": "RNG001:a.py:f:random.random",
                      "justification": "  "}]}))
    with pytest.raises(ValueError, match="justification"):
        Baseline.load(p)


def test_baseline_split_and_stale(tmp_path):
    f1, f2 = _finding(), _finding(detail="random.choice")
    p = tmp_path / "b.json"
    write_baseline(p, [f1])
    bl = Baseline.load(p)       # TODO-justify entries still load
    new, old = split_by_baseline([f1, f2], bl)
    assert old == [f1] and new == [f2]
    assert bl.unused([f2]) == [f1.key]


def test_write_baseline_preserves_justifications(tmp_path):
    p = tmp_path / "b.json"
    write_baseline(p, [_finding()])
    raw = json.loads(p.read_text())
    raw["entries"][0]["justification"] = "reviewed: fine"
    p.write_text(json.dumps(raw))
    prev = Baseline.load(p)
    write_baseline(p, [_finding(), _finding(detail="random.choice")],
                   prev)
    entries = {e["key"]: e["justification"]
               for e in json.loads(p.read_text())["entries"]}
    assert entries[_finding().key] == "reviewed: fine"
    assert entries[_finding(detail="random.choice").key].startswith(
        "TODO")


# ---------------------------------------------------------------------------
# the shipped tree's own guarantees
# ---------------------------------------------------------------------------

def _shipped_ctx():
    ctx = AnalysisContext(REPO)
    ctx.add_paths([REPO / "src", REPO / "examples"])
    return ctx


def test_shipped_tree_rng_clean():
    """After the overlay fix, the library carries zero rng-discipline
    findings — nothing hides behind the baseline."""
    assert collect_findings(_shipped_ctx(), ("rng",)) == []


def test_shipped_tree_visibility_exactly_the_engine_doors():
    """The only tier escapes are the two equivalence-locked built-in
    backends reaching the audited ``_engine_state`` door — and both
    are justified in the baseline."""
    found = collect_findings(_shipped_ctx(), ("visibility",))
    assert {(f.scope, f.detail) for f in found} == {
        ("DistributedPolicy", "_engine_state"),
        ("FloodingPolicy", "_engine_state")}
    bl = Baseline.load(REPO / "analysis_baseline.json")
    assert all(bl.covers(f) for f in found)
    assert all(bl.entries[f.key] and "TODO" not in bl.entries[f.key]
               for f in found)


def test_shipped_tree_obs_clean():
    """No print()/inline time.* survives in core/, net/, fl/ — all
    telemetry flows through repro.obs and the injectable clocks."""
    assert collect_findings(_shipped_ctx(), ("obs",)) == []


def test_cli_exits_zero_on_shipped_tree():
    """The CI/benchmark contract: ``python -m repro.analysis src
    examples`` from the repo root is clean under the baseline."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src", "examples"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "jit-readiness scorecard" in proc.stdout


def test_cli_exits_nonzero_on_new_findings():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--no-baseline",
         "--assume-library", str(FIXTURES / "rng_bad.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "RNG004" in proc.stdout
