"""dtlint's own test suite: every rule catches its seeded fixture
violations at exact (rule, file, line); suppression comments and the
baseline behave; and the real ``dynamo_tpu`` tree is clean modulo the
reviewed baseline (the static half of the repo's perf invariants).

Fixture modules under ``tests/dtlint_fixtures/`` mark each seeded
violation with a trailing ``# expect: RULE`` comment, so the expected
(file, line, rule) set is read from the fixtures themselves — adding a
fixture case is one line, and line-number drift cannot silently pass.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from tools.dtlint import LintConfig, RULES, apply_baseline, load_baseline, run_lint
from tools.dtlint.core import BaselineError, Finding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = "tests/dtlint_fixtures"

FIXTURE_CONFIG = LintConfig(
    root=REPO,
    paths=(FIXTURES,),
    aggregator_path=f"{FIXTURES}/fx_met001/mini_aggregator.py",
    grafana_path=f"{FIXTURES}/fx_met001/grafana.json",
    sync_allowlist_path=f"{FIXTURES}/sync_allowlist.json",
    thread_entries=((f"{FIXTURES}/fx_thr001.py", "Poller.poll"),),
    # v2 rule anchors, re-pointed at the fixture tree.
    warmup_scopes=(f"{FIXTURES}/fx_warm001.py",),
    warmup_func="Mini.warmup",
    async_scopes=(f"{FIXTURES}/fx_async001.py",),
    wire_writers=(
        f"{FIXTURES}/fx_wire001/writer.py::Pre.to_wire",
        f"{FIXTURES}/fx_wire001/writer.py::Pre.transform",
    ),
    wire_readers=(f"{FIXTURES}/fx_wire001/reader.py::Eng.generate",),
    wire_stop_writers=(f"{FIXTURES}/fx_wire001/writer.py::stops",),
    wire_stop_readers=(f"{FIXTURES}/fx_wire001/reader.py::StopC.from_dict",),
    mocker_path=f"{FIXTURES}/fx_wire001/mock.py",
    # Keep MET001 off the wire fixtures: the mocker mini's stats families
    # are channel-C subjects, not scrape-registry subjects.
    met001_exclude=("fx_wire001/",),
)


def expected_markers(relpath: str):
    """{(line, rule)} parsed from ``# expect: RULE`` fixture comments."""
    out = set()
    with open(os.path.join(REPO, relpath)) as f:
        for i, line in enumerate(f, start=1):
            m = re.search(r"#\s*expect:\s*([A-Z]+\d+)", line)
            if m:
                out.add((i, m.group(1)))
    return out


def fixture_findings(rules=None):
    return run_lint(FIXTURE_CONFIG, rules=rules).findings


# --- exact per-rule detection -------------------------------------------------

@pytest.mark.parametrize("rule,fixture", [
    ("JIT001", f"{FIXTURES}/fx_jit001.py"),
    ("JIT002", f"{FIXTURES}/fx_jit002.py"),
    ("DON001", f"{FIXTURES}/fx_don001.py"),
    ("SYNC001", f"{FIXTURES}/fx_sync001.py"),
    ("THR001", f"{FIXTURES}/fx_thr001.py"),
    ("WARM001", f"{FIXTURES}/fx_warm001.py"),
    ("ASYNC001", f"{FIXTURES}/fx_async001.py"),
    ("LEAK001", f"{FIXTURES}/fx_leak001.py"),
    ("WIRE001", f"{FIXTURES}/fx_wire001/writer.py"),
    ("WIRE001", f"{FIXTURES}/fx_wire001/reader.py"),
    ("WIRE001", f"{FIXTURES}/fx_wire001/mock.py"),
])
def test_rule_catches_fixture_violations_at_exact_lines(rule, fixture):
    found = {
        (f.line, f.rule)
        for f in fixture_findings(rules=[rule])
        if f.file == fixture
    }
    assert found == expected_markers(fixture), (
        f"{rule} findings diverge from the fixture's # expect markers"
    )


def test_met001_covers_all_drift_directions():
    findings = fixture_findings(rules=["MET001"])
    keys = {f.key for f in findings}
    # (a) emitted but unregistered, (b) registered but unemitted,
    # (c) registered but unpinned, (d) pinned but unknown.
    assert "unregistered:rogue_total" in keys
    assert "unemitted:ghost_total" in keys
    assert "unpinned:ghost_total" in keys
    assert "unpinned:lonely_gauge" in keys
    assert "unknown:phantom_total" in keys
    # f-string wildcard emission satisfies registration (no unemitted
    # finding for the step_{phase} key), and clean keys stay clean.
    assert not any("step_decode_ok_total" in k for k in keys)
    assert not any("good" in k for k in keys)
    # Marker lines in the two fixture sources line up exactly.
    agg = f"{FIXTURES}/fx_met001/mini_aggregator.py"
    emit = f"{FIXTURES}/fx_met001/emitter.py"
    for path in (agg, emit):
        found_lines = {(f.line, f.rule) for f in findings if f.file == path}
        assert found_lines == expected_markers(path), path
    # The grafana-side unknown-key finding anchors on the dashboard file.
    grafana = [f for f in findings if f.key == "unknown:phantom_total"]
    assert grafana[0].file == f"{FIXTURES}/fx_met001/grafana.json"


def test_clean_fixture_has_zero_findings():
    clean = [f for f in fixture_findings() if f.file == f"{FIXTURES}/fx_clean.py"]
    assert clean == []


def test_suppression_comments_silence_only_their_line():
    # Every fixture carries one would-be violation with an inline
    # ``# dtlint: disable=RULE`` — none of those lines may be reported.
    for fixture in (f"{FIXTURES}/fx_jit001.py", f"{FIXTURES}/fx_jit002.py",
                    f"{FIXTURES}/fx_don001.py", f"{FIXTURES}/fx_sync001.py",
                    f"{FIXTURES}/fx_async001.py", f"{FIXTURES}/fx_leak001.py"):
        src = open(os.path.join(REPO, fixture)).read().splitlines()
        suppressed_lines = {
            i for i, l in enumerate(src, start=1) if "dtlint: disable=" in l
        }
        assert suppressed_lines, f"{fixture} lost its suppression case"
        hits = {f.line for f in fixture_findings() if f.file == fixture}
        assert not (hits & suppressed_lines), (
            f"{fixture}: suppressed lines {hits & suppressed_lines} reported"
        )


def test_sync001_allowlist_sanctions_exactly_the_named_sync():
    findings = fixture_findings(rules=["SYNC001"])
    # retire()'s np.asarray is allowlisted; decode_step's identical call is
    # not — same file, same call, different function.
    assert not any(f.qualname == "HotLoop.retire" for f in findings)
    assert any(
        f.qualname == "HotLoop.decode_step" and f.key == "sync:np.asarray"
        for f in findings
    )
    # off_path() is outside the hot-path scope entirely.
    assert not any(f.qualname == "HotLoop.off_path" for f in findings)


def test_warm001_distinguishes_unwarmed_from_arity_drift():
    keys = {f.key for f in fixture_findings(rules=["WARM001"])}
    assert keys == {"unwarmed:spec", "arity:admit"}


def test_wire001_covers_both_channels_and_directions():
    keys = {f.key for f in fixture_findings(rules=["WIRE001"])}
    assert keys == {
        "ghost-read:request:ghost_field",
        "dead-write:request:dead_field",
        "ghost-read:stop_conditions:ghost_stop",
        "dead-write:stop_conditions:phantom_stop",
        "mocker-stats:mock_only_total",
    }


def test_sync001_flags_stale_allowlist_entries(tmp_path):
    """The allowlist can only shrink: entries naming vanished functions or
    vanished syncs fail the run like a stale baseline would."""
    stale = {
        "hot_paths": {f"{FIXTURES}/fx_sync001.py": [
            "HotLoop.decode_step", "HotLoop.gone",
        ]},
        "allowed_syncs": [{
            "file": f"{FIXTURES}/fx_sync001.py", "func": "HotLoop.decode_step",
            "call": "np.array", "role": "per_step", "path": "fixture",
            "reason": "stale: decode_step has no np.array sync",
        }],
    }
    p = tmp_path / "allow.json"
    p.write_text(json.dumps(stale))
    cfg = LintConfig(
        root=REPO, paths=(FIXTURES,), sync_allowlist_path=str(p),
        warmup_scopes=FIXTURE_CONFIG.warmup_scopes,
        warmup_func=FIXTURE_CONFIG.warmup_func,
        async_scopes=FIXTURE_CONFIG.async_scopes,
    )
    keys = {f.key for f in run_lint(cfg, rules=["SYNC001"]).findings
            if f.key.startswith("stale-allowlist:")}
    assert f"stale-allowlist:hot:{FIXTURES}/fx_sync001.py:HotLoop.gone" in keys
    assert any(k.startswith("stale-allowlist:call:") for k in keys)


# --- the whole-program call graph (v2) ----------------------------------------

def test_project_graph_resolves_cross_module_calls():
    from tools.dtlint.callgraph import gid, project_graph
    from tools.dtlint.core import ProjectIndex

    index = ProjectIndex(FIXTURE_CONFIG)
    pg = project_graph(index)
    sched = f"{FIXTURES}/fx_callgraph/sched.py"
    models = f"{FIXTURES}/fx_callgraph/models.py"
    # from-import and module-attribute call sites both resolve across
    # module boundaries into real edges.
    assert gid(models, "helper") in pg.edges[gid(sched, "Sched.step")]
    assert gid(models, "chain") in pg.edges[gid(sched, "Sched.step")]
    # jit(lambda x: self.model.device_fn(x)) resolves through the
    # module-typed attribute to a cross-module jit root.
    assert gid(models, "device_fn") in pg.jit_roots()
    # Module-returner registry pattern: m = pick(cfg); m.device_fn(x).
    assert pg.resolve_call_multi(sched, "Sched.route", "m.device_fn") == {
        gid(models, "device_fn")
    }


def test_return_class_fixpoint_crosses_modules():
    from tools.dtlint.callgraph import DEVICE, HOST, gid, project_graph
    from tools.dtlint.core import ProjectIndex

    index = ProjectIndex(FIXTURE_CONFIG)
    pg = project_graph(index)
    rc = pg.infer_return_classes()
    models = f"{FIXTURES}/fx_callgraph/models.py"
    sched = f"{FIXTURES}/fx_callgraph/sched.py"
    assert rc[gid(models, "host_fn")] == HOST
    assert rc[gid(models, "device_fn")] == DEVICE
    assert rc[gid(models, "chain")] == DEVICE   # device through a helper...
    assert rc[gid(sched, "relay")] == DEVICE    # ...and across modules


# --- baseline behavior --------------------------------------------------------

def test_baseline_absorbs_matching_findings_and_reports_stale(tmp_path):
    findings = fixture_findings(rules=["JIT001"])
    assert findings
    victim = findings[0]
    entries = [{
        "rule": victim.rule, "file": victim.file,
        "qualname": victim.qualname, "key": victim.key,
        "reason": "fixture: reviewed and kept",
    }]
    remaining, stale = apply_baseline(findings, entries)
    assert victim not in remaining and not stale
    # Identity matching survives line drift: same (rule,file,qualname,key)
    # at another line is still absorbed.
    moved = Finding(victim.rule, victim.file, victim.line + 100,
                    victim.qualname, victim.message, victim.key)
    remaining, stale = apply_baseline([moved], entries)
    assert remaining == [] and stale == []
    # A stale entry (no matching finding) is an error, not a freebie.
    bogus = [{**entries[0], "key": "call:nonexistent.thing"}]
    remaining, stale = apply_baseline(findings, bogus)
    assert stale == bogus and victim in remaining


def test_baseline_entries_require_reasons(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"entries": [{
        "rule": "JIT001", "file": "x.py", "qualname": "f", "key": "call:t",
    }]}))
    with pytest.raises(BaselineError, match="reason"):
        load_baseline(str(p))


# --- the real tree ------------------------------------------------------------

def test_real_tree_is_clean_modulo_baseline():
    """THE acceptance gate: every rule over all of dynamo_tpu/, with the
    reviewed baseline applied, finds nothing — and no baseline entry is
    stale. This is the same invocation CI runs."""
    result = run_lint(
        LintConfig(root=REPO),
        baseline_path=os.path.join(REPO, "dtlint_baseline.json"),
    )
    assert result.findings == [], "\n".join(f.render() for f in result.findings)
    assert result.stale_baseline == [], result.stale_baseline
    assert result.baseline_size <= 15, (
        f"baseline has {result.baseline_size} entries; the budget is 15 — "
        "fix findings instead of accumulating exceptions"
    )


def test_real_baseline_entries_all_carry_reasons():
    entries = load_baseline(os.path.join(REPO, "dtlint_baseline.json"))
    for e in entries:
        assert len(e["reason"]) >= 20, f"baseline reason too thin: {e}"


def test_sync_allowlist_declares_one_per_step_sync_per_path():
    """The statically declared blocking-sync budget: each decode path gets
    AT MOST one per_step allowlist entry, and the single-step path's budget
    is exactly 1."""
    with open(os.path.join(REPO, "tools/dtlint/sync_allowlist.json")) as f:
        cfg = json.load(f)
    per_step = [e for e in cfg["allowed_syncs"] if e["role"] == "per_step"]
    by_path = {}
    for e in per_step:
        by_path.setdefault(e["path"], []).append(e)
    assert len(by_path.get("sync", [])) == 1
    for path, entries in by_path.items():
        assert len(entries) == 1, f"path {path} declares {len(entries)} per-step syncs"


def test_sync_allowlist_sanctions_no_sync_on_a_stats_path():
    """What a scrape calls while traffic runs is in SYNC001's scope and declares no
    blocking sync (the one batched exception lives in the baseline, with its reason)."""
    with open(os.path.join(REPO, "tools/dtlint/sync_allowlist.json")) as f:
        cfg = json.load(f)
    stats_funcs = {"Scheduler.metrics", "Scheduler.kv_gauges", "Scheduler.debug_state"}
    assert [e for e in cfg["allowed_syncs"] if e["func"] in stats_funcs] == []
    assert stats_funcs <= set(cfg["hot_paths"]["dynamo_tpu/engine/scheduler.py"])


# --- CLI ----------------------------------------------------------------------

def test_cli_json_exit_codes():
    env = {**os.environ, "PYTHONPATH": REPO}
    # Clean run (real tree + baseline) exits 0 with ok=true JSON.
    out = subprocess.run(
        [sys.executable, "-m", "tools.dtlint", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["ok"] and payload["findings"] == []

    # An injected violation (the JIT001 fixture) fails the same invocation
    # shape CI uses — rule-scoped, no baseline.
    out = subprocess.run(
        [sys.executable, "-m", "tools.dtlint",
         f"{FIXTURES}/fx_jit001.py", "--rule", "JIT001",
         "--baseline", "", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1
    payload = json.loads(out.stdout)
    assert not payload["ok"]
    assert {f["rule"] for f in payload["findings"]} == {"JIT001"}
    assert all(f["line"] > 0 and f["file"].endswith("fx_jit001.py")
               for f in payload["findings"])


def test_cli_github_annotations_from_json(tmp_path):
    env = {**os.environ, "PYTHONPATH": REPO}
    out = subprocess.run(
        [sys.executable, "-m", "tools.dtlint",
         f"{FIXTURES}/fx_jit001.py", "--rule", "JIT001",
         "--baseline", "", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1
    dump = tmp_path / "findings.json"
    dump.write_text(out.stdout)
    # The CI annotation step replays the dump; it decorates but never gates
    # (the lint step already failed the job), so it exits 0.
    out2 = subprocess.run(
        [sys.executable, "-m", "tools.dtlint", "--github",
         "--from-json", str(dump)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out2.returncode == 0, out2.stdout + out2.stderr
    assert "::error file=" in out2.stdout
    assert "title=dtlint JIT001" in out2.stdout


def test_cli_diff_mode_runs_clean():
    # Whatever the working tree's changed-file set is, a tree that is clean
    # modulo baseline filters down to zero reported findings.
    env = {**os.environ, "PYTHONPATH": REPO}
    out = subprocess.run(
        [sys.executable, "-m", "tools.dtlint", "--diff"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_rule_registry_is_complete():
    import tools.dtlint.rules_async  # noqa: F401
    import tools.dtlint.rules_jit  # noqa: F401
    import tools.dtlint.rules_leak  # noqa: F401
    import tools.dtlint.rules_metrics  # noqa: F401
    import tools.dtlint.rules_sync  # noqa: F401
    import tools.dtlint.rules_threads  # noqa: F401
    import tools.dtlint.rules_warmup  # noqa: F401
    import tools.dtlint.rules_wire  # noqa: F401

    assert set(RULES) == {
        "JIT001", "JIT002", "SYNC001", "DON001", "MET001", "THR001",
        "WARM001", "ASYNC001", "LEAK001", "WIRE001",
    }


def test_static_warmup_report_agrees_with_the_real_scheduler():
    """The export over the REAL tree: the kinds the scheduler
    serves are (modulo the baselined open-ended mm bucket) all statically
    warmed, including the spec-decode round added for exactly this gap."""
    from tools.dtlint.rules_warmup import static_warmup_report

    report = static_warmup_report(REPO)
    warmed = report["warmed"]
    assert "decode" in warmed
    assert "spec" in warmed, (
        "spec-round executables fell out of Scheduler.warmup()"
    )
    # Every serving-path dispatch kind (modulo the baselined mm bucket) is
    # statically warmed at an intersecting arity — the same coverage
    # relation WARM001 enforces (the dynamic cross-check against the
    # flight recorder is tests/test_decode_paths.py's).
    for kind, arities in report["serving"].items():
        if kind == "prefill_mm":
            continue
        assert kind in warmed, f"serving kind '{kind}' never warmed"
        if arities and warmed[kind]:
            assert set(arities) & set(warmed[kind]), (
                f"serving kind '{kind}' keys {arities} but warmup "
                f"registers {warmed[kind]}"
            )
