"""mxlint: the AST static-analysis suite (ISSUE 8).

Three layers:

1. **fixture corpus** — every rule fires on its seeded-violation file
   under ``tests/lint_fixtures/`` (exactly the seeded findings, at the
   seeded lines — including the aliased ``from jax import jit as J``
   form the old grep lint missed) and stays silent on the compliant
   twin;
2. **framework** — suppression grammar (justification REQUIRED),
   baseline grandfathering, stale-baseline tolerance + pruning, parse
   errors as findings, JSON shape, CLI exit codes;
3. **tier-1 gate lane** — ``python tools/mxlint.py mxnet_tpu
   tools`` exits 0 with ZERO unsuppressed findings, and the
   ``--json`` artifact is written to
   (``$MXTPU_ARTIFACT_DIR/mxlint.json``, default /tmp/mxtpu_artifacts)
   so the lint trajectory is recorded every round.
"""
import functools
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.analysis import run, ALL_RULE_IDS
from mxnet_tpu.analysis.core import Baseline, SUPPRESSION_RULE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "lint_fixtures")
MXLINT = os.path.join(ROOT, "tools", "mxlint.py")


def _fixture(name, rules):
    """Report over one fixture file/dir, no baseline."""
    return run([os.path.join(FIXTURES, name)], rules=rules,
               baseline=Baseline(), root=ROOT)


def _lines(report, rule=None):
    return sorted(f.line for f in report.findings
                  if rule is None or f.rule == rule)


# ---------------------------------------------------------------------------
# Fixture corpus: seeded violation fires, compliant twin is silent
# ---------------------------------------------------------------------------

def test_jit_site_fixture_pair():
    rep = _fixture("jit_site_violation.py", ["jit-site"])
    # 6 seeded: direct call, ALIASED `from jax import jit as J` (the
    # form the grep lint walked past), aliased pjit, pmap, decorator,
    # and the @functools.partial(jax.jit, ...) wrap
    assert _lines(rep) == [11, 15, 19, 23, 26, 31], \
        [f.render() for f in rep.findings]
    assert any("decorator" in f.message for f in rep.findings)
    assert any("functools.partial" in f.message for f in rep.findings)
    ok = _fixture("jit_site_ok.py", ["jit-site"])
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_dispatch_hook_fixture_pair():
    rep = _fixture("dispatch_hook_violation.py", ["dispatch-hook"])
    assert _lines(rep) == [8, 12], [f.render() for f in rep.findings]
    ok = _fixture("dispatch_hook_ok.py", ["dispatch-hook"])
    assert ok.clean, [f.render() for f in ok.findings]


def test_lock_discipline_fixture_pair():
    rep = _fixture("lock_discipline_violation.py", ["lock-discipline"])
    # unlocked global read, finalizer-lock (the PR 4 deadlock class),
    # the read+write halves of the unlocked `self._stats[k] = ...`, and
    # a deferred callback defined under the lock but running without it
    assert _lines(rep) == [14, 18, 28, 28, 43, 53], \
        [f.render() for f in rep.findings]
    assert any("weakref.finalize" in f.message for f in rep.findings)
    ok = _fixture("lock_discipline_ok.py", ["lock-discipline"])
    # Condition alias, _locked-suffix helper, lock-free finalizer,
    # __init__ construction, callback re-acquiring where it runs: all
    # clean with zero suppressions
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_host_sync_fixture_pair():
    rep = _fixture("host_sync_violation.py", ["host-sync"])
    # ...including the standalone marker above a DECORATED def (which
    # arms the decorator's line, not the def's)
    assert _lines(rep) == [9, 10, 11, 18], \
        [f.render() for f in rep.findings]
    msgs = " ".join(f.message for f in rep.findings)
    for form in (".asnumpy()", ".wait_to_read()", "np.asarray"):
        assert form in msgs
    ok = _fixture("host_sync_ok.py", ["host-sync"])
    assert ok.clean, [f.render() for f in ok.findings]
    # the one justified disable in the twin is honoured AND recorded
    assert len(ok.suppressed) == 1
    assert ok.suppressed[0][1]          # justification text rides along


def test_donation_fixture_pair():
    rep = _fixture("donation_violation.py", ["donation-safety"])
    # ...including the use after a donation that happens inside an
    # except handler (handler bodies are in the linear statement order)
    assert _lines(rep) == [13, 19, 26, 36], \
        [f.render() for f in rep.findings]
    assert any("loop" in f.message for f in rep.findings)
    ok = _fixture("donation_ok.py", ["donation-safety"])
    assert ok.clean, [f.render() for f in ok.findings]


def test_trace_purity_fixture_pair():
    rep = _fixture("trace_purity_violation.py", ["trace-purity"])
    # telemetry 2 deep, global mutation 3 deep, self mutation via a
    # local-instance method call, wall clock + global RNG in a
    # jit-decorated kernel
    assert _lines(rep) == [26, 33, 42, 48, 49], \
        [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "telemetry" in msgs[26]
    # the 3-deep chain is printed hop by hop
    assert "call chain" in msgs[33]
    assert "level1" in msgs[33] and "level2" in msgs[33]
    assert "wall clock" in msgs[48]
    assert "RNG" in msgs[49]
    ok = _fixture("trace_purity_ok.py", ["trace-purity"])
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_host_sync_transitive_fixture_pair():
    rep = _fixture("host_sync_chain_violation.py", ["host-sync"])
    # both findings anchor at the SINK lines (the .asnumpy /
    # .wait_to_read), not in the hot function; the recursive
    # drain<->fetch pair (an SCC) terminates and still reports
    assert _lines(rep) == [21, 31], [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "hot_loop" in msgs[21] and "log_metrics" in msgs[21]
    assert "call chain" in msgs[21]
    assert "drain" in msgs[31]
    # sink-line anchors: refactoring an intermediate caller must not
    # invalidate a baseline entry (keyed on rule/path/anchor)
    anchors = {f.line: f.anchor for f in rep.findings}
    assert "asnumpy" in anchors[21]
    assert "wait_to_read" in anchors[31]
    # the dynamic cb(out) call was NOT traversed: no third finding
    ok = _fixture("host_sync_chain_ok.py", ["host-sync"])
    # ref edge to the pool resolver + unreachable epoch helper: clean
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_lockset_fixture_pair():
    rep = _fixture("lockset_violation.py", ["lockset"])
    assert _lines(rep) == [30, 33], [f.render() for f in rep.findings]
    # the finding proposes the exact annotation to add, and the locked
    # evidence comes from the ENTRY lockset of the private helper
    # (called only under the lock — no lexical with in _bump)
    for f in rep.findings:
        assert "# guarded by: self._lock" in f.message
    assert any("_bump" in f.message for f in rep.findings)
    ok = _fixture("lockset_ok.py", ["lockset"])
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_donation_interproc_fixture_pair():
    rep = _fixture("donation_interproc_violation.py",
                   ["donation-safety"])
    # NO markers in the fixture: the wrapper's donated params and the
    # factory's returned donating program are both inferred
    assert _lines(rep) == [16, 16, 22, 37], \
        [f.render() for f in rep.findings]
    msgs = " ".join(f.message for f in rep.findings)
    assert "fused_step" in msgs          # param-propagation inference
    assert "upd" in msgs                 # returns-donating inference
    ok = _fixture("donation_interproc_ok.py", ["donation-safety"])
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_thread_race_fixture_pair():
    rep = _fixture("thread_race_violation.py", ["thread-race"])
    # the attr race (write under a thread root reached THROUGH A REF
    # EDGE — _flush escapes as a value) anchors at the racing write;
    # the finalizer-thread global write is the second finding
    assert _lines(rep) == [31, 43], [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    # both witness chains ride in the message, with the registration
    # site named, and the finding proposes the exact annotation
    assert "registered at" in msgs[31]
    assert "_flush" in msgs[31] and "depth" in msgs[31]
    assert "# guarded by: self._lock" in msgs[31]
    assert "finalizer" in msgs[43]
    assert "main thread" in msgs[43]
    assert "# guarded by: _lock" in msgs[43]
    ok = _fixture("thread_race_ok.py", ["thread-race"])
    # locked+annotated attr, lock-free finalizer pending deque with
    # ONE justified disable (the PR 4 pattern): clean
    assert ok.clean, [f.render() for f in ok.findings]
    assert len(ok.suppressed) == 1 and ok.suppressed[0][1]


def test_collective_discipline_fixture_pair():
    rep = _fixture("collective_violation.py", ["collective-discipline"])
    # ungated _host_allgather from a public entry, step-gate guarding
    # a kv exchange (channel mismatch), rank-divergent psum
    assert _lines(rep) == [30, 34, 37], \
        [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "NO CollectiveGate crossing" in msgs[30]
    assert "channel 'kv'" in msgs[34] and "channel 'step'" in msgs[34]
    assert "DIFFERENT collective sequences" in msgs[37]
    assert "psum" in msgs[37] and "rank" in msgs[37]
    ok = _fixture("collective_ok.py", ["collective-discipline"])
    # lexical crossing, ENTRY-gated private helper, gated call to the
    # marked broadcast primitive, rank-arm with no collectives: clean
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_future_lifecycle_fixture_pair():
    rep = _fixture("future_lifecycle_violation.py", ["future-lifecycle"])
    # strand through risky()'s raise edge, double resolve, return-path
    # strand, and two resolvers skipping the request's entered spans
    assert _lines(rep) == [25, 28, 34, 35, 41], \
        [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "UNRESOLVED" in msgs[25] and "risky" in msgs[25]
    assert "raises ValueError" in msgs[25]       # the witness chain
    assert "SECOND time" in msgs[28]
    assert "returns at line 35" in msgs[35]
    assert "entered scopes" in msgs[41] and "span" in msgs[41]
    ok = _fixture("future_lifecycle_ok.py", ["future-lifecycle"])
    # handler-path resolution, sentinel dequeue, transfer to the
    # resolving shed(), done-guarded late resolve: all clean
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_resource_release_fixture_pair():
    rep = _fixture("resource_release_violation.py", ["resource-release"])
    # bare acquire, never-exited span, jumpable exit, tmp without
    # unlink-on-failure, leaked non-daemon thread, jumpable join
    assert _lines(rep) == [21, 27, 32, 39, 47, 52], \
        [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "with _lock" in msgs[21]
    assert "never exits" in msgs[27]
    assert "must_raise" in msgs[32] and "finally" in msgs[32]
    assert "unlink" in msgs[39]
    assert "non-daemon" in msgs[47]
    assert "join" in msgs[52]
    ok = _fixture("resource_release_ok.py", ["resource-release"])
    # with-lock, finally-release, finally-exit, escape-to-owner,
    # unlink-on-failure, daemon thread, finally-join: all clean
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_torn_state_fixture_pair():
    rep = _fixture("torn_state_violation.py", ["torn-state-on-raise"])
    # bump/unbump and set/clear pairs straddling an unguarded boom()
    assert _lines(rep) == [19, 24], [f.render() for f in rep.findings]
    msgs = {f.line: f.message for f in rep.findings}
    assert "self._depth" in msgs[19] and "boom" in msgs[19]
    assert "raises RuntimeError" in msgs[19]     # the witness chain
    assert "self._busy" in msgs[24]
    ok = _fixture("torn_state_ok.py", ["torn-state-on-raise"])
    # finally-restore, guarded call, init-then-publish idiom, lone
    # mutation: all clean
    assert ok.clean and not ok.suppressed, \
        [f.render() for f in ok.findings]


def test_every_rule_has_an_exercised_fixture_pair():
    """Meta-test guarding the NEXT rule family from shipping
    fixture-less: every id in ALL_RULE_IDS declares its fixture pair
    (``fixture_basenames``), every declared fixture exists on disk
    with the violation/compliant twin convention, every fixture file
    in the corpus is declared by some rule, and every fixture is
    actually exercised by a test in this file."""
    from mxnet_tpu.analysis.rules import rule_table
    table = rule_table()
    declared = set()
    for rid in ALL_RULE_IDS:
        rule = table[rid]
        names = getattr(rule, "fixture_basenames", ())
        assert names, "rule %s declares no fixtures" % rid
        assert len(names) % 2 == 0 and any(
            "violation" in n for n in names) and any(
            "ok" in n for n in names), (rid, names)
        for n in names:
            assert os.path.exists(os.path.join(FIXTURES, n)), \
                "rule %s: fixture %s missing" % (rid, n)
        declared.update(names)
    on_disk = {n for n in os.listdir(FIXTURES) if n != "README.md"}
    undeclared = on_disk - declared
    assert not undeclared, \
        "fixtures no rule declares (stale?): %s" % sorted(undeclared)
    with open(os.path.abspath(__file__), encoding="utf-8") as f:
        test_src = f.read()
    unexercised = {n for n in on_disk if n not in test_src}
    assert not unexercised, \
        "fixtures never exercised by a test: %s" % sorted(unexercised)


def test_registry_fixture_pair():
    rep = _fixture("registry_violation", ["registry-consistency"])
    msgs = [f.message for f in rep.findings]
    assert len(msgs) == 7, [f.render() for f in rep.findings]
    # one undeclared use per registry kind + the uncovered prefix...
    assert any("'d2h_typo'" in m and "SITES" in m for m in msgs)
    assert any("'bad_code'" in m for m in msgs)
    assert any("'serving.requets'" in m for m in msgs)
    assert any("dynamic counter prefix" in m for m in msgs)
    # ...and one unused declaration per registry kind
    assert any("'kv_push'" in m and "never consulted" in m for m in msgs)
    assert any("'group2ctx'" in m and "never constructed" in m
               for m in msgs)
    assert any("'faults.injected.*'" in m and "dead" in m for m in msgs)
    ok = _fixture("registry_ok", ["registry-consistency"])
    assert ok.clean, [f.render() for f in ok.findings]


# ---------------------------------------------------------------------------
# Framework: suppressions, baseline, parse errors
# ---------------------------------------------------------------------------

def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


_VIOLATION_SRC = "import jax\n\n\ndef f(fn):\n    return jax.jit(fn)%s\n"


def test_suppression_requires_justification(tmp_path):
    # bare disable: the finding STILL reports, plus a grammar finding
    p = _write(tmp_path, "bare.py",
               _VIOLATION_SRC % "   # mxlint: disable=jit-site")
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    rules = sorted(f.rule for f in rep.findings)
    assert rules == ["jit-site", SUPPRESSION_RULE], \
        [f.render() for f in rep.findings]
    assert "justification" in rep.findings[0].message \
        or "justification" in rep.findings[1].message


def test_suppression_with_justification_silences(tmp_path):
    p = _write(tmp_path, "just.py",
               _VIOLATION_SRC % "   # mxlint: disable=jit-site -- fixture")
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    assert rep.clean
    assert [(f.rule, j) for f, j in rep.suppressed] == \
        [("jit-site", "fixture")]


def test_standalone_suppression_covers_next_line(tmp_path):
    src = ("import jax\n\n\ndef f(fn):\n"
           "    # mxlint: disable=jit-site -- covers the next line\n"
           "    return jax.jit(fn)\n")
    p = _write(tmp_path, "standalone.py", src)
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    assert rep.clean and len(rep.suppressed) == 1


def test_unknown_rule_in_suppression_is_flagged(tmp_path):
    p = _write(tmp_path, "typo.py",
               _VIOLATION_SRC % "   # mxlint: disable=jit-sight -- typo")
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    rules = sorted(f.rule for f in rep.findings)
    assert rules == ["jit-site", SUPPRESSION_RULE]
    assert any("unknown rule id" in f.message for f in rep.findings)


def test_baseline_grandfathers_and_reports_stale(tmp_path):
    p = _write(tmp_path, "old.py", _VIOLATION_SRC % "")
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    assert len(rep.findings) == 1
    doc = Baseline.render(rep.findings)
    doc["findings"].append({"rule": "jit-site", "path": "gone.py",
                            "anchor": "jax.jit(deleted_code)"})
    bl_path = _write(tmp_path, "bl.json", json.dumps(doc))
    rep2 = run([p], rules=["jit-site"], baseline=bl_path,
               root=str(tmp_path))
    # grandfathered: clean exit, the finding visible as baselined, and
    # the entry whose code no longer exists WARNS instead of erroring
    assert rep2.clean
    assert len(rep2.baselined) == 1
    assert len(rep2.stale_baseline) == 1
    assert rep2.stale_baseline[0]["path"] == "gone.py"
    assert "stale" in rep2.render_text()


def test_baseline_loader_tolerates_garbage(tmp_path):
    p = _write(tmp_path, "v.py", _VIOLATION_SRC % "")
    bl_path = _write(tmp_path, "bad.json", "{not json")
    rep = run([p], rules=["jit-site"], baseline=bl_path,
              root=str(tmp_path))
    # unreadable baseline: warn and lint WITHOUT it — never a crash
    assert len(rep.findings) == 1
    assert any("unreadable" in w for w in rep.warnings)
    bl2 = _write(tmp_path, "odd.json",
                 json.dumps({"findings": [42, {"rule": "jit-site"}]}))
    rep2 = run([p], rules=["jit-site"], baseline=bl2, root=str(tmp_path))
    assert len(rep2.findings) == 1 and len(rep2.warnings) == 2


def test_parse_error_is_a_finding(tmp_path):
    p = _write(tmp_path, "broken.py", "def f(:\n")
    rep = run([p], baseline=Baseline(), root=str(tmp_path))
    assert [f.rule for f in rep.findings] == ["parse-error"]


def test_baseline_never_hides_gate_compromising_rules(tmp_path):
    """Neither --update-baseline nor a hand-edited entry may grandfather
    a bare suppression or a parse error — those mean the gate itself is
    compromised and must keep failing until the code is fixed."""
    bare = _write(tmp_path, "bare.py",
                  _VIOLATION_SRC % "   # mxlint: disable=jit-site")
    broken = _write(tmp_path, "broken.py", "def f(:\n")
    rep = run([bare, broken], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    rules = sorted(f.rule for f in rep.findings)
    assert rules == ["jit-site", SUPPRESSION_RULE, "parse-error"]
    # render (what --update-baseline writes) drops both forbidden rules
    doc = Baseline.render(rep.findings)
    assert [e["rule"] for e in doc["findings"]] == ["jit-site"]
    # and even a hand-edited baseline listing them cannot hide them
    doc["findings"].extend(
        {"rule": f.rule, "path": f.path, "anchor": f.anchor}
        for f in rep.findings if f.rule != "jit-site")
    bl_path = _write(tmp_path, "bl.json", json.dumps(doc))
    rep2 = run([bare, broken], rules=["jit-site"], baseline=bl_path,
               root=str(tmp_path))
    assert sorted(f.rule for f in rep2.findings) == \
        [SUPPRESSION_RULE, "parse-error"], \
        [f.render() for f in rep2.findings]


def test_registry_duplicate_declaration_is_flagged(tmp_path):
    """Two SITES declarations in one scan (e.g. a fixture mini-registry
    next to the runtime's) must not silently bind an arbitrary one —
    the duplicate is a finding and uses check against the FIRST."""
    a = _write(tmp_path, "a.py",
               'SITES = ("dispatch",)\n\n\ndef go(fire):\n'
               '    fire("dispatch")\n')
    b = _write(tmp_path, "b.py", 'SITES = ("other",)\n')
    rep = run([a, b], rules=["registry-consistency"], baseline=Baseline(),
              root=str(tmp_path))
    msgs = [f.message for f in rep.findings]
    assert any("duplicate SITES" in m for m in msgs), msgs
    # the legitimate use against the first declaration stays clean
    assert not any("not declared" in m for m in msgs), msgs


def test_json_report_shape(tmp_path):
    p = _write(tmp_path, "v.py", _VIOLATION_SRC % "")
    rep = run([p], rules=["jit-site"], baseline=Baseline(),
              root=str(tmp_path))
    doc = rep.to_dict()
    assert doc["clean"] is False
    assert doc["counts"] == {"jit-site": 1}
    f = doc["findings"][0]
    assert set(f) == {"rule", "path", "line", "col", "message", "anchor"}
    json.dumps(doc)                      # JSON-serializable end to end


# ---------------------------------------------------------------------------
# CLI: stable exit codes, JSON artifact, baseline update
# ---------------------------------------------------------------------------

def _cli(args, cwd=ROOT):
    return subprocess.run([sys.executable, MXLINT] + args,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=300, cwd=cwd)


def test_cli_exit_codes(tmp_path):
    clean = _write(tmp_path, "clean.py", "x = 1\n")
    dirty = _write(tmp_path, "dirty.py", _VIOLATION_SRC % "")
    assert _cli(["--baseline", "none", clean]).returncode == 0
    proc = _cli(["--baseline", "none", dirty])
    assert proc.returncode == 1
    assert "jit-site" in proc.stdout
    assert _cli(["--no-such-flag", clean]).returncode == 2
    assert _cli(["--baseline", "none",
                 str(tmp_path / "missing.py")]).returncode == 2
    assert _cli(["--rules", "not-a-rule", clean]).returncode == 2
    assert _cli([]).returncode == 2


def test_cli_json_operand_forms(tmp_path):
    clean = _write(tmp_path, "clean.py", "x = 1\n")
    # '-' means stdout: the report prints, nothing named '-' is linted
    proc = _cli(["--baseline", "none", "--json", "-", clean])
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["clean"] is True
    # with no operand the report also goes to stdout
    proc = _cli(["--baseline", "none", "--json", clean])
    assert proc.returncode == 0
    assert json.loads(proc.stdout)["paths"] == [clean]
    # an ambiguous operand (not '-', not *.json, not an existing lint
    # path) is a usage error, never silently linted or guessed at
    proc = _cli(["--baseline", "none", "--json",
                 str(tmp_path / "report.out"), clean])
    assert proc.returncode == 2
    assert "--json operand" in proc.stderr


def test_cli_list_rules():
    proc = _cli(["--list-rules"])
    assert proc.returncode == 0
    assert proc.stdout.split() == list(ALL_RULE_IDS)


def test_cli_update_baseline_prunes_stale(tmp_path):
    dirty = _write(tmp_path, "dirty.py", _VIOLATION_SRC % "")
    bl = str(tmp_path / "bl.json")
    with open(bl, "w") as f:
        json.dump({"findings": [{"rule": "jit-site", "path": "gone.py",
                                 "anchor": "deleted"}]}, f)
    proc = _cli(["--baseline", bl, "--update-baseline", dirty])
    assert proc.returncode == 0, proc.stderr
    with open(bl) as f:
        doc = json.load(f)
    anchors = [e["anchor"] for e in doc["findings"]]
    assert anchors == ["return jax.jit(fn)"]        # stale entry pruned
    # and the refreshed baseline makes the same run clean
    assert _cli(["--baseline", bl, dirty]).returncode == 0


def test_cli_update_baseline_partial_rules_preserves_others(tmp_path):
    dirty = _write(tmp_path, "dirty.py", _VIOLATION_SRC % "")
    bl = str(tmp_path / "bl.json")
    assert _cli(["--baseline", bl,
                 "--update-baseline", dirty]).returncode == 0
    # a dispatch-hook-only refresh must not wipe the jit-site entry the
    # full gate run depends on
    proc = _cli(["--baseline", bl, "--rules", "dispatch-hook",
                 "--update-baseline", dirty])
    assert proc.returncode == 0, proc.stderr
    with open(bl) as f:
        doc = json.load(f)
    assert [e["rule"] for e in doc["findings"]] == ["jit-site"]
    assert _cli(["--baseline", bl, dirty]).returncode == 0


def test_cli_update_baseline_needs_a_file(tmp_path):
    dirty = _write(tmp_path, "dirty.py", _VIOLATION_SRC % "")
    # '--baseline none' disabled the baseline: nothing to rewrite, and
    # silently clobbering the default committed file would be worse
    proc = _cli(["--baseline", "none", "--update-baseline", dirty])
    assert proc.returncode == 2
    assert "no file to write" in proc.stderr


# ---------------------------------------------------------------------------
# Tier-1 gate lane: the whole runtime lints clean, artifact banked
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _full_repo_gate_run():
    """ONE timed full-repo CLI run shared by the gate lane and the
    wall-time guard (each full mxflow pass costs ~5s of tier-1 budget;
    both tests assert on the same artifact)."""
    import time as _time
    art_dir = os.environ.get("MXTPU_ARTIFACT_DIR", "/tmp/mxtpu_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art = os.path.join(art_dir, "mxlint.json")
    t0 = _time.monotonic()
    proc = _cli(["--json", art, "mxnet_tpu", "tools"])
    wall = _time.monotonic() - t0
    return proc, wall, art


def test_mxlint_gate_lane():
    """`run_checks.sh lint` equivalent: zero unsuppressed findings over
    mxnet_tpu/ tools/ against the committed baseline, with the
    JSON report written under ``$MXTPU_ARTIFACT_DIR``."""
    proc, _, art = _full_repo_gate_run()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(art) as f:
        doc = json.load(f)
    assert doc["clean"] is True
    assert doc["findings"] == []
    assert doc["rules"] == list(ALL_RULE_IDS)
    # every honoured suppression carries its justification text, and
    # the committed baseline has no stale entries
    assert doc["suppressed"], "expected justified disables in-tree"
    assert all(s["justification"] for s in doc["suppressed"])
    assert doc["stale_baseline"] == []
    # the grandfathered raw-jit sites are visible, not silently gone
    assert any(b["rule"] == "jit-site" for b in doc["baselined"])


def test_gate_catches_a_seeded_regression(tmp_path):
    """End-to-end negative control: drop an aliased-jit file into a
    copy of the scan set and the gate exits 1 — proving the lane fails
    when someone actually adds a raw compile site."""
    bad = _write(tmp_path, "regression.py",
                 "from jax import jit as J\n\n\ndef f(fn):\n"
                 "    return J(fn)\n")
    proc = _cli(["--baseline",
                 os.path.join(ROOT, "tools", "mxlint_baseline.json"),
                 bad])
    assert proc.returncode == 1
    assert "jit-site" in proc.stdout


@pytest.mark.parametrize("fixture,rule", [
    ("trace_purity_violation.py", "trace-purity"),
    ("host_sync_chain_violation.py", "host-sync"),
    ("lockset_violation.py", "lockset"),
    ("donation_interproc_violation.py", "donation-safety"),
    ("thread_race_violation.py", "thread-race"),
    ("collective_violation.py", "collective-discipline"),
    ("future_lifecycle_violation.py", "future-lifecycle"),
    ("resource_release_violation.py", "resource-release"),
    ("torn_state_violation.py", "torn-state-on-raise"),
])
def test_gate_catches_each_interprocedural_seed(fixture, rule):
    """Negative control per NEW rule: each seeded fixture fails the
    CLI gate against the COMMITTED baseline — the lane cannot go green
    on un-fixed interprocedural violations."""
    proc = _cli(["--baseline",
                 os.path.join(ROOT, "tools", "mxlint_baseline.json"),
                 os.path.join(FIXTURES, fixture)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert rule in proc.stdout


# ---------------------------------------------------------------------------
# mxflow: call graph, effect summaries, --changed, wall-time guard
# ---------------------------------------------------------------------------

def _project_of(paths, root):
    from mxnet_tpu.analysis.core import Project, iter_python_files
    proj = Project(root=str(root))
    for p in iter_python_files([str(x) for x in paths]):
        proj.add_file(p)
    return proj


def test_callgraph_resolution(tmp_path):
    """Cross-module (absolute AND relative import), self-type method,
    nested-def and local-instance resolution; dynamic calls counted,
    never edged; SCCs detected."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "util.py").write_text(
        "def helper(x):\n    return x\n\n\n"
        "def ping(x):\n    return pong(x)\n\n\n"
        "def pong(x):\n    return ping(x)\n")
    (pkg / "main.py").write_text(
        "from . import util\n"
        "from pkg.util import helper as H\n\n\n"
        "class Engine:\n"
        "    def run(self, x):\n"
        "        return self._step(x)\n\n"
        "    def _step(self, x):\n"
        "        def inner(y):\n"
        "            return util.helper(y)\n"
        "        return inner(H(x))\n\n\n"
        "def drive(x, cb):\n"
        "    e = Engine()\n"
        "    cb(x)\n"
        "    return e.run(x)\n")
    proj = _project_of([pkg], tmp_path)
    g = proj.callgraph()

    def fi(path, qual):
        got = g._by_key.get(("pkg/%s" % path, qual))
        assert got is not None, (path, qual, sorted(g._by_key))
        return got

    def callee_names(f):
        return sorted(c.qualname for c, _l, _c in g.callees(f))

    # relative-import module alias + nested def + aliased from-import
    assert callee_names(fi("main.py", "Engine._step")) == \
        ["Engine._step.inner", "helper"]
    assert callee_names(fi("main.py", "Engine._step.inner")) == ["helper"]
    # self-type method resolution + local-instance constructor typing
    assert callee_names(fi("main.py", "Engine.run")) == ["Engine._step"]
    drive = fi("main.py", "drive")
    assert "Engine.run" in callee_names(drive)
    # cb(x) through a parameter is DYNAMIC: counted, not edged
    assert g.dynamic_calls.get(drive) == 1
    # the ping<->pong recursion is one SCC of size 2
    sccs = [sorted(f.qualname for f in c) for c in g.sccs()]
    assert ["ping", "pong"] in sccs
    stats = g.stats()
    assert stats["functions"] >= 7 and stats["largest_scc"] == 2
    assert stats["cyclic_sccs"] == 1


def test_summary_facts_and_cache(tmp_path):
    """Direct effect facts of one function, and the content-keyed
    facts cache: a second run over the same text is a cache hit."""
    p = tmp_path / "mod.py"
    # NOTE: _LOG is a module global — mutating a PARAMETER's object is
    # deliberately not a fact (the executor's owned-accumulator
    # pattern, a traced root passing its own dict down to be filled,
    # would drown the signal); globals/closures/self are tracked
    p.write_text(
        "import time\n\n_LOG = []\n\n\n"
        "def effects(out):\n"
        "    t = time.time()\n"
        "    out.wait_to_read()\n"
        "    _LOG.append(t)\n"
        "    return t\n")
    proj = _project_of([p], tmp_path)
    g = proj.callgraph()
    summ = proj.summaries()
    (fi,) = [f for f in g.functions if f.name == "effects"]
    facts = summ.facts_of(fi)
    assert [form for _l, _c, form in facts.syncs] == [".wait_to_read()"]
    assert facts.clock and facts.clock[0][1] == "time.time"
    assert any("_LOG.append" in d for _l, d in facts.mutations)
    # second run, same text: served from the facts cache
    from mxnet_tpu.analysis import summaries as sm
    before = sm.cache_stats()
    proj2 = _project_of([p], tmp_path)
    proj2.summaries()
    after = sm.cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


def test_changed_subset_expands_to_reverse_dependents(tmp_path):
    """--changed core semantics: linting only a changed CALLEE pulls
    in its callers (their findings depend on its summary); without
    expansion the caller's finding is filtered out."""
    (tmp_path / "util.py").write_text(
        "import jax\n\n\n"
        "def fused(fn, w, s):\n"
        "    step = jax.jit(fn, donate_argnums=(0, 1))\n"
        "    return step(w, s)\n")
    (tmp_path / "caller.py").write_text(
        "from util import fused\n\n\n"
        "def train(fn, w, s):\n"
        "    out = fused(fn, w, s)\n"
        "    return out, w\n")
    kw = dict(rules=["donation-safety"], baseline=Baseline(),
              root=str(tmp_path))
    full = run([str(tmp_path)], **kw)
    assert [f.path for f in full.findings] == ["caller.py"]
    narrow = run([str(tmp_path)], only=["util.py"], **kw)
    assert narrow.clean and narrow.subset == ["util.py"]
    expanded = run([str(tmp_path)], only=["util.py"],
                   expand_dependents=True, **kw)
    assert [f.path for f in expanded.findings] == ["caller.py"]
    assert expanded.subset == ["caller.py", "util.py"]
    # subset mode never reports stale-baseline noise
    assert expanded.stale_baseline == []


def _dep_proj(tmp_path):
    (tmp_path / "util.py").write_text(
        "def fetch(b):\n"
        "    return b.asnumpy()\n")
    (tmp_path / "hot.py").write_text(
        "from util import fetch\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    (tmp_path / "other.py").write_text(
        "def unrelated():\n"
        "    return 1\n")
    return dict(rules=["host-sync"], baseline=Baseline(),
                root=str(tmp_path),
                dep_cache=str(tmp_path / "dep.json"))


def test_dep_cache_fast_path(tmp_path):
    """A full run banks the dependency skeleton; a later subset run
    with a valid cache parses ONLY the reverse closure — the untouched
    non-dependent file is never read into the project — and still
    finds the chain through the unchanged caller."""
    kw = _dep_proj(tmp_path)
    full = run([str(tmp_path)], **kw)
    assert len(full.findings) == 1 and full.files == 3
    assert os.path.exists(kw["dep_cache"])
    # edit the sink (the pre-commit scenario), lint just the change
    (tmp_path / "util.py").write_text(
        "def fetch(b):\n"
        "    x = 1\n"
        "    return b.asnumpy(), x\n")
    rep = run([str(tmp_path)], only=["util.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"
    assert rep.files == 2                      # util + hot, not other
    assert rep.subset == ["hot.py", "util.py"]
    # the chain finding reflects the EDITED file: sink moved to line 3
    assert [(f.path, f.line) for f in rep.findings] == [("util.py", 3)]


def test_dep_cache_stale_falls_back_and_refreshes(tmp_path):
    """An un-touched file whose hash disagrees with the cache (edited
    behind --changed's back, cache from another branch, ...) forces
    the full parse — which rewrites the cache, so the NEXT subset run
    goes fast again."""
    kw = _dep_proj(tmp_path)
    run([str(tmp_path)], **kw)
    (tmp_path / "other.py").write_text(
        "def unrelated():\n"
        "    return 2\n")                      # changed, NOT in only
    rep = run([str(tmp_path)], only=["util.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "miss:stale"
    assert rep.files == 3                      # full view reparsed
    rep2 = run([str(tmp_path)], only=["util.py"],
               expand_dependents=True, **kw)
    assert rep2.dep_cache == "hit" and rep2.files == 2
    # and no cache at all is its own miss
    os.unlink(kw["dep_cache"])
    rep3 = run([str(tmp_path)], only=["util.py"],
               expand_dependents=True, **kw)
    assert rep3.dep_cache == "miss:absent" and rep3.files == 3


def test_dep_cache_keeps_registry_context(tmp_path):
    """Subset parsing must not orphan registry USES: the files
    declaring SITES/COUNTERS/FUSED_FALLBACK_CODES are always in the
    parse set, so a changed counter_inc call checks against the real
    declarations instead of reporting a phantom undeclared use."""
    (tmp_path / "reg.py").write_text(
        'COUNTERS = ("serving.requests",)\n')
    (tmp_path / "user.py").write_text(
        "from mxnet_tpu import telemetry\n\n\n"
        "def f():\n"
        '    telemetry.counter_inc("serving.requests")\n')
    (tmp_path / "other.py").write_text(
        "def unrelated():\n"
        "    return 1\n")
    kw = dict(rules=["registry-consistency"], baseline=Baseline(),
              root=str(tmp_path),
              dep_cache=str(tmp_path / "dep.json"))
    # prime with ALL rules: the cache is written by runs that build
    # the call graph (a registry-only run never needs it)
    assert run([str(tmp_path)], **dict(kw, rules=None)).clean
    (tmp_path / "user.py").write_text(
        "from mxnet_tpu import telemetry\n\n\n"
        "def f():\n"
        '    telemetry.counter_inc("serving.requests")\n'
        "    return None\n")
    rep = run([str(tmp_path)], only=["user.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"
    assert rep.files == 2                      # user + reg, not other
    assert rep.clean, [f.render() for f in rep.findings]


def test_dep_cache_fast_path_parses_callees(tmp_path):
    """Facts flow CALLEE-ward too: a donation misuse introduced in a
    touched CALLER needs the untouched callee's summary (the donating
    builder) to be detected — the fast path must close the parse set
    over imports, not just reverse dependents."""
    (tmp_path / "util.py").write_text(
        "import jax\n\n\n"
        "def fused(fn, w, s):\n"
        "    step = jax.jit(fn, donate_argnums=(0, 1))\n"
        "    return step(w, s)\n")
    (tmp_path / "caller.py").write_text(
        "from util import fused\n\n\n"
        "def train(fn, w, s):\n"
        "    out = fused(fn, w, s)\n"
        "    return out\n")
    kw = dict(rules=["donation-safety"], baseline=Baseline(),
              root=str(tmp_path),
              dep_cache=str(tmp_path / "dep.json"))
    assert run([str(tmp_path)], **kw).clean    # primes the cache
    # the pre-commit edit: reuse w after it rode a donated position
    (tmp_path / "caller.py").write_text(
        "from util import fused\n\n\n"
        "def train(fn, w, s):\n"
        "    out = fused(fn, w, s)\n"
        "    return out, w\n")
    rep = run([str(tmp_path)], only=["caller.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"
    assert [f.path for f in rep.findings] == ["caller.py"], \
        [f.render() for f in rep.findings]
    assert rep.files == 2                      # caller + util (callee)


def test_changed_keeps_chain_sink_in_untouched_file(tmp_path):
    """Editing only the hot CALLER to reach an existing blocking
    helper must still fail --changed: the sink anchors in the
    untouched helper file, and the finding survives the subset filter
    because its witness chain crosses the touched file — on both the
    dep-cache fast path and the full-parse subset path."""
    kw = _dep_proj(tmp_path)
    run([str(tmp_path)], **kw)                 # primes the cache
    (tmp_path / "hot.py").write_text(          # edit the CALLER only
        "from util import fetch\n\n\n"
        "def loop(batches, log):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        log(fetch(b))\n")
    rep = run([str(tmp_path)], only=["hot.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"
    assert [(f.path, f.line) for f in rep.findings] == [("util.py", 2)]
    assert "hot.py" in rep.findings[0].via
    # chain-bearing findings expose the crossing files in the JSON too
    assert rep.findings[0].to_dict()["via"] == rep.findings[0].via \
        or rep.findings[0].to_dict()["via"] == list(rep.findings[0].via)
    # same answer without the cache (full-parse subset path)
    rep2 = run([str(tmp_path)], only=["hot.py"],
               expand_dependents=True,
               **dict(kw, dep_cache=None))
    assert [(f.path, f.line) for f in rep2.findings] == [("util.py", 2)]


def test_changed_closure_is_audited(tmp_path):
    """--changed reports WHAT it linted: the touched files, the
    reverse-dependent expansion, the parsed set and how many findings
    anchored outside the subset were kept only via chain crossings —
    so a '0 findings' on a partial view is auditable; --json carries
    the closure record verbatim."""
    kw = _dep_proj(tmp_path)
    run([str(tmp_path)], **kw)                 # primes the cache
    (tmp_path / "hot.py").write_text(          # edit the CALLER only
        "from util import fetch\n\n\n"
        "def loop(batches, log):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        log(fetch(b))\n")
    rep = run([str(tmp_path)], only=["hot.py"],
              expand_dependents=True, **kw)
    c = rep.closure
    assert c["touched"] == ["hot.py"]
    assert c["linted"] == ["hot.py"] and c["dependents"] == 0
    assert "util.py" in c["parsed"]            # the callee was parsed
    assert c["via_kept"] == 1                  # sink-elsewhere finding
    assert rep.to_dict()["closure"] == c
    # touching the CALLEE expands to its reverse dependent
    (tmp_path / "util.py").write_text(
        "def fetch(b):\n"
        "    return b.asnumpy()\n")
    rep2 = run([str(tmp_path)], only=["util.py"],
               expand_dependents=True, **kw)
    c2 = rep2.closure
    assert c2["touched"] == ["util.py"]
    assert c2["linted"] == ["hot.py", "util.py"]
    assert c2["dependents"] == 1
    # a full (non-subset) run has no closure record
    assert run([str(tmp_path)], **kw).closure is None


def test_local_shadowing_never_fabricates_a_call_edge(tmp_path):
    """A parameter (or any local binding) named like a module function
    must resolve as DYNAMIC, not as the shadowed module function —
    otherwise correct code fails the gate on a chain that is not a
    real call path."""
    (tmp_path / "shadow.py").write_text(
        "def fetch(b):\n"
        "    return b.asnumpy()\n\n\n"
        "def loop(batches, fetch):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    kw = dict(rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "shadow.py").write_text(   # positive control: no param
        "def fetch(b):\n"
        "    return b.asnumpy()\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    rep = run([str(tmp_path)], **kw)
    assert [(f.path, f.line) for f in rep.findings] == [("shadow.py", 2)]


def test_relative_import_inside_package_init_resolves(tmp_path):
    """`from . import util` inside pkg/__init__.py resolves against
    the package ITSELF (its module name already dropped '__init__'),
    so chains out of package __init__ files are not silently lost."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "util.py").write_text(
        "def fetch(b):\n"
        "    return b.asnumpy()\n")
    (pkg / "__init__.py").write_text(
        "from . import util\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        util.fetch(b)\n")
    rep = run([str(tmp_path)], rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    assert [(f.path, f.line) for f in rep.findings] \
        == [("pkg/util.py", 2)], [f.render() for f in rep.findings]
    assert "pkg/__init__.py" in rep.findings[0].via


def test_nested_def_binding_does_not_shadow_hot_scope(tmp_path):
    """A name bound INSIDE a nested def shadows nothing in the hot
    function's own scope: the outer np.asarray sync must still be
    flagged even when a nested helper has a param named `np`."""
    (tmp_path / "hotnp.py").write_text(
        "import numpy as np\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    def helper(np):\n"
        "        return np\n"
        "    for b in batches:\n"
        "        helper(np.asarray(b))\n")
    rep = run([str(tmp_path)], rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    assert [f.rule for f in rep.findings] == ["host-sync"], \
        [f.render() for f in rep.findings]
    (tmp_path / "hotnp.py").write_text(      # compliant twin: the HOT
        "import numpy as np\n\n\n"           # scope itself rebinds np
        "def loop(batches, np):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        np.asarray(b)\n")
    rep = run([str(tmp_path)], rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    assert rep.clean, [f.render() for f in rep.findings]


def test_lockset_method_escaping_as_value_loses_entry_locks(tmp_path):
    """A private method handed somewhere as a VALUE (Timer/Thread
    callback target) can be invoked bare — its locked call-edge
    callers must not credit it with a held-at-entry lockset."""
    (tmp_path / "escape.py").write_text(
        "import threading\n\n\n"
        "class Buf:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.buf = []\n\n"
        "    def flush(self):\n"
        "        with self._lock:\n"
        "            self.buf.append(0)\n"
        "            self._drain()\n\n"
        "    def start(self):\n"
        "        threading.Timer(1.0, self._drain).start()\n\n"
        "    def _drain(self):\n"
        "        self.buf.append(1)\n")
    rep = run([str(tmp_path)], rules=["lockset"], baseline=Baseline(),
              root=str(tmp_path))
    assert [f.rule for f in rep.findings] == ["lockset"], \
        [f.render() for f in rep.findings]
    assert "buf" in rep.findings[0].message


def test_decorator_above_jit_runs_at_def_time(tmp_path):
    """A decorator stacked above @jax.jit evaluates ONCE, at def time,
    in the enclosing scope — it must not become a call edge of the
    traced function (a false 'inside the trace cone' gate failure)."""
    (tmp_path / "deco.py").write_text(
        "import jax\n\n"
        "_CALLS = []\n\n\n"
        "def audit():\n"
        "    def wrap(fn):\n"
        "        _CALLS.append(fn.__name__)\n"
        "        return fn\n"
        "    return wrap\n\n\n"
        "@audit()\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    return x + 1\n")
    kw = dict(rules=["trace-purity"], baseline=Baseline(),
              root=str(tmp_path))
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "deco.py").write_text(       # positive control: the
        "import jax\n\n"                     # impurity IN the body
        "_CALLS = []\n\n\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    _CALLS.append(1)\n"
        "    return x + 1\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["trace-purity"]


def test_staticmethod_donation_needs_no_self_shift(tmp_path):
    """@staticmethod params line up with the call args as written: the
    bound-method shift must not move inferred donated positions off by
    one (dropping the real donation, flagging the wrong arg)."""
    (tmp_path / "sm.py").write_text(
        "import jax\n\n\n"
        "class Step:\n"
        "    @staticmethod\n"
        "    def fused(w, s):\n"
        "        prog = jax.jit(lambda a, b: (a, b),\n"
        "                       donate_argnums=(1,))\n"
        "        return prog(w, s)\n\n"
        "    def train(self, w, s):\n"
        "        out = self.fused(w, s)\n"
        "        return out, s\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "'s'" in rep.findings[0].message
    (tmp_path / "sm.py").write_text(         # compliant twin: reuse w
        "import jax\n\n\n"                   # (position 0, NOT donated)
        "class Step:\n"
        "    @staticmethod\n"
        "    def fused(w, s):\n"
        "        prog = jax.jit(lambda a, b: (a, b),\n"
        "                       donate_argnums=(1,))\n"
        "        return prog(w, s)\n\n"
        "    def train(self, w, s):\n"
        "        out = self.fused(w, s)\n"
        "        return out, w\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert rep.clean, [f.render() for f in rep.findings]


def test_changed_registry_decl_edit_reaches_untouched_users(tmp_path):
    """Registry uses are string-keyed, not call edges: touching only
    the DECLARING file must fall back to the full parse (every use
    site re-checked) and the use-site finding in the untouched file
    must survive the subset filter via its declaring-file `via`."""
    (tmp_path / "reg.py").write_text(
        'COUNTERS = ("serving.requests", "serving.errors")\n')
    (tmp_path / "user.py").write_text(
        "from mxnet_tpu import telemetry\n\n\n"
        "def f():\n"
        '    telemetry.counter_inc("serving.requests")\n'
        '    telemetry.counter_inc("serving.errors")\n')
    kw = dict(rules=["registry-consistency"], baseline=Baseline(),
              root=str(tmp_path),
              dep_cache=str(tmp_path / "dep.json"))
    assert run([str(tmp_path)], **dict(kw, rules=None)).clean
    (tmp_path / "reg.py").write_text(        # drop a declared counter
        'COUNTERS = ("serving.requests",)\n')
    rep = run([str(tmp_path)], only=["reg.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "miss:registry-decl-touched"
    assert [f.path for f in rep.findings] == ["user.py"], \
        [f.render() for f in rep.findings]
    assert "serving.errors" in rep.findings[0].message
    assert "reg.py" in rep.findings[0].via


def test_dep_cache_survives_narrow_runs(tmp_path):
    """A one-off narrow run (fixture test, single file) must not
    clobber the repo-wide skeleton: the cache is keyed on the lint
    path set and only a --changed fallback may overwrite across sets."""
    kw = _dep_proj(tmp_path)
    run([str(tmp_path)], **kw)               # repo-wide prime
    import mxnet_tpu.analysis.core as _core
    doc_before = _core.load_dep_cache(kw["dep_cache"])
    run([str(tmp_path / "other.py")], **kw)  # narrow run, same cache
    doc_after = _core.load_dep_cache(kw["dep_cache"])
    assert doc_after == doc_before           # untouched
    rep = run([str(tmp_path)], only=["util.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"            # still valid
    # a cache from a DIFFERENT path set is a miss, and the --changed
    # fallback rewrites it for its own (canonical) set
    rep2 = run([str(tmp_path / "hot.py"), str(tmp_path / "util.py")],
               only=["util.py"], expand_dependents=True, **kw)
    assert rep2.dep_cache == "miss:paths"
    doc2 = _core.load_dep_cache(kw["dep_cache"])
    assert doc2["paths"] == ["hot.py", "util.py"]


def test_same_named_defs_keep_distinct_facts(tmp_path):
    """Branch-defined same-named defs must not alias the LAST def's
    effect facts: an impurity in the FIRST variant (both are traced —
    each carries its own @jax.jit) must still be flagged."""
    (tmp_path / "variants.py").write_text(
        "import jax\n"
        "import time\n\n\n"
        "def build(flag):\n"
        "    if flag:\n"
        "        @jax.jit\n"
        "        def kernel(x):\n"
        "            return x * time.time()\n"
        "    else:\n"
        "        @jax.jit\n"
        "        def kernel(x):\n"
        "            return x + 1\n"
        "    return kernel\n")
    rep = run([str(tmp_path)], rules=["trace-purity"],
              baseline=Baseline(), root=str(tmp_path))
    assert [f.rule for f in rep.findings] == ["trace-purity"], \
        [f.render() for f in rep.findings]
    assert "reads the wall clock" in rep.findings[0].message
    assert rep.findings[0].line == 9           # the FIRST variant's line


def test_shadowed_module_names_are_not_global_effects(tmp_path):
    """A parameter named `random` (or `np`, `time`, ...) makes calls
    through it calls on a runtime object — classifying them as global
    RNG/clock reads fails the gate on correct code."""
    (tmp_path / "shadowed.py").write_text(
        "import jax\n\n\n"
        "def helper(random):\n"
        "    return random.random()\n\n\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    return helper(x)\n")
    kw = dict(rules=["trace-purity"], baseline=Baseline(),
              root=str(tmp_path))
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "shadowed.py").write_text(     # positive control
        "import jax\n"
        "import random\n\n\n"
        "def helper(x):\n"
        "    return x * random.random()\n\n\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    return helper(x)\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["trace-purity"]
    assert "draws from the global RNG" in rep.findings[0].message


def test_bound_method_passed_as_value_is_traced(tmp_path):
    """jax.jit(self._kernel): the bound method runs under the tracer —
    it must be a trace-purity root via the same self-type resolution
    the call edges use."""
    (tmp_path / "bound.py").write_text(
        "import jax\n"
        "import time\n\n\n"
        "class K:\n"
        "    def build(self):\n"
        "        return jax.jit(self._kernel)\n\n"
        "    def _kernel(self, x):\n"
        "        return x * time.time()\n")
    rep = run([str(tmp_path)], rules=["trace-purity"],
              baseline=Baseline(), root=str(tmp_path))
    assert [f.rule for f in rep.findings] == ["trace-purity"], \
        [f.render() for f in rep.findings]
    assert "reads the wall clock" in rep.findings[0].message


def test_changed_handles_paths_with_spaces(tmp_path, monkeypatch):
    """git -z plumbing: a touched path containing a space must reach
    the linter intact, not be split into fragments that silently match
    nothing (a clean exit on an unlinted violation)."""
    def g(*a):
        return subprocess.run(["git", "-C", str(tmp_path)] + list(a),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
    assert g("init", "-q").returncode == 0
    (tmp_path / "base.py").write_text("x = 1\n")
    g("add", ".")
    assert g("-c", "user.email=t@t", "-c", "user.name=t",
             "commit", "-qm", "seed").returncode == 0
    (tmp_path / "my probe.py").write_text(_VIOLATION_SRC % "")
    (tmp_path / "base.py").write_text("x = 2\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location("_mxlint_cli", MXLINT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    files, err = mod.changed_files("HEAD")
    assert err is None, err
    assert files == ["base.py", "my probe.py"]


def test_nested_local_store_is_not_a_global_mutation(tmp_path):
    """An outer `global` declaration does not inherit into nested
    defs: a traced kernel's plain local store to a name its ENCLOSING
    function declared global is pure (Python scoping), while the
    kernel declaring `global` itself is the real impurity."""
    (tmp_path / "pure.py").write_text(
        "import jax\n\n"
        "_N = 0\n\n\n"
        "def outer():\n"
        "    global _N\n"
        "    _N = 1\n\n"
        "    def kernel(x):\n"
        "        _N = x + 1\n"
        "        return _N\n\n"
        "    return jax.jit(kernel)\n")
    kw = dict(rules=["trace-purity"], baseline=Baseline(),
              root=str(tmp_path))
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "pure.py").write_text(         # positive control
        "import jax\n\n"
        "_N = 0\n\n\n"
        "def outer():\n"
        "    def kernel(x):\n"
        "        global _N\n"
        "        _N = 2\n"
        "        return x\n\n"
        "    return jax.jit(kernel)\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["trace-purity"]
    assert "writes global '_N'" in rep.findings[0].message


def test_changed_cli_smoke():
    """--changed against the repo's own git state: exits clean either
    way (nothing touched, or the touched subset lints clean) and never
    crashes; --changed-base with a bogus ref is a usage error."""
    # base HEAD, not the default origin/main: on a committed tree this
    # takes the cheap nothing-touched path instead of re-linting the
    # whole branch's worth of files on every tier-1 run
    proc = _cli(["--changed", "--changed-base", "HEAD",
                 "mxnet_tpu", "tools"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "--changed" in proc.stdout or "mxlint" in proc.stdout
    proc = _cli(["--changed", "--changed-base", "no-such-ref-xyz",
                 "mxnet_tpu"])
    assert proc.returncode == 2
    proc = _cli(["--changed", "--update-baseline", "mxnet_tpu"])
    assert proc.returncode == 2
    proc = _cli(["--dep-cache"])
    assert proc.returncode == 2


def test_changed_cli_dep_cache_self_primes(tmp_path):
    """The first --changed run (cache absent) pays the full parse and
    banks the skeleton; the second hits it. '--dep-cache none' opts
    out entirely."""
    cache = str(tmp_path / "dep.json")
    first = _cli(["--changed", "--changed-base", "HEAD",
                  "--dep-cache", cache, "mxnet_tpu", "tools"])
    assert first.returncode == 0, first.stdout + first.stderr
    if "no python files touched" in first.stdout:
        pytest.skip("clean tree: --changed has nothing to lint")
    assert "dep cache miss:absent" in first.stdout
    assert os.path.exists(cache)
    second = _cli(["--changed", "--changed-base", "HEAD",
                   "--dep-cache", cache, "mxnet_tpu", "tools"])
    assert second.returncode == 0, second.stdout + second.stderr
    # a touched registry-DECLARING file legitimately forces the full
    # parse every time (string-keyed uses have no call edges to follow)
    assert ("dep cache hit" in second.stdout
            or "miss:registry-decl-touched" in second.stdout), \
        second.stdout
    off = _cli(["--changed", "--changed-base", "HEAD",
                "--dep-cache", "none", "mxnet_tpu", "tools"])
    assert off.returncode == 0
    assert "dep cache off" in off.stdout


def test_chain_finding_baseline_keys_on_sink(tmp_path):
    """Refactoring an INTERMEDIATE caller (rename, line drift) must
    not invalidate a grandfathered chain finding: the baseline keys on
    the sink line only."""
    hot = tmp_path / "hot.py"
    hot.write_text(
        "from sink import fetch\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    sink = tmp_path / "sink.py"
    sink.write_text(
        "def fetch(b):\n"
        "    return b.asnumpy()\n")
    kw = dict(rules=["host-sync"], root=str(tmp_path))
    rep = run([str(tmp_path)], baseline=Baseline(), **kw)
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert (f.path, f.line) == ("sink.py", 2)       # anchored at the sink
    bl_path = _write(tmp_path, "bl.json",
                     json.dumps(Baseline.render(rep.findings)))
    assert run([str(tmp_path)], baseline=bl_path, **kw).clean
    # refactor the intermediate caller: rename + shift lines
    hot.write_text(
        "from sink import fetch\n\n\n"
        "def renamed_loop(batches, extra):   # mxlint: hot\n"
        "    del extra\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    rep3 = run([str(tmp_path)], baseline=bl_path, **kw)
    assert rep3.clean, [x.render() for x in rep3.findings]
    assert len(rep3.baselined) == 1
    assert rep3.stale_baseline == []


def test_trace_purity_via_includes_registration_file(tmp_path):
    """The file holding the jit/_InstrumentedProgram REGISTRATION call
    is part of the witness: a --changed run touching only that file
    (the newly-introduced `jax.jit(helper)` line) must still surface
    the impurity that anchors in the untouched helper file."""
    (tmp_path / "util.py").write_text(
        "_CACHE = {}\n\n\n"
        "def helper(x):\n"
        "    _CACHE[0] = x\n"
        "    return x\n")
    (tmp_path / "app.py").write_text(
        "import jax\n\n"
        "from util import helper\n\n"
        "prog = jax.jit(helper)\n")
    kw = dict(rules=["trace-purity"], baseline=Baseline(),
              root=str(tmp_path))
    full = run([str(tmp_path)], **kw)
    assert [(f.path, f.line) for f in full.findings] == [("util.py", 5)]
    assert "app.py" in full.findings[0].via
    narrow = run([str(tmp_path)], only=["app.py"],
                 expand_dependents=True, **kw)
    assert [(f.path, f.line) for f in narrow.findings] \
        == [("util.py", 5)], [f.render() for f in narrow.findings]


def test_suppressed_sync_never_hides_another(tmp_path):
    """Every sync site in every reachable sink gets its own finding: a
    justified disable on the FIRST fetch in a helper must not swallow
    the bare fetch on the next line, nor a farther sink function
    behind the suppressed one."""
    (tmp_path / "util.py").write_text(
        "def deeper(b):\n"
        "    return b.wait_to_read()\n\n\n"
        "def fetch(b):\n"
        "    x = b.asnumpy()   # mxlint: disable=host-sync -- "
        "deliberate: admission-path marshalling\n"
        "    b.wait_to_read()\n"
        "    return deeper(b), x\n")
    (tmp_path / "hot.py").write_text(
        "from util import fetch\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    rep = run([str(tmp_path)], rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    assert sorted((f.path, f.line) for f in rep.findings) \
        == [("util.py", 2), ("util.py", 7)], \
        [f.render() for f in rep.findings]
    assert [(f.path, f.line) for f, _ in rep.suppressed] \
        == [("util.py", 6)]


def test_param_annotation_runs_at_def_time(tmp_path):
    """A parameter annotation on a traced def evaluates ONCE, at def
    time, in the enclosing scope — like a stacked decorator it must
    not become a call edge of the traced function."""
    (tmp_path / "anno.py").write_text(
        "import jax\n\n"
        "_SPECS = []\n\n\n"
        "def make_spec():\n"
        "    _SPECS.append(1)\n"
        "    return None\n\n\n"
        "@jax.jit\n"
        "def kernel(x: make_spec()):\n"
        "    return x + 1\n")
    kw = dict(rules=["trace-purity"], baseline=Baseline(),
              root=str(tmp_path))
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "anno.py").write_text(       # positive control: the
        "import jax\n\n"                     # call IN the body
        "_SPECS = []\n\n\n"
        "def make_spec():\n"
        "    _SPECS.append(1)\n"
        "    return None\n\n\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    make_spec()\n"
        "    return x + 1\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["trace-purity"]


def test_unbound_base_method_call_keeps_arg_positions(tmp_path):
    """`Base.update(self, w)` super-delegation passes self EXPLICITLY
    as arg 0 — the bound-method shift must not move the inferred
    donated position onto self (false finding) while missing the real
    use-after-donate of w."""
    (tmp_path / "base.py").write_text(
        "import jax\n\n\n"
        "class Base:\n"
        "    def update(self, w):\n"
        "        step = jax.jit(lambda v: v, donate_argnums=(0,))\n"
        "        return step(w)\n")
    (tmp_path / "sub.py").write_text(
        "from base import Base\n\n\n"
        "class Sub(Base):\n"
        "    def __init__(self):\n"
        "        self.count = 0\n\n"
        "    def update(self, w):\n"
        "        y = Base.update(self, w)\n"
        "        self.count += 1\n"
        "        return y, w\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert [(f.path, f.line) for f in rep.findings] == [("sub.py", 11)], \
        [f.render() for f in rep.findings]
    assert "'w'" in rep.findings[0].message
    # the bound form still shifts: self.update-style delegation via an
    # instance consumes the receiver binding
    (tmp_path / "sub.py").write_text(
        "from base import Base\n\n\n"
        "def drive(w):\n"
        "    b = Base()\n"
        "    y = b.update(w)\n"
        "    return y, w\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert [(f.path, f.line) for f in rep.findings] == [("sub.py", 7)], \
        [f.render() for f in rep.findings]


def test_decorator_armed_hot_sink_not_double_counted(tmp_path):
    """A hot caller reaching a sink whose # mxlint: hot marker arms
    the DECORATOR line must produce only the direct finding — the
    transitive skip mirrors _hot_functions' def-or-decorator-line
    check, or the same sync line is reported twice under one baseline
    key."""
    (tmp_path / "m.py").write_text(
        "def wrap(fn):\n"
        "    return fn\n\n\n"
        "# mxlint: hot\n"
        "@wrap\n"
        "def fetch(b):\n"
        "    return b.asnumpy()\n\n\n"
        "def loop(batches):   # mxlint: hot\n"
        "    for b in batches:\n"
        "        fetch(b)\n")
    rep = run([str(tmp_path)], rules=["host-sync"], baseline=Baseline(),
              root=str(tmp_path))
    assert [(f.path, f.line) for f in rep.findings] == [("m.py", 8)], \
        [f.render() for f in rep.findings]


def test_donation_gate_skips_graph_on_donation_free_tree(tmp_path):
    """--rules donation-safety on a tree with no donate_argnums and no
    markers must answer without building the call graph (the cheap
    gate runs BEFORE the interprocedural build)."""
    (tmp_path / "plain.py").write_text(
        "def helper(x):\n"
        "    return x + 1\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert rep.clean
    assert "callgraph" not in rep.timings, rep.timings
    # positive control: one literal donate_argnums anywhere re-enables
    # the interprocedural feed
    (tmp_path / "prog.py").write_text(
        "import jax\n\n\n"
        "def build(fn):\n"
        "    return jax.jit(fn, donate_argnums=(0,))\n")
    rep = run([str(tmp_path)], rules=["donation-safety"],
              baseline=Baseline(), root=str(tmp_path))
    assert "callgraph" in rep.timings, rep.timings


def test_divergence_sees_fallthrough_suffix(tmp_path):
    """`if rank != 0: return` BEFORE a psum diverges too: a
    terminating arm skips the block's suffix, the fallthrough arm
    inherits it — sequence comparison must include both."""
    kw = dict(rules=["collective-discipline"], baseline=Baseline(),
              root=str(tmp_path))
    (tmp_path / "early.py").write_text(
        "from jax import lax\n\n\n"
        "def step(rank, x):\n"
        "    if rank != 0:\n"
        "        return x\n"
        "    return lax.psum(x, 'dp')\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.line for f in rep.findings] == [5], \
        [f.render() for f in rep.findings]
    assert "DIFFERENT collective sequences" in rep.findings[0].message
    (tmp_path / "early.py").write_text(   # rank-invariant control:
        "from jax import lax\n\n\n"       # both arms reach the psum
        "def step(rank, x):\n"
        "    if rank != 0:\n"
        "        x = x * 2\n"
        "    return lax.psum(x, 'dp')\n")
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]


def test_collective_call_site_channel_override(tmp_path):
    """A call-line `# mxsync: collective channel=...` overrides the
    def-line default: the step-gated commit path calling a kv-default
    primitive mismatches without the override and is clean with it."""
    src_tmpl = (
        "class CollectiveGate:\n"
        "    def __init__(self, channel='step'):\n"
        "        self.channel = channel\n\n"
        "    def arrive_and_wait(self):\n"
        "        return 0\n\n\n"
        "def bcast(tree):   # mxsync: collective channel=kv\n"
        "    return tree\n\n\n"
        "def commit(tree):\n"
        "    gate = CollectiveGate(channel='step')\n"
        "    gate.arrive_and_wait()\n"
        "    return bcast(tree)%s\n")
    kw = dict(rules=["collective-discipline"], baseline=Baseline(),
              root=str(tmp_path))
    (tmp_path / "ov.py").write_text(src_tmpl % "")
    rep = run([str(tmp_path)], **kw)
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "channel 'kv'" in rep.findings[0].message
    assert "'step'" in rep.findings[0].message
    (tmp_path / "ov.py").write_text(
        src_tmpl % "   # mxsync: collective channel=step")
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]


def test_thread_spawned_from_thread_keeps_its_own_root(tmp_path):
    """A Thread target spawning ANOTHER thread hands the inner target
    to the NEW thread — following that registration edge during root
    propagation would fabricate a cross-root race between two points
    of one sequential spawn chain."""
    (tmp_path / "spawn.py").write_text(
        "import threading\n\n\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._outer).start()\n\n"
        "    def _outer(self):\n"
        "        threading.Thread(target=self._inner).start()\n\n"
        "    def _inner(self):\n"
        "        self._n = 1\n"
        "        self._report()\n\n"
        "    def _report(self):\n"
        "        return self._n\n")
    rep = run([str(tmp_path)], rules=["thread-race"],
              baseline=Baseline(), root=str(tmp_path))
    assert rep.clean, [f.render() for f in rep.findings]


def test_closure_read_of_shadowing_local_is_not_a_global(tmp_path):
    """A nested worker reading an ENCLOSING function's local that
    shadows a module-global name touches the closure variable, not the
    global — Python scoping walks every enclosing frame, so must the
    global-access resolution."""
    kw = dict(rules=["thread-race"], baseline=Baseline(),
              root=str(tmp_path))
    (tmp_path / "closure.py").write_text(
        "import threading\n\n"
        "_buf = []\n\n\n"
        "def start():\n"
        "    _buf = []\n"
        "    def worker():\n"
        "        return len(_buf)\n"
        "    threading.Thread(target=worker).start()\n\n\n"
        "def writeback():\n"
        "    global _buf\n"
        "    _buf = [1]\n")
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "closure.py").write_text(   # positive control: no
        "import threading\n\n"              # shadowing local — the
        "_buf = []\n\n\n"                   # worker reads the global
        "def start():\n"
        "    def worker():\n"
        "        return len(_buf)\n"
        "    threading.Thread(target=worker).start()\n\n\n"
        "def writeback():\n"
        "    global _buf\n"
        "    _buf = [1]\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["thread-race"], \
        [f.render() for f in rep.findings]


def test_function_level_excepthook_registers_one_root(tmp_path):
    """A hook assignment inside a function must register exactly ONE
    thread root (with the function as scope, so the registration ref
    edge is excluded from main propagation) — the whole-tree module
    scan used to see it too, and the two clone roots fabricated a
    cross-root race for code that only ever runs under the hook."""
    (tmp_path / "hook.py").write_text(
        "import sys\n\n\n"
        "def _hook(t, v, tb):\n"
        "    pass\n\n\n"
        "def install():\n"
        "    sys.excepthook = _hook\n")
    from mxnet_tpu.analysis.core import Project, iter_python_files
    proj = Project(root=str(tmp_path))
    for p in iter_python_files([str(tmp_path)]):
        proj.add_file(p)
    tm = proj.threads()
    assert len(tm.roots) == 1, [r.label() for r in tm.roots]
    assert tm.roots[0].kind == "excepthook"


def test_pool_submit_is_a_thread_root(tmp_path):
    """`self._pool.submit(self._resolve, ...)` on a ThreadPoolExecutor
    attr makes _resolve a thread root — the serving resolver-pool
    shape — so its unlocked writes race main-thread reads."""
    (tmp_path / "pool.py").write_text(
        "import threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n\n\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._pool = ThreadPoolExecutor(2)\n"
        "        self._done = 0\n\n"
        "    def dispatch(self, batch):\n"
        "        self._pool.submit(self._resolve, batch)\n\n"
        "    def _resolve(self, batch):\n"
        "        self._done += 1\n\n"
        "    def done(self):\n"
        "        return self._done\n")
    rep = run([str(tmp_path)], rules=["thread-race"],
              baseline=Baseline(), root=str(tmp_path))
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "pool-worker" in rep.findings[0].message
    assert "_resolve" in rep.findings[0].message


def test_lint_wall_time_guard():
    """The full-repo mxflow run stays inside its wall-time budget
    (MXLINT_BUDGET_S, default 60s — ~10x the measured cost, so only a
    pathological blowup of the interprocedural passes trips it), and
    the JSON report carries per-rule timings + call-graph stats."""
    budget = float(os.environ.get("MXLINT_BUDGET_S", "60"))
    proc, wall, art = _full_repo_gate_run()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert wall < budget, \
        "full mxflow lint took %.1fs (budget %.0fs)" % (wall, budget)
    with open(art) as f:
        doc = json.load(f)
    for rule in ALL_RULE_IDS:
        assert rule in doc["timings"], doc["timings"]
    assert "callgraph" in doc["timings"] and "summaries" in doc["timings"]
    # the mxsync/mxlife models are timed under their own keys (like
    # callgraph/summaries) so rule timings never double-count the builds
    assert "threads" in doc["timings"] and "collectives" in doc["timings"]
    assert "lifecycle" in doc["timings"]
    cg = doc["callgraph"]
    for key in ("functions", "call_edges", "ref_edges", "dynamic_calls",
                "sccs", "cyclic_sccs", "largest_scc", "facts_cache",
                "thread_roots", "thread_rooted_functions",
                "collective_sites", "collective_host_sites",
                "gate_crossings", "lifecycle_future_classes",
                "lifecycle_resolver_functions",
                "lifecycle_simulated_functions", "may_raise_functions"):
        assert key in cg, cg
    assert cg["functions"] > 1000        # the graph really covers the repo
    assert cg["call_edges"] > 500
    # the mxsync models really cover the runtime: the coalescer/
    # sampler/heartbeat/pool roots and the kvstore/spmd collective
    # surface are all discoverable statically
    assert cg["thread_roots"] >= 10, cg
    assert cg["collective_sites"] >= 5, cg
    assert cg["collective_host_sites"] >= 4, cg
    assert cg["gate_crossings"] >= 4, cg
    # ...and the mxlife model: the serving _Request class, its
    # resolver set, and the runtime's real may-raise surface
    assert cg["lifecycle_future_classes"] >= 1, cg
    assert cg["lifecycle_resolver_functions"] >= 2, cg
    assert cg["may_raise_functions"] >= 100, cg


# ---------------------------------------------------------------------------
# mxlife: may_raise summaries, typestate semantics, --explain
# ---------------------------------------------------------------------------

def test_may_raise_propagates_through_unguarded_calls(tmp_path):
    """An unguarded own raise seeds may_raise; it propagates to
    callers through UNGUARDED call sites only — a try with ANY except
    handler swallows (conservative-quiet), while handler bodies and
    finally bodies propagate past their own try."""
    (tmp_path / "m.py").write_text(
        "def origin(x):\n"
        "    raise ValueError(x)\n\n\n"
        "def unguarded(x):\n"
        "    return origin(x)\n\n\n"
        "def guarded(x):\n"
        "    try:\n"
        "        return origin(x)\n"
        "    except Exception:\n"
        "        return None\n\n\n"
        "def in_handler(x):\n"
        "    try:\n"
        "        return 1\n"
        "    except Exception:\n"
        "        return origin(x)\n\n\n"
        "def in_finally(x):\n"
        "    try:\n"
        "        return 1\n"
        "    finally:\n"
        "        origin(x)\n")
    proj = _project_of([tmp_path / "m.py"], tmp_path)
    g = proj.callgraph()
    summ = proj.summaries()
    by = {fi.name: fi for fi in g.functions}
    assert summ.may_raise(by["origin"])
    assert summ.may_raise(by["unguarded"])
    assert not summ.may_raise(by["guarded"])
    assert summ.may_raise(by["in_handler"])
    assert summ.may_raise(by["in_finally"])
    # the witness chain bottoms out at the origin raise
    hops, line, exc = summ.raise_chain(by["unguarded"])
    assert [h.name for h, _l in hops] == ["origin"]
    assert line == 2 and exc == "ValueError"


def test_future_lifecycle_resolving_callee_discharges(tmp_path):
    """Passing an owned request to an in-scan callee that resolves its
    parameter on every path discharges the obligation (the _shed
    pattern) — and the same code WITHOUT the resolving callee is a
    strand."""
    common = (
        "from concurrent.futures import Future\n\n\n"
        "class Req:\n"
        "    def __init__(self):\n"
        "        self.future = Future()\n\n\n"
        "def risky(x):\n"
        "    if x:\n"
        "        raise RuntimeError(x)\n\n\n"
        "def shed(req, exc):\n"
        "    if not req.future.done():\n"
        "        req.future.set_exception(exc)\n\n\n")
    kw = dict(rules=["future-lifecycle"], baseline=Baseline(),
              root=str(tmp_path))
    (tmp_path / "m.py").write_text(
        common
        + "def drive(q, x):\n"
        "    req = q.get()\n"
        "    try:\n"
        "        risky(x)\n"
        "    except Exception as e:\n"
        "        shed(req, e)\n"
        "        return\n"
        "    req.future.set_result(x)\n")
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "m.py").write_text(        # positive control: the
        common                             # handler forgets the request
        + "def drive(q, x):\n"
        "    req = q.get()\n"
        "    try:\n"
        "        risky(x)\n"
        "    except Exception:\n"
        "        return\n"
        "    req.future.set_result(x)\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["future-lifecycle"], \
        [f.render() for f in rep.findings]
    assert "UNRESOLVED" in rep.findings[0].message


def test_future_lifecycle_finally_resolution_is_clean(tmp_path):
    """A finally-guarded resolve covers the raise leg too — the
    linearized try/except/finally walk must see it."""
    (tmp_path / "m.py").write_text(
        "from concurrent.futures import Future\n\n\n"
        "class Req:\n"
        "    def __init__(self):\n"
        "        self.future = Future()\n\n\n"
        "def risky(x):\n"
        "    if x:\n"
        "        raise RuntimeError(x)\n\n\n"
        "def drive(q, x):\n"
        "    req = q.get()\n"
        "    out = None\n"
        "    try:\n"
        "        out = risky(x)\n"
        "    finally:\n"
        "        if not req.future.done():\n"
        "            req.future.set_result(out)\n")
    rep = run([str(tmp_path)], rules=["future-lifecycle"],
              baseline=Baseline(), root=str(tmp_path))
    assert rep.clean, [f.render() for f in rep.findings]


def test_cli_explain(tmp_path):
    """--explain <rule> prints the rule's doc, finding format and its
    fixture pair paths; exit 2 on an unknown rule id."""
    proc = _cli(["--explain", "future-lifecycle"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "future-lifecycle" in proc.stdout
    assert "future_lifecycle_violation.py" in proc.stdout
    assert "future_lifecycle_ok.py" in proc.stdout
    assert "rule, path, line, col, message" in proc.stdout
    proc = _cli(["--explain", "no-such-rule"])
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr
    # every rule id explains without error (doc + fixtures wired)
    for rid in ALL_RULE_IDS:
        assert _cli(["--explain", rid]).returncode == 0, rid


def test_changed_refinds_lifecycle_strand_through_callee_edit(tmp_path):
    """mxlife rides the --changed machinery: touching only the CALLEE
    whose may_raise summary creates the caller's strand must re-find
    the caller's finding through the reverse-dependent closure, on the
    dep-cache fast path."""
    (tmp_path / "util.py").write_text(
        "def risky(x):\n"
        "    if x:\n"
        "        raise RuntimeError(x)\n")
    (tmp_path / "worker.py").write_text(
        "from concurrent.futures import Future\n"
        "from util import risky\n\n\n"
        "class Req:\n"
        "    def __init__(self):\n"
        "        self.future = Future()\n\n\n"
        "def drive(q, x):\n"
        "    req = q.get()\n"
        "    risky(x)\n"
        "    req.future.set_result(x)\n")
    kw = dict(rules=["future-lifecycle"], baseline=Baseline(),
              root=str(tmp_path),
              dep_cache=str(tmp_path / "dep.json"))
    full = run([str(tmp_path)], **kw)
    assert [(f.path, f.line) for f in full.findings] \
        == [("worker.py", 12)], [f.render() for f in full.findings]
    (tmp_path / "util.py").write_text(       # edit ONLY the callee
        "def risky(x):\n"
        "    x = x + 1\n"
        "    if x:\n"
        "        raise RuntimeError(x)\n")
    rep = run([str(tmp_path)], only=["util.py"],
              expand_dependents=True, **kw)
    assert rep.dep_cache == "hit"
    assert [(f.path, f.line) for f in rep.findings] \
        == [("worker.py", 12)], [f.render() for f in rep.findings]
    # the witness names the EDITED origin raise line
    assert "util.py:4" in rep.findings[0].message


def test_finally_resolution_covers_return_legs(tmp_path):
    """A future resolved in a finally covers a `return` INSIDE the try
    too — the return leg runs the finalbody before exiting, so no
    strand may report (the rule's own recommended fix must not keep
    firing)."""
    (tmp_path / "m.py").write_text(
        "from concurrent.futures import Future\n\n\n"
        "class Req:\n"
        "    def __init__(self):\n"
        "        self.future = Future()\n\n\n"
        "def risky(x):\n"
        "    if x:\n"
        "        raise RuntimeError(x)\n\n\n"
        "def drive(q, x):\n"
        "    req = q.get()\n"
        "    try:\n"
        "        if x:\n"
        "            return 1\n"
        "        risky(x)\n"
        "    finally:\n"
        "        if not req.future.done():\n"
        "            req.future.set_result(x)\n"
        "    return 0\n")
    rep = run([str(tmp_path)], rules=["future-lifecycle"],
              baseline=Baseline(), root=str(tmp_path))
    assert rep.clean, [f.render() for f in rep.findings]


def test_done_guarded_late_resolve_is_not_a_double(tmp_path):
    """A resolve under `if not v.future.done():` AFTER an earlier
    resolve on the same path is the sanctioned idempotent form — the
    R-state is runtime-infeasible on the not-done branch and must not
    report a phantom double-resolve; the truly unguarded second
    resolve still does."""
    common = (
        "from concurrent.futures import Future\n\n\n"
        "class Req:\n"
        "    def __init__(self):\n"
        "        self.future = Future()\n\n\n")
    kw = dict(rules=["future-lifecycle"], baseline=Baseline(),
              root=str(tmp_path))
    (tmp_path / "m.py").write_text(
        common
        + "def drive(q, x, exc):\n"
        "    req = q.get()\n"
        "    req.future.set_result(x)\n"
        "    if not req.future.done():\n"
        "        req.future.set_exception(exc)\n")
    rep = run([str(tmp_path)], **kw)
    assert rep.clean, [f.render() for f in rep.findings]
    (tmp_path / "m.py").write_text(        # positive control: bare
        common
        + "def drive(q, x, exc):\n"
        "    req = q.get()\n"
        "    req.future.set_result(x)\n"
        "    req.future.set_exception(exc)\n")
    rep = run([str(tmp_path)], **kw)
    assert [f.rule for f in rep.findings] == ["future-lifecycle"]
    assert "SECOND time" in rep.findings[0].message
