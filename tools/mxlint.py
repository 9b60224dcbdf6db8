#!/usr/bin/env python
"""mxlint — AST static analysis for the runtime's own invariants.

Usage::

    python tools/mxlint.py [options] <paths...>

    python tools/mxlint.py mxnet_tpu tools        # the CI gate
    python tools/mxlint.py --json out.json mxnet_tpu       # JSON report
    python tools/mxlint.py --rules jit-site mxnet_tpu      # one rule
    python tools/mxlint.py --update-baseline mxnet_tpu tools
    python tools/mxlint.py --changed mxnet_tpu tools  # pre-commit

Options:
    --rules a,b,...      run only these rule ids (default: all)
    --list-rules         print the rule ids and exit 0
    --explain RULE       print the rule's documentation, its finding
                         format and its fixture pair under
                         tests/lint_fixtures/, then exit 0 (exit 2 on
                         an unknown rule id) — the fast way for a new
                         contributor to see what a rule polices and
                         what compliant code looks like
    --baseline PATH      grandfather file (default:
                         tools/mxlint_baseline.json; 'none' disables)
    --update-baseline    rewrite the baseline from the current findings
                         (stale entries pruned) and exit 0
    --json [PATH]        emit the JSON report to PATH (or stdout when no
                         PATH follows); the text report is skipped
    --changed            lint only files touched vs the git merge-base
                         PLUS their transitive reverse call-graph
                         dependents (a changed callee changes its
                         callers' effect summaries). Findings are
                         filtered to the subset — keeping sinks whose
                         witness chain crosses it — and stale-baseline
                         hygiene is skipped. With a valid dep cache
                         only the subset plus its import closure is
                         PARSED (the fast pre-commit loop); otherwise
                         the whole path set is parsed and the cache
                         refreshed.
    --changed-base REF   base ref for --changed (default: origin/main,
                         falling back to main, then HEAD — on the
                         default branch this means "what my working
                         tree touches", the pre-commit loop)
    --dep-cache PATH     dependency-skeleton cache written by full
                         runs and consumed by --changed (default:
                         .mxlint_depcache.json at the repo root;
                         'none' disables). Purely an accelerator: a
                         stale or absent cache falls back to the full
                         parse, never to wrong results.

Exit codes (stable; run_checks.sh and the tier-1 lane key on them):
    0  clean — no unsuppressed, non-baselined findings (stale-baseline
       entries and suppressed/baselined findings only warn)
    1  findings
    2  usage error (unknown flag/rule, missing path)

Suppression grammar (the justification is REQUIRED)::

    something_flagged()   # mxlint: disable=<rule> -- why this is safe

The analyzer itself lives in ``mxnet_tpu/analysis`` (stdlib-only: no
jax import, no native build — ``bash tools/run_checks.sh lint`` runs it
standalone).
"""
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# import the analysis package WITHOUT executing mxnet_tpu/__init__.py
# (which pulls in jax, ~5s and a hard dependency): a stub parent whose
# __path__ points at the package directory lets the normal import
# machinery load mxnet_tpu.analysis standalone — the lint stage of
# run_checks.sh must work on a box with no jax and no native build
if "mxnet_tpu" not in sys.modules:
    _pkg = types.ModuleType("mxnet_tpu")
    _pkg.__path__ = [os.path.join(ROOT, "mxnet_tpu")]
    sys.modules["mxnet_tpu"] = _pkg

from mxnet_tpu.analysis import run, ALL_RULE_IDS          # noqa: E402
from mxnet_tpu.analysis.core import Baseline              # noqa: E402

DEFAULT_BASELINE = os.path.join(ROOT, "tools", "mxlint_baseline.json")
DEFAULT_DEP_CACHE = os.path.join(ROOT, ".mxlint_depcache.json")


def usage(msg):
    sys.stderr.write("mxlint: %s\n(see tools/mxlint.py --help)\n" % msg)
    return 2


def _git(*args):
    import subprocess
    try:
        proc = subprocess.run(["git"] + list(args), cwd=ROOT,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        return None, str(e)
    if proc.returncode != 0:
        return None, proc.stderr.strip()
    return proc.stdout, None


def changed_files(base_ref=None):
    """Repo-relative .py paths touched vs the merge-base (committed,
    staged, unstaged) plus untracked files, or (None, error)."""
    base = None
    for ref in ([base_ref] if base_ref else ["origin/main", "main"]):
        out, _err = _git("merge-base", "HEAD", ref)
        if out is not None:
            base = out.strip()
            break
    if base is None and base_ref:
        return None, "cannot resolve --changed-base %r" % base_ref
    if base is None:
        base = "HEAD"
    # -z: NUL-separated, unquoted — a path with a space (or a name git
    # would C-quote) must come back intact, not split into fragments
    # that silently match nothing
    out, err = _git("diff", "--name-only", "-z", base)
    if out is None:
        return None, "git diff failed: %s" % err
    files = {f for f in out.split("\0") if f}
    out, err = _git("ls-files", "--others", "--exclude-standard", "-z")
    if out is not None:
        files.update(f for f in out.split("\0") if f)
    # deleted files stay in the set: a deleted callee changes its
    # callers' effect summaries, and the dep cache's reverse map still
    # knows who called it — the closure lints those callers
    return sorted(f for f in files if f.endswith(".py")), None


def explain_rule(rid):
    """Print one rule's story: its module docstring (what it polices,
    how to comply/suppress), the finding format, and the fixture pair
    a contributor can read/run. Exit 0, or 2 on an unknown id."""
    from mxnet_tpu.analysis.rules import rule_table
    table = rule_table()
    if rid not in table:
        return usage("unknown rule %r (known: %s)"
                     % (rid, ", ".join(ALL_RULE_IDS)))
    rule = table[rid]
    import inspect
    doc = (inspect.getdoc(inspect.getmodule(type(rule)))
           or "").strip()
    print("rule: %s" % rid)
    print("=" * (6 + len(rid)))
    print(doc)
    print()
    print("finding format: <rule, path, line, col, message> — rendered")
    print("as 'path:line:col: %s: <message>'; baseline identity is" % rid)
    print("(rule, path, anchor) where anchor is the stripped finding")
    print("line, so unrelated edits never invalidate an entry.")
    print()
    print("fixture pair (run them to see the rule fire / stay silent):")
    for name in getattr(rule, "fixture_basenames", ()):
        path = os.path.join("tests", "lint_fixtures", name)
        kind = "violation" if "violation" in name else "compliant"
        print("  %-10s %s" % (kind + ":", path))
    print()
    print("try: python tools/mxlint.py --baseline none --rules %s "
          "tests/lint_fixtures/%s" % (
              rid, getattr(rule, "fixture_basenames", ("", ))[0]))
    return 0


def main(argv):
    paths = []
    rules = None
    baseline = DEFAULT_BASELINE
    update_baseline = False
    json_path = None
    want_json = False
    changed = False
    changed_base = None
    dep_cache = DEFAULT_DEP_CACHE

    args = list(argv)
    while args:
        a = args.pop(0)
        if a in ("-h", "--help"):
            print(__doc__)
            return 0
        if a == "--list-rules":
            print("\n".join(ALL_RULE_IDS))
            return 0
        if a == "--explain":
            if not args:
                return usage("--explain needs a rule id")
            return explain_rule(args.pop(0))
        if a == "--rules":
            if not args:
                return usage("--rules needs a comma-separated id list")
            rules = [r.strip() for r in args.pop(0).split(",") if r.strip()]
            continue
        if a == "--baseline":
            if not args:
                return usage("--baseline needs a path (or 'none')")
            baseline = args.pop(0)
            if baseline.lower() == "none":
                baseline = None
            continue
        if a == "--update-baseline":
            update_baseline = True
            continue
        if a == "--changed":
            changed = True
            continue
        if a == "--changed-base":
            if not args:
                return usage("--changed-base needs a git ref")
            changed_base = args.pop(0)
            continue
        if a == "--dep-cache":
            if not args:
                return usage("--dep-cache needs a path (or 'none')")
            dep_cache = args.pop(0)
            if dep_cache.lower() == "none":
                dep_cache = None
            continue
        if a == "--json":
            want_json = True
            if args and args[0] == "-":          # explicit stdout
                json_path = args.pop(0)
            elif args and not args[0].startswith("-"):
                if args[0].endswith(".json"):
                    json_path = args.pop(0)
                elif not os.path.exists(args[0]):
                    # neither an existing lint path nor a recognizable
                    # output path — guessing either way silently does
                    # the wrong thing, so refuse
                    return usage(
                        "--json operand %r is neither an existing lint "
                        "path nor a .json output path; use '-' for "
                        "stdout or an output path ending in .json"
                        % args[0])
            continue
        if a.startswith("-"):
            return usage("unknown option %r" % a)
        paths.append(a)
    if not paths:
        return usage("no paths given")

    # analysis runs with repo-relative display paths so baseline entries
    # and reports are machine-independent; relative CLI paths resolve
    # against the CWD as usual
    abs_paths = [os.path.abspath(p) for p in paths]
    missing = [p for p, ap in zip(paths, abs_paths)
               if not os.path.exists(ap)]
    if missing:
        return usage("no such path(s): %s" % ", ".join(missing))

    if update_baseline and baseline is None:
        return usage("--update-baseline with '--baseline none' has no "
                     "file to write; give --baseline a path")
    if changed and update_baseline:
        return usage("--changed lints a partial view; refusing to "
                     "rewrite the baseline from it")
    if changed_base and not changed:
        return usage("--changed-base only makes sense with --changed")

    only = None
    if changed:
        only, err = changed_files(changed_base)
        if only is None:
            return usage(err)
        if not only:
            print("mxlint (--changed): no python files touched — "
                  "nothing to lint")
            return 0

    try:
        if update_baseline:
            # partition against an EMPTY baseline: every current
            # unsuppressed finding lands in the fresh file, stale
            # entries implicitly pruned
            report = run(abs_paths, rules=rules, baseline=Baseline(),
                         root=ROOT, dep_cache=dep_cache)
            out_path = baseline
            doc = Baseline.render(report.findings)
            if rules:
                # a partial-rule run only refreshes ITS rules' entries —
                # wiping the others would fail the next full gate run
                prior = Baseline.load(out_path)
                doc["findings"] = sorted(
                    doc["findings"]
                    + [{"rule": r, "path": p, "anchor": a, "count": n}
                       for (r, p, a), n in prior.entries.items()
                       if r not in set(report.rules)],
                    key=lambda e: (e["rule"], e["path"], e["anchor"]))
            with open(out_path, "w") as f:
                json.dump(doc, f, indent=2, sort_keys=True)
                f.write("\n")
            print("mxlint: baseline %s rewritten with %d finding(s)"
                  % (os.path.relpath(out_path), len(report.findings)))
            return 0
        report = run(abs_paths, rules=rules, baseline=baseline, root=ROOT,
                     only=only, expand_dependents=changed,
                     dep_cache=dep_cache)
    except ValueError as e:          # unknown rule id
        return usage(str(e))
    except FileNotFoundError as e:
        return usage("no such path: %s" % e)

    if changed and not want_json:
        # the audit line for a "0 findings" on a partial view: exactly
        # what closure was linted (touched + reverse dependents), what
        # was parsed to support it, and how many findings anchored
        # OUTSIDE the subset survived only via their witness chains
        c = report.closure or {}
        print("mxlint (--changed): %d touched + %d reverse "
              "dependent(s) = %d file(s) linted, %d parsed (dep cache "
              "%s); %d chain finding(s) kept from outside the subset"
              % (len(c.get("touched", only)), c.get("dependents", 0),
                 len(report.subset or []), report.files,
                 report.dep_cache or "off", c.get("via_kept", 0)))
    if want_json:
        doc = json.dumps(report.to_dict(), indent=2, sort_keys=True)
        if json_path and json_path != "-":
            with open(json_path, "w") as f:
                f.write(doc + "\n")
        else:
            print(doc)
    else:
        print(report.render_text())
    return 0 if report.clean else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
