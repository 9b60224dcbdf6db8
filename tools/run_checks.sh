#!/usr/bin/env bash
# One-shot validation gate: everything the repo claims, in one command.
#   bash tools/run_checks.sh          # full gate (lint + build + tests)
#   bash tools/run_checks.sh lint     # static stage only — no native
#                                     # build, no jax import, seconds
set -e
cd "$(dirname "$0")/.."

lint_stage() {
  echo "== mxlint (AST static analysis)"
  # replaces the old grep stanzas (raw jax.jit / raw dispatch_hook),
  # which an aliased `from jax import jit` walked straight past.
  # Thirteen rules across four families — direct (jit-site,
  # dispatch-hook, lock-discipline, host-sync, donation-safety,
  # registry-consistency), mxflow interprocedural (lockset,
  # trace-purity + transitive layers), mxsync concurrency
  # (thread-race, collective-discipline) and mxlife lifecycle
  # (future-lifecycle, resource-release, torn-state-on-raise) — all
  # stdlib-only: this stage needs no jax import and no native build.
  # Zero unsuppressed findings over the runtime and the tools,
  # against the committed grandfather file
  # tools/mxlint_baseline.json. `python tools/mxlint.py --explain
  # <rule>` documents any rule that fires; the pre-commit loop is
  # `python tools/mxlint.py --changed ...` (tools/pre-commit.sample).
  python tools/mxlint.py mxnet_tpu tools
  # the rule registry itself stays consistent: 13 ids, each with a
  # fixture pair (the meta-test enforces the pairing; this is the
  # jax-free smoke that the CLI agrees)
  test "$(python tools/mxlint.py --list-rules | wc -l)" -eq 13
}

if [ "${1:-}" = "lint" ]; then
  lint_stage
  echo "LINT OK"
  exit 0
fi

lint_stage
echo "== native build"
make -s
echo "== C++ unit tests"
make -s testcpp
echo "== python suite (virtual 8-device CPU mesh)"
python -m pytest tests/ -q
echo "== multichip dryrun (8 virtual devices: dp/sp/tp + Module dp + pp/ep)"
python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('MULTICHIP OK')"
echo "== amalgamation build + tests"
python -m pytest tests/test_amalgamation.py -q
echo "ALL CHECKS PASSED"
