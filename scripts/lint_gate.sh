#!/usr/bin/env bash
# jaxlint gate — the documented pre-push step.
#
# Runs ALL suites (tracing R* + concurrency T* + lifecycle L*) over the
# repo's standard hazard surface, enforces the committed count-based baseline
# (results/jaxlint_baseline.json: new findings fail, fixed findings only
# ever loosen the gate), and always leaves a SARIF artifact at
# results/jaxlint.sarif for CI annotation / editor ingestion — findings
# that are new vs the baseline carry level=error in it, grandfathered
# ones level=note.
#
# Usage:
#   scripts/lint_gate.sh              # gate + artifact
#   scripts/lint_gate.sh --fix-hints  # extra args pass through to the
#                                     # human-readable enforcement run
set -uo pipefail
cd "$(dirname "$0")/.."

# the SARIF artifact is written regardless of the verdict (a failing CI
# run needs the annotations MORE than a passing one)
python lint_tpu.py --suite all --format sarif > results/jaxlint.sarif
sarif_status=$?
if [ $sarif_status -ge 2 ]; then
    echo "lint_gate: jaxlint could not run (exit $sarif_status)" >&2
    exit "$sarif_status"
fi

python lint_tpu.py --suite all "$@"
status=$?
if [ $status -ne 0 ]; then
    echo "lint_gate: FAILED — new findings vs results/jaxlint_baseline.json" >&2
    echo "lint_gate: SARIF artifact at results/jaxlint.sarif" >&2
    exit "$status"
fi
echo "lint_gate: clean (SARIF artifact at results/jaxlint.sarif)"
