#!/usr/bin/env bash
# Tier-1 CI gate: the invariant analyzer's three legs, then the test suite.
#
#   bash scripts/ci_check.sh [extra pytest args...]
#
# Correctness lives in the tests; speed is measured on the chip by the
# benchmark in bench/ (`python3 bench/run.py --workload <cell> ...`, cells
# in BENCHMARK.json), never by a CPU timing here.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# invariant analyzer first: the three checks are seconds, the suite is
# minutes — fail fast on a broken invariant before paying for the tests.
# (1) repo-specific lint: fails on any finding not grandfathered in
#     analysis_baseline.json or suppressed with `# repro: noqa[rule-id]`
python -m repro.analysis src
# (2) runtime retrace detector: hot-path jits must compile once per
#     power-of-two bucket, never per distinct batch size
python -m repro.analysis.retrace --smoke
# (3) lock-order checker: no acquisition cycles, no JAX dispatch while a
#     plane lock is held, across a threaded serve/swap/churn scenario
python -m repro.analysis.lockgraph --smoke

python -m pytest -x -q "$@"
