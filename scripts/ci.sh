#!/usr/bin/env bash
# CI entry point — tiered stages, each independently failable with its own
# log section (.github/workflows/ci.yml runs one stage per job):
#
#   --lint    ruff check over src/tests/benchmarks/scripts when ruff is
#             installed (rule set + line length pinned in ruff.toml so the
#             local run and CI agree byte-for-byte); otherwise degrades to
#             a python -m compileall syntax pass (the container gates
#             optional tooling — CI images install ruff, minimal dev boxes
#             may not).  Also fails if any Python cache artifact
#             (__pycache__/, .pytest_cache/, *.pyc) is ever TRACKED by
#             git — .gitignore keeps them out, this keeps them out
#             forever.  Finally runs scripts/check_docs.py: every repo
#             path and public symbol referenced by README.md or
#             docs/architecture.md must exist in the tree (AST-harvested
#             symbol universe), so the documentation cannot rot silently.
#   --tier1   kernel-parity gate first (pytest -m "kernels and not slow":
#             every op in kernels/ops.py, Pallas-interpret vs ref.py,
#             including the masked ops' and the multi-mask (Q, N)-plane
#             ops' edge cases), then the full tier-1 suite (pytest -x -q,
#             slow cases deselected per pytest.ini).
#   --chaos   serving-tier failover suite (pytest -m chaos): kill an
#             executor mid-wave (heartbeat-dead while HOLDING fragments)
#             and between waves, and prove zero queries are lost — results
#             at exact parity with a healthy run via lease re-dispatch.
#             The cases also run inside --tier1 (they are not slow-marked);
#             this stage re-runs them in isolation so failover regressions
#             get their own red CI job instead of hiding in the suite.
#   --cache   serving-tier cache hierarchy suite (pytest -m cache): the
#             shard-probe and semantic result caches — snapshot-commit
#             invalidation (refresh/compact can never serve stale),
#             time-travel isolation, LRU byte bounds, bit parity on every
#             hit, degraded-answer keying, admission interplay (a semantic
#             hit consumes no token-bucket budget), and the chaos × cache
#             crossover.  Like --chaos, the cases also run inside --tier1;
#             this stage re-runs them in isolation so a cache-coherence
#             regression gets its own red CI job instead of hiding in the
#             suite.
#   --bench   benchmark smoke + regression gate, TWO bench records:
#               bench_query_paths --tiny  -> BENCH_query_paths.json
#               bench_kernels             -> BENCH_kernels.json
#             Stale records are deleted first and each file must exist
#             non-empty after its run — a bench that crashes before
#             writing its record fails the stage loudly instead of letting
#             check_bench green-light leftover data (check_bench itself
#             also exits 2 on a missing/empty/row-less input).
#             scripts/check_bench.py gates both files against their
#             committed baselines (benchmarks/baselines/<same name>):
#             broken batched/sequential parity, batched throughput not
#             above sequential, filtered recall-vs-oracle < 0.95, zone
#             pruning not reducing fragments, the heterogeneous-filter
#             row (table2.filtered_hetero) not beating one-query
#             probe() calls in its interleaved timing window, not
#             reducing kernel dispatches or diverging from them, the
#             mixed-flavor row (table2.filtered_mixed_flavor) not
#             completing in EXACTLY one kernel dispatch per shard, throughput
#             regression vs baseline on the kernel.* rows (35% noise
#             budget; machine factor pinned by the pure-numpy anchor.*
#             row, so even a uniform kernel regression is caught — table2
#             rows are never wall-clock-gated: they ride the scheduler and
#             swing >2x with load, so they gate on same-window ratios and
#             recall), ANY recall drop vs the baseline, or a baseline row
#             missing from the run.
#
# No stage flags (or --all) runs every stage in order.
#
# Updating a benchmark baseline (after an intentional perf/recall change):
#   PYTHONPATH=src python -m benchmarks.bench_query_paths --tiny \
#       --json benchmarks/baselines/BENCH_query_paths.json
#   PYTHONPATH=src python -m benchmarks.bench_kernels \
#       --json benchmarks/baselines/BENCH_kernels.json
# then commit the new baseline alongside the change that justifies it, and
# say why in the commit message.  Never refresh a baseline to silence a
# regression you cannot explain.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

run_lint=false
run_tier1=false
run_chaos=false
run_cache=false
run_bench=false
if [ "$#" -eq 0 ]; then
  run_lint=true; run_tier1=true; run_chaos=true; run_cache=true; run_bench=true
fi
for arg in "$@"; do
  case "$arg" in
    --lint)  run_lint=true ;;
    --tier1) run_tier1=true ;;
    --chaos) run_chaos=true ;;
    --cache) run_cache=true ;;
    --bench) run_bench=true ;;
    --all)   run_lint=true; run_tier1=true; run_chaos=true; run_cache=true; run_bench=true ;;
    *) echo "usage: $0 [--lint] [--tier1] [--chaos] [--cache] [--bench] [--all]" >&2; exit 2 ;;
  esac
done

if $run_lint; then
  echo "== lint =="
  if command -v git >/dev/null 2>&1 && [ -d .git ]; then
    tracked_caches=$(git ls-files | grep -E '(^|/)(__pycache__|\.pytest_cache)/|\.pyc$' || true)
    if [ -n "$tracked_caches" ]; then
      echo "LINT-ERROR: Python cache artifacts are tracked by git:" >&2
      echo "$tracked_caches" >&2
      echo "  (git rm -r --cached them; .gitignore already excludes them)" >&2
      exit 1
    fi
  fi
  if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks scripts
  else
    echo "ruff not installed — falling back to a compileall syntax pass"
    python -m compileall -q src tests benchmarks scripts
  fi
  # the docs front door must not rot: every path / public symbol referenced
  # by README.md and docs/architecture.md has to exist in the tree
  python scripts/check_docs.py
fi

if $run_tier1; then
  echo "== tier-1: kernel parity (Pallas-interpret vs ref oracle) =="
  python -m pytest -q -m "kernels and not slow"
  echo "== tier-1: full suite =="
  python -m pytest -x -q
fi

if $run_chaos; then
  echo "== chaos: executor failover (kill mid-wave, zero queries lost) =="
  python -m pytest -q -m chaos
fi

if $run_cache; then
  echo "== cache: serving-tier hierarchy (invalidation, parity, LRU bounds) =="
  python -m pytest -q -m cache
fi

if $run_bench; then
  echo "== benchmark smoke (batched + filtered query paths, kernels) =="
  # never let a stale record from an earlier run satisfy the gate
  rm -f BENCH_query_paths.json BENCH_kernels.json
  python -m benchmarks.bench_query_paths --tiny --json BENCH_query_paths.json
  python -m benchmarks.bench_kernels --json BENCH_kernels.json
  for rec in BENCH_query_paths.json BENCH_kernels.json; do
    if [ ! -s "$rec" ]; then
      echo "BENCH-ERROR: $rec missing or empty — the bench run crashed before writing it" >&2
      exit 1
    fi
  done
  echo "== benchmark regression gate =="
  python scripts/check_bench.py BENCH_query_paths.json BENCH_kernels.json \
    --baseline benchmarks/baselines/BENCH_query_paths.json \
    --baseline benchmarks/baselines/BENCH_kernels.json
fi
