#!/usr/bin/env bash
# Tier-1 suite, chunked.
#
# One monolithic pytest run is flaky on this container: the process
# accumulates jit caches / forced-device subprocesses for ~10 minutes and
# trips external timeouts. Each chunk below is an independent interpreter
# with a fresh XLA, comfortably under the per-command budget, and a chunk
# failure pinpoints the layer that broke.
#
# Usage: scripts/ci.sh [extra pytest args]
set -uo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# The suite runs on the CPU: Pallas kernels in interpret mode or as their
# jnp twins (kernels/ops.py). The chip is driven by `python chip_smoke.py`
# on the TPU host, not by this script.
export JAX_PLATFORMS=cpu

CHUNKS=(
  "tests/test_kernels.py tests/test_property.py tests/test_tpu_compile.py"
  "tests/test_filters.py"
  "tests/test_backends.py"
  "tests/test_quant.py"
  "tests/test_system.py"
  "tests/test_serve.py"
  "tests/test_planner.py"
  "tests/test_persistent.py"
  "tests/test_obs.py"
  "tests/test_obs_shard.py"
  "tests/test_distributed.py"
  "tests/test_shard.py"
  "tests/test_models_smoke.py tests/test_dryrun_small.py"
)

fail=0
for chunk in "${CHUNKS[@]}"; do
  echo "=== pytest ${chunk} ==="
  # shellcheck disable=SC2086
  python -m pytest -q ${chunk} "$@" || fail=1
done

# Serving-path smoke: the launcher must stay runnable end to end (admission →
# probe → bucket → resume → report), not just unit-tested. Shrunk bring-up
# (corpus/training) — the serving path exercised is identical and the W_q
# ground-truth labeling is the expensive part. --explain/--prometheus keep
# the observability surfaces (lifecycle timelines, calibration report,
# exposition scrape) runnable, not just unit-tested.
echo "=== serve smoke ==="
python -m repro.launch.serve --requests 8 --batch 4 \
  --corpus 2000 --train-queries 64 --explain 2 --prometheus || fail=1

# Sharded serving smoke: the same launcher on a 2-shard engine with the
# health surface — per-shard EXPLAIN attribution, shard skew gauges in the
# scrape, and the --status structured JSON report.
echo "=== serve smoke (sharded + status) ==="
python -m repro.launch.serve --requests 8 --batch 4 \
  --corpus 2000 --train-queries 64 --explain 2 --prometheus \
  --shards 2 --status || fail=1

# EXPLAIN smoke: the quickstart's per-query lifecycle reports across all
# three backends (dense / pallas / pallas_persistent) plus planner routing.
echo "=== quickstart --explain smoke ==="
python examples/quickstart.py --explain --backend dense \
  --corpus 2000 --train-queries 96 --eval-batch 16 --plan-queries 64 \
  || fail=1

# Filter-algebra smoke: composite (AND/OR/NOT) workloads end to end through
# probe → estimate → resume, recall vs the brute-force pre-filter oracle.
# --quick keeps it small and does not overwrite BENCH_filter_algebra.json.
echo "=== filter-algebra smoke ==="
python -m benchmarks.filter_algebra --quick || fail=1

# Benchmark smoke + artifact gate: runs each headline bench (quant,
# persistent, planner, serve, obs, shard) at --quick scale into a temp
# dir, then
# structurally validates both the fresh output and the committed BENCH_*.json
# artifacts (headline metric present, acceptance booleans true). Quick runs
# never scale-match the committed protocol, so no timing-noise regression
# gating happens here — run `scripts/bench_check.py --run` at full scale
# before refreshing a committed artifact.
echo "=== bench smoke + artifact check ==="
python scripts/bench_check.py --run --quick \
  quant persistent planner serve obs shard || fail=1

if [ "$fail" -ne 0 ]; then
  echo "CI: FAILURES (see chunks above)"
  exit 1
fi
echo "CI: all chunks green"
