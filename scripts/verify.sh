#!/usr/bin/env bash
# Tier-1 verify + serving smoke: what CI runs and what every PR must keep
# green.
#
#   scripts/verify.sh            # lint + full pytest + tiny serving bench
#   scripts/verify.sh --smoke    # lint + serving-counter smoke only (fast):
#                                # asserts the fused-dashboard counters,
#                                # partial_fusions > 0 / subplan_saved > 0
#                                # on the mixed-join-shape workload, the
#                                # concurrent-callers scenario (async_batches
#                                # > 0, fused compiles < async requests,
#                                # malformed batch-mates isolated), AND the
#                                # restart warm-start scenario (a second
#                                # process over the same cache_dir: zero
#                                # plan rebuilds, persist_hits == distinct
#                                # fingerprints, bitwise-identical answers;
#                                # the XLA-cache compile-time and wall-clock
#                                # wins are gated by the timed run only),
#                                # AND the tracing-overhead scenario
#                                # (tracing-on answers bitwise-identical to
#                                # tracing-off, warm overhead bounded, all
#                                # pipeline-stage histograms populated),
#                                # AND the mesh-serving scenario (a 4×-scale
#                                # db through QueryService(mesh=...) on 8
#                                # forced host devices: answers bitwise-
#                                # identical to an identically-padded
#                                # single-device service, individually and
#                                # fused; zero recompiles on within-bucket
#                                # per-shard growth; warm restart with
#                                # plan_builds == 0 from the topology-keyed
#                                # store partition), AND the multi-tenant
#                                # adversarial-mix scenario (one tenant
#                                # flooding malformed + oversized queries is
#                                # held to its token-bucket/queue quota with
#                                # TYPED rejections while the victim
#                                # tenant's p95 stays within 2x its solo
#                                # baseline and its answers stay bitwise-
#                                # identical; cross-tenant submissions still
#                                # fuse — fused compiles < requests; per-
#                                # tenant counters/histograms appear in
#                                # metrics_v2()["tenants"]; no root span
#                                # leaks — open_requests == 0).
#                                # Writes + schema-validates the
#                                # BENCH_serving.json perf trajectory.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== lint (ruff/pyflakes, or built-in fallback) =="
python scripts/lint.py

if [[ "${1:-}" == "--smoke" ]]; then
  # keep the previous trajectory around for the advisory perf diff
  for f in BENCH_serving.json; do
    [[ -f "$f" ]] && cp "$f" "$f.prev"
  done
  echo "== smoke: fused + mixed + async + restart + tracing + mesh + tenant gates =="
  python benchmarks/serving_queries.py --smoke --record BENCH_serving.json
  echo "== smoke: BENCH_serving.json schema check =="
  python -m benchmarks.recorder BENCH_serving.json
  # advisory perf diff vs the previous run: printed, never fails the
  # build (single-run timings on a shared box are noisy)
  for f in BENCH_serving.json; do
    if [[ -f "$f.prev" ]]; then
      echo "== smoke: advisory perf diff $f.prev -> $f =="
      python benchmarks/report.py --compare "$f.prev" "$f" || true
    fi
  done
  exit 0
fi

echo "== tier-1: pytest =="
python -m pytest -x -q

# tiny tables; gates cache counters, fused-batching + partial-fusion
# counters, answer identity, warm speedup, and zero same-bucket recompiles.
echo "== smoke: serving benchmark (tiny, incl. fusion counters) =="
python benchmarks/serving_queries.py --tiny
