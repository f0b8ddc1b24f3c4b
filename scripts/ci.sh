#!/usr/bin/env bash
# Tier-1 CI gate: run the full suite with the src layout on PYTHONPATH.
#
# Policy: the suite must COLLECT with zero errors and report zero
# failures on the pinned toolchain (requirements-ci.txt, hypothesis
# included).
#
# Failure handling is exit-code-first: `set -e` aborts on any non-pytest
# failure between the suite and the smoke (mktemp, the smoke invocation
# itself, ...), and pytest's own exit status is captured explicitly from
# its pipeline. The collection-error grep is only a secondary guard for
# pytest versions that exit 0 despite collection problems; it matches
# both the singular and plural spellings ("error during collection",
# "errors while collecting", "N errors").
#
# Perf smoke (ROADMAP): with CI_PERF_SMOKE=1 (or --perf-smoke), a
# quick-mode run of benchmarks/throughput_latency.py gates on
#   * packed admission >= CI_SMOKE_TOLERANCE * serial throughput,
#   * zero-copy sharing reserving strictly fewer blocks than the copy
#     path on an overlapping-chunk workload,
#   * reservation-aware preemption on a pool-starved workload:
#     preemptions > 0, every preempted request reaches DONE (zero
#     FAILED), final logits bit-identical to an unpressured run, and a
#     strictly lower max head-stall iteration count than preemption-off
#     (count-based, immune to runner timing noise),
#   * unified eviction policy: the reuse-aware (GDSF) policy takes
#     strictly fewer tier misses than LRU on the skewed chunk workload
#     (fully deterministic, count-based),
#   * layer-granular streamed tier loads: layerwise preloading hides a
#     nonzero number of layer loads behind window compute, blocks on
#     strictly fewer layer awaits than eager whole-variant loading, and
#     measures strictly less exposed load time at real await points,
#   * tensor-parallel sharded serving: a subprocess with 4 forced host
#     devices runs the same workload unsharded and head-sharded —
#     output tokens identical, traced decode logits bit-identical, and
#     per-device KV bytes + attention FLOPs strictly lower (count-based,
#     immune to runner timing noise),
#   * quantized chunk-cache tiers: int8 cpu/ssd tiers take strictly
#     fewer deep (SSD) tier misses than fp32 at an equal byte budget
#     (count-based), AND the quantized lane's ROUGE-L score stays
#     within eps of the fp32 lane at an exactly matched recompute
#     ratio with the dequant read path exercised,
#   * online serving front end (benchmarks/serve_bench.py): >= 24
#     multi-turn mixed-tenant requests over real HTTP with streamed
#     tokens bit-identical to an offline Engine.run replay of the
#     same trace, one mid-decode HTTP cancel delivering a strict
#     prefix with the KV pool settled (zero reserved blocks), zero
#     FAILED states, and per-tenant TTFT/queue-wait p99 rollups,
#   * quality-vs-recompute frontier on a reordered-context workload
#     (quality_vs_recompute.frontier_compare): the blend strategy
#     (CacheBlend fusion — top KV-deviation tokens anywhere in the
#     chunk) must reach ROUGE-L within eps of the cachecraft anchor
#     point at a STRICTLY lower recompute-token count (count-based),
# and writes results/fig22_ci_smoke.json for the CI artifact upload
# (plus the preemption trajectory in results/BENCH_preemption.json,
# the sharded trajectory in results/BENCH_sharded.json, the quant
# trajectory in results/BENCH_quant.json, the serve trajectory in
# results/BENCH_serve.json, and the frontier trajectory in
# results/BENCH_frontier.json).
# --smoke-only skips the pytest suite for fast local iteration on the
# perf gates.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

perf_smoke="${CI_PERF_SMOKE:-0}"
smoke_only=0
while [[ $# -gt 0 ]]; do
    case "$1" in
        --perf-smoke) perf_smoke=1; shift ;;
        --smoke-only) perf_smoke=1; smoke_only=1; shift ;;
        *) break ;;
    esac
done

status=0
if [[ "$smoke_only" == "0" ]]; then
    log="$(mktemp)"
    python -m pytest -q -p no:cacheprovider "$@" 2>&1 | tee "$log" \
        || status=$?

    # exit-code-first; the greps are a secondary guard only. Cover both
    # the "error during collection" and "errors while collecting"
    # spellings anywhere, and the "N error(s)" short-summary form on the
    # log tail (a passing test may legitimately log "ERROR" lines, so
    # the summary pattern must not scan the whole log).
    if [[ "$status" == "0" ]]; then
        if grep -qiE "error(s)? (during|while) collect(ion|ing)" "$log" \
            || tail -n 3 "$log" | grep -qE "[0-9]+ error(s)?(,| in )"; then
            echo "CI: collection errors detected despite exit 0 -> FAIL"
            status=1
        fi
    fi

    # `|| true`: an INTERNALERROR/usage-error run emits no summary line
    # and must not let set -e kill the script before cleanup
    summary=$(grep -E "[0-9]+ (passed|failed|skipped|error)" "$log" \
        | tail -1 || true)
    echo "CI summary: ${summary:-no summary line found}"
    echo "CI exit status: $status"
    rm -f "$log"
fi

if [[ "$status" == "0" && "$perf_smoke" == "1" ]]; then
    echo "CI: perf smoke (admission throughput" \
         "+ copy-vs-zerocopy shared-block gate + preemption gate" \
         "+ eviction tier-miss gate + layerwise-preload gate" \
         "+ sharded bit-equality/FLOPs gate" \
         "+ quantized-tier capacity/quality gate" \
         "+ online-serve HTTP streaming/cancel gate" \
         "+ blend-vs-cachecraft recompute-frontier gate)"
    python -m benchmarks.throughput_latency --ci-smoke || status=$?
    echo "CI perf smoke exit status: $status"
fi

exit "$status"
