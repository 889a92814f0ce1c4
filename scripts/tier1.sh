#!/usr/bin/env bash
# Tier-1 test runner: the canonical 3-chunk split.
#
# The single-process tier-1 run (`pytest tests/ -q -m 'not slow'`) takes
# ~1300s on a 2-core box and times out the 870s verify budget — every PR
# since 7 hand-rolled the same split. This script IS the split:
#
#   chunk 1  models + kernels (the XLA-compile-heavy leg)
#   chunk 2  engine + e2e service / disagg / multimaster / tiering drills
#   chunk 3  everything else (scheduler, coordination, devtools, common)
#
# Membership is pattern-based with chunk 3 as the remainder, so new test
# files are always covered; the script fails loudly if the chunks do not
# partition tests/test_*.py. Each chunk runs under its own `timeout -k
# 10 870` with the same flags as the ROADMAP's tier-1 verify line, and
# passed-test accounting is aggregated across chunks (dots counting, the
# same scheme the verify line uses).
#
# Usage: scripts/tier1.sh [1|2|3|all]        (default: all, sequential)
#   env XLLM_TIER1_TIMEOUT=<s>               per-chunk timeout (870)
set -u
cd "$(dirname "$0")/.."

WHICH="${1:-all}"
TIMEOUT="${XLLM_TIER1_TIMEOUT:-870}"

CHUNK1_PATTERNS=(
    test_models test_models_extra test_gemma test_mixtral test_qwen2_vl
    test_hf_parity test_loader test_quant test_mrope test_speculative
    test_sarathi test_seq_parallel test_pipeline test_tp_serving
    test_moe_pd test_checkpoint_serving test_pallas_attention
    test_cp_paged_attention test_chip_compile
    test_bringup
)
CHUNK2_PATTERNS=(
    test_engine test_e2e_epd test_e2e_ha test_e2e_pd_disagg
    test_e2e_real_engine test_e2e_routing test_e2e_service
    test_multimaster test_multiprocess_cluster test_multihost test_soak
    test_chaos_failover test_kv_tiering test_fleet_observability
    test_hybrid_scheduling test_mixed_decode_chunk
    test_chunked_multimodal test_dp_replicas test_northstar_topology
    test_pallas_engine_routing test_kv_pool_in_place
)

in_list() {
    local needle="$1"; shift
    local x
    for x in "$@"; do [ "$x" = "$needle" ] && return 0; done
    return 1
}

chunk1=(); chunk2=(); chunk3=()
for f in tests/test_*.py; do
    base="$(basename "$f" .py)"
    if in_list "$base" "${CHUNK1_PATTERNS[@]}"; then
        chunk1+=("$f")
    elif in_list "$base" "${CHUNK2_PATTERNS[@]}"; then
        chunk2+=("$f")
    else
        chunk3+=("$f")
    fi
done

# Pattern-drift guard: every explicit CHUNK1/CHUNK2 pattern must match a
# live test file (a renamed/deleted file would silently shift its slot
# into the remainder chunk — fail loudly instead). Chunk 3 being the
# remainder of the same glob, the partition itself holds by construction.
for base in "${CHUNK1_PATTERNS[@]}" "${CHUNK2_PATTERNS[@]}"; do
    if [ ! -f "tests/$base.py" ]; then
        echo "tier1.sh: chunk pattern '$base' matches no tests/$base.py" \
             "(stale pattern — update the chunk lists)" >&2
        exit 2
    fi
done

run_chunk() {
    local n="$1"; shift
    local log="/tmp/_t1_chunk$n.log"
    rm -f "$log"
    echo "=== tier-1 chunk $n ($# files, timeout ${TIMEOUT}s) ==="
    set -o pipefail
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python -m pytest "$@" -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly 2>&1 | tee "$log"
    local rc=${PIPESTATUS[0]}
    local dots
    dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)
    echo "chunk $n: DOTS_PASSED=$dots rc=$rc"
    TOTAL_DOTS=$((TOTAL_DOTS + dots))
    [ "$rc" -ne 0 ] && FAILED_CHUNKS+=("$n(rc=$rc)")
    return 0
}

# Pure-python fallback drill: the wire/ownership/native differential
# suites run a second time with XLLM_NATIVE=0 forced, proving the
# mandatory fallbacks carry the same behavior a no-toolchain box gets.
# Rides after chunk 3; its dots are not added to TOTAL_DOTS (they would
# double-count tests the normal chunks already ran).
PURE_FILES=(tests/test_native_hotcore.py tests/test_dispatch_wire.py
            tests/test_multimaster.py)
run_pure_drill() {
    local log="/tmp/_t1_pure.log"
    rm -f "$log"
    echo "=== tier-1 pure-fallback drill (XLLM_NATIVE=0," \
         "${#PURE_FILES[@]} files) ==="
    set -o pipefail
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu XLLM_NATIVE=0 \
        python -m pytest "${PURE_FILES[@]}" -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly 2>&1 | tee "$log"
    local rc=${PIPESTATUS[0]}
    echo "pure drill: rc=$rc"
    [ "$rc" -ne 0 ] && FAILED_CHUNKS+=("pure(rc=$rc)")
    return 0
}

TOTAL_DOTS=0
FAILED_CHUNKS=()
case "$WHICH" in
    1) run_chunk 1 "${chunk1[@]}" ;;
    2) run_chunk 2 "${chunk2[@]}" ;;
    3) run_chunk 3 "${chunk3[@]}"; run_pure_drill ;;
    all)
        run_chunk 1 "${chunk1[@]}"
        run_chunk 2 "${chunk2[@]}"
        run_chunk 3 "${chunk3[@]}"
        run_pure_drill
        ;;
    *) echo "usage: scripts/tier1.sh [1|2|3|all]" >&2; exit 2 ;;
esac

echo
echo "tier1.sh: TOTAL DOTS_PASSED=$TOTAL_DOTS"
if [ "${#FAILED_CHUNKS[@]}" -gt 0 ]; then
    echo "tier1.sh: non-zero chunk exits: ${FAILED_CHUNKS[*]} (inspect" \
         "/tmp/_t1_chunk*.log — the known container-limitation failures" \
         "exit 1 too)"
    exit 1
fi
