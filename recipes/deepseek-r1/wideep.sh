#!/usr/bin/env bash
# deepseek-r1 wide-EP disaggregated serving (BASELINE config 5).
# Ref: recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml — a
# tp-heavy prefill pool, an ep-heavy decode pool (MLA latent cache
# replicated, experts sharded over ep), KVBM host offload on decode,
# optional SLA planner scaling both pools.
#
# Production (per pool):
#   HUB=... MODEL_PATH=/ckpt/deepseek-r1 ROLE=decode  ./wideep.sh
#   HUB=... MODEL_PATH=/ckpt/deepseek-r1 ROLE=prefill ./wideep.sh
# SMOKE=1: SAME topology at CI scale — tiny-deepseek, ep=2 decode +
# tp=2 prefill pools on a virtual CPU mesh, one completion served.
# Exercised by tests/test_recipes_launch.py.
set -euo pipefail
cd "$(dirname "$0")/../.."

EP="${EP:-16}"
BURST="${BURST:-24}"
PREFILL_TP="${PREFILL_TP:-16}"
PAGE="${PAGE:-32}"
NUM_PAGES="${NUM_PAGES:-8192}"
SLOTS="${SLOTS:-128}"
KVBM_MB="${KVBM_MB:-65536}"
MODEL_ARGS=(--model-path "${MODEL_PATH:-/ckpt/deepseek-r1}")

PRECOMPILE="${PRECOMPILE:-1}"
if [ "${SMOKE:-0}" = "1" ]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="--xla_force_host_platform_device_count=4"
  EP=2 PREFILL_TP=2 PAGE=4 NUM_PAGES=64 SLOTS=2 KVBM_MB=8 BURST=4
  MODEL_ARGS=(--model tiny-deepseek)
  PRECOMPILE=0  # CI smoke: skip the shape warmup
fi
# persistent XLA compile cache: worker restarts replay compiled serving
# programs from disk (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)

COMMON=("${MODEL_ARGS[@]}" --model-name "${MODEL:-deepseek-r1}"
        --page-size "$PAGE" --num-pages "$NUM_PAGES"
        --max-decode-slots "$SLOTS" --decode-steps-per-dispatch "$BURST")
# serving default: compile every shape at startup (PRECOMPILE=0 skips)
[ "$PRECOMPILE" = "1" ] && COMMON+=(--precompile)
# DYN_KV_DTYPE=fp8: quantized latent cache (per-row scales); default bf16
# SPEC_MODE=ngram: prompt-lookup speculative decoding (decode pool)
[ -n "${SPEC_MODE:-}" ] && COMMON+=(--spec "$SPEC_MODE")

case "${ROLE:-all}" in
  decode)
    exec python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode decode --ep "$EP" --tp "${TP:-1}" \
      --kvbm-host-mb "$KVBM_MB" ;;
  prefill)
    exec python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode prefill --tp "$PREFILL_TP" ;;
  planner)
    exec python -m dynamo_tpu.planner --hub "$HUB" \
      --ttft "${TTFT_SLA:-2.0}" --itl "${ITL_SLA:-0.05}" ;;
  frontend)
    exec python -m dynamo_tpu.frontend --hub "$HUB" --host 0.0.0.0 \
      --port "${PORT:-8000}" ;;
  all)  # single-host bringup / SMOKE
    HUBLOG=$(mktemp)
    python -m dynamo_tpu.runtime.hub_server --port 0 > "$HUBLOG" &
    trap 'kill $(jobs -p) 2>/dev/null' EXIT
    until grep -q DYNAMO_HUB "$HUBLOG" 2>/dev/null; do sleep 0.2; done
    HUB=$(grep -m1 DYNAMO_HUB "$HUBLOG" | cut -d= -f2)
    echo "hub: $HUB"
    python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode prefill --tp "$PREFILL_TP" &
    python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode decode --ep "$EP" --tp "${TP:-1}" --kvbm-host-mb "$KVBM_MB" \
      --max-local-prefill-length "${MAX_LOCAL_PREFILL:-16}" &
    exec python -m dynamo_tpu.frontend --hub "$HUB" --host 127.0.0.1 \
      --port "${PORT:-8000}" ;;
  *) echo "unknown ROLE=${ROLE}"; exit 2 ;;
esac
