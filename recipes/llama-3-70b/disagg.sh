#!/usr/bin/env bash
# llama-3-70b TP=8 disaggregated prefill/decode (BASELINE config 3).
# Ref: recipes/llama-3-70b/vllm/disagg-multi-node/deploy.yaml — here the
# same topology as launchable processes: a tp-sharded prefill pool and a
# tp-sharded decode pool on separate hosts, KV pulled per shard over the
# transfer plane, OpenAI frontend in front.
#
# Production (per host; HUB set to a shared hub address):
#   HUB=host:port MODEL_PATH=/ckpt/llama-3-70b ROLE=prefill ./disagg.sh
#   HUB=host:port MODEL_PATH=/ckpt/llama-3-70b ROLE=decode  ./disagg.sh
#   HUB=host:port ROLE=frontend ./disagg.sh
# Multi-host workers (one identity spanning hosts) add COORDINATOR,
# NUM_PROCESSES, PROCESS_ID (parallel/spmd.py leader/follower replay).
#
# SMOKE=1: the SAME topology at CI scale on a virtual CPU mesh — tiny
# spec, tp=2, all roles in one script run, serving a real completion.
# Exercised by tests/test_recipes_launch.py.
set -euo pipefail
cd "$(dirname "$0")/../.."

TP="${TP:-8}"
BURST="${BURST:-24}"
PAGE="${PAGE:-32}"
NUM_PAGES="${NUM_PAGES:-4096}"
SLOTS="${SLOTS:-64}"
MODEL_ARGS=(--model-path "${MODEL_PATH:-/ckpt/llama-3-70b}")

PRECOMPILE="${PRECOMPILE:-1}"
if [ "${SMOKE:-0}" = "1" ]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="--xla_force_host_platform_device_count=2"
  TP=2 PAGE=4 NUM_PAGES=64 SLOTS=2 BURST=4
  MODEL_ARGS=(--model tiny-test)
  PRECOMPILE=0  # CI smoke: skip the shape warmup
fi
# persistent XLA compile cache: worker restarts replay compiled serving
# programs from disk (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)

COMMON=(--tp "$TP" --page-size "$PAGE" --num-pages "$NUM_PAGES"
        --max-decode-slots "$SLOTS" --decode-steps-per-dispatch "$BURST"
        "${MODEL_ARGS[@]}"
        --model-name "${MODEL:-llama-3-70b}")
# serving default: compile every shape at startup (PRECOMPILE=0 skips)
[ "$PRECOMPILE" = "1" ] && COMMON+=(--precompile)
# DYN_KV_DTYPE=fp8: quantized KV cache — BOTH pools must match (the
# transfer plane carries packed fp8 payloads); default bf16
# SPEC_MODE=ngram: prompt-lookup speculative decoding (decode pool)
[ -n "${SPEC_MODE:-}" ] && COMMON+=(--spec "$SPEC_MODE")
MH=()
[ -n "${COORDINATOR:-}" ] && MH=(--coordinator-address "$COORDINATOR"
  --num-processes "${NUM_PROCESSES:-2}" --process-id "${PROCESS_ID:-0}")

case "${ROLE:-all}" in
  prefill)
    exec python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      "${MH[@]}" --mode prefill ;;
  decode)
    exec python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      "${MH[@]}" --mode decode \
      --max-local-prefill-length "${MAX_LOCAL_PREFILL:-128}" ;;
  frontend)
    exec python -m dynamo_tpu.frontend --hub "$HUB" --host 0.0.0.0 \
      --port "${PORT:-8000}" ;;
  all)  # single-host bringup / SMOKE: every role in this process tree
    HUBLOG=$(mktemp)
    python -m dynamo_tpu.runtime.hub_server --port 0 > "$HUBLOG" &
    trap 'kill $(jobs -p) 2>/dev/null' EXIT
    until grep -q DYNAMO_HUB "$HUBLOG" 2>/dev/null; do sleep 0.2; done
    HUB=$(grep -m1 DYNAMO_HUB "$HUBLOG" | cut -d= -f2)
    echo "hub: $HUB"
    python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode prefill &
    python -m dynamo_tpu.engine.worker --hub "$HUB" "${COMMON[@]}" \
      --mode decode --max-local-prefill-length "${MAX_LOCAL_PREFILL:-16}" &
    exec python -m dynamo_tpu.frontend --hub "$HUB" --host 127.0.0.1 \
      --port "${PORT:-8000}" ;;
  *) echo "unknown ROLE=${ROLE}"; exit 2 ;;
esac
