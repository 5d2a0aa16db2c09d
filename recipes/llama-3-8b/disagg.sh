#!/usr/bin/env bash
# Llama-3-8B disaggregated: 1 prefill + 1 decode worker, KV-aware routing
# (BASELINE config 2; ref docs/architecture/disagg_serving.md).
# Spawns: hub, prefill worker, decode worker, OpenAI frontend.
set -euo pipefail
cd "$(dirname "$0")/../.."
PORT="${PORT:-8000}"
MODEL_ARGS=(--model "${MODEL:-llama-3-8b}")
[ -n "${MODEL_PATH:-}" ] && MODEL_ARGS=(--model-path "$MODEL_PATH")
# compile cache + shape warmup (serving default; see README): the cache
# lives where JAX_COMPILATION_CACHE_DIR says, else in
# <checkout>/.jax_cache; PRECOMPILE=0 skips the warmup
[ "${PRECOMPILE:-1}" = "1" ] && MODEL_ARGS+=(--precompile)
# DYN_KV_DTYPE=fp8: quantized KV cache — BOTH pools must match (packed
# fp8 payloads cross the transfer plane); default bf16
# SPEC_MODE=ngram: prompt-lookup speculative decoding on the decode pool
[ -n "${SPEC_MODE:-}" ] && MODEL_ARGS+=(--spec "$SPEC_MODE")
# GUIDED_MODE=off disables guided decoding (guided requests always
# prefill locally on the decode pool, so disagg composes cleanly)
[ -n "${GUIDED_MODE:-}" ] && MODEL_ARGS+=(--guided "$GUIDED_MODE")

python -m dynamo_tpu.runtime.hub_server --port 0 > /tmp/dyn-hub.out &
HUB_PID=$!
trap 'kill $(jobs -p) 2>/dev/null' EXIT
until grep -q DYNAMO_HUB /tmp/dyn-hub.out 2>/dev/null; do sleep 0.2; done
HUB=$(grep -m1 DYNAMO_HUB /tmp/dyn-hub.out | cut -d= -f2)
echo "hub: $HUB"

python -m dynamo_tpu.engine.worker --hub "$HUB" "${MODEL_ARGS[@]}" \
  --mode prefill &
python -m dynamo_tpu.engine.worker --hub "$HUB" "${MODEL_ARGS[@]}" \
  --mode decode --max-local-prefill-length "${MAX_LOCAL_PREFILL:-128}" &
exec python -m dynamo_tpu.frontend --hub "$HUB" --host 0.0.0.0 --port "$PORT"
