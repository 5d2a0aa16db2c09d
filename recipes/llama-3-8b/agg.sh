#!/usr/bin/env bash
# Llama-3-8B aggregated single worker (BASELINE config 1).
# One process: in-memory hub + JAX engine worker + OpenAI HTTP frontend.
#   MODEL_PATH=/ckpt ./agg.sh     # real weights (else random-weight preset)
set -euo pipefail
cd "$(dirname "$0")/../.."
# Persistent XLA compile cache + startup shape warmup (serving default):
# restarts replay compiled programs from disk, and no request ever eats
# a compile. The cache lives where JAX_COMPILATION_CACHE_DIR says, else
# in <checkout>/.jax_cache; PRECOMPILE=0 skips the warmup.
ARGS=(run --in http --out engine --port "${PORT:-8000}")
[ "${PRECOMPILE:-1}" = "1" ] && ARGS+=(--precompile)
# DYN_KV_DTYPE=fp8: quantized KV cache (throughput mode — ~half the
# decode HBM read/step; default bf16 is bit-identical serving)
# SPEC_MODE=ngram: prompt-lookup speculative decoding (>=1.5x per-stream
# tok/s on repetitive/agentic prompts; greedy output unchanged)
[ -n "${SPEC_MODE:-}" ] && ARGS+=(--spec "$SPEC_MODE")
# GUIDED_MODE=off disables guided decoding (response_format / forced
# tool_choice grammar masks; default auto — also via DYN_GUIDED_MODE)
[ -n "${GUIDED_MODE:-}" ] && ARGS+=(--guided "$GUIDED_MODE")
if [ -n "${MODEL_PATH:-}" ]; then
  ARGS+=(--model-path "$MODEL_PATH")
else
  ARGS+=(--model "${MODEL:-llama-3-8b}")
fi
exec python -m dynamo_tpu.cli "${ARGS[@]}"
