#!/usr/bin/env python3
"""``jax.named_scope`` is metadata: the proof, for any two checkouts.

    python3 tools/hlo_metadata_proof.py --parent <checkout> [--change <checkout>]

For each configuration of ``perfbench/configs`` that stands for a family
(dense GQA, MiMo, JoyAI, Solar, Falcon-H1) it compiles, in a process a
checkout, the single-prompt and the packed prefill program of the largest
bucket and the longest decode burst at the configuration's real widths for a DESCRIBED v5e chip (no chip
needed; ``jax.default_backend`` is told "tpu" so the programs take the
chip's paths: the Mosaic kernels, the padded pools, megablox). From the
optimised HLO text it strips every ``metadata={...}`` and the module's
source-location tables, and compares the two sides byte for byte, each
side compiled from a copy at one and the same path. A scope that changed a
program, reordered an operation or renamed a Mosaic call (its instruction
name is outside the metadata) shows as a difference. Prints one line a
program and exits 1 on any.

``--one <checkout> <out dir>`` is the per-checkout half: it writes
``<config>.<program>.hlo`` (stripped) there.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

CONFIGS = ("mistral-7b-v0.3", "mimo-v2.5", "joyai-llm-flash",
           "solar-open2-250b", "falcon-h1-34b")
# an instruction's metadata, and the module's tables of the files,
# functions, lines and stack frames that the metadata points into
_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_TABLES = re.compile(
    r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n")


def strip(text: str) -> str:
    return _TABLES.sub("", _METADATA.sub("", text))


def one(tree: str, out: str, configs=CONFIGS) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["DYNAMO_PALLAS"] = "1"
    sys.path[:0] = [tree, os.path.join(tree, "perfbench")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    # a Mosaic call's serialized body names the file and line of each of
    # its operations, with the frames that called it: keep the kernel's own
    # frame alone, so a line that moved in a caller is not a difference
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.default_backend = lambda: "tpu"  # the chip's paths, traced here
    from lib import stack as stk

    from dynamo_tpu.models.family import get_family

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree_):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree_)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    os.makedirs(out, exist_ok=True)
    for name in configs:
        with open(os.path.join(tree, "perfbench", "configs", name + ".json")) as f:
            config = json.load(f)
        spec = stk.model_spec(config)
        cfg = stk.engine_config(config, 0, profile=False)
        fam = get_family(spec)
        params = on_chip(jax.eval_shape(
            lambda k: fam.init_params(spec, k), jax.random.PRNGKey(0)))
        rows = ({"state_rows": cfg.max_decode_slots}
                if getattr(fam, "recurrent", False) else {})
        k, v = on_chip(jax.eval_shape(lambda: fam.init_cache(
            spec, cfg.num_pages + 1, cfg.page_size, **rows)))
        i32, f32 = jnp.int32, jnp.float32
        T, B, P = max(cfg.prefill_buckets), cfg.max_decode_slots, cfg.max_pages_per_seq
        n, N = cfg.decode_steps_per_dispatch, cfg.prefill_pack_size
        burst = (arr((B,), bool), arr((B,), f32), arr((B,), i32),
                 arr((B,), f32), arr((B,), jnp.uint32), arr((B,), i32))
        if spec.is_mla:
            m = fam.mla
            programs = {
                "prefill": m.prefill_forward.lower(
                    spec, params, arr((T,), i32), arr((P,), i32),
                    arr((), i32), k, arr((), i32), mesh=None, counts=v),
                "packed": m.prefill_forward_batch.lower(
                    spec, params, arr((N, T), i32), arr((N, P), i32),
                    arr((N,), i32), k, arr((N,), i32), mesh=None, counts=v),
                "decode": m.decode_steps.lower(
                    spec, params, arr((B,), i32), arr((B, P), i32),
                    arr((B,), i32), k, *burst, n_steps=n, n_logprobs=0,
                    mesh=None, counts=v),
            }
        else:
            m = fam.m
            programs = {
                "prefill": m.prefill_forward.lower(
                    spec, params, arr((T,), i32), arr((P,), i32),
                    arr((), i32), k, v, arr((), i32), mesh=None),
                "packed": m.prefill_forward_batch.lower(
                    spec, params, arr((N, T), i32), arr((N, P), i32),
                    arr((N,), i32), k, v, arr((N,), i32), mesh=None),
                "decode": m.decode_steps.lower(
                    spec, params, arr((B,), i32), arr((B, P), i32),
                    arr((B,), i32), k, v, *burst, n_steps=n, n_logprobs=0,
                    mesh=None),
            }
        for prog, lowered in programs.items():
            text = lowered.compile().as_text()
            with open(os.path.join(out, f"{name}.{prog}.hlo"), "w") as f:
                f.write(strip(text))
            print(f"{name}.{prog}: {len(text)} bytes of HLO, "
                  f"{text.count('op_name=')} instructions with an op_name, "
                  f"{text.count('tpu_custom_call')} Mosaic calls", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent")
    ap.add_argument("--change", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--one", nargs=2, metavar=("TREE", "OUT"))
    ap.add_argument("--keep", default=None, help="keep the stripped HLO here")
    ap.add_argument("--configs", default=",".join(CONFIGS),
                    help="the configurations to compile, comma-separated")
    args = ap.parse_args(argv)
    if args.one:
        one(*args.one, configs=args.configs.split(","))
        return 0
    if not args.parent:
        ap.error("--parent <checkout> is required")
    work = args.keep or tempfile.mkdtemp(prefix="hlo_proof_")
    sides = {"parent": args.parent, "change": args.change}
    # each side is compiled from a copy at ONE path: the path of a source
    # file is part of a Mosaic call's serialized body
    at = os.path.join(work, "tree")
    for side, tree in sides.items():
        shutil.rmtree(at, ignore_errors=True)
        os.makedirs(at)
        for sub in ("dynamo_tpu", "perfbench"):
            shutil.copytree(
                os.path.join(tree, sub), os.path.join(at, sub),
                ignore=shutil.ignore_patterns("__pycache__"))
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", at,
             os.path.join(work, side), "--configs", args.configs],
            check=True, cwd=at,
        )
    shutil.rmtree(at, ignore_errors=True)
    differ = 0
    for fn in sorted(os.listdir(os.path.join(work, "parent"))):
        a = open(os.path.join(work, "parent", fn), "rb").read()
        b = open(os.path.join(work, "change", fn), "rb").read()
        same = a == b
        differ += not same
        print(f"{fn}: {'byte-equal' if same else 'DIFFERS'} without metadata "
              f"({len(a)} / {len(b)} bytes, sha256 "
              f"{hashlib.sha256(a).hexdigest()[:12]} / "
              f"{hashlib.sha256(b).hexdigest()[:12]})")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
