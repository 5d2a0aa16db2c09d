"""chip_smoke.py rehearsed on the CPU: its serving phase at a tiny spec,
its refusal to run without a TPU, and its tp=4 build on four virtual
devices. The script has no CPU option — the tests call its functions.
"""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

import chip_smoke
from dynamo_tpu.engine.config import EngineConfig, ModelSpec

pytestmark = pytest.mark.integration


def test_serving_phase_on_cpu_at_tiny_spec(monkeypatch):
    """The whole stack in one process — hub, launch_engine_worker with
    precompile, HTTP frontend — serving the smoke's six requests: every
    count exact, every SSE chunk an engine delta, two prefill buckets
    hit, a page boundary crossed, and NO compile after precompile (the
    burst feed path's glue programs are part of the walk)."""
    spec = dataclasses.replace(ModelSpec.tiny(), name="tiny-smoke")
    cfg = EngineConfig(
        pipeline_decode=True, decode_steps_per_dispatch=4,
        decode_steps_admit_pending=0, prefill_buckets=(128, 512),
        prefill_pack_size=4, guided_mode="off",
    )
    phase = asyncio.run(chip_smoke.serve_phase(spec, cfg))
    assert [r["name"] for r in phase["results"]] == [
        r[0] for r in chip_smoke.REQUESTS
    ]
    assert {r["bucket"] for r in phase["results"]} == {128, 512}
    assert not any("error" in r for r in phase["precompile"].values())
    assert {"decode[8x1]", "decode[8x4]", "burst_feed"} <= set(
        phase["precompile"]
    )
    for prompt, ids in phase["streams"].items():
        assert len(ids) >= 32 and len(prompt) >= 29
    # what only holds on the chip is a separate check — and fails here,
    # where the XLA fall-through was (rightly) taken and counted
    assert phase["fallbacks"]
    with pytest.raises(chip_smoke.SmokeFailure, match="fallbacks"):
        chip_smoke.check_device_path(phase, guided=False)
    # the engine object outlives its stack for the numeric checks
    logits = chip_smoke.engine_prefill_logits(
        phase["engine"], list(min(phase["streams"], key=len))
    )
    assert logits.shape == (spec.vocab_size,)
    # the four-chip phase's stream check: equal streams agree; one that
    # parts early passes only where tp=1's logits tie to a bfloat16's last
    # place (0.03125 between 4 and 8)
    prompt = min(phase["streams"], key=len)
    one = phase["streams"][prompt]
    engine = phase["engine"]
    assert chip_smoke.streams_agree(engine, prompt, one, one) == len(one)
    tied = np.full_like(logits, -1.0)
    tied[[5, 6, 7, 8]] = 4.375, 4.375, 4.34375, 4.3125
    monkeypatch.setattr(chip_smoke, "engine_prefill_logits", lambda e, t: tied)
    for token in (6, 7):
        assert chip_smoke.streams_agree(
            engine, prompt, [5] + one[1:], [token] + one[1:]) == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="not at a tie"):
        chip_smoke.streams_agree(engine, prompt, [5] + one[1:], [8] + one[1:])


def test_latent_prefill_reading_on_cpu_at_toy_widths(monkeypatch, capsys):
    """The smoke's fourth reading, the latent prefill kernel against its
    XLA twin, rehearsed at toy widths with the kernel interpreted: the
    four cases run, agree, and print both times a call."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    monkeypatch.setattr(chip_smoke, "LATENT", dict(
        H=4, dn=16, dr=8, dv=12, dc=24, lanes=40, page=4, pages_per_seq=16,
        rows=16))
    monkeypatch.setattr(chip_smoke, "LATENT_CASES", (
        ((0, 16),), ((16, 16),), ((48, 16),), ((0, 16), (0, 9))))
    chip_smoke.latent_prefill_kernel_vs_twin()
    out = capsys.readouterr().out
    assert out.count("kernel vs XLA walk") == 5  # a line a member
    assert out.count("ms a call, XLA walk") == 4


def test_main_fails_without_a_tpu(capsys):
    """No accelerator: non-zero exit and no result line — never a CPU
    run under the chip's name."""
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_tp4_build_does_not_put_the_model_on_device_0():
    """The four-chip phase's build, on four virtual CPU devices: weights
    and pools are born sharded, so device 0 holds its shard (replicated
    leaves whole) and never every parameter."""
    spec = dataclasses.replace(ModelSpec.dryrun(), name="tp4-smoke")
    cfg = dataclasses.replace(
        chip_smoke.four_chip_config(4), num_pages=64, max_pages_per_seq=8
    )

    async def build():
        stack = await chip_smoke.start_stack(spec, cfg, precompile=False)
        await chip_smoke.stop_stack(stack)
        return stack.engine

    engine = asyncio.run(build())
    dev0 = jax.devices()[0]
    assert engine.mesh.shape["tp"] == 4
    want, whole = chip_smoke.shard_report(engine, dev0)
    assert chip_smoke.resident_bytes(engine, dev0) == want
    assert want < 0.5 * whole
    wq = engine.params["layers"][0]["wq"]
    assert {s.data.shape for s in wq.addressable_shards} == {
        (spec.hidden_size, spec.num_heads * spec.head_dim // 4)
    }
