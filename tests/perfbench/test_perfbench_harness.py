"""The whole command, rehearsed on the CPU at a toy size, on a benchmark
that lives in a temporary directory: a configuration, a traffic mix and a
per-layer metric with its reader are ADDED AS FILES there, and the harness
finds and runs them with no edit to any file of ``perfbench/``. The
rehearsal path exists for these tests only and prints no device metric; the
real command refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(REPO, "perfbench", "run.py")

PICO = {
    "name": "pico", "source": "none: a toy for the tests",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 272,
    "num_hidden_layers": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "reference": "dense_gqa",
    "engine": {
        "page_size": 16, "num_pages": 64, "max_pages_per_seq": 16,
        "max_decode_slots": 4, "prefill_buckets": [128],
        "prefill_pack_size": 2, "max_prefill_chunk_tokens": 128,
        "decode_steps_per_dispatch": 2, "decode_steps_admit_pending": 2,
        "pipeline_decode": True, "kv_dtype": "bf16", "guided_mode": "off",
    },
    "correct": {
        "samples": 2, "min_tokens": 36, "max_tokens": 100, "decode_steps": 2,
        "padded_tokens": 128,
        "limits": {"prefill_rel_rms": 0.03, "decode_rel_rms": 0.03,
                   "packed_prefill_rel_rms": 0.03, "served_token_gap": 0.1},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"], "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["fused_decode_attention"],
    },
}
PICO_OPEN = {
    "name": "pico-open", "loop": "open", "rate_rps": 3.0,
    "prompt_tokens": {"dist": "lognormal", "median": 60, "sigma": 0.4,
                      "min": 36, "max": 120},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 10},
    "max_total_tokens": 200, "lead_in_s": 1.0, "tail_s": 2.0,
    "temperature": 0.0,
}
PICO_CLOSED = {
    "name": "pico-closed", "loop": "closed", "clients": 3,
    "pool_requests": 300,
    "prompt_tokens": {"dist": "fixed", "value": 50},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 10},
    "max_total_tokens": 200, "lead_in_s": 1.0, "temperature": 0.0,
}
NEW_READER = '''
"""A reader a later PR brings: found by the directory scan."""


def requests_ok(run, cell):
    return sum(1 for r in run["records"] if r["windowed"] and r["ok"])


def nothing_to_read(run, cell):
    return None
'''


def _entry(name, **kw):
    with open(os.path.join(REPO, "perfbench", "metrics", name + ".json")) as f:
        m = json.load(f)
    e = {k: m[k] for k in ("name", "unit", "better", "source")}
    if m["kind"] == "end_to_end":
        e["bound"] = 0.1
    else:
        e.update(layer=m["layer"], moves=m["moves"])
    e.update(kw)
    return e


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("toybench")
    bench = root / "bench"
    for d in ("configs", "traffic", "metrics", "readers"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "pico.json").write_text(json.dumps(PICO))
    (bench / "traffic" / "pico-open.json").write_text(json.dumps(PICO_OPEN))
    (bench / "traffic" / "pico-closed.json").write_text(json.dumps(PICO_CLOSED))
    (bench / "readers" / "pico.py").write_text(NEW_READER)
    for name in ("ttft_p50_ms", "tpot_p50_ms", "out_tok_s", "setup_s",
                 "engine.compiles_in_window", "loadgen.late_p99_ms",
                 "frontend.ttft_overhead_p50_ms",
                 "model.decode_step_ms", "device.idle_share"):
        shutil.copy(
            os.path.join(REPO, "perfbench", "metrics", name + ".json"),
            bench / "metrics",
        )
    for name, reader in (("pico.requests_ok", "pico:requests_ok"),
                         ("pico.absent", "pico:nothing_to_read")):
        (bench / "metrics" / f"{name}.json").write_text(json.dumps({
            "name": name, "unit": "requests", "better": "higher",
            "kind": "per_layer", "layer": "load generator",
            "source": "program_counter", "moves": "tpot_p50_ms",
            "reader": reader,
        }))
    both = ["pico.open", "pico.closed"]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["bench"],
        "run_seconds": 3,
        "configs": [{"name": "pico", "source": "none", "reduced": [],
                     "file": "bench/configs/pico.json", "why": "toy"}],
        "workloads": [
            {"name": "pico.open", "config": "pico", "traffic": "pico-open",
             "chips": 1, "why": "toy"},
            {"name": "pico.closed", "config": "pico", "traffic": "pico-closed",
             "chips": 1, "why": "toy"},
        ],
        "end_to_end": [
            _entry("ttft_p50_ms", workloads=["pico.open"]),
            _entry("tpot_p50_ms"),
            _entry("out_tok_s", workloads=["pico.closed"]),
            _entry("setup_s"),
        ],
        "per_layer": [
            _entry("engine.compiles_in_window", workloads=both),
            _entry("loadgen.late_p99_ms", workloads=["pico.open"]),
            _entry("frontend.ttft_overhead_p50_ms", workloads=["pico.open"]),
            _entry("model.decode_step_ms", workloads=both),
            _entry("device.idle_share", workloads=both),
            {"name": "pico.requests_ok", "unit": "requests",
             "better": "higher", "source": "program_counter",
             "layer": "load generator", "moves": "tpot_p50_ms"},
            {"name": "pico.absent", "unit": "requests", "better": "higher",
             "source": "program_counter", "layer": "load generator",
             "moves": "tpot_p50_ms"},
        ],
    }))
    return str(root)


def _run(root, workload, trace, seed, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as the chip is one device
    proc = subprocess.run(
        [sys.executable, RUN, "--root", root, "--workload", workload,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace), *extra],
        env=env, capture_output=True, text=True, timeout=300,
    )
    return proc


@pytest.fixture(scope="module")
def lines(bench_root):
    """The three rehearsed runs, each once: (open, e2e), (open, traced),
    (closed, e2e)."""
    out = {}
    for key, (workload, trace, seed) in {
        "open0": ("pico.open", 0, 7), "open1": ("pico.open", 1, 2**31 + 3),
        "closed0": ("pico.closed", 0, 8),
    }.items():
        proc = _run(bench_root, workload, trace, seed, "--rehearse-cpu")
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        out[key] = (json.loads(proc.stdout.strip().splitlines()[-1]),
                    proc.stdout)
    return out


def test_the_result_line_has_the_contract_s_keys(lines):
    line, _ = lines["open0"]
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 9  # round(3 req/s x 3 s), whatever the seed
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"


def test_trace_0_reports_the_cell_s_end_to_end_metrics(lines):
    open0, closed0 = lines["open0"][0], lines["closed0"][0]
    assert set(open0["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert set(closed0["metrics"]) == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    for m in list(open0["metrics"].values()) + list(closed0["metrics"].values()):
        assert m["value"] > 0 and m["unit"]
    assert closed0["attempted"] > 0 and closed0["correct"] is True


def test_added_files_are_found_and_run_with_no_edit_to_perfbench(lines):
    line, _ = lines["open1"]
    assert line["metrics"]["pico.requests_ok"] == {
        "value": 9.0, "unit": "requests"}
    assert line["metrics"]["engine.compiles_in_window"]["value"] == 0.0
    assert "loadgen.late_p99_ms" in line["metrics"]
    # the tap on the engine's streams saw the requests the client sent
    assert 0 < line["metrics"]["frontend.ttft_overhead_p50_ms"]["value"] < 500


def test_a_reader_that_finds_nothing_leaves_its_metric_out(lines):
    assert "pico.absent" not in lines["open1"][0]["metrics"]


def test_a_rehearsal_never_prints_a_device_metric(lines):
    line, _ = lines["open1"]
    assert "model.decode_step_ms" not in line["metrics"]
    assert "device.idle_share" not in line["metrics"]
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_every_number_compared_is_printed_beside_its_limit(lines):
    _, text = lines["open0"]
    for what in ("prefill_rel_rms", "decode_rel_rms",
                 "packed_prefill_rel_rms", "served_token_gap",
                 "requests failed 0",
                 "compiles in the window 0", "fallback series {}"):
        assert what in text
    assert text.count("(limit") >= 6


def test_the_real_command_refuses_to_run_without_a_tpu(bench_root):
    proc = _run(bench_root, "pico.open", 0, 1)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(
        ln.startswith("{") for ln in proc.stdout.strip().splitlines()
    )


def test_an_unknown_workload_is_an_error(bench_root):
    proc = _run(bench_root, "pico.nope", 0, 1, "--rehearse-cpu")
    assert proc.returncode != 0 and "pico.nope" in proc.stderr


def test_the_memory_peak_is_read_when_the_window_closes(lines):
    line, text = lines["open0"]
    assert "bytes when the window closed" in text
    assert "memory_peak_bytes" in line["device"]
