"""The readers of the engine's own spans (``perfbench/lib/spans.py``,
``perfbench/readers/spans.py``): inert on a program that has no spans, the
clock fit, the pairing of launches with executions on hand-made traces, the
idle-by-phase attribution, and the CPU rehearsal, which prints the
host-clock span metrics and no device one."""

import json
import os
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from lib import spans  # noqa: E402
from lib import spec as spec_mod  # noqa: E402

sys.path.insert(0, HERE)
import test_perfbench_harness as harness  # noqa: E402

READERS = spec_mod.load_readers([os.path.join(REPO, "perfbench", "readers")])
NEW = sorted(k for k in READERS if k.startswith("spans:"))
PROGRAMS = {"decode": ["decode_steps"], "prefill": ["prefill_forward"]}
CELL = types.SimpleNamespace(config={"trace_names": {"programs": PROGRAMS}})


def test_every_new_metric_names_a_reader_that_exists():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    named = set()
    for e in bench["per_layer"]:
        m = json.load(open(os.path.join(
            REPO, "perfbench", "metrics", e["name"] + ".json")))
        assert m["reader"] in READERS, e["name"]
        if m["reader"].startswith("spans:"):
            named.add(m["reader"])
            assert e["workloads"] == m["workloads"]
    assert named == set(NEW) and len(NEW) == 7


def test_the_new_modules_load_without_jax_or_the_program():
    """``load_readers`` executes every module under ``readers/`` in every
    run: the new ones must cost a ``--trace 0`` run nothing."""
    code = (
        "import sys; sys.path.insert(0, 'perfbench')\n"
        "from lib import spec\n"
        "import importlib.util as u\n"
        "for f in ('perfbench/lib/spans.py', 'perfbench/readers/spans.py'):\n"
        "    s = u.spec_from_file_location('m', f)\n"
        "    m = u.module_from_spec(s); s.loader.exec_module(m)\n"
        "bad = [k for k in sys.modules if k == 'jax' or "
        "k.startswith(('jax.', 'dynamo_tpu'))]\n"
        "assert not bad, bad\n"
    )
    import subprocess

    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


# -- a program without spans ---------------------------------------------


class _RingRecorder:
    """The parent's flight recorder, as a reader sees it: no ``complete``,
    no ``finished``."""


def _parent_run(tmp_path, with_trace: bool) -> dict:
    """A run dict of the parent's shape: an engine without ``flight``, a
    trace without ``engine.*`` annotations."""
    trace_dir = None
    if with_trace:
        d = tmp_path / "trace" / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        shutil.copy(os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb"),
                    d / "vm.xplane.pb")
        trace_dir = str(tmp_path / "trace")
    return {
        "engine": types.SimpleNamespace(config=None), "records": [],
        "t0": 100.0, "seconds": 51.0, "trace_dir": trace_dir,
        "traced": (15.0, 21.0, 50.0) if with_trace else None,
    }


@pytest.mark.parametrize("with_trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("reader", NEW)
def test_a_new_reader_finds_nothing_on_the_parent_s_program(
        tmp_path, capsys, reader, with_trace):
    run = _parent_run(tmp_path, with_trace)
    assert READERS[reader](run, CELL) is None
    # and says nothing: the recorded slice holds device events only
    assert "spans:" not in capsys.readouterr().out


def test_a_recorder_that_rotated_is_not_read():
    flight = types.SimpleNamespace(complete=False, finished=lambda: [1])
    assert spans.timelines(types.SimpleNamespace(flight=flight)) is None
    assert spans.timelines(types.SimpleNamespace(flight=_RingRecorder())) is None
    flight.complete = True
    assert spans.timelines(types.SimpleNamespace(flight=flight)) == [1]


def test_a_reader_raises_nothing(capsys):
    """Whatever it meets: a run dict with nothing it expects."""
    for reader in NEW:
        assert READERS[reader]({"trace_dir": 7}, None) is None
    assert "found nothing it could read" in capsys.readouterr().out


# -- the clock fit -------------------------------------------------------


def test_the_clock_fit_recovers_a_known_offset():
    offset = 8.25e14
    # monotonic samples 0.8 ms apart; the annotation opens 1-3 us after
    # the clock was read, once 400 us after (a pre-empted thread)
    clock = [(m + offset + jit, m) for m, jit in zip(
        (1e9 + 8e5 * i for i in range(41)),
        [1e3, 2e3, 3e3, 1.5e3] * 10 + [4e5])]
    fit = spans.fit_clock(clock)
    assert abs(fit["offset_ns"] - offset - 1.75e3) <= 500
    assert fit["samples"] == 41 and fit["iqr_ns"] <= 2e3
    assert 3.9e5 < fit["residual_ns"] < 4.1e5
    assert spans.fit_clock([]) is None


# -- pairing -------------------------------------------------------------


def _launch(kind, seq, at, **counts):
    return spans.Launch(kind, seq, at, at + 50.0, counts)


def _module(kind, a, b):
    name = {"prefill": "jit_prefill_forward_batch_impl(1)",
            "decode": "jit_decode_steps_impl(2)"}.get(kind, "jit__feed(3)")
    return spans.Module(kind, name, a, b)


def test_pairing_drops_the_executions_ahead_of_the_first_launch():
    """Two executions open the trace that earlier launches queued: one
    running when the first traced launch is made, one begun after it."""
    launches = [
        _launch("decode", 11, 1000), _launch("feed", 12, 1100),
        _launch("prefill", 13, 2000, tokens=300, rows=2),
        _launch("decode", 14, 3000), _launch("prefill", 15, 9000, tokens=90),
    ]
    modules = [
        _module("decode", 100, 1500),  # began before launch 11: dropped
        _module("prefill", 1500, 1900),  # began after it, but is not 11's
        _module("decode", 1900, 3300), _module("other", 3300, 3310),
        _module("prefill", 3310, 3700), _module("decode", 3700, 5000),
    ]
    pairs = spans.pair(launches, modules)
    assert [(ln.seq, m.start) for ln, m in pairs] == [
        (11, 1900), (13, 3310), (14, 3700)]  # 15 had not run yet


def test_pairing_refuses_a_kind_mismatch():
    """No alignment gives every launch a program of its kind: a prefill
    launch would meet a decode program."""
    launches = [_launch("prefill", 1, 1000), _launch("prefill", 2, 1100)]
    modules = [_module("prefill", 1200, 1300), _module("decode", 1300, 1400)]
    assert spans.pair(launches, modules) is None
    # nor one that breaks causality: the program began before its launch
    assert spans.pair([_launch("decode", 1, 1000), _launch("prefill", 2, 5000)],
                      [_module("decode", 1100, 1200),
                       _module("prefill", 1200, 1300)]) is None
    assert spans.pair([], modules) is None and spans.pair(launches, []) is None


# -- idle by phase -------------------------------------------------------


def test_idle_gaps_go_to_the_innermost_phase():
    phases = sorted([
        ("idle", 0, 100), ("process", 100, 400),
        ("process.d2h_sync", 150, 300), ("dispatch.d2h_wait", 150, 300),
        ("eager_readmit", 420, 800), ("packed_prefill", 500, 700),
    ], key=lambda p: (p[1], -p[2]))
    assert spans.innermost(phases) == [
        (0, 100, "idle"), (100, 150, "process"),
        (150, 300, "dispatch.d2h_wait"), (300, 400, "process"),
        (400, 420, None), (420, 500, "eager_readmit"),
        (500, 700, "packed_prefill"), (700, 800, "eager_readmit"),
    ]
    busy = [(50, 120), (350, 410), (600, 850)]
    by = spans.idle_by_phase(busy, (0, 900), phases)
    ns = {k: round(v * 1e9) for k, v in by.items()}
    assert ns == {
        "idle": 50, "process": 30 + 50, "dispatch.d2h_wait": 150,
        "(none)": 10, "eager_readmit": 80, "packed_prefill": 100,
        "(untraced)": 50,
    }
    assert sum(ns.values()) == 900 - (70 + 60 + 250)
    assert spans.away("idle") and spans.away("readmit.d2h_wait")
    assert not spans.away("process") and not spans.away(None)


# -- the readers on a hand-made run --------------------------------------


def _timeline(t0, seq, prompt_tokens, admit, dispatch, token, delta):
    events = [
        {"name": "admit", "t": admit, "t_last": admit, "n": 1},
        {"name": "prefill_dispatch", "t": dispatch, "t_last": dispatch,
         "n": 1, "seq": seq},
        {"name": "first_token", "t": token, "t_last": token, "n": 1},
        {"name": "first_delta", "t": delta, "t_last": delta, "n": 1},
    ]
    return types.SimpleNamespace(
        t0=t0, events=events, attrs={"prompt_tokens": prompt_tokens})


def test_the_chain_metrics_from_timelines_and_a_paired_trace(capsys):
    offset = 5e12  # profiler ns = monotonic ns + offset
    mono = lambda s: s * 1e9 + offset  # noqa: E731
    tls = [
        _timeline(110.0, 13, 200, 0.010, 0.020, 0.400, 0.401),
        _timeline(110.1, 13, 180, 0.012, 0.018, 0.300, 0.302),
        _timeline(120.0, 15, 90, 0.030, 0.040, 0.500, 0.503),  # not traced
        _timeline(90.0, 2, 50, 0.5, 0.6, 0.7, 0.8),  # before the window
    ]
    flight = types.SimpleNamespace(complete=True, finished=lambda: tls)
    launches = [
        _launch("decode", 11, mono(109.0), steps=8, live=16, slots=32),
        _launch("prefill", 13, mono(110.015), tokens=380, rows=2),
        _launch("decode", 14, mono(110.05), steps=4, live=32, slots=32),
    ]
    modules = [
        _module("decode", mono(109.1), mono(110.2)),
        _module("prefill", mono(110.2), mono(110.25)),
        _module("decode", mono(110.25), mono(110.3)),
    ]
    records = [
        {"ok": True, "windowed": True, "prompt_tokens": 200, "due": 9.99,
         "sent": 9.995, "chunks": [10.402, 10.5]},
        {"ok": True, "windowed": True, "prompt_tokens": 180, "due": 10.09,
         "sent": 10.095, "chunks": [10.403]},
    ]
    run = {
        "engine": types.SimpleNamespace(flight=flight), "t0": 100.0,
        "seconds": 51.0, "records": records,
        "_spans": {
            "phases": [("idle", mono(109.0), mono(109.05))],
            "launches": launches, "modules": modules,
            "clock": [], "clock_fit": {"offset_ns": offset, "residual_ns": 0.0,
                                       "iqr_ns": 0.0, "samples": 9},
            "pairs": spans.pair(launches, modules),
            "busy": [(mono(109.1), mono(110.3))],
            "window": (mono(109.1), mono(110.3)),
        },
    }
    r = lambda name: READERS["spans:" + name](run, CELL)  # noqa: E731
    assert r("queue_wait_p50_ms") == pytest.approx(12.0)  # 10, 12, 30
    assert r("dispatch_to_first_token_p50_ms") == pytest.approx(380.0)
    # launch 13's program starts at 110.2: 180 and 82 ms after the events
    assert r("prefill_device_wait_p50_ms") == pytest.approx(131.0, abs=1e-3)
    # and ends at 110.25: first tokens at 110.4 and 110.4
    assert r("first_token_landing_p50_ms") == pytest.approx(150.0, abs=1e-3)
    assert r("batch_occupancy") == pytest.approx(
        100.0 * (16 * 8 + 32 * 4) / (32 * 12))
    assert r("prefill_paired_tok_s") == pytest.approx(380 / 0.05, rel=1e-6)
    assert r("idle_host_busy_share") == 0.0
    # a recorder that rotated: every timeline metric goes, the trace's stay
    flight.complete = False
    run.pop("_chains")
    assert r("queue_wait_p50_ms") is None
    assert r("prefill_device_wait_p50_ms") is None
    assert r("batch_occupancy") is not None


def test_the_chain_line_adds_up_per_request(capsys):
    """``match_records`` finds the client's record of a request by prompt
    length and instants; the line's parts are those of the same requests."""
    tls = [_timeline(110.0, 13, 200, 0.010, 0.020, 0.400, 0.401)]
    chains = [spans.chain(tl) for tl in tls]
    recs = [
        {"ok": True, "prompt_tokens": 200, "sent": 30.0, "chunks": [30.5]},
        {"ok": True, "prompt_tokens": 200, "sent": 9.99, "chunks": [10.41]},
        {"ok": False, "prompt_tokens": 200, "sent": 9.99, "chunks": []},
    ]
    assert spans.match_records(chains, recs, 100.0) == [(chains[0], recs[1])]
    tls[0].events.pop()  # never handed a delta: no chain
    assert spans.chain(tls[0]) is None


# -- the rehearsal -------------------------------------------------------

SPAN_METRICS = [
    "engine.queue_wait_p50_ms", "engine.dispatch_to_first_token_p50_ms",
    "engine.prefill_device_wait_p50_ms", "engine.first_token_landing_p50_ms",
    "engine.batch_occupancy", "model.prefill_paired_tok_s.chat",
    "device.idle_host_busy_share",
]


@pytest.fixture(scope="module")
def traced_line(tmp_path_factory):
    """The toy benchmark of ``test_perfbench_harness`` with the span metrics
    added as files, one traced rehearsal of its open-loop cell."""
    root = tmp_path_factory.mktemp("spanbench")
    bench = root / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "pico.json").write_text(json.dumps(harness.PICO))
    (bench / "traffic" / "pico-open.json").write_text(
        json.dumps(harness.PICO_OPEN))
    names = ["ttft_p50_ms", "setup_s", "engine.host_share", *SPAN_METRICS]
    for name in names:
        shutil.copy(os.path.join(REPO, "perfbench", "metrics", name + ".json"),
                    bench / "metrics")
    cells = ["pico.open"]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["bench"],
        "run_seconds": 3,
        "configs": [{"name": "pico", "source": "none", "reduced": [],
                     "file": "bench/configs/pico.json", "why": "toy"}],
        "workloads": [{"name": "pico.open", "config": "pico",
                       "traffic": "pico-open", "chips": 1, "why": "toy"}],
        "end_to_end": [harness._entry("ttft_p50_ms", workloads=cells),
                       harness._entry("setup_s")],
        "per_layer": [harness._entry(n, workloads=cells) for n in names[2:]],
    }))
    proc = harness._run(str(root), "pico.open", 1, 2**31 + 11, "--rehearse-cpu")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_the_rehearsal_prints_the_host_clock_span_metrics(traced_line):
    line, text = traced_line
    got = line["metrics"]
    assert line["correct"] is True
    for name in ("engine.queue_wait_p50_ms",
                 "engine.dispatch_to_first_token_p50_ms"):
        assert 0 <= got[name]["value"] < 3000 and got[name]["unit"] == "ms"
    assert 0 < got["engine.batch_occupancy"]["value"] <= 100
    # the profiled engine's annotations are in the CPU trace too
    assert "spans: " in text and "phase annotations" in text


def test_the_rehearsal_prints_no_device_span_metric(traced_line):
    line, _ = traced_line
    for name in ("engine.prefill_device_wait_p50_ms",
                 "engine.first_token_landing_p50_ms",
                 "model.prefill_paired_tok_s.chat",
                 "device.idle_host_busy_share"):
        assert name not in line["metrics"]
