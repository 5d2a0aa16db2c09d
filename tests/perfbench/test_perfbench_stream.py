"""The readers of the delivery path (``perfbench/readers/stream.py``): the
eight entries by name, inert on a program without the spans, each reader on
a hand-made trace, timeline or snapshot pair with a value known by hand, the
``stream:`` lines, and a CPU rehearsal that prints the host-clock ones."""

import importlib.util
import json
import os
import shutil
import subprocess
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
PROGRAMS = {"decode": ["decode_steps"], "prefill": ["prefill_forward"]}
CELL = types.SimpleNamespace(
    config={"trace_names": {"programs": PROGRAMS}}, readers=READERS)
# the six cells whose tests do not hold their metric set to an exact list
# (``has == JOINED | set(NEW)`` in the five newer cells' test files: a cell
# of theirs on a new metric's list turns that test red; PERF.md section 7)
ALL_CELLS = [
    "mistral7b.chat", "nemo12b.batch", "mimo25.longtail", "mistral7b.batch",
    "joyai-flash.reasoning", "nemo12b.chat",
]
CHAT = ["mistral7b.chat", "nemo12b.chat"]
# metric -> (reader, moves, cells, source)
NEW = {
    "engine.stream_tpot_p50_ms": (
        "stream:stream_tpot_p50_ms", "tpot_p50_ms", ALL_CELLS, "program_span"),
    "frontend.tpot_overhead_p50_ms": (
        "stream:tpot_overhead_p50_ms", "tpot_p50_ms", ALL_CELLS,
        "program_span"),
    # the links of a gap's chain shift a stream's tokens alike and add
    # nothing to its time per token: their scatter widens a gap's tail
    "engine.burst_landing_p50_ms": (
        "stream:burst_landing_p50_ms", "itl_p95_ms", CHAT, "device_trace"),
    "engine.post_to_stream_p95_ms": (
        "stream:post_to_stream_p95_ms", "itl_p95_ms", CHAT, "program_span"),
    # and a first token pays the mean wait whole
    "engine.post_to_stream_mean_ms": (
        "stream:post_to_stream_mean_ms", "ttft_p50_ms", CHAT,
        "program_counter"),
    "engine.stream_gap_p95_ms": (
        "stream:stream_gap_p95_ms", "itl_p95_ms", CHAT, "program_span"),
    "runtime.loop_lag_max_ms": (
        "stream:loop_lag_max_ms", "itl_p95_ms", CHAT, "program_counter"),
    "runtime.loop_stalled_share": (
        "stream:loop_stalled_share", "tpot_p50_ms", ALL_CELLS,
        "program_counter"),
}
STREAM_READERS = sorted(r for r, *_ in NEW.values())


def _module():
    """``readers/stream.py`` as a module, for its private helpers."""
    path = os.path.join(REPO, "perfbench", "readers", "stream.py")
    s = importlib.util.spec_from_file_location("stream_under_test", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


STREAM = _module()


# -- the entries, by name ------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_is_found_by_name_and_agrees_with_its_file(name):
    reader, moves, cells, source = NEW[name]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    found = [e for e in bench["per_layer"] if e["name"] == name]
    assert len(found) == 1
    entry = found[0]
    m = json.load(open(os.path.join(
        REPO, "perfbench", "metrics", name + ".json")))
    for key in ("name", "unit", "better", "source", "layer", "moves",
                "workloads"):
        assert entry[key] == m[key], key
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (m["reader"], m["moves"], m["workloads"], m["source"]) == (
        reader, moves, cells, source)
    assert m["reader"] in READERS and m["kind"] == "per_layer"
    assert m["better"] == "lower"
    assert m["unit"] == ("%" if name.endswith("_share") else "ms")
    # every cell listed exists and reports the end-to-end metric it moves
    e2e = {e["name"]: e for e in bench["end_to_end"]}[moves]
    assert set(cells) <= set(e2e["workloads"])
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    # its layer is one the benchmark already names
    assert entry["layer"] in {"engine scheduler", "HTTP frontend"}


def test_the_module_brings_the_eight_readers_and_nothing_else():
    assert sorted(k for k in READERS if k.startswith("stream:")) == \
        STREAM_READERS
    assert len(STREAM_READERS) == 8


def test_the_module_loads_without_jax_or_the_program():
    """``load_readers`` executes every module under ``readers/`` in every
    run: this one must cost a ``--trace 0`` run nothing."""
    code = (
        "import sys; sys.path.insert(0, 'perfbench')\n"
        "import importlib.util as u\n"
        "s = u.spec_from_file_location('m', 'perfbench/readers/stream.py')\n"
        "m = u.module_from_spec(s); s.loader.exec_module(m)\n"
        "bad = [k for k in sys.modules if k == 'jax' or "
        "k.startswith(('jax.', 'dynamo_tpu'))]\n"
        "assert not bad, bad\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


# -- a program without the spans -----------------------------------------


def _parent_run(tmp_path, with_trace: bool) -> dict:
    """A run dict of the parent's shape: a flight recorder that kept the
    window but records no ``delta``, snapshots without the two families, an
    engine without a probe, a trace without ``stream.*`` annotations."""
    trace_dir = None
    if with_trace:
        d = tmp_path / "trace" / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        shutil.copy(os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb"),
                    d / "vm.xplane.pb")
        trace_dir = str(tmp_path / "trace")
    tls = [_timeline(110.0, 200, 0.4, None, 0, 9)]
    flight = types.SimpleNamespace(complete=True, finished=lambda: tls)
    snap = {"window.at": {"secs": 100.0, "calls": 0},
            "idle": {"secs": 1.0, "calls": 3}}
    later = dict(snap, **{"window.at": {"secs": 151.0, "calls": 0}})
    return {
        "engine": types.SimpleNamespace(config=None, flight=flight),
        "records": [{"ok": True, "windowed": True, "prompt_tokens": 200,
                     "sent": 9.9, "due": 9.9, "chunks": [10.5, 10.9],
                     "completion_tokens": 9}],
        "profile": (snap, later), "t0": 100.0, "seconds": 51.0,
        "trace_dir": trace_dir,
        "traced": (15.0, 21.0, 50.0) if with_trace else None,
    }


@pytest.mark.parametrize("with_trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("reader", STREAM_READERS)
def test_a_reader_finds_nothing_on_the_parent_s_program(
        tmp_path, capsys, reader, with_trace):
    run = _parent_run(tmp_path, with_trace)
    assert READERS[reader](run, CELL) is None
    out = capsys.readouterr().out
    assert "found nothing it could read" not in out  # None, not an error
    assert "stream:" not in out  # and nothing to say


def test_a_reader_raises_nothing(capsys):
    """Whatever it meets: a run dict with nothing it expects."""
    for reader in STREAM_READERS:
        assert READERS[reader]({"trace_dir": 7}, None) is None
    assert "found nothing it could read" in capsys.readouterr().out


# -- the trace's events --------------------------------------------------


def _event(name, start, duration=0.0, **stats):
    return types.SimpleNamespace(
        name=name, start_ns=start, duration_ns=duration,
        stats=list(stats.items()))


def _profile(*lines):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name="XLA Ops", events=[
                _event("stream.post", 5.0, seq=1)])]),
        types.SimpleNamespace(name=spans.HOST_PLANE, lines=[
            types.SimpleNamespace(name="python", events=list(evs))
            for evs in lines]),
    ])


def test_the_delivery_events_of_a_trace_by_name_on_any_line():
    step = [
        _event("engine.process", 900.0, 400.0),
        _event("stream.post", 1000.0, 200.0, seq="14"),
        _event("stream.post", 400.0, 50.0, seq=13),
    ]
    loop = [
        _event("stream.take", 1300.0, 1.0, rid=2, wait_us=300),
        _event("stream.take", 1250.0, 1.0, rid=1, wait_us=250),
        _event("loop.stall", 9000.0, 1.0, lag_us=70_000),
        _event("stream.taken", 1.0),
    ]
    ev = STREAM._delivery_events(_profile(step, loop))
    assert [tuple(p) for p in ev["posts"]] == [
        (13, 400.0), (14, 1000.0)]
    assert [tuple(t) for t in ev["takes"]] == [
        (1, 1250.0, 250), (2, 1300.0, 300)]
    assert [tuple(s) for s in ev["stalls"]] == [(9000.0, 70_000)]
    # a trace with the engine's annotations alone holds none
    assert STREAM._delivery_events(_profile(step[:1])) is None


# -- the readers on a hand-made run --------------------------------------


def _timeline(t0, prompt_tokens, first_delta, last_delta, n_delta, generated,
              *, split=False, dropped=0):
    """A finished timeline: ``first_delta`` and, with ``last_delta``, one
    coalesced ``delta`` entry (``split``: two, a ``preempt`` between)."""
    events = [
        {"name": "admit", "t": 0.01, "t_last": 0.01, "n": 1},
        {"name": "first_token", "t": first_delta - 0.001,
         "t_last": first_delta - 0.001, "n": 1},
        {"name": "first_delta", "t": first_delta, "t_last": first_delta,
         "n": 1},
    ]
    if last_delta is not None:
        mid = (first_delta + last_delta) / 2
        if split:
            events += [
                {"name": "delta", "t": first_delta + 0.01, "t_last": mid,
                 "n": n_delta // 2},
                {"name": "preempt", "t": mid, "t_last": mid, "n": 1},
                {"name": "delta", "t": mid + 0.01, "t_last": last_delta,
                 "n": n_delta - n_delta // 2},
            ]
        else:
            events.append({"name": "delta", "t": first_delta + 0.01,
                           "t_last": last_delta, "n": n_delta})
    return types.SimpleNamespace(
        t0=t0, events=events, dropped_events=dropped,
        attrs={"prompt_tokens": prompt_tokens, "generated": generated})


def _record(prompt_tokens, sent, chunks, tokens, ok=True):
    return {"ok": ok, "windowed": True, "prompt_tokens": prompt_tokens,
            "due": sent, "sent": sent, "chunks": chunks,
            "completion_tokens": tokens}


def _tpot_run():
    """Four streams enqueued inside the window [100, 151) and three the
    readers must leave out; the client's records of the four."""
    tls = [
        # (1.4 - 0.4) / (21 - 1) = 50 ms a token
        _timeline(110.0, 200, 0.4, 1.4, 10, 21),
        # a preemption split the deltas: the LAST entry counts, 60 ms
        _timeline(111.0, 180, 0.2, 0.8, 5, 11, split=True),
        _timeline(112.0, 150, 0.3, 1.0, 7, 8),  # 100 ms
        _timeline(113.0, 90, 0.1, 0.5, 4, 5),  # 100 ms
        _timeline(90.0, 70, 0.1, 0.5, 4, 5),  # before the window
        _timeline(114.0, 60, 0.1, None, 0, 1),  # one token: no gap
        _timeline(115.0, 50, 0.1, 0.9, 4, 5, dropped=2),  # events dropped
    ]
    records = [
        # first chunk 10.41 (engine 10.40), last 11.45 (engine 11.40):
        # (11.45 - 10.41) / 20 = 52 ms, 2 ms over the engine's
        _record(200, 9.99, [10.41, 10.9, 11.45], 21),
        _record(180, 10.99, [11.21, 11.5, 11.84], 11),  # 63 ms: +3
        _record(150, 11.99, [12.31, 13.08], 8),  # 110 ms: +10
        _record(90, 12.99, [13.11, 13.53], 5),  # 105 ms: +5
    ]
    flight = types.SimpleNamespace(complete=True, finished=lambda: tls)
    return {"engine": types.SimpleNamespace(flight=flight), "t0": 100.0,
            "seconds": 51.0, "records": records}


def test_the_engine_s_time_per_token_and_the_overhead_beyond_it(capsys):
    run = _tpot_run()
    r = lambda name: READERS["stream:" + name](run, CELL)  # noqa: E731
    # 50, 60, 100, 100 ms: the median lies between 60 and 100
    assert r("stream_tpot_p50_ms") == pytest.approx(80.0)
    # +2, +3, +10, +5 ms: between 3 and 5
    assert r("tpot_overhead_p50_ms") == pytest.approx(4.0, abs=1e-6)
    # one of the client's four streams finds no timeline: 75% matched
    run = _tpot_run()
    run["records"][3]["prompt_tokens"] = 91
    assert r("stream_tpot_p50_ms") == pytest.approx(80.0)
    assert READERS["stream:tpot_overhead_p50_ms"](run, CELL) is None
    assert "75.0% of the client's streams matched" in capsys.readouterr().out
    # a request of the same prompt length enqueued while another waited
    # between its send and its first chunk matches that record too: its
    # first delta is seconds off the client's first chunk, so it is left
    # out and the share stays the client's own
    run = _tpot_run()
    run["engine"].flight.finished().insert(
        0, _timeline(110.2, 200, 3.0, 4.0, 10, 21))
    run["records"][0]["chunks"] = [10.41, 10.9, 11.45]
    run["records"][0]["sent"] = 9.99
    run["records"].append(_record(200, 10.1, [13.21, 14.23], 21))
    got, share = STREAM._matched(run)
    assert share == 1.0 and len(got) == 5
    assert sorted(round(1e3 * (c - row["tpot"]), 3) for row, _, c in got) == [
        1.0, 2.0, 3.0, 5.0, 10.0]
    # a closed loop counts a stream by its chunks, as the load generator
    # does: the one enqueued at 90 s whose tokens came at 100.1-100.5 s
    # (100 ms a token) is the window's, one wholly before it is not
    run = _tpot_run()
    run["plan"] = {"loop": "closed"}
    tls = run["engine"].flight.finished()
    tls[4] = _timeline(90.0, 70, 10.1, 10.5, 4, 5)
    tls.append(_timeline(80.0, 70, 1.0, 19.9, 4, 5))
    assert READERS["stream:stream_tpot_p50_ms"](run, CELL) == \
        pytest.approx(100.0)  # 50, 60, 100, 100, 100
    # a recorder that rotated: neither is read
    run = _tpot_run()
    run["engine"].flight.complete = False
    assert READERS["stream:stream_tpot_p50_ms"](run, CELL) is None
    assert READERS["stream:tpot_overhead_p50_ms"](run, CELL) is None


def test_the_six_cell_reader_has_the_trace_read_and_the_lines_printed(
        monkeypatch):
    """The throughput cells list no metric of the trace's own: the reader
    every cell lists is what brings their ``stream:`` lines."""
    fn, asked = READERS["stream:stream_tpot_p50_ms"], []
    monkeypatch.setitem(fn.__wrapped__.__globals__, "_delivery",
                        lambda run, cell: asked.append(cell))
    assert fn(_tpot_run(), CELL) == pytest.approx(80.0)
    assert asked == [CELL]


def _launch(kind, seq, at, **counts):
    return spans.Launch(kind, seq, at, at + 50.0, counts)


def _program(kind, a, b):
    name = {"prefill": "jit_prefill_forward_batch_impl(1)",
            "decode": "jit_decode_steps_impl(2)"}[kind]
    return spans.Module(kind, name, a, b)


def _ring(maxlen=None):
    """The heartbeat's wake-ups: 900 ms late a second before the window's
    opening snapshot (at 100 s of the clock), 250 ms and 350 ms inside it,
    2 s late after its closing one (at 160 s)."""
    import collections

    s = 1_000_000_000
    return collections.deque(
        [(99 * s, 900_000), (100 * s + 1, 40), (120 * s, 250_000),
         (120 * s + 5, 90), (159 * s, 350_000), (160 * s + 1, 2_000_000)],
        maxlen=maxlen)


def _traced_run():
    """Three decode bursts of 4 steps with a prefill between the second and
    the third, their posts and the takes of two streams, one stall; times
    in ns on the profiler's clock, ms = 1e6."""
    ms = 1e6
    launches = [
        _launch("decode", 11, 0 * ms, steps=4), _launch("decode", 12, 40 * ms,
                                                        steps=4),
        _launch("prefill", 13, 60 * ms, tokens=300, rows=1),
        _launch("decode", 14, 90 * ms, steps=4),
    ]
    modules = [
        _program("decode", 1 * ms, 41 * ms), _program("decode", 41 * ms, 81 * ms),
        _program("prefill", 81 * ms, 91 * ms), _program("decode", 91 * ms, 131 * ms),
    ]
    posts = [
        STREAM._Post(11, 43 * ms),  # 2 ms after 41
        STREAM._Post(12, 85 * ms),  # 4 ms after 81
        STREAM._Post(13, 95 * ms),  # a wave's: no decode
        STREAM._Post(14, 139 * ms),  # 8 ms after 131
        STREAM._Post(99, 500 * ms),  # its burst not paired
    ]
    takes = [
        STREAM._Take(1, 43.2 * ms, 200), STREAM._Take(2, 43.4 * ms, 400),
        STREAM._Take(1, 85.3 * ms, 300), STREAM._Take(2, 85.6 * ms, 600),
        STREAM._Take(3, 95.2 * ms, 100),
        STREAM._Take(1, 139.1 * ms, 100), STREAM._Take(2, 139.2 * ms, 200),
        STREAM._Take(3, 149.2 * ms, 10_200),
    ]
    # the loop stood still from 60 to 130 ms: the step thread idle 30 ms,
    # in process 20 ms, between phases 20 ms; the device idle 5 ms of it
    stalls = [STREAM._Stall(130 * ms, 70_000)]
    phases = sorted([
        ("idle", 50 * ms, 90 * ms), ("process", 90 * ms, 110 * ms),
        ("process.d2h_sync", 95 * ms, 100 * ms), ("idle", 130 * ms, 150 * ms),
    ], key=lambda p: (p[1], -p[2]))
    snap = lambda at, **c: {  # noqa: E731
        "window.at": {"secs": at, "calls": 0},
        **{k.replace("__", "."): {"secs": 0.0, "calls": v}
           for k, v in c.items()}}
    return {
        "engine": types.SimpleNamespace(
            flight=None, loop_probe=types.SimpleNamespace(lags=_ring())),
        "t0": 100.0, "seconds": 51.0, "records": [],
        "profile": (
            snap(100.0, stream__items=10, stream__wait_us=9000,
                 event_loop__stalled_us=80_000),
            snap(160.0, stream__items=1010, stream__wait_us=509_000,
                 event_loop__stalled_us=680_000),
        ),
        "_stream": {"posts": posts, "takes": takes, "stalls": stalls},
        "_spans": {
            "phases": phases, "launches": launches, "modules": modules,
            "clock": [], "clock_fit": None,
            "pairs": spans.pair(launches, modules),
            "busy": [(1 * ms, 125 * ms), (130 * ms, 131 * ms)],
            "window": (1 * ms, 131 * ms),
        },
    }


def test_the_trace_s_three_metrics_known_by_hand():
    run = _traced_run()
    r = lambda name: READERS["stream:" + name](run, CELL)  # noqa: E731
    # 2, 4 and 8 ms after the paired decode programs' ends
    assert r("burst_landing_p50_ms") == pytest.approx(4.0)
    # waits 100 100 200 200 300 400 600 10200 us: the 95th percentile lies
    # 0.65 of the way from 600 to 10200
    assert r("post_to_stream_p95_ms") == pytest.approx(
        (600 + 0.65 * 9600) * 1e-3)
    # stream 1: 42.1, 53.8; stream 2: 42.2, 53.6; stream 3: 54.0 ms
    assert r("stream_gap_p95_ms") == pytest.approx(
        53.8 + 0.8 * (54.0 - 53.8), abs=1e-6)
    # without a pairing no landing; without takes nothing of theirs
    run["_spans"]["pairs"] = None
    assert r("burst_landing_p50_ms") is None
    run["_stream"] = {"posts": [], "takes": [], "stalls": []}
    assert r("post_to_stream_p95_ms") is None
    assert r("stream_gap_p95_ms") is None


def test_the_whole_window_s_mean_wait_from_the_counters():
    run = _traced_run()
    r = READERS["stream:post_to_stream_mean_ms"]
    # 1000 items lay 500 ms in all between the snapshots
    assert r(run, CELL) == pytest.approx(0.5)
    run["profile"][1]["stream.items"]["calls"] = 10  # nothing was taken
    assert r(run, CELL) is None
    del run["profile"][1]["stream.items"]  # the parent's snapshot
    assert r(run, CELL) is None


def test_the_heartbeat_s_two_metrics_between_the_window_s_snapshots(capsys):
    run = _traced_run()
    r = lambda name: READERS["stream:" + name](run, CELL)  # noqa: E731
    # the largest lag between the snapshots' own instants, 100.0 and
    # 160.0 s: not the 900 ms before them, nor the 2 s after
    assert r("loop_lag_max_ms") == pytest.approx(350.0)
    # 600 ms of stalls in the 60 s that really lay between them, not 51
    assert r("loop_stalled_share") == pytest.approx(100.0 * 0.6 / 60.0)
    # a ring that turned over since the window opened is not the window's
    run["engine"].loop_probe.lags = _ring(maxlen=6)
    assert r("loop_lag_max_ms") == pytest.approx(350.0)  # reaches back to 99
    ring = _ring(maxlen=4)
    run["engine"].loop_probe.lags = ring
    assert ring[0][0] > 100e9 and r("loop_lag_max_ms") is None
    assert "ring begins after the window opened" in capsys.readouterr().out
    run["engine"].loop_probe.lags = []
    assert r("loop_lag_max_ms") is None  # no wake-up kept
    del run["engine"].loop_probe
    assert r("loop_lag_max_ms") is None  # the parent's engine
    assert r("loop_stalled_share") == pytest.approx(1.0)  # the counter's own
    del run["profile"][1]["event_loop.stalled_us"]
    assert r("loop_stalled_share") is None


def test_the_real_probe_answers_the_reader():
    """The reader against ``LoopProbe`` itself, ticked by hand."""
    sys.path.insert(0, REPO)
    from dynamo_tpu.runtime.loop_probe import LoopProbe

    probe = LoopProbe()
    for at, lag in _ring():
        probe._tick(at, lag)
    run = _traced_run()
    run["engine"].loop_probe = probe
    assert READERS["stream:loop_lag_max_ms"](run, CELL) == pytest.approx(350.0)


def test_the_stream_lines_say_the_chain_and_what_a_stall_overlapped(capsys):
    run = _traced_run()
    STREAM._log(run, CELL, run["_stream"])
    out = capsys.readouterr().out.splitlines()
    assert out[0] == ("stream: 5 posts, 8 takes of 3 streams, 1 stalls in "
                      "the trace")
    chain = out[1]
    # programs 40 ms of 4 steps; between the bursts 0 and 10 ms of prefill;
    # cycles 40 and 50 ms; landings 2, 4, 8; waits between 200 and 300 us
    for part in ("decode program 40.00 of 4.00 steps",
                 "between two bursts 5.00 (mean 5.00)",
                 "cycle start to start 45.00 (mean 45.00; all cycles over all "
                 "their steps 11.25 a step)",
                 "device end to post 4.00", "post to take 0.25",
                 "over 0 matched streams"):
        assert part in chain, (part, chain)
    # the engine's gaps of test_the_trace_s_three_metrics; no client here
    assert "in the trace 53.96, between the client's chunks over the " \
        "whole window -; the client's time per output token, p50 -" in out[2]
    assert out[3].endswith("1000 items took 0.50 ms from post to take on "
                           "average")
    # the ring's four wake-ups inside the snapshots: 40, 250000, 90, 350000
    assert "ticked 4 times" in out[4] and "late 150.03 ms on average, 2 " \
        "times over 50 ms for 600.0 ms in all; the latest, 350.0 ms, +59.0 s " \
        "from the window's opening (it is 51 s)" in out[4]
    # the stall: 70 ms from +59 ms of the traced window (which opens at 1)
    assert out[5] == (
        "stream: loop.stall 70.0 ms from +0.059 s of the traced window: "
        "step thread idle 43%, (none) 29%, process 21%, process.d2h_sync "
        "7%; device busy 93%")
    assert len(out) == 6


# -- the rehearsal -------------------------------------------------------


@pytest.fixture(scope="module")
def traced_line(tmp_path_factory):
    """The toy benchmark of ``test_perfbench_harness`` with the eight
    metrics added as files, one traced rehearsal of its open-loop cell."""
    root = tmp_path_factory.mktemp("streambench")
    bench = root / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "pico.json").write_text(json.dumps(harness.PICO))
    (bench / "traffic" / "pico-open.json").write_text(
        json.dumps(harness.PICO_OPEN))
    names = ["ttft_p50_ms", "setup_s", *sorted(NEW)]
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
    proc = harness._run(str(root), "pico.open", 1, 2**31 + 51, "--rehearse-cpu")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_the_rehearsal_prints_the_host_clock_stream_metrics(traced_line):
    line, text = traced_line
    got = line["metrics"]
    assert line["correct"] is True
    for name in ("engine.stream_tpot_p50_ms", "engine.post_to_stream_p95_ms",
                 "engine.post_to_stream_mean_ms", "engine.stream_gap_p95_ms",
                 "runtime.loop_lag_max_ms"):
        assert 0 <= got[name]["value"] < 5000 and got[name]["unit"] == "ms"
    assert 0 <= got["runtime.loop_stalled_share"]["value"] <= 100
    assert got["runtime.loop_stalled_share"]["unit"] == "%"
    # what the frontend adds to a gap is small beside the toy's gap itself
    assert abs(got["frontend.tpot_overhead_p50_ms"]["value"]) < \
        got["engine.stream_tpot_p50_ms"]["value"] + 50
    # the profiled engine's annotations are in the CPU trace too
    assert "stream: gap chain, p50 ms:" in text
    assert "found nothing it could read" not in text


def test_the_rehearsal_prints_no_device_stream_metric(traced_line):
    line, _ = traced_line
    assert "engine.burst_landing_p50_ms" not in line["metrics"]
