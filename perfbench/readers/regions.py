"""Readers of device time by the program's own regions
(``lib/regions.py``): how much of the busy device the registry names,
prefill's share of the traced window, a decode step and a prefilled token
split into the five groups of ``dynamo_tpu/models/regions.py``.

Every reader returns None, and raises nothing, on a checkout without the
registry (any commit before PR 37: these metrics are absent from its
line), on a trace that stores no program, and on a CPU rehearsal. The
trace is joined once a run and kept on the run; that reading prints the
``regions:`` lines: the totals, seconds by kind of program and group, the
twelve largest (kind, region / leaf) rows, and a decode step's wall split
between decode programs, prefill programs, the rest and idle.
"""

import functools
import time

from lib import regions, trace


def _say(msg: str) -> None:
    print(msg, flush=True)


def _reader(fn):
    """None, and a line in the log, where ``fn`` meets something it did not
    expect: a reader raises nothing, on any program."""
    @functools.wraps(fn)
    def guarded(run, cell):
        try:
            return fn(run, cell)
        except Exception as e:  # noqa: BLE001
            _say(f"regions: {fn.__name__} found nothing it could read: {e!r}")
            return None
    return guarded


def _registry(run):
    if "_registry" not in run:
        run["_registry"] = regions.load_registry()
    return run["_registry"]


def _joined(run, cell):
    """The run's trace by region, once: None without a registry, a trace
    or a device operation in it."""
    if "_regions" not in run:
        run["_regions"] = None
        registry = _registry(run)
        # run["trace"] is None on a rehearsal: no device plane to join
        path = run.get("trace") and run.get("trace_dir") and (
            trace.find_xplane(run["trace_dir"]))
        if registry is not None and path:
            t = time.monotonic()
            try:
                run["_regions"] = regions.reduce_file(
                    path, cell.config["trace_names"]["programs"], registry)
            except Exception as e:  # noqa: BLE001 - a reader never raises
                _say(f"regions: the trace could not be joined: {e!r}")
            if run["_regions"] is not None:
                _say(f"regions: joined {path} in "
                     f"{time.monotonic() - t:.1f} s")
                try:
                    _log(run, cell, run["_regions"])
                except Exception as e:  # noqa: BLE001 - a log line costs no metric
                    _say(f"regions: no summary: {e!r}")
    return run["_regions"]


def _decode_steps(run, cell):
    """The decode steps executed inside the traced window, as
    ``device:decode_step_ms`` counts them: its programs' seconds over its
    step."""
    step_ms = cell.readers["device:decode_step_ms"](run, cell)
    secs = (run["trace"]["by_kind"].get("decode") or {}).get("secs")
    if not step_ms or not secs:
        return None
    return 1e3 * secs / step_ms


def _paired_prefills(run, cell, joined):
    """(seconds by group, real prompt tokens) over the prefill launches
    ``spans:prefill_paired_tok_s`` pairs with programs that ran wholly
    inside the traced window. Where the launches cannot be paired (that
    reader then finds nothing either), over every prefill program of the
    window and the tokens of the dispatches tapped during it, as
    ``device:prefill_tok_s`` counts them: a program at either edge may
    then be counted on one side only, and a log line says so."""
    if "_regions_prefills" not in run:
        run["_regions_prefills"] = _prefill_groups(run, cell, joined)
    return run["_regions_prefills"]


def _prefill_groups(run, cell, joined):
    # that reader reads the spans once a run and keeps them on the run
    cell.readers["spans:prefill_paired_tok_s"](run, cell)
    tr = run.get("_spans")
    if tr and tr["pairs"] and tr["window"]:
        w0, w1 = tr["window"]
        groups = dict.fromkeys(_registry(run).GROUPS, 0.0)
        tokens = 0
        for ln, m in tr["pairs"]:
            if ln.kind != "prefill" or m.start < w0 or m.end > w1:
                continue
            for g, s in joined["per_module"].get(m.start, {}).items():
                groups[g] += s
            tokens += ln.counts.get("tokens", 0)
        return groups, tokens
    if not run.get("traced") or "prefill" not in joined["by_kind"]:
        return None, 0
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    tokens = sum(sum(ns) for t, ns in run.get("prefills", ()) if a <= t < b)
    if tokens:
        _say("regions: no launch is paired with its program in this trace: "
             "model.prefill_region_us_tok.* divide all the window's prefill "
             "programs by the tokens tapped while it was traced")
    return joined["by_kind"]["prefill"]["groups"], tokens


@_reader
def region_named_share(run, cell):
    """Busy device time resolved to a region of the registry, over busy
    time."""
    j = _joined(run, cell)
    if not j or not j["busy_s"]:
        return None
    return 100.0 * j["named_s"] / j["busy_s"]


@_reader
def prefill_device_share(run, cell):
    """Device time inside prefill programs over the traced window."""
    j = _joined(run, cell)
    if not j or not j["window_s"]:
        return None
    return 100.0 * j["by_kind"].get("prefill", {"secs": 0.0})["secs"] / (
        j["window_s"])


def _decode_region_ms(group: str):
    @_reader
    def read(run, cell):
        j = _joined(run, cell)
        if not j or "decode" not in j["by_kind"]:
            return None
        steps = _decode_steps(run, cell)
        if not steps:
            return None
        return 1e3 * j["by_kind"]["decode"]["groups"][group] / steps
    read.__name__ = read.__qualname__ = f"decode_region_ms_{group}"
    read.__doc__ = (f"The group {group}'s device time inside decode programs "
                    "over the decode steps executed.")
    return read


def _prefill_region_us_tok(group: str):
    @_reader
    def read(run, cell):
        j = _joined(run, cell)
        if not j:
            return None
        groups, tokens = _paired_prefills(run, cell, j)
        if not groups or not tokens:
            return None
        return 1e6 * groups[group] / tokens
    read.__name__ = read.__qualname__ = f"prefill_region_us_tok_{group}"
    read.__doc__ = (f"The group {group}'s device time inside the paired "
                    "prefill programs over the real prompt tokens their "
                    "launches carried.")
    return read


# one reader a group, under the names the metric files give: the groups are
# the registry's, spelled out here so that the module loads without it
decode_region_ms_attn_proj = _decode_region_ms("attn_proj")
decode_region_ms_attn_ctx = _decode_region_ms("attn_ctx")
decode_region_ms_ffn = _decode_region_ms("ffn")
decode_region_ms_head = _decode_region_ms("head")
decode_region_ms_rest = _decode_region_ms("rest")
prefill_region_us_tok_attn_proj = _prefill_region_us_tok("attn_proj")
prefill_region_us_tok_attn_ctx = _prefill_region_us_tok("attn_ctx")
prefill_region_us_tok_ffn = _prefill_region_us_tok("ffn")
prefill_region_us_tok_head = _prefill_region_us_tok("head")
prefill_region_us_tok_rest = _prefill_region_us_tok("rest")


# -- the log lines ---------------------------------------------------------


def _log(run, cell, j) -> None:
    for line in regions.describe(j, top=12):
        _say(line)
    steps = _decode_steps(run, cell)
    if not steps:
        return
    kinds = j["by_kind"]

    def ms(kind):
        return 1e3 * kinds.get(kind, {"secs": 0.0})["secs"] / steps

    other = sum(
        k["secs"] for name, k in kinds.items()
        if name not in ("decode", "prefill")
    )
    idle = max(0.0, j["window_s"] - j["busy_s"])
    line = (
        f"regions: a decode step's wall: decode {ms('decode'):.3f} + prefill "
        f"{ms('prefill'):.3f} + other {1e3 * other / steps:.3f} + idle "
        f"{1e3 * idle / steps:.3f} ms = {1e3 * j['window_s'] / steps:.3f} ms "
        f"over {steps:.1f} steps"
    )
    # the traced run's own gap between a stream's tokens, at the client
    tpot = cell.readers["client:tpot_p50_ms"](run, cell)
    if tpot:
        line += f"; the traced run's own tpot_p50_ms {tpot:.3f}"
    _say(line)
