"""Tier-1 gate for tools/dynalint: the package must scan clean against the
committed baseline, the baseline itself must honor its own policy, and every
rule must prove it can both catch (true-positive fixture) and be silenced
(suppressed-negative fixture).

Fast by construction: dynalint is pure stdlib AST — no JAX, no model init —
so the whole-package scan fits well inside the <5s budget on CPU.
"""

from __future__ import annotations

import asyncio
import json
import re
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))  # tools/ is repo-level, not a package dep

from tools.dynalint import baseline as baseline_mod  # noqa: E402
from tools.dynalint import catalog  # noqa: E402
from tools.dynalint import wire  # noqa: E402
from tools.dynalint.core import build_index, run_paths, scan_file  # noqa: E402
from tools.dynalint.rules import RULES  # noqa: E402

FIXTURES = REPO_ROOT / "tools" / "dynalint" / "fixtures"
BASELINE = REPO_ROOT / "tools" / "dynalint" / "baseline.json"
WIRE_SCHEMA = REPO_ROOT / "tools" / "dynalint" / "wire_schema.json"
PROTOCOL_MD = REPO_ROOT / "docs" / "PROTOCOL.md"
# the CLI's default scan scope (package + tooling + the cluster helper
# that speaks the repl.* wire protocol from tests)
SCAN_SCOPE = [
    REPO_ROOT / "dynamo_tpu",
    REPO_ROOT / "tools",
    REPO_ROOT / "tests" / "hub_cluster.py",
]


# ---------------------------------------------------------------- the gate


YARDSTICK_S = 0.27  # ``_yardstick()`` where the scan takes its 2.8 s


def _yardstick() -> float:
    """The thread's CPU-seconds for a fixed piece of pure-Python work: what
    a second of this thread is worth now (a core that is shared with a
    busy sibling, or slower, gives less work a CPU-second)."""
    t0 = time.thread_time()
    sum(i * i for i in range(4_000_000))
    return time.thread_time() - t0


def test_dynalint_clean_against_baseline_under_5s():
    """THE gate: scanning the full default scope — including the
    interprocedural wire-schema/deadline/lock passes and the committed
    protocol-catalog drift check — yields no findings beyond the
    committed baseline, in under 5 seconds of the scanning thread's own
    CPU on a machine to itself, the best of up to three scans. The wall
    beside five busy workers says how loaded the machine is; the process's
    CPU counts what earlier tests' threads still burn in this worker
    (8.13 s in one whole run); and a thread's own CPU-seconds stretch when
    its core is shared (5.06 and 5.41 s as the best of three inside whole
    runs, 2.5-2.9 s alone): the budget grows by what the yardstick, taken
    before and after a scan, says a CPU-second is worth, and never
    shrinks."""
    elapsed = budget = float("inf")
    for _ in range(3):
        before = _yardstick()
        t0 = time.thread_time()
        findings, _suppressed, _warnings = run_paths(
            SCAN_SCOPE, REPO_ROOT, wire_schema_path=WIRE_SCHEMA
        )
        took = time.thread_time() - t0
        worth = min(before, _yardstick()) / YARDSTICK_S
        if took < elapsed:
            elapsed, budget = took, 5.0 * max(1.0, worth)
        if elapsed < budget:
            break
    base = baseline_mod.load(BASELINE)
    new, _old, _stale = baseline_mod.split(findings, base)
    assert not new, "new dynalint findings:\n" + "\n".join(
        f.render() for f in new
    )
    assert elapsed < budget, (
        f"dynalint scan took {elapsed:.2f}s (budget {budget:.2f}s)")


def test_baseline_never_grandfathers_dl001_dl002():
    """DL001/DL002/DL007 are fixed outright, never baselined (ISSUE
    acceptance criterion + baseline.py policy; DL007 because a
    grandfathered wire-schema drift is a shipped protocol break)."""
    assert "DL007" in baseline_mod.NEVER_BASELINE
    data = json.loads(BASELINE.read_text())
    bad = [e for e in data["findings"]
           if e["rule"] in baseline_mod.NEVER_BASELINE]
    assert not bad, f"baseline contains banned rules: {bad}"


def test_committed_baseline_is_empty():
    """The satellite contract: every in-tree finding is FIXED or
    reason-suppressed — the baseline grandfathers nothing."""
    data = json.loads(BASELINE.read_text())
    assert data["findings"] == [], (
        "baseline must stay empty; fix or reason-suppress instead: "
        f"{data['findings']}"
    )


def test_stale_baseline_entries_are_reported():
    """A baseline fingerprint nothing produces any more must surface (the
    baseline shrinks monotonically, it never accretes dead weight)."""
    findings, _s, _w = run_paths([REPO_ROOT / "dynamo_tpu"], REPO_ROOT)
    fake = {"deadbeef0000": {
        "fingerprint": "deadbeef0000", "rule": "DL003",
        "path": "dynamo_tpu/nonexistent.py", "context": "gone",
    }}
    _new, _old, stale = baseline_mod.split(findings, fake)
    assert [e["fingerprint"] for e in stale] == ["deadbeef0000"]


def test_unused_suppression_is_reported(tmp_path):
    """A disable whose finding is gone must surface — otherwise it sits
    there masking the NEXT finding on that line forever."""
    (tmp_path / "mod.py").write_text(
        "import asyncio\n\n\n"
        "async def fine():\n"
        "    # dynalint: disable=DL001 -- stale: the sleep was removed\n"
        "    await asyncio.sleep(0)\n"
    )
    (tmp_path / "mod2.py").write_text(
        "# dynalint: disable-file=DL005 -- stale: class went away\n"
        "X = 1\n"
    )
    findings, suppressed, warnings = run_paths([tmp_path], tmp_path)
    assert not findings and not suppressed
    assert any("unused suppression for DL001" in w for w in warnings)
    assert any(
        "unused suppression for DL005" in w and "mod2.py:1" in w
        for w in warnings
    ), "stale file-wide disable not reported"


def test_package_has_no_unused_suppressions():
    """Every in-repo disable still silences a live finding (full default
    scope, since tools/ and the cluster helper are now scanned too)."""
    _f, _s, warnings = run_paths(SCAN_SCOPE, REPO_ROOT)
    unused = [w for w in warnings if "unused suppression" in w]
    assert not unused, "\n".join(unused)


def test_in_repo_suppressions_carry_reasons():
    """Every ``# dynalint: disable=`` in the scanned scope must have a
    written ``-- reason`` (the satellite contract: suppress WITH a
    reason)."""
    offenders = []
    files = [
        *(REPO_ROOT / "dynamo_tpu").rglob("*.py"),
        *(REPO_ROOT / "tools").rglob("*.py"),
        REPO_ROOT / "tests" / "hub_cluster.py",
    ]
    for f in files:
        if "__pycache__" in f.parts:
            continue
        for i, line in enumerate(f.read_text().splitlines(), 1):
            if "dynalint: disable" in line and "--" not in line:
                offenders.append(f"{f.relative_to(REPO_ROOT)}:{i}")
    assert not offenders, f"suppressions without reasons: {offenders}"


# ------------------------------------------------------------ the fixtures


def _expected_findings(path: Path) -> dict[int, set[str]]:
    expected: dict[int, set[str]] = {}
    for i, line in enumerate(path.read_text().splitlines(), 1):
        m = re.search(r"# EXPECT: (DL\d+)", line)
        if m:
            expected.setdefault(i, set()).add(m.group(1))
    return expected


@pytest.mark.parametrize(
    "fixture", sorted(FIXTURES.glob("dl0*.py")), ids=lambda p: p.stem
)
def test_fixture_golden(fixture: Path):
    """Each fixture produces EXACTLY its ``# EXPECT: DLnnn`` findings —
    no false negatives on the marked lines, no false positives anywhere
    else — and exercises at least one suppressed negative."""
    expected = _expected_findings(fixture)
    assert expected, f"{fixture.name} has no EXPECT markers"
    active, suppressed, _ctx = scan_file(fixture, REPO_ROOT)
    got: dict[int, set[str]] = {}
    for f in active:
        got.setdefault(f.line, set()).add(f.rule)
    assert got == expected, (
        f"{fixture.name}: expected {expected}, got {got}"
    )
    rule_id = fixture.stem[:5].upper().replace("DL0", "DL0")
    assert any(f.rule == rule_id for f in active), (
        f"{fixture.name} has no {rule_id} true positive"
    )
    assert any(f.rule == rule_id for f in suppressed), (
        f"{fixture.name} has no {rule_id} suppressed negative"
    )


def test_every_rule_has_a_fixture():
    stems = {p.stem[:5].upper() for p in FIXTURES.glob("dl0*.py")}
    assert stems == set(RULES), f"fixtures {stems} != rules {set(RULES)}"


# ------------------------------------------------- catalog <-> runtime sync


def test_fault_site_catalog_matches_runtime():
    """tools/dynalint/catalog.py and runtime/faults.py KNOWN_SITES are the
    same registry spelled twice (dynalint never imports the package under
    scan); they must never drift."""
    from dynamo_tpu.runtime.faults import KNOWN_SITES

    assert set(catalog.FAULT_SITES) == set(KNOWN_SITES)


def test_unknown_fault_site_in_spec_warns(caplog):
    from dynamo_tpu.runtime.faults import FaultRegistry

    reg = FaultRegistry()
    with caplog.at_level("WARNING", logger="dynamo.faults"):
        reg.configure("engine.setp:error@0.1")
    assert any("unknown site" in r.message for r in caplog.records)
    reg.clear()


def test_stale_catalog_entry_warns(tmp_path):
    """A catalogued site/metric no code uses is cross-file drift: the
    runner reports it (the code-level complement lives in DL006)."""
    (tmp_path / "mod.py").write_text(
        'FAULTS = None\n\ndef f():\n    FAULTS.fire("transport.send")\n'
    )
    fake_catalog = types.SimpleNamespace(
        FAULT_SITES={"transport.send": "", "ghost.site": ""},
        METRIC_NAMES={"ghost_metric_total": ""},
    )
    findings, _s, warnings = run_paths(
        [tmp_path], tmp_path, catalog=fake_catalog
    )
    assert not findings
    assert any("ghost.site" in w for w in warnings)
    assert any("ghost_metric_total" in w for w in warnings)


def test_stale_span_catalog_entry_warns(tmp_path):
    """SPAN_NAMES gets the same two-way discipline: a catalogued span no
    code emits is stale (DL006 already fails the unknown-emitted
    direction — see the dl006 fixture)."""
    (tmp_path / "mod.py").write_text(
        "tracing = None\n\ndef f():\n"
        '    with tracing.span("http.request"):\n        pass\n'
    )
    fake_catalog = types.SimpleNamespace(
        FAULT_SITES={},
        METRIC_NAMES={},
        SPAN_NAMES={"http.request": "", "ghost.span": ""},
    )
    findings, _s, warnings = run_paths(
        [tmp_path], tmp_path, catalog=fake_catalog
    )
    assert not findings
    assert any(
        "span 'ghost.span'" in w and "never emitted" in w for w in warnings
    )


# ----------------------------------------------- wire schema (DL007) contract


def _extracted_schema() -> dict:
    index = build_index(SCAN_SCOPE, REPO_ROOT)
    return wire.extract(index).to_canonical()


def test_wire_schema_matches_code_both_directions():
    """The committed protocol catalog IS the extracted one — drift in
    either direction (op added/removed/changed in code or hand-edited in
    the JSON) fails (same two-way contract as DL006)."""
    extracted = _extracted_schema()
    committed = json.loads(WIRE_SCHEMA.read_text())
    assert committed == extracted, (
        "wire_schema.json drifted from the code; review the protocol "
        "change, then: python -m tools.dynalint --update-wire-schema "
        "--emit-protocol\n"
        + "\n".join(
            m for _k, m in wire._diff_schema(committed, extracted)
        )
    )


def test_protocol_md_matches_schema():
    """docs/PROTOCOL.md is the rendered catalog; a stale doc fails."""
    committed = json.loads(WIRE_SCHEMA.read_text())
    assert PROTOCOL_MD.exists(), "docs/PROTOCOL.md missing: run " \
        "python -m tools.dynalint --emit-protocol"
    assert PROTOCOL_MD.read_text() == wire.render_protocol_md(committed), (
        "docs/PROTOCOL.md drifted: python -m tools.dynalint --emit-protocol"
    )


def test_wire_schema_covers_expected_channels():
    """The catalog documents all three conventions the repo actually
    speaks (sanity: extraction anchors are alive)."""
    committed = json.loads(WIRE_SCHEMA.read_text())
    assert set(committed["channels"]) == {
        "hub", "worker.admin", "disagg.transfer"
    }
    hub_ops = committed["channels"]["hub"]
    for op in ("put", "watch", "subscribe", "repl.status", "repl.sync"):
        assert op in hub_ops, f"hub op {op!r} missing from catalog"
    assert "clear_kv_blocks" in committed["channels"]["worker.admin"]
    err = committed["transport_err_codes"]
    assert set(err["emitted"]) == set(err["handled"]) == {
        "deadline", "unavailable", "over_quota", "stream"
    }
    frames = committed["stream_frames"]
    # every emitted frame kind has an rx dispatch; "req" is legacy-only
    # (handled for old clients, never sent by the compact-id client)
    assert set(frames["emitted"]) == {
        "open", "cancel", "data", "end", "err"
    }
    assert set(frames["handled"]) == set(frames["emitted"]) | {"req"}
    assert "req" in frames["notes"]
    # coalescing is part of the catalogued protocol, not an impl detail
    assert "payloads" in frames["emitted"]["data"]
    assert "ch" in frames["emitted"]["open"]


def test_missing_dispatcher_anchor_is_a_finding(tmp_path):
    """A refactor that moves/renames a dispatch function must fail loudly
    instead of silently extracting an empty server side."""
    target = tmp_path / "dynamo_tpu" / "runtime"
    target.mkdir(parents=True)
    # the anchored file exists but the qualname is gone
    (target / "hub_server.py").write_text(
        "class HubServer:\n    def _route(self, op):\n        return None\n"
    )
    findings, _s, _w = run_paths([tmp_path / "dynamo_tpu"], tmp_path)
    assert any(
        f.rule == "DL007" and "anchor" in f.detail for f in findings
    ), [f.render() for f in findings]


# ----------------------------------------------------------- mutation tests


def _scan_mutated(tmp_path, fixture: str, old: str, new: str):
    src = (FIXTURES / fixture).read_text()
    assert old in src, f"mutation target {old!r} not in {fixture}"
    # keep the dynalint/fixtures path marker so the copy gets the same
    # self-contained-channel treatment as the original
    fdir = tmp_path / "dynalint" / "fixtures"
    fdir.mkdir(parents=True, exist_ok=True)
    mutated = fdir / fixture
    mutated.write_text(src.replace(old, new))
    active, suppressed, _ = scan_file(mutated, tmp_path)
    return active, suppressed


def test_mutation_extra_client_field_is_caught(tmp_path):
    """Synthetic drift: a field added to a clean sender trips DL007."""
    active, _ = _scan_mutated(
        tmp_path, "dl007_wire_schema.py",
        'hub._call("lookup", key="a")\n\n\ndef typoed_op',
        'hub._call("lookup", key="a", epoch=1)\n\n\ndef typoed_op',
    )
    assert any(
        f.rule == "DL007" and "epoch" in f.detail for f in active
    ), [f.render() for f in active]


def test_mutation_renamed_server_op_is_caught(tmp_path):
    """Synthetic drift: renaming the server branch orphans every sender
    of the old op name."""
    active, _ = _scan_mutated(
        tmp_path, "dl007_wire_schema.py",
        'if op == "lookup":', 'if op == "lookup_v2":',
    )
    assert any(
        f.rule == "DL007" and f.detail == "op:hub:lookup" for f in active
    ), [f.render() for f in active]


def test_mutation_dropped_deadline_forward_is_caught(tmp_path):
    """Synthetic drift: deleting the context argument from a clean
    forwarding call trips DL008."""
    active, _ = _scan_mutated(
        tmp_path, "dl008_deadline.py",
        "self.engine.generate(request, context):\n"
        "            yield item\n\n    async def forwards_child",
        "self.engine.generate(request):\n"
        "            yield item\n\n    async def forwards_child",
    )
    assert any(
        f.rule == "DL008" and f.detail == "drop:Operator.forwards_is_clean:generate"
        for f in active
    ), [f.render() for f in active]


def test_mutation_dropped_wire_headers_is_caught(tmp_path):
    """Synthetic drift: a req frame that stops calling wire_headers()
    trips DL008's wire-send check."""
    active, _ = _scan_mutated(
        tmp_path, "dl008_deadline.py",
        '"headers": context.wire_headers(),', '"headers": {},',
    )
    assert sum(
        1 for f in active
        if f.rule == "DL008" and f.detail.startswith("req-headers")
    ) == 2, [f.render() for f in active]


# --------------------------------------------- interprocedural rule details


def test_dl008_serving_surface_root_context(tmp_path):
    """A deadline-less root Context() on a serving surface is flagged;
    one with deadline= is not (path-scoped: the same code outside the
    serving surfaces stays silent)."""
    code = (
        "import time\n"
        "Context = None\n"
        "def handler(request):\n"
        "    bad = Context(request_id='x')\n"
        "    good = Context(request_id='x', deadline=time.monotonic())\n"
        "    return bad, good\n"
    )
    surface = tmp_path / "dynamo_tpu" / "grpc"
    surface.mkdir(parents=True)
    (surface / "svc.py").write_text(code)
    elsewhere = tmp_path / "dynamo_tpu" / "runtime"
    elsewhere.mkdir(parents=True)
    (elsewhere / "svc.py").write_text(code)
    findings, _s, _w = run_paths([tmp_path / "dynamo_tpu"], tmp_path)
    flagged = [f for f in findings if f.rule == "DL008"]
    assert len(flagged) == 1, [f.render() for f in findings]
    assert flagged[0].path == "dynamo_tpu/grpc/svc.py"
    assert flagged[0].line == 4


def test_dl009_wire_taint_is_transitive_and_precise(tmp_path):
    """The call-graph pass: a helper that dials taints its callers, but
    a name shared with an un-tainted definition does NOT smear (the
    unanimity rule — queue.put must not look like RemoteHub.put)."""
    (tmp_path / "mod.py").write_text(
        "import asyncio\n"
        "class A:\n"
        "    async def dial(self):\n"
        "        await asyncio.open_connection('h', 1)\n"
        "    async def via(self):\n"
        "        await self.dial()\n"
        "    async def locked(self):\n"
        "        async with self.lock:\n"
        "            await self.via()\n"
        "class B:\n"
        "    async def put(self): ...\n"
        "class C:\n"
        "    async def put(self):\n"
        "        await asyncio.open_connection('h', 1)\n"
        "    async def locked(self, q):\n"
        "        async with self.lock:\n"
        "            await q.put(1)\n"  # ambiguous name: stays quiet
    )
    findings, _s, _w = run_paths([tmp_path], tmp_path)
    dl9 = [f for f in findings if f.rule == "DL009"]
    assert len(dl9) == 1 and dl9[0].context == "A.locked", (
        [f.render() for f in dl9]
    )


def test_dl008_unanimity_rule_no_name_smear(tmp_path):
    """A same-named callee that takes no context must block the
    bare-name match (same unanimity rule as the wire taint): an
    unrelated cache.put inside a request-path function stays silent."""
    (tmp_path / "mod.py").write_text(
        "class Store:\n"
        "    async def put(self, key, value, context): ...\n"
        "class Cache:\n"
        "    async def put(self, key, value): ...\n"
        "class Op:\n"
        "    async def run(self, request, context, cache):\n"
        "        await cache.put('k', request)\n"  # ambiguous: silent
    )
    findings, _s, _w = run_paths([tmp_path], tmp_path)
    assert not [f for f in findings if f.rule == "DL008"], (
        [f.render() for f in findings]
    )


def test_dl001_awaited_asyncio_acquire_not_flagged(tmp_path):
    """``await lock.acquire()`` is an asyncio lock (yields to the loop):
    DL009's business, never DL001's thread-block finding."""
    (tmp_path / "mod.py").write_text(
        "async def f(lock):\n"
        "    await lock.acquire()\n"
        "    lock.release()\n"
    )
    findings, _s, _w = run_paths([tmp_path], tmp_path)
    assert not [f for f in findings if f.rule == "DL001"], (
        [f.render() for f in findings]
    )


def test_dl007_unsent_server_op_warns_not_fails(tmp_path):
    """Handled-but-never-sent is the warn direction (dead surface), and
    TOOLING_OPS annotations silence it with a written reason."""
    fdir = tmp_path / "dynalint" / "fixtures"
    fdir.mkdir(parents=True)
    (fdir / "mod.py").write_text(
        (FIXTURES / "dl007_wire_schema.py").read_text()
    )
    # explicit file path (the dir-walk skips fixture dirs) + a dir so the
    # runner treats this as a project scan and emits cross-file warnings
    findings, _s, warnings = run_paths(
        [tmp_path, fdir / "mod.py"], tmp_path
    )
    assert any(
        "op 'evict'" in w and "nothing in scope sends" in w
        for w in warnings
    ), warnings
    assert not any(
        f.rule == "DL007" and "evict" in f.detail for f in findings
    )


def test_tooling_ops_all_have_reasons():
    for op, reason in wire.TOOLING_OPS.items():
        assert reason and len(reason) > 10, f"TOOLING_OPS[{op!r}] needs a reason"


# ------------------------------------------------------------ CLI modes


def test_cli_github_format():
    from tools.dynalint.cli import render_github
    from tools.dynalint.core import Finding

    f = Finding(rule="DL007", path="a/b.py", line=3, col=4,
                message="op 'x' is sent but unhandled", hint="fix it")
    line = render_github(f)
    assert line.startswith("::error file=a/b.py,line=3,col=5,")
    assert "title=dynalint DL007" in line
    assert "fix it" in line


def test_cli_changed_only_withholds_untouched_files(monkeypatch, capsys):
    """--changed-only: full-scope scan, report filtered to git-dirty
    files — per-file findings in untouched files are withheld, but
    project-level DL007 findings always report (they're attributed to
    the OTHER side of the drift, which may not be the edited file)."""
    from tools.dynalint import cli as cli_mod

    monkeypatch.setattr(
        cli_mod, "changed_files", lambda root, scope=(): set()
    )
    # per-file rule findings (DL001 fixture) in an "untouched" file: withheld
    rc = cli_mod.main([
        "tools/dynalint/fixtures/dl001_blocking.py",
        "--no-baseline", "--changed-only", "--no-external",
    ])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err
    assert "withheld" in out.err
    # cross-file DL007 findings bypass the dirty-path filter entirely
    rc = cli_mod.main([
        "tools/dynalint/fixtures/dl007_wire_schema.py",
        "--no-baseline", "--changed-only", "--no-external",
    ])
    out = capsys.readouterr()
    assert rc == 1, "cross-file DL007 findings must not be withheld"
    assert "DL007" in out.out
    monkeypatch.setattr(
        cli_mod, "changed_files",
        lambda root, scope=(): {"tools/dynalint/fixtures/dl001_blocking.py"},
    )
    rc = cli_mod.main([
        "tools/dynalint/fixtures/dl001_blocking.py",
        "--no-baseline", "--changed-only", "--no-external",
    ])
    assert rc == 1  # the fixture's findings are in a "changed" file now


def test_cli_emit_protocol_roundtrip(tmp_path):
    """--emit-protocol writes the rendered catalog; output equals the
    in-process renderer over the committed schema."""
    from tools.dynalint import cli as cli_mod

    out = tmp_path / "PROTO.md"
    rc = cli_mod.main(["--emit-protocol", str(out), "--no-external"])
    assert rc == 0
    committed = json.loads(WIRE_SCHEMA.read_text())
    assert out.read_text() == wire.render_protocol_md(committed)


# -------------------------------------------------------- entry point + spawn


# ------------------------------------------------------- v3 JAX layer


def test_jit_registry_contract():
    """The core.py jit registry: jit assigns and @partial decorators are
    extracted with their donation/static declarations, shard_map sites
    carry their specs, and the hot closure is rooted at the engine step
    thread."""
    index = build_index(SCAN_SCOPE, REPO_ROOT)
    jits = index.jits
    pf = jits[("dynamo_tpu/models/llama.py", "prefill_forward")]
    assert pf.donate_argnums == (5, 6)
    assert pf.static_argnums == (0,)
    assert pf.static_argnames == ("mesh",)
    assert pf.wrapped_fn is not None
    assert pf.wrapped_fn.qualname == "prefill_forward_impl"
    fda = jits[
        ("dynamo_tpu/ops/pallas/fused_decode.py", "fused_decode_attention")
    ]
    assert fda.donate_argnums == (1, 2)
    assert fda.static_argnames == (
        "interpret", "window", "window_pages_override", "scope")
    assert any(
        sm.path == "dynamo_tpu/ops/attention.py" for sm in index.shard_maps
    ), "attention.py shard_map sites missing from the registry"
    assert (
        "dynamo_tpu/engine/core.py", "InferenceEngine._thread_loop"
    ) in index.hot, "the step thread itself must be hot"
    # the closure must not leak through stdlib method names: bytes.encode
    # in a hot sink must not drag the ViT encoder in
    assert (
        "dynamo_tpu/multimodal/vit.py", "VitEncoder.encode"
    ) not in index.hot


def test_baseline_regen_determinism(tmp_path):
    """Two consecutive --update-baseline runs over the same tree produce
    byte-identical baselines (sorted entries, stable fingerprints) —
    baseline churn in review means the tool, not the code, changed."""
    from tools.dynalint import cli as cli_mod

    target = FIXTURES / "dl003_swallowed.py"
    outs = []
    for name in ("a.json", "b.json"):
        path = tmp_path / name
        cli_mod.main([
            str(target), "--baseline", str(path),
            "--update-baseline", "--no-external",
        ])
        outs.append(path.read_bytes())
    assert outs[0] == outs[1], "baseline regen is not deterministic"


def test_cli_sarif_format(capsys):
    """--format=sarif emits one SARIF 2.1.0 document: full rule catalog,
    results with physical locations and the line-independent fingerprint
    (so code-scanning alerts track across rebases like the baseline)."""
    from tools.dynalint import cli as cli_mod

    rc = cli_mod.main([
        "tools/dynalint/fixtures/dl014_silent_fallback.py",
        "--no-baseline", "--no-external", "--format=sarif",
    ])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "dynalint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"DL010", "DL011", "DL012", "DL013", "DL014",
            "DL015"} <= rule_ids
    results = run["results"]
    assert results and all(r["ruleId"] == "DL014" for r in results)
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith(
        "dl014_silent_fallback.py"
    )
    assert loc["region"]["startLine"] > 0
    assert results[0]["partialFingerprints"]["dynalintFingerprint/v1"]


def test_changed_files_respects_scan_scope(tmp_path):
    """--changed-only scoping: a dirty file OUTSIDE the scan scope (e.g.
    deploy/) must not count as a change — the report should read 'no
    scanned file changed', not silently withhold real findings behind an
    unrelated dirty path."""
    from tools.dynalint import cli as cli_mod

    repo = tmp_path / "repo"
    (repo / "pkg").mkdir(parents=True)
    (repo / "deploy").mkdir()
    def git(*argv):
        return subprocess.run(
            ["git", *argv], cwd=repo, capture_output=True, text=True,
            timeout=30,
        )
    if git("init").returncode != 0:
        pytest.skip("git unavailable")
    (repo / "pkg" / "mod.py").write_text("x = 1\n")
    (repo / "deploy" / "values.yaml").write_text("a: 1\n")
    git("add", "-A")
    git("-c", "user.email=t@t", "-c", "user.name=t", "commit", "-m", "x")
    (repo / "deploy" / "values.yaml").write_text("a: 2\n")  # dirty, off-scope
    scoped = cli_mod.changed_files(repo, (repo / "pkg",))
    assert scoped == set(), f"off-scope dirt leaked in: {scoped}"
    unscoped = cli_mod.changed_files(repo)
    assert "deploy/values.yaml" in (unscoped or set())
    (repo / "pkg" / "mod.py").write_text("x = 2\n")  # dirty, in-scope
    scoped = cli_mod.changed_files(repo, (repo / "pkg",))
    assert scoped == {"pkg/mod.py"}


def test_no_hotpath_baseline_entries():
    """Acceptance: DL010/DL014/DL015 findings in engine/ and ops/ are
    FIXED (or carry a reasoned suppression at the site), never
    grandfathered into the baseline."""
    base = json.loads(BASELINE.read_text())
    offenders = [
        e for e in base.get("findings", [])
        if e["rule"] in ("DL010", "DL014", "DL015")
        and (e["path"].startswith("dynamo_tpu/engine/")
             or e["path"].startswith("dynamo_tpu/ops/"))
    ]
    assert not offenders, offenders


def test_fallback_note_counts_and_warns_once(caplog):
    """The DL014 remedy: note_fallback bumps
    dynamo_fused_fallback_total{reason} every time and logs each reason
    exactly once (warning by default, debug when expected=True)."""
    from dynamo_tpu.ops import fallback as fb

    assert "fused_fallback_total" in catalog.METRIC_NAMES
    fb.reset_seen()
    ctr = fb._FALLBACKS.labels("quant_tp_shardmap")
    before = ctr._value.get()
    with caplog.at_level("DEBUG", logger="dynamo.ops.fallback"):
        fb.note_fallback("quant_tp_shardmap", detail="test")
        fb.note_fallback("quant_tp_shardmap", detail="test")
        fb.note_fallback("no_pallas_backend", expected=True)
    assert ctr._value.get() == before + 2
    warned = [r for r in caplog.records
              if "quant_tp_shardmap" in r.message]
    assert len(warned) == 1 and warned[0].levelname == "WARNING"
    expected = [r for r in caplog.records
                if "no_pallas_backend" in r.message]
    assert len(expected) == 1 and expected[0].levelname == "DEBUG"


def test_quant_tp_fallback_emits_metric_and_is_not_silent():
    """ROADMAP #7 end to end: decode_update_attention with an fp8 pool
    under a tp>1 mesh takes the XLA path AND accounts for it — the
    counter moves; the result stays numerically sane."""
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices (XLA_FLAGS host platform count)")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from dynamo_tpu.ops import fallback as fb
    from dynamo_tpu.ops import quant
    from dynamo_tpu.ops.attention import decode_update_attention

    fb.reset_seen()
    ctr = fb._FALLBACKS.labels("quant_tp_shardmap")
    before = ctr._value.get()
    B, H, KH, D, page = 2, 4, 2, 8, 4
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    rng = np.random.default_rng(0)

    def mk_pool():
        vals = jnp.asarray(
            0.1 * rng.standard_normal((1, 6, KH, page, D)), jnp.float32
        )
        return quant.QuantPool(
            vals.astype(quant.FP8_DTYPE),
            jnp.ones((1, 6, KH), quant.SCALE_DTYPE),
        )

    k_pages = mk_pool()
    v_pages = mk_pool()
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((B, KH, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, KH, D)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    attn, k_pages, v_pages = decode_update_attention(
        q, k_pages, v_pages, k_new, v_new, bt,
        jnp.asarray([3, 5], jnp.int32),
        jnp.asarray([1, 2], jnp.int32), jnp.asarray([2, 0], jnp.int32),
        layer=0, mesh=mesh,
    )
    assert attn.shape == (B, H, D)
    assert not bool(jnp.any(jnp.isnan(attn)))
    assert ctr._value.get() > before, (
        "fp8 + tp>1 took the XLA path without counting itself"
    )


def test_cli_entry_point_exits_zero():
    """``python -m tools.dynalint`` is the single CI entry point; it must
    pass against the committed baseline (externals skipped gracefully
    when not installed)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynalint"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_spawn_keeps_strong_ref_and_logs_crashes(caplog):
    """The DL002 remedy: spawn() holds the task strongly and surfaces
    unexpected exceptions through the 'dynamo.tasks' logger."""
    from dynamo_tpu.runtime import context as ctx_mod

    async def scenario():
        async def boom():
            raise RuntimeError("kaput")

        async def fine():
            return 42

        t1 = ctx_mod.spawn(boom(), name="boom-task")
        t2 = ctx_mod.spawn(fine(), name="fine-task")
        assert t1 in ctx_mod._BACKGROUND_TASKS
        assert t2 in ctx_mod._BACKGROUND_TASKS
        await asyncio.gather(t1, t2, return_exceptions=True)
        await asyncio.sleep(0)  # let done-callbacks run
        assert t1 not in ctx_mod._BACKGROUND_TASKS
        assert t2 not in ctx_mod._BACKGROUND_TASKS

    with caplog.at_level("ERROR", logger="dynamo.tasks"):
        asyncio.run(scenario())
    crashes = [r for r in caplog.records if "boom-task" in r.message
               or "kaput" in str(r.args)]
    assert crashes, "crashed background task was not logged"
    assert not any("fine-task" in str(r.args) for r in caplog.records)
