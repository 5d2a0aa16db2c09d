"""What every layer family's programs and engine keep, written ONCE. A
family's file (``tests/test_<family>.py``) states its row (``Family``: the
toy spec, the keys its plain reference reads, sizes, tolerances, paths,
what is its own behind a case) and runs ``cases(row)`` through one
parametrised test, as ``tests/kda_step_cases.py`` is used. A field of the
row is what three families or more set; a case two families run
(``two_slots``, ``behind_bursts``, ``preempt``, ``gates``) is a function
here that their files name (``cases(row, case(...))``).

What is read-only is built once a family a worker: the reference module,
weights, tokens and the reference's logits (``_model``), the jitted
programs (``_programs``). An engine is built a case (an async test has an
event loop of its own) over ONE shape of ``EngineConfig`` a family, so the
model modules' own jits compile once a worker too. ``tests/conftest.py``
runs the families' files first, each in one run of consecutive tests, and
drops what a worker compiled behind a file.
"""

import asyncio
import dataclasses
import functools
import importlib.util
import inspect
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.family import GqaFamily, get_family
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.runtime.context import PRIORITY_HEADER, Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNKS = {"one-shot": [(0, 37)], "three-chunks": [(0, 16), (16, 16), (32, 5)]}
# (table row, start, tokens) a member: an empty member beside a prompt, two
# prompts of different lengths, two RESUMED chunks
PACKS = ([(0, 0, 13), (0, 0, 0)], [(1, 0, 16), (2, 0, 8)],
         [(1, 16, 9), (2, 8, 16)])
PATHS = {None: "default", "0": "xla", "1": "kernel"}
PROMPT = tuple(int(t) for t in np.arange(7, 7 + 21) % 96)  # two chunks of 16


@dataclasses.dataclass(eq=False)
class Family:
    """A family's row. A path is a value of ``DYNAMO_PALLAS`` (None: the
    machine's choice, the XLA twin on the CPU); ``also``: case -> what the
    family alone does behind it (``pack`` the first pack, ``packed`` the last)."""

    spec: ModelSpec
    config: dict  # the published keys the plain reference reads
    reference: str  # perfbench/references/<reference>.py
    seed: int = 11
    tol: float = 3e-4  # logits against the reference
    rtol: float | None = None  # None: ``tol`` again
    pool_tol: float = 1e-5  # bursts: the caches of the two ways
    pack_tol: float | None = None  # a packed row's state against a single's
    page: int = 4
    pages_per_seq: int = 16
    tokens: int = 40
    seqs: int = 3
    state_rows: int = 0  # > 0: the family is recurrent
    prompts: tuple = ((21, "0"), (21, "1"))  # prefill-decode: (tokens, path)
    chunked: dict = dataclasses.field(default_factory=lambda: dict(CHUNKS))
    chunked_paths: tuple = (None,)
    packs: tuple = PACKS
    pack_path: str | None = None
    bursts_paths: tuple = ("1",)  # (): the family's file had no such case
    inactive_paths: tuple = ()
    engine: dict = dataclasses.field(default_factory=dict)
    # ONE path for the engine cases: ``models/``'s own jits find a program
    # by spec and shapes, whatever path traced it
    engine_path: str | None = "1"
    served: tuple = ((PROMPT, 6), (PROMPT, 6))  # (prompt, tokens asked)
    streams: tuple = ()  # pipeline_decode of each streams case
    also: dict[str, Callable] = dataclasses.field(default_factory=dict)

    def close(self, got, want, tol=None):
        _close(got, want, self.tol if tol is None else tol, self.rtol)

    def own(self, case, *args):
        """What the family alone asserts behind ``case``, if anything."""
        if case in self.also:
            self.also[case](*args)


def _close(got, want, tol=3e-4, rtol=None):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol if rtol is None else rtol, atol=tol)


@functools.cache
def _reference(fam):
    """The benchmark's own plain reference, loaded by path: not a copy."""
    spec = importlib.util.spec_from_file_location(
        fam.reference,
        os.path.join(REPO, f"perfbench/references/{fam.reference}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def tokens(seqs=3, n=40):
    """The token matrix every family's cases cut their prompts from."""
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (seqs, n), 0, 96))


@functools.cache
def _model(fam):
    """(weights, tokens [seqs, T], the plain reference's logits of them)."""
    params = get_family(fam.spec).init_params(
        fam.spec, jax.random.PRNGKey(fam.seed))
    toks = tokens(fam.seqs, fam.tokens)
    want = np.asarray(_reference(fam).forward(
        fam.config, fam.seed, toks,
        np.tile(np.arange(fam.tokens), (fam.seqs, 1))))
    return params, toks, want


def _cache(fam, rows=None, spec=None, page=None):
    """The pair the engine holds (``models/family.py``): K and V sides, or
    a latent family's pools and its experts' counters. Three tables."""
    spec, page = spec or fam.spec, page or fam.page
    rows = fam.state_rows if rows is None else rows
    return get_family(spec).init_cache(
        spec, 1 + 3 * fam.pages_per_seq * (fam.page // page), page,
        **({"state_rows": rows} if rows else {}))


def _table(fam, row, page=None):
    n = fam.pages_per_seq * (fam.page // (page or fam.page))
    return jnp.arange(n, dtype=jnp.int32) + 1 + row * n


def _tables(fam, rows):
    """A slot a member of ``rows``; None: an empty slot (the trash page's)."""
    return jnp.stack([
        jnp.zeros((fam.pages_per_seq,), jnp.int32) if r is None
        else _table(fam, r) for r in rows])


def _path(monkeypatch, path):
    if path is not None:
        monkeypatch.setenv("DYNAMO_PALLAS", path)


def _as_pair(fn):
    """A program of ``models/mla.py`` in the pair's signature."""
    def program(spec, params, tokens, tables, at, k, v, *rest, **kw):
        return fn(spec, params, tokens, tables, at, k, *rest, counts=v, **kw)
    return program


@functools.cache
def _traced(latent, path):
    """(prefill, packed prefill, decode step, decode burst) of a model
    module in the pair's signature ``(spec, params, tokens, tables, at, k,
    v, ...)``. With ``DYNAMO_PALLAS`` unset: the module's OWN jits, which
    the engine calls and which donate the cache. Under a named path: a set
    of the contract's own, nothing donated (``path`` is the key and nothing
    else: the variable is read at TRACE time, and ``jax.jit`` finds a
    function it has traced by identity)."""
    m = mla if latent else llama
    wrap = _as_pair if latent else (lambda fn: lambda *a, **kw: fn(*a, **kw))
    if path is None:
        return tuple(wrap(fn) for fn in (
            m.prefill_forward, m.prefill_forward_batch, m.decode_forward,
            m.decode_steps))
    return tuple(
        jax.jit(wrap(fn), static_argnums=(0,),
                static_argnames=("n_steps", "n_logprobs"))
        for fn in (m.prefill_forward_impl, m.prefill_forward_batch_impl,
                   m.decode_forward_impl, m.decode_steps_impl))


def _programs(fam):
    return _traced(fam.spec.is_mla, os.environ.get("DYNAMO_PALLAS"))


def _prefill(fam, pf, params, toks, row, start, n, k, v, bucket=16,
             spec=None, page=None):
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = toks[row, start: start + n]
    logits, k, v, *drops = pf(
        spec or fam.spec, params, jnp.asarray(padded), _table(fam, row, page),
        jnp.asarray(start, jnp.int32), k, v, jnp.asarray(n, jnp.int32))
    assert not drops or int(drops[0]) == 0  # no expert dropped a token
    return logits, k, v


def _step(fam, df, params, toks, at, k, v, spec=None):
    """One teacher-forced decode step of three slots: ``at`` maps a slot to
    (table row, tokens in the pool); the others are empty or inactive."""
    fed, seq = np.zeros((3,), np.int32), np.ones((3,), np.int32)
    for s, (row, n) in at.items():
        fed[s], seq[s] = toks[row, n], n + 1
    return df(
        spec or fam.spec, params, jnp.asarray(fed),
        _tables(fam, [at[s][0] if s in at else None for s in range(3)]),
        jnp.asarray(seq), k, v, jnp.asarray([s in at for s in range(3)]))


def _pools(fam, k, v):
    """The leaves of the cache that hold a sequence's pages, state or
    tails (not the directory, not the counters)."""
    return jax.tree.leaves(k if fam.spec.is_mla else (k.pools, v.pools))


def _is_state(fam, leaf):
    """A leaf ``[layers, state rows + the trash row, ...]``, not of pages."""
    return bool(fam.state_rows) and leaf.shape[1] == fam.state_rows + 1


def _live(fam, leaf, rows):
    """What the sequences on table rows ``rows`` own of a leaf: their pages
    or their state rows (claimed in order), not the trash's, not the free."""
    if _is_state(fam, leaf):
        return np.asarray(leaf)[:, list(rows)]
    return np.asarray(leaf)[:, np.concatenate(
        [np.asarray(_table(fam, r)) for r in rows])]


def _stats(fam, k):
    return np.asarray(k.rows.stats[0]) if fam.state_rows else None


def prefill_then_decode(fam, monkeypatch, n=21, path=None, spec=None,
                        model=None, held=None, steps=6):
    """A prompt through the prefill program, then teacher-forced decode
    steps through every kind's pages, state rows and tails (slot 2 of
    three; the others empty or inactive): every position's logits are the
    reference's whole forward pass."""
    _path(monkeypatch, path)
    params, toks, want = model or _model(fam)
    held = held or (lambda got, at, what: fam.close(got, want[1, at]))
    pf, _, df, _ = _programs(fam)
    k, v = _cache(fam, spec=spec)
    logits, k, v = _prefill(
        fam, pf, params, toks, 1, 0, n, k, v, bucket=32, spec=spec)
    held(logits, n - 1, "prefill")
    for j in range(steps):
        lg, k, v = _step(fam, df, params, toks, {2: (1, n + j)}, k, v, spec)
        held(lg[2], n + j, f"decode step {j}")
    if fam.state_rows:
        stats = _stats(fam, k)
        assert stats[llama.STAT_CLAIMS] == 1 and stats[llama.STAT_MISSING] == 0
    # the environment still names the path that keyed ``_programs``' jits
    assert path is None or attn_ops.use_pallas() == (path == "1")
    fam.own("prefill-decode", k, v, n, steps)


def chunked(fam, monkeypatch, chunks, path=None, page=None):
    """Chunks at ``start_pos`` > 0 resume what the chunk before left
    (pages, latents, state, tails): every chunk ends at the reference's
    logits, the last at the one-shot prefill's."""
    _path(monkeypatch, path)
    params, toks, want = _model(fam)
    pf = _programs(fam)[0]
    k, v = _cache(fam, page=page)
    for start, n in chunks:
        logits, k, v = _prefill(
            fam, pf, params, toks, 0, start, n, k, v,
            bucket=64 if n > 16 else 16, page=page)
        fam.close(logits, want[0, start + n - 1])
    if fam.state_rows:
        stats = _stats(fam, k)
        assert stats[llama.STAT_CLAIMS] == 1 and stats[llama.STAT_MISSING] == 0
    fam.own("chunked", k, v, chunks)


def pack(fam, monkeypatch):
    """Packed rows (``fam.packs``), among them an empty member: each row's
    logits are the reference's; the empty member claims no state and
    writes no page; the state a packed row left is a single prefill's."""
    _path(monkeypatch, fam.pack_path)
    params, toks, want = _model(fam)
    pf, pb, _, _ = _programs(fam)
    k, v = _cache(fam)
    claimed = []
    bucket = 16 if max(n for p in fam.packs for _, _, n in p) <= 16 else 32
    for i, members in enumerate(fam.packs):
        M = len(members)
        padded = np.zeros((M, bucket), np.int32)
        rows, starts, lens = [], np.zeros(M, np.int32), np.zeros(M, np.int32)
        for j, (row, start, n) in enumerate(members):
            padded[j, :n] = toks[row, start: start + n]
            rows.append(row if n else None)
            if n:
                starts[j], lens[j] = start, n
                claimed += [row] * (start == 0)
        logits, k, v = pb(
            fam.spec, params, jnp.asarray(padded), _tables(fam, rows),
            jnp.asarray(starts), k, v, jnp.asarray(lens))[:3]
        assert np.isfinite(np.asarray(logits)).all()
        for j, (row, start, n) in enumerate(members):
            if n:
                fam.close(logits[j], want[row, start + n - 1])
        if i == 0:
            _after_first_pack(fam, pf, params, toks, members, k, v)
    if fam.state_rows:
        owner = np.asarray(k.rows.owner[0])
        assert sorted(owner[:fam.state_rows]) == sorted(
            [int(_table(fam, r)[0]) for r in claimed]
            + [0] * (fam.state_rows - len(claimed)))
        assert owner[fam.state_rows] == 0  # the trash row is nobody's
        stats = _stats(fam, k)
        assert stats[llama.STAT_CLAIMS] == len(claimed)
        assert stats[llama.STAT_MISSING] == 0
    fam.own("packed", k, v, members)


def _after_first_pack(fam, pf, params, toks, members, k, v):
    """No page but the live members' own moved, and (``pack_tol``) member
    0's state and tails are a single prefill's in a wider bucket: neither
    the neighbour nor the padding reaches them."""
    live = sorted({row for row, _, n in members if n})
    idle = [r for r in range(3) if r not in live]
    for leaf in _pools(fam, k, v):
        if not _is_state(fam, leaf) and idle:
            assert not _live(fam, leaf, idle).any()
    fam.own("pack", k, v)
    if fam.pack_tol is None:
        return
    row, start, n = members[0]
    k1, v1 = _cache(fam)
    _, k1, v1 = _prefill(
        fam, pf, params, toks, row, start, n, k1, v1, bucket=32)
    at = int(np.argmax(
        np.asarray(k.rows.owner[0]) == int(_table(fam, row)[0])))
    for a, b in zip(_pools(fam, k, v), _pools(fam, k1, v1)):
        if _is_state(fam, a):
            _close(np.asarray(a)[:, at], np.asarray(b)[:, 0], fam.pack_tol)


def two_slots(fam, monkeypatch, path, steps=12):
    """Teacher-forced steps after prefills of 14 and 1 tokens: across page
    boundaries, a slot that starts from ONE token in the pool, an empty
    slot that stays inactive and is counted nowhere (``also["two-slots"]``:
    how the experts' counters grew)."""
    _path(monkeypatch, path)
    params, toks, want = _model(fam)
    pf, _, df, _ = _programs(fam)
    k, v = _cache(fam)
    for row, n in enumerate((14, 1)):
        _, k, v = _prefill(fam, pf, params, toks, row, 0, n, k, v)
    before = np.asarray(v)
    for j in range(steps):
        lg, k, v = _step(
            fam, df, params, toks, {0: (0, 14 + j), 1: (1, 1 + j)}, k, v)
        fam.close(lg[0], want[0, 14 + j])
        fam.close(lg[1], want[1, 1 + j])
    fam.own("two-slots", np.asarray(v) - before, steps)


def bursts(fam, monkeypatch, path):
    """Eight greedy steps as one burst and as eight bursts of one behind
    two prefills, beside an inactive slot: the same tokens, the plain
    reference's choices at every position decoded, and the same cache LEAF
    BY LEAF on the live rows (the burst finds its rows once; state, tails
    and the latent schedule carry between steps)."""
    _path(monkeypatch, path)
    params, toks, want = _model(fam)
    pf, _, _, ds = _programs(fam)
    B, lens = 3, (9, 14)
    tables = _tables(fam, [0, 1, None])
    active = jnp.asarray([True, True, False])
    z = jnp.zeros((B,), jnp.int32)

    def run(sizes):
        k, v = _cache(fam)
        for row, n in enumerate(lens):
            _, k, v = _prefill(fam, pf, params, toks, row, 0, n, k, v)
        fed = np.array([toks[0, lens[0]], toks[1, lens[1]], 0], np.int32)
        seq = np.array([lens[0] + 1, lens[1] + 1, 1], np.int32)
        out = []
        for n_steps in sizes:
            o, k, v = ds(
                fam.spec, params, jnp.asarray(fed), tables, jnp.asarray(seq),
                k, v, active, jnp.zeros((B,)), z, jnp.ones((B,)),
                jnp.zeros((B,), jnp.uint32), z, n_steps=n_steps,
                n_logprobs=0)[:3]
            o = np.asarray(o)
            out.append(o[:2])
            fed[:2], seq[:2] = o[:2, -1], seq[:2] + n_steps
        return np.concatenate(out, axis=1), k, v

    one, k1, v1 = run([1] * 8)
    eight, k8, v8 = run([8])
    np.testing.assert_array_equal(one, eight)
    for a, b in zip(_pools(fam, k8, v8), _pools(fam, k1, v1)):
        _close(_live(fam, a, (0, 1)), _live(fam, b, (0, 1)), fam.pool_tol)
    if fam.state_rows:
        assert _stats(fam, k8)[llama.STAT_MISSING] == 0
    fam.own("bursts", k8, v8, 8)
    # the plain reference over prompt + fed token + what was decoded
    seqs = np.zeros((2, fam.tokens), np.int32)
    at = np.zeros((2, 8), np.int32)
    for r, n in enumerate(lens):
        seqs[r, : n + 1] = toks[r, : n + 1]
        seqs[r, n + 1: n + 9] = eight[r]
        at[r] = n + np.arange(8)
    chosen = np.asarray(_reference(fam).forward(
        fam.config, fam.seed, seqs, at)).argmax(axis=-1)
    np.testing.assert_array_equal(eight, chosen)


def inactive(fam, monkeypatch, path):
    """A decode step with one live slot: the other sequence's pages (its
    slot inactive) and, of a recurrent family, its state row and tail and
    those of a row whose owner was released stay as they were TO THE BIT,
    every leaf; the live slot's own move."""
    _path(monkeypatch, path)
    params, toks, _ = _model(fam)
    pf, _, df, _ = _programs(fam)
    k, v = _cache(fam)
    lens = (9, 14, 11)
    for row, n in enumerate(lens):
        _, k, v = _prefill(fam, pf, params, toks, row, 0, n, k, v)
    if fam.state_rows:
        k = llama.release_state_rows(k, jnp.asarray(
            [int(_table(fam, 2)[0]), -1], jnp.int32))
        assert list(np.asarray(k.rows.owner[0])) == [
            1, 1 + fam.pages_per_seq, 0, 0]
    before = [np.asarray(leaf) for leaf in _pools(fam, k, v)]
    _, k, v = df(
        fam.spec, params, jnp.asarray(toks[:, 20]), _tables(fam, [0, 1, 2]),
        jnp.asarray([n + 1 for n in lens], jnp.int32), k, v,
        jnp.asarray([True, False, False]))
    moved = []
    for now, was in zip(_pools(fam, k, v), before):
        np.testing.assert_array_equal(
            _live(fam, now, (1, 2)), _live(fam, was, (1, 2)))
        moved.append(not np.array_equal(
            _live(fam, now, (0,)), _live(fam, was, (0,))))
        # a recurrent kind's state and tail move at every step
        assert moved[-1] or not _is_state(fam, now)
    assert any(moved)
    if fam.state_rows:
        assert _stats(fam, k)[llama.STAT_MISSING] == 0


def _engine(fam, kvbm=None, **kw):
    base = dict(
        page_size=fam.page, num_pages=64, max_pages_per_seq=fam.pages_per_seq,
        max_decode_slots=2, prefill_buckets=(16,), max_prefill_chunk_tokens=16,
        decode_steps_per_dispatch=4, seed=fam.seed)
    base.update(fam.engine)
    base.update(kw)
    return InferenceEngine(fam.spec, EngineConfig(**base), kvbm=kvbm)


async def _greedy(engine, prompt, n, out=None, ctx=None):
    out = [] if out is None else out
    async for item in engine.generate(
        {"token_ids": [int(t) for t in prompt],
         "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": n, "ignore_eos": True}},
        ctx or Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    return out


_WHOLE = {False: jax.jit(llama.reference_forward, static_argnums=0),
          True: jax.jit(mla.reference_forward, static_argnums=0)}


def _whole(spec, params, tokens):
    """``reference_forward`` as ONE program a spec a length, not op by op."""
    return _WHOLE[spec.is_mla](spec, params, jnp.asarray(tokens))


def _greedy_reference(fam, params, prompt, n, spec=None):
    """``n`` greedy tokens of the whole-sequence pass, a token a pass."""
    spec = spec or fam.spec
    seq = [int(t) for t in prompt]
    for _ in range(n):
        padded = np.zeros((64,), np.int32)
        padded[: len(seq)] = seq
        lg = _whole(spec, params, padded)
        seq.append(int(np.argmax(np.asarray(lg[len(seq) - 1]))))
    return seq[len(prompt):]


def _fallbacks(*reasons):
    from dynamo_tpu.ops import fallback

    return [fallback._FALLBACKS.labels(r)._value.get() for r in reasons]


RECURRENT_GATES = ("ring_prefill", "spec_decode", "mesh", "prefix_reuse",
                   "page_transfer", "multimodal")


def serves(fam, monkeypatch):
    """The toy model through the REAL engine on the schedule that serves
    (scheduler, chunked prompts, pipelined bursts; ``engine_path``'s
    kernels interpreted), ``fam.served``'s prompts in turn: each greedy
    stream is the whole forward pass's own; pages and rows go back; a
    recurrent family reuses nothing under a prefix. ``also["serves"]``
    (the engine, its snapshot, what was served, the streams): its counters."""
    _path(monkeypatch, fam.engine_path)
    engine = _engine(fam, pipeline_decode=True)
    # ahead of the event loop, whose 60 s are the engine's: the whole pass
    return _serves(fam, engine, [
        _greedy_reference(fam, engine.params, p, n) for p, n in fam.served])


async def _serves(fam, engine, wants):
    rec = engine.fam
    assert rec.recurrent == bool(fam.state_rows)
    outs = [await _greedy(engine, prompt, n) for prompt, n in fam.served]
    assert outs == wants
    prompt = [int(t) for t in fam.served[0][0]]
    assert engine.allocator.active_pages == 0
    if rec.recurrent:
        # no page is reused under a prefix: it holds no state
        assert isinstance(rec, GqaFamily) and not engine.allocator.prefix_cache
        assert not any(getattr(rec, f"supports_{g}") for g in RECURRENT_GATES)
        assert engine.allocator._hash_page == {}
        assert engine.allocator.evictable_pages == 0
        assert engine.prefix_hit_tokens(prompt) == 0
    await engine.close()
    engine._metrics_publishes = 0
    for _ in range(34):  # two refreshes bring the device's counters over
        engine._publish_metrics()
    if rec.recurrent:
        assert engine.state_counters() == {
            "rows": engine.config.max_decode_slots, "rows_live": 0,
            "claims": len(fam.served), "row_missing": 0}
        engine._flush_state_releases()
        assert not np.asarray(engine.k_pages.rows.owner[0]).any()
    fam.own("serves", engine, engine.profile_snapshot(), fam.served, outs)


async def streams(fam, monkeypatch, pipeline):
    """Three prompts on two slots, one of them chunked behind running
    bursts: every stream is what it gets alone, pipelined or not; pages
    and rows are claimed and freed as slots turn over, none goes
    missing."""
    _path(monkeypatch, fam.engine_path)
    prompts = [[3, 9, 27], [8, 64, 32, 5],
               [int(t) for t in np.arange(5, 5 + 37) * 7 % 96]]
    engine = _engine(fam, pipeline_decode=pipeline, async_admissions=True)
    want = [_greedy_reference(fam, engine.params, p, n)
            for p, n in zip(prompts, (12, 9, 6))]
    outs = await asyncio.gather(*(
        _greedy(engine, p, n) for p, n in zip(prompts, (12, 9, 6))))
    assert outs == want
    assert engine.allocator.active_pages == 0
    await engine.close()
    if fam.state_rows:
        stats = _stats(fam, engine.k_pages)
        assert stats[llama.STAT_MISSING] == 0 and stats[llama.STAT_CLAIMS] == 3


async def behind_bursts(fam, monkeypatch, chunk, n, busy):
    """Chunked under load: two streams decode in pipelined bursts while a
    prompt of ``n`` tokens prefills in chunks of ``chunk``, each launched
    behind the burst in flight (no flush lands it first); the first token
    is the reference's, all six those the prompt gets alone, unchunked."""
    _path(monkeypatch, fam.engine_path)
    _, toks, want = _model(fam)
    prompt = toks[0, :n]
    alone = _engine(fam, max_decode_slots=3, prefill_buckets=(64,),
                    max_prefill_chunk_tokens=64)
    unchunked = await _greedy(alone, prompt, 6)
    assert unchunked[0] == int(want[0, n - 1].argmax())
    assert alone.chunked_prefill["chunks"] == 0
    await alone.close()

    engine = _engine(fam, max_decode_slots=3, prefill_buckets=(chunk,),
                     max_prefill_chunk_tokens=chunk, pipeline_decode=True)
    chunks, run_chunk = [], engine._run_partial_chunk

    def watched(waiting, sp, token_ids, start, end):
        chunks.append((start, len(engine._pipeline)))
        return run_chunk(waiting, sp, token_ids, start, end)

    engine._run_partial_chunk = watched
    a, b = [], []

    async def later():
        while min(len(a), len(b)) < 4:
            await asyncio.sleep(0.002)
        return await _greedy(engine, prompt, 6)

    outs = await asyncio.gather(
        _greedy(engine, [3, 9, 27], busy, out=a),
        _greedy(engine, [8, 64, 32, 5], busy, out=b), later())
    assert outs[2] == unchunked and [len(o) for o in outs[:2]] == [busy, busy]
    starts = list(range(0, n, chunk))
    assert chunks == [(start, 1) for start in starts]
    assert engine.chunked_prefill == {
        "chunks": len(starts), "chunks_behind_burst": len(starts)}
    assert engine.allocator.active_pages == 0
    await engine.close()


async def preempt(fam, monkeypatch):
    """A batch stream preempted for an interactive one gives its row and
    pages back and resumes by prefilling its prompt and its output so far
    from an empty state: the tokens of an undisturbed run."""
    # on the XLA twins where a program is its own (one slot, buckets of
    # 32 and 64); the bucket of 16 is the other engine cases' program
    monkeypatch.setenv("DYNAMO_PALLAS", "0")
    prompt = [5, 11, 17, 23, 29]
    engine = _engine(fam, max_decode_slots=1, prefill_buckets=(16, 32, 64),
                     max_prefill_chunk_tokens=64)
    want = _greedy_reference(fam, engine.params, prompt, 24)
    got: list = []
    batch = asyncio.create_task(_greedy(
        engine, prompt, 24, out=got,
        ctx=Context(headers={PRIORITY_HEADER: "batch"})))
    while len(got) < 6:
        await asyncio.sleep(0.002)
    quick = await _greedy(engine, [2, 4, 6], 3)
    assert quick == _greedy_reference(fam, engine.params, [2, 4, 6], 3)
    assert await batch == want
    assert sum(engine.preemptions.values()) >= 1
    assert engine.allocator.active_pages == 0
    await engine.close()
    if fam.state_rows:
        assert _stats(fam, engine.k_pages)[llama.STAT_MISSING] == 0


async def gates(fam, monkeypatch):
    """What moves, reuses or rolls back pages alone is off for a recurrent
    model by the family's attributes; what is asked for anyway joins the
    fallback series under its own reason; a decode-side disaggregated
    request is served by a local prefill; the programs with no recurrent
    form say so to a direct caller."""
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig
    from dynamo_tpu.parallel.mesh import make_mesh

    _path(monkeypatch, fam.engine_path)
    rec = get_family(fam.spec)
    assert rec.recurrent and rec.supports_packed_prefill
    assert not any(getattr(rec, f"supports_{g}") for g in RECURRENT_GATES)
    plain = get_family(ModelSpec.tiny())
    assert plain.supports_prefix_reuse and plain.supports_page_transfer
    assert not plain.recurrent
    names = ("recurrent_no_page_offload", "recurrent_no_spec_decode",
             "recurrent_no_ring_prefill", "recurrent_no_page_transfer")
    before = _fallbacks(*names)
    engine = _engine(
        fam, spec_mode="ngram", sp=2,
        kvbm=KvBlockManager(KvbmConfig(host_bytes=1 << 20)))
    assert engine.kvbm is None and engine.offload is None
    assert not engine._spec_on
    assert [b - a for a, b in zip(before, _fallbacks(*names))] == [1, 1, 1, 0]
    # a decode-side disaggregated request: the pull is refused, counted,
    # and the stream is served by a local prefill of prompt + first token
    out = []
    async for item in engine.generate(
        {"token_ids": [4, 8, 15, 16], "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
         "disagg": {"mode": "decode", "kv_transfer": {
             "first_token": 23, "address": "127.0.0.1:1", "handle": "x"}}},
        Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    assert out == _greedy_reference(fam, engine.params, [4, 8, 15, 16, 23], 3)
    assert _fallbacks(names[3])[0] - before[3] == 1
    await engine.close()
    k, v = _cache(fam)
    with pytest.raises(NotImplementedError, match="speculative verify"):
        llama.verify_forward_impl(
            fam.spec, engine.params, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, fam.pages_per_seq), jnp.int32),
            jnp.zeros((1,), jnp.int32), k, v, jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="one device"):
        llama.cache_shardings(make_mesh(tp=2, dp=1), "bf16", fam.spec)
    with pytest.raises(ValueError, match="meshes"):
        InferenceEngine(fam.spec, EngineConfig(seed=fam.seed),
                        mesh=make_mesh(tp=2, dp=1))


def case(name, fn, **kw):
    return pytest.param(fn, kw, id=name)


def cases(fam, *more):
    """The cases ``fam``'s row names, then ``more``: what its file adds."""
    out = [case(f"prefill-decode-{n}-{PATHS[path]}", prefill_then_decode,
                n=n, path=path) for n, path in fam.prompts]
    out += [case(f"chunked-{name}" + (f"-{PATHS[path]}" if path else ""),
                 chunked, chunks=chunks, path=path)
            for path in fam.chunked_paths
            for name, chunks in fam.chunked.items()]
    out += [case("pack", pack)]
    out += [case("bursts", bursts, path=path) for path in fam.bursts_paths]
    out += [case(f"inactive-{PATHS[path]}", inactive, path=path)
            for path in fam.inactive_paths]
    out += [case("engine-serves", serves)]
    out += [case("engine-streams-" + ("pipelined" if on else "plain"),
                 streams, pipeline=on) for on in fam.streams]
    return out + list(more)


def run(case, fam, monkeypatch, **kw):
    """What a case hands back to await runs under an async test's clock."""
    out = case(fam, monkeypatch, **kw)
    if inspect.isawaitable(out):
        asyncio.run(asyncio.wait_for(
            out, timeout=float(os.environ.get("DYN_TEST_TIMEOUT", "60"))))
