"""Guided decoding (guided/ + engine masked sampling + OpenAI surface).

The load-bearing contract is CONFORMANCE AT TEMPERATURE > 0: with a
grammar attached, every completion parses and validates against the
requested schema because sampling itself is masked — across all three
model families, composed with speculative decoding (masked verify
logits, bit-identical greedy goldens), across migration resume, and
with typed 400s (never 500s, never silent drops) on everything the
compiler refuses. The grammar compiler itself is pinned by unit goldens
(regex -> DFA -> token masks) so engine failures localize.
"""

import asyncio
import json

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.guided import (
    GrammarCompiler,
    GrammarError,
    RegexError,
    TokenVocab,
    compile_regex,
    grammar_from_request,
    schema_to_regex,
)
from dynamo_tpu.parsers import make_tool_config, parse_tool_calls
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.faults import FAULTS

pytestmark = pytest.mark.integration

TINY_GQA = ModelSpec(
    name="tiny-test", vocab_size=272, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)
FAMILIES = {
    "gqa": TINY_GQA,
    "mla": ModelSpec.tiny_deepseek(),
    "gptoss": ModelSpec.tiny_gpt_oss(),
}
# JSON-capable vocab per model vocab size (MockTokenizer's byte+16
# mapping cannot reach '{' inside a 96-entry vocab)
VOCABS = {
    fam: TokenVocab.ascii_json(spec.vocab_size)
    for fam, spec in FAMILIES.items()
}

# every production bounded (string maxLength, enum'd number, boolean,
# bounded whitespace): a random-weight greedy toy model can then NEVER
# wander an unbounded digit/whitespace loop — termination is structural,
# which keeps these engine goldens deterministic. Free-form integers are
# covered by the compiler unit tests.
SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "maxLength": 8},
        "age": {"enum": [0, 1, 7, 42]},
        "ok": {"type": "boolean"},
    },
    "required": ["name", "age", "ok"],
}
GRAMMAR = grammar_from_request(
    {"response_format": {"type": "json_schema",
                         "json_schema": {"name": "t", "schema": SCHEMA}}},
)


def _cfg(**kw) -> EngineConfig:
    base = dict(
        page_size=4, num_pages=256, max_pages_per_seq=64,
        max_decode_slots=2, prefill_buckets=(16, 32, 64),
        decode_steps_per_dispatch=2, pipeline_decode=True,
    )
    base.update(kw)
    return EngineConfig(**base)


async def _gen(engine, prompt, n, temperature=0.0, seed=None, guided=None,
               expect_error=False):
    req = {
        "token_ids": list(prompt),
        "stop_conditions": {"max_tokens": n},
        "sampling": {"temperature": temperature},
    }
    if seed is not None:
        req["sampling"]["seed"] = seed
    if guided is not None:
        req["guided"] = {**guided, "prompt_len": len(prompt)}
    out, reasons, errors = [], [], []
    async for item in engine.generate(req, Context()):
        if item.get("error"):
            errors.append(item["error"])
        out.extend(item.get("token_ids") or ())
        if item.get("finish_reason") is not None:
            reasons.append(item["finish_reason"])
    if not expect_error:
        assert not errors, errors
    return out, reasons, errors


# ------------------------------------------------------- compiler units


def test_regex_dfa_matches_and_rejects():
    d = compile_regex("-?(0|[1-9][0-9]*)(\\.[0-9]+)?")

    def match(s):
        st = d.start
        for ch in s:
            st = d.step_char(st, ch)
            if st is None:
                return False
        return d.accept[st]

    assert match("0") and match("-42") and match("3.14")
    assert not match("01") and not match("1.") and not match("")
    for bad in ("[", "(a", "a)", "^x", "x$", "a{999999}"):
        with pytest.raises(RegexError):
            compile_regex(bad)


def test_wide_alphabet_patterns_rejected_fast():
    """CPU-exhaustion guard: subset construction is linear in the
    MENTIONED alphabet per state, so an untrusted pattern must not be
    able to materialize a huge one. A wide class range is refused at
    PARSE time (the frontend-edge 400 stays cheap); a pattern spraying
    thousands of distinct literal chars is refused at compile before
    construction starts. Pre-fix, '[ -\\uffff]{64}' pinned a core for
    minutes."""
    from dynamo_tpu.guided.regex_dfa import parse_regex

    wide = "[ -" + chr(0xFFFF) + "]{64}"
    with pytest.raises(RegexError, match="range wider"):
        parse_regex(wide)
    with pytest.raises(RegexError):
        compile_regex(wide)
    # distinct literals bypass the class budget; the alphabet cap holds
    many_literals = "".join(chr(0x4E00 + i) for i in range(1100))
    with pytest.raises(RegexError, match="distinct characters"):
        compile_regex(many_literals)
    # real grammars stay comfortably inside both caps
    compile_regex(schema_to_regex(SCHEMA))


def test_guided_regex_alternation_whitespace_binding():
    """The whitespace affixes wrap the WHOLE pattern: a top-level
    alternation in nvext.guided_regex tolerates a leading newline (chat
    models routinely open with one) and a trailing run on EVERY branch,
    not just the outermost ones."""
    g = grammar_from_request({"nvext": {"guided_regex": "yes|no"}})
    d = compile_regex(g["regex"])

    def match(s):
        st = d.start
        for ch in s:
            st = d.step_char(st, ch)
            if st is None:
                return False
        return d.accept[st]

    for s in ("yes", "no", "\nno", " yes ", "no\n"):
        assert match(s), s
    for s in ("maybe", "yesno", ""):
        assert not match(s), s


def test_schema_lowering_strictness():
    # strict structured output: every property must be required
    with pytest.raises(GrammarError):
        schema_to_regex({"type": "object",
                         "properties": {"a": {"type": "string"}},
                         "required": []})
    with pytest.raises(GrammarError):
        schema_to_regex({"type": "object", "additionalProperties": True})
    with pytest.raises(GrammarError):
        schema_to_regex({"$ref": "#/defs/x"})
    # supported shapes lower and compile
    src = schema_to_regex({
        "type": "object",
        "properties": {
            "kind": {"enum": ["a", "b"]},
            "vals": {"type": "array", "items": {"type": "number"},
                     "minItems": 1, "maxItems": 3},
            "note": {"anyOf": [{"type": "string"}, {"type": "null"}]},
        },
        "required": ["kind", "vals", "note"],
    })
    compile_regex(src)


def test_token_masks_and_state_walk():
    vocab = VOCABS["gqa"]
    comp = GrammarCompiler(vocab, vocab_size=272)
    st = comp.state_for(GRAMMAR, eos_ids=(2,))
    m = st.mask()
    # start state: only whitespace or '{' (and never EOS — the grammar
    # is not satisfied yet)
    allowed = {vocab.tokens[i] for i in np.nonzero(m)[0]}
    assert "{" in allowed and not m[2]
    assert allowed <= {"{", " ", "\n", "\t", "\r", "{\""}
    # an off-grammar token flips violated and releases the constraint
    assert not st.advance(vocab.tokens.index("]"))
    assert st.violated and not st.constraining
    # a fresh cursor driven greedily to completion allows EOS exactly
    # at the accepting state
    st2 = comp.state_for(GRAMMAR, eos_ids=(2,))
    for ch in '{"name":"x","age":7,"ok":true}':
        tok = vocab.tokens.index(ch)
        assert st2.advance(tok), ch
    assert st2.mask()[2]
    assert st2.advance(2) and st2.done and not st2.violated


def test_compiler_lru_and_snapshot():
    vocab = TokenVocab.ascii_json(96)
    comp = GrammarCompiler(vocab, vocab_size=96, cache_entries=2)
    base = {"type": "object", "properties": {}, "required": []}
    keys = []
    for i in range(3):
        schema = {"type": "object",
                  "properties": {f"lru{i}": {"type": "integer"}},
                  "required": [f"lru{i}"]}
        g = grammar_from_request(
            {"response_format": {"type": "json_schema",
                                 "json_schema": {"name": "x",
                                                 "schema": schema}}})
        comp.compile(g)
        keys.append(g)
    del base
    snap = comp.snapshot()
    assert snap["compiles"] == 3 and snap["evictions"] == 1
    assert snap["entries"] == 2
    comp.compile(keys[-1])
    assert comp.snapshot()["hits"] == 1
    assert comp.snapshot()["compile_ms_mean"] > 0


def test_vocab_prompt_len_resume_state():
    """state_for advances over prefix tokens past prompt_len — the
    migration/disagg continuity hook."""
    vocab = VOCABS["gqa"]
    comp = GrammarCompiler(vocab, vocab_size=272)
    prefix = [vocab.tokens.index(c) for c in '{"name"']
    st = comp.state_for(GRAMMAR, eos_ids=(2,), prefix_tokens=prefix)
    allowed = {vocab.tokens[i] for i in np.nonzero(st.mask())[0]}
    # mid-grammar: the next token must continue toward ':'
    assert ":" in allowed and "{" not in allowed


# --------------------------------------- preprocessor grammar selection


def test_preprocessor_tool_choice_shapes():
    """Every tool_choice shape flows to grammar selection (satellite:
    preprocessor.py previously special-cased only "none")."""
    from dynamo_tpu.frontend.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.frontend.tokenizer import MockTokenizer

    pp = OpenAIPreprocessor(
        MockTokenizer(), model_name="m", tool_call_parser="hermes"
    )
    tools = [
        {"type": "function", "function": {
            "name": "f1",
            "parameters": {"type": "object",
                           "properties": {"x": {"type": "integer"}},
                           "required": ["x"]}}},
        {"type": "function", "function": {"name": "f2"}},
    ]
    msgs = [{"role": "user", "content": "hi"}]

    # "none"/"auto"/absent: no grammar, and "none" also disables the jail
    for tc in ("none", "auto", None):
        req = {"messages": msgs, "tools": tools}
        if tc is not None:
            req["tool_choice"] = tc
        assert pp.preprocess(req)["guided"] is None
    assert pp._tool_config({"tools": tools, "tool_choice": "none"}) is None
    assert pp._tool_config({"tools": tools, "tool_choice": "auto"}) is not None
    assert pp._tool_config({"tools": tools, "tool_choice": "required"}) is not None

    # "required": grammar over ALL declared tools
    g = pp.preprocess(
        {"messages": msgs, "tools": tools, "tool_choice": "required"}
    )["guided"]
    assert g["kind"] == "tool_call"
    assert "f1" in g["regex"] and "f2" in g["regex"]
    assert "<tool_call>" in g["regex"]
    assert g["prompt_len"] > 0

    # named function: grammar over exactly that tool
    g = pp.preprocess(
        {"messages": msgs, "tools": tools,
         "tool_choice": {"type": "function", "function": {"name": "f2"}}}
    )["guided"]
    assert "f2" in g["regex"] and "f1" not in g["regex"]

    # forced tool_choice without a model tool parser: typed 400 material
    bare = OpenAIPreprocessor(MockTokenizer(), model_name="m")
    with pytest.raises(ValueError, match="tool-call parser"):
        bare.preprocess(
            {"messages": msgs, "tools": tools, "tool_choice": "required"}
        )

    # response_format selection + nvext regex escape hatch
    assert pp.preprocess(
        {"messages": msgs, "response_format": {"type": "json_object"}}
    )["guided"]["kind"] == "json_object"
    assert pp.preprocess(
        {"messages": msgs, "response_format": {"type": "text"}}
    )["guided"] is None
    assert pp.preprocess(
        {"messages": msgs, "nvext": {"guided_regex": "[0-9]{3}"}}
    )["guided"]["kind"] == "regex"


# ------------------------------------- conformance goldens (3 families)


@pytest.mark.parametrize("fam", sorted(FAMILIES))
async def test_schema_conformance_at_temperature(fam):
    """THE acceptance bar: at temperature > 0 with fixed seeds, every
    completion parses and validates against the schema — sampling is
    masked, so conformance is structural, not probabilistic."""
    spec = FAMILIES[fam]
    vocab = VOCABS[fam]
    rng = np.random.default_rng(1)
    prompt = rng.integers(3, min(90, spec.vocab_size), 20).tolist()
    engine = InferenceEngine(spec, _cfg(), guided_vocab=vocab)
    await engine.start()
    for seed in (1, 7):
        toks, reasons, _ = await _gen(
            engine, prompt, 300, temperature=0.9, seed=seed, guided=GRAMMAR
        )
        text = vocab.text(toks)
        parsed = json.loads(text)  # parses...
        assert set(parsed) == {"name", "age", "ok"}  # ...and validates
        assert parsed["age"] in (0, 1, 7, 42)
        assert isinstance(parsed["ok"], bool)
        assert len(parsed["name"]) <= 8
        assert reasons[-1] == "stop", (reasons, text)
    assert engine.allocator.active_pages == 0
    counters = engine.guided_snapshot()
    assert counters["compiles"] + counters["hits"] > 0
    await engine.close()


async def test_guided_truncation_counts_truncated_not_ok():
    """A guided stream cut by max_tokens mid-grammar is NOT conformance
    delivered: the outcome counter must land in truncated, never ok —
    ok strictly means the grammar reached acceptance."""
    from dynamo_tpu.guided.runtime import GUIDED_REQUESTS

    vocab = VOCABS["gqa"]
    prompt = np.random.default_rng(3).integers(3, 90, 16).tolist()
    engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await engine.start()
    ok0 = GUIDED_REQUESTS.labels(outcome="ok")._value.get()
    trunc0 = GUIDED_REQUESTS.labels(outcome="truncated")._value.get()
    toks, reasons, _ = await _gen(engine, prompt, 4, guided=GRAMMAR)
    assert reasons[-1] == "length"
    with pytest.raises(json.JSONDecodeError):
        json.loads(vocab.text(toks))  # genuinely cut mid-grammar
    assert GUIDED_REQUESTS.labels(outcome="ok")._value.get() == ok0
    assert (
        GUIDED_REQUESTS.labels(outcome="truncated")._value.get()
        == trunc0 + 1
    )
    assert engine.allocator.active_pages == 0
    await engine.close()


async def test_min_tokens_beyond_grammar_stops_at_completion():
    """A completed grammar leaves only eos legal: min_tokens larger
    than the grammar's longest sentence must end the stream at grammar
    completion instead of streaming eos padding at the client."""
    vocab = VOCABS["gqa"]
    g = grammar_from_request(
        {"response_format": {"type": "json_schema",
                             "json_schema": {"name": "b",
                                             "schema": {"type": "boolean"}}}},
    )
    prompt = [5, 6, 7, 8]
    engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await engine.start()
    req = {
        "token_ids": prompt,
        "stop_conditions": {"max_tokens": 64, "min_tokens": 48},
        "sampling": {"temperature": 0.0},
        "guided": {**g, "prompt_len": len(prompt)},
    }
    toks, reasons = [], []
    async for item in engine.generate(req, Context()):
        assert not item.get("error"), item
        toks.extend(item.get("token_ids") or ())
        if item.get("finish_reason") is not None:
            reasons.append(item["finish_reason"])
    await engine.close()
    assert reasons[-1] == "stop"
    # "true"/"false" + bounded whitespace + one eos — nowhere near the
    # 48-token min_tokens floor, and no repeated-eos tail
    assert len(toks) <= 12, toks
    assert json.loads(vocab.text(toks)) in (True, False)
    assert toks.count(toks[-1]) == 1, toks

    # same contract on the stop_token_ids branch: eos pushed out of
    # vocab range so the accepting mask admits ONLY the stop token —
    # the slot must stop there, not stream stop-token padding to 48
    engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await engine.start()
    req = {
        "token_ids": prompt,
        "eos_token_ids": [100000],
        "stop_conditions": {"max_tokens": 64, "min_tokens": 48,
                            "stop_token_ids": [271]},
        "sampling": {"temperature": 0.0},
        "guided": {**g, "prompt_len": len(prompt)},
    }
    toks, reasons = [], []
    async for item in engine.generate(req, Context()):
        assert not item.get("error"), item
        toks.extend(item.get("token_ids") or ())
        if item.get("finish_reason") is not None:
            reasons.append(item["finish_reason"])
    await engine.close()
    assert reasons[-1] == "stop"
    assert toks[-1] == 271 and toks.count(271) == 1, toks
    assert len(toks) <= 12, toks
    assert json.loads(vocab.text(toks[:-1])) in (True, False)


async def test_mixed_guided_and_free_slots_share_engine():
    """Constrained and free slots share one engine cycle; the free
    stream's output is unaffected by its constrained neighbor."""
    vocab = VOCABS["gqa"]
    prompt = np.random.default_rng(2).integers(3, 90, 16).tolist()
    free_alone = InferenceEngine(TINY_GQA, _cfg())
    await free_alone.start()
    ref, _, _ = await _gen(free_alone, prompt, 24, temperature=0.8, seed=5)
    await free_alone.close()

    engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await engine.start()
    (g_out, g_r, _), (f_out, _f_r, _) = await asyncio.gather(
        _gen(engine, prompt, 300, temperature=0.8, seed=4, guided=GRAMMAR),
        _gen(engine, prompt, 24, temperature=0.8, seed=5),
    )
    json.loads(vocab.text(g_out))
    assert f_out == ref  # per-request RNG: neighbor masks don't leak
    assert engine.allocator.active_pages == 0
    await engine.close()


# ------------------------------------------------ guided x spec decode


async def test_guided_spec_greedy_golden_bit_identical():
    """Guided composes with speculative decoding: masked verify logits,
    bit-identical greedy stream vs spec-off, conformant output, and the
    scratch-cursor lookahead means rejected tails never perturb the
    grammar state (rollback-by-construction)."""
    vocab = VOCABS["gqa"]
    # the prompt is itself a conformant document, one character a token:
    # the prompt-lookup drafter then proposes ITS string body and values
    # behind the keys the model emits, which the grammar allows (so they
    # survive the lookahead) and the model's own argmax does not choose,
    # so drafts are rejected by construction. A random prompt's drafts
    # are cut to their grammar-legal prefix, mostly forced tokens, and
    # every one is accepted (rejected == 0 on rng(0..11))
    doc = '{"name": "zzzzzzzz", "age": 42, "ok": true}'
    prompt = [vocab.tokens.index(ch) for ch in doc]
    outs = {}
    for mode in ("off", "ngram"):
        engine = InferenceEngine(
            TINY_GQA, _cfg(spec_mode=mode, spec_reprobe_tokens=16),
            guided_vocab=vocab,
        )
        await engine.start()
        outs[mode], reasons, _ = await _gen(
            engine, prompt, 300, guided=GRAMMAR
        )
        if mode == "ngram":
            assert engine.spec_verifies > 0, "spec never engaged"
            # rejected tails occurred AND the stream stayed conformant:
            # the mask-state rollback contract under rejection
            assert engine.spec_rejected > 0
        assert reasons[-1] == "stop"
        assert engine.allocator.active_pages == 0
        await engine.close()
    assert outs["ngram"] == outs["off"]
    json.loads(vocab.text(outs["off"]))


# ---------------------------------------------- migration continuity


async def test_guided_migration_resume_continuity():
    """The frontend migration shape: engine A dies mid-grammar, engine B
    resumes with prompt+generated and the SAME guided spec (original
    prompt_len) — the stitched stream equals one uninterrupted run and
    still parses."""
    vocab = VOCABS["gqa"]
    prompt = np.random.default_rng(4).integers(3, 90, 16).tolist()
    guided = {**GRAMMAR, "prompt_len": len(prompt)}

    ref_engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await ref_engine.start()
    full, _, _ = await _gen(ref_engine, prompt, 300, guided=GRAMMAR)
    await ref_engine.close()

    a = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await a.start()
    part1, r1, _ = await _gen(a, prompt, 10, guided=GRAMMAR)
    assert r1[-1] == "length"
    await a.close()

    b = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await b.start()
    # migration re-drives with prompt+generated and the ORIGINAL guided
    # spec (prompt_len still marks the original prompt end)
    out2, reasons2, errors2 = [], [], []
    async for item in b.generate(
        {"token_ids": prompt + part1,
         "stop_conditions": {"max_tokens": 300},
         "sampling": {"temperature": 0.0},
         "guided": dict(guided)},
        Context(),
    ):
        assert not item.get("error"), item
        out2.extend(item.get("token_ids") or ())
        if item.get("finish_reason") is not None:
            reasons2.append(item["finish_reason"])
    assert b.allocator.active_pages == 0
    await b.close()
    assert part1 + out2 == full
    json.loads(vocab.text(full))


# ------------------------------------------------- compile-fault path


async def test_guided_compile_fault_is_typed_400_no_leak():
    """Injected engine.guided_compile failure: typed invalid_request
    error (the frontend maps it to 400), zero pages touched, outcome
    counter trips, and the engine keeps serving."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    vocab = VOCABS["gqa"]
    prompt = [5, 6, 7]
    # a grammar no other test compiles, so the process-wide shared
    # cache cannot satisfy it before the fault fires
    schema = {"type": "object",
              "properties": {"fault_probe": {"type": "integer"}},
              "required": ["fault_probe"]}
    g = grammar_from_request(
        {"response_format": {"type": "json_schema",
                             "json_schema": {"name": "f",
                                             "schema": schema}}})
    trips0 = FAULTS.snapshot()["trips"].get(
        "engine.guided_compile:error", 0
    )
    FAULTS.configure("engine.guided_compile:error@1.0x1", seed=7)
    try:
        engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
        await engine.start()
        _out, reasons, errors = await _gen(
            engine, prompt, 8, guided=g, expect_error=True
        )
        assert reasons == ["error"]
        assert errors and errors[0].startswith("invalid_request:")
        assert engine.allocator.active_pages == 0
        snap = FAULTS.snapshot()
        assert snap["trips"].get(
            "engine.guided_compile:error"
        ) == trips0 + 1, snap
        # counter outcome trips on every /metrics exposition
        text = MetricsRegistry().exposition().decode()
        assert 'dynamo_guided_requests_total{outcome="compile_error"}' in text
        # the fault was 1-shot: the SAME grammar now compiles and serves
        toks, reasons, _ = await _gen(engine, prompt, 300, guided=g)
        assert reasons[-1] == "stop"
        json.loads(vocab.text(toks))
        await engine.close()
    finally:
        FAULTS.configure("")


async def test_guided_unavailable_without_vocab():
    engine = InferenceEngine(TINY_GQA, _cfg())
    await engine.start()
    _, reasons, errors = await _gen(
        engine, [3, 4, 5], 8, guided=GRAMMAR, expect_error=True
    )
    assert reasons == ["error"]
    assert "unavailable" in errors[0]
    await engine.close()


# --------------------------------------------- forced tool-call loop


async def test_forced_tool_call_parses_through_tool_parser():
    """Constrain-then-parse: a forced tool call generated under the
    hermes grammar is consumed by parse_tool_calls with valid JSON
    arguments — the guarantee parsers/ used to only hope for."""
    tool_cfg = make_tool_config("hermes")
    tools = [{"type": "function", "function": {
        "name": "lookup",
        "parameters": {"type": "object",
                       "properties": {"q": {"type": "string",
                                            "maxLength": 6}},
                       "required": ["q"]}}}]
    g = grammar_from_request(
        {"tools": tools, "tool_choice": "required"}, tool_cfg=tool_cfg
    )
    vocab = VOCABS["gqa"]
    engine = InferenceEngine(TINY_GQA, _cfg(), guided_vocab=vocab)
    await engine.start()
    prompt = [9, 10, 11, 12]
    toks, reasons, _ = await _gen(
        engine, prompt, 400, temperature=0.8, seed=3, guided=g
    )
    assert reasons[-1] == "stop"
    text = vocab.text(toks)
    calls, _normal = parse_tool_calls(text, tool_cfg)
    assert len(calls) == 1
    assert calls[0].name == "lookup"
    args = json.loads(calls[0].arguments)
    assert set(args) == {"q"} and len(args["q"]) <= 6
    await engine.close()


# ------------------------------------------ observability + artifact


async def test_guided_phases_metric_and_snapshot():
    """guided.* profile phases accumulate, guided_snapshot carries the
    compiler stats, and the outcome counter lands ok trips."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    vocab = VOCABS["gqa"]
    engine = InferenceEngine(
        TINY_GQA,
        _cfg(spec_mode="ngram", spec_reprobe_tokens=16, profile=True),
        guided_vocab=vocab,
    )
    await engine.start()
    prompt = np.random.default_rng(6).integers(3, 90, 16).tolist()
    toks, _, _ = await _gen(engine, prompt, 300, guided=GRAMMAR)
    json.loads(vocab.text(toks))
    snap = engine.profile_snapshot()
    await engine.close()
    assert snap.get("guided.mask", {}).get("calls", 0) > 0, snap
    assert snap.get("guided.lookahead", {}).get("calls", 0) > 0, snap
    text = MetricsRegistry().exposition().decode()
    assert 'dynamo_guided_requests_total{outcome="ok"}' in text

