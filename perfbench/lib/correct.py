"""How ``correct`` is decided: the engine that served the window against
the configuration's plain reference, outside the timed window.

A seeded sample of prompts goes through the engine's own prefill program
(bucketed, paged, on its live weights and pools) and then through decode
steps over the paged cache and the fused decode kernel, teacher-forced with
seeded tokens; the reference computes the same sequences whole, with no
cache. The prefill goes through every layer. The decode steps go through
the model's first ``decode_layers`` layers only (their pages of the pools,
the final norm and the head), against the same cut of the reference: every
layer is the same code, and a decode program of another depth is traced
anew, a layer at a time, at several seconds a layer in every process.
Logits are compared, not tokens: with random weights the largest logit
changes on rounding. The number compared is the root-mean-square
difference as a share of the reference's root mean square, over every
compared logit of a phase: steady from seed to seed where a maximum is not.

Those programs are not all that serves. Packed prompts go through
``fam.prefill_batch``, a second implementation, and tokens come from the
full-depth ``decode_steps`` bursts with the sampler on the device, which
return tokens and no logits. So a second sample, one row a decode slot,
goes through the programs AS SERVED (``served_outputs``): every row
through the packed prefill at the pack widths the engine offers (logits,
compared as above), then one greedy burst of every length the engine
dispatches, each feeding on the last, every slot live. The reference then
reads each row with the engine's own tokens appended, and each token the
engine chose is held to the reference's logits at its position: the
reference's largest logit less its logit for the chosen token, over the
root mean square of that position's logits, averaged over the tokens
(``served_token_gap``; 0 where the engine chose the reference's own
token). A rounding difference moves a choice between near-equal logits,
a gap of hundredths in a few tokens of a hundred; a wrong page, a lost
carry between steps or a wrong sampler chooses a token about four such
units down.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import random

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference(config: dict, bench_dir: str | None = None):
    """The module ``references/<config["reference"]>.py``: beside the
    configuration's file, or among the benchmark's own."""
    name = config["reference"]
    for d in filter(None, (bench_dir, HERE)):
        path = os.path.join(d, "references", name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"perfbench_reference_{name}", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SystemExit(f"no reference {name!r} under references/")


def sample(config: dict, engine_cfg, offered_buckets, seed: int) -> dict:
    """The sequences of the check, from the seed: ``samples`` prompts of
    lengths spread between ``min_tokens`` and ``max_tokens`` (one of them
    ending just short of a page boundary, so its decode steps cross it) and
    ``decode_steps`` forced tokens after each."""
    c = config["correct"]
    rng = random.Random(f"perfbench-correct:{seed}")
    S, K = int(c["samples"]), int(c["decode_steps"])
    top = min(int(c["max_tokens"]), max(offered_buckets))
    lo = int(c["min_tokens"])
    page = engine_cfg.page_size
    lens = [rng.randint(lo, top) for _ in range(S)]
    lens[0] = max(lo, (lens[0] // page) * page - 2)  # crosses a page in 3 steps
    vocab = config["vocab_size"]
    T = int(c["padded_tokens"])
    if max(lens) + K > T:
        raise SystemExit("correct: padded_tokens too short for the sample")
    tokens = np.zeros((S, T), np.int32)
    for s, n in enumerate(lens):
        tokens[s, : n + K] = [rng.randrange(vocab) for _ in range(n + K)]
    depth = min(int(c.get("decode_layers", 0)) or config["num_hidden_layers"],
                config["num_hidden_layers"])
    return {"lens": lens, "steps": K, "tokens": tokens, "decode_layers": depth}


def engine_logits(engine, smp: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prefill logits [S, V], decode logits [S, K, V]) of the sample
    through the engine's programs. The engine must be closed: the calls
    donate its pools."""
    cfg, spec = engine.config, engine.spec
    lens, K, tokens = smp["lens"], smp["steps"], smp["tokens"]
    S = len(lens)
    B = cfg.max_decode_slots
    pps = cfg.max_pages_per_seq
    if S > B or S * pps > cfg.num_pages:
        raise SystemExit("correct: the sample does not fit the engine")
    tables = np.zeros((B, pps), np.int32)
    for s in range(S):
        tables[s] = 1 + s * pps + np.arange(pps)  # page 0 is the trash page
    prefill = []
    for s, n in enumerate(lens):
        bucket = cfg.bucket_for(n)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = tokens[s, :n]
        logits, engine.k_pages, engine.v_pages, _ = engine.fam.prefill(
            spec, engine.params, jnp.asarray(padded), jnp.asarray(tables[s]),
            jnp.asarray(0, jnp.int32), engine.k_pages, engine.v_pages,
            jnp.asarray(n, jnp.int32), mesh=engine.mesh,
        )
        prefill.append(np.asarray(logits, np.float32))
    active = np.zeros((B,), bool)
    active[:S] = True
    decode = np.zeros((S, K, spec.vocab_size), np.float32)
    step = engine.fam.m.decode_forward
    # the first ``depth`` layers as a model of their own: their weights,
    # their pages (as the full prefill wrote them), the same norm and head
    depth = smp["decode_layers"]
    spec = dataclasses.replace(spec, num_layers=depth)
    params = dict(engine.params, layers=engine.params["layers"][:depth])
    # (a pool is an array, or values and scales where the cache is fp8)
    k_pages, v_pages = (
        jax.tree.map(lambda a: a[:depth], pool)
        for pool in (engine.k_pages, engine.v_pages)
    )
    for j in range(K):
        fed = np.zeros((B,), np.int32)
        seq = np.ones((B,), np.int32)
        for s, n in enumerate(lens):
            fed[s] = tokens[s, n + j]
            seq[s] = n + j + 1
        logits, k_pages, v_pages = step(
            spec, params, jnp.asarray(fed), jnp.asarray(tables),
            jnp.asarray(seq), k_pages, v_pages, jnp.asarray(active),
            mesh=engine.mesh,
        )
        decode[:, j] = np.asarray(logits[:S], np.float32)
    if depth == engine.spec.num_layers:
        # no cut: the steps donated the engine's own pools
        engine.k_pages, engine.v_pages = k_pages, v_pages
    return np.stack(prefill), decode


def reference_logits(ref, config: dict, seed: int, smp: dict, *, quant=None):
    """The same positions from the plain reference (or, with ``quant``,
    from the control): (prefill [S, V], decode [S, K, V])."""
    lens, K = smp["lens"], smp["steps"]
    last = np.asarray([[n - 1] for n in lens], np.int32)
    after = np.asarray(
        [[n + j for j in range(K)] for n in lens], np.int32
    )
    full, early = ref.forward(
        config, seed, smp["tokens"], last, quant=quant,
        early=(smp["decode_layers"], after),
    )
    return np.asarray(full, np.float32)[:, 0], np.asarray(early, np.float32)


def served_sample(config: dict, engine, seed: int) -> dict:
    """The rows of the served-programs check, from the seed: one sequence a
    decode slot, lengths spread as the first sample's, each followed by
    one forced token; every row will generate ``generated`` tokens, a
    burst of every length the engine dispatches."""
    c = config["correct"]
    cfg = engine.config
    rng = random.Random(f"perfbench-correct-served:{seed}")
    R = cfg.max_decode_slots
    bursts = sorted(engine._burst_lengths)
    top = min(int(c["max_tokens"]), max(engine._prefill_shapes))
    lens = [rng.randint(int(c["min_tokens"]), top) for _ in range(R)]
    T = int(c["padded_tokens"])
    if max(lens) + 1 + sum(bursts) > T:
        raise SystemExit("correct: padded_tokens too short for the bursts")
    tokens = np.zeros((R, T), np.int32)
    for r, n in enumerate(lens):
        tokens[r, : n + 1] = [
            rng.randrange(config["vocab_size"]) for _ in range(n + 1)
        ]
    return {"lens": lens, "tokens": tokens, "bursts": bursts,
            "generated": sum(bursts)}


def served_outputs(engine, smp: dict):
    """The rows through the programs as served. Returns (packed-prefill
    logits [R, V], tokens generated [R, G]) and writes the generated tokens
    into ``smp["tokens"]`` behind each row's forced token, so the reference
    reads the sequences the engine decoded. The engine must be closed."""
    cfg, spec, fam = engine.config, engine.spec, engine.fam
    lens, tokens = smp["lens"], smp["tokens"]
    R, B, pps = len(lens), cfg.max_decode_slots, cfg.max_pages_per_seq
    tables = np.zeros((B, pps), np.int32)
    page = 1  # page 0 is the trash page
    for r, n in enumerate(lens):
        need = -(-(n + 1 + smp["generated"]) // cfg.page_size)
        tables[r, :need] = page + np.arange(need)
        page += need
    if page > cfg.num_pages + 1:
        raise SystemExit("correct: the served rows do not fit the engine")

    # packed prefill, in the shapes the engine offers: rows of one bucket
    # in packs of its width, a short pack padded with empty rows
    prefill = np.zeros((R, spec.vocab_size), np.float32)
    by_bucket: dict[int, list[int]] = {}
    for r, n in enumerate(lens):
        by_bucket.setdefault(cfg.bucket_for(n), []).append(r)
    for bucket, rows in sorted(by_bucket.items()):
        nb = engine._prefill_shapes[bucket]
        if not fam.supports_packed_prefill or nb < 2:
            raise SystemExit(f"correct: no packed prefill at bucket {bucket}")
        for at in range(0, len(rows), nb):
            pack = rows[at: at + nb]
            toks = np.zeros((nb, bucket), np.int32)
            bts = np.zeros((nb, pps), np.int32)
            nts = np.zeros((nb,), np.int32)  # empty rows: the trash page
            for i, r in enumerate(pack):
                toks[i, : lens[r]] = tokens[r, : lens[r]]
                bts[i], nts[i] = tables[r], lens[r]
            logits, engine.k_pages, engine.v_pages, _ = fam.prefill_batch(
                spec, engine.params, jnp.asarray(toks), jnp.asarray(bts),
                jnp.zeros((nb,), jnp.int32), engine.k_pages, engine.v_pages,
                jnp.asarray(nts), mesh=engine.mesh,
            )
            prefill[pack] = np.asarray(logits, np.float32)[: len(pack)]

    # one greedy burst of each length, every row live, each feeding on the
    # last: the full-depth decode programs and the sampler on the device
    active = np.zeros((B,), bool)
    active[:R] = True
    fed = np.zeros((B,), np.int32)
    seq = np.ones((B,), np.int32)
    for r, n in enumerate(lens):
        fed[r], seq[r] = tokens[r, n], n + 1
    zB = jnp.zeros((B,), jnp.int32)
    made = []
    for n_steps in smp["bursts"]:
        out, engine.k_pages, engine.v_pages = fam.decode_steps(
            spec, engine.params, engine._feed_array(fed),
            jnp.asarray(tables), jnp.asarray(seq),
            engine.k_pages, engine.v_pages, jnp.asarray(active),
            jnp.zeros((B,), jnp.float32), zB, jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.uint32), zB,
            n_steps=n_steps, n_logprobs=0, mesh=engine.mesh,
        )
        out = np.asarray(out, np.int32)
        made.append(out[:R])
        fed[:R] = out[:R, -1]
        seq[:R] += n_steps
    made = np.concatenate(made, axis=1)
    for r, n in enumerate(lens):
        tokens[r, n + 1: n + 1 + made.shape[1]] = made[r]
    return prefill, made


def served_reference(ref, config: dict, seed: int, smp: dict, *, quant=None):
    """The plain reference (or the control) on the rows as the engine
    decoded them: logits [R, 1 + G, V] at each prompt's last position and
    at the positions whose logits chose the G generated tokens."""
    G = smp["generated"]
    at = np.asarray(
        [[n - 1] + [n + j for j in range(G)] for n in smp["lens"]], np.int32
    )
    return np.asarray(
        ref.forward(config, seed, smp["tokens"], at, quant=quant), np.float32
    )


def token_gap(chosen: np.ndarray, want: np.ndarray) -> np.ndarray:
    """For each chosen token [...], the reference's largest logit less its
    logit for that token, over the root mean square of the position's
    logits ``want`` [..., V]. 0 where the reference chose the same."""
    want = np.asarray(want, np.float64)
    got = np.take_along_axis(want, np.asarray(chosen)[..., None], axis=-1)[..., 0]
    return (want.max(axis=-1) - got) / np.sqrt(np.mean(want ** 2, axis=-1))


def served_numbers(prefill, chosen, want, bursts: list[int]) -> dict:
    """The served-programs rows of the comparison. ``chosen`` [R, G] are
    tokens: the engine's, or the argmax of a control's logits at the same
    positions. One number for all of them: a burst's few dozen tokens
    alone read from 0 to twice the mean, seed to seed; the bursts apart
    are printed beside it."""
    gaps = token_gap(chosen, want[:, 1:])
    also, at = {}, 0
    for n in bursts:
        also[f"token_gap_burst_of_{n}"] = float(gaps[:, at: at + n].mean())
        at += n
    also["token_gap_max"] = float(gaps.max())
    also["tokens_as_the_reference"] = float((gaps == 0).mean())
    return {
        "packed_prefill_rel_rms": rel_rms(prefill, want[:, 0]),
        "served_token_gap": float(gaps.mean()),
        "also": also,
    }


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def rel_max(got: np.ndarray, want: np.ndarray) -> float:
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / np.abs(want).max())


def compare(got, want, limits: dict, served: dict | None = None) -> dict:
    """Each number compared beside its limit. ``served`` holds the rows of
    ``served_numbers``; a row the configuration gives no limit for is an
    error, not a pass."""
    (gp, gd), (wp, wd) = got, want
    rows = {
        "prefill_rel_rms": rel_rms(gp, wp),
        "decode_rel_rms": rel_rms(gd, wd),
    }
    also = {
        "prefill_rel_max": rel_max(gp, wp),
        "decode_rel_max": rel_max(gd, wd),
    }
    if served is not None:
        rows.update({k: v for k, v in served.items() if k != "also"})
        also.update(served["also"])
    missing = sorted(set(rows) - set(limits))
    if missing:
        raise SystemExit(f"correct: the configuration sets no limit for {missing}")
    return {
        "rows": {k: {"value": v, "limit": limits[k]} for k, v in rows.items()},
        "also": also,
        "ok": all(v <= limits[k] for k, v in rows.items()),
    }


def check_engine(engine, config: dict, seed: int, weights_seed: int,
                 bench_dir: str | None = None) -> dict:
    """The whole comparison for one engine: both samples, both sides,
    verdict."""
    import time

    t0 = time.monotonic()
    ref = load_reference(config, bench_dir)
    smp = sample(config, engine.config, list(engine._prefill_shapes), seed)
    rows = served_sample(config, engine, seed)
    got = engine_logits(engine, smp)
    packed, chosen = served_outputs(engine, rows)  # over the same pages
    t1 = time.monotonic()
    want = reference_logits(ref, config, weights_seed, smp)
    served = served_numbers(
        packed, chosen, served_reference(ref, config, weights_seed, rows),
        rows["bursts"],
    )
    out = compare(got, want, config["correct"]["limits"], served)
    out["sample_lens"] = smp["lens"]
    out["served_rows"] = {"rows": len(rows["lens"]), "bursts": rows["bursts"]}
    out["secs"] = {"engine": round(t1 - t0, 1),
                   "reference": round(time.monotonic() - t1, 1)}
    return out
