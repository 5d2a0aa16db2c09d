"""One general traffic generator, driven by a data file.

A traffic file (``perfbench/traffic/<name>.json``) carries every parameter
of a mix; this module turns it and a seed into a plan: a list of requests
with the instant each is due (open loop) or the order clients take them in
(closed loop), a prompt length, an output length and the seed of its
content. The seed chooses content, order and jitter, never volume: under
every seed a plan offers the same number of requests and the same multiset
of prompt and output lengths. No JAX here: the load generator's child
process imports this module.

Traffic file keys (all lengths in tokens, all times in seconds):

``loop``            ``"open"`` or ``"closed"``
``rate_rps``        open loop: arrivals per second, a fixed number
``clients``         closed loop: concurrent clients, or
``clients_per_slot``  closed loop: clients as a multiple of the decode slots
``pool_requests``   closed loop: size of the request pool; clients take it in its
                    seeded order, again and again (about one window's requests,
                    so every window serves nearly the whole multiset)
``prompt_tokens`` / ``output_tokens``
                    ``{"dist": "lognormal", "median": m, "sigma": s,
                    "min": a, "max": b}`` or ``{"dist": "fixed", "value": v}``
                    or ``{"dist": "uniform", "min": a, "max": b}``
``max_total_tokens``  prompt + output is clipped to this (the output gives way)
``lead_in_s``       the same arrivals run this long before the window opens
``tail_s``          open loop: arrivals go on this long after it closes, at most
``shared_prefix``   ``{"share": 0..1, "groups": n}``: that share of every
                    prompt is one of ``groups`` common prefixes (0 = none)
``burst``           open loop: ``{"size": k}`` arrivals come in groups of k
                    at one instant (1 = a plain Poisson process)
``temperature``     sampling temperature sent with every request
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

_STD = NormalDist()


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the (i + 0.5) / n quantiles of ``dist``: the same
    multiset whenever ``n`` is the same, whatever the seed."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if kind == "lognormal":
            x = float(dist["median"]) * math.exp(
                float(dist["sigma"]) * _STD.inv_cdf(q)
            )
        elif kind == "uniform":
            x = lo + q * (hi - lo)
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def _requests(traffic: dict, n: int, rng: random.Random, tag: str) -> list[dict]:
    """``n`` requests whose lengths are the fixed multisets, paired and
    ordered by ``rng``."""
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    cap = int(traffic.get("max_total_tokens", 0))
    share = traffic.get("shared_prefix") or {}
    groups = max(1, int(share.get("groups", 1)))
    reqs = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        if cap and p + o > cap:
            o = max(1, cap - p)
        reqs.append({
            "id": f"{tag}{i}",
            "prompt_tokens": p,
            "max_tokens": o,
            "content_seed": rng.getrandbits(48),
            "prefix_tokens": int(p * float(share.get("share", 0.0))),
            "prefix_group": rng.randrange(groups),
        })
    return reqs


def _arrivals(n: int, start: float, length: float, burst: int,
              rng: random.Random) -> list[float]:
    """``n`` instants in ``[start, start + length)``: sorted uniform draws,
    which is a Poisson process given its count; in groups of ``burst``."""
    groups = -(-n // max(1, burst))
    at = sorted(start + rng.random() * length for _ in range(groups))
    return [at[i // max(1, burst)] for i in range(n)]


def make_plan(traffic: dict, seed: int, seconds: float, *,
              decode_slots: int = 0) -> dict:
    """The whole plan of one run. Times are relative to the opening of the
    measured window; a request with ``due`` in ``[0, seconds)`` is
    ``windowed`` and counts, the lead-in and the tail do not."""
    rng = random.Random(f"perfbench:{seed}")
    lead = float(traffic.get("lead_in_s", 0.0))
    loop = traffic["loop"]
    plan = {
        "loop": loop, "seconds": float(seconds), "lead_in_s": lead,
        "temperature": float(traffic.get("temperature", 0.0)),
    }
    if loop == "open":
        rate = float(traffic["rate_rps"])
        tail = float(traffic.get("tail_s", 0.0))
        burst = int((traffic.get("burst") or {}).get("size", 1))
        reqs = []
        for tag, start, length in (
            ("l", -lead, lead), ("w", 0.0, float(seconds)),
            ("t", float(seconds), tail),
        ):
            n = int(round(rate * length))
            part = _requests(traffic, n, rng, tag)
            for r, due in zip(part, _arrivals(n, start, length, burst, rng)):
                r["due"] = due
                r["windowed"] = tag == "w"
            reqs += part
        plan["requests"] = reqs
    elif loop == "closed":
        clients = int(traffic.get("clients", 0)) or int(
            round(float(traffic["clients_per_slot"]) * decode_slots)
        )
        if clients < 1:
            raise ValueError("closed loop: no clients")
        plan["clients"] = clients
        plan["requests"] = _requests(
            traffic, int(traffic["pool_requests"]), rng, "c"
        )
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    return plan


_ALPHABET = "abcdefghijklmnopqrstuvwxyz"


def content_for(request: dict, overhead_tokens: int) -> str:
    """The user message of one request: ``prompt_tokens`` less the chat
    template's tokens, one byte a token (the repo's mock tokenizer), its
    first byte on distinct from every shared prefix ('#' never occurs in
    one), so that prompts share nothing but what ``shared_prefix`` asks."""
    body = max(1, request["prompt_tokens"] - overhead_tokens)
    shared = min(body - 1, request.get("prefix_tokens", 0))
    out = ""
    if shared > 0:
        prng = random.Random(f"prefix:{request['prefix_group']}")
        out = "".join(prng.choice(_ALPHABET) for _ in range(shared))
    rng = random.Random(request["content_seed"])
    own = "".join(rng.choice(_ALPHABET) for _ in range(body - shared - 1))
    return out + "#" + own


def offered(plan: dict) -> dict:
    """What the plan offers inside the window (open loop) or in its pool
    (closed loop): the numbers that must not change with the seed."""
    reqs = [
        r for r in plan["requests"]
        if plan["loop"] == "closed" or r["windowed"]
    ]
    return {
        "requests": len(reqs),
        "prompt_tokens": sum(r["prompt_tokens"] for r in reqs),
        "output_tokens": sum(r["max_tokens"] for r in reqs),
    }
