"""DL013 fixture: shard_map specs that don't cover the callable.

Arity: an in_specs tuple shorter/longer than the wrapped callable's
positional params flags. Pytree leaves: a quant-capable value (one the
enclosing function probes with ``is_quant``) entering shard_map under a
bare array-only ``P(...)`` spec flags — a QuantPool's scale leaves
would have no spec at all.
"""
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.quant import is_quant
from jax import shard_map as compat_shard_map


def _kernel(q, k, v):
    return q


def run(mesh, q, k, v):
    good = compat_shard_map(
        _kernel, mesh=mesh,
        in_specs=(P("tp"), P("tp"), P("tp")), out_specs=P("tp"),
    )
    a = good(q, k, v)
    bad = compat_shard_map(  # EXPECT: DL013
        _kernel, mesh=mesh,
        in_specs=(P("tp"), P("tp")), out_specs=P("tp"),
    )
    b = bad(q, k, v)
    return a, b


def run_quant(mesh, q, k_pages, v_pages):
    if is_quant(k_pages):
        k_pages = k_pages.vals
    sm = compat_shard_map(  # EXPECT: DL013
        _kernel, mesh=mesh,
        in_specs=(P(None), P(None, "tp"), P(None, "tp")),
        out_specs=P(None),
    )
    args = (q, k_pages, v_pages)
    return sm(*args)


def run_guarded(mesh, q, k_pages, v_pages):
    if is_quant(k_pages):
        raise NotImplementedError("quant pools take the counted fallback")
    # dynalint: disable=DL013 -- the guard above rejects quant pools;
    # plain array leaves are fully covered by these specs
    sm = compat_shard_map(
        _kernel, mesh=mesh,
        in_specs=(P(None), P(None, "tp"), P(None, "tp")),
        out_specs=P(None),
    )
    return sm(q, k_pages, v_pages)
