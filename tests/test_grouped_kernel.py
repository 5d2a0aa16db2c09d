"""The grouped product of decode-sized calls (``ops/pallas/grouped.py``),
interpreted on the CPU against ``jax.lax.ragged_dot``, and the rule that
sends a call to it or to megablox (``models/moe.py: _grouped_matmul``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import moe
from dynamo_tpu.ops import fallback
from dynamo_tpu.ops.pallas import grouped

# (groups, hidden, expert width) of the seven MoE cells' held experts: the
# widths HALF the published ones, so that each stays a whole number of
# 128-lane tiles as the chip's compiler wants it (LFM2's 1,536 -> 6 tiles,
# Solar's 1,280 -> 5, Ling's 2,560 -> 10: none a power of two), a quarter
# of the groups
CELLS = {
    "mimo-v2.5": (8, 2048, 1024),
    "joyai-llm-flash": (8, 1024, 384),
    "solar-open2-250b": (8, 2048, 640),
    "ling-3.0-flash": (16, 1280, 384),
    "lfm2-24b-a2b": (16, 1024, 768),
    "longcat-flash-chat": (8, 3072, 1024),
    "trinity-mini": (8, 1024, 512),
}


def _sizes(case: str, g: int) -> tuple[int, list[int]]:
    """(rows m, sizes [g]) of a case."""
    sizes = [0] * g
    if case == "a-step":
        # m no multiple of 128; groups 0 and 2 empty like most of the
        # tail; group 1's three rows end an aligned block; group 3 crosses
        # the 128-row boundary and takes two chunks; group 5 is the idle
        # slots' 51 identical rows; the last 9 rows belong to no group
        sizes[1], sizes[3], sizes[4], sizes[5], sizes[g - 1] = 3, 140, 1, 51, 7
        return 211, sizes
    if case == "all-zero":
        return 130, sizes
    if case == "one-group":  # every row on the last expert: three chunks
        sizes[g - 1] = 300
        return 300, sizes
    raise AssertionError(case)


def _operands(m, k, n, g, sizes, seed):
    ka, kw = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    if sizes[5] == 51:  # the idle slots' group: one row 51 times
        at = sum(sizes[:5])
        a = a.at[at: at + 51].set(a[at])
    w = jax.random.normal(kw, (g, k, n), jnp.float32) * k ** -0.5
    return a.astype(jnp.bfloat16), w.astype(jnp.bfloat16)


def _check(a, w, sizes, **kw):
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped.grouped_matmul(a, w, sizes, interpret=True, **kw)
    assert got.shape == (a.shape[0], w.shape[2]) and got.dtype == a.dtype
    held = int(sizes.sum())
    want = jax.lax.ragged_dot(a, w, sizes)
    # bf16 operands, float32 over the whole k, one rounding to bf16: the
    # two differ by the order of a float32 sum, so by one bf16 step
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("case", ["a-step", "all-zero", "one-group"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_kernel_matches_ragged_dot(cell, case):
    """Both products of an expert layer (hidden -> width, width -> hidden)
    at a cell's reduced widths."""
    g, d, f = CELLS[cell]
    m, sizes = _sizes(case, g)
    for k, n in ((d, f), (f, d)):
        a, w = _operands(m, k, n, g, sizes, seed=g + k)
        _check(a, w, sizes)


def test_tiles_of_n_divide_the_expert():
    """Where two whole experts do not fit beside the rows the tile is the
    widest multiple of 128 that divides n; the kernel run with such a tile
    gives what it gives with the expert whole."""
    g, d, f = CELLS["longcat-flash-chat"]
    m, sizes = _sizes("a-step", g)
    a, w = _operands(m, f, d, g, sizes, seed=3)
    for tn in (1024, 768):  # 3,072 = 3 x 1,024 = 4 x 768
        _check(a, w, sizes, tn=tn)
    with pytest.raises(ValueError):  # 2,048 does not divide 3,072
        grouped.grouped_matmul(a, w, jnp.asarray(sizes), tn=2048)
    # the rule at the published sizes: every decode call takes the expert
    # whole, under a tp split of 2 too; a split that leaves no whole lane
    # tile (768 / 4) stays on megablox, as Mosaic would refuse its slices
    assert grouped.tile_n(512, 2048, 1536, 2) == 1536  # LFM2
    assert grouped.tile_n(1536, 6144, 2048, 2) == 2048  # LongCat, gate
    assert grouped.tile_n(1536, 2048, 6144, 2) == 6144  # LongCat, down
    assert grouped.tile_n(1024, 2560, 768 // 2, 2) == 384  # Ling, tp 2
    assert grouped.tile_n(1024, 2560, 768 // 4, 2) is None  # Ling, tp 4
    assert grouped.tile_n(1024, 768 // 4, 2560, 2) is None
    # an expert too large to hold twice goes in divisors of its width
    assert grouped.tile_n(1024, 8192, 4096, 2) == 1024
    assert grouped.tile_n(1024, 8192, 3072, 2) == 1536
    # rows that do not fit stream: no tile
    assert grouped.tile_n(32768, 2048, 1536, 2) is None


def test_schedule_lists_the_non_empty_groups_in_order():
    offs, ids, count = grouped.group_schedule(
        jnp.asarray([0, 3, 0, 140, 1, 0], jnp.int32))
    assert offs.tolist() == [0, 0, 3, 3, 143, 144, 144]
    assert count.tolist() == [3] and ids.tolist()[:3] == [1, 3, 4]
    offs, ids, count = grouped.group_schedule(jnp.zeros(4, jnp.int32))
    assert count.tolist() == [0] and offs.tolist() == [0] * 5


def test_dispatch_goes_by_the_calls_shape(monkeypatch):
    """On the chip a decode step's call takes the kernel and a prefill
    call megablox, read from the static shapes alone, each counted by its
    path in ``dynamo_grouped_product_total``. Neither is a downgrade: the
    benchmark's output check refuses a run with any
    ``dynamo_fused_fallback_total`` series, and no series appears."""
    import importlib

    # the package re-exports the function under the module's name
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    took = []

    def kernel(a, w, sizes, **kw):
        took.append(("kernel", a.shape, kw))
        return jnp.zeros((a.shape[0], w.shape[2]), a.dtype)

    def shipped(a, w, sizes, **kw):
        took.append(("megablox", a.shape, kw["tiling"]))
        return jnp.zeros((a.shape[0], w.shape[2]), a.dtype)

    monkeypatch.setattr(grouped, "grouped_matmul", kernel)
    monkeypatch.setattr(megablox, "gmm", shipped)

    def counted():
        return [fallback._GROUPED.labels(p)._value.get()
                for p in ("resident", "streamed")]

    def downgrades():
        return [line for line in fallback.REGISTRY.exposition().decode().splitlines()
                if line.startswith("dynamo_fused_fallback_total{")]

    before, series = counted(), downgrades()
    w = jax.ShapeDtypeStruct((64, 2048, 1536), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32)

    def call(m):
        rows = jax.ShapeDtypeStruct((m, 2048), jnp.bfloat16)
        return jax.eval_shape(moe._grouped_matmul, rows, w, sizes)

    assert call(512).shape == (512, 1536)  # 128 slots x top-4
    assert took == [("kernel", (512, 2048), {"scope": "gmm"})]
    assert counted() == [before[0] + 1, before[1]]
    assert call(32768 + 5).shape == (32768 + 5, 1536)  # 8 x 1,024 tokens
    assert took[1] == ("megablox", (32768 + 128, 2048), (128, 1024, 1536))
    assert counted() == [before[0] + 1, before[1] + 1]
    assert downgrades() == series
