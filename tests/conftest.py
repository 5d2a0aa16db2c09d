"""Test configuration.

All tests run on a virtual 8-device CPU mesh (the chip is reached through
``chip_smoke.py``, never from the tests). Env vars must be set before the
first ``import jax`` anywhere in the test process.
"""

import os

# force CPU: the tests need the virtual 8-device CPU mesh whatever the
# machine holds (a box with a chip would otherwise hand every test
# process the TPU, one process at a time), so set both the env var —
# inherited by the worker processes tests spawn — and the jax config.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the pytest process itself runs without the persistent compile cache
# (engine/compile_cache.py turns it on for every engine process): compile
# counts and cold-vs-warm assertions must mean the same on every run, and
# thousands of throwaway toy programs are not worth writing to disk
jax.config.update("jax_enable_compilation_cache", False)

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(params=[
    pytest.param({}, id="default"),
    pytest.param(
        {"pipeline_decode": True, "decode_steps_per_dispatch": 8},
        id="serving",
    ),
])
def decode_schedule(request) -> dict:
    """EngineConfig fields of the decode schedule: the one the fields'
    defaults give (synchronous single steps), then the one that serves
    (cli.py, engine/worker.py, chip_smoke.py and every benchmark cell:
    pipelined bursts of 8). A test that takes this runs once on each."""
    return request.param


def pytest_collection_modifyitems(items):
    """The layer families' files (a module with a ``FAMILY``: its row of
    ``tests/family_contract.py``) run first. ``--dist load`` deals the
    collection in runs of consecutive tests, the longest at the start
    (a 24th of the collection a worker), so a family's cases land on one
    worker or two, which compile its toy programs once, where the end of
    the collection is dealt two tests at a time to whichever worker is
    free and every worker compiles every family. The order is the same
    on every run."""
    items.sort(key=lambda item: not hasattr(
        getattr(item, "module", None), "FAMILY"))


@pytest.fixture(scope="module", autouse=True)
def _a_familys_programs_go_with_its_file(request):
    """A compiled CPU program holds memory MAPPINGS for as long as JAX
    caches it, and a process may hold 65,530 (``vm.max_map_count``): one
    family's file leaves ~19,000 behind, and nothing after it runs its toy
    spec again. Behind a family's LAST test on a worker, what the worker
    compiled goes; the families run first, so little else is held yet."""
    yield
    if hasattr(request.module, "FAMILY"):
        jax.clear_caches()


@pytest.fixture(scope="module")
def ref(request):
    """The plain reference of the module's ``FAMILY`` (its row of the
    contract, ``tests/family_contract.py``), loaded by path."""
    import family_contract

    return family_contract._reference(request.module.FAMILY)


@pytest.fixture(scope="module")
def model(request):
    """(weights, tokens, the reference's logits) of the module's
    ``FAMILY``, made once a worker."""
    import family_contract

    return family_contract._model(request.module.FAMILY)


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (pytest-asyncio is not installed)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        sig = inspect.signature(fn)
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in sig.parameters
            if name in pyfuncitem.funcargs
        }
        timeout = float(os.environ.get("DYN_TEST_TIMEOUT", "60"))
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=timeout))
        return True
    return None
