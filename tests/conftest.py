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
