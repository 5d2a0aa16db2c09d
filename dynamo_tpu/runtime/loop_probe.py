"""The event loop's heartbeat: how late a 50 ms sleep wakes.

One process runs the HTTP frontend, the transport, the worker endpoint and
every stream's ``generate()`` on one event loop, beside the engine's step
thread under one interpreter lock. A callback that blocks, a thread that
keeps the lock or a machine that stands still all show as the same thing
from outside: streams stop for seconds. The probe tells the first of them
apart. It sleeps ``INTERVAL_S`` on the loop it serves and books by how much
it woke late:

- ``lags``: (the wake-up's ``time.monotonic_ns``, its lag in us), the ONE
  store of the lags: a bounded ring of the last ``RING`` wake-ups (a
  quarter of an hour). The telemetry collector reads what is new since its
  last sample into its histogram (``engine/telemetry.py``; the probe itself
  touches no Prometheus object); a reader of a window takes the entries
  between the window's two instants (their largest is the window's latest
  wake-up: a running maximum could not be differenced);
- ``ticks``: the wake-ups so far, a reader's cursor into the ring;
- ``stalled_us``: the sum of the lags over 50 ms, a plain int that two
  snapshots difference (``event_loop.stalled_us`` of
  ``InferenceEngine.profile_snapshot()``);
- ``on_stall(lag_us)``, called at the wake-up of a lag over 50 ms: a
  profiled engine writes a ``loop.stall`` annotation from it.

Always on: 20 wake-ups a second. No JAX here.
"""

from __future__ import annotations

import asyncio
import collections
import time
from typing import Callable

from dynamo_tpu.runtime.context import spawn

__all__ = ["LoopProbe", "INTERVAL_S", "STALL_US", "RING"]

INTERVAL_S = 0.05
STALL_US = 50_000  # a lag over this is a stall: a whole interval lost
RING = 18_000  # wake-ups kept: 15 minutes of them


class LoopProbe:
    def __init__(self, on_stall: Callable[[int], None] | None = None) -> None:
        self.lags: collections.deque = collections.deque(maxlen=RING)
        self.ticks = 0
        self.stalled_us = 0
        self._on_stall = on_stall
        self._task: asyncio.Task | None = None

    def start(self) -> None:
        """Begin ticking on the running loop; a probe that ticks stays."""
        if self._task is None or self._task.done():
            self._task = spawn(self._run(), name="loop-probe")

    async def stop(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    def since(self, ticks: int) -> list[tuple[int, int]]:
        """The wake-ups after the first ``ticks`` of them that the ring
        still holds, oldest first."""
        fresh = min(self.ticks - ticks, len(self.lags))
        return [self.lags[i] for i in range(len(self.lags) - fresh,
                                            len(self.lags))]

    def _tick(self, now_ns: int, lag_us: int) -> None:
        self.lags.append((now_ns, lag_us))
        self.ticks += 1
        if lag_us > STALL_US:
            self.stalled_us += lag_us
            if self._on_stall is not None:
                self._on_stall(lag_us)

    async def _run(self) -> None:
        interval_ns = int(INTERVAL_S * 1e9)
        while True:
            due = time.monotonic_ns() + interval_ns
            await asyncio.sleep(INTERVAL_S)
            now = time.monotonic_ns()
            self._tick(now, max(0, now - due) // 1000)
