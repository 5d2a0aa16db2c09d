"""Opt-in uvloop installation for process entrypoints.

``DYN_UVLOOP=1`` swaps the default asyncio event loop for uvloop at the
frontend/worker/gateway entrypoints — worth ~20-40% on the syscall-bound
stream plane (benchmarks.stream_bench measures it on this box). The
dependency is deliberately optional: when uvloop isn't installed (it is
not vendored) or the platform doesn't support it, we log once and fall
back to the stock loop. Library code must never call this — only process
``main()``s, before their ``asyncio.run``.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger("dynamo.eventloop")


def maybe_install_uvloop(env: dict[str, str] | None = None) -> bool:
    """Install uvloop as the event-loop policy if DYN_UVLOOP asks for it.

    Returns True iff uvloop is now the policy; falls back cleanly (False)
    when the knob is off or uvloop is unavailable.
    """
    raw = (env or os.environ).get("DYN_UVLOOP", "")
    if raw.lower() not in ("1", "true", "yes", "on"):
        return False
    try:
        import uvloop
    except ImportError:
        log.warning("DYN_UVLOOP=1 but uvloop is not installed; using asyncio")
        return False
    uvloop.install()
    log.info("uvloop installed as event-loop policy")
    return True
