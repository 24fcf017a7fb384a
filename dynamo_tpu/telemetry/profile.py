"""On-demand device profiling around engine dispatches.

Role-equivalent of the reference's `nsys`-oriented profiling hooks, TPU-
native: `jax.profiler` traces (viewable in TensorBoard / Perfetto) are
started on demand — `/debug/profile?seconds=N` on the frontend, or
programmatically — into `DYN_PROFILE_DIR`. While a window is open, every
process-level phase (`telemetry/trace.py::phase`: the engine loop's passes
and dispatches, the frontend's synchronous bodies) is also a profiler
annotation `dyn:<name>`, so the host's phases lie on the device timeline's
own clock. The window is
the switch: with none open a phase costs two clock readings.

Everything degrades gracefully without JAX (mocker/echo deployments):
`start()` reports the error instead of raising.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
from typing import Any, Optional

from dynamo_tpu.runtime.logging import get_logger

logger = get_logger("dynamo_tpu.telemetry.profile")

_active: bool = False  # fast flag read by trace.phase on its hot path
_lock = threading.Lock()
_session: Optional[dict[str, Any]] = None


def default_dir() -> str:
    return os.environ.get(
        "DYN_PROFILE_DIR", os.path.join("/tmp", "dynamo_tpu_profile")
    )


def active() -> bool:
    return _active


def start(
    seconds: float = 5.0, out_dir: Optional[str] = None
) -> dict[str, Any]:
    """Open a jax.profiler trace window for `seconds` (auto-stopped by a
    timer thread, so one HTTP poke profiles a live server hands-free).
    Returns {"profile_dir", "seconds"} or {"error": ...}."""
    global _active, _session
    seconds = max(0.1, min(float(seconds), 300.0))
    out_dir = out_dir or default_dir()
    with _lock:
        if _active:
            return {"error": "a profile window is already open", **(_session or {})}
        try:
            import jax
        except Exception as e:  # noqa: BLE001 — no-JAX deployment
            return {"error": f"jax unavailable: {e}"}
        run_dir = os.path.join(out_dir, time.strftime("%Y%m%d-%H%M%S"))
        try:
            os.makedirs(run_dir, exist_ok=True)
            jax.profiler.start_trace(run_dir)
        except Exception as e:  # noqa: BLE001 — profiler init failure
            return {"error": f"profiler start failed: {e}"}
        _active = True
        _session = {"profile_dir": run_dir, "seconds": seconds}
        timer = threading.Timer(seconds, stop)
        timer.daemon = True
        timer.start()
        logger.info("device profile window open: %s (%.1fs)", run_dir, seconds)
        return dict(_session)


def stop() -> Optional[dict[str, Any]]:
    """Close the open window (idempotent). Returns the session info."""
    global _active, _session
    with _lock:
        if not _active:
            return None
        _active = False
        info, _session = _session, None
        try:
            import jax

            jax.profiler.stop_trace()
            logger.info("device profile window closed: %s", (info or {}).get("profile_dir"))
        except Exception:  # noqa: BLE001 — stop after runtime teardown
            logger.exception("profiler stop failed")
        return info


async def run_window(seconds: float, out_dir: Optional[str] = None) -> dict:
    """Async convenience: open a window, sleep through it, return info."""
    info = start(seconds, out_dir)
    if "error" not in info:
        await asyncio.sleep(seconds)
    return info
