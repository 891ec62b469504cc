"""What the drivers share: the run's result, a compile counter, the
profiler window and the checks with their limits."""

from __future__ import annotations

import contextlib
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Optional


@dataclass
class Run:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, float]              # end-to-end (trace off)
    memory_peak_bytes: int
    checks: Dict[str, Dict[str, Any]]      # name → value, limit, rule
    ctx: Dict[str, Any] = field(default_factory=dict)   # for the readers
    extra: Dict[str, Any] = field(default_factory=dict)  # printed too
    outputs: Dict[str, Any] = field(default_factory=dict)  # for the tools


def check(value: float, limit: Optional[float], rule: str
          ) -> Dict[str, Any]:
    """One compared number: it passes while ``value <= limit``.  A limit
    not yet set from readings (None) passes nothing."""
    return {"value": float(value),
            "limit": None if limit is None else float(limit), "rule": rule,
            "ok": limit is not None and bool(value <= limit)}


def all_ok(checks: Dict[str, Dict[str, Any]]) -> bool:
    return all(c["ok"] for c in checks.values())


def log(*a) -> None:
    print("bench:", *a, file=sys.stderr, flush=True)


class Compiles:
    """Counts the backend compiles that happen from now on: a window that
    compiles is not measuring the steady state."""

    def __init__(self):
        import jax
        self.count = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` so far."""
    best = 0
    for d in devices:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0)))
    return best


@contextlib.contextmanager
def profiled(enabled: bool, workdir: Path, tag: str):
    """Profile the block when ``enabled``; yields the log directory (None
    when off).  The trace is written inside the checkout and removed by
    ``discard``."""
    if not enabled:
        yield None
        return
    import jax
    d = Path(workdir) / ".bench_trace" / tag
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def discard(d: Optional[Path]) -> None:
    if d is not None:
        shutil.rmtree(d, ignore_errors=True)


def span(enabled: bool, name: str):
    """A host span in the profiler's trace (a no-op when tracing is off)."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
