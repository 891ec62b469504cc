"""Reductions over the names the serving program gives itself.

The engine (``src/repro/serve/engine.py``) runs its jitted programs under
stable names, so each device op carries the XLA module it ran in
(``jit_serve_decode(<id>)``, ``jit_serve_prefill(<id>)``, ...), and opens
host spans named ``serve.*`` around its phases.

The per-layer readers get the trace the driver loaded (``ctx["trace"]``):
the device's ops and program executions on the host's clock, and the
benchmark's own ``bench.*`` spans.  So a reader finds a phase's device
time by its program's name, and the host time around it by the benchmark
span that holds the phase.  The ``serve.*`` spans are read from a trace
file by ``program_spans``, for the tools and the tests.

A trace of a program that does not name itself holds none of these
names; the readers then give None.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from . import trace as TR

PREFIX = "serve."
PREFILL = ("jit_serve_prefill", "jit_serve_prefill_quiet")
DECODE = ("jit_serve_decode",)
NO_SPAN = "host: no serve span"


@dataclass
class Span(TR.Event):
    """A host span of the program, with the stats it was opened with
    (``req_id``, ``chunk``, ``step_num``)."""
    stats: Dict[str, Any] = field(default_factory=dict)


def program_name(module: str) -> str:
    """``jit_serve_decode(1234)`` → ``jit_serve_decode``."""
    return module.split("(", 1)[0]


def names_itself(tr: TR.Trace, device: int) -> bool:
    """Whether the traced program ran under the engine's names."""
    return any(program_name(m.name).startswith("jit_serve_")
               for m in tr.modules.get(device, []))


def executions(tr: TR.Trace, device: int, names: Sequence[str]
               ) -> List[TR.Event]:
    """The device's executions of the programs ``names``, by start."""
    return [m for m in tr.modules.get(device, [])
            if program_name(m.name) in names]


def busy_in(tr: TR.Trace, device: int, names: Sequence[str]
            ) -> List[TR.Interval]:
    """Union of the device's op intervals inside the programs ``names``."""
    return TR.union((e.start, e.end) for e in tr.ops.get(device, [])
                    if program_name(e.module) in names)


def idle_ns(busy: Sequence[TR.Interval], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which none of ``busy`` ran."""
    return (hi - lo) - TR.length(TR.clip(busy, lo, hi))


def intersect(a: Sequence[TR.Interval], b: Sequence[TR.Interval]
              ) -> List[TR.Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` that ``b`` covers."""
    return TR.subtract(a, TR.subtract(a, b))


# -- the program's host spans, from a trace file ----------------------------

def program_spans(path: str, prefix: str = PREFIX) -> List[Span]:
    """The host spans whose names start with ``prefix`` in an
    ``.xplane.pb`` (or the newest one under a log directory), by start,
    on the host's clock (the clock ``trace.load`` aligns the device to)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(Span(e.name, e.start_ns, e.end_ns,
                                    stats=dict(e.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def leaves(spans: Sequence[Span]) -> List[Span]:
    """The spans that hold no other span of ``spans`` (which nest, as
    one thread's spans do)."""
    s = sorted(spans, key=lambda x: (x.start, -x.end))
    return [a for a, b in zip(s, s[1:] + [None])
            if b is None or b.start >= a.end]


def span_at(spans: Sequence[Span], t: float) -> str:
    """Name of the innermost program span open at ``t``."""
    name = TR.span_at(spans, t)
    return name if name.startswith(PREFIX) else NO_SPAN


def idle_by_leaf(tr: TR.Trace, device: int, within: Sequence[TR.Event],
                 spans: Sequence[Span]) -> Dict[str, float]:
    """Device-idle nanoseconds inside the ``within`` spans, split by the
    name of the leaf program span that covers them; what no leaf covers
    is under ``NO_SPAN``."""
    idle = TR.subtract(TR.union((w.start, w.end) for w in within),
                       TR.busy(tr, device))
    leaf = leaves(spans)
    out: Dict[str, float] = {}
    for name in sorted({s.name for s in leaf}):
        iv = TR.union((s.start, s.end) for s in leaf if s.name == name)
        out[name] = TR.length(intersect(idle, iv))
    out[NO_SPAN] = TR.length(idle) - sum(out.values())
    return out


def leaf_share(by_leaf: Dict[str, float]) -> Optional[float]:
    """Share (%) of the idle time in ``idle_by_leaf`` under a leaf."""
    tot = sum(by_leaf.values())
    if tot <= 0:
        return None
    return 100.0 * (tot - by_leaf.get(NO_SPAN, 0.0)) / tot
