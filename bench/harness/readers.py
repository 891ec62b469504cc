"""Reductions that several per-layer metric readers share."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from . import flops as F
from . import trace as TR

def step_spans(ctx: Dict[str, Any], name: str) -> List[TR.Event]:
    return [s for s in ctx["trace"].spans if s.name == name]


def decode_modules(tr: TR.Trace, device: int) -> set:
    """Names of the programs that ran the paged-attention kernel: the
    decode step (the only program with a custom call on the serve path)."""
    return {e.module for e in TR.kernel_events(tr, device) if e.module}


def per_step_decode(ctx: Dict[str, Any]) -> List[Tuple[float, float, int, int]]:
    """For each traced decode tick that ran the decode program: (device ns
    of the decode program, device ns of its kernel ops, live rows, live
    K/V tokens).  The ticks' host spans pair, in order, with the work
    the driver recorded as each began."""
    tr = ctx["trace"]
    dev = ctx["devices"][0]
    spans = step_spans(ctx, "bench.decode_tick")
    ticks = ctx["decode_ticks"]
    names = decode_modules(tr, dev)
    if not names or not spans:
        return []
    mods = [e for e in tr.modules.get(dev, []) if e.name in names]
    kern = [e for e in TR.kernel_events(tr, dev) if e.module in names]
    out = []
    for sp, (rows, kv) in zip(spans, ticks):
        if rows == 0:
            continue
        m_ns = sum(e.dur for e in mods if sp.start <= e.start < sp.end)
        k_ns = sum(e.dur for e in kern if sp.start <= e.start < sp.end)
        if m_ns > 0:
            out.append((m_ns, k_ns, rows, kv))
    return out


def decode_share(ctx: Dict[str, Any], kernel: bool) -> Optional[float]:
    rows = per_step_decode(ctx)
    model = F.Dense.of(ctx["config"])
    need = took = 0.0
    for m_ns, k_ns, r, kv in rows:
        work = (model.paged_decode_kernel(r, kv) if kernel
                else model.decode_step(r, kv))
        t = k_ns if kernel else m_ns
        if t <= 0:
            continue
        need += F.roofline_s(work, ctx["peak"])
        took += t / 1e9
    if took <= 0:
        return None
    return 100.0 * need / took


def idle_share(ctx: Dict[str, Any]) -> Optional[float]:
    if ctx.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
