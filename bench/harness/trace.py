"""From a profiler trace to the intervals the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per device operation and ``XLA Modules`` one per program
execution.  Host spans that the benchmark opens with
``jax.profiler.TraceAnnotation`` (named ``bench.*``) sit on the host
plane's thread lines.  All times are nanoseconds on one clock.

An op event's name is its HLO text (``%fusion.12 = bf16[...] fusion(...)``).
``op_parts`` reads the instruction's name and its opcode from it, so an
op is never classed by the operands it reads: a Pallas kernel is an op
whose opcode is ``custom-call``, whatever the jit named it.  A control-flow op (the
``while`` of a scanned layer stack) is an event that holds the events of
its body: it counts as busy time, but not as an op of its own.

Everything here is plain interval arithmetic on those events, so it is
tested on a small committed trace and reads every later trace the same
way.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start_ns, end_ns)

# host spans that block until the device work they launched is done: the
# device clock is aligned to them
ANCHORS = ("bench.engine_step", "bench.train_step")

# a collective, or the start or done half of an async one
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"send|recv)")
# an async wrapper whose computation is a collective
ASYNC_COLLECTIVE = re.compile(
    r"calls=%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")


@dataclass
class Event:
    name: str
    start: float
    end: float
    module: str = ""            # XLA module (program) the op ran in
    container: bool = False     # holds other ops (a while loop's body)

    @property
    def key(self) -> str:
        return op_key(self.name)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """Device ops and program executions per device, and the host spans."""
    ops: Dict[int, List[Event]]          # device id → ops, sorted by start
    modules: Dict[int, List[Event]]      # device id → program executions
    spans: List[Event]                   # host bench.* spans, by start
    shift_ns: float = 0.0                # added to device times by align

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Interval:
        """The traced window: from the first to the last host span or
        device event."""
        pts = [e.start for e in self.spans] + [e.end for e in self.spans]
        for evs in list(self.ops.values()) + list(self.modules.values()):
            if evs:
                pts += [evs[0].start, max(e.end for e in evs)]
        return (min(pts), max(pts)) if pts else (0.0, 0.0)


def _device_id(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(path: str, span_prefix: str = "bench.",
         anchors: Sequence[str] = ANCHORS) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a log directory),
    with the device events moved onto the host's clock (``align``)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [Event(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [Event(e.name, e.start_ns, e.end_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Event(e.name, e.start_ns, e.end_ns))
    for d in ops:
        ops[d].sort(key=lambda e: (e.start, -e.end))
        mark_containers(ops[d])
        assign_modules(ops[d], modules.get(d, []))
    for d in modules:
        modules[d].sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    tr = Trace(ops=ops, modules=modules, spans=spans)
    tr.shift_ns = align(tr, anchors)
    return tr


def clock_offset(modules: Sequence[Event], anchors: Sequence[Event],
                 max_shift_ns: float = 5e6) -> float:
    """Nanoseconds to add to device times to put them on the host's clock.

    The device planes keep their own clock, off the host's by up to a
    millisecond or so.  Each anchor span (a host span that blocks until
    its device work is done) holds the programs it launched, so the
    offset is the one, within ``max_shift_ns``, that puts the most
    program time inside anchors; among equals, the smallest shift."""
    mods = [(m.start, m.end) for m in modules]
    anc = union((a.start, a.end) for a in anchors)
    if not mods or not anc:
        return 0.0
    cands = {0.0}
    for a0, a1 in anc:
        for m0, m1 in mods:
            for c in (a0 - m0, a1 - m1):
                if abs(c) <= max_shift_ns and m1 - m0 <= a1 - a0:
                    cands.add(c)

    def inside(shift):
        return sum(length(clip(anc, m0 + shift, m1 + shift))
                   for m0, m1 in mods)

    return max(cands, key=lambda c: (round(inside(c)), -abs(c)))


def align(tr: Trace, anchors: Sequence[str]) -> float:
    """Shift every device event onto the host clock; returns the shift."""
    spans = [s for s in tr.spans if s.name in anchors]
    if not spans or not tr.modules:
        return 0.0
    dev = min(tr.modules)
    shift = clock_offset(tr.modules[dev], spans)
    if shift:
        for evs in list(tr.ops.values()) + list(tr.modules.values()):
            for e in evs:
                e.start += shift
                e.end += shift
    return shift


_HLO = re.compile(r"^\s*%?([A-Za-z0-9_.\-]+)\s*=\s*(.*)$", re.S)
# the opcode: the first lower-case word that opens an operand list (shapes
# and layouts hold only upper-case words before a parenthesis: T(8,128))
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")


def op_parts(name: str) -> Tuple[str, str]:
    """``%fusion.12 = bf16[8] fusion(...)`` → (``fusion``, ``fusion``);
    ``%closed_call.3 = bf16[8] custom-call(...)`` → (``closed_call``,
    ``custom-call``): the instruction's own name without its number, and
    its opcode.  A bare name (``all-gather-start.2``) is both."""
    m = _HLO.match(name)
    if not m:
        base = re.sub(r"[.]\d+$", "", (name.split() or [""])[0])
        return base, base
    instr = re.sub(r"[.]\d+$", "", m.group(1))
    op = _OPCODE.search(m.group(2))
    return instr, (op.group(1) if op else instr)


def op_key(name: str) -> str:
    """The name an op's time is summed under: its opcode, and its own
    name where that says more (``custom-call:closed_call``)."""
    instr, opcode = op_parts(name)
    return opcode if instr == opcode else f"{opcode}:{instr}"


def mark_containers(ops: List[Event]) -> None:
    """Flag each op whose interval holds a later op's (ops sorted by start,
    the longer first on ties)."""
    open_: List[Event] = []
    for e in ops:
        while open_ and open_[-1].end <= e.start:
            open_.pop()
        if open_ and e.end <= open_[-1].end:
            open_[-1].container = True
        open_.append(e)


def assign_modules(ops: List[Event], modules: List[Event]) -> None:
    """Tag each op with the program execution whose interval holds its
    start."""
    mods = sorted(modules, key=lambda e: e.start)
    j = 0
    for op in ops:
        while j < len(mods) and mods[j].end <= op.start:
            j += 1
        if j < len(mods) and mods[j].start <= op.start < mods[j].end:
            op.module = mods[j].name


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by the
    (disjoint, sorted) intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi) between the busy intervals."""
    return subtract([(lo, hi)], busy)


# -- reductions the metrics share --------------------------------------------

def busy(trace: Trace, device: int, lo: float = None, hi: float = None
         ) -> List[Interval]:
    """Union of the device's op intervals (optionally clipped)."""
    u = union((e.start, e.end) for e in trace.ops.get(device, []))
    if lo is not None:
        u = clip(u, lo, hi)
    return u


def is_collective(name: str) -> bool:
    _, opcode = op_parts(name)
    if opcode in ("async-start", "async-update", "async-done"):
        return bool(ASYNC_COLLECTIVE.search(name))
    return bool(COLLECTIVE.search(opcode))


def is_kernel(name: str) -> bool:
    return op_parts(name)[1] == "custom-call"


def op_time_by_name(trace: Trace, devices: Sequence[int] = None
                    ) -> List[Tuple[str, float]]:
    """Seconds of device time per op name, averaged over ``devices``,
    largest first."""
    devices = list(devices) if devices is not None else trace.devices
    tot: Dict[str, float] = {}
    for d in devices:
        for e in trace.ops.get(d, []):
            if not e.container:
                tot[e.key] = tot.get(e.key, 0.0) + e.dur
    n = max(1, len(devices))
    return sorted(((k, v / n / 1e9) for k, v in tot.items()),
                  key=lambda kv: -kv[1])


def span_at(spans: Sequence[Event], t: float) -> str:
    """Name of the innermost host span open at ``t`` ("host: no bench
    span" where none is)."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best else "host: no bench span"


def idle_gaps(trace: Trace, device: int, top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest idle gaps of one device inside the traced window,
    each labelled by the host span open at its middle."""
    lo, hi = trace.window()
    g = gaps(busy(trace, device), lo, hi)
    g.sort(key=lambda iv: -(iv[1] - iv[0]))
    return [(span_at(trace.spans, (s + e) / 2), (e - s) / 1e9)
            for s, e in g[:top]]


def breakdown(trace: Trace, devices: Sequence[int], top: int = 10):
    return {"device_ops": [[k, v] for k, v in
                           op_time_by_name(trace, devices)[:top]],
            "idle_gaps": [[k, v] for k, v in
                          idle_gaps(trace, devices[0], top)]}


def busy_seconds(trace: Trace, devices: Sequence[int]) -> float:
    """Seconds in which some op ran, averaged over ``devices``."""
    return sum(length(busy(trace, d)) for d in devices) \
        / max(1, len(devices)) / 1e9


def exposed_collective_ns(trace: Trace, device: int) -> float:
    """Time in which a collective op ran on ``device`` and no other op
    did."""
    coll, comp = [], []
    for e in trace.ops.get(device, []):
        if e.container:
            continue
        (coll if is_collective(e.name) else comp).append((e.start, e.end))
    return length(subtract(union(coll), union(comp)))


def kernel_events(trace: Trace, device: int) -> List[Event]:
    return [e for e in trace.ops.get(device, []) if is_kernel(e.name)]
