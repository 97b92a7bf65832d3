"""Reduce a profiler trace of the measured window to device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, and the compiled
HLO text of the programs the window ran. Each device plane's "XLA Ops"
line holds one event per HLO instruction run, named by the instruction's
text (``%name = <type> opcode(...)``). The compiled HLO says which of
those instructions are Mosaic kernels (``custom_call_target=
"tpu_custom_call"``) and which are collectives; an event whose
instruction it does not hold is judged by its own text. Everything else
on the device is XLA's own work. The benchmark's host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane on the same
clock; the span named ``window`` bounds the traced window.

Busy time is the union of the op intervals of each chip inside the
window, averaged over the chips; idle share is one minus busy over the
window. Idle gaps are attributed to the innermost host span that covers
their midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# One HLO instruction line: "%name = <type> opcode(...)".
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_OPCODE = re.compile(r"\s(?P<op>[a-z][\w\-]*)\(")
# Lines of a device plane that hold one event per op (TPU: "XLA Ops").
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds on the trace clock
    end: float


def _kind_of(line: str) -> Optional[Tuple[str, str]]:
    """(instruction name, kind) of one HLO instruction's text."""
    m = _INSTR.match(line)
    if not m:
        return None
    op = _OPCODE.search(" " + m.group("rest"))
    if not op:
        return None
    opcode = op.group("op")
    if opcode == "custom-call" and "tpu_custom_call" in line:
        kind = "kernel"
    elif opcode.replace("-start", "").replace("-done", "") in COLLECTIVES:
        kind = "collective"
    else:
        kind = "xla"
    return m.group("name"), kind


def hlo_kinds(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """Instruction name -> "kernel" | "collective" | "xla", over every
    compiled program the window ran."""
    kinds: Dict[str, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            hit = _kind_of(line)
            if hit:
                kinds[hit[0]] = hit[1]
    return kinds


def op_name(event_name: str) -> str:
    """The instruction name an event's text starts with."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or event_name


def classify(event_name: str, kinds: Dict[str, str]) -> str:
    """Kind of a device event: by its instruction in the compiled HLO,
    else by the event's own text, else XLA."""
    name = op_name(event_name)
    if name in kinds:
        return kinds[name]
    hit = _kind_of(event_name)
    if hit:
        return hit[1]
    if any(name.startswith(c) for c in COLLECTIVES):
        return "collective"
    return "xla"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


@dataclasses.dataclass
class Reduced:
    """What a per-layer reader takes from the trace."""

    window_s: float
    busy_s: float  # mean over chips
    chips: int
    kind_s: Dict[str, float]  # kernel / collective / xla, mean over chips
    op_s: Dict[str, float]  # per op name, mean over chips
    idle_by_span: Dict[str, float]  # seconds of idle, by host span
    n_events: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(devices: Sequence[Sequence[Event]], spans: Sequence[Event],
           window: Interval, kinds: Dict[str, str]) -> Reduced:
    """Reduce each chip's op events to busy, per-kind and per-op time
    inside ``window``, and attribute the idle gaps to host spans."""
    lo, hi = window
    n = max(len(devices), 1)
    busy = 0.0
    kind_s: Dict[str, float] = {"kernel": 0.0, "collective": 0.0, "xla": 0.0}
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    inner = sorted(spans, key=lambda s: s.end - s.start)
    n_events = 0
    for evs in devices:
        evs = clip(evs, lo, hi)
        n_events += len(evs)
        merged = union([(e.start, e.end) for e in evs])
        busy += sum(e - s for s, e in merged)
        for e in evs:
            d = e.end - e.start
            kind_s[classify(e.name, kinds)] += d / n
            name = op_name(e.name)
            op_s[name] = op_s.get(name, 0.0) + d / n
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            owner = next((sp.name for sp in inner
                          if sp.start <= mid <= sp.end), "outside spans")
            idle[owner] = idle.get(owner, 0.0) + (e - s) / n
    return Reduced(window_s=hi - lo, busy_s=busy / n, chips=len(devices),
                   kind_s=kind_s, op_s=op_s, idle_by_span=idle,
                   n_events=n_events)


def load(path: str) -> Tuple[List[List[Event]], List[Event]]:
    """(op events of each device plane, host span events) of an
    ``.xplane.pb``, on one clock, in seconds."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: List[List[Event]] = []
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = [Event(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name in OP_LINES
                   for e in line.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events]
    return devices, spans


def window_of(spans: Sequence[Event], name: str = "window"
              ) -> Optional[Interval]:
    """Bounds of the host span that marks the traced window."""
    hits = [s for s in spans if s.name == name]
    if not hits:
        return None
    return (min(s.start for s in hits), max(s.end for s in hits))


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries of a name -> seconds dict."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
