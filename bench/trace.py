"""Reduction of a profiler trace to the device numbers the metrics read.

A run with ``--trace 1`` records its measured window with the JAX profiler,
inside a host span named ``bench.window``.  :func:`read_xplane` keeps of
that trace only what the reduction needs:

- per device plane (``/device:TPU:<k>``), the events of its ``XLA Ops``
  line: the operations that ran on the device, named by HLO instruction
  and opcode (``%ec_matmul.1 custom-call``), with start and end on the
  trace's clock;
- the host spans the benchmark itself opens (names beginning ``bench.``),
  which say what the host was doing while the device waited.

The profiler stamps device events on a clock of their own, which on a
v5e lay 0.7 to 1.4 ms off the host's, either way.  :func:`aligned` moves
each device's events onto the host's clock before anything is reduced.

The reduction: busy time is the union of a device's operation intervals
inside the window, averaged over the devices; idle share is one minus busy
over window; a kernel's time is the sum of its events' durations; an idle
gap is a stretch of the window in which device 0 runs nothing, named by the
innermost benchmark span around its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SEND_SPAN = "bench.send"
HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# An op event's name is its HLO instruction: "%name = <shape> <opcode>(...".
HLO_OP = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int      # ns on the trace's clock
    end: int

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, List[Event]]
    host: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        unpack = lambda evs: [Event(n, int(s), int(e)) for n, s, e in evs]
        return cls(window=tuple(d["window"]), host=unpack(d["host"]),
                   devices={k: unpack(v) for k, v in d["devices"].items()})


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(text: str) -> str:
    """``"%ec_matmul.1 custom-call"`` of an op event whose name is the HLO
    instruction ``%ec_matmul.1 = f32[...] custom-call(...), ...``."""
    m = HLO_OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def read_xplane(path: str, chips: int) -> Trace:
    """The device operations of the first ``chips`` devices (those a cell
    uses) and the benchmark spans of one trace file, on the host's clock."""
    return aligned(collect(path, chips))


def collect(path: str, chips: int) -> Trace:
    """What :func:`read_xplane` keeps of a trace file, each event on the
    clock the profiler stamped it with."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) >= chips:
            continue
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(op_name(e.name), int(e.start_ns),
                                     int(e.start_ns + e.duration_ns))
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e.start)
        else:
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    if not devices or not any(devices.values()):
        names = {p.name: [l.name for l in p.lines] for p in data.planes}
        raise RuntimeError(f"the trace holds no {OPS_LINE!r} events on a "
                           f"TPU plane; planes and lines: {names}")
    window = (windows[0].start, windows[0].end)
    return Trace(window=window, devices=devices,
                 host=sorted(host, key=lambda e: e.start))


def aligned(trace: Trace) -> Trace:
    """The trace with each device's events shifted so that its first
    operation starts with the window's first ``bench.send`` span.

    The device runs nothing in the window before that send (the harness
    draws the first request, and waits for it, before the profiler starts),
    so its first operation is the send's; it truly starts later by the time
    the host takes to dispatch it, and that much early the events now lie."""
    sends = [e.start for e in trace.host if e.name == SEND_SPAN
             and trace.window[0] <= e.start < trace.window[1]]
    if not sends:
        raise RuntimeError(f"the window holds no {SEND_SPAN} span")
    devices = {}
    for dev, evs in trace.devices.items():
        shift = min(sends) - min(e.start for e in evs) if evs else 0
        devices[dev] = [Event(e.name, e.start + shift, e.end + shift)
                        for e in evs]
    return Trace(window=trace.window, devices=devices, host=trace.host)


def merged(events: List[Event], window: Tuple[int, int]
           ) -> List[Tuple[int, int]]:
    """The union of the events' intervals, clipped to ``window``."""
    lo, hi = window
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: List[Tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran on the device,
    averaged over the devices."""
    per = [sum(e - s for s, e in merged(evs, trace.window)) * 1e-9
           for evs in trace.devices.values()]
    return sum(per) / len(per)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def _in_window(trace: Trace, events: List[Event]) -> List[Event]:
    lo, hi = trace.window
    return [e for e in events if e.start >= lo and e.end <= hi]


def kernel_events(trace: Trace, pattern: str) -> Dict[str, List[Event]]:
    """Per device, the window's events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return {dev: [e for e in _in_window(trace, evs) if rx.search(e.name)]
            for dev, evs in trace.devices.items()}


def busy_share_of(trace: Trace, pattern: str) -> Optional[float]:
    """The share of the devices' busy time spent in events matching
    ``pattern`` (for example collectives); None where none ran."""
    hits = kernel_events(trace, pattern)
    if not any(hits.values()):
        return None
    part = sum(sum(e - s for s, e in merged(v, trace.window))
               for v in hits.values())
    whole = sum(sum(e - s for s, e in merged(v, trace.window))
                for v in trace.devices.values())
    return part / whole


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds per operation name, each event less the events nested in it
    (a loop's event holds its body's operations)."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [event, seconds of children]

    def close(item):
        ev, child = item
        out[ev.name] = out.get(ev.name, 0.0) + ev.seconds - child
        if stack:
            stack[-1][1] += ev.seconds

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def top_ops(trace: Trace, count: int = 10) -> List[List]:
    """The device operations that took most self time in the window,
    averaged over the devices."""
    total: Dict[str, float] = {}
    for evs in trace.devices.values():
        for name, sec in self_times(_in_window(trace, evs)).items():
            total[name] = total.get(name, 0.0) + sec / len(trace.devices)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:count]
    return [[name, sec] for name, sec in ranked]


def idle_gaps(trace: Trace, count: int = 10) -> List[List]:
    """The longest stretches of the window in which the first device ran
    nothing, each named by what the host was doing at its midpoint."""
    first = sorted(trace.devices)[0]
    busy = merged(trace.devices[first], trace.window)
    lo, hi = trace.window
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    spans = [e for e in trace.host if e.name != WINDOW_SPAN]
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        around = [h for h in spans if h.start <= mid < h.end]
        name = min(around, key=lambda h: h.end - h.start).name \
            if around else "outside any benchmark span"
        named.append([name, (e - s) * 1e-9])
    return sorted(named, key=lambda g: -g[1])[:count]


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
