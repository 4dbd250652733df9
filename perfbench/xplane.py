"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` reads the file with ``jax.profiler.ProfileData`` and keeps, for every
device plane (``/device:TPU:n``), the events of its ``XLA Ops`` and
``XLA Modules`` lines, and from the host planes the named spans (the
program's ``prefill`` / ``decode`` and the harness's own ``hb.*``). Everything
after that is arithmetic on plain lists, which the tests check on hand-made
events and on a small recorded trace:

* busy time is the *union* of the intervals in which an operation runs: the
  ``XLA Ops`` line nests (a ``while`` spans its body's operations), so a sum
  of durations would count time twice;
* time by name is *self* time: an operation's duration less what the
  operations nested inside it cover;
* an idle gap is named after the narrowest host span that covers its middle.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # (name, start_ns, duration_ns)
Interval = Tuple[float, float]              # (start_ns, end_ns)

_OP_LINE = "XLA Ops"
_MODULE_LINE = "XLA Modules"
_HOST_SPANS = ("hb.", "prefill", "decode")


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host_spans: List[Event]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, DeviceTrace] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace([], [])
            for line in plane.lines:
                if line.name == _OP_LINE:
                    dev.ops = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == _MODULE_LINE:
                    dev.modules = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
            if dev.ops or dev.modules:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(_HOST_SPANS):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return Trace(devices, host)


# -- names --------------------------------------------------------------------

_OP_NAME = re.compile(r"^%?([^\s=(]+)")
_TRAILING_ID = re.compile(r"(\.\d+)+$")


def op_name(event_name: str) -> str:
    """``%flash_fwd.13 = (bf16[...]) custom-call(...)`` -> ``flash_fwd``;
    ``jit_decode(6590898799803967883)`` -> ``jit_decode``."""
    m = _OP_NAME.match(event_name)
    name = m.group(1) if m else event_name
    return _TRAILING_ID.sub("", name)


# -- intervals ------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same instants."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def as_intervals(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def window_of(trace: Trace) -> Optional[Interval]:
    """From the first device operation's start to the last one's end, over
    all devices: the traced window."""
    starts, ends = [], []
    for dev in trace.devices.values():
        for _, s, d in dev.ops or dev.modules:
            starts.append(s)
            ends.append(s + d)
    return (min(starts), max(ends)) if starts else None


def busy_by_device(trace: Trace) -> Dict[str, float]:
    """Nanoseconds in which some operation ran, per device."""
    return {name: total(union(as_intervals(dev.ops or dev.modules)))
            for name, dev in trace.devices.items()}


def self_times(events: Sequence[Event]) -> List[Event]:
    """Each event with its duration less the time its nested events cover
    (events of one line nest properly: a child lies within its parent)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] for e in events]
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], events[i][1], max(self_ns[i], 0.0))
            for i in range(len(events))]


def time_by_name(events: Sequence[Event]) -> Dict[str, Tuple[float, int]]:
    """name -> (nanoseconds of self time, count), by :func:`op_name`."""
    out: Dict[str, List[float]] = {}
    for name, _, d in self_times(events):
        rec = out.setdefault(op_name(name), [0.0, 0])
        rec[0] += d
        rec[1] += 1
    return {k: (v[0], int(v[1])) for k, v in out.items()}


def events_within(events: Sequence[Event], spans: Sequence[Interval]) -> List[Event]:
    """Events that lie wholly inside one of ``spans``."""
    spans = sorted(spans)
    out = []
    for ev in events:
        s, e = ev[1], ev[1] + ev[2]
        for lo, hi in spans:
            if lo <= s and e <= hi:
                out.append(ev)
                break
    return out


def module_runs(dev: DeviceTrace, name: str) -> List[Event]:
    """Executions of the jitted program ``name`` on this device."""
    return [ev for ev in dev.modules if op_name(ev[0]) == name]


def whole_runs(runs: Sequence[Event], tolerance: float = 0.02) -> List[Event]:
    """The executions that the trace holds whole, for a program whose every
    execution does the same work: the profiler cuts the execution in flight
    when it starts (and may cut the last), and such a piece is shorter than
    the median by more than ``tolerance``."""
    if not runs:
        return []
    durations = sorted(d for _, _, d in runs)
    median = durations[len(durations) // 2]
    return [ev for ev in runs if ev[2] >= (1.0 - tolerance) * median]


def kernel_seconds_per_run(trace: Trace, module: str, kernels: Sequence[str]
                           ) -> Optional[float]:
    """Device seconds of the named ``kernels`` per execution of ``module``,
    over all devices, counting only the executions the trace holds whole and
    the kernel calls that lie inside them."""
    total_ns, runs_n = 0.0, 0
    for dev in trace.devices.values():
        runs = whole_runs(module_runs(dev, module))
        by_name = time_by_name(events_within(dev.ops, as_intervals(runs)))
        total_ns += sum(by_name.get(k, (0.0, 0))[0] for k in kernels)
        runs_n += len(runs)
    return total_ns / runs_n / 1e9 if runs_n and total_ns > 0 else None


# -- idle gaps --------------------------------------------------------------------

def idle_gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    gaps, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def name_gap(gap: Interval, host_spans: Sequence[Event]) -> str:
    mid = 0.5 * (gap[0] + gap[1])
    best, best_d = "unattributed", float("inf")
    for name, s, d in host_spans:
        if s <= mid <= s + d and d < best_d:
            best, best_d = name, d
    return best


def gaps_by_host_span(trace: Trace, device: str) -> Dict[str, float]:
    """Idle nanoseconds of ``device`` inside the traced window, by what the
    host was doing in the middle of each gap."""
    window = window_of(trace)
    if window is None:
        return {}
    dev = trace.devices[device]
    busy = union(as_intervals(dev.ops or dev.modules))
    out: Dict[str, float] = {}
    for gap in idle_gaps(busy, window):
        key = name_gap(gap, trace.host_spans)
        out[key] = out.get(key, 0.0) + (gap[1] - gap[0])
    return out


# -- the summary every traced run carries -------------------------------------------

def summary(trace: Trace) -> Dict:
    """``busy_s`` (mean over devices), ``window_s``, the fullest-idle
    device's idle share, and the breakdown: the ten operations with most self
    time (summed over devices) and the ten largest idle totals by host span
    (on the device that idles most)."""
    window = window_of(trace)
    if window is None:
        return {}
    busy = busy_by_device(trace)
    window_ns = window[1] - window[0]
    idlest = min(busy, key=busy.get)
    ops: Dict[str, float] = {}
    for dev in trace.devices.values():
        for name, (ns, _) in time_by_name(dev.ops).items():
            ops[name] = ops.get(name, 0.0) + ns
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps_by_host_span(trace, idlest).items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share_max": 1.0 - busy[idlest] / window_ns,
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps],
        },
    }
