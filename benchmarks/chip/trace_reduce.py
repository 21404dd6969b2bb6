"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device-busy time, the device time of named programs and
kernels, and the device's idle gaps with what the host was doing in each.

Planes named ``/device:TPU:<n>`` are devices; on each, the ``XLA Ops``
line holds one event per operation and the ``XLA Modules`` line one per
program run. Host threads are the ``/host:CPU`` plane's lines; the
harness's own spans there are named ``bench.*``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Ev:
    name: str
    start: float            # seconds, trace clock
    dur: float              # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Ev]]]   # plane -> line -> events
    host: List[Ev]                            # bench.* spans


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def short_name(name: str) -> str:
    """An operation's own name: on the TPU an ``XLA Ops`` event is named
    by its whole HLO instruction, ``%<name> = <shape> <op>(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Ev]]] = {}
    host: List[Ev] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                lines[line.name] = [Ev(short_name(e.name),
                                       e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9)
                                    for e in line.events]
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Ev(e.name, e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9))
    return Trace(devices, sorted(host, key=lambda e: e.start))


def union(evs: Sequence[Ev]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    out: List[Tuple[float, float]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1] = (out[-1][0], e.end)
        else:
            out.append((e.start, e.end))
    return out


def busy_events(lines: Dict[str, List[Ev]]) -> List[Ev]:
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def busy_seconds(lines: Dict[str, List[Ev]]) -> float:
    return sum(b - a for a, b in union(busy_events(lines)))


def matching(evs: Sequence[Ev], pattern: str) -> List[Ev]:
    """The events whose own name matches ``pattern``. Only the name: an
    operation's stats carry the name of the program around it, which
    every operation of that program shares."""
    rx = re.compile(pattern)
    return [e for e in evs if rx.search(e.name)]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took the most time, summed over calls
    and averaged over the chips."""
    tot: Dict[str, float] = {}
    for lines in trace.devices.values():
        for e in lines.get(OPS_LINE, []):
            tot[e.name] = tot.get(e.name, 0.0) + e.dur
    k = max(len(trace.devices), 1)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """Idle gaps of the first device between ``lo`` and ``hi`` (trace
    clock), each named by the host span that overlaps it most."""
    if not trace.devices:
        return []
    lines = trace.devices[sorted(trace.devices)[0]]
    gaps, prev = [], lo
    for a, b in union(busy_events(lines)):
        if a > prev:
            gaps.append((prev, min(a, hi)))
        prev = max(prev, b)
    if prev < hi:
        gaps.append((prev, hi))
    out = []
    for a, b in gaps:
        if b <= a:
            continue
        best, label = 0.0, "outside bench spans"
        for h in trace.host:
            ov = min(b, h.end) - max(a, h.start)
            if ov > best:
                best, label = ov, h.name
        out.append((a, b, label))
    return out


def breakdown(trace: Trace, lo: float, hi: float, n: int = 10) -> Dict:
    gaps = sorted(idle_gaps(trace, lo, hi), key=lambda g: -(g[1] - g[0]))
    return {"device_ops": top_ops(trace, n),
            "idle_gaps": [[label, b - a] for a, b, label in gaps[:n]]}


def window(trace: Trace) -> Tuple[float, float]:
    """The span the trace covers: from the first to the last event on any
    device or bench line."""
    evs = [e for lines in trace.devices.values() for line in lines.values()
           for e in line] + trace.host
    if not evs:
        return 0.0, 0.0
    return min(e.start for e in evs), max(e.end for e in evs)
