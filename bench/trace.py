"""The reduction from a profiler trace to the device numbers of a run.

Input: the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  Two kinds of
event are read from it:

- device operations: the events of every ``XLA Ops`` line of each device
  plane (``/device:TPU:<i>``);
- host spans: the events of the host threads on which the benchmark's
  ``bench:`` annotations lie.

The window is the span from the start of the first ``bench:request``
annotation to the end of the last.  On each device, busy time is the length
of the union of its operation intervals inside the window; ``busy_s`` is
the mean over the devices.  Idle gaps are the complement of that union in
the window, each named by what the host was doing at its midpoint: the
innermost ``bench:`` span, then the innermost host event of any kind.
Operation time is summed by name over leaf events (events that hold no other
event of their line), so nested events count once.  An operation is named by
its HLO text without layouts, operand names and fusion attributes
(``op_label``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]        # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:request"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over devices
    devices: int
    op_seconds: Dict[str, float]           # leaf operation time by name
    gap_seconds: Dict[str, float]          # idle time by host activity

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, k: int = 10) -> dict:
        top = lambda d: [[n, s] for n, s in sorted(
            d.items(), key=lambda kv: -kv[1])[:k]]
        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.gap_seconds)}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_label(hlo: str) -> str:
    """``fusion.95 = f32[16,1,2000] fusion(f32[16,2000,10001], ...)`` from
    the HLO text of a device event."""
    t = re.sub(r"\{[^{}]*\}", "", hlo)
    t = re.sub(r"\s%[\w.\-]+", "", t)
    t = t.split(", kind=")[0].split(", calls=")[0]
    return t.lstrip("%")[:160]


def read_xplane(path: str) -> Tuple[Dict[str, List[Interval]],
                                    List[Interval]]:
    """({device plane: its operation events}, host events of the threads
    that carry ``bench:`` spans)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_label(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host.extend(evs)
    return {k: v for k, v in devices.items() if v}, host


def leaves(events: Sequence[Interval]) -> List[Interval]:
    """Events that contain no other event of the same list."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, e) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e or nxt[2] > e:
            out.append((n, s, e))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def host_activity(host: Sequence[Interval], t: float) -> str:
    """What the host was doing at time t: the innermost bench span, and the
    innermost host event of any kind where that is another one."""
    covering = [(e - s, n) for n, s, e in host if s <= t < e]
    if not covering:
        return "no host span"
    spans = [c for c in covering if c[1].startswith(SPAN_PREFIX)]
    inner = min(covering)[1]
    name = min(spans)[1][len(SPAN_PREFIX):] if spans else "outside bench"
    if not inner.startswith(SPAN_PREFIX):
        name = f"{name} > {inner}"
    return name[:120]


def reduce(devices: Dict[str, List[Interval]],
           host: Sequence[Interval]) -> Summary:
    marks = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not marks:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    if not devices:
        raise ValueError("no device operation in the trace")
    busy_total = 0.0
    op_ns: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    for evs in devices.values():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                   if e > w0 and s < w1]
        for n, s, e in leaves(clipped):
            op_ns[n] = op_ns.get(n, 0.0) + (e - s)
        busy = union([(s, e) for _, s, e in clipped])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = host_activity(host, 0.5 * (g0 + g1))
                gap_ns[name] = gap_ns.get(name, 0.0) + (g1 - g0)
    nd = len(devices)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / nd * 1e-9, devices=nd,
                   op_seconds={k: v / nd * 1e-9 for k, v in op_ns.items()},
                   gap_seconds={k: v / nd * 1e-9 for k, v in gap_ns.items()})


def summarize(log_dir: str) -> Summary:
    devices, host = read_xplane(find_xplane(log_dir))
    return reduce(devices, host)
