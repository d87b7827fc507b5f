"""The reduction from a profiler trace to the program's own layers: device
time by the program's named scopes, and device idle time by its host spans.

Input: the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  JAX's
``ProfileData`` gives an event's own stats, but not the stats of the
event's metadata, where a device operation keeps its ``tf_op`` (the HLO
``op_name``, the path that ``jax.named_scope`` extends) and its
``program_id``.  So this module decodes the protobuf wire format itself,
with the field numbers of ``xplane.proto`` (XSpace, XPlane, XLine, XEvent,
XEventMetadata, XStatMetadata, XStat).  Two kinds of event are read:

- device operations: the events of every ``XLA Ops`` line of each device
  plane (``/device:TPU:<i>``), with the ``tf_op`` of their metadata;
- host spans: the events of the host threads that carry ``bench:`` or
  ``decsvm:`` spans.

The window is that of ``bench/trace.py``: from the start of the first
``bench:request`` span to the end of the last.  Times are those of
``ProfileData`` (whole nanoseconds), so both readers cut the same window.

- ``scope_seconds``: leaf operation time (``trace.leaves``) in the window
  by the innermost ``decsvm.`` segment of the operation's ``tf_op``, and
  ``unscoped`` for the rest.  XLA joins the names of instructions it merged
  with ``;``; the first name that holds a scope decides.
- ``span_idle_seconds``: the device-idle intervals of the window (the
  complement of the union of operation intervals), cut at every edge of a
  ``decsvm:`` span.  Each piece goes to the innermost ``decsvm:`` span that
  covers it, else to ``outside program``.  No interval is named by its
  midpoint.

Both are means over the device planes, as in ``bench/trace.py``.

The program's scope and span names are spelled here and not imported from
the program (``repro.core.trace``): a rename in the program then shows up
as a scope that reads nothing here, rather than the reader silently
following it.
"""
from __future__ import annotations

import bisect
import dataclasses
import struct
from typing import Dict, List, NamedTuple, Sequence, Tuple

from bench import trace

SCOPE_PREFIX = "decsvm."
SPAN_PREFIX = "decsvm:"
UNSCOPED = "unscoped"
OUTSIDE = "outside program"
# The program's scopes and spans, spelled here (see the module's doc).
SCOPES = ("decsvm.round", "decsvm.kkt_check", "decsvm.rho", "decsvm.bic")
SPANS = ("decsvm:lambda_grid", "decsvm:path_program", "decsvm:bic_table")

Interval = trace.Interval                  # (name, start_ns, end_ns)


class Op(NamedTuple):
    """A device operation: its HLO text, times, and metadata stats."""
    name: str
    start_ns: float
    end_ns: float
    tf_op: str
    program_id: int


# -- the protobuf wire format ------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, the raw bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 1:
            val, i = buf[i:i + 8], i + 8
        elif kind == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, val


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


# Field numbers of xplane.proto.
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_MD_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
EMD_NAME, EMD_STATS = 2, 5
SMD_ID, SMD_NAME = 1, 2
STAT_MD_ID, STAT_DOUBLE, STAT_UINT64, STAT_INT64 = 1, 2, 3, 4
STAT_STR, STAT_BYTES, STAT_REF = 5, 6, 7
MAP_KEY, MAP_VALUE = 1, 2


def _stat(buf, stat_names: Dict[int, str]):
    """(name, value) of one XStat."""
    name, val = None, None
    for f, v in _fields(buf):
        if f == STAT_MD_ID:
            name = stat_names.get(v)
        elif f == STAT_DOUBLE:
            val = struct.unpack("<d", v)[0]
        elif f == STAT_UINT64:
            val = v
        elif f == STAT_REF:                # a string kept as stat metadata
            val = stat_names.get(v)
        elif f == STAT_INT64:
            val = _int64(v)
        elif f == STAT_STR:
            val = bytes(v).decode("utf-8", "replace")
        elif f == STAT_BYTES:
            val = bytes(v)
    return name, val


def _map_entries(raw: Sequence[bytes]):
    for entry in raw:
        d = dict(_fields(entry))
        yield d.get(MAP_KEY, 0), d.get(MAP_VALUE, b"")


def _plane(buf):
    name, lines, emd, smd = "", [], [], []
    for f, v in _fields(buf):
        if f == PLANE_NAME:
            name = bytes(v).decode()
        elif f == PLANE_LINES:
            lines.append(v)
        elif f == PLANE_EVENT_MD:
            emd.append(v)
        elif f == PLANE_STAT_MD:
            smd.append(v)
    return name, lines, emd, smd


def _line(buf):
    name, ts, events = "", 0, []
    for f, v in _fields(buf):
        if f == LINE_NAME:
            name = bytes(v).decode()
        elif f == LINE_TIMESTAMP_NS:
            ts = _int64(v)
        elif f == LINE_EVENTS:
            events.append(v)
    return name, ts, events


def _events(ts_ns: int, raw: Sequence[bytes]):
    """(metadata id, start_ns, end_ns) of each event, on the clock of
    ``ProfileData``: whole nanoseconds."""
    for buf in raw:
        md = off = dur = 0
        for f, v in _fields(buf):
            if f == EVENT_MD_ID:
                md = v
            elif f == EVENT_OFFSET_PS:
                off = _int64(v)
            elif f == EVENT_DURATION_PS:
                dur = _int64(v)
        start = ts_ns + off // 1000
        yield md, float(start), float(start + dur // 1000)


def read_xplane(path: str) -> Tuple[Dict[str, List[Op]], List[Interval]]:
    """({device plane: its operations}, host events of the threads that
    carry ``bench:`` or ``decsvm:`` spans)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices: Dict[str, List[Op]] = {}
    host: List[Interval] = []
    for f, pbuf in _fields(space):
        if f != SPACE_PLANES:
            continue
        name, lines, emd, smd = _plane(pbuf)
        if name.startswith(trace.DEVICE_PREFIX):
            ops = devices.setdefault(name, [])
            stat_names = {}
            for _, v in _map_entries(smd):
                d = dict(_fields(v))
                stat_names[d.get(SMD_ID, 0)] = bytes(
                    d.get(SMD_NAME, b"")).decode()
            meta = {}
            for key, v in _map_entries(emd):
                md_name, stats = "", {}
                for g, w in _fields(v):
                    if g == EMD_NAME:
                        md_name = bytes(w).decode("utf-8", "replace")
                    elif g == EMD_STATS:
                        k, val = _stat(w, stat_names)
                        stats[k] = val
                meta[key] = (md_name, stats.get("tf_op") or "",
                             stats.get("program_id") or 0)
            for lbuf in lines:
                lname, ts, evs = _line(lbuf)
                if lname != trace.OPS_LINE:
                    continue
                for md, s, e in _events(ts, evs):
                    md_name, tf_op, prog = meta.get(md, ("", "", 0))
                    ops.append(Op(md_name, s, e, tf_op, prog))
        elif name.startswith("/host:"):
            names = {}
            for key, v in _map_entries(emd):
                names[key] = bytes(dict(_fields(v)).get(EMD_NAME, b"")
                                   ).decode("utf-8", "replace")
            for lbuf in lines:
                _, ts, evs = _line(lbuf)
                line = [(names.get(md, ""), s, e)
                        for md, s, e in _events(ts, evs)]
                if any(n.startswith((trace.SPAN_PREFIX, SPAN_PREFIX))
                       for n, _, _ in line):
                    host.extend(line)
    return {k: v for k, v in devices.items() if v}, host


# -- the reduction -----------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The innermost ``decsvm.`` segment of an op's name path, else
    ``unscoped``.  ``tf_op`` is ``<op_name>:<op_type>``; merged
    instructions join their op_names with ``;``."""
    for name in tf_op.rsplit(":", 1)[0].split(";"):
        found = [s for s in name.split("/") if s.startswith(SCOPE_PREFIX)]
        if found:
            return found[-1]
    return UNSCOPED


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: int
    scope_seconds: Dict[str, float]        # leaf op time by scope
    span_idle_seconds: Dict[str, float]    # idle time by decsvm: span

    @property
    def leaf_s(self) -> float:
        return sum(self.scope_seconds.values())

    def share(self, scope: str) -> float:
        """The scope's share of all leaf time in the window (0 to 1)."""
        total = self.leaf_s
        return self.scope_seconds.get(scope, 0.0) / total if total else 0.0


def _segments(spans: Sequence[Interval], w0: float, w1: float):
    """Cut points over [w0, w1] at every span edge, and the innermost span
    covering each piece between consecutive cuts (``OUTSIDE`` if none)."""
    cuts = sorted({w0, w1} | {x for _, s, e in spans for x in (s, e)
                              if w0 < x < w1})
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(e - s, n) for n, s, e in spans if s <= a and b <= e]
        labels.append(min(covering)[1] if covering else OUTSIDE)
    return cuts, labels


def reduce(devices: Dict[str, List[Op]],
           host: Sequence[Interval]) -> Summary:
    marks = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    if not marks:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    if not devices:
        raise ValueError("no device operation in the trace")
    spans = [h for h in host if h[0].startswith(SPAN_PREFIX)]
    cuts, labels = _segments(spans, w0, w1)
    scope_ns: Dict[str, float] = {}
    idle_ns: Dict[str, float] = {}
    for ops in devices.values():
        clipped = [(scope_of(o.tf_op), max(o.start_ns, w0),
                    min(o.end_ns, w1)) for o in ops
                   if o.end_ns > w0 and o.start_ns < w1]
        for scope, s, e in trace.leaves(clipped):
            scope_ns[scope] = scope_ns.get(scope, 0.0) + (e - s)
        busy = trace.union([(s, e) for _, s, e in clipped])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            k = bisect.bisect_right(cuts, g0) - 1
            while g0 < g1:
                piece_end = min(g1, cuts[k + 1])
                idle_ns[labels[k]] = (idle_ns.get(labels[k], 0.0)
                                      + piece_end - g0)
                g0, k = piece_end, k + 1
    nd = len(devices)
    return Summary(window_s=(w1 - w0) * 1e-9, devices=nd,
                   scope_seconds={k: v / nd * 1e-9
                                  for k, v in scope_ns.items()},
                   span_idle_seconds={k: v / nd * 1e-9
                                      for k, v in idle_ns.items()})


def summarize(log_dir: str) -> Summary:
    devices, host = read_xplane(trace.find_xplane(log_dir))
    return reduce(devices, host)
