"""From a profiler trace (``.xplane.pb``, or gzipped) of a window to the
numbers the per-layer metrics read.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run, with its start and duration on the
device, on the host's clock.  A ``while`` (a scan) is an event too, and
the ops of its body nest inside it, so an op's own time is its duration
less that of the ops nested in it.  Each op's metadata names it (the HLO
instruction, e.g. ``fusion.32`` or ``gather_rows.6`` for a Pallas
kernel's custom call) and gives its ``tf_op``: the JAX name stack, which
holds the harness's ``jax.named_scope``.  JAX's ``ProfileData`` reads the
events; the metadata, which it does not expose, is read here from the
protobuf.

The harness's host spans (``bench_window``, ``bench_run``,
``bench_segment``, ``bench_evaluate``) are events of a host thread.  The
window is the ``bench_window`` span; device time outside it is not
counted.  ``busy`` is the union of a device's op intervals inside the
window; each idle gap is named after the innermost harness span open at
its midpoint.
"""
from __future__ import annotations

import dataclasses
import gzip

from bench import adapter

OPS_LINE = 'XLA Ops'
HOST_SPANS = (adapter.SPAN_WINDOW, adapter.SPAN_RUN, adapter.SPAN_SEGMENT,
              adapter.SPAN_EVALUATE, adapter.SPAN_PRECOMPUTE)
#: innermost first: the span a gap is named after
SPAN_DEPTH = {adapter.SPAN_EVALUATE: 0, adapter.SPAN_SEGMENT: 1,
              adapter.SPAN_PRECOMPUTE: 1, adapter.SPAN_RUN: 2,
              adapter.SPAN_WINDOW: 3}


@dataclasses.dataclass(frozen=True)
class Op:
    name: str           # HLO instruction, e.g. 'fusion.32'
    start: int          # ns
    end: int            # ns
    scope: str = ''     # tf_op: the JAX name stack


@dataclasses.dataclass(frozen=True)
class Trace:
    devices: dict       # plane name -> [Op] sorted by start
    spans: list         # [(name, start, end)] of the harness's host spans


@dataclasses.dataclass(frozen=True)
class Reduced:
    window_s: float
    busy_s: float               # mean over devices
    op_s: dict                  # op name -> own device seconds in the window
    op_n: dict                  # op name -> events in the window
    scope_s: dict               # name-stack fragment -> own device seconds
    gaps: list                  # [(span name, seconds)], longest first

    def time_of(self, match) -> tuple:
        """(seconds, events) of the ops whose name ``match`` accepts."""
        names = [n for n in self.op_s if match(n)]
        return (sum(self.op_s[n] for n in names),
                sum(self.op_n[n] for n in names))


def base_name(op_name: str) -> str:
    """'gather_rows.6' -> 'gather_rows'."""
    head, _, tail = op_name.rpartition('.')
    return head if head and tail.isdigit() else op_name


# -- the protobuf, just enough of it for the event metadata ------------------------

def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7f) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one protobuf message; length-delimited
    values as bytes, others as ints or raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f'protobuf wire type {wire}')
        yield field, v


def _metadata(xspace: bytes) -> dict:
    """(plane name, event name) -> (display name, tf_op) for every event
    metadata of every device plane.  XSpace.planes = 1; XPlane: name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata: name = 2,
    display_name = 4, stats = 5; XStat: metadata_id = 1, str_value = 5;
    XStatMetadata: id = 1, name = 2."""
    out = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, events, stat_names = '', [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = pv.decode()
            elif pf == 4:
                events.append(dict(_fields(pv)).get(2, b''))
            elif pf == 5:
                sm = dict(_fields(dict(_fields(pv)).get(2, b'')))
                stat_names[sm.get(1, 0)] = sm.get(2, b'').decode()
        if not name.startswith('/device:'):
            continue
        for em in events:
            ev_name, display, tf_op = '', '', ''
            for f, v in _fields(em):
                if f == 2:
                    ev_name = v.decode(errors='replace')
                elif f == 4:
                    display = v.decode(errors='replace')
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == 'tf_op' and 5 in st:
                        tf_op = st[5].decode(errors='replace')
            out[(name, ev_name)] = (display, tf_op)
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    with open(path, 'rb') as f:
        raw = f.read()
    if raw[:2] == b'\x1f\x8b':
        raw = gzip.decompress(raw)
    meta = _metadata(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:') and 'CPU' not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    display, tf_op = meta.get((plane.name, e.name), ('', ''))
                    name = display or e.name.split(' = ')[0].lstrip('%')
                    start = int(e.start_ns)
                    ops.append(Op(name, start, start + int(e.duration_ns),
                                  tf_op))
            if ops:
                devices[plane.name] = sorted(ops,
                                             key=lambda o: (o.start, -o.end))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        start = int(e.start_ns)
                        spans.append((e.name, start,
                                      start + int(e.duration_ns)))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


# -- reduction ---------------------------------------------------------------------------------

def _clip(op, lo, hi):
    return max(min(op.end, hi) - max(op.start, lo), 0)


def _own_times(ops, lo, hi):
    """Each op's time in [lo, hi] less that of the ops nested in it (ops
    sorted by start, outer before inner)."""
    own = [_clip(op, lo, hi) for op in ops]
    stack = []
    for i, op in enumerate(ops):
        while stack and ops[stack[-1]].end <= op.start:
            stack.pop()
        if stack and op.end <= ops[stack[-1]].end:
            own[stack[-1]] -= own[i]
        stack.append(i)
    return own


def _busy(ops, lo, hi):
    """(busy ns, idle gaps [(start, end)]) of ``ops`` clipped to [lo, hi]."""
    busy, gaps, cur = 0, [], lo
    for op in ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def _span_at(spans, t):
    open_ = [s for s in spans if s[1] <= t < s[2]]
    if not open_:
        return 'none'
    return min(open_, key=lambda s: SPAN_DEPTH.get(s[0], 9))[0]


def reduce(trace: Trace, scopes=(adapter.TRAIN_SCOPE,)) -> Reduced:
    """``scope_s[f]``: own device seconds of the ops whose name stack
    holds ``f``."""
    windows = [s for s in trace.spans if s[0] == adapter.SPAN_WINDOW]
    if not windows:
        raise ValueError('the trace holds no bench_window span')
    if not trace.devices:
        raise ValueError('the trace holds no device operations')
    _, lo, hi = windows[0]
    op_s, op_n, scope_s = {}, {}, {f: 0.0 for f in scopes}
    busy_total, all_gaps = 0, []
    for ops in trace.devices.values():
        busy, gaps = _busy(ops, lo, hi)
        busy_total += busy
        all_gaps += gaps
        for op, own in zip(ops, _own_times(ops, lo, hi)):
            if _clip(op, lo, hi) <= 0:
                continue
            op_s[op.name] = op_s.get(op.name, 0.0) + own * 1e-9
            op_n[op.name] = op_n.get(op.name, 0) + 1
            for f in scopes:
                if f in op.scope:
                    scope_s[f] += own * 1e-9
    gaps = sorted(((_span_at(trace.spans, (a + b) // 2), (b - a) * 1e-9)
                   for a, b in all_gaps), key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(trace.devices),
                   op_s=op_s, op_n=op_n, scope_s=scope_s, gaps=gaps)
