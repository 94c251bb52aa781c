"""The program's own spans against the device trace: what the host was
doing while a chip sat idle, and how much device time the named gathers
take.

The program opens every span as a profiler annotation named ``ix.<name>``
(``repro.obs``), so under ``jax.profiler`` each one is a host event on the
same clock as the device operations. ``read_xplane`` reads them beside
what ``trace.read_xplane`` reads; ``idle_by_span`` and ``scope_s`` work on
plain tuples, so a test can drive them with synthetic events.

* The spans are the ``ix.*`` events of the host lines that hold the
  harness's ``query <n>`` annotations, returned in one list with those
  annotations. A ``sync`` span is labelled with its ``site``:
  ``ix.sync[site=meta]``.
* A device operation that is not a kernel and whose ``tf_op`` names the
  scope ``padded_rows`` (``graph/csr.py``) has the kind ``scoped``;
  ``trace.reduce`` counts it as glue, as before. On a v5e the ``tf_op``
  (the op's ``op_name`` metadata, e.g. ``jit(fn)/padded_rows/gather:``)
  is a statistic of the op's event *metadata*, not of the event, and
  ``ProfileData`` shows only the event's own; so ``op_scopes`` reads it
  from the trace file's bytes.
* ``idle_by_span``: every idle stretch of each chip inside the window is
  split by the innermost ``ix.*`` span open on the host at each instant;
  time under none goes to ``untraced``. Seconds, averaged over chips.
* ``scope_s``: the device self time of the ``scoped`` operations inside
  the window, per chip.

Both return None when there is nothing to read: no query annotation, no
``ix.*`` span, or no scoped operation, as in a trace of a program that
opens no such span.
"""
from __future__ import annotations

import glob
import os

from . import trace as T

PREFIX = "ix."
SCOPE = "padded_rows"
UNTRACED = "untraced"
OP_NAME_STAT = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + size], "little"), i + size
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_scopes(raw: bytes) -> dict:
    """{device plane name: {op name: {tf_op, ...}}} from a serialized
    ``XSpace``: per plane, each event metadata's name (what an op event
    is named) with its ``tf_op`` statistics. Field numbers are those of
    ``xplane.proto``: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2, stats
    5; XStatMetadata.name 2; XStat.metadata_id 1, str_value 5,
    ref_value 7 (a stat metadata id whose name is the string)."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf in (4, 5):
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                if pf == 5:
                    stat_names[entry.get(1, 0)] = bytes(
                        meta.get(2, b"")).decode()
                else:
                    events.append(entry.get(2, b""))
        if not T.DEVICE_PLANE.match(name):
            continue
        ops = out[name] = {}
        for ev in events:
            op, tags = "", set()
            for ef, v in _fields(ev):
                if ef == 2:
                    op = bytes(v).decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == OP_NAME_STAT:
                        tags.add(bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            ops.setdefault(op, set()).update(tags)
    return out


def _label(name: str, stats: dict) -> str:
    site = stats.get("site")
    return f"{name}[site={site}]" if site is not None else name


def read_xplane(logdir: str) -> tuple[dict, list]:
    """(chips, spans) from the newest trace under ``logdir``: chips as
    ``trace.read_xplane`` gives them, with the kind ``scoped`` for the
    named gathers, and the query annotations with the ``ix.*`` spans of
    their host lines."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    data = ProfileData.from_serialized_xspace(raw)
    chips, spans = {}, []
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            tags = scopes.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name != T.OPS_LINE:
                    continue
                for ev in line.events:
                    kind = T.op_kind(ev.name, dict(ev.stats))
                    if kind == "glue" and any(SCOPE in t for t in
                                              tags.get(ev.name, ())):
                        kind = "scoped"
                    ops.append(T.Op(ev.name, ev.start_ns, ev.end_ns, kind))
            chips[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                if not any(T.QUERY.match(ev.name) for ev in events):
                    continue
                for ev in events:
                    if T.QUERY.match(ev.name):
                        spans.append(T.Span(ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name.startswith(PREFIX):
                        spans.append(T.Span(_label(ev.name, dict(ev.stats)),
                                            ev.start_ns, ev.end_ns))
    return chips, spans


def _window(spans: list):
    queries = [s for s in spans if T.QUERY.match(s.name)]
    if not queries:
        return None
    return (min(s.start_ns for s in queries), max(s.end_ns for s in queries))


def _innermost(spans: list, lo: float, hi: float) -> list:
    """[(a, b, label)] tiling [lo, hi): the innermost ``ix.*`` span open
    over each piece (the latest started, then the shortest), ``untraced``
    where none is."""
    ix = sorted((s for s in spans if s.name.startswith(PREFIX)),
                key=lambda s: s.start_ns)
    cuts = sorted({lo, hi} | {t for s in ix for t in (s.start_ns, s.end_ns)
                              if lo < t < hi})
    pieces, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ix) and ix[i].start_ns <= a:
            active.append(ix[i])
            i += 1
        active = [s for s in active if s.end_ns > a]
        inner = max(active, key=lambda s: (s.start_ns, -s.end_ns),
                    default=None)
        pieces.append((a, b, inner.name if inner else UNTRACED))
    return pieces


def idle_by_span(chips: dict, spans: list) -> dict | None:
    """{label: idle seconds per chip} over the window, largest first."""
    win = _window(spans)
    if win is None or not chips or not any(
            s.name.startswith(PREFIX) for s in spans):
        return None
    lo, hi = win
    pieces = _innermost(spans, lo, hi)
    out: dict[str, float] = {}
    for ops in chips.values():
        edges = [lo] + [t for iv in T._merged(ops, lo, hi) for t in iv] + [hi]
        j = 0
        for s, e in zip(edges[::2], edges[1::2]):
            while j < len(pieces) and pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                a, b, label = pieces[k]
                d = min(b, e) - max(a, s)
                if d > 0:
                    out[label] = out.get(label, 0.0) + d
                k += 1
    n = len(chips)
    return {k: v / n / 1e9
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def scope_s(chips: dict, spans: list) -> float | None:
    """Device self time of the ``scoped`` operations in the window, in
    seconds per chip; None without one."""
    win = _window(spans)
    if win is None or not chips:
        return None
    total, seen = 0.0, False
    for ops in chips.values():
        for o, d in T._self_times(ops, *win):
            if o.kind == "scoped":
                total += d
                seen = True
    return total / len(chips) / 1e9 if seen else None
