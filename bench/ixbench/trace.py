"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples: the device operations of each chip, and the host annotations
that the harness opens around each query. ``reduce`` works on those tuples
alone, so a test can drive it with a synthetic event list.

* Device operations are the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane.
* A Pallas (Mosaic) kernel is a custom call: an operation named
  ``custom-call.<n>``, or whose name (on a v5e, the op's HLO text) or
  statistics name the ``tpu_custom_call`` target. The name scope of the
  ``pallas_call`` is not enough: the pads and reshapes around a kernel
  carry it too. Every other operation is XLA glue.
* The traced window runs from the start of the first ``query <n>``
  annotation to the end of the last. Busy time is the union of a chip's
  operation intervals inside it; numbers of several chips are averaged.
* Ops nest on that line: a ``while`` spans the ops of its body. Kernel and
  glue time, and each op's time in the breakdown, are self time: an op's
  interval less those of the ops inside it. So kernel plus glue time is the
  busy time.
* An idle gap is a stretch of the window in which a chip runs nothing. It
  is named by the annotation it starts in, with its offset into that
  query: what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE = "XLA Ops"
QUERY = re.compile(r"^query \d+$")
KERNEL = re.compile(r"tpu_custom_call")
CUSTOM_TARGET = "custom_call_target="
TOP = 10


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float
    kind: str            # "kernel" | "glue"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass(frozen=True)
class Reduced:
    chips: int
    window_s: float
    busy_s: float        # per chip, averaged
    kernel_s: float      # per chip, averaged
    glue_s: float
    device_ops: list     # [[name, seconds summed over chips], ...]
    idle_gaps: list      # [[label, seconds], ...]


def op_kind(name: str, stats: dict) -> str:
    """``kernel`` for a Pallas custom call, else ``glue``. The TPU profiler
    names an op either bare (``custom-call.3``) or by its HLO text
    (``%intersect_count_pallas.1 = s32[..] custom-call(..),
    custom_call_target="tpu_custom_call", ..``); the text of another op
    can name a kernel only as an operand, never with its target."""
    text = " ".join([name] + [str(v) for v in stats.values()])
    if KERNEL.search(text):
        return "kernel"
    op = name.lstrip("%").split(" ", 1)[0]
    if op.startswith("custom-call") and CUSTOM_TARGET not in text:
        return "kernel"
    return "glue"


def read_xplane(logdir: str) -> tuple[dict, list]:
    """({plane name: [Op, ...]}, [Span, ...]) from the newest trace under
    ``logdir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    chips, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns, ev.end_ns,
                                  op_kind(ev.name, dict(ev.stats))))
            chips[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if QUERY.match(ev.name):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    return chips, spans


def _merged(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    iv = sorted((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops
                if o.end_ns > lo and o.start_ns < hi)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ops, lo: float, hi: float) -> list[tuple[Op, float]]:
    """Each op inside [lo, hi) with its self time there: its clipped
    interval less the parts that ops starting inside it cover."""
    iv = sorted(((max(o.start_ns, lo), min(o.end_ns, hi), o) for o in ops
                 if o.end_ns > lo and o.start_ns < hi),
                key=lambda t: (t[0], -t[1]))
    self_ns = [e - s for s, e, _ in iv]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(iv):
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_ns[parent] -= min(e, iv[parent][1]) - s
        stack.append(i)
    return [(o, d) for (_, _, o), d in zip(iv, self_ns)]


def _label(spans: list, t: float) -> str:
    for sp in spans:
        if sp.start_ns <= t < sp.end_ns:
            return f"{sp.name} +{(t - sp.start_ns) / 1e6:.1f}ms"
    return "between queries"


def reduce(chips: dict, spans: list) -> Reduced | None:
    """Device numbers of the traced window; None when the trace holds no
    query annotation or no device operation inside the window."""
    queries = [s for s in spans if QUERY.match(s.name)]
    if not queries or not chips:
        return None
    lo = min(s.start_ns for s in queries)
    hi = max(s.end_ns for s in queries)
    busy = kernel = glue = 0.0
    per_op: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    for ops in chips.values():
        merged = _merged(ops, lo, hi)
        busy += sum(e - s for s, e in merged)
        for o, d in _self_times(ops, lo, hi):
            per_op[o.name] = per_op.get(o.name, 0.0) + d
            if o.kind == "kernel":
                kernel += d
            else:
                glue += d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label(queries, s)))
    if busy == 0.0:
        return None
    n = len(chips)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:TOP]
    return Reduced(
        chips=n, window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
        kernel_s=kernel / n / 1e9, glue_s=glue / n / 1e9,
        device_ops=[[k, v / 1e9] for k, v in top_ops],
        idle_gaps=[[label, d / 1e9] for d, label in top_gaps])
