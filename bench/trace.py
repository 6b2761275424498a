"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time in the traced window, device time
per operation, and the idle gaps, each labelled
by the host span (``bench.*`` ``TraceAnnotation``) that covers it.

The traced window is the ``bench.window`` host span; without one, the
span of all device operations.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, less the control-flow
ops (``while``, ``conditional``, ``call``) that only enclose others.  An
event's name there is its HLO instruction (``%fusion.697 = ... kind=kLoop,
calls=...``); an op is known by the instruction's name (``fusion.697``),
its base (``fusion``; a Pallas kernel's is its function name, such as
``exsdotp_gemm_pallas``) and its fusion kind.
"""
from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
ENCLOSING = ("while", "conditional", "call")
_KIND = re.compile(r"kind=(k\w+)")


def _merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclasses.dataclass
class Op:
    name: str        # HLO instruction name, e.g. fusion.697
    base: str        # name without its number, e.g. fusion
    kind: str        # fusion kind (kLoop, kOutput, ...) or ""
    start_ns: int
    end_ns: int
    device: int


def parse_op(text: str):
    """(name, base, kind) of an ``XLA Ops`` event's HLO text."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    base = re.sub(r"(\.\d+)+$", "", name.split(".remat")[0])
    m = _KIND.search(text)
    return name, base, m.group(1) if m else ""


def matmul_instructions(hlo_text: str) -> set:
    """Names of the instructions of a compiled module that compute a
    matrix product in XLA: ``convolution``/``dot`` ops, and fusions whose
    fused computation holds one."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head and "=" not in line.split("(")[0]:
            cur = head.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            comps[cur].append(line)
    has_mm = {c for c, body in comps.items()
              if any(re.search(r"\s(convolution|dot)\(", b) for b in body)}
    out = set()
    for body in comps.values():
        for line in body:
            m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
            if not m:
                continue
            if re.search(r"\s(convolution|dot)\(", line):
                out.add(m.group(1))
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            if calls and calls.group(1) in has_mm and " fusion(" in line:
                out.add(m.group(1))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                   # union of op intervals, mean over chips
    ops: list                       # Op events inside the window
    gaps: list                      # (seconds, label), longest first
    spans: list                     # (name, start_ns, end_ns) host spans

    def op_seconds(self, match) -> float:
        """Device seconds of the ops for which ``match(op)`` holds, summed
        over chips and averaged per chip."""
        chips = max(1, len({o.device for o in self.ops}))
        return sum(o.end_ns - o.start_ns for o in self.ops if match(o)) \
            / 1e9 / chips

    def by_base(self) -> list:
        tot = collections.Counter()
        for o in self.ops:
            tot[f"{o.base} {o.kind}".strip()] += o.end_ns - o.start_ns
        chips = max(1, len({o.device for o in self.ops}))
        return [(n, t / 1e9 / chips) for n, t in tot.most_common()]

    def gaps_by_label(self) -> list:
        tot = collections.Counter()
        for s, label in self.gaps:
            tot[label] += s
        return tot.most_common()

    def breakdown(self) -> dict:
        """The ``breakdown`` of a traced result line: the 10 kinds of
        device op (base name and fusion kind) that took most time, and
        idle time by what the host was doing."""
        return {"device_ops": [[n, s] for n, s in self.by_base()[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps_by_label()[:10]]}


def _device_index(plane_name: str) -> "int | None":
    if not plane_name.startswith("/device:TPU:"):
        return None
    tail = plane_name[len("/device:TPU:"):]
    return int(tail) if tail.isdigit() else None


def reduce(path: str, *, chips: int = 1) -> Reduction:
    """Reduce the trace at ``path`` over its ``bench.window`` span, for
    the first ``chips`` TPU devices."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and dev < chips and line.name == OPS_LINE:
                for e in line.events:
                    name, base, kind = parse_op(e.name)
                    if base in ENCLOSING:
                        continue
                    ops.append(Op(name, base, kind, int(e.start_ns),
                                  int(e.end_ns), dev))
            elif dev is None:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns), int(e.end_ns)))
    if not ops:
        raise ValueError(f"no device operations in {path}")
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        lo, hi = min(o.start_ns for o in ops), max(o.end_ns for o in ops)
    ops = [o for o in ops if o.end_ns > lo and o.start_ns < hi]
    per_dev = collections.defaultdict(list)
    for o in ops:
        per_dev[o.device].append((o.start_ns, o.end_ns))
    busy = {d: _merge(_clip(iv, lo, hi)) for d, iv in per_dev.items()}
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) \
        / 1e9 / max(1, len(busy))
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    gaps = []
    for d, iv in busy.items():
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _label(inner, a, b)))
    gaps.sort(key=lambda g: -g[0])
    return Reduction((hi - lo) / 1e9, busy_s, ops, gaps, spans)


def _label(spans, a, b) -> str:
    """The innermost host span that overlaps the gap ``[a, b)`` most."""
    best, best_key = "host (no bench span)", None
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name[len(SPAN_PREFIX):], key
    return best
