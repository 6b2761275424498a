"""Charge a profile's device time to the layer and the phase of the
program that issued it, and label idle time by what the host was doing
inside the program.

``trace.reduce`` knows a device op by its HLO instruction name.  This
module adds what the program names itself (``repro.scopes`` in ``src/``):

* the module run that encloses each op: the ``XLA Modules`` line of its
  device (``jit_train_step``, ``jit_prefill_step``, ``jit_decode_step``);
* the op's scope path, read from the compiled module's text: an
  instruction's ``metadata={op_name=...}`` with the transformation
  wrappers removed and only the scope vocabulary kept (``op_scopes``).
  A fusion carries its own metadata, its root's.  Compiled modules that
  share a name (a block prefill per prompt length) are told apart by
  the typed shapes of the instructions seen in each run;
* the program's host spans (``repro.*`` ``TraceAnnotation``s).  Each
  idle gap is cut at span borders and each piece is labelled by the
  innermost span of either kind over it: a ``bench.`` span by its name
  without the prefix, a ``repro.`` span by its full name.

An op whose instruction has no ``op_name`` (copies XLA inserts), or one
whose ``op_name`` holds no scope of the vocabulary, is unscoped and is
listed by base name.  On a program that names nothing, every op is
unscoped and every metric that reads a scope finds nothing to read.

XLA fuses a quantize cast into the fusion that lays out a GEMM's
operand, and that fusion carries its root's scope (``gemm``, ``dgrad``,
``wgrad``).  ``Module.casts`` names each fusion that holds a cast to an
``f8`` type made under a ``quant_*`` scope, so quantize time can be read
whether XLA fused the cast or not (``ScopedOp.quantizes``).

The vocabulary below is a copy of ``repro.scopes``, and the profile is
read a second time beside ``trace.reduce``: the benchmark runs on
programs that lack ``repro.scopes`` (the parent of the change that added
it), and ``trace.py`` stays as the accepted benchmark has it.  A test
(``bench/tests/test_scoped.py``) holds the copy equal to the program's.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import statistics

import harness

trace = harness.load_module("trace.py", "bench_trace")

#: the program's scope vocabulary (``repro.scopes.VOCABULARY``)
LAYERS = ("embed", "norm", "attn", "mlp", "moe", "head", "loss",
          "optimizer")
VOCABULARY = frozenset(LAYERS + (
    "qkv", "rope", "kv_write", "kv_gather", "core", "out",
    "quant_x", "quant_w", "gemm", "quant_g", "dgrad", "wgrad"))
PROGRAM_SPAN = "repro."
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
_BUCKET_NS = 10_000_000

_WRAPPER = re.compile(r"^[\w.\-]+\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?\[[\d,]*\])")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT = re.compile(r"^\s*(\([^()]*\)|\S+)\s+[\w\-]+\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_F8_CONVERT = re.compile(r"^f8e\w*\[\S*\s+convert\(")


def scope_path(op_name: str) -> tuple:
    """The vocabulary scopes of an ``op_name``, outermost first:
    ``transpose(jvp(loss))`` reads ``loss``; ``jit(...)``, ``while``,
    ``body``, remat markers and primitive names are dropped."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in VOCABULARY:
            out.append(part)
    return tuple(out)


def _shapes(rhs: str) -> tuple:
    """Typed shapes of the result of an instruction, given the text right
    of its ``=``; layouts are left out.  The module's text and the
    trace's op text print the result alike (operands only the trace
    prints with their shapes)."""
    m = _RESULT.match(_LAYOUT.sub("", rhs))
    return tuple(_SHAPE.findall(m.group(1))) if m else ()


def _op_shapes(text: str) -> tuple:
    """``_shapes`` of an op's text in the trace (``%name = ...``)."""
    m = _INSTR.match(text)
    return _shapes(m.group(2)) if m else ()


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: scope path}`` for every instruction of a compiled
    module (fused computations and loop bodies included) whose metadata
    has an ``op_name``; the path is ``()`` where it holds no scope of
    the vocabulary."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        o = _OP_NAME.search(m.group(2))
        if o:
            out[name] = scope_path(o.group(1))
    return out


def _quant(path) -> bool:
    return any(p.startswith("quant_") for p in path or ())


def cast_scopes(hlo_text: str) -> dict:
    """``{fusion: scope path of the quantize cast it holds}`` for each
    fusion whose fused computation (or one that it calls) converts to an
    ``f8`` type under a ``quant_*`` scope, while the fusion's own path
    names no ``quant_*`` scope."""
    held, callees, fusions, comp = {}, collections.defaultdict(list), [], None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            h = _COMPUTATION.match(line)
            if h:
                comp = h.group(1)
            continue
        rhs = m.group(2)
        o = _OP_NAME.search(rhs)
        path = scope_path(o.group(1)) if o else ()
        if _F8_CONVERT.match(_LAYOUT.sub("", rhs)) and _quant(path):
            held.setdefault(comp, path)
        c = _CALLS.search(rhs)
        if c and " fusion(" in rhs:
            callees[comp].append(c.group(1))
            fusions.append((m.group(1), c.group(1), path))

    def holds(name, seen=()):
        if name in held or name in seen:
            return held.get(name)
        for callee in callees.get(name, ()):
            found = holds(callee, seen + (name,))
            if found:
                return found
        return None

    return {f: holds(c) for f, c, path in fusions
            if not _quant(path) and holds(c)}


@dataclasses.dataclass
class Module:
    """One compiled module: its name, instruction scopes and shapes, and
    the fusions that hold a quantize cast (``cast_scopes``)."""
    name: str
    scopes: dict
    shapes: dict
    casts: dict

    @classmethod
    def from_text(cls, hlo_text: str) -> "Module":
        first = hlo_text.lstrip().splitlines()[0] if hlo_text.strip() else ""
        m = _MODULE.match(first)
        shapes = {}
        for line in hlo_text.splitlines():
            i = _INSTR.match(line)
            if i:
                shapes[i.group(1)] = _shapes(i.group(2))
        return cls(m.group(1) if m else "", op_scopes(hlo_text), shapes,
                   cast_scopes(hlo_text))


@dataclasses.dataclass
class ScopedOp:
    op: object          # trace.Op
    module: str         # enclosing module run's name, "" outside any
    run: int            # index of that run in ``Scoped.runs``, -1 if none
    scope: tuple        # scope path; None where unscoped
    cast: tuple = None  # scope path of the quantize cast fused into it

    @property
    def top(self) -> str:
        return self.scope[0] if self.scope else UNSCOPED

    def under(self, scope: str) -> bool:
        """Whether ``scope`` is on the op's path or on its fused cast's."""
        return scope in (self.scope or ()) or scope in (self.cast or ())

    @property
    def quantizes(self) -> bool:
        """A quantize pass: under a ``quant_*`` scope, or a fusion that
        holds a quantize cast."""
        return _quant(self.scope) or self.cast is not None


@dataclasses.dataclass
class Scoped:
    ops: list           # ScopedOp, one per op of the Reduction
    runs: list          # module runs: (name, device, start_ns, end_ns)
    spans: list         # (name, start_ns, end_ns) of both kinds
    gaps: list          # idle pieces (seconds, label), longest first
    chips: int
    window: tuple       # (start_ns, end_ns) of the traced window

    def whole(self, run: int) -> bool:
        """Whether module run ``run`` lies wholly inside the window."""
        lo, hi = self.window
        return run >= 0 and self.runs[run][2] >= lo and self.runs[run][3] <= hi

    def seconds(self, match) -> float:
        """Device seconds of the ops for which ``match(scoped_op)`` holds,
        per chip."""
        return sum(o.op.end_ns - o.op.start_ns for o in self.ops
                   if match(o)) / 1e9 / self.chips

    def run_seconds(self, module: str) -> list:
        """Device durations (s) of the runs of ``module`` that lie wholly
        inside the window, in order."""
        return [(e - s) / 1e9 for i, (n, _, s, e) in enumerate(self.runs)
                if n == module and self.whole(i)]

    def by_top(self, module: "str | None" = None) -> dict:
        """Seconds per chip by top-level scope (and ``unscoped``)."""
        tot = collections.Counter()
        for o in self.ops:
            if module is None or o.module == module:
                tot[o.top] += o.op.end_ns - o.op.start_ns
        return {k: v / 1e9 / self.chips for k, v in tot.most_common()}

    def unscoped_by_base(self, module: "str | None" = None,
                         n: int = 6) -> list:
        tot = collections.Counter()
        for o in self.ops:
            if o.scope is None and (module is None or o.module == module):
                tot[f"{o.op.base} {o.op.kind}".strip()] += (
                    o.op.end_ns - o.op.start_ns)
        return [(k, v / 1e9 / self.chips) for k, v in tot.most_common(n)]

    def scoped_share(self, module: "str | None" = None) -> float:
        """Share of the op time (summed over ops, of ``module``'s runs
        where given) that a scope owns."""
        mine = lambda o: module is None or o.module == module
        total = self.seconds(mine)
        return self.seconds(lambda o: mine(o) and o.scope is not None) \
            / total if total > 0 else 0.0

    def gaps_by_label(self) -> list:
        tot = collections.Counter()
        for s, label in self.gaps:
            tot[label] += s
        return tot.most_common()

    def finding(self, per: str, n: int, module: "str | None" = None) -> str:
        """A finding line: device ms per ``per`` (``n`` of them) by
        top-level scope, the scoped share, the unscoped ops by base."""
        tops = ", ".join(f"{k} {1e3 * v / max(1, n):.2f}"
                         for k, v in self.by_top(module).items())
        rest = ", ".join(f"{k} {1e3 * v / max(1, n):.2f}"
                         for k, v in self.unscoped_by_base(module))
        where = f" in {module}" if module else ""
        mine = lambda o: module is None or o.module == module
        quant = self.seconds(lambda o: mine(o) and o.quantizes)
        fused = self.seconds(lambda o: mine(o) and o.cast is not None)
        return (f"device ms per {per}{where} by scope ({n} {per}s): {tops}; "
                f"quantize {1e3 * quant / max(1, n):.2f} (of it casts fused "
                f"under other scopes {1e3 * fused / max(1, n):.2f}); "
                f"scoped {100 * self.scoped_share(module):.1f} % of op "
                f"time; "
                f"unscoped by base: {rest or 'none'}")


def _read(path: str, chips: int):
    """Module runs, op texts by (device, start, name), and program host
    spans of the profile at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    runs, texts, spans = [], {}, []
    for plane in data.planes:
        dev = trace._device_index(plane.name)
        for line in plane.lines:
            if dev is not None and dev < chips and line.name == MODULES_LINE:
                for e in line.events:
                    runs.append((e.name.split("(", 1)[0], e.name, dev,
                                 int(e.start_ns), int(e.end_ns)))
            elif dev is not None and dev < chips and line.name == trace.OPS_LINE:
                for e in line.events:
                    texts[(dev, int(e.start_ns),
                           trace.parse_op(e.name)[0])] = e.name
            elif dev is None:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPAN):
                        spans.append((e.name.split("#", 1)[0],
                                      int(e.start_ns), int(e.end_ns)))
    return runs, texts, spans


def _window(red) -> tuple:
    win = [s for s in red.spans if s[0] == trace.WINDOW_SPAN]
    if win:
        return win[0][1], win[0][2]
    return (min(o.start_ns for o in red.ops), max(o.end_ns for o in red.ops))


def choose(cands: list, ops: list, texts: dict) -> "Module | None":
    """The compiled module among ``cands`` (one name) that ran ``ops``:
    the one whose instructions' typed shapes match most of the ops'
    texts in the trace (``texts`` by device, start and name)."""
    if not cands:
        return None
    return max(cands, key=lambda m: sum(
        1 for o in ops if m.shapes.get(o.name) == _op_shapes(
            texts.get((o.device, o.start_ns, o.name), ""))))


def attribute(path: str, red, modules=(), *, chips: int = 1) -> Scoped:
    """Charge each op of ``red`` (``trace.reduce`` of the profile at
    ``path``) to its module run and scope, given the compiled modules'
    text (``modules``: HLO texts), and label the idle gaps by the
    innermost span of either kind."""
    found, texts, prog_spans = _read(path, chips)
    lo, hi = _window(red)
    by_name = collections.defaultdict(list)
    for text in modules:
        m = Module.from_text(text)
        by_name[m.name].append(m)

    runs = sorted(found, key=lambda r: (r[2], r[3]))
    per_dev = collections.defaultdict(list)     # device -> (start, index)
    for i, r in enumerate(runs):
        per_dev[r[2]].append((r[3], i))

    def enclosing(op) -> int:
        # by the op's start: an op may end a nanosecond after its run
        rs = per_dev.get(op.device, [])
        k = bisect.bisect_right(rs, (op.start_ns, len(runs))) - 1
        if k >= 0 and runs[rs[k][1]][4] > op.start_ns:
            return rs[k][1]
        return -1

    placed = [(op, enclosing(op)) for op in red.ops]
    members = collections.defaultdict(list)     # program id -> ops
    for op, i in placed:
        if i >= 0:
            members[runs[i][1]].append(op)
    chosen = {pid: choose(by_name.get(pid.split("(", 1)[0], []), ops,
                          texts) for pid, ops in members.items()}

    out = []
    for op, i in placed:
        mod = chosen.get(runs[i][1]) if i >= 0 else None
        scope = mod.scopes.get(op.name) if mod is not None else None
        cast = mod.casts.get(op.name) if mod is not None else None
        out.append(ScopedOp(op, runs[i][0] if i >= 0 else "", i,
                            scope or None, cast))
    spans = [s for s in red.spans if s[0] != trace.WINDOW_SPAN] + [
        s for s in prog_spans if s[2] > lo and s[1] < hi]
    return Scoped(out, [(r[0], r[2], r[3], r[4]) for r in runs], spans,
                  _gaps(red, spans, lo, hi),
                  max(1, len({o.device for o in red.ops})), (lo, hi))


def _gaps(red, spans, lo, hi) -> list:
    """Idle time of each chip inside the window, split at span borders:
    each piece is labelled by the innermost span over it (``bench.``
    spans without their prefix, ``repro.`` spans by full name)."""
    per_dev = collections.defaultdict(list)
    for o in red.ops:
        per_dev[o.device].append((o.start_ns, o.end_ns))
    buckets = collections.defaultdict(list)     # spans by 10 ms of time
    for sp in spans:
        for k in range(sp[1] // _BUCKET_NS, sp[2] // _BUCKET_NS + 1):
            buckets[k].append(sp)
    pieces = []
    for iv in per_dev.values():
        busy = trace._merge(trace._clip(iv, lo, hi))
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                near = {sp for k in range(a // _BUCKET_NS,
                                          (b - 1) // _BUCKET_NS + 1)
                        for sp in buckets.get(k, ())}
                pieces.extend(_pieces(near, a, b))
    pieces.sort(key=lambda g: -g[0])
    return pieces


def _pieces(spans, a, b) -> list:
    """``[a, b)`` cut at the borders of the spans over it, each cut
    charged to the shortest span that covers it; runs of one label are
    merged."""
    over = [(s, e, n) for n, s, e in spans if e > a and s < b]
    cuts = sorted({a, b} | {x for s, e, _ in over for x in (s, e)
                            if a < x < b})
    out = []
    for x, y in zip(cuts, cuts[1:]):
        inner = [(e - s, n) for s, e, n in over if s <= x and e >= y]
        label = _name(min(inner)[1]) if inner else "host (no span)"
        if out and out[-1][1] == label:
            out[-1][0] += (y - x) / 1e9
        else:
            out.append([(y - x) / 1e9, label])
    return [tuple(p) for p in out]


def _name(span: str) -> str:
    if span.startswith(trace.SPAN_PREFIX):
        return span[len(trace.SPAN_PREFIX):]
    return span


# ---------------------------------------------------- the program's side --

def serve_modules(bat, prompt_lengths) -> list:
    """Compiled text of the batcher's ``decode_step`` at the cell's
    shapes and of its ``prefill_step`` at each prompt length (the
    persistent cache hands back what the run compiled); ``[]`` for a
    batcher that has no such functions."""
    import jax.numpy as jnp
    import numpy as np
    if not (hasattr(bat, "prefill_step") and hasattr(bat, "decode_step")):
        return []
    out = [bat.decode_step.lower(bat.params,
                                 jnp.zeros((bat.max_batch,), jnp.int32),
                                 bat.cache).compile().as_text()]
    view = {"kv": bat.cache["kv"], "pt": jnp.asarray(bat.pt[:1]),
            "lens": jnp.zeros((1,), jnp.int32)}
    for p in prompt_lengths:
        out.append(bat.prefill_step.lower(
            bat.params, jnp.zeros((1, p), np.int32), view).compile().as_text())
    return out


def first_token_finding(records, req, uids) -> "str | None":
    """A finding line: TTFT of the requests ``uids`` measured when the
    program sampled each first token (``ContinuousBatcher.ticks``)
    against when the tick that sampled it returned (the harness's
    clock); None without tick records."""
    first = {u: t for r in records for u, _, t in r.admitted}
    pairs = [(first[u] - req[u]["due"], req[u]["times"][0] - req[u]["due"])
             for u in uids if u in first and req[u]["times"]]
    if not pairs:
        return None
    at_sample = sorted(p[0] for p in pairs)
    at_end = sorted(p[1] for p in pairs)
    return (f"TTFT at the first token's sampling: median "
            f"{1e3 * statistics.median(at_sample):.1f} ms, max "
            f"{1e3 * at_sample[-1]:.1f}; at the tick's end: median "
            f"{1e3 * statistics.median(at_end):.1f} ms, max "
            f"{1e3 * at_end[-1]:.1f}; {len(pairs)} requests")


def tick_finding(records, t0: float, t1: float) -> "str | None":
    """A finding line from the program's tick records
    (``ContinuousBatcher.ticks``) that lie in ``[t0, t1]`` (the window,
    on ``time.perf_counter``): the queue when admission began, over the
    ticks that admitted, and the pool pages in use at each tick's end;
    None without records there."""
    recs = [r for r in records if t0 <= r.start and r.end <= t1]
    if not recs:
        return None
    queue = sorted(r.queue for r in recs if r.admitted)
    pages = sorted(r.pages for r in recs)
    q = (f"median {statistics.median(queue)}, max {queue[-1]} over "
         f"{len(queue)} admitting ticks" if queue else "no admission")
    return (f"program tick records: {len(recs)} ticks; queue at admission "
            f"{q}; pages in use median {statistics.median(pages)}, max "
            f"{pages[-1]}")
