"""Serving mode: open-loop arrivals into the program's ``ContinuousBatcher``.

Set-up makes the weights from the seed, builds the batcher, sends one
request of each prompt length of the mix through it (compiling each
block prefill and the decode step), then offers arrivals at the cell's
fixed rate for ``warm_seconds`` before the window opens.  Arrivals go on
at that rate through the window and after it, until every request due in
the window has its first token (at most ``drain_seconds``).

The harness sees a token when the tick (``ContinuousBatcher.step``) that
produced it returns.  The gap between tokens is every gap of consecutive
tokens of one request, both returned inside the window.  Its median is
the decode step as a user feels it (four gaps in five are decode
ticks).  Its tail is the mean of the slowest tenth of the gaps, not a
percentile: a gap lasts one tick, and ticks come in levels, one per
kind (a decode step alone, 293 ms on the chip; with a prefill of 128,
256, 512, 1024 or 2048 tokens, 508 to 1330 ms; with two prefills about
1.1 s).  In a window each level of the slowest fifth holds the gaps of
one to eight ticks, so a percentile there lies a few ticks' worth of
gaps from a border at most, and jumps by 10 to 50 % when one tick's
slots or one coincidence of two arrivals in a tick changes; the mean of
the tenth moves by a fraction of that.  A pause of the host of 1 to 3 s
with the device idle, in about one run of four, still moves it by up to
13 % (PERF.md).  A finding line prints the percentiles, the share of
gaps in bands of levels and the median tick of each kind.  Tokens per
second counts the tokens returned inside the window.

``correct``: once the window has closed, a sample drawn from the seed of
the window's finished requests, the longest among them, until it holds
``check_tokens`` served tokens (at most ``check_max_requests``).  With
the program's state freed, the float32 reference runs each prompt with
its served tokens, and the widest gap by which a served token's logit
lies below the reference's best logit at its position is compared with
the limit (greedy decoding).
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

import harness
import reference
import traffic
from repro.models import build_model
from repro.serve.scheduler import ContinuousBatcher, ServeRequest

trace_mod = harness.load_module("trace.py", "bench_trace")


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (an infinite miss stays infinite)."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])


def tail_mean(values, share: float) -> float:
    """Mean of the largest ``share`` % of ``values`` (at least one)."""
    v = sorted(values, reverse=True)
    return float(np.mean(v[: max(1, math.ceil(share / 100 * len(v)))]))


class Session:
    """Drives the batcher: arrival bookkeeping, ticks, and the per-request
    and per-tick logs the metrics read."""

    def __init__(self, bat, spans):
        self.bat, self.spans = bat, spans
        self.req = {}        # uid -> record
        self.ticks = []      # per tick: start, end, prefills, decodes

    def offer(self, r, due_abs: float, now: float):
        self.req[r.uid] = {"due": due_abs, "enq": now, "prompt": r.prompt,
                           "max_new": r.max_new, "admit": None,
                           "times": []}
        self.bat.pending.append(ServeRequest(r.uid, r.prompt, r.max_new))

    def tick(self):
        bat = self.bat
        before = {s.req.uid: len(s.out) for s in bat.slots if s is not None}
        pend = {r.uid for r in bat.pending}
        n_done = len(bat.done)
        ts = time.perf_counter()
        with self.spans("batcher_step"):
            bat.step()
        te = time.perf_counter()
        now_out = {s.req.uid: len(s.out) for s in bat.slots if s is not None}
        for uid in list(bat.done)[n_done:]:
            now_out[uid] = len(bat.done[uid])
        prefills, decodes = [], []
        for uid, n in now_out.items():
            rec = self.req.get(uid)
            old = before.get(uid, 0)
            if rec is None or n == old:
                continue
            p = len(rec["prompt"])
            if uid in pend:
                rec["admit"] = ts
                prefills.append(p)
                if n - old == 2:
                    decodes.append(p + 1)
            else:
                decodes.append(p + old)
            rec["times"].extend([te] * (n - old))
        self.ticks.append({"start": ts, "end": te, "prefills": prefills,
                           "decodes": decodes})

    def idle(self) -> bool:
        return not self.bat.pending and all(s is None for s in self.bat.slots)

    def drive(self, reqs, warm_s, seconds, drain_s, tw=None, compiles=None):
        """Offer ``reqs`` (due times from now; segment 1 is the window's)
        open loop: ``warm_s`` of arrivals, the window of ``seconds``, then
        on until every request due in the window has its first token or
        ``drain_s`` has passed.
        The profiler (``tw``) and the compile count run in the window."""
        clock0 = time.perf_counter()
        w0, w1 = clock0 + warm_s, clock0 + warm_s + seconds
        in_window = [r.uid for r in reqs if r.segment == 1]
        lateness, nxt, opened, load = [], 0, False, []
        while True:
            now = time.perf_counter()
            with self.spans("arrivals"):
                while nxt < len(reqs) and clock0 + reqs[nxt].due <= now:
                    r = reqs[nxt]
                    self.offer(r, clock0 + r.due, now)
                    lateness.append(now - (clock0 + r.due))
                    nxt += 1
            if not opened and now >= w0:
                opened = True
                if compiles is not None:
                    compiles.armed = True
                if tw is not None:
                    tw.start()
            if tw is not None and tw.due():
                tw.stop()
            if now >= w1:
                if compiles is not None:
                    compiles.armed = False
                if tw is not None:
                    tw.stop()
                if now >= w1 + drain_s or all(
                        self.req[u]["times"] for u in in_window):
                    break
            if w0 <= now < w1:
                load.append((now, len(self.bat.pending),
                             sum(s is not None for s in self.bat.slots)))
            if self.idle():
                gap = (clock0 + reqs[nxt].due - now) if nxt < len(reqs) \
                    else 1e-3
                time.sleep(min(max(gap, 0.0), 0.005))
                continue
            self.tick()
        if tw is not None:
            tw.stop()
        return {"clock0": clock0, "w0": w0, "w1": w1,
                "in_window": in_window, "lateness": lateness,
                "load": load}


def window_numbers(sess, win, seconds) -> dict:
    """TTFT of the requests due in the window (a request with no first
    token is an infinite miss), gaps between tokens both returned inside
    it, tokens returned inside it, its ticks."""
    w0, w1 = win["w0"], win["w1"]
    recs = [sess.req[u] for u in win["in_window"]]
    ttft = [(r["times"][0] - r["due"]) if r["times"] else math.inf
            for r in recs]
    gaps = [b - a for r in sess.req.values()
            for a, b in zip(r["times"], r["times"][1:]) if w0 <= a and b < w1]
    n_tok = sum(1 for r in sess.req.values() for x in r["times"]
                if w0 <= x < w1)
    return {"recs": recs, "ttft": ttft, "gaps": gaps, "n_tok": n_tok,
            "failed": sum(1 for r in recs if not r["times"]),
            "ticks": [k for k in sess.ticks
                      if w0 <= k["start"] and k["end"] < w1],
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": percentile(ttft, 95) * 1e3,
            "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else math.inf,
            "itl_tail10_mean_ms": tail_mean(gaps, 10) * 1e3 if gaps
            else math.inf,
            "tokens_per_s": n_tok / seconds}


#: bands of tick levels (ms) the gap shares are printed for
GAP_LEVELS_MS = (100, 400, 750, 950)


def level_findings(m, load, seconds) -> list:
    """Finding lines: the share of gaps at each tick level with the ITL
    percentiles, the median tick by kind, and the requests in the system
    (queued and in slots) over the window with its trend."""
    gaps = m["gaps"]
    edges = (0,) + GAP_LEVELS_MS
    shares = []
    for lo, hi in zip(edges, edges[1:] + (math.inf,)):
        n = sum(1 for g in gaps if lo <= g * 1e3 < hi)
        shares.append(f"{lo}-{hi} ms {100 * n / max(1, len(gaps)):.1f} %")
    pct = ", ".join([f"p{q} {percentile(gaps, q) * 1e3:.1f}"
                     for q in (50, 90, 95, 99)]
                    + [f"slowest tenth mean {m['itl_tail10_mean_ms']:.1f}"]
                    ) if gaps else "none"
    kinds = {}
    for k in m["ticks"]:
        p = k["prefills"]
        kind = ("decode" if not p else f"prefill {p[0]}" if len(p) == 1
                else f"{len(p)} prefills")
        kinds.setdefault(kind, []).append(k["end"] - k["start"])
    ticks = ", ".join(f"{n} x{len(v)} {np.median(v) * 1e3:.1f}"
                      for n, v in sorted(kinds.items()))
    out = [f"gaps: {len(gaps)}; {pct} ms; shares {'; '.join(shares)}",
           f"tick median ms by kind: {ticks}"]
    if load:
        ls = load_series(load)
        out.append(f"in system (queued + in slots): open {ls['start']}, "
                   f"close {ls['end']}, mean {ls['mean']:.2f}, max "
                   f"{ls['max']}, trend {ls['trend_per_min']:+.2f} per min; "
                   f"queued max {ls['queued_max']}")
    return out


def load_series(load) -> dict:
    """The requests in the system (queued and in slots) over a window,
    from ``drive``'s samples (one per loop pass), read on a one-second
    grid so that idle passes weigh no more than busy ones: first, last,
    mean, max, least-squares trend, and the most ever queued."""
    t = np.array([x[0] for x in load]) - load[0][0]
    n = np.array([x[1] + x[2] for x in load], np.float64)
    grid = np.arange(0.0, t[-1] + 1e-9, 1.0)
    g = n[np.searchsorted(t, grid, side="right") - 1]
    trend = float(np.polyfit(grid, g, 1)[0]) * 60 if len(grid) > 1 else 0.0
    return {"start": int(n[0]), "end": int(n[-1]), "mean": float(g.mean()),
            "max": int(n.max()), "trend_per_min": trend,
            "queued_max": max(x[1] for x in load)}


def warm_up(sess, lengths, seed, vocab):
    """One request of each prompt length: compiles every block prefill
    of the mix and the decode step (two new tokens each)."""
    for i, p in enumerate(lengths):
        r = traffic.Request(-1 - i, 0.0,
                            traffic.hashed_tokens(seed, 4, i * 4096, p, vocab),
                            2)
        sess.offer(r, time.perf_counter(), time.perf_counter())
    while not sess.idle():
        sess.tick()


def run(*, workload, conf, seed, seconds, trace, t_start):
    devs = jax.devices()[: workload["chips"]]
    spans = harness.Spans()
    cfg = harness.model_config(conf)
    t = workload["traffic"]
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = harness.make_weights(shapes, seed)
    srv = workload["server"]
    bat = ContinuousBatcher(model, params, max_batch=srv["slots"],
                            max_len=srv["max_len"],
                            page_size=srv["page_size"])
    sess = Session(bat, spans)
    warm_up(sess, t["prompt_lengths"], seed, cfg.vocab_size)
    warm_s, drain_s = t["warm_seconds"], t["drain_seconds"]
    reqs = traffic.arrivals(t, seed, [warm_s, seconds, drain_s],
                            cfg.vocab_size)
    compiles = harness.CompileCounter()

    tw = harness.TracedWindow(trace, workload["trace_seconds"],
                              harness.ROOT / ".bench_trace" / workload["name"],
                              spans)
    win = sess.drive(reqs, warm_s, seconds, drain_s, tw, compiles)
    w0, w1, in_window, lateness = (win["w0"], win["w1"], win["in_window"],
                                   win["lateness"])
    setup_s = w0 - t_start
    harness.find(f"generator lateness: median {np.median(lateness) * 1e3:.2f} "
                 f"ms, max {max(lateness) * 1e3:.2f} ms over {len(lateness)} "
                 f"arrivals (enqueued at the next tick boundary)")
    harness.find(f"compiles inside the window: {compiles.n}")

    # end-to-end numbers
    m = window_numbers(sess, win, seconds)
    recs, failed = m["recs"], m["failed"]
    waits = [r["admit"] - r["due"] for r in recs if r["admit"] is not None]
    queue_wait_p95 = percentile(waits, 95) if waits else math.inf
    ttft, gaps, n_tok, ticks_w = m["ttft"], m["gaps"], m["n_tok"], m["ticks"]
    harness.find(f"window: {len(recs)} requests due, {failed} without a "
                 f"first token; {n_tok} tokens; {len(ticks_w)} ticks, tick "
                 f"median {np.median([k['end'] - k['start'] for k in ticks_w]) * 1e3:.2f} ms; "
                 f"TTFT p50 {m['ttft_p50_ms']:.1f} ms, p95 {m['ttft_p95_ms']:.1f} ms, "
                 f"queue wait p95 {queue_wait_p95 * 1e3:.1f} ms; "
                 f"unfinished at stop {sum(1 for r in recs if len(r['times']) < r['max_new'])}")
    for line in level_findings(m, win["load"], seconds):
        harness.find(line)
    peak = harness.memory_peak_bytes(devs)
    harness.find(f"memory peak {peak} bytes")

    # correctness on a seeded sample of finished window requests
    fin = [u for u in in_window
           if len(sess.req[u]["times"]) == sess.req[u]["max_new"]]
    sample = check_sample(fin, sess.req, seed, workload["check_tokens"],
                          workload["check_max_requests"])
    served = {u: np.asarray(bat.done[u]) for u in sample}
    del bat, params, sess.bat
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = harness.make_weights(shapes, seed)
    worst = 0.0
    for u in sample:
        prompt, out = sess.req[u]["prompt"], served[u]
        seq = np.concatenate([prompt, out[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        gap, _ = reference.serve_logit_gaps(ref_params, conf, seq, pos, out,
                                            out)
        worst = max(worst, float(gap.max()))
    harness.find(f"reference {time.perf_counter() - t_ref:.2f} s over "
                 f"{len(sample)} requests, "
                 f"{sum(len(served[u]) for u in sample)} served tokens")
    limit = workload["limits"]["served_logit_gap"]
    checks = [("sampled_requests", len(sample), workload["check_min_requests"]),
              ("served_logit_gap", worst, limit)]
    correct = (failed == 0 and len(sample) >= workload["check_min_requests"]
               and worst <= limit)

    out = {"correct": bool(correct), "attempted": len(recs), "failed": failed,
           "memory_peak_bytes": peak, "checks": checks,
           "sample": [(sess.req[u]["prompt"], served[u]) for u in sample],
           "end_to_end": {
               "serve_itl_p50_ms": {"value": m["itl_p50_ms"], "unit": "ms"},
               "serve_itl_tail10_mean_ms": {"value": m["itl_tail10_mean_ms"],
                                            "unit": "ms"},
               "serve_tokens_per_s": {"value": m["tokens_per_s"],
                                      "unit": "tokens/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}}
    if trace:
        red = trace_mod.reduce(harness.xplane_file(tw.dir), chips=len(devs))
        a, b = tw.t0, tw.t1
        out.update(busy_s=red.busy_s, window_s=red.window_s,
                   breakdown=red.breakdown())
        out["ctx"] = {
            "trace": red, "conf": conf, "workload": workload,
            "peaks": harness.peaks(devs[0].device_kind), "chips": len(devs),
            "ticks": [k for k in sess.ticks if a <= k["start"] and k["end"] <= b]}
    return out


def check_sample(finished, req, seed, tokens, most):
    """The longest finished request, then others in a seeded order, until
    the sample holds ``tokens`` served tokens or ``most`` requests."""
    if not finished:
        return []
    size = lambda u: len(req[u]["prompt"]) + req[u]["max_new"]
    longest = max(finished, key=size)
    rng = np.random.default_rng(harness.seed_u64(seed, 5))
    rest = [u for u in rng.permutation(finished) if u != longest]
    out, n = [longest], req[longest]["max_new"]
    for u in rest:
        if n >= tokens or len(out) >= most:
            break
        out.append(int(u))
        n += req[u]["max_new"]
    return out
