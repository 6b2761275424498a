#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: offer its traffic mix at
several fixed rates in turn, through one batcher in one process, and
report whether completions kept pace with arrivals.

    python3 bench/knee_sweep.py --cell serve-deepseek-l8-chat \\
        --rates 0.7,0.85 --seconds 120 --seed 7 --out <dir>/knee.json

Each rate gets the mix's warm-up and then a window of ``--seconds``; the
next rate follows without a drain, so the requests still in slots carry
its load over.  Per rate: requests due and completed per second in the
window, tokens per second returned and offered (the window's requests'
output tokens over its seconds), the requests in the system (queued and
in slots) at the window's start and end with their least-squares trend
over it (a trend that climbs through a window longer than a request's
life is past the knee), and the TTFT and ITL tails.  The knee is the
highest rate whose requests in the system do not climb; the cell's fixed
rate (its mix file) is 4/5 of it.  The benchmark's runs never search for
a rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    wl, conf = harness.load_cell(a.cell)
    harness.use_program()
    harness.require_chips(wl["chips"])
    harness.enable_cache()
    import jax
    import numpy as np
    import traffic
    from repro.models import build_model
    mode = harness.load_module("modes/serve.py", "knee_mode_serve")

    cfg = harness.model_config(conf)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = harness.make_weights(shapes, a.seed)
    srv = wl["server"]
    bat = mode.ContinuousBatcher(model, params, max_batch=srv["slots"],
                                 max_len=srv["max_len"],
                                 page_size=srv["page_size"])
    spans = harness.Spans()
    mode.warm_up(mode.Session(bat, spans),
                 wl["traffic"]["prompt_lengths"], a.seed, cfg.vocab_size)
    res = []
    for k, rate in enumerate(float(r) for r in a.rates.split(",")):
        mix = dict(wl["traffic"], rate_per_s=rate)
        warm = mix["warm_seconds"]
        reqs = traffic.arrivals(mix, a.seed + k, [warm, a.seconds],
                                cfg.vocab_size)
        reqs = [dataclasses.replace(r, uid=r.uid + 1_000_000 * (k + 1))
                for r in reqs]
        sess = mode.Session(bat, spans)
        win = sess.drive(reqs, warm, a.seconds, 0.0)
        m = mode.window_numbers(sess, win, a.seconds)
        done = sum(1 for r in sess.req.values() if len(r["times"]) ==
                   r["max_new"] and win["w0"] <= r["times"][-1] < win["w1"])
        load = win["load"]
        ls = mode.load_series(load)
        row = {"rate": rate,
               "due_per_s": len(win["in_window"]) / a.seconds,
               "completed_per_s": done / a.seconds,
               "tokens_per_s": m["tokens_per_s"],
               "offered_tokens_per_s": sum(
                   r.max_new for r in reqs if r.segment == 1) / a.seconds,
               **{f"in_system_{k}": v for k, v in ls.items()},
               "ttft_p50_ms": m["ttft_p50_ms"], "ttft_p95_ms": m["ttft_p95_ms"],
               "itl_tail10_mean_ms": m["itl_tail10_mean_ms"],
               "tick_median_ms": 1e3 * float(np.median(
                   [t["end"] - t["start"] for t in m["ticks"]]))}
        harness.find(json.dumps(row))
        for line in mode.level_findings(m, load, a.seconds):
            harness.find(f"rate {rate}: {line}")
        res.append(row)
    pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(a.out).write_text(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
