#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<name>.json``: its configuration
(``bench/configs/<config>.json``), its mode (``bench/modes/<mode>.py``),
its traffic and the per-layer metrics its traced run reports (each read
by ``bench/metrics/<metric>.py``).  Findings go on earlier lines; the
last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and ``checks`` last: each compared number beside its limit).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiled part of the window.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or without the system under test (``src/``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

# libtpu's logs stay in the checkout (its default is a fixed /tmp path)
if "TPU_LOG_DIR" not in os.environ:
    _logs = harness.ROOT / ".bench_trace" / "tpu_logs"
    _logs.mkdir(parents=True, exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(_logs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    workload, conf = harness.load_cell(args.workload)
    harness.use_program()
    device = harness.require_chips(workload["chips"])
    harness.find(f"device {device['platform']} {device['kind']} "
                 f"x{device['count']}")
    harness.find(f"compile cache {harness.enable_cache()}")
    mode = harness.load_module(f"modes/{workload['mode']}.py",
                               f"bench_mode_{workload['mode']}")
    out = mode.run(workload=workload, conf=conf, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=T_START)
    device = {**device, "memory_peak_bytes": out["memory_peak_bytes"]}
    if args.trace:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
        metrics = harness.per_layer_metrics(workload["per_layer"],
                                            out["ctx"])
    else:
        metrics = out["end_to_end"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = out["breakdown"]
    harness.emit(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
