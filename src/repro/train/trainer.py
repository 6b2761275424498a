"""Trainer — the host-side loop: data, checkpoints, restart, stragglers.

Fault-tolerance model (designed for 1000+ nodes, exercised in tests on 1):

* **checkpoint/restart**: atomic async checkpoints every ``save_every``
  steps; on construction the trainer auto-resumes from LATEST. A crash
  (or induced failure — ``fail_at_step`` hook in tests) loses at most the
  steps since the last save; data is hash-addressed so resume is
  bit-exact.
* **straggler mitigation**: per-step wall time is tracked against a
  running median; steps slower than ``straggler_factor``x are counted and
  surfaced in metrics — at fleet scale this signal drives hot-spare swaps;
  here it additionally triggers an optional callback.
* **elastic re-scale**: state is saved device-layout-free; ``restore``
  re-shards onto whatever mesh is current (see checkpoint/ckpt.py), so a
  512-chip job restarts on 256 chips by just rebuilding the mesh.
* **numeric faults**: non-finite grads skip the update (train_step),
  so a single bad batch/node cannot poison the weights.

Each step's host phases run inside the spans of
``repro.scopes.TRAIN_SPANS`` (``jax.profiler.TraceAnnotation``): the
batch, the jitted call's dispatch, the wait for the device, the metrics'
fetch to host floats and the checkpoint.
"""
from __future__ import annotations

import time
from statistics import median
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..checkpoint.ckpt import CheckpointManager
from ..data.pipeline import DataConfig, SyntheticTokens

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, model, train_step, state, data: SyntheticTokens,
                 *, ckpt_dir: str, save_every: int = 50,
                 shardings: Any = None, straggler_factor: float = 3.0,
                 on_straggler: Optional[Callable[[int, float], None]] = None,
                 fail_at_step: Optional[int] = None):
        self.model = model
        self.train_step = jax.jit(train_step, donate_argnums=(0,)) if not (
            hasattr(train_step, "lower")) else train_step
        self.data = data
        self.ckpt = CheckpointManager(ckpt_dir)
        self.save_every = save_every
        self.shardings = shardings
        self.straggler_factor = straggler_factor
        self.on_straggler = on_straggler
        self.fail_at_step = fail_at_step
        self.step_times: list[float] = []
        self.straggler_count = 0
        self.metrics_log: list[dict] = []

        latest = self.ckpt.latest_step()
        if latest is not None:
            state = self.ckpt.restore(latest, state, shardings)
            self.start_step = latest
        else:
            self.start_step = 0
        self.state = state

    def run(self, num_steps: int, aux_fn: Optional[Callable] = None):
        try:
            return self._run(num_steps, aux_fn)
        finally:
            # flush any in-flight async checkpoint even when a step raises:
            # the atomic publish (rename + LATEST) then reflects the most
            # recent completed save, which is what restart resumes from.
            self.ckpt.wait()

    def _run(self, num_steps: int, aux_fn: Optional[Callable] = None):
        for step in range(self.start_step, self.start_step + num_steps):
            if self.fail_at_step is not None and step == self.fail_at_step:
                raise RuntimeError(f"induced failure at step {step}")
            with jax.profiler.TraceAnnotation("repro.train.batch"):
                batch = self.data.global_batch_at_step(step)
                aux = aux_fn(step) if aux_fn else None
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("repro.train.dispatch"):
                if aux is not None:
                    self.state, metrics = self.train_step(self.state,
                                                          batch, aux)
                else:
                    self.state, metrics = self.train_step(self.state, batch)
            with jax.profiler.TraceAnnotation("repro.train.wait"):
                jax.block_until_ready(jax.tree.leaves(metrics)[0])
            dt = time.perf_counter() - t0
            self._track_straggler(step, dt)
            with jax.profiler.TraceAnnotation("repro.train.fetch"):
                metrics = {k: float(np.asarray(v))
                           for k, v in metrics.items()}
            metrics["step"] = step
            metrics["step_time_s"] = dt
            self.metrics_log.append(metrics)
            if (step + 1) % self.save_every == 0:
                with jax.profiler.TraceAnnotation("repro.train.ckpt"):
                    self.ckpt.save_async(step + 1, self.state)
        return self.metrics_log

    def _track_straggler(self, step: int, dt: float):
        # ignore the first (compile) step for the baseline
        if len(self.step_times) >= 3:
            med = median(self.step_times[1:])
            if dt > self.straggler_factor * med:
                self.straggler_count += 1
                if self.on_straggler:
                    self.on_straggler(step, dt)
        self.step_times.append(dt)

    def save_now(self, step: int):
        self.ckpt.save(step, self.state)
