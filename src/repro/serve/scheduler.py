"""Continuous batching over the paged KV cache (DESIGN.md §12).

The batcher owns a fixed pool of decode slots (``max_batch``) and a
page allocator over the shared pool; requests flow through a slot
state machine::

    pending --admit--> prefill --first token--> decoding --stop--> free
                 (pages alloc'd)        (page per boundary)  (pages freed)

* **admit** — a free slot takes the oldest pending request: its pages
  are allocated, the prompt prefills in ONE block ``decode_step`` on a
  single-slot *view* of the shared cache (the pool is functionally
  updated, so the slot's pages land in the common arrays), and the
  first token is sampled from the prefill logits.
* **decode** — all active slots advance in lockstep: one batched
  ``decode_step`` over ``[max_batch]`` tokens.  Idle slots ride along
  pinned at ``lens = 0`` with an all-trash page table; their logits
  are garbage and discarded.  A slot crossing a page boundary gets its
  next page allocated just before the step.
* **retire** — finished sequences free their pages back to the
  allocator and zero their table row.  Freed pages keep their stale
  payloads (possibly NaN-poisoned scale codes); the decode kernel's
  structural garbage masking is what makes skipping the scrub safe.

``pt``/``lens`` live host-side (numpy) as the scheduler's ground
truth and are pushed into the device cache each step — the cache's
own ``lens + s`` advance is ignored, which is also what keeps idle
slots from drifting.

The block prefill and the lockstep decode are two jitted functions,
``prefill_step`` and ``decode_step`` (both ``model.decode_step``), so a
profile's ``XLA Modules`` line tells the phases apart.  Each tick opens
the host spans of ``repro.scopes.SERVE_SPANS`` and leaves a
``TickRecord`` in ``ticks``, the last ``TICK_RECORDS`` of them.

Greedy decoding reproduces ``serve.decode.generate`` token for token:
same kernels, same cache math — only the page *numbering* differs,
and the gather re-assembles identical sequences either way.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .kv_cache import max_pages

__all__ = ["ServeRequest", "PageAllocator", "ContinuousBatcher",
           "TickRecord", "TICK_RECORDS"]

#: how many of the latest ticks ``ContinuousBatcher.ticks`` keeps
TICK_RECORDS = 4096


@dataclasses.dataclass
class ServeRequest:
    uid: Any
    prompt: np.ndarray            # [P] int32 token ids
    max_new_tokens: int


class PageAllocator:
    """Free-list over pool pages 1..P-1 (page 0 is the trash page)."""

    def __init__(self, n_pages: int):
        self._free = list(range(n_pages - 1, 0, -1))
        self._usable = n_pages - 1

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self._usable - len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(f"page pool exhausted: want {n}, "
                               f"have {len(self._free)}")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages) -> None:
        for p in pages:
            assert p > 0, "page 0 is reserved"
            self._free.append(int(p))


@dataclasses.dataclass
class _Slot:
    req: ServeRequest
    remaining: int
    tok: int                      # last sampled token (next step's input)
    out: list


@dataclasses.dataclass
class TickRecord:
    """One tick of ``ContinuousBatcher.step`` that admitted or decoded.

    Times are ``time.perf_counter`` seconds.  ``admitted`` holds, per
    request prefilled in the tick, ``(uid, prompt length, time its first
    token was sampled)``; ``decoding`` the slots that ran the lockstep
    decode; ``pages`` the pool pages in use at the tick's end; ``queue``
    the pending requests when admission began."""
    start: float
    end: float
    admitted: list
    decoding: int
    pages: int
    queue: int


class ContinuousBatcher:
    """Mid-flight admission + lockstep paged decode for one model.

    ``model`` must support block decode and a paged cache
    (``init_cache(..., paged=True)``) — the dense/MoE families.
    """

    def __init__(self, model, params, *, max_batch: int, max_len: int,
                 page_size: int = 16, temperature: float = 0.0,
                 key=None, rules=None, impl: str = "auto",
                 eos_id: Optional[int] = None):
        if temperature > 0.0 and key is None:
            raise ValueError("temperature>0 requires key=")
        assert getattr(model, "block_decode", False), model.cfg.family
        self.model, self.params = model, params
        self.max_batch, self.max_len = max_batch, max_len
        self.page_size = page_size
        self.temperature, self.key, self.eos_id = temperature, key, eos_id
        self.mp = max_pages(max_len, page_size)
        self.cache = model.init_cache(max_batch, max_len, paged=True,
                                      page_size=page_size)
        self.alloc = PageAllocator(1 + max_batch * self.mp)
        # scheduler-owned tables (the init identity table is discarded)
        self.pt = np.zeros((max_batch, self.mp), np.int32)
        self.lens = np.zeros((max_batch,), np.int32)
        self.slots: list[Optional[_Slot]] = [None] * max_batch
        self.pending: deque[ServeRequest] = deque()
        self.done: dict[Any, np.ndarray] = {}
        self.ticks: deque[TickRecord] = deque(maxlen=TICK_RECORDS)

        def prefill_step(params, tokens, cache):
            return model.decode_step(params, tokens, cache, rules=rules,
                                     impl=impl)

        def decode_step(params, tokens, cache):
            return model.decode_step(params, tokens, cache, rules=rules,
                                     impl=impl)

        self.prefill_step = jax.jit(prefill_step)
        self.decode_step = jax.jit(decode_step)

    # ------------------------------------------------------------- state --

    def _push_tables(self):
        self.cache = {**self.cache, "pt": jnp.asarray(self.pt),
                      "lens": jnp.asarray(self.lens)}

    def _ensure(self, b: int, pos: int):
        """Back cache slot ``pos`` of sequence ``b`` with a real page."""
        j = pos // self.page_size
        assert j < self.mp, (pos, self.max_len)
        if self.pt[b, j] == 0:
            self.pt[b, j] = self.alloc.alloc(1)[0]

    def _sample(self, logits) -> np.ndarray:
        if self.temperature > 0.0:
            self.key, sub = jax.random.split(self.key)
            return np.asarray(jax.random.categorical(
                sub, jnp.asarray(logits, jnp.float32) / self.temperature,
                axis=-1))
        # matches generate's jnp.argmax tie-breaking (first max)
        return np.asarray(jnp.argmax(jnp.asarray(logits), axis=-1))

    # ------------------------------------------------------- transitions --

    def _admit(self, admitted: list):
        for b in range(self.max_batch):
            if self.slots[b] is not None or not self.pending:
                continue
            req = self.pending.popleft()
            prompt = np.asarray(req.prompt, np.int32)
            p = len(prompt)
            assert p + req.max_new_tokens <= self.max_len, req.uid
            with jax.profiler.TraceAnnotation("repro.serve.prefill",
                                              uid=str(req.uid),
                                              prompt_len=p):
                self.lens[b] = 0
                for pos in range(p):
                    self._ensure(b, pos)
                # single-slot view prefill: pool leaves are shared, so the
                # functional update lands the pages in the common arrays
                view = {"kv": self.cache["kv"],
                        "pt": jnp.asarray(self.pt[b:b + 1]),
                        "lens": jnp.zeros((1,), jnp.int32)}
                logits, view = self.prefill_step(
                    self.params, jnp.asarray(prompt[None]), view)
                self.cache = {**self.cache, "kv": view["kv"]}
                self.lens[b] = p
                tok = int(self._sample(logits[:, -1])[0])
            admitted.append((req.uid, p, time.perf_counter()))
            slot = _Slot(req, req.max_new_tokens - 1, tok, [tok])
            if self._finished(slot):
                self._retire(b, slot)
            else:
                self.slots[b] = slot

    def _finished(self, slot: _Slot) -> bool:
        return slot.remaining <= 0 or (self.eos_id is not None
                                       and slot.tok == self.eos_id)

    def _retire(self, b: int, slot: _Slot):
        self.done[slot.req.uid] = np.asarray(slot.out, np.int32)
        self.alloc.free(self.pt[b][self.pt[b] != 0])
        self.pt[b] = 0
        self.lens[b] = 0
        self.slots[b] = None

    # -------------------------------------------------------------- step --

    def step(self):
        """One scheduler tick: admit, lockstep-decode, retire."""
        start, queue, admitted = time.perf_counter(), len(self.pending), []
        with jax.profiler.TraceAnnotation("repro.serve.admit"):
            self._admit(admitted)
        active = [b for b in range(self.max_batch)
                  if self.slots[b] is not None]
        if active:
            self._decode(active)
        if admitted or active:
            self.ticks.append(TickRecord(
                start, time.perf_counter(), admitted, len(active),
                self.alloc.in_use, queue))

    def _decode(self, active: list):
        toks = np.zeros((self.max_batch,), np.int32)
        for b in active:
            toks[b] = self.slots[b].tok
            self._ensure(b, int(self.lens[b]))
        with jax.profiler.TraceAnnotation("repro.serve.push_tables"):
            self._push_tables()
        with jax.profiler.TraceAnnotation("repro.serve.decode"):
            logits, new_cache = self.decode_step(
                self.params, jnp.asarray(toks), self.cache)
        # keep the updated pool; device pt/lens are overwritten from the
        # host tables on the next push (idle slots stay pinned at 0)
        self.cache = {**self.cache, "kv": new_cache["kv"]}
        with jax.profiler.TraceAnnotation("repro.serve.sample"):
            sampled = self._sample(logits)
        with jax.profiler.TraceAnnotation("repro.serve.retire"):
            for b in active:
                self.lens[b] += 1
                slot = self.slots[b]
                slot.tok = int(sampled[b])
                slot.out.append(slot.tok)
                slot.remaining -= 1
                if self._finished(slot):
                    self._retire(b, slot)

    def run(self, requests) -> dict:
        self.pending.extend(requests)
        while self.pending or any(s is not None for s in self.slots):
            self.step()
        return self.done
