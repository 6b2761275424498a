"""Tile/layout autotuning for the packed kernels (DESIGN.md §14).

The packed Pallas kernels ship with static 128-ish tile heuristics
(``ops.blockscale_blocks`` / ``mx_packed_blocks`` / ``attention_blocks``)
that respect the compiled-TPU legality floors but were never *measured*:
nothing in the stack knew whether a 128³ tile or a 32×256×1024 tile is
closer to the roofline on a given backend.  This module closes that gap
with a sweep-and-cache autotuner:

* **Candidate enumeration** (``gemm_tile_candidates`` /
  ``attention_tile_candidates``) — every swept tile is *legal by
  construction*: sublane axes (M / block_q) are 8-multiples, lane axes
  (N, K / block_k) are 128-multiples, packed K-tiles are multiples of
  every participating codec's ``lane_unit`` (FP8 → 128, FP4 → 256, FP6
  → 512 elements — the floor below which a packed byte run stops being
  a 128-multiple lane tile) *and* of the MX group, tiles never exceed
  the minimally padded problem, and the per-step VMEM working set stays
  under a budget.  Attention candidates must divide S/T exactly (those
  kernels assert divisibility instead of padding).  The packed-GEMM
  sweep additionally carries a *layout* axis: each tile shape is tried
  with the grid-pipelined K-loop and with the double-buffered manual-DMA
  K-loop (``mx_gemm_packed_pallas(double_buffer=True)``) — bitwise
  equal, different streaming schedules.

* **Measurement** (``autotune``) — median-of-iters wall clock through
  ``time_us_median`` (every iteration synchronized with
  ``block_until_ready`` — async dispatch must not leak into the number;
  the median discards scheduler outliers).  The bench callable is
  injected, so tests drive the machinery with deterministic stubs.

* **Persistent cache** — one JSON file per kernel under
  ``benchmarks/baselines/tune/`` (override with ``REPRO_TUNE_DIR``),
  keyed per (shape, formats, backend).  Entries from another backend
  never apply (the backend is part of the key), and a version bump
  invalidates the whole file.  The in-process memo makes repeat lookups
  free; a cache hit never re-times anything, so tuned runs are
  deterministic and CI (which commits the cache) never sweeps.

``ops``'s entry points opt in with ``tiles="auto"``; the static
heuristics stay the default, so every existing oracle test is untouched.
Any *legal* tile choice preserves the kernels' numerics contract: MX
group scales are a property of the data layout (groups of 32 along K),
not of the tile grid, so on exact-arithmetic operands
(``tests/fuzz.exact_mx_operands``) every candidate — and the
double-buffered layout — is bitwise equal to the static default.  For
the block-scaled GEMM the scale grid IS the config's block size, so its
candidates only *subdivide* the scale blocks (the kernel reads the same
scalar scale per compute tile — see ``blockscale_gemm_pallas``'s
``scale_block_*`` parameters).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import time

import numpy as np

_log = logging.getLogger(__name__)

__all__ = ["TuneResult", "autotune", "peek", "clear_memo", "cache_dir",
           "time_us_median", "gemm_tile_candidates",
           "attention_tile_candidates", "gemm_packed_tiles",
           "blockscale_tiles", "attention_tiles"]

CACHE_VERSION = 1

# per-grid-step VMEM working-set budget for swept GEMM tiles (bytes);
# ~half the 16 MiB/core so the pipelined next tile fits alongside
VMEM_BUDGET = 8 * 2 ** 20

_MEMO: dict = {}


# ------------------------------------------------------------ cache -------

def cache_dir() -> str:
    """Resolution order: ``REPRO_TUNE_DIR`` env var → the repo's
    committed ``benchmarks/baselines/tune/`` (when running from a
    checkout) → ``~/.cache/repro/tune``."""
    env = os.environ.get("REPRO_TUNE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    cand = os.path.join(repo, "benchmarks", "baselines", "tune")
    if os.path.isdir(os.path.join(repo, "benchmarks")):
        return cand
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "tune")


def _cache_path(kernel: str, cdir=None) -> str:
    return os.path.join(cdir or cache_dir(), f"{kernel}.json")


def _load(kernel: str, cdir=None) -> dict:
    path = _cache_path(kernel, cdir)
    memo_key = ("file", path)
    if memo_key in _MEMO:
        return _MEMO[memo_key]
    data = {"version": CACHE_VERSION, "entries": {}}
    try:
        with open(path) as f:
            raw = json.load(f)
        if raw.get("version") == CACHE_VERSION:
            data = raw
    except (OSError, ValueError):
        pass
    _MEMO[memo_key] = data
    return data


def _store(kernel: str, key: str, entry: dict, cdir=None) -> None:
    data = _load(kernel, cdir)
    data["entries"][key] = entry
    path = _cache_path(kernel, cdir)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                     # read-only checkout: memo still serves


def clear_memo() -> None:
    """Drop the in-process cache memo (tests; after editing cache files)."""
    _MEMO.clear()


# ------------------------------------------------------------ timing ------

def time_us_median(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-clock microseconds of ``fn(*args)``.

    Every iteration blocks on the result (``jax.block_until_ready``) —
    including the warmups, so compilation and the async dispatch queue
    are fully drained before the first timed sample — and the median of
    per-iteration times is returned rather than the mean, so a single
    scheduler hiccup cannot skew the number (the timing convention
    shared with ``benchmarks/run.py`` — EXPERIMENTS.md §Conventions).
    """
    import jax
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


# ------------------------------------------------------------ core --------

@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of a tile lookup: the chosen ``tiles`` tuple, the median
    microseconds it measured (None on a pure cache hit recorded by an
    older sweep without timing, or a stubbed bench) and the ``source``
    — 'cache' (no timing ran), 'swept' (this call measured every
    candidate) or 'default' (no candidates; the static heuristic)."""
    tiles: tuple
    us: "float | None"
    source: str


def peek(kernel: str, key: str, *, cache_dir=None) -> "TuneResult | None":
    """Cached entry for ``key``, or None — never sweeps."""
    entry = _load(kernel, cache_dir)["entries"].get(key)
    if entry is None:
        return None
    return TuneResult(tuple(entry["tiles"]), entry.get("us"), "cache")


def autotune(kernel: str, key: str, candidates, bench_fn, *,
             iters: int = 3, warmup: int = 1,
             cache_dir=None) -> TuneResult:
    """Pick the fastest tile tuple for ``key`` among ``candidates``.

    ``bench_fn(tiles) -> float`` returns ONE wall-clock measurement in
    microseconds (injected so tests can stub it); the autotuner takes
    the median of ``iters`` calls after ``warmup`` discarded ones.  The
    winner is persisted under ``<cache_dir>/<kernel>.json`` keyed by
    ``key``; a later call with the same key returns it without invoking
    ``bench_fn`` at all (cache-hit determinism).  A candidate whose
    bench raises (a tile the compiler refuses, say) is skipped and
    logged with its error; if every candidate fails, the errors are
    raised together — a sweep never silently ends on an untested tile.
    """
    candidates = [tuple(c) for c in candidates]
    assert candidates, kernel
    hit = peek(kernel, key, cache_dir=cache_dir)
    if hit is not None and tuple(hit.tiles) in candidates:
        return hit
    best, best_us, errors = None, math.inf, []
    for cand in candidates:
        try:
            for _ in range(max(warmup, 0)):
                bench_fn(cand)
            us = float(np.median([bench_fn(cand)
                                  for _ in range(max(iters, 1))]))
        except Exception as e:  # noqa: BLE001 — reported, then re-raised
            errors.append(f"{cand}: {type(e).__name__}: {e}")
            _log.warning("autotune %s: candidate %s skipped: %s", kernel,
                         cand, errors[-1])
            continue
        if us < best_us:
            best, best_us = cand, us
    if best is None:
        raise RuntimeError(f"autotune {kernel} [{key}]: every candidate "
                           "failed:\n  " + "\n  ".join(errors))
    _store(kernel, key, {"tiles": list(best), "us": best_us}, cache_dir)
    return TuneResult(best, best_us, "swept")


# ------------------------------------------------- candidate spaces -------

def _ceil_mult(dim: int, unit: int) -> int:
    return max(unit, dim + (-dim) % unit)


def _ladder(cap: int, units) -> list:
    """Ascending multiples of each unit up to ``cap`` (deduped)."""
    out = set()
    for u in units:
        b = u
        while b <= cap:
            out.add(b)
            b *= 2
    return sorted(out)


def gemm_tile_candidates(m: int, n: int, k: int, *, lane_units=(128,),
                         group: int = 1,
                         vmem_bytes_fn=None) -> "list[tuple]":
    """Legal (block_m, block_n, block_k) candidates for a packed
    (M, K) × (K, N) GEMM sweep.

    Floors (the ``mx_packed_blocks`` legality rules, enumerated instead
    of fixed): block_m is a sublane 8-multiple, block_n a lane
    128-multiple, block_k a multiple of lcm(128, group, *lane_units) —
    so every candidate's packed byte run is a legal lane tile for every
    codec involved.  No tile exceeds the minimally padded problem
    (padding cost is bounded by one tile), and ``vmem_bytes_fn(tiles)``
    (when given) prunes candidates whose per-step working set exceeds
    ``VMEM_BUDGET``.
    """
    ku = 128 * group // math.gcd(128, group)
    for u in lane_units:
        ku = ku * u // math.gcd(ku, u)
    cands = []
    for bm in _ladder(min(256, _ceil_mult(m, 8)), (8,)):
        for bn in _ladder(min(512, _ceil_mult(n, 128)), (128,)):
            for bk in _ladder(min(4 * ku, _ceil_mult(k, ku)), (ku,)):
                t = (bm, bn, bk)
                if vmem_bytes_fn and vmem_bytes_fn(t) > VMEM_BUDGET:
                    continue
                cands.append(t)
    return cands


def attention_tile_candidates(s: int, t: int, *, q_floor: int = 8,
                              k_floor: int = 8) -> "list[tuple]":
    """Legal (block_q, block_k) candidates for an S × T attention sweep:
    powers of two ≤ 128 that divide the length *exactly* (the attention
    kernels assert divisibility — masks are positional, so padding would
    need an extra in-kernel mask), bounded below by the sublane floor
    (8; the decode q axis may fall to ``q_floor=1`` — S=1 steady-state
    decode, interp/CPU-only below 8, the §12 convention)."""
    def picks(n, floor):
        return [b for b in (128, 64, 32, 16, 8, 4, 2, 1)
                if b >= floor and n % b == 0]

    return [(bq, bk) for bq in picks(s, q_floor) for bk in picks(t, k_floor)]


# ------------------------------------------------- kernel frontends -------
# Each frontend builds the cache key, the legal candidate space and a
# synthetic-operand bench closure for one kernel family, and funnels
# through ``autotune``.  Synthetic operands (random payload bytes /
# carrier values at the caller's real shapes) keep the sweep callable
# from inside a jit trace: timing runs on concrete arrays regardless of
# whether the caller's operands are tracers.

def _backend_tag(impl: str) -> str:
    import jax
    mode = "interp" if impl == "pallas_interpret" else "compiled"
    return f"{jax.default_backend()}-{mode}"


def _pad_to(x: int, b: int) -> int:
    return x + (-x) % b


def gemm_packed_tiles(m: int, n: int, k: int, mx_a, mx_b, *,
                      impl: str = "pallas", sweep: bool = True,
                      bench_fn=None, cache_dir=None,
                      iters: int = 3) -> "tuple[tuple, bool, TuneResult]":
    """Tuned (block_m, block_n, block_k) + double-buffer flag for
    ``mx_gemm_packed_pallas`` on an (M, K) × (K, N) problem.

    Returns ``((bm, bn, bk), double_buffer, result)``.  The swept
    layout axis is the K-loop streaming schedule: each tile shape is a
    candidate twice, ``(bm, bn, bk, 0)`` grid-pipelined and
    ``(bm, bn, bk, 1)`` double-buffered manual DMA (only when the
    problem has ≥ 2 K-tiles — a single-tile K-loop has nothing to
    overlap).  With ``sweep=False`` a cache miss falls back to the
    static heuristic (``ops.mx_packed_blocks``) instead of timing —
    the CPU-CI mode, where only the committed cache ever answers.
    """
    from ..core.formats import get_mx_format
    from .codec import get_codec

    mx_a = get_mx_format(mx_a)
    mx_b = get_mx_format(mx_b) if mx_b is not None else mx_a
    ca, cb = get_codec(mx_a), get_codec(mx_b)
    g = mx_a.group

    def vmem(tl):
        bm, bn, bk = tl[:3]
        return (bm * ca.packed_cols(bk) + bn * cb.packed_cols(bk)
                + (bm + bn) * bk                    # u8 scale codes
                + 2 * bm * bn * 4)                  # acc + out
    base = gemm_tile_candidates(m, n, k, group=g,
                                lane_units=(ca.lane_unit, cb.lane_unit),
                                vmem_bytes_fn=vmem)
    cands = []
    for bm, bn, bk in base:
        cands.append((bm, bn, bk, 0))
        if _pad_to(k, bk) // bk >= 2:
            cands.append((bm, bn, bk, 1))
    key = (f"m{m}n{n}k{k}|{mx_a.name}+{mx_b.name}|{_backend_tag(impl)}")
    kernel = "mx_gemm_packed"
    hit = peek(kernel, key, cache_dir=cache_dir)
    if hit is not None and tuple(hit.tiles) in cands:
        return tuple(hit.tiles[:3]), bool(hit.tiles[3]), hit
    if not sweep and bench_fn is None:
        from . import ops
        return ops.mx_packed_blocks(m, n, g, ca, cb), False, TuneResult(
            ops.mx_packed_blocks(m, n, g, ca, cb) + (0,), None, "default")

    if bench_fn is None:
        from .blockscale_gemm import mx_gemm_packed_pallas
        rng = np.random.default_rng(0)
        interp = impl == "pallas_interpret"

        def bench_fn(tl):
            import jax.numpy as jnp
            bm, bn, bk, db = tl
            mp, np_, kp = _pad_to(m, bm), _pad_to(n, bn), _pad_to(k, bk)
            ap = jnp.asarray(rng.integers(
                0, 256, (mp, ca.packed_cols(kp)), dtype=np.uint8))
            bp = jnp.asarray(rng.integers(
                0, 256, (np_, cb.packed_cols(kp)), dtype=np.uint8))
            s_a = jnp.full((mp, kp), 127, jnp.uint8)
            s_b = jnp.full((np_, kp), 127, jnp.uint8)
            return time_us_median(
                lambda: mx_gemm_packed_pallas(
                    ap, bp, s_a, s_b, mx_a=mx_a, mx_b=mx_b,
                    block_m=bm, block_n=bn, block_k=bk,
                    double_buffer=bool(db), interpret=interp),
                warmup=0, iters=1)

    res = autotune(kernel, key, cands, bench_fn, iters=iters,
                   cache_dir=cache_dir)
    return tuple(res.tiles[:3]), bool(res.tiles[3]), res


def blockscale_tiles(m: int, n: int, k: int, scale_blocks, q_dtype_a,
                     q_dtype_b, *, impl: str = "pallas", sweep: bool = True,
                     bench_fn=None, cache_dir=None,
                     iters: int = 3) -> "tuple[tuple, TuneResult]":
    """Tuned compute tiles for ``blockscale_gemm_pallas`` under a FIXED
    scale grid ``scale_blocks = (sm, sn, sk)``.

    The scale grid is the numerics contract (one scale per (sm × sk) /
    (sk × sn) block — DESIGN.md §3), so candidates only *subdivide* it:
    bm | sm (8-multiples), bn | sn and bk | sk (128-multiples).  Every
    candidate reads the same scalar scale per compute tile, so the math
    is unchanged (identical on exact operands; K-split order aside).
    """
    import jax.numpy as jnp
    sm, sn, sk = scale_blocks

    def divs(s, unit):
        return [b for b in _ladder(s, (unit,)) if s % b == 0]

    cands = [(bm, bn, bk) for bm in divs(sm, 8) for bn in divs(sn, 128)
             for bk in divs(sk, 128)]
    key = (f"m{m}n{n}k{k}|s{sm}x{sn}x{sk}|{jnp.dtype(q_dtype_a).name}"
           f"+{jnp.dtype(q_dtype_b).name}|{_backend_tag(impl)}")
    kernel = "blockscale_gemm"
    hit = peek(kernel, key, cache_dir=cache_dir)
    if hit is not None and tuple(hit.tiles) in cands:
        return tuple(hit.tiles), hit
    if not sweep and bench_fn is None:
        return (sm, sn, sk), TuneResult((sm, sn, sk), None, "default")

    if bench_fn is None:
        from .blockscale_gemm import blockscale_gemm_pallas
        rng = np.random.default_rng(0)
        interp = impl == "pallas_interpret"
        mp, np_, kp = _pad_to(m, sm), _pad_to(n, sn), _pad_to(k, sk)
        a = jnp.asarray(rng.normal(0, 1, (mp, kp)), jnp.float32)
        b = jnp.asarray(rng.normal(0, 1, (kp, np_)), jnp.float32)
        sa = jnp.ones((mp // sm, kp // sk), jnp.float32)
        sb = jnp.ones((kp // sk, np_ // sn), jnp.float32)

        def bench_fn(tl):
            bm, bn, bk = tl
            return time_us_median(
                lambda: blockscale_gemm_pallas(
                    a, b, sa, sb, q_dtype_a=q_dtype_a, q_dtype_b=q_dtype_b,
                    block_m=bm, block_n=bn, block_k=bk,
                    scale_block_m=sm, scale_block_n=sn, scale_block_k=sk,
                    interpret=interp),
                warmup=0, iters=1)

    res = autotune(kernel, key, cands, bench_fn, iters=iters,
                   cache_dir=cache_dir)
    return tuple(res.tiles), res


def attention_tiles(kind: str, bh: int, s: int, t: int, hd: int, *,
                    fmt_k=None, fmt_v=None, causal: bool = True,
                    impl: str = "pallas", sweep: bool = True,
                    bench_fn=None, cache_dir=None,
                    iters: int = 3) -> "tuple[tuple, TuneResult]":
    """Tuned (block_q, block_k) for the flash/decode sweeps.

    ``kind`` ∈ {'flash', 'mx_flash', 'decode', 'mx_decode'} — the four
    §11/§12 kernels.  Candidates divide S and T exactly (q floor 8 for
    the train/prefill kernels, 1 for decode — §12's short-q convention);
    the packed variants key on the KV formats, whose codec only affects
    byte traffic, not legality of (bq, bk).  Falls back to the static
    heuristic on a cache miss when ``sweep=False``.
    """
    assert kind in ("flash", "mx_flash", "decode", "mx_decode"), kind
    from ..core.formats import get_mx_format
    decode = kind.endswith("decode")
    q_floor = 1 if decode else 8
    cands = attention_tile_candidates(s, t, q_floor=q_floor)
    fk = get_mx_format(fmt_k).name if fmt_k is not None else "carrier"
    fv = (get_mx_format(fmt_v).name if fmt_v is not None else fk)
    key = (f"bh{bh}s{s}t{t}hd{hd}|{fk}+{fv}|causal={int(causal)}"
           f"|{_backend_tag(impl)}")
    kernel = f"{kind}_attention"
    hit = peek(kernel, key, cache_dir=cache_dir)
    if hit is not None and tuple(hit.tiles) in cands:
        return tuple(hit.tiles), hit
    if not sweep and bench_fn is None:
        from . import ops
        static = (ops.decode_attention_blocks(s, t) if decode
                  else (ops.attention_blocks(s, t) or (8, 8)))
        return static, TuneResult(static, None, "default")

    if bench_fn is None:
        bench_fn = _attention_bench(kind, bh, s, t, hd, fmt_k, fmt_v,
                                    causal, impl)
    res = autotune(kernel, key, cands, bench_fn, iters=iters,
                   cache_dir=cache_dir)
    return tuple(res.tiles), res


def _attention_bench(kind, bh, s, t, hd, fmt_k, fmt_v, causal, impl):
    """Synthetic-operand bench closure for one attention kernel family."""
    import jax.numpy as jnp
    from ..core.formats import get_mx_format
    from .codec import get_codec

    rng = np.random.default_rng(0)
    interp = impl == "pallas_interpret"
    q = jnp.asarray(rng.normal(0, 1, (bh, s, hd)), jnp.float32)
    if kind in ("mx_flash", "mx_decode"):
        mx_k = get_mx_format(fmt_k)
        mx_v = get_mx_format(fmt_v) if fmt_v is not None else mx_k
        ck, cv = get_codec(mx_k), get_codec(mx_v)
        kp = jnp.asarray(rng.integers(
            0, 256, (bh, t, ck.packed_cols(hd)), dtype=np.uint8))
        vp = jnp.asarray(rng.integers(
            0, 256, (bh, t, cv.packed_cols(hd)), dtype=np.uint8))
        s8 = jnp.full((bh, t, hd // mx_k.group), 127, jnp.uint8)
        if kind == "mx_flash":
            from .flash_attention import mx_flash_attention_pallas

            def run(bq, bk):
                return mx_flash_attention_pallas(
                    q, kp, s8, vp, s8, mx_k=mx_k, mx_v=mx_v, causal=causal,
                    block_q=bq, block_k=bk, interpret=interp)
        else:
            from .decode_attention import mx_decode_attention_pallas
            lens = jnp.zeros((bh,), jnp.int32)

            def run(bq, bk):
                return mx_decode_attention_pallas(
                    q, kp, s8, vp, s8, lens, mx_k=mx_k, mx_v=mx_v,
                    block_q=bq, block_k=bk, interpret=interp)
    else:
        k = jnp.asarray(rng.normal(0, 1, (bh, t, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (bh, t, hd)), jnp.float32)
        if kind == "flash":
            from .flash_attention import flash_attention_pallas

            def run(bq, bk):
                return flash_attention_pallas(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=interp)
        else:
            from .decode_attention import decode_attention_pallas
            lens = jnp.zeros((bh,), jnp.int32)

            def run(bq, bk):
                return decode_attention_pallas(
                    q, k, v, lens, block_q=bq, block_k=bk, interpret=interp)

    def bench_fn(tl):
        bq, bk = tl
        return time_us_median(lambda: run(bq, bk), warmup=0, iters=1)

    return bench_fn
