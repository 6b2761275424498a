"""Autotune machinery (DESIGN.md §14).

Four layers:

1. the sweep-and-cache core with *injected* bench stubs: a miss sweeps
   every candidate and persists the winner, a hit never re-times, the
   memo survives being dropped (re-read from disk), a version bump or a
   stale entry outside the candidate space invalidates, and failing
   candidates are skipped (all-fail falls back to the first candidate,
   unpersisted);
2. candidate legality by construction: every generated GEMM tile
   respects the sublane/lane floors, the codec ``lane_unit`` and the
   MX group, stays under the VMEM budget when a cost model is given,
   and attention tiles divide S/T exactly; the packed-GEMM layout axis
   only offers double buffering when the K loop has ≥ 2 tiles, and
   blockscale candidates only subdivide the fixed scale grid;
3. ``tiles="auto"`` numerics: with a deliberately non-default winner
   seeded into a scratch cache, the tuned path is *bitwise* equal to
   the static default on exact-arithmetic operands for all five MX
   formats (GEMM) and for the packed flash sweep — the §14 contract
   that tuning can never change results;
4. the double-buffered manual-DMA K-loop is bitwise equal to the
   grid-pipelined schedule for each codec lane class, and every
   "DESIGN.md §N" / "EXPERIMENTS.md §X" reference in src/ and
   benchmarks/ resolves to a real heading.
"""
import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

import fuzz
from repro.core import formats as F
from repro.kernels import autotune, ops
from repro.kernels.blockscale_gemm import mx_gemm_packed_pallas
from repro.kernels.codec import get_codec

MX_NAMES = list(F.MX_FORMATS)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Scratch cache dir + no env sweeping; memo cleared on both sides."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    monkeypatch.delenv("REPRO_TUNE_SWEEP", raising=False)
    autotune.clear_memo()
    yield str(tmp_path)
    autotune.clear_memo()


def _ceil_mult(x, u):
    return max(u, x + (-x) % u)


# ----------------------------------------------- sweep-and-cache core --

def test_autotune_sweeps_persists_then_hits(tune_dir):
    calls = []

    def bench(tl):
        calls.append(tl)
        return float(sum(tl))

    cands = [(32,), (8,), (16,)]
    res = autotune.autotune("toy", "k1", cands, bench, iters=3, warmup=1)
    assert res.source == "swept" and res.tiles == (8,)
    assert len(calls) == len(cands) * (3 + 1)   # warmup + iters each
    with open(os.path.join(tune_dir, "toy.json")) as f:
        data = json.load(f)
    assert data["version"] == autotune.CACHE_VERSION
    assert data["entries"]["k1"]["tiles"] == [8]

    calls.clear()
    res2 = autotune.autotune("toy", "k1", cands, bench)
    assert res2.source == "cache" and res2.tiles == (8,)
    assert not calls                             # a hit never re-times

    autotune.clear_memo()                        # force the disk re-read
    res3 = autotune.autotune("toy", "k1", cands, bench)
    assert res3.source == "cache" and res3.tiles == (8,)
    assert not calls


def test_cache_version_mismatch_invalidates(tune_dir):
    path = os.path.join(tune_dir, "toy.json")
    with open(path, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION - 1,
                   "entries": {"k": {"tiles": [8], "us": 1.0}}}, f)
    autotune.clear_memo()
    assert autotune.peek("toy", "k") is None


def test_stale_entry_outside_candidates_resweeps(tune_dir):
    autotune.autotune("toy", "k", [(64,)], lambda tl: 1.0)
    calls = []

    def bench(tl):
        calls.append(tl)
        return float(sum(tl))

    res = autotune.autotune("toy", "k", [(8,), (16,)], bench)
    assert res.source == "swept" and res.tiles == (8,) and calls


def test_failing_candidates_skipped_all_fail_defaults(tune_dir, caplog):
    """A refused candidate is skipped *and logged with its error*; when
    every candidate fails the sweep raises instead of returning an
    untested tile."""
    def bench(tl):
        if tl == (8,):
            raise RuntimeError("illegal tile")
        return float(sum(tl))

    with caplog.at_level("WARNING", logger=autotune.__name__):
        res = autotune.autotune("toy", "k2", [(8,), (16,)], bench)
    assert res.source == "swept" and res.tiles == (16,)
    assert "(8,)" in caplog.text and "illegal tile" in caplog.text

    def bomb(tl):
        raise RuntimeError("no candidate runs")

    with pytest.raises(RuntimeError, match="every candidate failed"):
        autotune.autotune("toy", "k3", [(8,), (16,)], bomb)
    assert autotune.peek("toy", "k3") is None    # failures never persist


# ----------------------------------------------- candidate legality ----

@pytest.mark.parametrize("name", MX_NAMES)
def test_gemm_candidates_respect_floors(name):
    mx = F.get_mx_format(name)
    c = get_codec(mx)
    m, n, k = 40, 200, 4 * c.lane_unit
    cands = autotune.gemm_tile_candidates(
        m, n, k, group=mx.group, lane_units=(c.lane_unit,))
    assert cands
    for bm, bn, bk in cands:
        assert bm % 8 == 0 and bn % 128 == 0
        assert bk % 128 == 0 and bk % mx.group == 0
        assert bk % c.lane_unit == 0             # packed byte run legal
        assert bm <= _ceil_mult(m, 8)            # ≤ minimally padded dims
        assert bn <= _ceil_mult(n, 128)
        assert bk <= _ceil_mult(k, c.lane_unit)


def test_gemm_candidates_respect_vmem_budget():
    def cost(tl):
        bm, bn, bk = tl
        return 64 * (bm * bk + bk * bn + bm * bn)

    free = autotune.gemm_tile_candidates(4096, 4096, 4096)
    kept = autotune.gemm_tile_candidates(4096, 4096, 4096,
                                         vmem_bytes_fn=cost)
    assert kept and set(kept) < set(free)        # pruning removed some
    for tl in kept:
        assert cost(tl) <= autotune.VMEM_BUDGET


def test_attention_candidates_divide_exactly():
    for s, t in [(40, 96), (128, 128), (1, 8), (96, 64)]:
        lo = autotune.attention_tile_candidates(s, t, q_floor=1)
        assert lo
        for bq, bk in lo:
            assert s % bq == 0 and t % bk == 0 and bk >= 8
        for bq, bk in autotune.attention_tile_candidates(s, t):
            assert bq >= 8                       # train/prefill floor


def test_packed_layout_axis_needs_two_k_tiles(tune_dir):
    seen = []

    def bench(tl):
        seen.append(tuple(tl))
        return float(len(seen))

    autotune.gemm_packed_tiles(128, 128, 256, "mxfp8e4m3", None,
                               impl="pallas_interpret", bench_fn=bench)
    cands = set(seen)
    assert any(db for *_, db in cands)
    for bm, bn, bk, db in cands:
        if db:                                   # ≥ 2 K tiles to overlap
            assert _ceil_mult(256, bk) // bk >= 2
    # the single-K-tile shape (bk = 256) must appear grid-pipelined only
    assert (128, 128, 256, 0) in cands and (128, 128, 256, 1) not in cands


def test_blockscale_candidates_subdivide_scale_grid(tune_dir):
    seen = []

    def bench(tl):
        seen.append(tuple(tl))
        return float(sum(tl))

    sm, sn, sk = 128, 128, 256
    (bm, bn, bk), res = autotune.blockscale_tiles(
        256, 256, 512, (sm, sn, sk), jnp.float8_e4m3fn, jnp.float8_e5m2,
        impl="pallas_interpret", sweep=True, bench_fn=bench)
    assert res.source == "swept"
    for tm, tn, tk in set(seen):                 # scale grid never moves
        assert sm % tm == 0 and sn % tn == 0 and sk % tk == 0
    assert (bm, bn, bk) == min(set(seen), key=sum)


# ----------------------------------------------- tiles="auto" numerics --

@pytest.mark.parametrize("name", MX_NAMES)
def test_tiles_auto_bit_exact_gemm(tune_dir, name):
    mx = F.get_mx_format(name)
    m, k, n = 16, 256, 128
    # seed a deliberately non-default winner: the stub prefers the
    # smallest tile and the double-buffered layout when offered
    tiles, db, res = autotune.gemm_packed_tiles(
        m, n, k, mx, mx, impl="pallas_interpret", sweep=True,
        bench_fn=lambda tl: float(tl[0] + tl[1] + tl[2] - tl[3]))
    assert res.source == "swept"
    assert tiles[0] == 8                         # static heuristic picks 16

    rng = np.random.default_rng(7)
    a, b = fuzz.exact_mx_operands(rng, m, k, n, mx)
    ap, sa8 = ops.mx_quantize(jnp.asarray(a), mx, packed=True)
    bp, sb8 = ops.mx_quantize(jnp.asarray(b.T), mx, packed=True)
    base = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a=mx,
                              impl="pallas_interpret")
    auto = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a=mx,
                              impl="pallas_interpret", tiles="auto")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(auto))


def test_tiles_auto_bit_exact_mx_flash(tune_dir):
    mx = F.get_mx_format("mxfp8e4m3")
    bh, s, t, hd = 2, 64, 64, 64
    tiles, res = autotune.attention_tiles(
        "mx_flash", bh, s, t, hd, fmt_k=mx, causal=True,
        impl="pallas_interpret", sweep=True,
        bench_fn=lambda tl: float(tl[0] + tl[1]))
    assert res.source == "swept"
    assert tiles == (8, 8)                       # static pick is (64, 64)

    rng = np.random.default_rng(3)
    q, k, v = fuzz.exact_attention_operands(rng, bh, s, t, hd)
    kp, ks8 = ops.mx_quantize_kv(jnp.asarray(k), mx)
    vp, vs8 = ops.mx_quantize_kv(jnp.asarray(v), mx)
    base = ops.mx_flash_attention_packed(
        jnp.asarray(q), kp, ks8, vp, vs8, mx_k=mx, impl="pallas_interpret")
    auto = ops.mx_flash_attention_packed(
        jnp.asarray(q), kp, ks8, vp, vs8, mx_k=mx, impl="pallas_interpret",
        tiles="auto")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(auto))


def test_tiles_auto_cache_miss_matches_static(tune_dir):
    """CPU CI with an empty cache: auto falls back to the static
    heuristic (no sweep, no timing) — byte-identical, zero surprise."""
    mx = F.get_mx_format("mxfp4e2m1")
    rng = np.random.default_rng(5)
    a, b = fuzz.exact_mx_operands(rng, 16, 256, 128, mx)
    ap, sa8 = ops.mx_quantize(jnp.asarray(a), mx, packed=True)
    bp, sb8 = ops.mx_quantize(jnp.asarray(b.T), mx, packed=True)
    base = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a=mx,
                              impl="pallas_interpret")
    auto = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a=mx,
                              impl="pallas_interpret", tiles="auto")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(auto))
    assert not os.path.exists(os.path.join(tune_dir, "mx_gemm_packed.json"))


# ----------------------------------------------- double buffering ------

@pytest.mark.parametrize("name", ["mxfp8e4m3", "mxfp6e2m3", "mxfp4e2m1"])
def test_double_buffer_bitwise_equal(name):
    mx = F.get_mx_format(name)
    c = get_codec(mx)
    m, n, k = 16, 128, 2 * c.lane_unit           # ≥ 2 K tiles to overlap
    rng = np.random.default_rng(11)
    a, b = fuzz.exact_mx_operands(rng, m, k, n, mx)
    ap, sa8 = ops.mx_quantize(jnp.asarray(a), mx, packed=True)
    bp, sb8 = ops.mx_quantize(jnp.asarray(b.T), mx, packed=True)
    sae8 = jnp.repeat(sa8, mx.group, axis=-1)
    sbe8 = jnp.repeat(sb8, mx.group, axis=-1)
    kw = dict(mx_a=mx, mx_b=mx, block_m=8, block_n=128,
              block_k=c.lane_unit, interpret=True)
    grid = mx_gemm_packed_pallas(ap, bp, sae8, sbe8,
                                 double_buffer=False, **kw)
    dbuf = mx_gemm_packed_pallas(ap, bp, sae8, sbe8,
                                 double_buffer=True, **kw)
    # same accumulation order — bitwise, NaN poison included
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(dbuf))


# ----------------------------------------------- § references resolve --

def test_design_section_references_resolve():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "DESIGN.md")) as f:
        sections = set(re.findall(r"^## §(\d+)", f.read(), re.M))
    with open(os.path.join(repo, "EXPERIMENTS.md")) as f:
        exp_heads = {h.split()[0]
                     for h in re.findall(r"^## (.+)$", f.read(), re.M)}
    bad = []
    for root in ("src", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(repo, root)):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, fn)) as f:
                    text = f.read()
                for run in re.findall(
                        r"DESIGN\.md\s+(§\d+(?:\s*/\s*§\d+)*)", text):
                    for num in re.findall(r"§(\d+)", run):
                        if num not in sections:
                            bad.append((fn, f"DESIGN.md §{num}"))
                for nm in re.findall(r"EXPERIMENTS\.md\s+§([\w*]+)", text):
                    if nm not in exp_heads:
                        bad.append((fn, f"EXPERIMENTS.md §{nm}"))
    assert not bad, f"dangling section references: {bad}"
