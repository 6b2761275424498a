"""Multi-device integration tests (subprocess with forced device count):

* compressed fp8 gradient all-reduce == exact mean (within fp8 error),
  error feedback keeps accumulated drift tiny;
* a (data=2, model=2)-sharded train step produces the same losses as the
  single-device step — the sharding rules don't change the math.
"""
import os
import subprocess
import sys
import textwrap


def _run(script: str, timeout=560):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, (r.stderr[-3000:] or r.stdout[-3000:])
    return r.stdout


def test_compressed_allreduce_8dev():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.optim.grad_compress import (compressed_psum_mean,
                                               error_feedback_init)
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        # per-device distinct gradients, laid out on the data axis
        g_all = rng.normal(0, 1, (8, 256)).astype(np.float32)
        gd = jax.device_put(jnp.asarray(g_all),
                            NamedSharding(mesh, P("data", None)))

        # reduce over data: wrap so each shard passes its own row
        import functools
        def one(g, e):
            r, ne = compressed_psum_mean({"w": g}, {"w": e}, mesh, "data")
            return r["w"], ne["w"]
        ef = jnp.zeros((8, 256), jnp.float32)
        efd = jax.device_put(ef, NamedSharding(mesh, P("data", None)))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("data", None), P("data", None)),
                           out_specs=(P("data", None), P("data", None)),
                           check_vma=False)
        def run(g, e):
            from repro.optim.grad_compress import _quantize_leaf
            gc = g[0] + e[0]
            q, s = _quantize_leaf(gc, jnp.float8_e5m2)
            ne = gc - q.astype(jnp.float32) * s
            qs = jax.lax.all_gather(q, "data")
            ss = jax.lax.all_gather(s, "data")
            red = jnp.tensordot(ss, qs.astype(jnp.float32), axes=((0,),(0,)))
            return (red / 8)[None], ne[None]

        acc_t = np.zeros(256); acc_c = np.zeros(256)
        e = efd
        for it in range(30):
            red, e = run(gd, e)
            acc_t += g_all.mean(0)
            acc_c += np.asarray(red)[0]
        rel = np.abs(acc_c - acc_t).max() / (np.abs(acc_t).max() + 1e-9)
        assert rel < 0.02, rel
        # single-shot fp8 reduction is coarse (>= 1% typ); EF fixed it
        print("COMP_OK", rel)
    """))
    assert "COMP_OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh, set_mesh
        from repro.configs import ARCHS
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig
        from repro.parallel.sharding import make_rules, param_pspecs
        from repro.train.train_step import make_train_state, make_train_step

        cfg = ARCHS["deepseek-7b"].reduced()
        model = build_model(cfg)
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, schedule="constant")
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)))

        def losses(mesh):
            from contextlib import nullcontext
            state = make_train_state(model, jax.random.key(0), opt)
            rules = make_rules(mesh) if mesh else None
            step = make_train_step(model, opt, rules=rules, impl="xla")
            if mesh is not None:
                pspecs = param_pspecs(
                    jax.eval_shape(lambda: state["params"]), mesh)
                sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                    is_leaf=lambda x: type(x).__name__ == "PartitionSpec")
                state["params"] = jax.tree.map(jax.device_put,
                                               state["params"], sh)
            out = []
            stepj = jax.jit(step)
            with set_mesh(mesh) if mesh is not None else nullcontext():
                for _ in range(3):
                    state, m = stepj(state, toks)
                    out.append(float(m["loss"]))
            return out

        l1 = losses(None)
        mesh = make_mesh((2, 2), ("data", "model"))
        l2 = losses(mesh)
        print("L1", l1); print("L2", l2)
        np.testing.assert_allclose(l1, l2, rtol=2e-2, atol=2e-2)
        print("SHARD_OK")
    """))
    assert "SHARD_OK" in out


def test_tp_gemm_matches_reference():
    """Explicit narrow-wire TP GEMMs == plain qlinear within fp8 noise."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh, set_mesh
        from repro.core.policy import HFP8
        from repro.core.linear import qlinear
        from repro.parallel.sharding import make_rules
        from repro.parallel.tp_gemm import tp_column_linear, tp_row_linear
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, seq_shard=True)
        rng = np.random.default_rng(0)
        B, S, K, N = 4, 16, 32, 64
        x = jnp.asarray(rng.normal(0, 1, (B, S, K)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(0, 0.3, (K, N)), jnp.bfloat16)

        def loss_tp(x, w):
            return (tp_column_linear(x, w, HFP8, rules)
                    .astype(jnp.float32) ** 2).sum()

        def loss_ref(x, w):
            return (qlinear(x, w, HFP8, impl="xla")
                    .astype(jnp.float32) ** 2).sum()

        with set_mesh(mesh):
            vt, gt = jax.jit(jax.value_and_grad(loss_tp, (0, 1)))(x, w)
        vr, gr = jax.jit(jax.value_and_grad(loss_ref, (0, 1)))(x, w)
        assert abs(float(vt) - float(vr)) / float(vr) < 0.05, (vt, vr)
        for a, b in zip(jax.tree.leaves(gt), jax.tree.leaves(gr)):
            na = np.asarray(a, np.float32); nb = np.asarray(b, np.float32)
            denom = np.abs(nb).max() + 1e-6
            assert np.abs(na - nb).max() / denom < 0.3, \
                np.abs(na - nb).max() / denom

        # row-parallel
        h = jnp.asarray(rng.normal(0, 1, (B, S, N)), jnp.bfloat16)
        w2 = jnp.asarray(rng.normal(0, 0.3, (N, K)), jnp.bfloat16)
        def loss_tp2(h, w2):
            return (tp_row_linear(h, w2, HFP8, rules)
                    .astype(jnp.float32) ** 2).sum()
        def loss_ref2(h, w2):
            return (qlinear(h, w2, HFP8, impl="xla")
                    .astype(jnp.float32) ** 2).sum()
        with set_mesh(mesh):
            vt2, gt2 = jax.jit(jax.value_and_grad(loss_tp2, (0, 1)))(h, w2)
        vr2, gr2 = jax.jit(jax.value_and_grad(loss_ref2, (0, 1)))(h, w2)
        assert abs(float(vt2) - float(vr2)) / float(vr2) < 0.05
        print("TPGEMM_OK")
    """))
    assert "TPGEMM_OK" in out


def test_block_tp_gemm_matches_block_qlinear():
    """Block-scaled TP path ≡ single-device block-scaled qlinear within
    wire-format tolerance (fwd + grads), and proj() routes hfp8_block to
    the TP GEMM under sequence-parallel rules."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.core.policy import get_policy
        from repro.core.linear import qlinear
        from repro.parallel.sharding import make_rules
        from repro.parallel.tp_gemm import (tp_applicable, tp_column_linear,
                                            tp_row_linear)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, seq_shard=True)
        pol = get_policy("hfp8_block")
        rng = np.random.default_rng(0)
        B, S, K, N = 4, 16, 32, 64
        x = jnp.asarray(rng.normal(0, 1, (B, S, K)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(0, 0.3, (K, N)), jnp.bfloat16)
        assert tp_applicable(x, rules, pol)  # block policy no longer opts out

        def check(tp_fn, x, w):
            def loss_tp(x, w):
                return (tp_fn(x, w, pol, rules).astype(jnp.float32)**2).sum()
            def loss_ref(x, w):
                return (qlinear(x, w, pol, impl="xla")
                        .astype(jnp.float32) ** 2).sum()
            with set_mesh(mesh):
                vt, gt = jax.jit(jax.value_and_grad(loss_tp, (0, 1)))(x, w)
            vr, gr = jax.jit(jax.value_and_grad(loss_ref, (0, 1)))(x, w)
            assert abs(float(vt) - float(vr)) / float(vr) < 0.05, (vt, vr)
            for a, b in zip(jax.tree.leaves(gt), jax.tree.leaves(gr)):
                na = np.asarray(a, np.float32)
                nb = np.asarray(b, np.float32)
                rel = np.abs(na - nb).max() / (np.abs(nb).max() + 1e-6)
                assert rel < 0.3, rel

        check(tp_column_linear, x, w)
        h = jnp.asarray(rng.normal(0, 1, (B, S, N)), jnp.bfloat16)
        w2 = jnp.asarray(rng.normal(0, 0.3, (N, K)), jnp.bfloat16)
        check(tp_row_linear, h, w2)

        # proj() routing: with hfp8_block + seq-parallel rules the block
        # path goes through the TP GEMM, not GSPMD qlinear
        import repro.models.layers as L
        hits = []
        orig = L.tp_column_linear
        def spy(*a, **k):
            hits.append(1)
            return orig(*a, **k)
        L.tp_column_linear = spy
        try:
            with set_mesh(mesh):
                y = jax.jit(lambda x, w: L.proj(
                    x, w, None, pol, rules, "xla", kind="col"))(x, w)
        finally:
            L.tp_column_linear = orig
        assert hits, "proj() did not route hfp8_block to the TP GEMM"
        assert y.shape == (B, S, N)
        print("BLOCKTP_OK")
    """))
    assert "BLOCKTP_OK" in out


def test_mx_tp_gemm_bit_exact_vs_single_device():
    """MX over the explicit TP wire (DESIGN.md §9): fwd/dgrad/wgrad of
    the column- and row-parallel MX GEMMs are BIT-EXACT against the
    single-device mxfp8 qlinear (ops.mx_gemm) on exact-arithmetic
    operands — small-int activations, one-hot weight columns, a
    2-token-support cotangent, so every quantize/dequant (including
    the wire's own E8M0 re-grouping) and every f32 partial sum is
    exact — and proj() routes mxfp8 onto the TP wire."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.core.policy import get_policy
        from repro.core.linear import qlinear
        from repro.parallel.sharding import make_rules
        from repro.parallel.tp_gemm import (tp_applicable, tp_column_linear,
                                            tp_row_linear)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, seq_shard=True)
        pol = get_policy("mxfp8")
        B, S, K, N = 4, 32, 64, 128
        rng = np.random.default_rng(7)
        x = rng.integers(-2, 3, (B, S, K)).astype(np.float32)
        assert tp_applicable(jnp.asarray(x), rules, pol)
        w = np.zeros((K, N), np.float32)
        for n in range(N):
            w[n % K, n] = rng.choice([-2.0, -1.0, 1.0, 2.0])
        g = np.zeros((B, S, N), np.float32)
        for (b, s) in [(0, 3), (2, 17)]:
            g[b, s] = rng.choice([-1.0, 0.0, 1.0], N)

        def check(tp_fn, x, w, g):
            xj = jnp.asarray(x, jnp.bfloat16)
            wj = jnp.asarray(w, jnp.bfloat16)
            gj = jnp.asarray(g, jnp.bfloat16)
            def tp(x, w):
                y, vjp = jax.vjp(
                    lambda x, w: tp_fn(x, w, pol, rules), x, w)
                return (y,) + vjp(gj)
            def sd(x, w):
                y, vjp = jax.vjp(
                    lambda x, w: qlinear(x, w, pol, impl="xla"), x, w)
                return (y,) + vjp(gj)
            # the ambient mesh is entered outside the trace (jax.set_mesh
            # refuses to be used inside jax.jit)
            with set_mesh(mesh):
                got = jax.jit(tp)(xj, wj)
            want = jax.jit(sd)(xj, wj)
            for name, a, b in zip(("y", "dx", "dw"), got, want):
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    err_msg=name)

        check(tp_column_linear, x, w, g)

        # row-parallel: one nonzero per weight column (injective map)
        x2 = rng.integers(-2, 3, (B, S, N)).astype(np.float32)
        w2 = np.zeros((N, K), np.float32)
        perm = rng.permutation(N)[:K]
        for k in range(K):
            w2[perm[k], k] = rng.choice([-2.0, -1.0, 1.0, 2.0])
        g2 = np.zeros((B, S, K), np.float32)
        for (b, s) in [(1, 5), (3, 30)]:
            g2[b, s] = rng.choice([-1.0, 0.0, 1.0], K)
        check(tp_row_linear, x2, w2, g2)

        # proj() routes mxfp8 onto the explicit TP wire
        import repro.models.layers as L
        hits = []
        orig = L.tp_column_linear
        def spy(*a, **k):
            hits.append(1)
            return orig(*a, **k)
        L.tp_column_linear = spy
        try:
            with set_mesh(mesh):
                y = jax.jit(lambda x, w: L.proj(
                    x, w, None, pol, rules, "xla", kind="col"))(
                    jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16))
        finally:
            L.tp_column_linear = orig
        assert hits, "proj() did not route mxfp8 to the TP GEMM"
        assert y.shape == (B, S, N)
        print("MXTP_OK")
    """))
    assert "MXTP_OK" in out


def test_moe_ep_matches_reference():
    """shard_map expert-parallel MoE == einsum dispatch reference."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.configs import ARCHS
        from repro.core.policy import get_policy
        from repro.models import moe as MOE
        from repro.parallel.sharding import make_rules
        cfg = dataclasses.replace(
            ARCHS["granite-moe-3b-a800m"].reduced(),
            n_experts=6, top_k=2, capacity_factor=8.0)  # high cap: no drops
        policy = get_policy("bf16")  # isolate dispatch math from fp8 noise
        rng = np.random.default_rng(0)
        params = MOE.init_moe(jax.random.key(0), cfg, jnp.bfloat16)
        x = jnp.asarray(rng.normal(0, 1, (4, 8, cfg.d_model)), jnp.bfloat16)
        y_ref, aux_ref = jax.jit(lambda p, v: MOE.moe_ffn(
            v, p, cfg, policy, rules=None, impl="xla"))(params, x)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, seq_shard=True)
        with set_mesh(mesh):
            y_ep, aux_ep = jax.jit(lambda p, v: MOE.moe_ffn_ep(
                v, p, cfg, policy, rules=rules, impl="xla"))(params, x)
        np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=0.05, atol=0.05)
        assert abs(float(aux_ep["loss"]) - float(aux_ref["loss"])) < 1e-3
        # aux is a metrics dict on both paths; capacity_factor=8 with the
        # t_loc*k clamp means nothing drops on either
        for aux in (aux_ref, aux_ep):
            assert set(aux) == {"loss", "drop_frac", "capacity"}, aux
            assert float(aux["drop_frac"]) == 0.0, aux
        # EP capacity is clamped to the local token supply: t_loc=16, k=2
        assert float(aux_ep["capacity"]) <= 16 * 2, aux_ep
        print("MOEEP_OK")
    """))
    assert "MOEEP_OK" in out


def test_mx_dp_wire_bit_exact_vs_oracle_8dev():
    """The packed MX gradient wire (DESIGN.md §13) on a real 8-way data
    axis is BIT-EXACT against the numpy oracle: per-source
    exact-arithmetic operands (span=8 keeps every 8-source f32 partial
    sum exact) with one poisoned group — reduced mean AND per-source
    new error feedback match ``compressed_mean_mx_ref`` element for
    element, NaN poison included."""
    out = _run(textwrap.dedent("""
        import os, sys, functools
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core.formats import get_mx_format
        from repro.kernels.ref import compressed_mean_mx_ref
        from fuzz import exact_mx_operands

        mesh = make_mesh((8,), ("data",))
        for name in ("mxfp6e3m2", "mxfp4e2m1"):
            mx = get_mx_format(name)
            rng = np.random.default_rng(3)
            a, _ = exact_mx_operands(rng, 8, 256, 1, mx, span=8)
            g_all = a.astype(np.float32)       # row i = source replica i
            sh = NamedSharding(mesh, P("data", None))
            gd = jax.device_put(jnp.asarray(g_all), sh)
            ed = jax.device_put(jnp.zeros_like(gd), sh)

            @jax.jit
            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P("data", None), P("data", None)),
                               out_specs=(P("data", None), P("data", None)),
                               check_vma=False)
            def run(g, e, mx=mx):
                from repro.optim.grad_compress import _leaf_mx
                red, ne = _leaf_mx(g[0], e[0], mx, "data", 8, 4)
                return red[None], ne[None]

            red, ne = run(gd, ed)
            want, want_efs = compressed_mean_mx_ref(
                [g_all[i] for i in range(8)],
                [np.zeros(256, np.float32)] * 8, mx=name)
            assert not np.all(np.isfinite(want))   # poison reached output
            for d in range(8):
                np.testing.assert_array_equal(
                    np.asarray(red)[d], want, err_msg=f"{name} red dev{d}")
                np.testing.assert_array_equal(
                    np.asarray(ne)[d], want_efs[d],
                    err_msg=f"{name} ef dev{d}")
        print("MXDP_ORACLE_OK")
    """))
    assert "MXDP_ORACLE_OK" in out


def test_mx_dispatch_a2a_bit_exact_vs_oracle():
    """The MoE packed dispatch wire: fwd AND vjp of ``mx_dispatch_a2a``
    on a 4-way model axis are bit-exact against the numpy roundtrip
    oracle composed with the a2a block permutation (tiled split-0 /
    concat-0: out[i, j] = in[j, i] per row block).  The bwd hop uses
    the wide bwd format, checked independently."""
    out = _run(textwrap.dedent("""
        import os, sys, functools
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core.formats import get_mx_format
        from repro.kernels.ref import mx_dispatch_wire_ref
        from repro.parallel.tp_gemm import mx_dispatch_a2a
        from fuzz import exact_mx_operands

        tp, R, d = 4, 8, 64
        mx_f, mx_b = "mxfp6e3m2", "mxfp8e5m2"
        mxf = get_mx_format(mx_f)
        rng = np.random.default_rng(11)
        x, _ = exact_mx_operands(rng, tp * tp * R, d, 1, mxf, span=8)
        g, _ = exact_mx_operands(rng, tp * tp * R, d, 1,
                                 get_mx_format(mx_b), span=8,
                                 specials=False)
        X = x.astype(np.float32).reshape(tp, tp * R, d)
        G = g.astype(np.float32).reshape(tp, tp * R, d)
        mesh = make_mesh((tp,), ("model",))
        sh = NamedSharding(mesh, P("model", None, None))
        xd = jax.device_put(jnp.asarray(X), sh)
        gd = jax.device_put(jnp.asarray(G), sh)

        @jax.jit
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("model", None, None),) * 2,
                           out_specs=(P("model", None, None),) * 2,
                           check_vma=False)
        def run(xl, gl):
            y, vjp = jax.vjp(lambda v: mx_dispatch_a2a(
                v, "model", get_mx_format("mxfp6e3m2"),
                get_mx_format("mxfp8e5m2")), xl[0])
            (dx,) = vjp(gl[0])
            return y[None], dx[None]

        y, dx = run(xd, gd)
        perm = lambda A: (A.reshape(tp, tp, R, d).transpose(1, 0, 2, 3)
                          .reshape(tp, tp * R, d))
        want_y = perm(mx_dispatch_wire_ref(X, mx=mx_f))
        want_dx = perm(mx_dispatch_wire_ref(G, mx=mx_b))
        assert not np.all(np.isfinite(want_y))   # poison group survives
        np.testing.assert_array_equal(np.asarray(y), want_y, err_msg="fwd")
        np.testing.assert_array_equal(np.asarray(dx), want_dx,
                                      err_msg="bwd")
        print("MXA2A_ORACLE_OK")
    """))
    assert "MXA2A_ORACLE_OK" in out


def test_moe_ep_packed_wire_matches_einsum():
    """EP MoE with an MX policy routes both dispatch all-to-alls through
    the packed wire (spied) and still matches the einsum reference
    within wire-format tolerance; a group-misaligned d_model refuses the
    wire and falls back to the raw bf16 a2a."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.configs import ARCHS
        from repro.core.policy import get_policy
        from repro.models import moe as MOE
        import repro.parallel.tp_gemm as TPG
        from repro.parallel.sharding import make_rules

        cfg = dataclasses.replace(
            ARCHS["granite-moe-3b-a800m"].reduced(),
            n_experts=6, top_k=2, capacity_factor=8.0)
        assert cfg.d_model % 32 == 0    # group-aligned: wire eligible
        policy = get_policy("mxfp8")
        rng = np.random.default_rng(0)
        params = MOE.init_moe(jax.random.key(0), cfg, jnp.bfloat16)
        x = jnp.asarray(rng.normal(0, 1, (4, 8, cfg.d_model)), jnp.bfloat16)
        y_ref, aux_ref = jax.jit(lambda p, v: MOE.moe_ffn(
            v, p, cfg, policy, rules=None, impl="xla"))(params, x)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, seq_shard=True)
        hits = []
        orig = TPG.mx_dispatch_a2a
        def spy(*a, **k):
            hits.append(1)
            return orig(*a, **k)
        TPG.mx_dispatch_a2a = spy
        try:
            with set_mesh(mesh):
                y_ep, aux_ep = jax.jit(lambda p, v: MOE.moe_ffn_ep(
                    v, p, cfg, policy, rules=rules, impl="xla"))(params, x)
        finally:
            TPG.mx_dispatch_a2a = orig
        assert len(hits) >= 2, "both a2a hops should take the packed wire"
        # the EP path quantizes the dispatch buffer through the wire on
        # top of the GEMM quantization both paths share -> slightly
        # wider band than the bf16-wire parity test
        np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=0.05, atol=0.12)
        assert abs(float(aux_ep["loss"]) - float(aux_ref["loss"])) < 2e-3
        assert float(aux_ep["drop_frac"]) == 0.0, aux_ep

        # misaligned d_model (40 % 32 != 0): bf16 fallback, wire unused
        cfg_mis = dataclasses.replace(cfg, d_model=40, d_ff=80)
        params_mis = MOE.init_moe(jax.random.key(1), cfg_mis, jnp.bfloat16)
        x_mis = jnp.asarray(rng.normal(0, 1, (4, 8, 40)), jnp.bfloat16)
        hits2 = []
        TPG.mx_dispatch_a2a = (lambda *a, **k:
                               (hits2.append(1), orig(*a, **k))[1])
        try:
            with set_mesh(mesh):
                y_mis, _ = jax.jit(lambda p, v: MOE.moe_ffn_ep(
                    v, p, cfg_mis, policy, rules=rules, impl="xla"))(
                    params_mis, x_mis)
        finally:
            TPG.mx_dispatch_a2a = orig
        assert not hits2, "misaligned d_model must not take the MX wire"
        assert np.all(np.isfinite(np.asarray(y_mis, np.float32)))
        print("MOEMX_OK")
    """))
    assert "MOEMX_OK" in out


def test_dp_compress_train_step_matches_uncompressed():
    """``make_train_step(dp_compress=True)`` trains a real mxfp6 model
    over the compressed DP wire (``Policy.mx_dp_grad`` = mxfp6e3m2):
    losses track the uncompressed run, nothing skips, and the error
    feedback picks up the (real, nonzero) quantization residual."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig
        from repro.parallel.sharding import make_rules
        from repro.train.train_step import make_train_state, make_train_step

        cfg = ModelConfig(name="dpc", family="dense", n_layers=1,
            d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
            vocab_size=64, head_dim=32, policy_name="mxfp6",
            attn_q_chunk=32)
        mesh = make_mesh((4,), ("data",))
        rules = make_rules(mesh)
        model = build_model(cfg)
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 32)))

        def losses(dp_compress):
            state = make_train_state(model, jax.random.key(0), opt,
                                     dp_compress=dp_compress)
            step = jax.jit(make_train_step(model, opt, rules=rules,
                                           impl="xla",
                                           dp_compress=dp_compress))
            out = []
            with set_mesh(mesh):
                for _ in range(3):
                    state, m = step(state, toks)
                    out.append(float(m["loss"]))
                    assert int(m["skipped"]) == 0
            return out, state

        lc, sc = losses(True)
        lu, su = losses(False)
        assert "ef" in sc and "ef" not in su
        assert all(np.isfinite(lc)), lc
        np.testing.assert_allclose(lc, lu, rtol=0.05, atol=0.05)
        ef_norm = sum(float(jnp.abs(e).sum())
                      for e in jax.tree.leaves(sc["ef"]))
        assert ef_norm > 0, "mxfp6 residual should land in the ef tree"
        print("COMPRESSED", lc, "PLAIN", lu)
        print("DPC_OK")
    """))
    assert "DPC_OK" in out


def test_elastic_restore_onto_mesh():
    """A checkpoint written layout-free restores onto a (2,2) mesh with
    explicit shardings — the elastic-scaling path (save on N chips,
    resume on M)."""
    out = _run(textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh
        from repro.checkpoint.ckpt import CheckpointManager
        from repro.configs import ARCHS
        from repro.models import build_model
        from repro.parallel.sharding import param_pspecs

        cfg = ARCHS["llama3.2-3b"].reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(7, params)                      # "saved on 1 chip"

        mesh = make_mesh((2, 2), ("data", "model"))
        pspecs = param_pspecs(jax.eval_shape(lambda: params), mesh)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: type(x).__name__ == "PartitionSpec")
        back = mgr.restore(7, params, shardings)  # "resumed on 4 chips"
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert len(b.sharding.device_set) >= 1
        # at least the big 2D params actually ended up distributed
        emb = back["embed"]
        assert len(emb.sharding.device_set) == 4, emb.sharding
        print("ELASTIC_OK")
    """))
    assert "ELASTIC_OK" in out


def test_mxfp6_train_step_tp_matches_gspmd():
    """mxfp6 (DESIGN.md §10) runs a real train step through
    models/layers.py on BOTH distribution paths: sequence-parallel
    rules route the group-aligned projections onto the explicit TP
    wire (packed sub-byte payloads + E8M0 byte grids — asserted via a
    proj() spy), plain rules keep them under GSPMD over the packed MX
    pipeline, and the two agree on the losses."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        import repro.models.layers as L
        from repro.optim.adamw import AdamWConfig
        from repro.parallel.sharding import make_rules
        from repro.train.train_step import make_train_state, make_train_step

        cfg = ModelConfig(
            name="sub-byte-mxfp6", family="dense", n_layers=1,
            d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
            vocab_size=64, head_dim=32, policy_name="mxfp6",
            attn_q_chunk=32)
        mesh = make_mesh((2, 2), ("data", "model"))
        model = build_model(cfg)
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 32)))

        def losses(rules, steps=2):
            state = make_train_state(model, jax.random.key(0), opt)
            step = jax.jit(make_train_step(model, opt, rules=rules,
                                           impl="xla"))
            out = []
            with set_mesh(mesh):
                for _ in range(steps):
                    state, m = step(state, toks)
                    out.append(float(m["loss"]))
            return out

        hits = []
        orig = L.tp_column_linear
        L.tp_column_linear = (lambda *a, **k:
                              (hits.append(1), orig(*a, **k))[1])
        try:
            l_tp = losses(make_rules(mesh, seq_shard=True))
        finally:
            L.tp_column_linear = orig
        assert hits, "proj() did not route mxfp6 to the TP wire"
        l_g = losses(make_rules(mesh))
        assert all(np.isfinite(l_tp)) and all(np.isfinite(l_g))
        np.testing.assert_allclose(l_tp, l_g, rtol=0.05, atol=0.05)
        print("TP", l_tp, "GSPMD", l_g)
        print("MXFP6_TP_OK")
    """))
    assert "MXFP6_TP_OK" in out


def test_mxfp4_train_step_and_misaligned_fallback():
    """mxfp4 takes the explicit TP wire on group-aligned shapes and
    trains (finite losses); a group-MISALIGNED model (seq % 32 != 0)
    refuses the wire — proj() spy never fires — and still trains via
    the GSPMD fallback."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        import repro.models.layers as L
        from repro.optim.adamw import AdamWConfig
        from repro.parallel.sharding import make_rules
        from repro.train.train_step import make_train_state, make_train_step

        def run(seq):
            cfg = ModelConfig(
                name="sub-byte-mxfp4", family="dense", n_layers=1,
                d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                vocab_size=64, head_dim=32, policy_name="mxfp4",
                attn_q_chunk=seq)
            mesh = make_mesh((2, 2), ("data", "model"))
            model = build_model(cfg)
            opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
            state = make_train_state(model, jax.random.key(0), opt)
            rules = make_rules(mesh, seq_shard=True)
            step = jax.jit(make_train_step(model, opt, rules=rules,
                                           impl="xla"))
            toks = jnp.asarray(
                np.random.default_rng(0).integers(0, 64, (4, seq)))
            hits = []
            orig = L.tp_column_linear
            L.tp_column_linear = (lambda *a, **k:
                                  (hits.append(1), orig(*a, **k))[1])
            try:
                with set_mesh(mesh):
                    losses = []
                    for _ in range(2):
                        state, m = step(state, toks)
                        losses.append(float(m["loss"]))
            finally:
                L.tp_column_linear = orig
            return losses, bool(hits)

        l_ok, wired = run(32)          # seq 32: whole groups -> TP wire
        assert wired, "aligned mxfp4 did not take the TP wire"
        assert all(np.isfinite(l_ok)), l_ok
        l_mis, wired_mis = run(24)     # seq 24: no whole groups
        assert not wired_mis, "misaligned shapes took the wire"
        assert all(np.isfinite(l_mis)), l_mis
        print("OK", l_ok, "MIS", l_mis)
        print("MXFP4_TP_OK")
    """))
    assert "MXFP4_TP_OK" in out
