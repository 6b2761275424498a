"""Quickstart: the paper's primitive, end to end, in two minutes on CPU.

1. ExSdotp semantics: fused vs cascaded accumulation accuracy (Table IV in
   miniature);
2. the expanding-GEMM Pallas kernel (interpret mode) vs its oracle;
3. a tiny quantized-trained transformer (default HFP8: forward fp8-E4M3,
   backward fp8-E5M2, fp32 accumulation everywhere; ``--policy mxfp6``
   or ``mxfp4`` runs the packed sub-byte MX pipeline instead) — loss
   goes down;
4. greedy decoding from the trained model.

Run:  PYTHONPATH=src python examples/quickstart.py [--policy mxfp4]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import exsdotp as X
from repro.core import formats as F
from repro.core.policy import POLICIES
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import format_packed_footprint
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.serve.decode import generate
from repro.train.train_step import make_train_state, make_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--policy", default="hfp8", choices=sorted(POLICIES))
ARGS = ap.parse_args()
enable_compile_cache()

print("=" * 64)
print("1) ExSdotp: fused 3-term add beats the ExFMA cascade")
rng = np.random.default_rng(0)
a = F.quantize_np(rng.normal(0, 1, 256), "fp8")
b = F.quantize_np(rng.normal(0, 1, 256), "fp8")
exact = float(a @ b)
fused = X.exsdotp_chain_np(a, b, "fp8")
casc = X.exfma_chain_np(a, b, "fp8")
print(f"   exact={exact:+.6f} fused={fused:+.6f} (err {abs(fused-exact):.2e})"
      f" cascade={casc:+.6f} (err {abs(casc-exact):.2e})")

print("=" * 64)
print("2) Pallas expanding GEMM (interpret mode) == oracle")
A = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float8_e4m3)
B = jnp.asarray(rng.normal(0, 1, (128, 32)), jnp.float8_e5m2)
out = ops.exsdotp_gemm(A, B, 1.0, impl="pallas_interpret", blocks=(32, 32, 64))
want = ref.exsdotp_gemm_ref(A, B, 1.0)
print(f"   max|kernel - oracle| = {float(jnp.max(jnp.abs(out - want))):.2e}")

print("=" * 64)
print(f"3) {ARGS.policy} training (quantized fwd/bwd, fp32 accum)")
# the packed-payload footprint this policy's GEMM operands occupy
# (DESIGN.md §10): sub-byte MX policies really store 0.75 / 0.5 B/elem
print(format_packed_footprint(ARGS.policy))
cfg = dataclasses.replace(ARCHS["qwen2.5-3b"].reduced(), vocab_size=64,
                          policy_name=ARGS.policy)
model = build_model(cfg)
opt = AdamWConfig(lr=3e-3, warmup_steps=5, schedule="constant")
state = make_train_state(model, jax.random.key(0), opt)
step = jax.jit(make_train_step(model, opt, impl="xla"))
# learnable synthetic task: tokens follow t+1 = (t*5+1) mod V
toks = np.zeros((8, 33), np.int32)
toks[:, 0] = rng.integers(0, 64, 8)
for i in range(32):
    toks[:, i + 1] = (toks[:, i] * 5 + 1) % 64
toks = jnp.asarray(toks)
losses = []
for i in range(30):
    state, m = step(state, toks)
    losses.append(float(m["loss"]))
print(f"   loss: step0={losses[0]:.3f} -> step29={losses[-1]:.3f} "
      f"(scale={float(m.get('loss_scale', 1.0)):.0f})")
assert losses[-1] < losses[0], "HFP8 training failed to learn"

print("=" * 64)
print("4) greedy decode with KV cache")
out = generate(model, state["params"], toks[:2, :4], max_new_tokens=6,
               max_len=64)
print(f"   prompt {np.asarray(toks[0,:4])} -> generated {np.asarray(out[0])}")
print("   expected continuation:",
      [(int(toks[0, 3]) * pow(5, k+1, 64) + sum(pow(5, j, 64) for j in range(k+1))) % 64
       for k in range(6)])
print("done.")
