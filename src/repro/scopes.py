"""The names the program gives its layers and phases in a profile.

Device side: ``jax.named_scope``.  A scope is compile-time metadata only:
every HLO instruction traced inside one carries the scope path in its
``metadata={op_name=...}`` (for example
``jit(train_step)/transpose(jvp())/while/body/.../attn/core/dgrad/dot_general``);
the compiled ops, their names and their cost are unchanged.  A profile's
device ops are charged to scopes by looking their instructions up in the
compiled module's text (``bench/scoped.py``).  The vocabulary is fixed:

* ``LAYERS`` — the top-level layer of a model step: ``embed``, ``norm``,
  ``attn``, ``mlp``, ``moe``, ``head`` (the final norm and the logits
  product), ``loss`` (the cross-entropy after the head), ``optimizer``
  (``optim.adamw.adamw_update``).
* ``ATTN`` — inside ``attn``: ``qkv`` (the q/k/v projections), ``rope``,
  ``kv_write`` (a decode cache's update: the packed KV quantize and the
  ``.at[].set`` into the page pool), ``kv_gather`` (the page gather and
  the GQA head repeat), ``core`` (scores, softmax and values: the
  chunked XLA attention, the quantized flash path, the decode-attention
  kernels) and ``out`` (the output projection).
* ``GEMM`` — inside ``core.linear.qlinear``/``linear``: ``quant_x``,
  ``quant_w``, ``gemm`` in the forward; ``quant_g``, ``dgrad``, ``wgrad``
  in the custom-VJP backward (the weight's and activation's quantize
  passes of the backward nest as ``dgrad/quant_w``, ``wgrad/quant_x``
  and ``wgrad/quant_g``).

Pallas kernels keep their function names (``exsdotp_gemm_pallas``,
``mx_gemm_packed_pallas``, ``mx_decode_attention_pallas``, ...): the
scope gives each call its role.

Host side: ``jax.profiler.TraceAnnotation`` spans, on the device trace's
clock and near free when no profiler runs.  ``SERVE_SPANS`` are opened by
``serve.scheduler.ContinuousBatcher.step``, ``TRAIN_SPANS`` by
``train.trainer.Trainer``.
"""
from __future__ import annotations

import re

__all__ = ["LAYERS", "ATTN", "GEMM", "VOCABULARY", "SERVE_SPANS",
           "TRAIN_SPANS", "scope_path"]

LAYERS = ("embed", "norm", "attn", "mlp", "moe", "head", "loss",
          "optimizer")
ATTN = ("qkv", "rope", "kv_write", "kv_gather", "core", "out")
GEMM = ("quant_x", "quant_w", "gemm", "quant_g", "dgrad", "wgrad")
VOCABULARY = frozenset(LAYERS + ATTN + GEMM)

SERVE_SPANS = ("repro.serve.admit", "repro.serve.prefill",
               "repro.serve.push_tables", "repro.serve.decode",
               "repro.serve.sample", "repro.serve.retire")
TRAIN_SPANS = ("repro.train.batch", "repro.train.dispatch",
               "repro.train.wait", "repro.train.fetch", "repro.train.ckpt")

_WRAPPER = re.compile(r"^[\w.\-]+\((.*)\)$")


def scope_path(op_name: str) -> tuple:
    """The vocabulary scopes of an ``op_name``, outermost first.

    Transformation wrappers are unwrapped (``transpose(jvp(loss))`` reads
    ``loss``) and every other component (``jit(...)``, ``while``,
    ``body``, ``checkpoint``, ``rematted_computation``, the primitive's
    own name) is dropped."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in VOCABULARY:
            out.append(part)
    return tuple(out)
