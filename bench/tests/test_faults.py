"""The harness's correctness check catches a broken timed path.

Each test skips the harness's look for a chip (it calls the mode's
``run`` itself, on the CPU, at a tiny size with the cell's own limits),
breaks the program underneath the timed path, drives the rest of a run
and sees ``correct`` come out false: a step that returns its state
unchanged, half of the batch left out with the mean taken over the
rest, and a served token altered where it is produced.  (The cells run on one chip, so no
exchange between chips can be left out.)  The control, the program's
next precision down (``test_control.CONTROL_POLICY``) in place of the
configuration's, goes through the same whole run and the same limits.
"""
import time

import pytest

import harness
from test_control import CONTROL_POLICY, half_batch, with_policy

TRAIN_CONF = {"name": "tiny-train", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 4, "num_hidden_layers": 2,
              "vocab_size": 256, "layer_norm_eps": 1e-5, "rope_theta": 10000,
              "use_qkv_bias": True, "tie_word_embeddings": False,
              "norm_type": "layernorm",
              "program": {"family": "dense", "policy": "hfp8",
                          "quantize_head": False, "attn_q_chunk": 8}}
SERVE_CONF = {"name": "tiny-serve", "hidden_size": 128,
              "intermediate_size": 256, "num_attention_heads": 4,
              "num_key_value_heads": 4, "num_hidden_layers": 2,
              "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
              "tie_word_embeddings": False, "norm_type": "rmsnorm",
              "program": {"family": "dense", "policy": "mxfp8",
                          "quantize_head": False, "attn_q_chunk": 8}}


def train_cell():
    wl, _ = harness.load_cell("train-stablelm-l6")
    wl["traffic"].update(seq=32, batch=2)
    wl["name"] = "test-train-faults"
    return wl


def serve_cell():
    wl, _ = harness.load_cell("serve-deepseek-l8-chat")
    wl["traffic"].update(rate_per_s=8.0, prompt_lengths=[8, 16],
                         prompt_weights=[0.5, 0.5], output_median=6,
                         output_min=4, output_max=12, warm_seconds=0.5,
                         drain_seconds=20)
    wl["server"].update(slots=4, max_len=32)
    wl.update(check_tokens=40, check_min_requests=2, check_max_requests=8,
              name="test-serve-faults")
    return wl


def run_mode(name, wl, conf, **patch):
    mode = harness.load_module(f"modes/{name}.py", f"test_mode_{name}")
    for k, v in patch.items():
        setattr(mode, k, v(getattr(mode, k)))
    return mode.run(workload=wl, conf=conf, seed=2 ** 33 + 17, seconds=1.0,
                    trace=False, t_start=time.perf_counter())


def frozen_state(make):
    def make_broken(model, opt, **kw):
        step = make(model, opt, **kw)

        def broken(state, tokens, aux=None):
            _, metrics = step(state, tokens)
            return state, metrics
        return broken
    return make_broken


def altered_token(cls):
    class Broken(cls):
        def _sample(self, logits):
            out = super()._sample(logits)
            return (out + 1) % logits.shape[-1]
    return Broken


@pytest.mark.parametrize("fault", [frozen_state, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(fault):
    out = run_mode("train", train_cell(), TRAIN_CONF,
                   make_train_step=fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name,cell,conf,number", [
    ("train", train_cell, TRAIN_CONF, "head_grad_rel_diff"),
    ("serve", serve_cell, SERVE_CONF, "served_logit_gap")],
    ids=["train", "serve"])
def test_control_is_not_correct(name, cell, conf, number):
    out = run_mode(name, cell(), with_policy(conf, CONTROL_POLICY))
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert out["correct"] is False, out["checks"]
    value, limit = checks[number]
    assert value > limit, out["checks"]


def test_serve_altered_token_is_not_correct():
    out = run_mode("serve", serve_cell(), SERVE_CONF,
                   ContinuousBatcher=altered_token)
    assert out["correct"] is False, out["checks"]


def test_sound_runs_read_below_the_faults():
    """The unbroken program at the same tiny size: its numbers are what
    the faults above are read against (each printed beside its limit)."""
    t = run_mode("train", train_cell(), TRAIN_CONF)
    s = run_mode("serve", serve_cell(), SERVE_CONF)
    for name, value, limit in t["checks"] + s["checks"]:
        print(name, value, limit)
    assert t["failed"] == 0 and s["failed"] == 0
