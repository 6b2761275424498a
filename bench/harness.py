"""What every mode of the benchmark shares: files, the device, weights
from the seed, the model configuration, tracing and the result line.

Nothing here imports JAX at module level, so ``run.py`` can refuse a
checkout without the system under test before JAX starts.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(rel: str) -> dict:
    with open(BENCH / rel) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    """(workload, configuration) of a cell, the workload's traffic mix
    read from ``bench/mixes/<traffic>.json``."""
    wl = load_json(f"workloads/{name}.json")
    wl["traffic"] = load_json(f"mixes/{wl['traffic']}.json")
    return wl, load_json(f"configs/{wl['config']}.json")


def load_module(rel: str, name: str):
    """Import ``bench/<rel>`` as a fresh module (modes and metric readers
    are found by file name, so a later cell adds files, not edits)."""
    path = BENCH / rel
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(msg: str) -> None:
    """A finding line: printed before the result, read by people."""
    print(f"[bench] {msg}", flush=True)


def use_program() -> None:
    """Put the system under test (``src/``) on the path, or refuse."""
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: no system under test at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


# ------------------------------------------------------------- device --

def require_chips(n: int) -> dict:
    """The device record of the result; exits without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(f"bench: cell needs {n} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def enable_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``.jax_cache/`` in the checkout), holding every program, however
    quick to compile, so the second run of a cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def peaks(kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


# ------------------------------------------------------------ seeding --

def seed_key(seed: int):
    """A JAX key from all bits of ``seed`` (``jax.random.key`` keeps
    only the low 32)."""
    import jax
    if seed < 0:
        raise ValueError("seed must be >= 0")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def seed_u64(seed: int, salt: int) -> int:
    """A 64-bit mix of (seed, salt) for the host-side generators."""
    x = (seed * 0x9E3779B97F4A7C15 + salt * 0xBF58476D1CE4E5B9) % 2 ** 64
    x ^= x >> 31
    return x


# ------------------------------------------------------- configuration --

def model_config(conf: dict):
    """The program's ``ModelConfig`` for a ``bench/configs`` file: the
    published keys as run, plus the ``program`` group (family, policy)."""
    from repro.configs.base import ModelConfig
    prog = conf["program"]
    norm = conf["norm_type"]
    eps = conf["layer_norm_eps"] if norm == "layernorm" else conf["rms_norm_eps"]
    return ModelConfig(
        name=conf["name"], family=prog["family"],
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=bool(conf.get("use_qkv_bias", False)), norm=norm,
        norm_eps=eps, rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        policy_name=prog["policy"], quantize_head=prog["quantize_head"],
        attn_q_chunk=prog["attn_q_chunk"])


# ------------------------------------------------------------ weights --

def make_weights(shapes, seed: int):
    """Weights for the program's parameter tree ``shapes`` (from
    ``jax.eval_shape(model.init, ...)``), made on the device in one
    jitted call from the seed, in the dtype the tree declares.

    Matrices ``[..., K, N]`` ~ N(0, 1/K); the embedding ~ N(0, 0.02^2);
    norm scales ~ 1 + N(0, 0.1^2); every bias ~ N(0, 0.02^2), so each
    leaf is exercised (zero biases would leave their gradients' paths
    untested).  The harness makes these, not the program, so the plain
    reference can take the same arrays."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, s.shape, jnp.float32)
            if name == "embed":
                v = z * 0.02
            elif name == "scale":
                v = 1.0 + 0.1 * z
            elif name.startswith("b"):
                v = 0.02 * z
            else:
                v = z * s.shape[-2] ** -0.5
            out.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill)(seed_key(seed))


def _leaf_names(tree) -> list:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


def norm_arrays(tree, minus=None):
    """Traceable per-leaf float32 norms of ``tree`` (of ``tree - minus``
    where given), one per layer for each stacked ``layers/`` leaf."""
    import jax
    import jax.numpy as jnp
    names = _leaf_names(tree)
    leaves = jax.tree.leaves(tree)
    others = jax.tree.leaves(minus) if minus is not None else [None] * len(leaves)
    out = []
    for n, x, y in zip(names, leaves, others):
        x = x.astype(jnp.float32)
        if y is not None:
            x = x - y.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if n.startswith("layers/") else None
        out.append(jnp.atleast_1d(jnp.sqrt(jnp.sum(x * x, axis=axes))))
    return out


def norms_to_dict(tree, arrays) -> dict:
    """``{leaf name: norm}`` for ``norm_arrays``' output; the layer index
    of a stacked leaf follows ``#``."""
    import jax
    import numpy as np
    res = {}
    for n, v in zip(_leaf_names(tree), jax.device_get(arrays)):
        v = np.asarray(v, np.float64)
        if n.startswith("layers/"):
            res.update({f"{n}#{i}": float(x) for i, x in enumerate(v)})
        else:
            res[n] = float(v[0])
    return res


def leaf_norms(tree, minus=None) -> dict:
    """Per-leaf norms of ``tree`` (or of ``tree - minus``) on the device,
    each stacked layer leaf split into its layers (the worst-leaf
    comparisons run per layer)."""
    import jax
    return norms_to_dict(tree, jax.jit(norm_arrays)(tree, minus))


class CompileCounter:
    """Counts the XLA compiles JAX reports while ``armed`` (the window
    should see none)."""

    def __init__(self):
        import jax
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "backend_compile" in event:
            self.n += 1


class GcPauses:
    """The garbage collector's pauses (start, end, generation) on the
    host clock while ``armed``: a step or tick that stalls for a pause is
    told apart from one the device or the runtime held up."""

    def __init__(self):
        import gc
        self.armed, self.pauses, self._t = False, [], None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.armed:
            return
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.pauses.append((self._t, now, info["generation"]))
            self._t = None

    def within(self, a: float, b: float) -> float:
        """Seconds of pause inside ``[a, b)``."""
        return sum(max(0.0, min(b, e) - max(a, s)) for s, e, _ in self.pauses)


# ------------------------------------------------------------ tracing --

class Spans:
    """Host spans from the benchmark's own files: ``jax.profiler``
    ``TraceAnnotation``s (they land in the device trace, on the same
    clock) while a trace is on, nothing otherwise."""

    def __init__(self):
        self.on = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield


class TracedWindow:
    """Profiles the first ``trace_s`` seconds of a measured window into
    ``dir`` when ``on``, inside a ``bench.window`` span that marks the
    traced window for ``trace.reduce``; ``stop()`` is idempotent."""

    def __init__(self, on: bool, trace_s: float, directory: pathlib.Path,
                 spans: Spans):
        self.on, self.trace_s, self.dir, self.spans = on, trace_s, directory, spans
        self.t0 = self.t1 = None
        self.active = False

    def start(self):
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.spans.on = True
        self.active = True
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def due(self) -> bool:
        return self.active and time.perf_counter() - self.t0 >= self.trace_s

    def stop(self):
        if not self.active:
            return
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.spans.on = False
        self.active = False
        jax.profiler.stop_trace()


def xplane_file(directory: pathlib.Path) -> str:
    files = sorted(directory.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return str(files[-1])


# ------------------------------------------------------------- result --

def per_layer_metrics(names, ctx) -> dict:
    """Each per-layer metric from its own reader ``bench/metrics/<name>.py``
    (``compute(ctx)`` -> value or None); a reader that finds nothing to
    read leaves its metric out."""
    out = {}
    for name in names:
        mod = load_module(f"metrics/{name}.py", f"bench_metric_{name}")
        val = mod.compute(ctx)
        if val is None:
            find(f"metric {name}: nothing to read")
            continue
        out[name] = {"value": float(val), "unit": mod.UNIT}
    return out


def emit(result: dict, checks: list) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, and the result object (checks last) as the last line of
    stdout."""
    for name, value, limit in checks:
        print(f"[check] {name} = {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(result), flush=True)
