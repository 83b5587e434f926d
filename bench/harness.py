"""The training harness: one cell, one seed, one process.

It drives the program's own jitted train step
(``repro.launch.train_step.build_train_step``), built as
``launch/train.py`` builds it, around the program's ``build_model``,
``init_opt_state`` / ``opt_state_shardings``, ``param_shardings`` and the
host-side ``check_divergence`` read.  Weights and tokens come from the
benchmark (``weights.py``, ``data.py``), made from the seed; the weight
layout and the reference's loss from the configuration's architecture
module (``bench/reference/<name>.py``, ``manifest.resolve``'s ``"arch"``).

Set-up compiles the step once and drives that one object, with its state,
through the cell's first ``check_steps`` steps: the steps the correctness
check compares with the reference.  The measured window then continues
the same training run.  Per step the loop does what the trainer's loop
does: make the batch on the host, place it, call the step, wait for its
metrics and read the divergence breaker.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ModelConfig, OptimizerConfig, RunConfig,
                                ShapeConfig)
from repro.core.compression import Compressor
from repro.core.health import check_divergence
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh
from repro.launch.train_step import (build_train_step, init_opt_state,
                                     opt_state_shardings)
from repro.models import build_model
from repro.sharding import param_shardings

from bench import data, weights
from bench.reference import csgd

METRIC_KEYS = ("loss", "alpha", "n_evals", "wire_bytes", "steps_skipped",
               "consecutive_skips", "last_good_step")


def optimizer_of(traffic: dict) -> csgd.Optimizer:
    o = traffic["optimizer"]
    return csgd.Optimizer(gamma=o["gamma"], block=o["block"],
                          value_bits=o["value_bits"])


def control_compute(m: dict) -> str:
    """The precision just below the configuration's: the control's.  Its
    weights stay in the configuration's types; only the products run
    lower."""
    return "bfloat16" if m["compute_dtype"] == "float32" \
        else "float8_e4m3fn"


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _grad_norms(p0, p1, memory, workers):
    """Per leaf || sum_w m_w + W (p0 - p1) ||: after step 1 from zero
    memory this is the sum over workers of eta_w * g_w, the gradient as
    the optimizer received it."""
    return leaf_norms(jax.tree.map(
        lambda a, b, m: jnp.sum(m.astype(jnp.float32), 0)
        + workers * (a.astype(jnp.float32) - b.astype(jnp.float32)),
        p0, p1, memory))


@jax.jit
def _change_norms(p0, p3):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p0, p3))


@contextlib.contextmanager
def span(name: str, on: bool):
    if on:
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


@dataclasses.dataclass
class Trainer:
    """The program's train step for one cell, built once.  ``arch`` is
    the configuration's architecture module."""

    config: dict
    traffic: dict
    arch: types.ModuleType
    devices: list | None = None

    def __post_init__(self):
        m, t = self.config["model"], self.traffic
        o = t["optimizer"]
        self.m = m
        self.cfg = ModelConfig(**m)
        self.run = RunConfig(
            model=self.cfg,
            shape=ShapeConfig("bench", t["seq_len"], t["global_batch"],
                              "train"),
            optimizer=OptimizerConfig(
                kind=o["kind"], transport=o["transport"],
                compressor=Compressor(gamma=o["gamma"], method=o["method"],
                                      block=o["block"],
                                      value_bits=o["value_bits"])))
        self.model = build_model(self.cfg)
        self.mesh = make_mesh(t["mesh"], ("data", "model"),
                              devices=self.devices)
        self.workers = self.mesh.shape["data"]
        self.rows = t["global_batch"]
        self.seq = t["seq_len"]
        self.tokens_per_step = self.rows * (self.seq - 1)
        self.abstract = weights.abstract(self.arch, m)
        prog = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        if jax.tree.structure(prog) != jax.tree.structure(self.abstract) \
                or jax.tree.leaves(prog) != jax.tree.leaves(self.abstract):
            raise ValueError("the program's parameter tree differs from "
                             "the benchmark's weight layout")
        self.p_sh = param_shardings(self.abstract, self.mesh)
        self.b_sh = NamedSharding(self.mesh, P("data"))
        self.step_fn = None
        self.ops: dict = {}

    # -- state and inputs ---------------------------------------------------
    def fresh_state(self, seed: int):
        with jax.set_mesh(self.mesh):
            params = weights.make(self.arch, self.m, seed, self.p_sh)
            mask = self.model.stacked_mask(params)
            state = init_opt_state(self.abstract, self.run, self.workers,
                                   abstract=True, stacked_mask=mask)
            sh = opt_state_shardings(state, self.abstract, self.mesh,
                                     self.run)
            state = jax.jit(
                lambda: init_opt_state(self.abstract, self.run,
                                       self.workers, stacked_mask=mask),
                out_shardings=sh)()
        return params, state

    def host_batch(self, seed: int, step: int) -> np.ndarray:
        return data.global_batch(seed, step, self.workers, self.rows,
                                 self.seq, self.m["vocab_size"])

    def put(self, tokens: np.ndarray) -> dict:
        return {"tokens": jax.device_put(tokens, self.b_sh)}

    # -- the program's step -------------------------------------------------
    def compile(self, params, state, batch) -> float:
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh), dispatch.recording() as seen:
            jitted = build_train_step(self.model, self.run, self.mesh)(
                params, batch)
            self.step_fn = jitted.lower(params, state, batch).compile()
        self.ops = {k: sorted(v) for k, v in seen.items()}
        return time.perf_counter() - t0

    def call(self, params, state, batch):
        with jax.set_mesh(self.mesh):
            return self.step_fn(params, state, batch)

    def one_step(self, seed: int, step: int, params, state, *,
                 traced: bool = False, feed=None):
        """One step as the trainer's loop takes it.  Returns the new
        state, the step's metrics (device arrays) and its host seconds."""
        with span("bench.step", traced):
            t0 = time.perf_counter()
            with span("bench.make_batch", traced):
                tokens = self.host_batch(seed, step)
                if feed is not None:
                    tokens = feed(tokens)
            with span("bench.put_batch", traced):
                batch = self.put(tokens)
            host = time.perf_counter() - t0
            with span("bench.dispatch", traced):
                params, state, metrics = self.call(params, state, batch)
            with span("bench.wait", traced):
                jax.block_until_ready(metrics)
            t1 = time.perf_counter()
            with span("bench.divergence_read", traced):
                check_divergence(
                    {"step": step,
                     "consecutive_skips": metrics["consecutive_skips"],
                     "last_good_step": metrics["last_good_step"]},
                    self.run.optimizer.max_consecutive_skips)
            host += time.perf_counter() - t1
        return params, state, metrics, host


def check_steps(tr: Trainer, seed: int, params, state, n: int, feed=None):
    """Drive the compiled step through the run's first ``n`` steps and
    keep what the correctness check compares."""
    p0 = jax.tree.map(jnp.copy, params)
    rec = {"loss": [], "alpha": [], "n_evals": [], "wire_bytes": []}
    for s in range(n):
        params, state, met, _ = tr.one_step(seed, s, params, state,
                                            feed=feed)
        for k in ("loss", "alpha", "n_evals", "wire_bytes"):
            rec[k].append(float(met[k]))
        if s == 0:
            rec["grad_norms"] = np.asarray(_grad_norms(
                p0, params, state.memory, tr.workers)).tolist()
    rec["change_norms"] = np.asarray(_change_norms(p0, params)).tolist()
    rec["skipped"] = float(met["steps_skipped"])
    del p0
    return params, state, rec


def reference_record(config: dict, traffic: dict, arch, seed: int, n: int,
                     compute: str = "float32", devices=None) -> dict:
    """The reference's first ``n`` steps from the same weights and tokens,
    with the loss of ``arch``, the configuration's architecture module.
    Workers run on the devices in turn (several chips: in parallel)."""
    m, t = config["model"], traffic
    opt = optimizer_of(t)
    devices = devices or jax.devices()
    W = t["mesh"][0]
    rows = t["global_batch"] // W
    ctx = (jax.default_matmul_precision("highest") if compute == "float32"
           else contextlib.nullcontext())

    def loss_fn(p, tok):
        return arch.loss(p, tok, m, compute)

    # a worker's memory is replaced by its new memory: donate it
    step_w = jax.jit(lambda p, mem, a, tok: csgd.worker(loss_fn, opt, p,
                                                        mem, a, tok),
                     donate_argnums=(1,))
    apply = jax.jit(csgd.apply)
    with ctx:
        params = weights.make(arch, m, seed)
        p0 = params
        names = csgd.path_names(params)
        shapes = weights.shapes(arch, m)
        dev = [devices[w % len(devices)] for w in range(W)]
        mem = [jax.device_put(jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params), d)
            for d in dev]
        alpha = [jnp.float32(opt.alpha0)] * W
        rec = {"loss": [], "alpha": [], "n_evals": [], "wire_bytes": []}
        for s in range(n):
            tokens = data.global_batch(seed, s, W, t["global_batch"],
                                       t["seq_len"], m["vocab_size"])
            outs = []
            for w in range(W):
                pw = jax.device_put(params, dev[w])
                tw = jax.device_put(tokens[w * rows:(w + 1) * rows], dev[w])
                outs.append(step_w(pw, mem[w], jax.device_put(alpha[w],
                                                              dev[w]), tw))
            sents = [jax.device_put(o[3], dev[0]) for o in outs]
            new = apply(params, sents)
            mem = [o[4] for o in outs]
            alpha = [o[1] for o in outs]
            rec["loss"].append(float(np.mean([float(o[0]) for o in outs])))
            rec["alpha"].append(float(np.mean([float(o[1]) for o in outs])))
            rec["n_evals"].append(float(np.mean([float(o[2])
                                                 for o in outs])))
            rec["wire_bytes"].append(float(opt.wire_bytes(shapes)))
            if s == 0:
                msum = jax.tree.map(
                    lambda *a: sum(jax.device_put(x, dev[0]) for x in a)[None],
                    *mem)
                rec["grad_norms"] = np.asarray(
                    _grad_norms(p0, new, msum, W)).tolist()
            params = new
        rec["change_norms"] = np.asarray(_change_norms(p0, params)).tolist()
    rec["leaves"] = names
    return rec


def peak_memory(devices, step_fn=None) -> int:
    """The fullest chip's peak: the allocator's peak of live buffers plus
    the compiled step's temporary space, which the TPU runtime reserves
    for the program apart from the allocator's count."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    temp = 0
    if step_fn is not None:
        temp = getattr(step_fn.memory_analysis(), "temp_size_in_bytes", 0)
    return int(max(peaks) + temp) if peaks else 0


def free(*trees) -> None:
    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array):
                x.delete()
    gc.collect()


def profile_dir():
    return tempfile.mkdtemp(prefix="bench-trace-")


def start_trace(logdir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop_trace(logdir: str):
    from bench import trace
    jax.profiler.stop_trace()
    try:
        return trace.load(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def env_cache_dir(root) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else one fixed directory inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
