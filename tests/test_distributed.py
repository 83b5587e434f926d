"""Distributed integration tests (subprocess with 8 forced host devices so
the main pytest process keeps its single-device view).

Covers: DCSGD-ASSS == single-node CSGD-ASSS when every worker sees the same
batch; the compressed train step's only dp collective is the sparse
all-gather; decode step compiles with seq-sharded caches; the dry-run module
works end-to-end on a small mesh.
"""
import json
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_dcsgd_equals_csgd_same_data():
    """With identical per-worker batches, the distributed all-gather mean of
    identical sparse updates == the single-node compressed update."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.configs.base import RunConfig, OptimizerConfig, ShapeConfig
        from repro.core import Compressor, ArmijoConfig, CSGDConfig, csgd_asss
        from repro.models import build_model
        from repro.launch.mesh import make_mesh
        from repro.launch.train_step import build_train_step, init_opt_state, opt_state_shardings
        from jax import set_mesh
        from repro.sharding import param_shardings
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("qwen1.5-4b")
        m = build_model(cfg)
        comp = Compressor(gamma=0.1, min_compress_size=64)
        arm = ArmijoConfig()
        run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
                        optimizer=OptimizerConfig(kind="csgd_asss",
                                                  armijo=arm, compressor=comp))
        with set_mesh(mesh):
            params = m.init(jax.random.PRNGKey(0))
            one = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                                0, cfg.vocab_size)}
            batch = {"tokens": jnp.tile(one["tokens"], (4, 1))}  # same data 4x
            params = jax.device_put(params, param_shardings(params, mesh))
            st = init_opt_state(params, run, 4)
            st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
            batch = jax.device_put(batch, jax.tree.map(
                lambda _: NamedSharding(mesh, P("data")), batch))
            step = build_train_step(m, run, mesh)(params, batch)
            p_dist, st_dist, metrics = step(params, st, batch)

        # single-node reference on the same (single-worker) batch
        opt = csgd_asss(CSGDConfig(armijo=arm, compressor=comp))
        p0 = m.init(jax.random.PRNGKey(0))
        s0 = opt.init(p0)
        p_ref, s_ref, aux = opt.step(lambda p: m.loss(p, one)[0], p0, s0)

        da = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p_dist, p_ref)
        worst = max(jax.tree.leaves(da))
        print("MAXDIFF", worst)
        print("LOSSDIFF", abs(float(metrics["loss"]) - float(aux.loss)))
        assert worst < 5e-3, worst
        assert abs(float(metrics["loss"]) - float(aux.loss)) < 1e-4
    """)
    assert "MAXDIFF" in out


def test_compressed_step_trains_and_saves_wire_bytes():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.configs.base import RunConfig, OptimizerConfig, ShapeConfig
        from repro.core import Compressor, ArmijoConfig
        from repro.models import build_model
        from repro.launch.mesh import make_mesh
        from repro.launch.train_step import build_train_step, init_opt_state, opt_state_shardings
        from jax import set_mesh
        from repro.sharding import param_shardings
        from repro.data.synthetic import TokenPipeline
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("yi-34b")
        m = build_model(cfg)
        def mkrun(kind, gamma=0.05):
            return RunConfig(model=cfg, shape=ShapeConfig("t", 64, 8, "train"),
                optimizer=OptimizerConfig(kind=kind, armijo=ArmijoConfig(),
                    compressor=Compressor(gamma=gamma, min_compress_size=64),
                    eta=0.05))
        pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
        with set_mesh(mesh):
            results = {}
            for kind in ("csgd_asss", "dense"):
                run = mkrun(kind)
                params = m.init(jax.random.PRNGKey(0))
                params = jax.device_put(params, param_shardings(params, mesh))
                st = init_opt_state(params, run, 4)
                st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
                step = None
                for i in range(12):
                    b = jax.device_put(pipe.batch(i), jax.tree.map(
                        lambda _: NamedSharding(mesh, P("data")), pipe.batch(i)))
                    if step is None:
                        step = build_train_step(m, run, mesh)(params, b)
                    params, st, metrics = step(params, st, b)
                results[kind] = {k: float(v) for k, v in metrics.items()}
            print("CSGD", results["csgd_asss"])
            print("DENSE", results["dense"])
            assert results["csgd_asss"]["loss"] < 7.0
            # compression reduces wire bytes by >5x at gamma=0.05
            assert results["csgd_asss"]["wire_bytes"] * 5 < results["dense"]["wire_bytes"]
    """)
    assert "CSGD" in out


def test_decode_step_seq_sharded_cache_compiles():
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.configs.base import RunConfig, OptimizerConfig, ShapeConfig
        from repro.models import build_model
        from repro.launch.mesh import make_mesh
        from repro.launch.train_step import build_decode_step
        from jax import set_mesh
        import re

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("yi-34b")
        m = build_model(cfg)
        shape = ShapeConfig("d", 256, 8, "decode")
        run = RunConfig(model=cfg, shape=shape)
        with set_mesh(mesh):
            params_like = jax.eval_shape(m.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
            cache_like = jax.eval_shape(lambda: m.init_cache(8, 256))
            tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            step = build_decode_step(m, run, mesh, shape)(params_like, tok, cache_like)
            co = step.lower(params_like, tok, cache_like, jnp.int32(255)).compile()
            txt = co.as_text()
            assert "all-reduce" in txt  # flash-decode combine over seq shards
            print("DECODE_OK", co.cost_analysis().get("flops"))
    """)


def test_dryrun_smoke_combo():
    """The dry-run machinery itself (uses its own 512-device env)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "rwkv6-1.6b",
         "--shape", "decode_32k", "--out", "/tmp/_test_dryrun.json"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    rec = json.load(open("/tmp/_test_dryrun.json"))[0]
    assert rec["status"] == "ok", rec
    assert rec["flops_per_chip"] > 0
    assert rec["collectives"]["total_wire_bytes"] > 0


def test_moe_expert_parallel_exact():
    """Expert-parallel shard_map MoE == single-device baseline (no_drop)."""
    run_sub("""
        import dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from jax import set_mesh
        from repro.launch.mesh import make_mesh
        from repro.models import moe as moe_mod

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_smoke_config("granite-moe-1b-a400m"),
                                  n_experts=8, experts_per_token=2,
                                  capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        p = moe_mod.init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        y_base, _ = moe_mod.moe_block(p, x, cfg, no_drop=True)
        with set_mesh(mesh):
            cfg_ep = dataclasses.replace(cfg, moe_expert_parallel=True)
            psh = {"router": {"w": NamedSharding(mesh, P())},
                   "wg": NamedSharding(mesh, P("model")),
                   "wi": NamedSharding(mesh, P("model")),
                   "wo": NamedSharding(mesh, P("model"))}
            pd = jax.device_put(p, psh)
            xd = jax.device_put(x, NamedSharding(mesh, P("data")))
            y_ep, _ = jax.jit(lambda p, x: moe_mod.moe_block(
                p, x, cfg_ep, no_drop=True))(pd, xd)
        err = float(jnp.max(jnp.abs(y_base - y_ep)))
        assert err < 1e-4, err
        print("EP_EXACT", err)
    """)


def test_kernel_runs_manual_over_every_axis_in_train_region():
    """dispatch's wrapper for compiled kernels (a shard_map manual over
    every axis, replicated specs) inside the train step's region — manual
    over 'data', auto over 'model' — keeps each dp worker's own operands
    and static arguments, forward and backward."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels import dispatch
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))
        op = lambda x, none, w, k: (x * w + k, none)
        x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
        w = jnp.linspace(0.5, 1.5, 128, dtype=jnp.float32)

        def worker(x, w):
            y, none = dispatch._manual_over_auto_axes(op, (x, None, w, 3), {})
            assert none is None
            g = jax.grad(lambda x: dispatch._manual_over_auto_axes(
                op, (x, None, w, 3), {})[0].sum())(x)
            return y, g

        with jax.set_mesh(mesh):
            y, g = jax.jit(jax.shard_map(
                worker, in_specs=(P("data"), P()), out_specs=P("data"),
                axis_names={"data"}, check_vma=False))(x, w)
        # rtol: XLA may fuse or reorder the arithmetic (one rounding)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x * w + 3),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g),
                                   np.broadcast_to(np.asarray(w), x.shape),
                                   rtol=1e-6)
        print("WRAP_OK")
    """)
    assert "WRAP_OK" in out
