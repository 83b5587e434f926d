"""Beyond-paper extensions (paper §V future work + serving optimizations):
momentum, wire value quantization, int8 KV cache, local iterations."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (ArmijoConfig, Compressor, CSGDConfig, csgd_asss,
                        topk_select)
from repro.data.synthetic import interpolated_regression
from repro.models import build_model
from repro.launch.mesh import make_mesh


def _problem(d=128, n=256, seed=0):
    A, b, _ = interpolated_regression(n, d, seed=seed)

    def bl(w, idx):
        r = A[idx] @ w - b[idx]
        return jnp.mean(r ** 2)
    return bl


def _run(opt, bl, d=128, steps=300):
    w = jnp.zeros(d)
    st = opt.init(w)

    @jax.jit
    def step(w, s, idx):
        return opt.step(lambda ww: bl(ww, idx), w, s)

    rng = np.random.default_rng(0)
    for t in range(steps):
        w, st, aux = step(w, st, jnp.asarray(rng.integers(0, 256, 32)))
    return float(aux.loss)


def test_momentum_csgd_converges():
    """Heavy-ball + EF-compression (paper §V) converges when the scale is
    damped by ~(1-beta) — the velocity amplifies the effective step by
    1/(1-beta), so a=3*sigma*(1-beta)=0.03 is the momentum-adjusted analog
    of the paper's a=3*sigma (verified: a=0.3 un-damped diverges)."""
    bl = _problem()
    cfg = CSGDConfig(armijo=ArmijoConfig(sigma=0.1, a_scale=0.03),
                     compressor=Compressor(gamma=0.05, min_compress_size=1),
                     momentum=0.9)
    loss = _run(csgd_asss(cfg), bl)
    assert np.isfinite(loss) and loss < 0.5, loss


def test_momentum_beats_plain_at_matched_scale():
    """At the damped scale, momentum reaches a lower loss than plain CSGD
    with the same tiny scale (acceleration), on this problem."""
    bl = _problem()
    base = CSGDConfig(armijo=ArmijoConfig(sigma=0.1, a_scale=0.03),
                      compressor=Compressor(gamma=0.05, min_compress_size=1))
    l_plain = _run(csgd_asss(base), bl)
    l_mom = _run(csgd_asss(base.replace(momentum=0.9)), bl)
    assert l_mom < l_plain, (l_mom, l_plain)


def test_value_quantization_converges():
    """8-bit wire values with EF error recycling: converges."""
    bl = _problem()
    cfg = CSGDConfig(armijo=ArmijoConfig(sigma=0.1, a_scale=0.3),
                     compressor=Compressor(gamma=0.05, min_compress_size=1,
                                           value_bits=8))
    loss = _run(csgd_asss(cfg), bl, steps=400)
    assert loss < 0.5, loss


def test_value_quantization_identity(key):
    """sent + residual == input, exactly, even with quantized values."""
    comp = Compressor(gamma=0.05, value_bits=8, min_compress_size=1)
    x = jax.random.normal(key, (4096,))
    sent, resid = comp.compress_dense(x)
    np.testing.assert_allclose(np.asarray(sent + resid), np.asarray(x),
                               atol=1e-6)
    # quantization bounded by top-value scale / 127
    s = topk_select(x, comp.k_for(4096))
    bound = float(jnp.max(jnp.abs(s.values))) / 127.0
    nz = np.nonzero(np.asarray(sent))[0]
    err = np.abs(np.asarray(sent)[nz] - np.asarray(x)[nz])
    assert np.all(err <= bound * 0.51 + 1e-7)


def test_wire_bytes_reflect_value_bits():
    comp32 = Compressor(gamma=0.01)
    comp8 = Compressor(gamma=0.01, value_bits=8)
    assert comp8.value_bytes == 1 and comp32.value_bytes == 4


@pytest.mark.parametrize("arch", ["yi-34b", "zamba2-7b",
                                  "granite-moe-1b-a400m"])
def test_int8_kv_cache_decode_close(arch, key):
    """int8 KV cache: decode logits within quantization tolerance of bf16
    cache; cache arrays actually int8."""
    B, S = 2, 32
    cfg = get_smoke_config(arch)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    m, m8 = build_model(cfg), build_model(cfg8)
    params = m.init(key)
    toks = jax.random.randint(jax.random.fold_in(key, 7), (B, S + 1), 0,
                              cfg.vocab_size)
    _, c1 = m.prefill(params, {"tokens": toks[:, :S]}, capacity=S + 2)
    lg1, _ = m.decode_step(params, toks[:, S:S + 1], c1, jnp.int32(S))
    _, c8 = m8.prefill(params, {"tokens": toks[:, :S]}, capacity=S + 2)
    assert c8.kv.k.dtype == jnp.int8
    assert c8.kv.k_scale.shape[-1] == 1
    lg8, _ = m8.decode_step(params, toks[:, S:S + 1], c8, jnp.int32(S))
    err = float(jnp.max(jnp.abs(lg1[..., :cfg.vocab_size]
                                - lg8[..., :cfg.vocab_size])))
    assert err < 0.5, err
    # same argmax (greedy decode unchanged at smoke scale)
    assert jnp.array_equal(jnp.argmax(lg1, -1), jnp.argmax(lg8, -1))


def test_local_steps_microbatch_mismatch_rejected_at_build_time():
    """local_steps consumes exactly one microbatch per local Armijo step;
    a mismatched microbatch count must fail in build_train_step with a
    clear message, not as an opaque assert inside the traced worker."""
    import pytest

    from repro.configs import get_smoke_config
    from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
    from repro.core import ArmijoConfig, Compressor
    from repro.launch.train_step import build_train_step
    from repro.models import build_model

    cfg = get_smoke_config("qwen1.5-4b")
    m = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))

    def mkrun(local_steps, microbatches):
        return RunConfig(
            model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
            optimizer=OptimizerConfig(
                kind="csgd_asss", armijo=ArmijoConfig(),
                compressor=Compressor(gamma=0.1, min_compress_size=64),
                local_steps=local_steps),
            microbatches=microbatches)

    with pytest.raises(ValueError, match="microbatches == local_steps"):
        build_train_step(m, mkrun(2, 4), mesh)
    with pytest.raises(ValueError, match="local_steps=3"):
        build_train_step(m, mkrun(3, 1), mesh)
    build_train_step(m, mkrun(2, 2), mesh)       # matched: builds fine


def test_local_steps_distributed():
    """Qsparse-local-style DCSGD-ASSS trains on an 8-device mesh."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = textwrap.dedent("""
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
        cfg = get_smoke_config("qwen1.5-4b")
        m = build_model(cfg)
        run = RunConfig(model=cfg, shape=ShapeConfig("t", 64, 8, "train"),
            optimizer=OptimizerConfig(kind="csgd_asss", armijo=ArmijoConfig(),
                compressor=Compressor(gamma=0.1, min_compress_size=64),
                local_steps=2),
            microbatches=2)
        pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
        with set_mesh(mesh):
            params = m.init(jax.random.PRNGKey(0))
            params = jax.device_put(params, param_shardings(params, mesh))
            st = init_opt_state(params, run, 4)
            st = jax.device_put(st, opt_state_shardings(st, params, mesh, run))
            step = None
            first = None
            for i in range(15):
                b = jax.device_put(pipe.batch(i), jax.tree.map(
                    lambda _: NamedSharding(mesh, P("data")), pipe.batch(i)))
                if step is None:
                    step = build_train_step(m, run, mesh)(params, b)
                params, st, metrics = step(params, st, b)
                if first is None:
                    first = float(metrics["loss"])
        last = float(metrics["loss"])
        print("LOCAL_STEPS", first, "->", last)
        assert last < first - 0.2, (first, last)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LOCAL_STEPS" in r.stdout
