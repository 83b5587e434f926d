"""Serving driver: batched prefill + autoregressive decode on a mesh.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --smoke \
        --mesh 4x2 --batch 8 --ctx 64 --gen 16

Production decode shapes (decode_32k / long_500k) are exercised via the
dry-run; this driver runs *real* batched generation on the (CPU-simulated)
mesh with the same sharded cache layout.
"""
from __future__ import annotations

import argparse
import time

import jax

from jax import set_mesh
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import parse_mesh
from repro.models import build_model
from repro.sharding import dp_axes_of


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--params-2d", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)
    dp = dp_axes_of(mesh)
    B, CTX, GEN = args.batch, args.ctx, args.gen
    shape = ShapeConfig("serve", CTX + GEN, B, "decode")

    with set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        from repro.sharding import param_pspecs
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           param_pspecs(params, two_d=args.params_2d))
        params = jax.device_put(params, psh)

        key = jax.random.PRNGKey(7)
        batch = {"tokens": jax.random.randint(key, (B, CTX), 0,
                                              cfg.vocab_size)}
        if cfg.family == "vlm":
            batch["image_embed"] = jax.random.normal(
                key, (B, cfg.n_patches, cfg.d_model))
        if cfg.family == "encdec":
            batch["src_embed"] = jax.random.normal(key, (B, 32, cfg.d_model))
        dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
        batch = jax.device_put(batch, jax.tree.map(
            lambda _: NamedSharding(mesh, P(dp_spec)), batch))

        t0 = time.time()
        logits, cache = jax.jit(
            lambda p, b: model.prefill(p, b, capacity=CTX + GEN))(params,
                                                                  batch)
        print(f"[{cfg.name}] prefill {B}x{CTX} on mesh {args.mesh}: "
              f"{time.time()-t0:.2f}s")

        decode = jax.jit(model.decode_step)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out = [tok]
        t0 = time.time()
        for i in range(GEN - 1):
            logits, cache = decode(params, tok, cache, jnp.int32(CTX + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(tok)
        dt = (time.time() - t0) / max(GEN - 1, 1)
        gen = jnp.concatenate(out, axis=1)
        print(f"decoded {GEN} tokens/request @ {dt*1e3:.1f} ms/step")
        for i in range(min(B, 4)):
            print(f"  req{i}: {list(map(int, gen[i]))[:16]}")


if __name__ == "__main__":
    main()
