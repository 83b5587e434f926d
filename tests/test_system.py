"""End-to-end behaviour tests: the paper's headline claims at CPU scale,
exercised through the public API (build_model + csgd_asss + data pipeline)."""
import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.paper_models import MLP_CONFIG, init_net, net_loss
from repro.core import (ArmijoConfig, Compressor, CSGDConfig, NonAdaptiveCSGD,
                        csgd_asss)
from repro.data.synthetic import (TokenPipeline, class_batch,
                                  teacher_classification)
from repro.models import build_model


def test_lm_trains_with_csgd_asss(key):
    """A small transformer LM's loss decreases under compressed adaptive
    training (the paper's setting transplanted to our production models)."""
    cfg = get_smoke_config("qwen1.5-4b")
    model = build_model(cfg)
    params = model.init(key)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64,
                         global_batch=4)
    opt = csgd_asss(CSGDConfig(
        armijo=ArmijoConfig(),
        compressor=Compressor(gamma=0.1, min_compress_size=512)))
    st = opt.init(params)

    @jax.jit
    def step(p, s, batch):
        return opt.step(lambda pp: model.loss(pp, batch)[0], p, s)

    losses = []
    for i in range(30):
        params, st, aux = step(params, st, pipe.batch(i))
        losses.append(float(aux.loss))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_mlp_csgd_beats_nonadaptive_small_eta(key):
    """Paper Figs 1-3 shape: CSGD-ASSS (a=3sigma) vs non-adaptive eta=0.01
    on a realizable classification task at 10% compression."""
    x, y = teacher_classification(512, n_classes=10, image=False)
    cfg = MLP_CONFIG
    comp = Compressor(gamma=0.1, min_compress_size=512)

    def run(opt, steps=120):
        params = init_net(cfg, key)
        st = opt.init(params)

        @jax.jit
        def step(p, s, b):
            return opt.step(lambda pp: net_loss(cfg, pp, b), p, s)
        loss = None
        for i in range(steps):
            params, st, aux = step(params, st, class_batch(x, y, 64, i))
            loss = float(aux.loss)
        return loss

    l_ad = run(csgd_asss(CSGDConfig(armijo=ArmijoConfig(a_scale=0.3),
                                    compressor=comp)))
    l_na = run(NonAdaptiveCSGD(eta=0.01, compressor=comp))
    assert np.isfinite(l_ad)
    assert l_ad < l_na, (l_ad, l_na)


def test_train_cli_runs(tmp_path):
    """The launch driver end-to-end (single device, tiny model) incl.
    checkpoint write + metrics log."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out_json = str(tmp_path / "log.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen1.5-4b",
         "--smoke", "--steps", "8", "--seq-len", "64", "--global-batch", "2",
         "--mesh", "1x1", "--gamma", "0.1", "--log-every", "2",
         "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "4",
         "--out", out_json],
        capture_output=True, text=True, timeout=900, env=env, cwd=repo)
    assert r.returncode == 0, r.stderr[-2000:]
    log = json.load(open(out_json))
    assert log and np.isfinite(log[-1]["loss"])
    from repro.checkpoint import checkpoint as ckpt
    assert ckpt.latest_step(str(tmp_path / "ck")) == 8


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py on a CPU-only JAX exits non-zero and prints no result
    line, before it builds anything."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(repo, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=repo)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
