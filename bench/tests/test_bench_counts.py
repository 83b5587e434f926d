"""The benchmark's counters from shapes, against hand counts for
paper-lm-100m and against the program's own counts: the model FLOPs of
its architecture module, and the architecture-independent EF counts."""
import pytest

from bench import flops, manifest, weights
from bench.reference import csgd
from bench.tests import tiny


@pytest.fixture(scope="module")
def lm100m():
    return tiny.lm100m()


@pytest.fixture(scope="module")
def lm():
    return manifest.architecture("transformer_lm")


def test_matmul_params_hand_count(lm, lm100m):
    # per layer: 4 * 768 * 768 attention + 3 * 768 * 2048 MLP; head 768 x V
    per_layer = 4 * 768 * 768 + 3 * 768 * 2048
    assert lm.matmul_params(lm100m) == 12 * per_layer + 768 * 16384 \
        == 97_517_568


def test_matmul_params_against_program_count(lm, lm100m):
    from repro.configs.base import ModelConfig
    n = ModelConfig(**lm100m).n_params()
    assert n == 110_119_680
    # the program counts the embedding table and the norm scales too
    assert lm.matmul_params(lm100m) == n - 16384 * 768 - 12 * 2 * 768 \
        - 768


def test_train_flops_per_token(lm, lm100m):
    S = 1023
    attn = 12 * 3 * 4 * 12 * 64 * (S * (S + 1) // 2) / S
    assert lm.train_flops_per_token(lm100m, S) == \
        pytest.approx(6 * 97_517_568 + attn)
    assert lm.train_flops_per_token(lm100m, S) == \
        pytest.approx(641.7e6, rel=1e-3)


def test_flash_forward_flops(lm, lm100m):
    assert lm.flash_forward_flops(lm100m, 8, 1023) == \
        4 * 8 * 12 * 64 * 1023 * 1024 / 2


def test_wire_bytes_and_ef_rows(lm, lm100m):
    opt = csgd.Optimizer(gamma=0.01, block=1024, value_bits=32)
    sh = weights.shapes(lm, lm100m)
    # embed and head: 12288 blocks x 10 entries, 16-bit indices + f32
    # values; per layer 576 (attention) and 1536 (MLP) blocks; norms whole
    head = (12288 * 10 // 2 + 12288 * 10) * 4
    attn = 12 * (576 * 10 // 2 + 576 * 10) * 4
    mlp = 12 * (1536 * 10 // 2 + 1536 * 10) * 4
    norms = 2 * 12 * 768 * 4 + 768 * 4
    assert opt.wire_bytes(sh) == 2 * head + 4 * attn + 3 * mlp + norms \
        == 6_528_000
    assert flops.ef_rows(sh, opt) == 107_520
    need = flops.ef_pass_bytes(107_520, 1024)
    assert need["stats"] == 2 * 107_520 * 1024 * 4 + 107_520 * 12
    assert need["update"] == 4 * 107_520 * 1024 * 4 + 107_520 * 4
