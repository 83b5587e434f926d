"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
swept over shapes and dtypes (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


@pytest.mark.parametrize("n", [1024, 3000, 8192, 65536])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ef_threshold_update_sweep(key, n, dtype):
    m = jax.random.normal(key, (n,), dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype)
    s1, m1 = ops.ef_threshold_update(m, g, 0.1, 0.3, impl="ref")
    s2, m2 = ops.ef_threshold_update(m, g, 0.1, 0.3, impl="pallas")
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(s1, np.float32),
                               np.asarray(s2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(m1, np.float32),
                               np.asarray(m2, np.float32), atol=tol)
    # fused-update identity: sent + m' == m + eta*g
    acc = np.asarray(m, np.float32) + 0.1 * np.asarray(g, np.float32)
    np.testing.assert_allclose(np.asarray(s2, np.float32)
                               + np.asarray(m2, np.float32), acc, atol=2e-2)


def test_dispatch_registry_and_resolution():
    """Every op is registered with a ref oracle; resolution follows the
    per-op policy (EF ops take the kernel path even off-TPU) and the
    process-wide override wins over policy."""
    from repro.kernels import dispatch
    reg = dispatch.registered()
    for op in ("ef_update", "block_stats", "ef_stats", "ef_stats_telemetry",
               "threshold_split", "attention", "rmsnorm", "wkv"):
        assert "ref" in reg[op], op
        assert "pallas-interpret" in reg[op], op
        assert "pallas-tpu" in reg[op], op
    on_tpu = jax.default_backend() == "tpu"
    want_ef = "pallas-tpu" if on_tpu else "pallas-interpret"
    assert dispatch.resolve("ef_update") == want_ef
    assert dispatch.resolve("attention") == ("pallas-tpu" if on_tpu
                                             else "ref")
    assert dispatch.resolve("attention", "pallas") == want_ef
    with dispatch.using("ref"):
        assert dispatch.resolve("ef_update") == "ref"
    assert dispatch.resolve("ef_update") == want_ef


@pytest.mark.parametrize("shape", [(5000,), (3, 4096), (2, 2500)])
def test_fused_ef_identity_bitlevel(key, shape):
    """The fused kernel's EF identity is BIT-exact: sent + m' == m + eta*g
    (each position is nonzero in exactly one of sent/m'), and the kernel
    path equals the ref.py math bit-for-bit in f32.

    eta is a power of two so eta*g is exact and FMA-vs-mul+add rounding
    cannot differ — the comparison against numpy is strict equality.
    """
    eta = 0.5
    m = jax.random.normal(key, shape, jnp.float32)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    sent, mnew, tau, *_ = ops.fused_ef_compress(m, g, eta, gamma=0.03,
                                                impl="pallas")
    acc = np.asarray(m, np.float32) + np.float32(eta) * np.asarray(
        g, np.float32)
    np.testing.assert_array_equal(np.asarray(sent) + np.asarray(mnew), acc)
    # disjoint support: fused split never duplicates or drops a position
    assert not np.any(np.logical_and(np.asarray(sent) != 0,
                                     np.asarray(mnew) != 0))
    sent_r, mnew_r, tau_r, *_ = ops.fused_ef_compress(m, g, eta, gamma=0.03,
                                                      impl="ref")
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(sent_r))
    np.testing.assert_array_equal(np.asarray(mnew), np.asarray(mnew_r))
    np.testing.assert_array_equal(np.asarray(tau), np.asarray(tau_r))


@pytest.mark.parametrize("shape", [(5000,), (3, 4096), (2, 2500)])
def test_fused_ef_telemetry_parity(key, shape):
    """The telemetry-fused pass 1 (DESIGN.md §10): tau equals the plain
    ef_stats pass bit-for-bit (same selection math), the moments equal the
    ref oracle across ref/pallas, and the moment totals reduce to the
    dense sums they claim to be."""
    eta = 0.5                       # power of two: acc exact in numpy too
    m = jax.random.normal(key, shape, jnp.float32)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    s_t, m_t, tau_t, *_, mom_p = ops.fused_ef_compress(
        m, g, eta, gamma=0.03, telemetry=True, impl="pallas")
    s_p, m_p, tau_p, *_ = ops.fused_ef_compress(m, g, eta, gamma=0.03,
                                                impl="pallas")
    np.testing.assert_array_equal(np.asarray(tau_t), np.asarray(tau_p))
    np.testing.assert_array_equal(np.asarray(s_t), np.asarray(s_p))
    np.testing.assert_array_equal(np.asarray(m_t), np.asarray(m_p))
    *_, mom_r = ops.fused_ef_compress(m, g, eta, gamma=0.03,
                                      telemetry=True, impl="ref")
    np.testing.assert_allclose(np.asarray(mom_p), np.asarray(mom_r),
                               rtol=1e-6)
    acc = np.asarray(m, np.float64) + eta * np.asarray(g, np.float64)
    np.testing.assert_allclose(float(jnp.sum(mom_p[:, 0])),
                               float(np.sum(np.asarray(g, np.float64)**2)),
                               rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(mom_p[:, 1])),
                               float(np.sum(acc**2)), rtol=1e-5)


def test_fused_ef_compress_block_budget(key):
    """Each full 1024-wide block keeps exactly k_b = round(gamma*block)
    entries (random floats: no ties)."""
    m = jax.random.normal(key, (4096,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (4096,))
    gamma = 0.05
    sent, *_ = ops.fused_ef_compress(m, g, 0.2, gamma=gamma)
    k_b = round(gamma * 1024)
    per_block = np.count_nonzero(np.asarray(sent).reshape(4, 1024), axis=1)
    np.testing.assert_array_equal(per_block, np.full(4, k_b))


def _pair_case(key, case):
    """(m, g) for the pass-1 wire-pair parity cases; eta = 0.5 keeps
    ``m + eta*g`` exact, so numpy can state the expected split."""
    # padded/stacked: the last block holds fewer than k_b = 10 entries, so
    # its padding lanes go on the wire, clamped to d - 1
    shape = {"stacked": (3, 1030), "padded": (2053,)}.get(case, (2, 2048))
    m = jax.random.normal(key, shape, jnp.float32)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    if case == "ties":              # a few levels: many lanes share tau
        m, g = jnp.round(m), jnp.round(g)
    elif case == "zero_blocks":     # an all-zero block, signed zeros in
        # it, and a block with fewer nonzeros than k_b (tau = 0)
        zero = jnp.zeros((1024,), jnp.float32)
        neg = zero.at[::3].set(-0.0)
        sparse = zero.at[jnp.array([5, 700, 1000])].set(
            jnp.array([1.5, -2.0, 0.25]))
        m = jnp.stack([neg, sparse])
        g = jnp.stack([neg, zero])
    return m, g


PAIR_CASES = ["normal", "ties", "zero_blocks", "padded", "stacked"]


@pytest.mark.parametrize("case", PAIR_CASES)
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("impl", ["ref", "pallas-interpret"])
def test_pass1_wire_pairs_match_block_extract(key, impl, batched, case):
    """Pass 1 hands out the wire pairs: bit-identical (order, signed
    zeros, clamped padding indices) to the block top-k_b of |sent| that
    ``block_extract_sparse`` takes, while tau, sent, m' and the moments
    are the plain two-pass split's."""
    from repro.core.compression import Compressor, block_extract_sparse

    eta, gamma = 0.5, 0.01
    m, g = _pair_case(key, case)
    if batched:                     # the leaf shares its launch
        other = jax.random.normal(jax.random.fold_in(key, 2), (2, 1100))
        out = ops.fused_ef_compress_batched(
            [other, m], [other, g], eta, gamma, telemetry=True,
            impl=impl)[1]
    else:
        out = ops.fused_ef_compress(m, g, eta, gamma, telemetry=True,
                                    impl=impl)
    comp = Compressor(gamma=gamma, method="block_topk")
    m2 = np.asarray(m).reshape(-1, m.shape[-1])
    L, d = m2.shape
    vals, idx = block_extract_sparse(out.sent.reshape(L, d), comp)
    np.testing.assert_array_equal(np.asarray(out.vals).view(np.int32),
                                  np.asarray(vals).view(np.int32))
    np.testing.assert_array_equal(np.asarray(out.idx), np.asarray(idx))
    assert out.vals.dtype == jnp.float32 and out.idx.dtype == jnp.int32

    # the split itself, stated in numpy
    acc = m2 + np.float32(eta) * np.asarray(g).reshape(L, d)
    nb = -(-d // 1024)
    blocks = np.pad(acc, ((0, 0), (0, nb * 1024 - d))).reshape(L * nb, 1024)
    k_b = comp.block_k()
    tau = -np.sort(-np.abs(blocks), axis=1)[:, k_b - 1:k_b]
    keep = np.abs(blocks) >= tau
    sent = np.where(keep, blocks, np.float32(0.0))
    unblock = lambda x: x.reshape(L, -1)[:, :d].reshape(m.shape)
    np.testing.assert_array_equal(np.asarray(out.tau), tau)
    np.testing.assert_array_equal(np.asarray(out.sent).view(np.int32),
                                  unblock(sent).view(np.int32))
    np.testing.assert_array_equal(np.asarray(out.resid).view(np.int32),
                                  unblock(blocks - sent).view(np.int32))
    g_blocks = np.pad(np.asarray(g, np.float64).reshape(L, d),
                      ((0, 0), (0, nb * 1024 - d))).reshape(L * nb, 1024)
    np.testing.assert_allclose(
        np.asarray(out.moments),
        np.stack([np.sum(g_blocks ** 2, axis=1),
                  np.sum(blocks.astype(np.float64) ** 2, axis=1)], axis=1),
        rtol=1e-6)
    # without telemetry pass 1 hands out the same pairs
    plain = ops.fused_ef_compress_batched([m], [g], eta, gamma,
                                          impl=impl)[0]
    np.testing.assert_array_equal(np.asarray(plain.vals).view(np.int32),
                                  np.asarray(out.vals).view(np.int32))
    np.testing.assert_array_equal(np.asarray(plain.idx),
                                  np.asarray(out.idx))


@pytest.mark.parametrize("impl", ["ref", "pallas-interpret"])
def test_pass1_wire_pairs_non_finite_stay_in_range(key, impl):
    """Non-finite accumulators need not rank like block_extract_sparse,
    but every wire index still falls inside the leaf."""
    m = jax.random.normal(key, (2, 2500), jnp.float32)
    m = m.at[0, :40].set(jnp.nan).at[1, 1030].set(jnp.inf) \
        .at[1, 2100:].set(jnp.nan)
    g = jnp.ones_like(m)
    out = ops.fused_ef_compress(m, g, 0.5, 0.01, telemetry=True, impl=impl)
    idx = np.asarray(out.idx)
    assert idx.shape == (2, 30)
    assert idx.min() >= 0 and idx.max() < 2500


def test_threshold_split_blocks_matches_ref(key):
    x = jax.random.normal(key, (3, 3072))
    tau = ops.block_topk_threshold(x, 16, 1024).reshape(-1, 1)
    s1, r1 = ops.threshold_split_blocks(x, tau, 1024, impl="ref")
    s2, r2 = ops.threshold_split_blocks(x, tau, 1024, impl="pallas")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_allclose(np.asarray(s2 + r2), np.asarray(x),
                               atol=0.0)


def test_kth_largest_tie_semantics():
    """Tied magnitudes count like lax.top_k duplicates: for [5, -5, 3, 0...]
    the 2nd largest |.| is 5 (not 3) in BOTH the ref and the kernel path."""
    x = jnp.zeros((512,)).at[0].set(5.0).at[1].set(-5.0).at[2].set(3.0)
    t_ref = ops.block_topk_threshold(x, 2, 512, impl="ref")
    t_pal = ops.block_topk_threshold(x, 2, 512, impl="pallas")
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_pal))
    assert float(t_pal[0]) == 5.0


@pytest.mark.parametrize("k_b", [1, 8, 32])
def test_block_stats_sweep(key, k_b):
    x = jax.random.normal(key, (4096,))
    t1 = ops.block_topk_threshold(x, k_b, 512, impl="ref")
    t2 = ops.block_topk_threshold(x, k_b, 512, impl="pallas")
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), rtol=1e-6)


@pytest.mark.parametrize("shape", [(1, 2, 128, 32), (2, 4, 256, 64),
                                   (1, 8, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(key, shape, causal, window):
    B, H, S, D = shape
    q = jax.random.normal(key, shape, jnp.float32) * 0.1
    k = jax.random.normal(jax.random.fold_in(key, 1), shape) * 0.1
    v = jax.random.normal(jax.random.fold_in(key, 2), shape)
    o1 = ops.attention(q, k, v, causal=causal, window=window, impl="ref")
    o2 = ops.attention(q, k, v, causal=causal, window=window, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)


def test_flash_attention_rectangular(key):
    B, H, D = 2, 2, 64
    k = jax.random.normal(key, (B, H, 256, D)) * 0.1
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, H, 256, D))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, H, 128, D)) * 0.1
    o1 = ops.attention(q, k, v, causal=True, impl="ref")
    o2 = ops.attention(q, k, v, causal=True, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)


def test_flash_attention_bf16(key):
    shape = (1, 2, 256, 64)
    q = (jax.random.normal(key, shape) * 0.1).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.fold_in(key, 1), shape) * 0.1
         ).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape
                          ).astype(jnp.bfloat16)
    o1 = ops.attention(q, k, v, impl="ref")
    o2 = ops.attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


@pytest.mark.parametrize("shape", [(8, 128), (2, 100, 256), (3, 7, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(key, shape, dtype):
    x = jax.random.normal(key, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],),
                          jnp.float32)
    o1 = ops.rms_norm(x, w, impl="ref")
    o2 = ops.rms_norm(x, w, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-5)


def test_softmax_invariance_flash(key):
    """Flash accumulation must be shift-invariant: adding a constant to all
    logits (via scaled q) changes nothing."""
    shape = (1, 1, 256, 64)
    q = jax.random.normal(key, shape) * 0.1
    k = jax.random.normal(jax.random.fold_in(key, 1), shape) * 0.1
    v = jax.random.normal(jax.random.fold_in(key, 2), shape)
    o1 = ops.attention(q, k, v, impl="pallas")
    o2 = ops.attention(q, k + 100.0 * 0, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


@pytest.mark.parametrize("S", [8, 33, 64])
@pytest.mark.parametrize("K", [8, 64])
def test_wkv_kernel_sweep(key, S, K):
    """RWKV-6 WKV Pallas kernel vs sequential oracle."""
    B, H, V = 2, 2, K
    ks = jax.random.split(key, 6)
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.3
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, V))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, K, V)) * 0.1
    y1, sT1 = ops.wkv(r, k, v, w, u, s0, impl="ref")
    y2, sT2 = ops.wkv(r, k, v, w, u, s0, impl="pallas")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2), atol=2e-5)


def test_wkv_kernel_matches_time_mix_scan(key):
    """The kernel path of rwkv.time_mix == the scan path (same block)."""
    from repro.configs import get_smoke_config
    from repro.kernels import dispatch
    from repro.models import rwkv as rwkv_mod
    cfg = get_smoke_config("rwkv6-1.6b")
    p = rwkv_mod.init_rwkv6(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    st = rwkv_mod.init_rwkv_state(cfg, 2)
    with dispatch.using("ref"):
        y_scan, st_scan = rwkv_mod.time_mix(p, x, cfg, st)
    st2 = rwkv_mod.init_rwkv_state(cfg, 2)
    with dispatch.using("pallas"):
        y_ker, st_ker = rwkv_mod.time_mix(p, x, cfg, st2)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_ker),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_scan.wkv),
                               np.asarray(st_ker.wkv), atol=1e-4)
