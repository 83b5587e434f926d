"""Hypothesis property tests on the system's invariants (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev extra "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.comm import wire as wire_fmt
from repro.core import (ArmijoConfig, Compressor, armijo_search,
                        topk_select, sparse_to_dense)
from repro.core.compression import block_extract_sparse
from repro.core.error_feedback import dequantize_ef, quantize_ef
from repro.kernels import ref
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

finite_arrays = st.integers(0, 2**31 - 1).flatmap(
    lambda seed: st.integers(64, 2048).map(
        lambda n: np.random.default_rng(seed).standard_normal(n)
        .astype(np.float32)))


@given(finite_arrays, st.floats(0.01, 0.9))
def test_topk_contraction_property(x, gamma):
    """Lemma 7 for arbitrary inputs and ratios."""
    d = x.size
    k = max(1, int(round(gamma * d)))
    s = topk_select(jnp.asarray(x), k)
    dense = np.asarray(sparse_to_dense(s))
    lhs = np.sum((x - dense) ** 2)
    rhs = (1 - k / d) * np.sum(x ** 2)
    assert lhs <= rhs + 1e-4 * max(1.0, rhs)


@given(finite_arrays)
def test_topk_idempotent(x):
    k = max(1, x.size // 10)
    s = topk_select(jnp.asarray(x), k)
    dense = sparse_to_dense(s)
    s2 = topk_select(dense, k)
    np.testing.assert_allclose(np.asarray(sparse_to_dense(s2)),
                               np.asarray(dense), atol=1e-7)


@given(finite_arrays, st.floats(0.0, 0.5), st.floats(0.0, 2.0))
def test_ef_update_telescopes(x, eta, tau):
    """sent + m' == m + eta*g exactly, for any threshold."""
    n = x.size // 2
    m, g = jnp.asarray(x[:n]), jnp.asarray(x[n:2 * n])
    sent, m_new = ref.ef_threshold_update(m, g, jnp.float32(eta),
                                          jnp.float32(tau))
    np.testing.assert_allclose(np.asarray(sent + m_new),
                               np.asarray(m + eta * g), atol=1e-5)


@given(finite_arrays)
def test_ef_quantization_bounded_error(x):
    """int8 EF storage: error bounded by scale/2 per block."""
    m = jnp.asarray(x)
    q = quantize_ef(m)
    back = dequantize_ef(q)
    err = np.abs(np.asarray(back) - x)
    per_block_bound = np.repeat(np.asarray(q.scale)[:, 0], 256)[:x.size]
    assert np.all(err <= per_block_bound * 0.75 + 1e-7)


@given(st.integers(0, 10**6), st.floats(0.05, 0.45),
       st.floats(0.5, 0.95))
def test_armijo_alpha_in_bounds(seed, sigma, rho):
    """Accepted alpha in [alpha_min, alpha_max]; condition holds on a
    random convex quadratic."""
    rng = np.random.default_rng(seed)
    scales = jnp.asarray(rng.uniform(0.1, 4.0, 16).astype(np.float32))

    def f(w):
        return jnp.sum(scales * w ** 2)

    w = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    g = jax.grad(f)(w)
    cfg = ArmijoConfig(sigma=sigma, rho=rho, max_backtracks=60)
    amax = jnp.float32(1.0)
    res = armijo_search(f, w, g, amax, cfg)
    assert 0 < float(res.alpha) <= 1.0 + 1e-6
    if bool(res.accepted):
        lhs = float(f(w - res.alpha * g))
        rhs = float(f(w) - sigma * res.alpha * jnp.sum(g ** 2))
        assert lhs <= rhs + 1e-4 * max(1.0, abs(rhs))


@given(st.integers(0, 10**6), st.integers(1, 4))
def test_attention_window_subset_of_causal(seed, wexp):
    """Sliding-window attention == causal attention when window >= seq."""
    rng = np.random.default_rng(seed)
    B, H, S, D = 1, 2, 16, 8
    q = jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32)) * .1
    k = jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32)) * .1
    v = jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32))
    full = ref.mha_reference(q, k, v, causal=True)
    win = ref.mha_reference(q, k, v, causal=True, window=S * wexp)
    np.testing.assert_allclose(np.asarray(win), np.asarray(full), atol=1e-5)


# ---------------------------------------------------------------------------
# packed wire format (DESIGN.md §8) — the property bodies are plain helpers
# so they can also be driven without hypothesis
# ---------------------------------------------------------------------------

def check_pack_roundtrip(seed: int, n: int, bits: int):
    """pack -> unpack is the identity on ``bits``-wide fields, for any
    length (zero-padding to whole words must never leak)."""
    hi = 1 << bits
    fields = jnp.asarray(np.random.default_rng(seed).integers(
        0, hi, (2, n), dtype=np.uint32))
    words = kops.pack_fields(fields, bits)
    back = kops.unpack_fields(words, n, bits)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(fields))
    # packed size is exactly the accounted ceil(n*bits/32) words
    assert words.shape == (2, -(-n * bits // 32))


def check_codec_roundtrip(seed: int, d: int, block: int, value_bits: int):
    """encode -> decode recovers (quantize_values(vals), idx) EXACTLY for
    odd row sizes d (padded last block) and every supported value width."""
    comp = Compressor(gamma=0.05, method="block_topk", block=block,
                      min_compress_size=1, value_bits=value_bits)
    x = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal((2, d)).astype(np.float32))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    payload = wire_fmt.encode_rows(vals, idx, spec)
    assert payload.nbytes == 2 * comp.wire_bytes(d)
    v2, i2 = wire_fmt.decode_rows(payload, spec)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(v2),
                                  np.asarray(comp.quantize_values(vals)))


def check_packed_ef_identity(seed: int, value_bits: int, log2_eta: int):
    """Bit-level EF identity END-TO-END through the packed path:
    decode(own payload) + m' == m + eta*g with strict float equality.

    Exactness argument: at unkept positions m' carries acc untouched; at
    kept positions the dequantized wire value v satisfies |acc - v| <=
    |v| / 2 (absmax int quantization with q >= 1, or bf16 rounding), so
    Sterbenz's lemma makes both acc - v and v + (acc - v) exact.  eta a
    power of two keeps acc = m + eta*g reproducible in numpy.
    """
    rng = np.random.default_rng(seed)
    d = 1280
    m = rng.standard_normal(d).astype(np.float32)
    g = rng.standard_normal(d).astype(np.float32)
    eta = np.float32(2.0 ** log2_eta)
    comp = Compressor(gamma=0.05, method="block_topk", block=256,
                      min_compress_size=1, value_bits=value_bits)
    acc = (jnp.asarray(m).reshape(1, -1).astype(jnp.float32)
           + eta * jnp.asarray(g).reshape(1, -1).astype(jnp.float32))
    vals, idx = block_extract_sparse(acc, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    v2, i2 = wire_fmt.decode_rows(
        wire_fmt.encode_rows(vals, idx, spec), spec)
    sent = jnp.zeros((d,), jnp.float32).at[i2.reshape(-1)].add(v2.reshape(-1))
    m_new = acc.reshape(-1) - sent
    np.testing.assert_array_equal(np.asarray(sent + m_new),
                                  np.asarray(acc.reshape(-1)))


@given(st.integers(0, 2**31 - 1), st.integers(1, 3000),
       st.sampled_from([4, 8, 16, 32]))
def test_pack_roundtrip_property(seed, n, bits):
    check_pack_roundtrip(seed, n, bits)


@given(st.integers(0, 2**31 - 1), st.integers(64, 2048),
       st.sampled_from([64, 256, 1024]), st.sampled_from([4, 8, 16, 32]))
def test_codec_roundtrip_property(seed, d, block, value_bits):
    check_codec_roundtrip(seed, d, block, value_bits)


@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8, 16, 32]),
       st.integers(-3, 1))
def test_packed_ef_identity_bitlevel_property(seed, value_bits, log2_eta):
    check_packed_ef_identity(seed, value_bits, log2_eta)


@given(st.integers(0, 10**6))
def test_blockwise_gamma_at_least_half(seed):
    """DESIGN §3: block-local selection achieves realized gamma >= gamma/2
    in energy terms for the kept-count (count-based check)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    comp = Compressor(gamma=0.1, method="block_topk", block=256,
                      min_compress_size=1)
    sent, resid = comp.compress_dense(x)
    kept = int(jnp.sum(sent != 0))
    assert kept >= int(0.5 * 0.1 * 4096)


def check_ragged_roundtrip(seed: int, d: int, block: int, value_bits: int):
    """Ragged codec (DESIGN.md §9): for random per-row valid counts in
    [1, k_max-per-period], decode returns exactly the masked quantized
    values, the count survives the header word, and the payload buffer
    stays the static budget size."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=block, min_compress_size=1,
                      value_bits=value_bits)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((3, d)).astype(np.float32))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    assert spec.ragged
    counts = jnp.asarray(rng.integers(1, spec.full_count + 1, 3), jnp.int32)
    payload = wire_fmt.encode_rows(vals, idx, spec, counts=counts)
    assert payload.nbytes == 3 * comp.wire_bytes(d)   # fixed budget buffer
    v2, i2, c2 = wire_fmt.decode_rows(payload, spec, return_counts=True)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))
    pos = np.arange(spec.k) % spec.count_period
    valid = pos[None, :] < np.asarray(counts)[:, None]
    expect = comp.quantize_values(jnp.where(jnp.asarray(valid), vals, 0.0))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(expect))
    assert np.all(np.asarray(v2)[~valid] == 0.0)
    # effective bytes are monotone in the count and bounded by the budget
    eff = np.asarray(spec.effective_row_bytes(counts))
    assert np.all(eff <= spec.row_bytes)
    assert np.all(np.asarray(spec.effective_row_bytes(spec.full_count))
                  == spec.row_bytes)


@given(st.integers(0, 2**31 - 1), st.integers(64, 2048),
       st.sampled_from([64, 256, 1024]), st.sampled_from([4, 8, 16, 32]))
def test_ragged_roundtrip_property(seed, d, block, value_bits):
    check_ragged_roundtrip(seed, d, block, value_bits)


# ---------------------------------------------------------------------------
# compression telemetry invariants (DESIGN.md §10)
# ---------------------------------------------------------------------------

import functools  # noqa: E402

_TEL_GAMMA = 0.05
_TEL_DS = (320, 1024, 1300)     # odd/padded block geometries


@functools.lru_cache(maxsize=None)
def _telemetry_fn(method: str, value_bits: int, adaptive: bool,
                  use_kernel: bool):
    """Jitted 1-worker worker_compress_aggregate -> CompressionTelemetry,
    cached per static config so hypothesis examples reuse compilations."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.dcsgd import worker_compress_aggregate

    comp = Compressor(gamma=_TEL_GAMMA,
                      max_gamma=_TEL_GAMMA if adaptive else 0.0,
                      method=method, block=256, min_compress_size=1,
                      value_bits=value_bits, use_kernel=use_kernel)
    mesh = make_mesh((1,), ("data",))
    f = shard_map(
        lambda g, m, eta, gt: worker_compress_aggregate(
            g, m, eta, comp, ("data",),
            gamma_t=gt if adaptive else None)[4],
        mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P(),
        axis_names={"data"}, check_vma=False)
    return jax.jit(f)


def _tel_inputs(seed: int, d: int):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    m = jnp.asarray(rng.standard_normal(d).astype(np.float32)) * 0.5
    return g, m


def check_telemetry_ranges(seed: int, d: int, method: str, value_bits: int,
                           gfrac: float):
    """For any shape, value width and per-round count: cosine in [-1, 1],
    backlog >= 0, decode_error >= 0, eff_gamma <= 1, everything finite."""
    g, m = _tel_inputs(seed, d)
    tel = _telemetry_fn(method, value_bits, True, True)(
        g, m, jnp.float32(0.25), jnp.float32(gfrac * _TEL_GAMMA))
    for leaf in jax.tree.leaves(tel):
        assert np.isfinite(float(leaf))
    assert -1.0 - 1e-5 <= float(tel.cosine) <= 1.0 + 1e-5
    assert float(tel.ef_backlog) >= 0.0
    assert float(tel.decode_error) >= 0.0
    assert float(tel.eff_gamma) <= 1.0 + 1e-5


def check_telemetry_identity_compressor(seed: int, d: int, log2_eta: int):
    """When compression is the identity (dense ship) and the EF memory is
    empty: backlog == 0, decode_error == 0 and eff_gamma == 1 BIT-EXACTLY
    (the residual is a literal zero — the one case where zero backlog is
    even reachable), and cosine == 1 to within one f32 ulp.  The cosine
    bound is one ulp rather than equality because XLA may emit FMA for
    ``sum(acc*g)`` but plain mul+add for ``sum(g*g)``, splitting the two
    otherwise-identical (power-of-two-scaled) reductions by one rounding.
    """
    g, _ = _tel_inputs(seed, d)
    tel = _telemetry_fn("none", 32, False, True)(
        g, jnp.zeros_like(g), jnp.float32(2.0 ** log2_eta), jnp.float32(0))
    assert float(tel.ef_backlog) == 0.0
    assert abs(float(tel.cosine) - 1.0) <= np.finfo(np.float32).eps
    assert float(tel.decode_error) == 0.0
    assert float(tel.eff_gamma) == 1.0


def check_telemetry_full_budget_matches_nonadaptive(seed: int, d: int,
                                                    method: str):
    """gamma_t == geometry_gamma with value_bits = 32: the ragged mask is
    a no-op and telemetry equals the non-adaptive compressor's bit-for-bit
    (the adaptive machinery adds zero distortion at full count)."""
    g, m = _tel_inputs(seed, d)
    eta = jnp.float32(0.25)
    t_ad = _telemetry_fn(method, 32, True, True)(
        g, m, eta, jnp.float32(_TEL_GAMMA))
    t_fx = _telemetry_fn(method, 32, False, True)(g, m, eta, jnp.float32(0))
    for a, b in zip(jax.tree.leaves(t_ad), jax.tree.leaves(t_fx)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def check_telemetry_scale_invariance(seed: int, d: int, value_bits: int,
                                     log2_c: int, gfrac: float):
    """Telemetry is a pure shape descriptor: scaling (g, m) by a power of
    two changes no field, bit-exactly, at every value width and per-round
    count (selection, quantization scales and all five sums scale
    exactly)."""
    g, m = _tel_inputs(seed, d)
    c = jnp.float32(2.0 ** log2_c)
    fn = _telemetry_fn("block_topk", value_bits, True, True)
    eta = jnp.float32(0.5)
    gt = jnp.float32(gfrac * _TEL_GAMMA)
    t1 = fn(g, m, eta, gt)
    t2 = fn(c * g, c * m, eta, gt)
    for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 2**31 - 1), st.sampled_from(_TEL_DS),
       st.sampled_from(["block_topk", "topk"]),
       st.sampled_from([4, 8, 16, 32]), st.floats(0.05, 1.0))
def test_telemetry_ranges_property(seed, d, method, value_bits, gfrac):
    check_telemetry_ranges(seed, d, method, value_bits, gfrac)


@given(st.integers(0, 2**31 - 1), st.sampled_from(_TEL_DS),
       st.integers(-2, 2))
def test_telemetry_identity_compressor_property(seed, d, log2_eta):
    check_telemetry_identity_compressor(seed, d, log2_eta)


@given(st.integers(0, 2**31 - 1), st.sampled_from(_TEL_DS),
       st.sampled_from(["block_topk", "topk"]))
def test_telemetry_full_budget_matches_nonadaptive_property(seed, d, method):
    check_telemetry_full_budget_matches_nonadaptive(seed, d, method)


@given(st.integers(0, 2**31 - 1), st.sampled_from(_TEL_DS),
       st.sampled_from([4, 8, 16, 32]), st.integers(-3, 3),
       st.floats(0.05, 1.0))
def test_telemetry_scale_invariance_property(seed, d, value_bits, log2_c,
                                             gfrac):
    check_telemetry_scale_invariance(seed, d, value_bits, log2_c, gfrac)


@given(st.integers(0, 2**31 - 1), st.integers(1, 3000),
       st.sampled_from([4, 8, 16, 32]), st.integers(1, 64))
def test_pack_roundtrip_with_counts_property(seed, n, bits, period):
    """Counts-aware pack -> unpack == identity on the valid mask, zeros on
    the invalid positions, for arbitrary period/count combinations."""
    rng = np.random.default_rng(seed)
    fields = jnp.asarray(rng.integers(0, 1 << bits, (2, n),
                                      dtype=np.uint32))
    counts = jnp.asarray(rng.integers(1, period + 1, 2), jnp.int32)
    words = kops.pack_fields(fields, bits, counts=counts, period=period)
    back = kops.unpack_fields(words, n, bits, counts=counts, period=period)
    pos = np.arange(n) % period
    valid = pos[None, :] < np.asarray(counts)[:, None]
    np.testing.assert_array_equal(np.asarray(back)[valid],
                                  np.asarray(fields)[valid])
    assert np.all(np.asarray(back)[~valid] == 0)


# ---------------------------------------------------------------------------
# bucketed transport round-trip (DESIGN.md §11)
# ---------------------------------------------------------------------------

def check_bucket_roundtrip(seed: int, method: str, value_bits: int,
                           adaptive: bool):
    """Random leaf mixes (stacked/unstacked, odd d, 1-5 leaves) encode
    into the flat bucket payload EXACTLY as the in-order concatenation of
    the per-leaf codec's payloads, and a 2-worker gathered bucket decodes
    per leaf bit-identically to per-leaf decode_rows — random per-row
    counts riding the ragged headers included."""
    from repro.comm.bucket import (build_bucket_plan, decode_buckets,
                                   encode_buckets)
    from repro.comm.exchange import check_bucket_payload
    from repro.core.dcsgd import _per_layer_topk

    rng = np.random.default_rng(seed)
    comp = Compressor(gamma=0.05, max_gamma=0.05 if adaptive else 0.0,
                      method=method, block=256, min_compress_size=64,
                      value_bits=value_bits)
    n_leaves = int(rng.integers(1, 6))
    shapes, stacked = [], []
    for _ in range(n_leaves):
        d = int(rng.integers(64, 3000))
        if rng.integers(2):
            shapes.append((int(rng.integers(1, 4)), d))
            stacked.append(True)
        else:
            shapes.append((d,))
            stacked.append(False)
    plan = build_bucket_plan(shapes, stacked, comp)
    if not plan.total_words:
        return                                     # nothing compresses

    def encode_worker(worker_seed):
        wrng = np.random.default_rng(worker_seed)
        rows, perleaf = [], []
        for ln in plan.leaves:
            if ln.dense:
                rows.append(None)
                perleaf.append(None)
                continue
            x = jnp.asarray(wrng.standard_normal((ln.L, ln.d))
                            .astype(np.float32))
            if method == "block_topk":
                vals, idx = block_extract_sparse(x, comp)
            else:
                vals, idx = _per_layer_topk(x, comp.k_for(ln.d))
            counts = None
            if ln.spec.ragged:
                counts = jnp.asarray(
                    wrng.integers(1, ln.spec.full_count + 1, ln.L),
                    jnp.int32)
            rows.append((vals, idx, counts))
            perleaf.append(wire_fmt.encode_rows(vals, idx, ln.spec,
                                                counts=counts))
        payload = encode_buckets(plan, rows)
        check_bucket_payload(payload, plan, comp)
        np.testing.assert_array_equal(
            np.asarray(payload),
            np.concatenate([np.asarray(p).reshape(-1)
                            for p in perleaf if p is not None]))
        return payload, perleaf

    pay_a, ref_a = encode_worker(seed + 1)
    pay_b, ref_b = encode_worker(seed + 2)
    decoded = decode_buckets(plan, jnp.stack([pay_a, pay_b]))
    for ln in plan.leaves:
        if ln.dense:
            assert decoded[ln.index] is None
            continue
        v2, i2 = decoded[ln.index]
        assert v2.shape == (2, ln.L, ln.spec.k)
        for w, ref_pay in enumerate((ref_a, ref_b)):
            v_ref, i_ref = wire_fmt.decode_rows(ref_pay[ln.index],
                                                ln.spec)
            np.testing.assert_array_equal(np.asarray(v2[w]),
                                          np.asarray(v_ref))
            np.testing.assert_array_equal(np.asarray(i2[w]),
                                          np.asarray(i_ref))


@given(st.integers(0, 2**31 - 1),
       st.sampled_from(["block_topk", "topk"]),
       st.sampled_from([4, 8, 16, 32]), st.booleans())
def test_bucket_roundtrip_property(seed, method, value_bits, adaptive):
    check_bucket_roundtrip(seed, method, value_bits, adaptive)


# ---- chunked ring schedule (DESIGN.md §14) ------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(1, 16), st.integers(1, 40),
       st.integers(0, 2000))
def test_ring_gather_schedule_property(seed, W, n_chunks, total_words):
    """For arbitrary (W, n_chunks, total_words) — including n_chunks that
    do not divide the buffer and n_chunks > total_words — the simulated
    ring schedule assembles, on EVERY worker, the bit-identical
    (W, total_words) buffer the flat all_gather produces, covering each
    slot exactly once (``ring_gather_reference`` raises otherwise).  The
    SPMD path shares ``chunk_table``/``step_source`` with the simulator
    and is pinned against ``lax.all_gather`` on real meshes in
    tests/distributed/test_overlap_exchange.py."""
    from repro.comm.ring import (chunk_table, n_permutes,
                                 ring_gather_reference)

    rng = np.random.default_rng(seed)
    bufs = rng.integers(0, 2**32, (W, total_words), dtype=np.uint32)
    out = ring_gather_reference(bufs, n_chunks)
    np.testing.assert_array_equal(
        out, np.broadcast_to(bufs[None], (W, W, total_words)))
    # chunk table: contiguous, exhaustive, near-even word-aligned split
    table = chunk_table(total_words, n_chunks)
    assert sum(ln for _, ln in table) == total_words
    off = 0
    for o, ln in table:
        assert o == off and ln >= 1
        off += ln
    if total_words:
        assert len(table) == min(n_chunks, total_words)
        lens = [ln for _, ln in table]
        assert max(lens) - min(lens) <= 1
    # the permute budget the HLO pins count: chunks x (W-1) per axis
    want = len(table) * (W - 1) if total_words else 0
    assert n_permutes((W,), total_words, n_chunks) == want


@given(st.integers(0, 2**31 - 1), st.integers(2, 8), st.integers(1, 17),
       st.integers(64, 1024), st.sampled_from([4, 8, 16, 32]))
def test_ring_carries_ragged_rows_property(seed, W, n_chunks, d,
                                           value_bits):
    """Ragged §9 payload rows (random per-worker valid counts in the
    header word) survive the chunked ring bit-exactly: chunk boundaries
    fall anywhere — mid-header, mid-field — yet every assembled row
    decodes to exactly its source worker's (values, indices, count)."""
    from repro.comm.ring import ring_gather_reference

    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=256, min_compress_size=1,
                      value_bits=value_bits)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    assert spec.ragged
    rng = np.random.default_rng(seed)
    payloads, expect = [], []
    for _ in range(W):
        x = jnp.asarray(rng.standard_normal((1, d)).astype(np.float32))
        vals, idx = block_extract_sparse(x, comp)
        counts = jnp.asarray(rng.integers(1, spec.full_count + 1, 1),
                             jnp.int32)
        pay = wire_fmt.encode_rows(vals, idx, spec, counts=counts)
        payloads.append(np.asarray(pay).reshape(-1))
        expect.append(wire_fmt.decode_rows(pay, spec, return_counts=True))
    out = ring_gather_reference(np.stack(payloads), n_chunks)
    # worker 0's assembled buffer: one payload row per source worker
    v2, i2, c2 = wire_fmt.decode_rows(jnp.asarray(out[0]), spec,
                                      return_counts=True)
    for src in range(W):
        ve, ie, ce = expect[src]
        np.testing.assert_array_equal(np.asarray(v2[src]),
                                      np.asarray(ve[0]))
        np.testing.assert_array_equal(np.asarray(i2[src]),
                                      np.asarray(ie[0]))
        np.testing.assert_array_equal(np.asarray(c2[src]),
                                      np.asarray(ce[0]))


# ---- gossip topology invariants (DESIGN.md §12) -------------------------

@given(st.sampled_from(["ring", "torus", "exp"]),
       st.sampled_from([4, 8, 16]))
def test_mixing_matrix_invariants_property(name, n):
    """Every registered topology builder yields a symmetric, doubly
    stochastic mixing matrix with a strictly positive spectral gap —
    the three conditions under which gossip averaging converges to the
    true mean at a geometric rate."""
    from repro.comm.topology import build_topology

    topo = build_topology(name, n)
    m = topo.mixing_matrix()
    assert m.shape == (n, n)
    np.testing.assert_array_equal(m, m.T)
    ones = np.ones(n)
    np.testing.assert_allclose(m @ ones, ones, atol=1e-12)
    np.testing.assert_allclose(ones @ m, ones, atol=1e-12)
    assert np.all(m >= 0.0)
    assert topo.spectral_gap() > 0.0
    # every row mixes self + degree neighbors at the uniform weight
    assert np.count_nonzero(m[0]) == topo.degree + 1
    np.testing.assert_allclose(m[m > 0], topo.mix_weight)


# ---------------------------------------------------------------------------
# federated tier: non-IID shard determinism + client sampling (DESIGN.md §13)
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(0, 1000),
       st.integers(0, 3), st.floats(0.05, 5.0))
def test_noniid_shard_determinism_property(seed, step, shard, alpha):
    """The (seed, step, shard) determinism contract survives the Dirichlet
    tilt AND n_shards refactors: the same shard of the same stream yields
    the bit-identical batch whether the cohort is 4 or 8 shards wide
    (same per-shard batch rows), across independent processes by
    construction (pure numpy SeedSequence)."""
    from repro.data.synthetic import TokenPipeline

    def pipe(n_shards):
        return TokenPipeline(vocab_size=128, seq_len=16,
                             global_batch=2 * n_shards, seed=seed,
                             n_shards=n_shards, shard=shard,
                             dirichlet_alpha=alpha)

    a = pipe(4).batch(step)["tokens"]
    b = pipe(8).batch(step)["tokens"]
    c = pipe(4).batch(step)["tokens"]       # fresh pipeline, same stream
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@given(st.integers(0, 2**31 - 1), st.floats(0.05, 50.0))
def test_dirichlet_tilt_property(seed, alpha):
    """The per-shard unigram tilt: a valid distribution, deterministic in
    (seed, shard), genuinely different across shards (non-IID), exactly
    the base zipf at alpha=0, and step-independent by construction (the
    tilt never sees the step counter)."""
    from repro.data.synthetic import TokenPipeline

    def probs(shard, a):
        return TokenPipeline(vocab_size=256, seq_len=8, global_batch=4,
                             seed=seed, n_shards=4, shard=shard,
                             dirichlet_alpha=a).unigram_probs()

    p0, p1 = probs(0, alpha), probs(1, alpha)
    for p in (p0, p1):
        assert np.all(p >= 0.0)
        np.testing.assert_allclose(p.sum(), 1.0, atol=1e-9)
    assert np.max(np.abs(p0 - p1)) > 0.0          # shards differ
    np.testing.assert_array_equal(probs(0, alpha), p0)   # deterministic
    base = probs(0, 0.0)
    zipf = 1.0 / np.arange(1, 257)
    np.testing.assert_allclose(base, zipf / zipf.sum(), atol=1e-12)


@given(st.integers(0, 2**31 - 1), st.integers(2, 16),
       st.floats(0.05, 20.0), st.integers(40, 400))
def test_dirichlet_label_shards_property(seed, n_shards, alpha, n):
    """Label-skew partition: a complete partition (every sample on exactly
    one shard), deterministic, and skew grows as alpha shrinks — at
    alpha <= 0.1 some class concentrates harder than the uniform split."""
    from repro.data.synthetic import dirichlet_label_shards

    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 5, n)
    s1 = dirichlet_label_shards(labels, n_shards, alpha, seed=seed)
    s2 = dirichlet_label_shards(labels, n_shards, alpha, seed=seed)
    np.testing.assert_array_equal(s1, s2)
    assert s1.shape == labels.shape
    assert s1.min() >= 0 and s1.max() < n_shards
    # per-class apportionment is exact: class sizes are conserved
    for c in np.unique(labels):
        assert (s1[labels == c] >= 0).all()
    assert np.bincount(s1, minlength=n_shards).sum() == n


@given(st.integers(0, 2**31 - 1), st.integers(1, 10**6),
       st.integers(2, 64))
def test_participation_mask_reproducible_property(seed, round_idx, n):
    """Same (seed, round) -> bit-identical mask, for both samplers; masks
    are 0/1 float32 and fixed mode hits clients_per_round exactly."""
    from repro.fed.sampling import participation_mask

    k = max(1, n // 2)
    m1 = participation_mask(n, round_idx, seed=seed, mode="fixed",
                            clients_per_round=k)
    m2 = participation_mask(n, round_idx, seed=seed, mode="fixed",
                            clients_per_round=k)
    np.testing.assert_array_equal(m1, m2)
    assert m1.dtype == np.float32
    assert set(np.unique(m1)) <= {0.0, 1.0}
    assert int(m1.sum()) == k
    b1 = participation_mask(n, round_idx, seed=seed, mode="bernoulli",
                            rate=0.9)
    b2 = participation_mask(n, round_idx, seed=seed, mode="bernoulli",
                            rate=0.9)
    np.testing.assert_array_equal(b1, b2)
    # different rounds decorrelate (not a frozen mask)
    m3 = participation_mask(n, round_idx + 1, seed=seed, mode="fixed",
                            clients_per_round=k)
    assert int(m3.sum()) == k


@given(st.integers(0, 2**31 - 1), st.floats(0.2, 0.9))
def test_bernoulli_participation_binomial_bounds_property(seed, rate):
    """Bernoulli sampling: the participation count stays within 6 sigma of
    the binomial mean (per-seed deterministic, so this is a pure tail
    bound on the underlying generator)."""
    from repro.fed.sampling import participation_mask

    n = 512
    m = participation_mask(n, 0, seed=seed, mode="bernoulli", rate=rate)
    cnt = m.sum()
    mu, sd = n * rate, np.sqrt(n * rate * (1 - rate))
    assert mu - 6 * sd - 1 <= cnt <= mu + 6 * sd + 1


@given(st.integers(0, 2**31 - 1), st.integers(2, 64))
def test_zero_participation_raises_property(seed, n):
    """A round nobody survives raises instead of producing 0/0 NaNs —
    rate=0 bernoulli deterministically, and stragglers only ever shrink
    the sampled set."""
    from repro.fed.sampling import (ZeroParticipationError,
                                    participation_mask)

    with pytest.raises(ZeroParticipationError):
        participation_mask(n, 0, seed=seed, mode="bernoulli", rate=0.0)
    full = participation_mask(n, 3, seed=seed, mode="fixed")
    try:
        dropped = participation_mask(n, 3, seed=seed, mode="fixed",
                                     straggler_rate=0.5)
    except ZeroParticipationError:
        return                       # everyone straggled: also correct
    assert np.all(dropped <= full)   # stragglers are a subset


# ---------------------------------------------------------------------------
# hostile-wire fuzz (DESIGN.md §16) — bodies live in tests/wire_fuzz.py so
# the fixed-seed tier in tests/test_faults.py drives the SAME invariants on
# images without the hypothesis dev extra
# ---------------------------------------------------------------------------

from wire_fuzz import (check_garbage_bucket_decode_safe,      # noqa: E402
                       check_garbage_rows_decode_safe,
                       check_honest_rows_verdict_clean)


@given(st.integers(0, 2**31 - 1), st.integers(64, 2048),
       st.sampled_from([64, 256, 1024]), st.sampled_from([4, 8, 16, 32]),
       st.booleans(), st.sampled_from(["block_topk", "topk"]))
def test_garbage_rows_decode_safe_property(seed, d, block, value_bits,
                                           adaptive, method):
    """Arbitrary uint32 garbage rows: decode never indexes out of bounds,
    nothing non-finite survives the verdict layer, the verdict is always
    a well-defined bool."""
    check_garbage_rows_decode_safe(seed, d, block, value_bits, adaptive,
                                   method)


@given(st.integers(0, 2**31 - 1), st.integers(64, 2048),
       st.sampled_from([64, 256, 1024]), st.sampled_from([4, 8, 16, 32]),
       st.booleans(), st.sampled_from(["block_topk", "topk"]))
def test_honest_rows_verdict_clean_property(seed, d, block, value_bits,
                                            adaptive, method):
    """Honest encodes are verdict-True everywhere; quarantine is a
    bit-exact pass-through on them (the faults-off guarantee)."""
    check_honest_rows_verdict_clean(seed, d, block, value_bits, adaptive,
                                    method)


@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8, 16, 32]),
       st.booleans())
def test_garbage_bucket_decode_safe_property(seed, value_bits, adaptive):
    """Same contract through the batched bucket decode with verdicts."""
    check_garbage_bucket_decode_safe(seed, value_bits, adaptive)


@given(st.integers(0, 2**31 - 1),
       st.sampled_from(["ring", "torus", "exp"]),
       st.sampled_from([4, 8, 16]))
def test_gossip_constant_fixed_point_property(seed, name, n):
    """A consensus-reached (constant-over-workers) state is a BIT-EXACT
    fixed point of the gossip round: the difference form makes every
    ``z_j - z_i`` literally zero before any weight multiplies it."""
    from repro.comm.topology import build_topology

    topo = build_topology(name, n)
    rng = np.random.default_rng(seed)
    row = rng.standard_normal(17).astype(np.float32)
    z = np.broadcast_to(row, (n, 17)).copy()
    np.testing.assert_array_equal(topo.mix_reference(z), z)
    # and one round strictly contracts a NON-constant state (gap > 0)
    z2 = rng.standard_normal((n, 17)).astype(np.float32)

    def err(a):
        return np.max(np.abs(a - a.mean(0)))

    assert err(topo.mix_reference(z2)) < err(z2)
