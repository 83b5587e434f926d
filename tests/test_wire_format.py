"""Unit tests for the bit-packed wire format (DESIGN.md §8): field<->word
pack/unpack (ref vs Pallas), codec round-trips, payload byte accounting,
and the tie-handling regression in the fused wire extraction."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import wire as wire_fmt
from repro.core import Compressor
from repro.core.compression import block_extract_sparse
from repro.core.dcsgd import worker_compress_aggregate
from repro.kernels import ops
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# field <-> word packing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8, 16, 32])
@pytest.mark.parametrize("n", [1, 7, 64, 1000, 4097])
def test_pack_unpack_fields_roundtrip(key, bits, n):
    """pack -> unpack recovers every field exactly, for odd lengths that
    exercise the zero-padding to whole words."""
    hi = np.uint32(1) << np.uint32(bits - 1)  # keep values within the field
    fields = jnp.asarray(
        np.random.default_rng(bits * 1000 + n).integers(
            0, int(hi), (3, n), dtype=np.uint32))
    words = ops.pack_fields(fields, bits)
    assert words.dtype == jnp.uint32
    assert words.shape == (3, -(-n * bits // 32))
    back = ops.unpack_fields(words, n, bits)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(fields))


@pytest.mark.parametrize("bits", [4, 8, 16])
def test_pack_fields_ref_pallas_parity(key, bits):
    fields = jnp.asarray(np.random.default_rng(bits).integers(
        0, 1 << bits, (5, 777), dtype=np.uint32))
    w_ref = ops.pack_fields(fields, bits, impl="ref")
    w_pal = ops.pack_fields(fields, bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_pal))
    f_ref = ops.unpack_fields(w_ref, 777, bits, impl="ref")
    f_pal = ops.unpack_fields(w_ref, 777, bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))


def test_pack_masks_out_of_range_fields():
    """Fields wider than ``bits`` are masked, not smeared into neighbors."""
    fields = jnp.full((1, 8), 0xFFFFFFFF, jnp.uint32)
    words = ops.pack_fields(fields, 8)
    np.testing.assert_array_equal(np.asarray(words),
                                  np.full((1, 2), 0xFFFFFFFF, np.uint32))
    back = ops.unpack_fields(words, 8, 8)
    np.testing.assert_array_equal(np.asarray(back),
                                  np.full((1, 8), 0xFF, np.uint32))


def test_wire_ops_registered():
    from repro.kernels import dispatch
    reg = dispatch.registered()
    for op in ("wire_pack", "wire_unpack"):
        assert set(reg[op]) == {"ref", "pallas-interpret", "pallas-tpu"}, op
        # backend policy: vectorized jnp ref on CPU, the kernel on TPU
        assert dispatch._POLICY[op] == "backend"


# ---------------------------------------------------------------------------
# codec: WireSpec layout + encode/decode round-trips
# ---------------------------------------------------------------------------

def test_wirespec_layout_math():
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    spec = wire_fmt.WireSpec.for_row(comp, 2048)
    k = 4 * comp.block_k()
    assert spec.k == k and spec.local and spec.index_bits == 16
    assert spec.header_words == 1
    assert spec.index_words == -(-k * 16 // 32)
    assert spec.value_words == -(-k * 8 // 32)
    assert spec.row_bytes == 4 * (1 + spec.index_words + spec.value_words)
    assert spec.row_bytes == comp.wire_bytes(2048)
    # uncompressed rows have no packed payload
    assert wire_fmt.WireSpec.for_row(Compressor(method="none"), 2048) is None
    # block padding can push nb*k_b PAST d at large gamma: such rows ship
    # dense (matching dcsgd's pmean branch), never a None spec deref
    fat = Compressor(gamma=0.55, method="block_topk", block=1024,
                     min_compress_size=64)
    assert fat.sparse_k(1100) >= 1100
    assert fat.wire_bytes(1100) == 1100 * 4
    # flat 32-bit indices once d outgrows 16-bit addressing (topk)
    big = wire_fmt.WireSpec.for_row(
        Compressor(gamma=0.01, method="topk"), 100000)
    assert big.index_bits == 32 and not big.local


def test_wirespec_rejects_bad_widths():
    with pytest.raises(ValueError):
        wire_fmt.WireSpec(k=8, d=64, value_bits=12, index_bits=16,
                          local=False)
    with pytest.raises(ValueError):
        wire_fmt.WireSpec(k=8, d=64, value_bits=8, index_bits=8, local=False)


@pytest.mark.parametrize("value_bits", [4, 8, 16, 32])
@pytest.mark.parametrize("d", [1300, 2048, 4097])
def test_encode_decode_roundtrip(key, value_bits, d):
    """decode(encode(vals, idx)) == (quantize_values(vals), idx) exactly,
    including odd row sizes with padded last blocks."""
    comp = Compressor(gamma=0.05, method="block_topk", block=256,
                      min_compress_size=64, value_bits=value_bits)
    x = jax.random.normal(key, (3, d))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    payload = wire_fmt.encode_rows(vals, idx, spec)
    assert payload.dtype == jnp.uint32
    assert payload.nbytes == 3 * comp.wire_bytes(d)
    v2, i2 = wire_fmt.decode_rows(payload, spec)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(v2),
                                  np.asarray(comp.quantize_values(vals)))


@pytest.mark.parametrize("value_bits", [4, 8, 16, 32])
@pytest.mark.parametrize("ragged", [False, True])
def test_roundtrip_rows_matches_encode_decode(key, value_bits, ragged):
    """``roundtrip_rows`` (the overlap transport's launch-free own-payload
    view, DESIGN.md §14) is BIT-IDENTICAL to a literal
    decode_rows(encode_rows(...)) at every value width, ragged counts
    included — so the delay-1 EF residual equals the one a real decode of
    the carried payload would produce."""
    d = 1300
    comp = Compressor(gamma=0.05, max_gamma=0.05 if ragged else 0.0,
                      method="block_topk", block=256, min_compress_size=64,
                      value_bits=value_bits)
    x = jax.random.normal(key, (3, d))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    assert spec.ragged == ragged
    counts = None
    if ragged:
        counts = jnp.asarray(
            np.random.default_rng(value_bits).integers(
                1, spec.full_count + 1, 3), jnp.int32)
    ref = wire_fmt.decode_rows(
        wire_fmt.encode_rows(vals, idx, spec, counts=counts), spec)
    got = wire_fmt.roundtrip_rows(vals, idx, spec, counts=counts)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def test_encode_decode_negative_values_sign_extension(key):
    """Two's-complement sub-byte fields: all-negative rows survive."""
    comp = Compressor(gamma=0.1, method="block_topk", block=256,
                      min_compress_size=64, value_bits=4)
    x = -jnp.abs(jax.random.normal(key, (1, 1024))) - 0.5
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, 1024)
    v2, _ = wire_fmt.decode_rows(wire_fmt.encode_rows(vals, idx, spec), spec)
    assert np.all(np.asarray(v2) < 0)
    np.testing.assert_array_equal(np.asarray(v2),
                                  np.asarray(comp.quantize_values(vals)))


# ---------------------------------------------------------------------------
# tie handling in the fused wire extraction (ROADMAP open item)
# ---------------------------------------------------------------------------

def _run_worker(tree, comp, eta=1.0, gamma_t=None):
    """worker_compress_aggregate under a 1-device shard_map (W == 1, so the
    returned update IS this worker's decoded wire contribution)."""
    from jax import shard_map
    mesh = make_mesh((1,), ("data",))
    mem = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        functools.partial(worker_compress_aggregate, comp=comp,
                          dp_axes=("data",), gamma_t=gamma_t),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(spec, spec, P(), P(), P()), axis_names={"data"}, check_vma=False)
    # telemetry (the 5th output) has dedicated coverage in
    # tests/test_property.py and tests/distributed/test_telemetry_exchange
    return jax.jit(f)(tree, mem, jnp.float32(eta))[:4]


@pytest.mark.parametrize("value_bits", [16, 8, 32])
def test_tie_drop_correction_regression(value_bits):
    """A block with MORE than k_b entries exactly at tau: the wire ships
    exactly k_b of them (documented drop) and the dropped tied entries are
    recycled into the EF memory by the decoded-payload correction, so
    sent + m' == acc holds bit-exactly.  Historically the correction only
    ran under value_bits<32; the packed wire applies it always (the
    residual is taken against what receivers actually decode)."""
    comp = Compressor(gamma=0.01, method="block_topk", block=512,
                      min_compress_size=64, value_bits=value_bits)
    k_b = comp.block_k()             # = 5
    assert k_b == 5
    d = 1024                         # two 512-wide blocks
    rng = np.random.default_rng(0)
    acc = rng.uniform(-1.0, 1.0, d).astype(np.float32)
    # block 0: EIGHT entries tied exactly at |acc| == 3.0 (> k_b of them)
    tied = np.array([3.0, -3.0, 3.0, 3.0, -3.0, 3.0, 3.0, -3.0], np.float32)
    acc[:8] = tied
    tree = {"x": jnp.asarray(acc)}

    upd, mem, wire, _ = _run_worker(tree, comp, eta=1.0)  # m=0, eta=1 -> acc
    upd, mem = np.asarray(upd["x"]), np.asarray(mem["x"])

    # drop semantics: exactly k_b entries per block survive on the wire
    assert np.count_nonzero(upd[:512]) == k_b
    assert np.count_nonzero(upd[512:]) == k_b
    kept_ties = np.count_nonzero(upd[:8])
    assert kept_ties == k_b          # all five winners come from the tie
    # correction semantics: dropped tied entries live on in the EF memory
    dropped = np.count_nonzero(mem[:8])
    assert dropped == 8 - k_b
    # and the EF identity is bit-exact through the packed wire
    np.testing.assert_array_equal(upd + mem, acc)


def test_tie_drop_matches_unfused_path():
    """The fused-kernel tie semantics equal the pure-jnp escape hatch."""
    comp_kwargs = dict(gamma=0.01, method="block_topk", block=512,
                       min_compress_size=64, value_bits=8)
    d = 1024
    rng = np.random.default_rng(1)
    acc = rng.uniform(-1.0, 1.0, d).astype(np.float32)
    acc[:8] = 2.5
    tree = {"x": jnp.asarray(acc)}
    u_k, m_k, w_k, _ = _run_worker(tree, Compressor(use_kernel=True,
                                                    **comp_kwargs))
    u_j, m_j, w_j, _ = _run_worker(tree, Compressor(use_kernel=False,
                                                    **comp_kwargs))
    np.testing.assert_array_equal(np.asarray(u_k["x"]), np.asarray(u_j["x"]))
    np.testing.assert_array_equal(np.asarray(m_k["x"]), np.asarray(m_j["x"]))
    assert float(w_k) == float(w_j)


# ---------------------------------------------------------------------------
# ragged payloads: valid-count header + decode-honors-count (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _ragged_comp(**kw):
    base = dict(gamma=0.05, max_gamma=0.05, method="block_topk", block=256,
                min_compress_size=64, value_bits=8)
    base.update(kw)
    return Compressor(**base)


def test_ragged_spec_layout():
    """Adaptive compressors get a count header word; the static budget
    bytes stay the trace-time bound."""
    comp = _ragged_comp()
    spec = wire_fmt.WireSpec.for_row(comp, 2048)
    assert spec.ragged
    plain = wire_fmt.WireSpec.for_row(
        Compressor(gamma=0.05, method="block_topk", block=256,
                   min_compress_size=64, value_bits=8), 2048)
    assert not plain.ragged
    assert spec.header_words == plain.header_words + 1
    assert spec.row_bytes == plain.row_bytes + 4
    assert comp.wire_bytes(2048) == spec.row_bytes
    # effective bytes at full count == the static budget; below it, less
    assert float(spec.effective_row_bytes(spec.full_count)) == spec.row_bytes
    assert float(spec.effective_row_bytes(1)) < spec.row_bytes
    # geometry comes from max_gamma, not gamma
    assert _ragged_comp(gamma=0.01).k_for(2048) == comp.k_for(2048)


@pytest.mark.parametrize("value_bits", [4, 8, 16, 32])
@pytest.mark.parametrize("method", ["block_topk", "topk"])
def test_ragged_roundtrip_random_counts(method, value_bits):
    """encode(counts) -> decode masks exactly the invalid suffix of each
    period, per row, for random counts in [1, full_count] — both index
    layouts, every value width."""
    comp = _ragged_comp(method=method, value_bits=value_bits)
    d = 1300
    rng = np.random.default_rng(value_bits)
    x = jnp.asarray(rng.standard_normal((4, d)).astype(np.float32))
    if method == "block_topk":
        vals, idx = block_extract_sparse(x, comp)
    else:
        from repro.core.dcsgd import _per_layer_topk
        vals, idx = _per_layer_topk(x, comp.k_for(d))
    spec = wire_fmt.WireSpec.for_row(comp, d)
    counts = jnp.asarray(rng.integers(1, spec.full_count + 1, 4),
                         jnp.int32)
    payload = wire_fmt.encode_rows(vals, idx, spec, counts=counts)
    assert payload.nbytes == 4 * comp.wire_bytes(d)   # fixed budget buffer
    # runtime pricing reads the counts straight from the header words
    from repro.comm.exchange import effective_payload_bytes
    np.testing.assert_allclose(
        float(effective_payload_bytes(payload, spec)),
        float(jnp.sum(spec.effective_row_bytes(counts))))
    assert float(effective_payload_bytes(payload, spec)) <= payload.nbytes
    v2, i2, c2 = wire_fmt.decode_rows(payload, spec, return_counts=True)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))
    pos = np.arange(spec.k) % spec.count_period
    for r in range(4):
        valid = pos < int(counts[r])
        expect = comp.quantize_values(
            jnp.where(jnp.asarray(valid), vals[r:r + 1], 0.0))
        np.testing.assert_array_equal(np.asarray(v2[r:r + 1]),
                                      np.asarray(expect))
        assert np.all(np.asarray(v2[r])[~valid] == 0.0)
        np.testing.assert_array_equal(np.asarray(i2[r])[valid],
                                      np.asarray(idx[r])[valid])


def test_effective_bytes_clamps_hostile_count_header():
    """Byte-metric counterpart of the decode clamp below: the gathered
    count header is worker-controlled garbage until proven otherwise.
    decode_rows masks any bit pattern into [0, k]; the pricing in
    effective_payload_bytes used to trust the raw header, so a hostile
    count (0xFFFFFFFF, or any value above full_count) inflated
    effective_wire_bytes beyond the static budget — it must clamp to the
    same [0, full_count] range."""
    from repro.comm.exchange import effective_payload_bytes
    comp = _ragged_comp(value_bits=32)
    d = 1024
    x = jax.random.normal(jax.random.PRNGKey(5), (2, d))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    payload = wire_fmt.encode_rows(vals, idx, spec)
    budget = float(payload.shape[0] * spec.row_bytes)
    full_pricing = float(jnp.sum(spec.effective_row_bytes(
        jnp.full((2,), spec.full_count, jnp.int32))))
    header_only = float(jnp.sum(spec.effective_row_bytes(
        jnp.zeros(2, jnp.int32))))
    # positive overflow clamps to full_count; bit patterns that read as
    # negative int32 (0xFFFFFFFF == -1, 0x80000000 == INT32_MIN) clamp to 0
    for garbage, expect in (
        (spec.full_count + 10_000, full_pricing),
        (0x7FFFFFFF, full_pricing),
        (0xFFFFFFFF, header_only),
        (0x80000000, header_only),
    ):
        hacked = payload.at[:, 0].set(jnp.uint32(garbage))
        eff = float(effective_payload_bytes(hacked, spec))
        assert eff <= budget, (garbage, eff, budget)
        assert eff == pytest.approx(expect), (garbage, eff, expect)
    # a zeroed header prices only the per-row header overhead
    zeroed = payload.at[:, 0].set(jnp.uint32(0))
    assert float(effective_payload_bytes(zeroed, spec)) == pytest.approx(
        float(jnp.sum(spec.effective_row_bytes(jnp.zeros(2, jnp.int32)))))


def test_decode_honors_count_not_payload_tail():
    """The fixed-k_max buffer is ragged-IN-CONTENT: rewriting the count
    header below the encoded count masks entries that were genuinely
    encoded — decode trusts the count, never the tail bytes."""
    comp = _ragged_comp(value_bits=32)
    d = 1024
    x = jax.random.normal(jax.random.PRNGKey(3), (1, d))
    vals, idx = block_extract_sparse(x, comp)
    spec = wire_fmt.WireSpec.for_row(comp, d)
    full = wire_fmt.encode_rows(vals, idx, spec)      # all entries valid
    k_b_small = 3
    hacked = full.at[:, 0].set(jnp.uint32(k_b_small))
    v2, i2 = wire_fmt.decode_rows(hacked, spec)
    pos = np.arange(spec.k) % spec.k_b
    assert np.all(np.asarray(v2)[0][pos >= k_b_small] == 0.0)
    np.testing.assert_array_equal(
        np.asarray(v2)[0][pos < k_b_small],
        np.asarray(vals)[0][pos < k_b_small])
    # decoded indices of masked entries are clamped in-bounds
    assert np.all((np.asarray(i2) >= 0) & (np.asarray(i2) < d))


def test_ragged_worker_effective_bytes_and_ef_identity(key):
    """worker_compress_aggregate(gamma_t): EF identity stays bit-exact at a
    reduced per-round level, effective bytes drop below the static budget,
    and the budget stays the payload's literal byte length."""
    from repro.core import tree_wire_bytes
    comp = _ragged_comp(value_bits=32)
    tree = {"v": jax.random.normal(key, (3000,))}
    upd, mem, wire, eff = _run_worker(tree, comp, eta=1.0,
                                      gamma_t=jnp.float32(0.02))
    assert int(wire) == tree_wire_bytes(tree, comp)
    assert float(eff) < float(wire)
    np.testing.assert_allclose(np.asarray(upd["v"] + mem["v"]),
                               np.asarray(tree["v"]), atol=1e-6)
    # at the full budget the two byte counts coincide
    _, _, wire_f, eff_f = _run_worker(tree, comp, eta=1.0,
                                      gamma_t=jnp.float32(0.05))
    assert float(eff_f) == float(wire_f)


def test_pack_fields_ragged_ref_pallas_parity():
    """Counts-aware pack/unpack: the Pallas kernels match the jnp ref for
    periodic (block-local) and prefix (flat) masks."""
    rng = np.random.default_rng(7)
    fields = jnp.asarray(rng.integers(0, 1 << 8, (5, 777), dtype=np.uint32))
    counts = jnp.asarray(rng.integers(1, 37, 5), jnp.int32)
    for period in (37, 777):          # block-periodic and whole-row prefix
        w_ref = ops.pack_fields(fields, 8, counts=counts, period=period,
                                impl="ref")
        w_pal = ops.pack_fields(fields, 8, counts=counts, period=period,
                                impl="pallas")
        np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_pal))
        f_ref = ops.unpack_fields(w_ref, 777, 8, counts=counts,
                                  period=period, impl="ref")
        f_pal = ops.unpack_fields(w_ref, 777, 8, counts=counts,
                                  period=period, impl="pallas")
        np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))
        # the mask is really applied
        pos = np.arange(777) % period
        assert np.all(np.asarray(f_ref)[pos[None, :] >= np.asarray(counts)[:, None]] == 0)


def test_ragged_fused_path_thresholds_at_budget(key):
    """Regression: with gamma (initial) < max_gamma the fused kernel path
    must threshold at the BUDGET level — otherwise block_extract comes up
    short and ships zeros.  Fused == unfused at a reduced gamma_t."""
    kw = dict(gamma=0.01, max_gamma=0.05, method="block_topk", block=512,
              min_compress_size=64, value_bits=32)
    tree = {"v": jax.random.normal(key, (3000,))}
    gt = jnp.float32(0.03)
    u_k, m_k, w_k, e_k = _run_worker(tree, Compressor(use_kernel=True, **kw),
                                     gamma_t=gt)
    u_j, m_j, w_j, e_j = _run_worker(tree, Compressor(use_kernel=False,
                                                      **kw), gamma_t=gt)
    np.testing.assert_allclose(np.asarray(u_k["v"]), np.asarray(u_j["v"]),
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(m_k["v"]), np.asarray(m_j["v"]),
                               atol=1e-7)
    assert float(w_k) == float(w_j) and float(e_k) == float(e_j)
    # k_t entries per (full) block actually survive: 0.03*512 ~ 15, not
    # the initial gamma's 5
    comp = Compressor(use_kernel=True, **kw)
    nz = np.count_nonzero(np.asarray(u_k["v"])[:512])
    assert nz == int(comp.block_k_t(gt))


def test_ragged_block_topk_requires_block_local_indices():
    """Adaptive block_topk with block > 2^16 cannot express the per-block
    count mask (entries are block-ordered, not row-sorted) — rejected at
    spec construction instead of silently mis-masking."""
    comp = Compressor(gamma=0.01, max_gamma=0.05, method="block_topk",
                      block=1 << 17, min_compress_size=64)
    with pytest.raises(ValueError, match="block-local"):
        wire_fmt.WireSpec.for_row(comp, 1 << 18)
    # the non-adaptive counterpart still builds (flat 32-bit indices)
    plain = Compressor(gamma=0.01, method="block_topk", block=1 << 17,
                       min_compress_size=64)
    spec = wire_fmt.WireSpec.for_row(plain, 1 << 18)
    assert spec.index_bits == 32 and not spec.local and not spec.ragged
