"""Unit tests for the bucketed payload transport (DESIGN.md §11): plan
building, stream pack/unpack reflow, bucket encode/decode bit-parity with
the per-leaf codec, the bucket accounting contract, the `_scatter_layers`
arities, and 1-device transport parity of worker_compress_aggregate."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import bucket as bucket_mod
from repro.comm import wire as wire_fmt
from repro.comm.bucket import (build_bucket_plan, decode_buckets,
                               encode_buckets)
from repro.comm.exchange import check_bucket_payload
from repro.core import Compressor
from repro.core.compression import block_extract_sparse, tree_wire_bytes
from repro.core.dcsgd import (_per_layer_topk, _scatter_layers,
                              worker_compress_aggregate)
from repro.kernels import ops
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# plan building
# ---------------------------------------------------------------------------

def _shapes_stacked(tree):
    leaves = jax.tree.leaves(tree)
    return [x.shape for x in leaves], [x.ndim >= 2 for x in leaves]


def test_plan_groups_and_offsets():
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=64, value_bits=8)
    shapes = [(3, 2048), (3000,), (50,), (2, 4, 300)]
    stacked = [True, False, False, True]
    plan = build_bucket_plan(shapes, stacked, comp)
    assert plan.dense_ids == (2,)                  # 50 < min_compress_size
    assert plan.compressed_ids == (0, 1, 3)
    # block_topk: every compressed leaf shares 16-bit local indices
    assert len(plan.buckets) == 1
    assert plan.buckets[0].index_bits == 16
    assert plan.buckets[0].leaf_ids == (0, 1, 3)
    assert plan.n_gathers == 1
    # the offset table is the in-order concatenation of exact payloads
    off = 0
    for ln in plan.leaves:
        if ln.dense:
            assert ln.words == 0
            continue
        assert ln.word_off == off
        assert ln.words == ln.L * ln.spec.row_words
        off += ln.words
    assert plan.total_words == off
    # ... and its byte total IS the per-leaf accounting
    tree = [jnp.zeros(s) for s in shapes]
    assert plan.total_words * 4 + 50 * 4 == tree_wire_bytes(tree, comp)


def test_plan_two_buckets_max():
    """Mixed 16/32-bit index layouts (flat topk straddling 2^16) make
    exactly two buckets — the only layout split a single Compressor can
    produce."""
    comp = Compressor(gamma=0.01, method="topk", min_compress_size=64)
    shapes = [(3000,), (70000,), (2048,), (100000,)]
    plan = build_bucket_plan(shapes, [False] * 4, comp)
    assert len(plan.buckets) == 2
    bits = {b.index_bits: b.leaf_ids for b in plan.buckets}
    assert bits[16] == (0, 2) and bits[32] == (1, 3)
    assert plan.n_gathers == 1                    # still ONE collective


def test_plan_geometry_matches_leaf_2d():
    assert bucket_mod.plan_geometry((3, 4, 5), True) == (3, 20)
    assert bucket_mod.plan_geometry((3, 4, 5), False) == (1, 60)
    assert bucket_mod.plan_geometry((7,), False) == (1, 7)
    assert bucket_mod.plan_geometry((7,), True) == (1, 7)


def test_plan_all_dense_has_no_gather():
    plan = build_bucket_plan([(10,), (20,)], [False, False],
                             Compressor(method="none"))
    assert plan.n_gathers == 0 and plan.total_words == 0
    assert plan.buckets == ()


# ---------------------------------------------------------------------------
# stream pack/unpack reflow (bucket-shaped launches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8, 16, 32])
@pytest.mark.parametrize("n_words", [1, 7, 511, 512, 513, 2000])
def test_stream_pack_matches_rowwise(bits, n_words):
    """pack_fields_stream == row-by-row pack_fields on any word-aligned
    row structure (packing is word-local), across the WORD_CHUNK reflow
    boundary."""
    F = max(1, 32 // bits)
    rng = np.random.default_rng(bits * 10000 + n_words)
    fields = jnp.asarray(rng.integers(0, 1 << min(bits, 31),
                                      (n_words * F,), dtype=np.uint32))
    stream = ops.pack_fields_stream(fields, bits)
    assert stream.shape == (n_words,)
    # rows of 1 word each is the finest row structure
    rows = ops.pack_fields(fields.reshape(n_words, F), bits)
    np.testing.assert_array_equal(np.asarray(stream),
                                  np.asarray(rows).reshape(-1))
    back = ops.unpack_fields_stream(stream, bits)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(fields))


def test_stream_pack_rejects_unaligned():
    with pytest.raises(ValueError, match="word-aligned"):
        ops.pack_fields_stream(jnp.zeros((3,), jnp.uint32), 16)


# ---------------------------------------------------------------------------
# bucket codec == per-leaf codec, bit for bit
# ---------------------------------------------------------------------------

def _leaf_rows(x, comp):
    """Per-leaf (vals, idx, spec) at the static budget, as dcsgd does."""
    if comp.method == "block_topk":
        vals, idx = block_extract_sparse(x, comp)
    else:
        vals, idx = _per_layer_topk(x, comp.k_for(x.shape[-1]))
    return vals, idx, wire_fmt.WireSpec.for_row(comp, x.shape[-1])


@pytest.mark.parametrize("method,value_bits", [
    ("block_topk", 4), ("block_topk", 8), ("block_topk", 32),
    ("topk", 16), ("topk", 32),
])
def test_bucket_encode_decode_equals_perleaf_codec(key, method, value_bits):
    """encode_buckets is the in-order concatenation of the EXACT per-leaf
    encode_rows payloads (no padding on the wire), and decode_buckets of a
    stacked 2-worker gather returns per-leaf arrays bit-identical to
    per-leaf decode_rows."""
    comp = Compressor(gamma=0.05, method=method, block=256,
                      min_compress_size=64, value_bits=value_bits)
    ks = jax.random.split(key, 3)
    leaves = [jax.random.normal(ks[0], (3, 1300)),
              jax.random.normal(ks[1], (1, 2048)),
              jax.random.normal(ks[2], (2, 70000) if method == "topk"
                                else (2, 4097))]
    plan = build_bucket_plan([x.shape for x in leaves], [True] * 3, comp)
    assert plan.dense_ids == ()
    rows = []
    perleaf = []
    for x in leaves:
        vals, idx, spec = _leaf_rows(x, comp)
        rows.append((vals, idx, None))
        perleaf.append((wire_fmt.encode_rows(vals, idx, spec), spec))
    payload = encode_buckets(plan, rows)
    check_bucket_payload(payload, plan, comp)
    np.testing.assert_array_equal(
        np.asarray(payload),
        np.concatenate([np.asarray(p).reshape(-1) for p, _ in perleaf]))

    # two "workers": this payload and a bit-twiddled sibling
    other = payload ^ jnp.uint32(0)
    gathered = jnp.stack([payload, other])
    decoded = decode_buckets(plan, gathered)
    for ln, (pay, spec) in zip(plan.leaves, perleaf):
        v_ref, i_ref = wire_fmt.decode_rows(pay, spec)
        v2, i2 = decoded[ln.index]
        assert v2.shape == (2, ln.L, spec.k)
        for w in range(2):
            np.testing.assert_array_equal(np.asarray(v2[w]),
                                          np.asarray(v_ref))
            np.testing.assert_array_equal(np.asarray(i2[w]),
                                          np.asarray(i_ref))


@pytest.mark.parametrize("value_bits", [4, 8, 16, 32])
def test_bucket_ragged_counts_roundtrip(key, value_bits):
    """Ragged buckets: per-leaf counts ride the header, the bucket codec
    masks exactly what per-leaf encode_rows/decode_rows mask."""
    comp = Compressor(gamma=0.05, max_gamma=0.05, method="block_topk",
                      block=256, min_compress_size=64,
                      value_bits=value_bits)
    ks = jax.random.split(key, 2)
    leaves = [jax.random.normal(ks[0], (3, 1300)),
              jax.random.normal(ks[1], (2, 2048))]
    plan = build_bucket_plan([x.shape for x in leaves], [True] * 2, comp)
    rng = np.random.default_rng(value_bits)
    rows, perleaf = [], []
    for x in leaves:
        vals, idx, spec = _leaf_rows(x, comp)
        counts = jnp.asarray(
            rng.integers(1, spec.full_count + 1, x.shape[0]), jnp.int32)
        rows.append((vals, idx, counts))
        perleaf.append((wire_fmt.encode_rows(vals, idx, spec,
                                             counts=counts), spec))
    payload = encode_buckets(plan, rows)
    np.testing.assert_array_equal(
        np.asarray(payload),
        np.concatenate([np.asarray(p).reshape(-1) for p, _ in perleaf]))
    decoded = decode_buckets(plan, payload[None])
    for ln, (pay, spec) in zip(plan.leaves, perleaf):
        v_ref, i_ref = wire_fmt.decode_rows(pay, spec)
        v2, i2 = decoded[ln.index]
        np.testing.assert_array_equal(np.asarray(v2[0]), np.asarray(v_ref))
        np.testing.assert_array_equal(np.asarray(i2[0]), np.asarray(i_ref))


def test_check_bucket_payload_catches_drift():
    comp = Compressor(gamma=0.05, method="block_topk", block=256,
                      min_compress_size=64)
    plan = build_bucket_plan([(3, 1300)], [True], comp)
    good = jnp.zeros((plan.total_words,), jnp.uint32)
    check_bucket_payload(good, plan, comp)
    with pytest.raises(ValueError, match="uint32"):
        check_bucket_payload(good.astype(jnp.int32), plan, comp)
    with pytest.raises(ValueError, match="plan says"):
        check_bucket_payload(jnp.zeros((plan.total_words + 1,),
                                       jnp.uint32), plan, comp)
    # accounting drift: a compressor whose wire_bytes disagrees with the
    # planned spec (different value width) must fail at trace time
    other = Compressor(gamma=0.05, method="block_topk", block=256,
                       min_compress_size=64, value_bits=8)
    with pytest.raises(ValueError, match="drift"):
        check_bucket_payload(good, plan, other)


# ---------------------------------------------------------------------------
# _scatter_layers arities (ISSUE 5 satellite: the 2-D pre-normalization
# was a no-op and the ndim handling duplicated)
# ---------------------------------------------------------------------------

def test_scatter_layers_2d():
    vals = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])          # (L=2, k=2)
    idx = jnp.asarray([[0, 3], [1, 1]], jnp.int32)
    out = _scatter_layers(vals, idx, 2, 4, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray([[1.0, 0.0, 0.0, 2.0], [0.0, 7.0, 0.0, 0.0]]))


def test_scatter_layers_3d_sums_workers():
    vals = jnp.asarray([[[1.0, 2.0]], [[10.0, 20.0]]])    # (W=2, L=1, k=2)
    idx = jnp.asarray([[[0, 2]], [[2, 3]]], jnp.int32)
    out = _scatter_layers(vals, idx, 1, 4, jnp.float32)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray([[1.0, 0.0, 12.0, 20.0]]))


def test_scatter_layers_rejects_bad_rank():
    with pytest.raises(ValueError, match="expected"):
        _scatter_layers(jnp.zeros((4,)), jnp.zeros((4,), jnp.int32), 1, 8,
                        jnp.float32)


def test_scatter_layers_arities_agree(key):
    """(L, k) == (1, L, k)-with-W=1 and (W, L, k) == sum of per-worker
    (L, k) scatters."""
    vals = jax.random.normal(key, (3, 2, 7))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (3, 2, 7), 0, 32)
    tri = _scatter_layers(vals, idx, 2, 32, jnp.float32)
    acc = sum(_scatter_layers(vals[w], idx[w], 2, 32, jnp.float32)
              for w in range(3))
    np.testing.assert_allclose(np.asarray(tri), np.asarray(acc),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# transport parity of worker_compress_aggregate (1 device)
# ---------------------------------------------------------------------------

def _run_worker(tree, comp, transport, gamma_t=None, eta=0.7):
    from jax import shard_map
    mesh = make_mesh((1,), ("data",))
    mem = jax.tree.map(lambda x: jnp.full_like(x, 0.05), tree)
    spec = jax.tree.map(lambda _: P(), tree)
    f = shard_map(
        functools.partial(worker_compress_aggregate, comp=comp,
                          dp_axes=("data",), gamma_t=gamma_t,
                          transport=transport),
        mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(spec, spec, P(), P(), P()), axis_names={"data"}, check_vma=False)
    return jax.jit(f)(tree, mem, jnp.float32(eta))


def _mixed_tree(key):
    ks = jax.random.split(key, 5)
    return {
        "a": jax.random.normal(ks[0], (3, 2048)),
        "b": jax.random.normal(ks[1], (3000,)),
        "tiny": jax.random.normal(ks[2], (50,)),          # dense pmean
        "c": jax.random.normal(ks[3], (2, 4, 300)),
        "big": jax.random.normal(ks[4], (70000,)),        # 32-bit (topk)
    }


@pytest.mark.parametrize("comp,gamma_t", [
    (Compressor(gamma=0.05, method="block_topk", block=512,
                min_compress_size=64, value_bits=8), None),
    (Compressor(gamma=0.05, method="block_topk", block=512,
                min_compress_size=64, value_bits=8, use_kernel=False),
     None),
    (Compressor(gamma=0.05, method="topk", min_compress_size=64,
                value_bits=16), None),
    (Compressor(gamma=0.05, max_gamma=0.05, method="block_topk", block=512,
                min_compress_size=64, value_bits=4), 0.02),
    (Compressor(gamma=0.05, max_gamma=0.05, method="topk",
                min_compress_size=64, value_bits=32), 0.013),
    (Compressor(method="none"), None),
])
def test_transport_parity_bit_exact(key, comp, gamma_t):
    """Bucketed == per-leaf: updates, new memory, and wire/effective
    bytes bit for bit; telemetry to <= 8 ulp (its f32 reduction order is
    not pinned across the two XLA programs — DESIGN.md §11)."""
    tree = _mixed_tree(key)
    gt = None if gamma_t is None else jnp.float32(gamma_t)
    ref = _run_worker(tree, comp, "perleaf", gt)
    got = _run_worker(tree, comp, "bucketed", gt)
    for name, a, b in zip(("updates", "memory", "wire", "eff", "tel"),
                          ref, got):
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            if name == "tel":
                np.testing.assert_array_max_ulp(np.asarray(u),
                                                np.asarray(v), maxulp=8)
            else:
                np.testing.assert_array_equal(np.asarray(u),
                                              np.asarray(v), err_msg=name)


def test_dense_byte_accounting_unified(key):
    """One accounting basis for every dense-shipping path (ISSUE 9 bugfix):
    byte counters charge ``size * itemsize`` of the f32 buffer the pmean
    actually moves — for ``dense_aggregate`` (which used to hard-code
    4 bytes/element) and for the transports' dense leaves alike, so the
    downlink's up/down byte split cannot drift between the two."""
    from jax import shard_map
    from repro.core.dcsgd import dense_aggregate
    tree = {
        "w": jax.random.normal(key, (2, 128)).astype(jnp.bfloat16),
        "t": jnp.ones((50,), jnp.float16),
    }
    f32 = jnp.dtype(jnp.float32).itemsize
    n_elem = sum(x.size for x in jax.tree.leaves(tree))
    expect = float(n_elem * f32)
    # the inputs are half-width on purpose: the charged basis must be the
    # shipped f32 accumulate, NOT the input-grad itemsize
    assert expect != sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(tree))

    mesh = make_mesh((1,), ("data",))
    spec = jax.tree.map(lambda _: P(), tree)
    upd, wire = jax.jit(shard_map(
        lambda g: dense_aggregate(g, jnp.float32(0.1), ("data",)),
        mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
        axis_names={"data"}, check_vma=False))(tree)
    assert all(u.dtype == jnp.float32 for u in jax.tree.leaves(upd))
    assert float(wire) == expect

    # transports: min_compress_size above every leaf size ships all leaves
    # dense through the pmean branch — same basis for wire AND effective
    comp = Compressor(gamma=0.05, method="block_topk", block=512,
                      min_compress_size=10**6, value_bits=8)
    tree32 = jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    for transport in ("perleaf", "bucketed"):
        _, _, wire_t, eff_t, _ = _run_worker(tree32, comp, transport)
        assert float(wire_t) == expect, transport
        assert float(eff_t) == expect, transport


def test_transport_rejects_unknown():
    tree = {"v": jnp.zeros((3000,))}
    with pytest.raises(ValueError, match="transport"):
        _run_worker(tree, Compressor(gamma=0.05, min_compress_size=64),
                    "carrier-pigeon")
