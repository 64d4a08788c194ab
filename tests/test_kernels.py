"""Per-kernel allclose tests: Pallas (interpret mode) vs pure-jnp oracle,
with shape/dtype sweeps (hypothesis) and authoritative external checks
(FIPS-197 vectors for AES, zlib for CRC32)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import (CRC_TABLE, expand_key, aes_decrypt_ref,
                               aes_encrypt_ref)
from repro.kernels.dpi_mlp import init_dpi_params, ternarize, train_dpi_params

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# AES
# ---------------------------------------------------------------------------

def test_aes_fips197_vector():
    key = np.arange(16, dtype=np.uint8)
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)
    rk = expand_key(key)
    ct = np.asarray(ops.aes_ecb(jnp.asarray(pt[None]), rk, impl="ref"))[0]
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"
    ct2 = np.asarray(ops.aes_ecb(jnp.asarray(pt[None]), rk, impl="pallas"))[0]
    assert (ct == ct2).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 2048), seed=st.integers(0, 2**31))
def test_aes_pallas_matches_ref_and_roundtrips(n, seed):
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    rk = expand_key(rng.integers(0, 256, 16, dtype=np.uint8))
    e_p = np.asarray(ops.aes_ecb(jnp.asarray(blocks), rk, impl="pallas"))
    e_r = np.asarray(ops.aes_ecb(jnp.asarray(blocks), rk, impl="ref"))
    np.testing.assert_array_equal(e_p, e_r)
    d = np.asarray(ops.aes_ecb(jnp.asarray(e_p), rk, decrypt=True,
                               impl="pallas"))
    np.testing.assert_array_equal(d, blocks)


def test_aes_ecb_identical_blocks_leak():
    """ECB property the paper's service inherits: identical plaintext
    blocks -> identical ciphertext blocks (documented limitation)."""
    key = RNG.integers(0, 256, 16, dtype=np.uint8)
    rk = expand_key(key)
    blocks = np.tile(RNG.integers(0, 256, (1, 16), dtype=np.uint8), (4, 1))
    ct = np.asarray(ops.aes_ecb(jnp.asarray(blocks), rk))
    assert (ct == ct[0]).all()


# ---------------------------------------------------------------------------
# CRC32
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 64), mtu=st.sampled_from([64, 256, 512, 4096]),
       seed=st.integers(0, 2**31))
def test_crc32_matches_zlib(n, mtu, seed):
    rng = np.random.default_rng(seed)
    pay = rng.integers(0, 256, (n, mtu), dtype=np.uint8)
    plen = rng.integers(0, mtu + 1, n).astype(np.int32)
    for impl in ("pallas", "ref"):
        got = np.asarray(ops.crc32(jnp.asarray(pay), jnp.asarray(plen),
                                   impl=impl))
        want = np.array([zlib.crc32(pay[i, :plen[i]].tobytes()) & 0xFFFFFFFF
                         for i in range(n)], np.uint32)
        np.testing.assert_array_equal(got, want, err_msg=impl)


def test_crc32_detects_corruption():
    pay = RNG.integers(0, 256, (4, 512), dtype=np.uint8)
    plen = np.full(4, 512, np.int32)
    c1 = np.asarray(ops.crc32(jnp.asarray(pay), jnp.asarray(plen)))
    pay2 = pay.copy()
    pay2[2, 100] ^= 0x01          # single bit flip
    c2 = np.asarray(ops.crc32(jnp.asarray(pay2), jnp.asarray(plen)))
    assert c1[2] != c2[2] and (c1[[0, 1, 3]] == c2[[0, 1, 3]]).all()


# ---------------------------------------------------------------------------
# DPI MLP
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(n=st.integers(1, 16), mtu=st.sampled_from([64, 256, 4096]),
       seed=st.integers(0, 2**31))
def test_dpi_pallas_matches_ref(n, mtu, seed):
    rng = np.random.default_rng(seed)
    params = ternarize(init_dpi_params(jax.random.key(seed % 97)))
    pay = rng.integers(0, 256, (n, mtu), dtype=np.uint8)
    s_p = np.asarray(ops.dpi_scores(jnp.asarray(pay), params, impl="pallas"))
    s_r = np.asarray(ops.dpi_scores(jnp.asarray(pay), params, impl="ref"))
    np.testing.assert_allclose(s_p, s_r, rtol=1e-5, atol=1e-5)


def test_dpi_training_separates_classes():
    from repro.data.dpi_dataset import make_dataset
    x, y = make_dataset(1024, seed=1)
    params = train_dpi_params(x, y, steps=200)
    xt, yt = make_dataset(512, seed=2)
    scores = np.asarray(ops.dpi_scores(
        jnp.asarray(xt.reshape(len(xt), 64)), params, impl="ref"))[:, 0]
    acc = ((scores > 0) == (yt > 0.5)).mean()
    assert acc > 0.85, f"ternary DPI accuracy too low: {acc}"


# ---------------------------------------------------------------------------
# Fused decrypt+DPI chain (one-HBM-pass kernel vs two-pass oracle)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(n=st.integers(1, 40), mtu=st.sampled_from([256, 1024]),
       seed=st.integers(0, 2**31))
def test_fused_chain_matches_ref_odd_shapes(n, mtu, seed):
    """Equivalence across packet counts NOT divisible by BLOCK_N (the
    grid-padding path), multiple MTUs, and random keys: identical
    plaintext, allclose DPI scores."""
    from repro.kernels.fused_chain import (BLOCK_N, fused_decrypt_dpi_pallas,
                                           fused_decrypt_dpi_ref)
    rng = np.random.default_rng(seed)
    if n % BLOCK_N == 0:
        n += 1                              # force the padded-grid path
    pay = rng.integers(0, 256, (n, mtu), dtype=np.uint8)
    rk = expand_key(rng.integers(0, 256, 16, dtype=np.uint8))
    params = ternarize(init_dpi_params(jax.random.key(seed % 89)))
    p_f, s_f = fused_decrypt_dpi_pallas(jnp.asarray(pay), rk, params)
    p_r, s_r = fused_decrypt_dpi_ref(jnp.asarray(pay), rk, params)
    assert p_f.shape == (n, mtu) and s_f.shape == (n,)
    np.testing.assert_array_equal(np.asarray(p_f), np.asarray(p_r))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)


def test_fused_chain_decrypt_roundtrip():
    """The fused kernel's decrypt really is AES^-1: encrypt with the
    reference, fuse-decrypt, recover the plaintext bytes."""
    from repro.kernels.fused_chain import fused_decrypt_dpi_pallas
    rng = np.random.default_rng(3)
    plain = rng.integers(0, 256, (7, 256), dtype=np.uint8)
    key = rng.integers(0, 256, 16, dtype=np.uint8)
    rk = expand_key(key)
    ct = np.asarray(ops.aes_ecb(jnp.asarray(plain.reshape(-1, 16)), rk,
                                impl="ref")).reshape(7, 256)
    params = ternarize(init_dpi_params(jax.random.key(0)))
    p_f, _ = fused_decrypt_dpi_pallas(jnp.asarray(ct), rk, params)
    np.testing.assert_array_equal(np.asarray(p_f), plain)


def test_fused_chain_tile_entry_matches_oneshot():
    """Streaming tile entry: short tiles padded to the fixed shape must
    return rows bit-identical to the one-shot call over the same rows."""
    from repro.kernels.fused_chain import (fused_decrypt_dpi_pallas,
                                           fused_decrypt_dpi_tile)
    rng = np.random.default_rng(11)
    pay = rng.integers(0, 256, (13, 256), dtype=np.uint8)
    rk = expand_key(rng.integers(0, 256, 16, dtype=np.uint8))
    params = ternarize(init_dpi_params(jax.random.key(5)))
    p_all, s_all = fused_decrypt_dpi_pallas(jnp.asarray(pay), rk, params)
    for lo, hi in ((0, 8), (8, 13)):        # full tile + short final tile
        p_t, s_t = fused_decrypt_dpi_tile(jnp.asarray(pay[lo:hi]), rk,
                                          params, tile_pkts=8)
        np.testing.assert_array_equal(np.asarray(p_t),
                                      np.asarray(p_all)[lo:hi])
        np.testing.assert_array_equal(np.asarray(s_t),
                                      np.asarray(s_all)[lo:hi])
    with pytest.raises(ValueError, match="tile carries"):
        fused_decrypt_dpi_tile(jnp.asarray(pay), rk, params, tile_pkts=8)


def test_fused_chain_kernel_matches_two_pass():
    from repro.kernels.fused_chain import (fused_decrypt_dpi_pallas,
                                           fused_decrypt_dpi_ref)
    from repro.kernels.ref import expand_key
    from repro.kernels.dpi_mlp import init_dpi_params, ternarize
    rng = np.random.default_rng(0)
    pay = rng.integers(0, 256, (5, 1024), dtype=np.uint8)
    rk = expand_key(rng.integers(0, 256, 16, dtype=np.uint8))
    params = ternarize(init_dpi_params(jax.random.key(0)))
    p1, s1 = fused_decrypt_dpi_pallas(jnp.asarray(pay), rk, params)
    p2, s2 = fused_decrypt_dpi_ref(jnp.asarray(pay), rk, params)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# DLRM preprocessing
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 700), n_dense=st.integers(1, 16),
       n_sparse=st.integers(1, 30), modulus=st.sampled_from([7, 1000, 100000]),
       seed=st.integers(0, 2**31))
def test_preproc_pallas_matches_ref(m, n_dense, n_sparse, modulus, seed):
    rng = np.random.default_rng(seed)
    recs = rng.integers(-10**6, 2**30, (m, n_dense + n_sparse)
                        ).astype(np.int32)
    p = np.asarray(ops.preproc(jnp.asarray(recs), n_dense, modulus,
                               impl="pallas"))
    r = np.asarray(ops.preproc(jnp.asarray(recs), n_dense, modulus,
                               impl="ref"))
    np.testing.assert_array_equal(p, r)


def test_preproc_tile_entry_matches_oneshot():
    """Streamed tiles (including a short final tile) reproduce the
    one-shot kernel bit for bit — the ingest's bit-identity contract at
    the kernel layer."""
    from repro.kernels.preproc import preproc_tile
    rng = np.random.default_rng(7)
    recs = rng.integers(-10**6, 2**30, (77, 39)).astype(np.int32)
    want = np.asarray(ops.preproc(jnp.asarray(recs), 13, 1000))
    got = [np.asarray(ops.preproc_tile(jnp.asarray(recs[lo:lo + 32]),
                                       13, 1000, tile_recs=32))
           for lo in range(0, 77, 32)]
    np.testing.assert_array_equal(np.concatenate(got), want)
    with pytest.raises(ValueError, match="tile carries"):
        preproc_tile(jnp.asarray(recs), 13, 1000, tile_recs=32)


def test_preproc_semantics():
    recs = np.array([[-5, 0, 99, 12345]], np.int32)
    out = np.asarray(ops.preproc(jnp.asarray(recs), 3, 100, impl="pallas"))
    dense = out[:, :3].view(np.float32)[0]
    np.testing.assert_allclose(dense, [0.0, 0.0, np.log1p(99)], rtol=1e-6)
    assert out[0, 3] == 12345 % 100


@pytest.mark.parametrize("cols", [(13, 27), (13, 26), (1, 1), None])
@settings(max_examples=3, deadline=None)
@given(rows=st.integers(1, 600), mtu=st.sampled_from([4096, 1024]),
       modulus=st.sampled_from([7, 1000, 40_000_000]),
       seed=st.integers(0, 2**31))
def test_preproc_packets_pallas_matches_record_ref(cols, rows, mtu, modulus,
                                                   seed):
    """The packet-row kernel equals ``preproc_ref`` over the records the
    rows carry, bit for bit, with the tail past the last whole record as
    sent.  ``cols`` None: (n_dense, n_sparse) drawn from the seed."""
    from repro.kernels.preproc import preproc_packets_pallas
    rng = np.random.default_rng(seed)
    n_dense, n_sparse = cols or (int(rng.integers(1, 17)),
                                 int(rng.integers(1, 31)))
    rec_words = n_dense + n_sparse
    n_rec = (mtu // 4) // rec_words
    words = rng.integers(-2**31, 2**31, (rows, mtu // 4), dtype=np.int64
                         ).astype(np.int32)
    words[0, :2] = [-2**31, 2**31 - 1]          # both ends, dense or not
    words[-1, n_dense:rec_words] = 2**31 - 1    # the largest ids
    pay = words.view(np.uint8)
    got = np.asarray(preproc_packets_pallas(jnp.asarray(pay), n_dense,
                                            rec_words, modulus))
    recs = words[:, :n_rec * rec_words].reshape(-1, rec_words)
    want = np.asarray(ops.preproc(jnp.asarray(recs), n_dense, modulus,
                                  impl="ref"))
    got_words = got.view(np.int32)
    np.testing.assert_array_equal(
        got_words[:, :n_rec * rec_words].reshape(-1, rec_words), want)
    tail = 4 * n_rec * rec_words
    np.testing.assert_array_equal(got[:, tail:], pay[:, tail:])


# ---------------------------------------------------------------------------
# Segmented reduce (collective offload math)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 8), words=st.integers(1, 1200),
       seed=st.integers(0, 2**31))
def test_chunk_reduce_pallas_bit_identical_to_ref(k, words, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((k, words)).astype(np.float32)
    u8 = np.ascontiguousarray(x).view(np.uint8).reshape(k, words * 4)
    a = np.asarray(ops.chunk_reduce(jnp.asarray(u8), impl="pallas"))
    b = np.asarray(ops.chunk_reduce(jnp.asarray(u8), impl="ref"))
    assert (a == b).all()
    # and the ref is the honest left fold
    acc = jnp.asarray(x[0])
    for i in range(1, k):
        acc = acc + x[i]
    assert (b.view(np.float32) == np.asarray(acc)).all()


def test_chunk_reduce_int32_exact():
    rng = np.random.default_rng(0)
    x = rng.integers(-(1 << 20), 1 << 20, (5, 333), dtype=np.int32)
    u8 = x.view(np.uint8).reshape(5, 333 * 4)
    for impl in ("pallas", "ref"):
        out = np.asarray(ops.chunk_reduce(jnp.asarray(u8), dtype="int32",
                                          impl=impl))
        assert (out.view(np.int32) == x.sum(0, dtype=np.int32)).all()


def test_chunk_reduce_order_matters_and_is_pinned():
    """Float fold order is part of the contract: reversing the rows
    changes bits (non-associativity is real on this data), while the
    same rows always fold identically."""
    rng = np.random.default_rng(4)
    x = (rng.standard_normal((4, 256)) * 10.0**rng.integers(
        -6, 6, (4, 256))).astype(np.float32)
    u8 = np.ascontiguousarray(x).view(np.uint8).reshape(4, 1024)
    a = np.asarray(ops.chunk_reduce(jnp.asarray(u8), impl="ref"))
    b = np.asarray(ops.chunk_reduce(jnp.asarray(u8[::-1].copy()),
                                    impl="ref"))
    assert (a == np.asarray(ops.chunk_reduce(jnp.asarray(u8),
                                             impl="ref"))).all()
    assert not (a == b).all()
