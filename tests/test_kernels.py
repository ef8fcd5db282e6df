"""Pallas kernel validation: interpret-mode vs pure-jnp oracles, swept over
shapes, dtypes, voter counts and masks (per-kernel allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import signs
from repro.kernels import ops, ref

BK = dict(block_r=8)
SHAPES = [(257,), (64, 129), (5, 7, 11), (4096,)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rho", [0.0, 0.3])
def test_sign_pack_matches_oracle(shape, dtype, rho):
    g = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    d = (jax.random.normal(jax.random.PRNGKey(1), shape, dtype)
         if rho else None)
    packed, n = ops.sign_pack_nd(g, d, rho, use_pallas=True,
                                 interpret=True, **BK)
    assert n == int(np.prod(shape))
    if not rho:   # no correction, no rounding: the oracle's exact words
        expect, _ = ops.sign_pack_nd(g, d, rho, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(expect))
    # the lane-plane words unpack to sgn(g + rho*d), coordinate for
    # coordinate (bit j of word w <-> coordinate w + 128*j of its row)
    u = g.astype(jnp.float32)
    if d is not None:
        u = u + rho * d.astype(jnp.float32)
    got_bits = np.asarray(ref.unpack_planes(packed))[:n]
    exp_bits = np.asarray(signs.sgn(u.reshape(-1)))
    mism = np.where(got_bits != exp_bits)[0]
    # FMA contraction may flip the sign of coords where g + rho*d rounds
    # to exactly 0 -- tolerate only those ULP-boundary cases
    uf = np.abs(np.asarray(u.reshape(-1)))
    assert all(uf[i] < 1e-6 for i in mism), (mism, uf[mism])


@pytest.mark.parametrize("shape", [(333,), (64, 64)])
@pytest.mark.parametrize("k", [1, 4, 5, 16])
def test_vote_update_matches_oracle(shape, k):
    rng = jax.random.PRNGKey(2)
    gs = jax.random.normal(rng, (k,) + shape)
    rows = jnp.stack([ops.sign_pack_nd(gs[i], None, 0.0, use_pallas=True,
                                       interpret=True, **BK)[0]
                      for i in range(k)])
    v = jax.random.normal(jax.random.fold_in(rng, 1), shape)
    out = ops.vote_update_nd(rows, v, mu=0.05, use_pallas=True,
                             interpret=True, **BK)
    vote = signs.majority_vote(
        signs.sgn(gs.reshape(k, -1).astype(jnp.float32)), axis=0)
    expect = (v.reshape(-1) - 0.05 * vote).reshape(shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-6)


@pytest.mark.parametrize("mask", [[1, 1, 0], [0, 1, 0], [1, 1, 1]])
def test_vote_update_mask(mask):
    k = len(mask)
    gs = jax.random.normal(jax.random.PRNGKey(3), (k, 200))
    rows = jnp.stack([ops.sign_pack_nd(gs[i], None, 0.0, use_pallas=True,
                                       interpret=True, **BK)[0]
                      for i in range(k)])
    v = jnp.zeros((200,))
    out = ops.vote_update_nd(rows, v, jnp.asarray(mask, jnp.float32),
                             mu=1.0, use_pallas=True, interpret=True, **BK)
    vote = signs.majority_vote(signs.sgn(gs), jnp.asarray(mask)[:, None],
                               axis=0)
    np.testing.assert_allclose(np.asarray(out), -np.asarray(vote),
                               rtol=1e-6)


@pytest.mark.parametrize("shape", [(500,), (32, 48)])
def test_ternary_quant_matches_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(4), shape)
    q_k = ops.ternary_quant_nd(x, jax.random.PRNGKey(5), use_pallas=True,
                               interpret=True, **BK)
    q_r = ops.ternary_quant_nd(x, jax.random.PRNGKey(5), use_pallas=False,
                               **BK)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r), rtol=1e-5)


def test_kernel_pipeline_roundtrip():
    """device compress -> edge vote+update == core.signs semantics."""
    k, n = 7, 1000
    gs = jax.random.normal(jax.random.PRNGKey(6), (k, n))
    delta = jax.random.normal(jax.random.PRNGKey(7), (n,))
    rows = jnp.stack([ops.sign_pack_nd(gs[i], delta, 0.2, use_pallas=True,
                                       interpret=True, **BK)[0]
                      for i in range(k)])
    v = jnp.ones((n,))
    out = ops.vote_update_nd(rows, v, mu=0.1, use_pallas=True,
                             interpret=True, **BK)
    s = signs.sgn(gs + 0.2 * delta[None])
    vote = signs.majority_vote(s, axis=0)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(1.0 - 0.1 * vote), rtol=1e-6)


@pytest.mark.parametrize("n", [4096, 8192])
@pytest.mark.parametrize("rho", [0.0, 0.2])
@pytest.mark.parametrize("acc_dtype", [jnp.int8, jnp.int16, jnp.int32])
def test_fused_tally_acc_matches_ref(n, rho, acc_dtype):
    """Streamed-client accumulate (pack->popcount->tally RMW fused into
    one pass) vs the pure-jnp oracle, swept over tally dtypes and the
    shared-correction fold."""
    p, d = 2, 3
    key = jax.random.PRNGKey(8)
    u = jax.random.normal(key, (p, d, n))
    db = (jax.random.normal(jax.random.fold_in(key, 1), (p, n))
          if rho else None)
    w = jax.random.randint(jax.random.fold_in(key, 2), (p, d), 0, 5)
    tally = jax.random.randint(jax.random.fold_in(key, 3),
                               (p, d, n // 128, 128), -20,
                               20).astype(acc_dtype)
    got = ops.fused_tally_acc_flat(u, db, rho, w, tally, interpret=True)
    expect = ref.tally_acc_ref(u, db, rho, w, tally.reshape(p, d, n))
    assert got.dtype == acc_dtype
    assert got.shape == tally.shape
    np.testing.assert_array_equal(np.asarray(got).reshape(p, d, n),
                                  np.asarray(expect))


def test_fused_tally_acc_accumulates_to_merged_vote():
    """Folding K clients through the kernel then thresholding the tally
    equals the merged weighted vote of the same K sign planes."""
    from repro.core import votes
    p, d, k, n = 1, 2, 6, 4096
    key = jax.random.PRNGKey(9)
    us = jax.random.normal(key, (k, p, d, n))
    ws = jax.random.randint(jax.random.fold_in(key, 1), (k, p, d), 0, 3)
    tally = jnp.zeros((p, d, n // 128, 128), jnp.int8)
    for c in range(k):
        tally = ops.fused_tally_acc_flat(us[c], None, 0.0, ws[c], tally,
                                         interpret=True)
    n_eff = jnp.sum(ws.astype(jnp.int32), axis=(0, 2))
    vote = votes.tally_vote(
        jnp.sum(tally.astype(jnp.int32), axis=1).reshape(p, n), n_eff)
    s_merged = signs.sgn(us.transpose(1, 0, 2, 3).reshape(p, k * d, n))
    w_merged = ws.transpose(1, 0, 2).reshape(p, k * d)
    from repro.core.topology import single_device_topology
    merged = votes.vote_ar_int8(single_device_topology(), s_merged,
                                w_merged, weight_bound=int(n_eff.max()))
    np.testing.assert_array_equal(np.asarray(vote), np.asarray(merged))


@pytest.mark.parametrize("word_rows", [1, 3, 6, 70])
def test_fused_kernels_any_row_count(word_rows):
    """The flat-buffer kernels take any number of 4096-coordinate rows:
    fewer than 8 (one whole-slab block) and a ragged last block past the
    64-row block (70 = 64 + 6) -- row counts at which the old
    power-of-two row block fell below the TPU's 8-row tile.  Every
    kernel matches its oracle bitwise there, and the words unpack to
    the plain sign vote."""
    p, d = 2, 3
    n = word_rows * 4096
    key = jax.random.PRNGKey(10)
    u = jax.random.normal(key, (p, d, n))
    db = jax.random.normal(jax.random.fold_in(key, 1), (p, n))
    words = ops.fused_pack_flat(u, db, 0.3, interpret=True)
    u3 = u.reshape(p * d, -1, 128)
    expect = ref.sign_pack_ref(u3, db.reshape(p, -1, 128), 0.3)
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(expect.reshape(p, d, -1)))
    s = signs.sgn(u + 0.3 * db[:, None])
    np.testing.assert_array_equal(np.asarray(ref.unpack_planes(words)),
                                  np.asarray(s))
    v = jax.random.normal(jax.random.fold_in(key, 2), (p, n))
    for mask in (None, jnp.asarray([[1, 0, 2], [0, 0, 0]])):
        got = ops.fused_vote_update_words(words, v, mask, 0.01,
                                          interpret=True)
        m = None if mask is None else mask[:, :, None]
        vote = signs.majority_vote(s, m, axis=1).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(v - 0.01 * vote))
    w = jnp.asarray([[1, 2, 0], [3, 1, 1]])
    tally = jnp.zeros((p, d, n // 128, 128), jnp.int8)
    got = ops.fused_tally_acc_flat(u, db, 0.3, w, tally, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got).reshape(p, d, n),
        np.asarray(ref.tally_acc_ref(u, db, 0.3, w,
                                     tally.reshape(p, d, n))))
