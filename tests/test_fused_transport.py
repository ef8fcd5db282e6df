"""Fused flat-buffer transport: bit-identity with the per-leaf transports
at the votes level.

Full train-step trajectory parity (method x transport x state_layout x
regime, single- and multi-device) lives in tests/test_parity_matrix.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import flatbuf, hier, signs, votes
from repro.core.topology import single_device_topology


@pytest.fixture(scope="module")
def topo():
    return single_device_topology()


def _tree(seed=0, pd=(2, 5), dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(key, pd + (3, 33), dtype),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   pd + (64,), dtype),
            "v": jax.random.normal(jax.random.fold_in(key, 2),
                                   pd + (7, 32), dtype)}


SPECS = {"w": P(None, None), "b": P(None), "v": P(None, None)}


@pytest.mark.parametrize("use_mask", [False, True])
def test_fused_vote_identical_to_per_leaf(topo, use_mask):
    tree = _tree()
    mask = None
    if use_mask:
        mask = jnp.asarray([[1, 1, 0, 1, 0], [1, 0, 0, 1, 1]],
                           jnp.float32) > 0.5
    vf = votes.fused_sign_vote(topo, tree, None, 0.0, mask)
    for k, leaf in tree.items():
        s = signs.sgn(leaf)
        v_ag = votes.majority_vote_dev(topo, s, mask, "ag_packed", SPECS[k])
        v_ar = votes.vote_ar_int8(topo, s, mask)
        assert vf[k].shape == leaf.shape[:1] + leaf.shape[2:]
        np.testing.assert_array_equal(np.asarray(vf[k]), np.asarray(v_ag))
        np.testing.assert_array_equal(np.asarray(vf[k]), np.asarray(v_ar))


def test_fused_vote_dc_folding(topo):
    """sgn(u + rho*delta) fused pre-sign == per-leaf corrected vote."""
    tree = _tree(seed=3)
    delta = {k: jax.random.normal(jax.random.PRNGKey(9),
                                  (2,) + v.shape[2:], v.dtype)
             for k, v in tree.items()}
    mask = jnp.asarray([[1, 1, 1, 0, 1], [1, 1, 1, 1, 1]]) > 0
    vf = votes.fused_sign_vote(topo, tree, delta, 0.3, mask)
    for k, leaf in tree.items():
        u = leaf + 0.3 * delta[k][:, None].astype(leaf.dtype)
        v_ag = votes.majority_vote_dev(topo, signs.sgn(u), mask,
                                       "ag_packed", SPECS[k])
        np.testing.assert_array_equal(np.asarray(vf[k]), np.asarray(v_ag))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_pallas_interpret_route_matches_jnp(topo, monkeypatch, dtype):
    """REPRO_FUSED_PALLAS=interpret drives the real kernels (interpret
    mode on CPU) through the same chain -- must match the jnp path
    bitwise, including bf16 trees (DC pre-added in leaf dtype: the
    kernel's f32 fold is only used for all-f32 trees)."""
    tree = _tree(seed=4, pd=(1, 4), dtype=dtype)
    delta = {k: jax.random.normal(jax.random.PRNGKey(8),
                                  (1,) + v.shape[2:], v.dtype)
             for k, v in tree.items()}
    mask = jnp.asarray([[1.0, 0.0, 1.0, 1.0]]) > 0.5
    v_jnp = votes.fused_sign_vote(topo, tree, delta, 0.5, mask)
    monkeypatch.setenv("REPRO_FUSED_PALLAS", "interpret")
    v_krn = votes.fused_sign_vote(topo, tree, delta, 0.5, mask)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(v_jnp[k]),
                                      np.asarray(v_krn[k]))


def _near_cancel(seed, rho, clients, pd=(1, 2)):
    """bf16 directions within 0.4% of -rho * delta, a bf16 delta tree
    and its flat buffer: the rounding of u + rho * delta decides the
    signs."""
    key = jax.random.PRNGKey(seed)
    delta = {k: jax.random.normal(jax.random.fold_in(key, i),
                                  pd[:1] + v.shape[2:], jnp.bfloat16)
             for i, (k, v) in enumerate(_tree(pd=pd).items())}
    us = []
    for c in range(clients):
        kc = jax.random.fold_in(key, 100 + c)
        us.append({k: (-rho * d[:, None].astype(jnp.float32)
                       * (1.0 + 0.004 * jax.random.uniform(
                           jax.random.fold_in(kc, i), pd[1:2] + d.shape[1:],
                           minval=-1.0, maxval=1.0))).astype(jnp.bfloat16)
                   for i, (k, d) in enumerate(delta.items())})
    layout = flatbuf.make_layout(us[0], batch_dims=2)
    d_buf = flatbuf.flatten_tree(layout, delta, batch_dims=1,
                                 dtype=jnp.bfloat16)
    return layout, us, d_buf


def test_fused_pallas_interpret_bf16_flat_delta_matches_jnp(topo,
                                                            monkeypatch):
    """bf16 directions with a bf16 flat delta: the Pallas route adds the
    correction on the flat lane-row buffer, the jnp route per leaf; the
    streamed tally (K = 3, one client with weight 0) and the flat
    vote+update master must agree bitwise."""
    rho, mu = 0.2, 1e-3
    layout, us, d_buf = _near_cancel(seed=21, rho=rho, clients=3)
    weights = [jnp.asarray([[1, 2]], jnp.int32),
               jnp.asarray([[0, 0]], jnp.int32),
               jnp.asarray([[2, 1]], jnp.int32)]
    v_buf = jax.random.normal(jax.random.PRNGKey(5), (1, layout.n_pad))
    mask = jnp.asarray([[True, True]])
    rows = layout.n_pad // flatbuf.LANES

    def run():
        tally = jnp.zeros((1, 2, rows, flatbuf.LANES), jnp.int8)
        for u, w in zip(us, weights):
            tally = votes.fused_sign_tally_accumulate(
                topo, layout, u, None, d_buf, rho, w, tally)
        master = votes.fused_sign_vote_update(
            topo, layout, us[0], d_buf, rho, mask, v_buf, mu, mu_static=mu)
        return np.asarray(tally), np.asarray(master)

    monkeypatch.delenv("REPRO_FUSED_PALLAS", raising=False)
    t_jnp, m_jnp = run()
    monkeypatch.setenv("REPRO_FUSED_PALLAS", "interpret")
    t_krn, m_krn = run()
    np.testing.assert_array_equal(t_jnp, t_krn)
    np.testing.assert_array_equal(m_jnp, m_krn)
    # the correction decides signs: without it the tally reads otherwise
    t_raw = votes.fused_sign_tally_accumulate(
        topo, layout, us[0], None, None, 0.0, weights[0],
        jnp.zeros((1, 2, rows, flatbuf.LANES), jnp.int8))
    t_one = votes.fused_sign_tally_accumulate(
        topo, layout, us[0], None, d_buf, rho, weights[0],
        jnp.zeros((1, 2, rows, flatbuf.LANES), jnp.int8))
    flips = np.sum(np.asarray(t_raw) != np.asarray(t_one))
    assert flips > 0.2 * 2 * layout.n           # 2 voters' real coordinates


@pytest.mark.parametrize("case", ["bf16_flat", "f32_flat", "mixed_flat",
                                  "bf16_tree"])
def test_fused_kernel_bufs_route(monkeypatch, case):
    """Which correction route ``_fused_kernel_bufs`` takes: a one-dtype
    non-f32 tree with a flat delta adds it on the flat buffer and never
    unflattens the delta; f32 trees hand the delta to the kernels' f32
    fold; mixed-dtype trees and a delta given as a tree pre-add it leaf
    by leaf."""
    rho = 0.3
    tree = _tree(seed=6, pd=(1, 3), dtype=jnp.bfloat16)
    if case == "f32_flat":
        tree = jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    if case == "mixed_flat":
        tree["b"] = tree["b"].astype(jnp.float32)
    delta = {k: jax.random.normal(jax.random.PRNGKey(7),
                                  (1,) + v.shape[2:], jnp.bfloat16)
             for k, v in tree.items()}
    layout = flatbuf.make_layout(tree, batch_dims=2)
    d_buf = flatbuf.flatten_tree(layout, delta, batch_dims=1,
                                 dtype=jnp.bfloat16)
    calls = []
    unflatten = flatbuf.unflatten_tree
    monkeypatch.setattr(flatbuf, "unflatten_tree",
                        lambda *a, **k: calls.append(1) or unflatten(*a, **k))
    flat = case != "bf16_tree"
    u_buf, k_buf = votes._fused_kernel_bufs(
        layout, tree, None if flat else delta, d_buf if flat else None, rho)

    pre_added = jax.tree.map(
        lambda u, dl: u + rho * dl[:, None].astype(u.dtype), tree, delta)
    dt = jnp.bfloat16 if case.startswith("bf16") else jnp.float32
    assert u_buf.shape == (1, 3, layout.n_pad) and u_buf.dtype == dt
    if case == "f32_flat":
        np.testing.assert_array_equal(
            np.asarray(u_buf),
            np.asarray(flatbuf.flatten_tree(layout, tree, 2, jnp.float32)))
        np.testing.assert_array_equal(np.asarray(k_buf),
                                      np.asarray(d_buf, np.float32))
    else:
        assert k_buf is None
        want = flatbuf.flatten_tree(layout, pre_added, 2, dt)
        np.testing.assert_array_equal(np.asarray(u_buf, np.float32),
                                      np.asarray(want, np.float32))
    assert bool(calls) == (case == "mixed_flat")


def test_fused_pallas_delta_slab_mapping(topo, monkeypatch):
    """Multi-tile buffer (rows not a power of two) with DC folded in the
    kernel: the per-voter delta re-read via the BlockSpec index map must
    match the jnp path for every (pod, device) slab."""
    key = jax.random.PRNGKey(11)
    # ~6 tiles of 4096 coords -> rows=6, row block 2, 3 blocks per slab
    tree = {"m": jax.random.normal(key, (2, 3, 24000)),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (2, 3, 500))}
    delta = {k: jax.random.normal(jax.random.fold_in(key, 2),
                                  (2,) + v.shape[2:], v.dtype)
             for k, v in tree.items()}
    v_jnp = votes.fused_sign_vote(topo, tree, delta, 0.4, None)
    monkeypatch.setenv("REPRO_FUSED_PALLAS", "interpret")
    v_krn = votes.fused_sign_vote(topo, tree, delta, 0.4, None)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(v_jnp[k]),
                                      np.asarray(v_krn[k]))


def test_per_leaf_fused_dispatch_falls_back(topo):
    """Per-leaf callers (FSDP lift) route 'fused' through ag_packed /
    ar_int8 -- identical votes either way."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 33))
    s = signs.sgn(x)
    out = votes.majority_vote_dev(topo, s, None, "fused", P(None))
    ref = signs.majority_vote(s[0], axis=0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref))


def test_algo_config_validates_transport():
    with pytest.raises(ValueError):
        hier.AlgoConfig(transport="bogus")
    with pytest.raises(ValueError):
        hier.AlgoConfig(method="bogus")
    hier.AlgoConfig(transport="fused")          # accepted
