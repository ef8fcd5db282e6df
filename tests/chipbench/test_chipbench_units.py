"""The yardstick's arithmetic: FLOPs, least bytes, peaks, the sampler."""
from __future__ import annotations

import math

import numpy as np
import pytest

import harness
import peaks
import sampler
from metrics import sign_pack_roofline, tally_acc_roofline, \
    vote_update_roofline
from reference import stablelm_3b_6l, xlstm_350m

XLSTM_SMOKE = {"n_layers": 8, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
               "vocab": 256, "norm_eps": 1e-6,
               "xlstm": {"m_per_s": 3, "proj_factor": 2.0, "conv_kernel": 4}}
DENSE_SMOKE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
               "head_dim": 16, "d_ff": 128, "vocab": 256, "norm_eps": 1e-6,
               "rope_theta": 1e4}


def test_dense_flops_hand_count():
    # per layer: q, k, v, o 4 * 64 * 64, SwiGLU 3 * 64 * 128; head 64 * 256
    mult = 2 * (4 * 64 * 64 + 3 * 64 * 128) + 64 * 256
    attn = 2 * 6 * 32 * 4 * 16           # 6 t h hd per layer, t = 32
    assert stablelm_3b_6l.flops_per_token(DENSE_SMOKE, 32) == \
        6 * mult + attn


def test_xlstm_flops_hand_count():
    d, din, h, k, ff = 64, 128, 4, 4, 85
    mlstm = d * 2 * din + k * din + 3 * din * din + 2 * din * h + din * d
    slstm = d * 4 * d + h * 16 * 64 + d * 2 * ff + ff * d
    mult = 2 * (3 * mlstm + slstm) + d * 256
    quad = 6 * 6 * 16 * din                    # 6 mLSTM blocks, t = 16
    assert xlstm_350m.flops_per_token(XLSTM_SMOKE, 16) == 6 * mult + quad


@pytest.mark.parametrize("ref,model", [(xlstm_350m, XLSTM_SMOKE),
                                       (stablelm_3b_6l, DENSE_SMOKE)])
def test_flops_count_every_multiplying_parameter(ref, model):
    """6 x (all weights but the embedding table and the 1-D gains and
    biases) is the linear part of the count."""
    shapes = harness.spec_shapes(ref.param_spec(model))
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    mult = 0
    for path, shape in leaves:
        key = jax.tree_util.keystr(path)
        stacked = "stacks" in key
        if "embed" in key or len(shape) - stacked < 2:
            continue
        mult += math.prod(shape)
    linear = ref.flops_per_token(model, 0)
    assert linear == 6 * mult


def test_least_bytes():
    n = 4096 * 8
    # bf16 direction read, one bit written, per voter
    assert sign_pack_roofline.least_bytes(n, 1, "bfloat16") \
        == n * (2 + 1 / 8)
    # two voters' bits read, the f32 master read and written
    assert vote_update_roofline.least_bytes(n, 2) == n * (2 / 8 + 8)
    # per client: bf16 direction read, int8 tally read and written
    assert tally_acc_roofline.least_bytes(n, 8, "bfloat16", "int8") \
        == n * 8 * 4
    assert tally_acc_roofline.tally_dtype(8) == "int8"
    assert tally_acc_roofline.tally_dtype(200) == "int32"


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def _pool(seed, **kw):
    args = dict(vocab=512, pods=2, devices=1, clients=4,
                batch_per_device=4, seq_len=16, n_batches=3,
                alpha_client=0.1)
    args.update(kw)
    return sampler.make_pool(seed=seed, **args)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_sampler_is_a_function_of_the_seed(seed):
    a, b = _pool(seed), _pool(seed)
    assert a.shape == (3, 2, 1, 4, 16) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, _pool(seed + 1))
    assert a.min() >= 0 and a.max() < 512


def test_sampler_skews_edges_and_clients():
    logits = sampler.client_logits(512, 2, 1, 4, seed=3, alpha_client=0.1)
    top = logits.argmax(axis=-1)
    assert top[0, 0, 0] != top[1, 0, 0]            # edges differ
    assert len(set(top[0, 0].tolist())) > 1         # clients differ
    flat = sampler.client_logits(512, 2, 1, 4, seed=3, hetero=0.0)
    assert np.allclose(flat[0], flat[1])            # hetero 0: IID edges


def test_sampler_rejects_uneven_carve():
    with pytest.raises(ValueError):
        _pool(1, batch_per_device=6)
