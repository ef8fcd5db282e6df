"""Two-level heterogeneous unigram token sampler, in numpy on the host.

The benchmark's own copy of the stream the training driver uses
(``src/repro/data/synthetic.py``): a Zipf(``skew``) base unigram over the
vocabulary; each edge q permutes it and mixes ``hetero`` of the permuted
ranks with ``1 - hetero`` of the base (inter-edge skew); with
``alpha_client`` each virtual client then tilts its edge's unigram by
log(V * Dirichlet(alpha_client)) (intra-edge skew).  Client c of device
d owns rows [c*b/K, (c+1)*b/K) of that device's [b, L] batch, the rows
the train step carves for voter d*K + c.

Everything is drawn from ``seed`` with numpy's PCG64: the same seed
gives the same batches, and every seed gives batches of the same shape.
"""
from __future__ import annotations

import numpy as np


def client_logits(vocab: int, pods: int, devices: int, clients: int,
                  seed: int, skew: float = 1.2, hetero: float = 1.0,
                  alpha_client: float | None = None) -> np.ndarray:
    """[P, D, K, V] float64 unigram logits of every client."""
    rng = np.random.default_rng([seed, 0])
    base = -skew * np.log(np.arange(1, vocab + 1, dtype=np.float64))
    edge = np.stack([hetero * base[rng.permutation(vocab)]
                     + (1.0 - hetero) * base for _ in range(pods)])
    out = np.broadcast_to(edge[:, None, None, :],
                          (pods, devices, clients, vocab)).copy()
    if alpha_client is not None and np.isfinite(alpha_client):
        mix = rng.dirichlet(np.full(vocab, float(alpha_client)),
                            size=(pods, devices, clients))
        out += np.log(np.maximum(mix * vocab, 1e-20))
    return out


def make_pool(vocab: int, pods: int, devices: int, clients: int,
              batch_per_device: int, seq_len: int, n_batches: int,
              seed: int, skew: float = 1.2, hetero: float = 1.0,
              alpha_client: float | None = None) -> np.ndarray:
    """[n_batches, P, D, b, L] int32 token batches, one per step."""
    if batch_per_device % clients:
        raise ValueError(f"batch {batch_per_device} does not divide into "
                         f"{clients} clients")
    logits = client_logits(vocab, pods, devices, clients, seed, skew,
                           hetero, alpha_client)
    probs = np.exp(logits - logits.max(axis=-1, keepdims=True))
    cdf = np.cumsum(probs, axis=-1)
    cdf /= cdf[..., -1:]
    rows = batch_per_device // clients
    rng = np.random.default_rng([seed, 1])
    u = rng.random((n_batches, pods, devices, clients, rows * seq_len))
    toks = np.empty(u.shape, np.int32)
    for q in range(pods):
        for d in range(devices):
            for c in range(clients):
                toks[:, q, d, c] = np.searchsorted(cdf[q, d, c],
                                                   u[:, q, d, c])
    np.minimum(toks, vocab - 1, out=toks)
    return toks.reshape(n_batches, pods, devices, batch_per_device,
                        seq_len)
