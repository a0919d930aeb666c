"""Open-loop Poisson arrivals over a fleet of documents.

Parameters (the traffic file's ``params``, overridden key by key by the
cell's ``benchmark/cells/<cell>.json``):

- ``rate_ops_per_s``: the offered rate over the whole fleet, fixed, never
  searched for; written in the cell's file and nowhere else;
- ``doc_distribution``: ``"zipf"`` (weight of the k-th hottest document
  ``k ** -zipf_s``) or ``"uniform"``;
- ``cap_ops_per_s``: no document is offered more than this; the excess of a
  capped document is spread over the others by their weights (water-filling),
  so the fleet still gets ``rate_ops_per_s``;
- ``tick_s``: the generator's batching grain.  An op that arrives inside a
  tick is due at the tick's END, when everything that arrived for its
  document in the tick is submitted together (concurrent ops with real
  ref-seq lag).

Which document is the k-th hottest is a permutation drawn from the seed, so
hot documents are not neighbours in the fleet (or on one shard of a mesh).
The number of ops in ``seconds`` is fixed, ``round(rate * seconds)``: a
Poisson process conditioned on its count is uniform order statistics, and a
fixed amount of work keeps runs comparable.
"""

from __future__ import annotations

import numpy as np


def doc_rates(params: dict, n_docs: int, seed: int) -> np.ndarray:
    rate = float(params["rate_ops_per_s"])
    cap = float(params.get("cap_ops_per_s", 0) or 0)
    if params["doc_distribution"] == "zipf":
        w = np.arange(1, n_docs + 1, dtype=np.float64) ** -float(
            params["zipf_s"])
    elif params["doc_distribution"] == "uniform":
        w = np.ones(n_docs)
    else:
        raise ValueError(
            f"doc_distribution {params['doc_distribution']!r} not known")
    if cap and cap * n_docs < rate:
        raise ValueError(f"{rate} ops/s cannot fit under a cap of {cap} "
                         f"on {n_docs} documents")
    capped = np.zeros(n_docs, bool)
    while True:
        free = rate - cap * capped.sum()
        r = np.where(capped, cap, free * w / w[~capped].sum())
        over = ~capped & (r > cap) if cap else np.zeros(n_docs, bool)
        if not over.any():
            break
        capped |= over
    perm = np.random.default_rng([seed, 0x2A]).permutation(n_docs)
    out = np.empty(n_docs)
    out[perm] = r
    return out


def schedule(params: dict, n_docs: int, seconds: float, seed: int,
             stream: int) -> tuple[np.ndarray, np.ndarray]:
    """``stream`` separates draws from one seed (warm-up and window)."""
    rates = doc_rates(params, n_docs, seed)
    rng = np.random.default_rng([seed, 0x5C, stream])
    n = int(round(float(params["rate_ops_per_s"]) * seconds))
    t = np.sort(rng.uniform(0.0, seconds, n))
    tick = np.floor(t / float(params["tick_s"])).astype(np.int64)
    doc = rng.choice(n_docs, size=n, p=rates / rates.sum())
    return tick, doc
