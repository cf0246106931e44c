"""One generator for every traffic mix: pools of requests from a data file.

A mix file gives two log-normal length distributions (``prompt`` and
``output``: median, sigma, and the clip range) and the pool size as requests
per serving slot (``pool_per_slot``).  Every pool holds the same set of
(prompt, output) lengths: the stratified quantiles of the two distributions,
paired by a permutation fixed by the mix (``pairing_seed``).  The pool's
index chooses their order, the same in every run; the run's seed chooses the
token ids.  So every seed asks for the same work, in the same order.
"""

from __future__ import annotations

import statistics

import numpy as np

from repro.serve.engine import Request


def _lengths(dist: dict, n: int) -> np.ndarray:
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = dist["median"] * np.exp(dist["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def pool_sizes(mix: dict, slots: int) -> list[tuple[int, int]]:
    """The (prompt length, output length) pairs of one pool."""
    n = int(mix["pool_per_slot"] * slots)
    prompts = _lengths(mix["prompt"], n)
    outputs = _lengths(mix["output"], n)
    pairing = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[pairing])]


def pool(mix: dict, slots: int, vocab: int, seed: int, index: int,
         first_rid: int) -> list[Request]:
    """Pool ``index`` of a run with ``seed``: the mix's sizes in the order
    of pool ``index``, with token ids drawn from (seed, index)."""
    sizes = pool_sizes(mix, slots)
    order = np.random.default_rng([mix["pairing_seed"], int(index)]).permutation(len(sizes))
    rng = np.random.default_rng([int(seed), int(index)])
    return [
        Request(rid=first_rid + i,
                prompt=rng.integers(0, vocab, sizes[j][0]).tolist(),
                max_new_tokens=sizes[j][1])
        for i, j in enumerate(order)
    ]
