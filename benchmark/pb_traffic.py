"""The one traffic generator. A traffic mix is a data file of parameters
under ``benchmark/traffic/``; everything drawn comes from ``--seed``.

Every seed gets the same multiset of sizes: the pool of (prompt, output)
lengths is drawn from the mix's own ``pool_seed``, and the run's seed only
orders it (anew for every pass over the pool) and draws the token ids. So
two seeds do the same work in another order, and a window that takes in
several passes does nearly the same work whatever the seed."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """The log-normal's ``n`` evenly spaced quantiles, in a drawn order: a
    small pool then holds the whole shape, tails included."""
    if spec["dist"] != "lognormal_quantiles":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = rng.permutation(np.exp(np.log(spec["median"])
                               + spec["sigma"] * np.asarray(z)))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_pool(traffic: dict):
    """``(prompt_lens, output_lens)`` of the mix's fixed pool."""
    rng = np.random.default_rng(traffic["pool_seed"])
    n = traffic["pool_size"]
    return (_lengths(traffic["prompt"], n, rng),
            _lengths(traffic["output"], n, rng))


def requests(traffic: dict, seed: int, vocab: int):
    """An endless iterator of ``(prompt token ids, max_new_tokens)``: the
    pool in an order drawn from ``seed``, cycled, with fresh uniform token
    ids for every request."""
    p_lens, o_lens = request_pool(traffic)
    rng = np.random.default_rng(seed)
    lo = traffic.get("token_min", 1)
    while True:
        for i in rng.permutation(len(p_lens)):
            yield (rng.integers(lo, vocab, size=int(p_lens[i])).tolist(),
                   int(o_lens[i]))


def corpus(traffic: dict, seed: int, n_steps: int) -> np.ndarray:
    """A token stream cut into ``batch`` lanes, ``[positions, batch]`` as the
    tutorial's ``batchify`` lays it out, long enough for ``n_steps`` steps of
    ``seq`` positions. Word types follow a Zipf law over ``types`` ids, so a
    model can learn it and no two rows agree."""
    c = traffic["corpus"]
    if c["dist"] != "zipf":
        raise ValueError(f"unknown corpus distribution {c['dist']!r}")
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, c["types"] + 1, dtype=np.float64)
    probs = ranks ** -float(c.get("exponent", 1.0))
    probs /= probs.sum()
    batch, seq = traffic["batch"], traffic["seq"]
    positions = n_steps * seq + 1
    ids = rng.choice(c["types"], size=positions * batch, p=probs)
    return ids.astype(np.int32).reshape(batch, positions).T.copy()
