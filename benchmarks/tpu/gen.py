"""Traffic and calibration data, made from ``--seed`` by the benchmark.

One general generator reads every traffic file; a mix is its parameters.

Serving.  A mix fixes a *pool* of request sizes and inter-arrival gaps,
drawn once, in order, from the mix's own ``pool_seed``; ``--seed`` draws
the prompt tokens (and the weights).  So every seed serves the same
sizes at the same times, and runs on different seeds differ by content,
not by the amount or the order of the work: with some twenty requests
in a window, the order alone moved the tails by up to half (PERF.md).
Prompt lengths snap to the mix's ``prompt_grid``: the server compiles one
prefill program per prompt length, and every length the window sends has
to be warmed up in set-up.

* ``open``: Poisson arrivals in wall time at ``rate`` requests per
  second; the pre-roll and the window each have a pool of their own,
  whose gaps are scaled to span that part exactly.
* ``backlog``: a closed backlog; the driver refills it whenever fewer
  than ``backlog_per_slot`` x slots requests wait, so it never empties.

Calibration.  A copy of the repo's seeded Zipf corpus with second-order
Markov mixing (``zipf_corpus``): the calibration traffic of the quantizer
cells, kept here so that a change to the program cannot change it.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due: float          # seconds after the start of the run (open loop)
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _lognormal(rng, median: float, sigma: float, lo: int, hi: int, n: int):
    x = np.exp(math.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def snap(lengths: np.ndarray, grid) -> np.ndarray:
    """Each length to the grid point nearest in log space."""
    g = np.asarray(sorted(grid), np.float64)
    idx = np.abs(np.log(lengths[:, None]) - np.log(g[None, :])).argmin(1)
    return g[idx].astype(np.int64)


def size_pool(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of the mix's fixed pool of ``n``
    requests (independent of ``--seed``)."""
    rng = np.random.default_rng(mix["pool_seed"])
    p, o = mix["prompt"], mix["output"]
    prompts = snap(_lognormal(rng, p["median"], p["sigma"], p["min"],
                              p["max"], n), mix["prompt_grid"])
    outs = _lognormal(rng, o["median"], o["sigma"], o["min"], o["max"], n)
    return prompts, outs


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send."""
    return sorted(int(x) for x in mix["prompt_grid"])


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    # ids 0/1 are left out as bos/pad, like the calibration corpus
    return rng.integers(2, vocab, size=n, dtype=np.int64).astype(np.int32)


def _poisson_pool(mix: dict, pool_seed: int, n: int, span_s: float):
    """n request sizes and n inter-arrival gaps that sum to ``span_s``."""
    prompts, outs = size_pool(dict(mix, pool_seed=pool_seed), n)
    gaps = np.random.default_rng(pool_seed + 1).exponential(1.0, n)
    return prompts, outs, gaps * (span_s / gaps.sum())


def open_loop(mix: dict, seed: int, preroll_s: float, window_s: float,
              vocab: int, rate: float) -> list[Request]:
    """Open-loop schedule at ``rate`` req/s: a pre-roll of ``preroll_s``
    seconds, then the window.  Each part sends its own fixed pool in the
    order it was drawn, so every seed sends the same schedule; the seed
    draws the prompts' tokens."""
    rng = np.random.default_rng(seed)
    out, t0 = [], 0.0
    for pool_seed, span in ((mix["pool_seed"] + 100, preroll_s),
                            (mix["pool_seed"], window_s)):
        n = int(round(rate * span))
        if n == 0:
            t0 += span
            continue
        prompts, outs, gaps = _poisson_pool(mix, pool_seed, n, span)
        due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        out += [Request(float(due[k]), _tokens(rng, int(prompts[k]), vocab),
                        int(outs[k])) for k in range(n)]
        t0 += span
    return out


def backlog(mix: dict, seed: int, n: int, vocab: int) -> list[Request]:
    """``n`` requests for a closed backlog, in the order they are sent."""
    prompts, outs = size_pool(mix, n)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    return [Request(0.0, _tokens(rng, int(prompts[k]), vocab), int(outs[k]))
            for k in order]


# ------------------------------------------------------------ calibration


def zipf_corpus(key, batch: int, seq_len: int, vocab: int, *,
                alpha: float = 1.2, markov_strength: float = 0.75,
                n_topics: int = 4, table_seed: int = 1):
    """(batch, seq_len) int32 calibration tokens, deterministic in ``key``.

    Zipf(alpha) unigrams by inverse CDF; a share ``markov_strength`` of
    positions is made predictable through fixed random permutations of
    the two previous tokens, and token 0 declares a topic that selects
    the permutation table, so attention concentrates on the first tokens
    as in natural text (the structure AttnCon importance reads)."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(key, 3)
    ranks = np.arange(1, max(vocab - 2, 1) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -alpha)
    cdf = jnp.asarray(cdf / cdf[-1], jnp.float32)
    u = jax.random.uniform(k1, (batch * seq_len,), jnp.float32)
    base = (jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                        cdf.shape[0] - 1) + 2).reshape(batch, seq_len)
    rs = np.random.RandomState(table_seed)
    p1 = jnp.asarray(np.stack([rs.permutation(vocab)
                               for _ in range(n_topics)]))
    p2 = jnp.asarray(rs.permutation(vocab))
    keep = jax.random.bernoulli(k2, 1.0 - markov_strength, (batch, seq_len))
    topic = jax.random.randint(k3, (batch,), 0, n_topics)
    topic_tok = (topic + 2).astype(jnp.int32)

    def step(carry, xs):
        t1, t2 = carry
        b, kp = xs
        det = (p1[topic, t1] + p2[t2]) % vocab
        tok = jnp.where(kp, b, det).astype(jnp.int32)
        return (tok, t1), tok

    _, toks = jax.lax.scan(step, (topic_tok, topic_tok),
                           (base[:, 1:].T.astype(jnp.int32), keep[:, 1:].T))
    return jnp.concatenate([topic_tok[:, None], toks.T], axis=1)
