"""Deterministic synthetic corpus (offline container: no WikiText).

A Zipf-distributed unigram stream is made *learnable* by a second-order
Markov mixing step: token t depends on (t-1, t-2) through fixed random
permutations, so a trained LM achieves far-below-unigram perplexity and
quantization damage is measurable — the property the paper's benchmarks
need (PPL orderings, not absolute values).

``n_topics > 1`` makes the first token(s) *globally important*: the sample's
topic (declared by token 0) selects which permutation table drives the
Markov structure, so a model must attend to the sequence start from every
position — recreating the attention-concentration-on-initial-tokens
phenomenon (StreamingLLM / Sun et al.) that RSQ's chunk observation and
AttnCon strategy exploit.  Without it, a purely local corpus cannot exhibit
the paper's "important token" structure at all.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def zipf_tokens(key, shape, vocab: int, alpha: float = 1.2) -> jax.Array:
    """Zipf(alpha) token ids in [2, vocab) (0/1 reserved bos/pad).

    Inverse-CDF sampling: one uniform per token and a binary search of the
    (vocab,) CDF.  (Gumbel-max over the vocabulary would hold a
    (tokens, vocab) array — 16 GB for 16 x 2048 tokens of a 128k vocab.)"""
    ranks = np.arange(1, max(vocab - 2, 1) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -alpha)
    cdf = jnp.asarray(cdf / cdf[-1], jnp.float32)
    u = jax.random.uniform(key, (int(np.prod(shape)),), jnp.float32)
    flat = jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       cdf.shape[0] - 1)
    return (flat + 2).reshape(shape).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class SyntheticCorpus:
    vocab_size: int
    seed: int = 0
    alpha: float = 1.2
    markov_strength: float = 0.75  # fraction of positions made predictable
    n_topics: int = 4  # topic (token 0) selects the transition table

    def _perms(self):
        rng = np.random.RandomState(self.seed + 1)
        p1 = np.stack([rng.permutation(self.vocab_size)
                       for _ in range(max(self.n_topics, 1))])
        p2 = rng.permutation(self.vocab_size)
        return jnp.asarray(p1), jnp.asarray(p2)

    def sample(self, key, batch: int, seq_len: int) -> jax.Array:
        """(batch, seq_len) int32, deterministic in (seed, key)."""
        k1, k2, k3 = jax.random.split(key, 3)
        base = zipf_tokens(k1, (batch, seq_len), self.vocab_size, self.alpha)
        p1, p2 = self._perms()
        keep = jax.random.bernoulli(k2, 1.0 - self.markov_strength,
                                    (batch, seq_len))
        n_t = max(self.n_topics, 1)
        topic = jax.random.randint(k3, (batch,), 0, n_t)
        # token 0 declares the topic (reserved ids [2, 2 + n_topics))
        topic_tok = (topic + 2).astype(jnp.int32)

        def step(carry, xs):
            t1, t2 = carry
            b, kp = xs
            det = (p1[topic, t1] + p2[t2]) % self.vocab_size
            tok = jnp.where(kp, b, det).astype(jnp.int32)
            return (tok, t1), tok

        (_, _), toks = jax.lax.scan(
            step, (topic_tok, topic_tok),
            (base[:, 1:].swapaxes(0, 1), keep[:, 1:].swapaxes(0, 1)))
        return jnp.concatenate([topic_tok[:, None], toks.swapaxes(0, 1)],
                               axis=1)

    def sample_indexed(self, key, indices, seq_len: int) -> jax.Array:
        """(len(indices), seq_len) int32 — sample rows *by global index*.

        Row ``i`` depends only on ``(key, indices[i])``: sampling any subset
        of indices yields exactly the corresponding rows of the full set.
        This is the per-sample determinism contract the sharded calibration
        loader builds on (data/calibration.py): each data-parallel group
        materializes only its own disjoint index slice, and the union over
        groups is bit-identical to the single-host global draw."""
        indices = jnp.asarray(indices, jnp.int32)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(indices)
        return jax.vmap(lambda k: self.sample(k, 1, seq_len)[0])(keys)

    def batches(self, batch: int, seq_len: int, n_steps: int,
                start_step: int = 0):
        """Deterministic, seekable iterator — the data-side contract that
        makes checkpoint-resume exact and host-local (no cross-host I/O
        dependency -> no data-induced stragglers)."""
        for step in range(start_step, n_steps):
            key = jax.random.fold_in(jax.random.key(self.seed), step)
            toks = self.sample(key, batch, seq_len)
            yield {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
