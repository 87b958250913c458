"""Token rows made on the device from the seed.

The distribution is the one ``repro.data.tokens.DomainSkewCorpus``
defines: ``n_domains`` domains, each a Zipf(``zipf_a``) over the
vocabulary re-ranked by its own permutation, the permutations drawn in
order from ``numpy.random.default_rng(corpus_seed)``. Node i draws each
row's domain from its row of Pi (``own_domain_share`` on domain
i mod n_domains, the rest spread evenly), and each token by inverse CDF.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def node_mix(traffic: dict) -> np.ndarray:
    """Pi: (nodes, n_domains), each node's domain mixture."""
    n, k = traffic["nodes"], traffic["n_domains"]
    own = traffic["own_domain_share"]
    pi = np.full((n, k), (1.0 - own) / (k - 1))
    pi[np.arange(n), np.arange(n) % k] = own
    return pi / pi.sum(1, keepdims=True)


def rank_probs(vocab: int, zipf_a: float) -> np.ndarray:
    """Probability of each rank, rank 0 the most frequent."""
    base = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_a)
    return base / base.sum()


def domain_tables(traffic: dict, vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """(cdf over ranks (vocab,), token of each rank per domain (K, vocab)).

    Domain k gives token v the probability of rank ``perm_k[v]``, so the
    token of rank r is ``argsort(perm_k)[r]``.
    """
    rng = np.random.default_rng(traffic["corpus_seed"])
    perms = np.stack(
        [rng.permutation(vocab) for _ in range(traffic["n_domains"])]
    )
    cdf = np.cumsum(rank_probs(vocab, traffic["zipf_a"]))
    return cdf.astype(np.float32), np.argsort(perms, axis=1).astype(np.int32)


def make_pool_fn(traffic: dict, vocab: int):
    """``pool(key) -> (pool_batches, nodes, rows_per_node, seq_len + 1)``
    int32 token rows; batch b of node i is rows[b, i]."""
    cdf, token_of_rank = domain_tables(traffic, vocab)
    log_pi = np.log(node_mix(traffic)).astype(np.float32)
    shape = (traffic["pool_batches"], traffic["nodes"], traffic["rows_per_node"])
    length = traffic["seq_len"] + 1

    def pool(key):
        k_dom, k_tok = jax.random.split(key)
        doms = jax.random.categorical(
            k_dom, jnp.asarray(log_pi)[None, :, None, :], shape=shape
        )
        u = jax.random.uniform(k_tok, shape + (length,), jnp.float32)
        c = jnp.asarray(cdf)
        ranks = jnp.minimum(jnp.searchsorted(c, u * c[-1], side="right"), vocab - 1)
        return jnp.asarray(token_of_rank)[doms[..., None], ranks]

    return pool
