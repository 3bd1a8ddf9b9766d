"""Synthetic deterministic data pipelines.

Two generators:

* :func:`lm_batches` — a *learnable* token stream for the LM architectures:
  tokens follow a fixed random bigram automaton, so next-token entropy is far
  below uniform and the training loss visibly decreases within a few hundred
  steps (used by examples/byzantine_training.py).
* :func:`classification_batches` — a separable Gaussian-mixture
  classification task standing in for Fashion-MNIST in the Fig 3 reproduction
  (no datasets are shipped in this container; DESIGN.md §3 table).

Workers draw disjoint slices of each global batch, matching the paper's
i.i.d.-sampling assumption; per-worker batches are what the byzantine game
aggregates over.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _bigram_table(vocab: int, seed: int, branching: int = 4) -> np.ndarray:
    """Each token can be followed by `branching` successors (uniformly)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(vocab, branching), dtype=np.int32)


def make_lm_batch(key: Array, vocab: int, batch: int, seq: int,
                  seed: int = 1234) -> Dict[str, Array]:
    """One (tokens, labels) batch from the bigram automaton."""
    table = jnp.asarray(_bigram_table(vocab, seed))
    k0, k1 = jax.random.split(key)
    start = jax.random.randint(k0, (batch,), 0, vocab, dtype=jnp.int32)
    choices = jax.random.randint(k1, (batch, seq), 0, table.shape[1],
                                 dtype=jnp.int32)

    def step(tok, choice):
        nxt = table[tok, choice]
        return nxt, nxt

    _, seqs = jax.lax.scan(
        lambda c, ch: step(c, ch), start, choices.T)
    toks = jnp.concatenate([start[:, None], seqs.T], axis=1)  # (B, S+1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def lm_batches(vocab: int, batch: int, seq: int, *, seed: int = 0
               ) -> Iterator[Dict[str, Array]]:
    # one compiled generator for the stream: called eagerly, the scan's
    # fresh body closure would compile again for every batch
    make = jax.jit(functools.partial(make_lm_batch, vocab=vocab, batch=batch,
                                     seq=seq, seed=seed + 77))
    step = 0
    while True:
        yield make(jax.random.fold_in(jax.random.key(seed), step))
        step += 1


# --------------------------------------------------------------- non-IID
def dirichlet_mixture(key: Array, n_workers: int, n_domains: int,
                      alpha: float) -> Array:
    """Per-worker Dirichlet(α) mixture over data domains -> (n_workers, K).

    Small α concentrates each worker on few domains (strong heterogeneity,
    the regime where coordinate-wise rules degrade — Yin et al. 2018);
    α → ∞ recovers i.i.d. workers.  Rows sum to 1.
    """
    if n_domains < 1:
        raise ValueError(f"n_domains must be >= 1, got {n_domains}")
    if alpha <= 0:
        raise ValueError(f"alpha must be positive, got {alpha}")
    return jax.random.dirichlet(
        key, jnp.full((n_domains,), alpha, jnp.float32), (n_workers,))


def make_noniid_lm_batch(key: Array, vocab: int, n_workers: int,
                         per_worker: int, seq: int, mixture: Array,
                         seed: int = 1234) -> Dict[str, Array]:
    """Worker-heterogeneous LM batch: ``(n_workers*per_worker, S)`` tokens.

    Domain k is its own bigram automaton (table seeded ``seed + k``); each
    of worker w's rows samples a domain from ``mixture[w]`` and walks that
    domain's automaton.  Row-major worker order, so ``split_workers`` with
    the same ``n_workers`` recovers the per-worker batches.  Deterministic
    in ``(key, mixture, seed)`` and jit-friendly (tables are constants).
    """
    n_domains = mixture.shape[1]
    if mixture.shape[0] != n_workers:
        raise ValueError(
            f"mixture rows ({mixture.shape[0]}) != n_workers ({n_workers})")
    tables = jnp.asarray(np.stack(
        [_bigram_table(vocab, seed + k) for k in range(n_domains)]))
    rows = n_workers * per_worker
    kd, k0, k1 = jax.random.split(key, 3)
    row_logits = jnp.repeat(jnp.log(mixture + 1e-20), per_worker, axis=0)
    domains = jax.random.categorical(kd, row_logits, axis=-1)      # (rows,)
    start = jax.random.randint(k0, (rows,), 0, vocab, dtype=jnp.int32)
    choices = jax.random.randint(k1, (rows, seq), 0, tables.shape[2],
                                 dtype=jnp.int32)

    def step(tok, choice):
        nxt = tables[domains, tok, choice]
        return nxt, nxt

    _, seqs = jax.lax.scan(step, start, choices.T)
    toks = jnp.concatenate([start[:, None], seqs.T], axis=1)       # (rows, S+1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def classification_batches(d_in: int, n_classes: int, batch: int, *,
                           seed: int = 0, noise: float = 1.0,
                           center_seed: int = 7777
                           ) -> Iterator[Tuple[Array, Array]]:
    """Gaussian mixture: class c centred at a fixed random unit vector.

    ``center_seed`` fixes the mixture itself — train and test iterators must
    share it (only ``seed`` varies the sampling stream), otherwise they are
    different tasks.
    """
    rng = np.random.default_rng(center_seed)
    centers = rng.normal(size=(n_classes, d_in)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    centers = jnp.asarray(centers) * 2.0
    step = 0
    while True:
        key = jax.random.fold_in(jax.random.key(seed + 1), step)
        kx, ky = jax.random.split(key)
        labels = jax.random.randint(ky, (batch,), 0, n_classes, dtype=jnp.int32)
        x = centers[labels] + noise * jax.random.normal(kx, (batch, d_in))
        yield x, labels
        step += 1
