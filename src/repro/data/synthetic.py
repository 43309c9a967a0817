"""Deterministic synthetic datasets mirroring the paper's evaluation data.

The paper evaluates on GENE/LRS (logistic regression), FOREST/KMS (K-means),
NETFLIX/NMFS (NMF), LJ/FRIEND (PageRank) plus we add LM token streams for the
assigned transformer architectures.  Everything is generated deterministically
from a seed so checkpoint/restart reproduces the exact stream (stateless,
index-addressable — the FT layer only persists the step counter).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp


# -- logistic regression (GENE / LRS analogues) ------------------------------


def logreg_dataset(n_rows: int, n_features: int, seed: int = 0, noise: float = 0.1):
    """Linearly-separable-ish binary data with a known ground-truth theta."""
    rng = np.random.default_rng(seed)
    theta_true = rng.normal(size=(n_features,)).astype(np.float32)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    logits = x @ theta_true + noise * rng.normal(size=(n_rows,)).astype(np.float32)
    y = (1 / (1 + np.exp(-logits)) > 0.5).astype(np.float32)
    return x, y, theta_true


# -- K-means (FOREST / KMS analogues) -----------------------------------------


def kmeans_dataset(n_rows: int, n_features: int, k: int, seed: int = 0, spread: float = 0.15):
    """Gaussian blobs around k well-separated centers."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-1, 1, size=(k, n_features)).astype(np.float32)
    assign = rng.integers(0, k, size=(n_rows,))
    x = centers[assign] + spread * rng.normal(size=(n_rows, n_features)).astype(np.float32)
    return x.astype(np.float32), centers, assign


# -- NMF (NETFLIX / NMFS analogues) -------------------------------------------


def nmf_dataset(n_rows: int, n_cols: int, rank: int, seed: int = 0, noise: float = 0.01):
    """Non-negative low-rank matrix R ≈ P·Q plus noise."""
    rng = np.random.default_rng(seed)
    p = np.abs(rng.normal(size=(n_rows, rank))).astype(np.float32)
    q = np.abs(rng.normal(size=(rank, n_cols))).astype(np.float32)
    r = p @ q + noise * np.abs(rng.normal(size=(n_rows, n_cols))).astype(np.float32)
    return r.astype(np.float32), p, q


# -- PageRank (LJ / FRIEND analogues) ------------------------------------------


def powerlaw_graph(n_vertices: int, avg_degree: int = 8, seed: int = 0):
    """Preferential-attachment-flavoured directed edge list (src, dst)."""
    rng = np.random.default_rng(seed)
    n_edges = n_vertices * avg_degree
    # Zipf-ish destination popularity, uniform sources — cheap power-law proxy.
    dst_pop = rng.zipf(1.6, size=n_edges) % n_vertices
    src = rng.integers(0, n_vertices, size=n_edges)
    edges = np.stack([src, dst_pop], axis=1).astype(np.int32)
    return edges


def rmat_graph(n_vertices: int, n_edges: int, seed: int = 0):
    """Graph500 R-MAT edge list (src, dst) with ``n_edges`` edges.

    Each edge picks one quadrant of the adjacency matrix per bit of a
    ``2^scale >= n_vertices`` id space with probabilities (a, b, c, 1-a-b-c)
    — Graph500's (0.57, 0.19, 0.19, 0.05), whose in-degree skew tops out
    near 1% of edges per vertex, so f32 credit sums stay accurate.  Vertex
    ids are permuted at random, as Graph500 does, then folded onto
    ``n_vertices``.
    """
    a, b, c = 0.57, 0.19, 0.19
    rng = np.random.default_rng(seed)
    scale = max(1, int(n_vertices - 1).bit_length())
    src = np.zeros(n_edges, np.int32)
    dst = np.zeros(n_edges, np.int32)
    for _ in range(scale):
        r = rng.random(n_edges, dtype=np.float32)
        lower = r >= a + b                                   # quadrants C, D
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # quadrants B, D
        src = (src << 1) | lower
        dst = (dst << 1) | right
    perm = (rng.permutation(1 << scale) % n_vertices).astype(np.int32)
    return np.stack([perm[src], perm[dst]], axis=1)


# -- LM token streams ----------------------------------------------------------


def lm_batch(step: int, global_batch: int, seq_len: int, vocab: int, seed: int = 0):
    """Index-addressable synthetic token batch: batch(step) is pure in (seed, step)."""
    rng = np.random.default_rng(np.uint64(seed * 1_000_003 + step))
    tokens = rng.integers(0, vocab, size=(global_batch, seq_len + 1), dtype=np.int32)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}


@dataclass
class SyntheticLM:
    """Stateless LM stream; restart(step) is exact by construction."""

    global_batch: int
    seq_len: int
    vocab: int
    seed: int = 0

    def batch(self, step: int):
        return lm_batch(step, self.global_batch, self.seq_len, self.vocab, self.seed)
