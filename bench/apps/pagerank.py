"""PageRank cells: data, job, compulsory work, plain reference and control.

The job is the public entry point, ``repro.analytics.pagerank.fit``, on a
Session the harness builds.  The reference and the control below import
nothing from ``repro``: they restate PageRank from its definition.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

KERNELS: dict = {}     # no Pallas kernel on this app's job path


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def rmat_bits(key, *, scale: int, n_edges: int, abc: tuple):
    """Graph500 R-MAT ids before relabelling: per bit of a ``2**scale`` id
    space each edge picks one quadrant of the adjacency matrix with
    probabilities (a, b, c, 1-a-b-c)."""
    a, b, c = abc

    def level(i, sd):
        src, dst = sd
        r = jax.random.uniform(jax.random.fold_in(key, i), (n_edges,))
        lower = r >= a + b                                    # quadrants C, D
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)   # quadrants B, D
        return (src << 1) | lower, (dst << 1) | right

    zeros = jnp.zeros((n_edges,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zeros, zeros))


def sizes(cfg: dict) -> tuple:
    """(vertices, edges) of a Graph 500 graph: ``2**scale`` and
    ``edgefactor * 2**scale``."""
    return 1 << cfg["scale"], cfg["edgefactor"] << cfg["scale"]


@partial(jax.jit, static_argnames=("scale", "n_edges", "abc"))
def rmat_edges(key, *, scale: int, n_edges: int, abc: tuple):
    """Graph 500 edge list ``(n_edges, 2)`` int32 over ``2**scale`` vertices,
    made on the device, with vertex labels randomly permuted as the
    specification's generator does."""
    k_bits, k_perm = jax.random.split(key)
    src, dst = rmat_bits(k_bits, scale=scale, n_edges=n_edges, abc=abc)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    return jnp.stack([perm[src], perm[dst]], axis=1)


def make_data(cfg: dict, seed: int) -> dict:
    edges = rmat_edges(seed_key(seed), scale=cfg["scale"], n_edges=sizes(cfg)[1],
                       abc=tuple(cfg["rmat_abc"]))
    return {"edges": edges.block_until_ready()}


def run_job(data: dict, cfg: dict, seed: int, session) -> np.ndarray:
    from repro.analytics import pagerank
    ranks, _ = pagerank.fit(data["edges"], sizes(cfg)[0], iters=cfg["iters"],
                            mode=cfg["accum_mode"], session=session)
    return ranks


def rounds(cfg: dict) -> int:
    return cfg["iters"]


def round_work(cfg: dict) -> dict:
    """Bytes and operations one round must move and do, whatever implements it.

    Read every edge's two int32 ids; read the rank and out-degree vectors and
    write the credit vector once each (f32); one multiply and one add per
    edge.  Gathers and scatters that touch a vertex more than once are not
    compulsory and are not counted.
    """
    v, e = sizes(cfg)
    return {"bytes": 8 * e + 3 * 4 * v, "flops": 2 * e}


def to_host(data: dict) -> dict:
    return {"edges": np.asarray(jax.device_get(data["edges"]))}


def reference(host: dict, cfg: dict, seed: int) -> dict:
    """PageRank from its definition, in float64 on the host."""
    # contiguous native-width ids, converted once rather than in every round
    src, dst = (np.ascontiguousarray(host["edges"][:, i], dtype=np.intp) for i in (0, 1))
    v, d = sizes(cfg)[0], cfg["damping"]
    inv_deg = 1.0 / np.maximum(np.bincount(src, minlength=v), 1).astype(np.float64)
    ranks = np.full(v, 1.0 / v)
    for _ in range(cfg["iters"]):
        credits = np.bincount(dst, weights=(ranks * inv_deg)[src], minlength=v)
        ranks = (1 - d) / v + d * credits
    return {"ranks": ranks}


def control(data: dict, cfg: dict, seed: int) -> np.ndarray:
    """The reference in the program's place, in bfloat16 on the device: ranks,
    the credits sent along edges and their scatter-added sums held in
    bfloat16; out-degrees counted exactly."""
    v, d, bf = sizes(cfg)[0], cfg["damping"], jnp.bfloat16

    @jax.jit
    def run(edges):
        src, dst = edges[:, 0], edges[:, 1]
        deg = jnp.maximum(jnp.zeros((v,), jnp.int32).at[src].add(1), 1).astype(jnp.float32)

        def step(_, ranks):
            w = (ranks[src].astype(jnp.float32) / deg[src]).astype(bf)
            credits = jnp.zeros((v,), bf).at[dst].add(w)
            return ((1 - d) / v + d * credits).astype(bf)
        return jax.lax.fori_loop(0, cfg["iters"], step, jnp.full((v,), 1.0 / v, bf))

    return np.asarray(run(data["edges"]).astype(jnp.float32))


def compare(result, ref: dict, cfg: dict) -> dict:
    """``ranks_gap``: the widest gap to the reference over its largest rank."""
    want = ref["ranks"]
    got = np.asarray(result, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"ranks_gap": float("inf")}
    return {"ranks_gap": float(np.max(np.abs(got - want)) / np.max(np.abs(want)))}
