"""k-means cells: data, job, compulsory work, plain reference and control.

The job is the public entry point, ``repro.analytics.kmeans.fit`` with the
``kmeans_assign`` Pallas kernel, on a Session the harness builds.  The
reference and the control below import nothing from ``repro``: they restate
Lloyd's algorithm from its definition.

The data is made so that Lloyd's rounds settle: the first centres that
``fit`` draws (rows chosen by ``default_rng(init_seed)``, a fixed seed, as
sklearn's single init with a fixed ``random_state``) are set to the blob
centres, one in each blob, so every centre settles within a few rounds and
every centre is compared.  The blob centres come from a fixed key and the
first centres with them, so every ``--seed`` runs the same program; the seed
draws the points.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the Pallas kernel on the job path: its name in the device trace, and how
# many times one round calls it
KERNELS = {"kmeans_assign": {"calls_per_round": 1}}


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@partial(jax.jit, static_argnames=("d", "spread"))
def blobs(key, centres_key, first, others, rest, *, d: int, spread: float):
    """``(n, d)`` f32 Gaussian blobs around ``k`` centres in [-1, 1]^d, made
    on the device.  Row ``first[j]`` is blob ``j``'s centre itself; the rows
    ``others`` take the blob labels ``rest`` in an order drawn from ``key``."""
    k_lab, k_noise = jax.random.split(key)
    k, n = first.shape[0], first.shape[0] + others.shape[0]
    centres = jax.random.uniform(centres_key, (k, d), jnp.float32, -1.0, 1.0)
    labels = (jnp.zeros((n,), jnp.int32)
              .at[others].set(jax.random.permutation(k_lab, rest))
              .at[first].set(jnp.arange(k, dtype=jnp.int32)))
    noise = spread * jax.random.normal(k_noise, (n, d), jnp.float32)
    return centres[labels] + noise.at[first].set(0.0)


def first_rows(n: int, k: int, init_seed: int) -> np.ndarray:
    """The rows ``fit(..., seed=init_seed)`` takes as first centres: k
    distinct rows drawn by numpy's ``default_rng(init_seed).choice``."""
    return np.random.default_rng(init_seed).choice(n, k, replace=False)


def make_data(cfg: dict, seed: int) -> dict:
    sizes = tuple(cfg["class_sizes"])
    n, k = sum(sizes), cfg["k"]
    first = first_rows(n, k, cfg["init_seed"])
    others = np.setdiff1d(np.arange(n, dtype=np.int32), first)
    rest = np.repeat(np.arange(k, dtype=np.int32), np.asarray(sizes) - 1)
    x = blobs(seed_key(seed), jax.random.key(cfg["centres_seed"]), first, others, rest,
              d=cfg["n_features"], spread=cfg["blob_spread"])
    return {"x": x.block_until_ready()}


def run_job(data: dict, cfg: dict, seed: int, session) -> np.ndarray:
    from repro.analytics import kmeans
    centres, _ = kmeans.fit(data["x"], cfg["k"], iters=cfg["iters"], seed=cfg["init_seed"],
                            use_kernel=True, session=session)
    return centres


def rounds(cfg: dict) -> int:
    return cfg["iters"]


def _assign_work(n: int, d: int, k: int) -> dict:
    """One assignment pass: read the points and centres, write an index and a
    distance per point; a multiply-add per point, centre and feature."""
    return {"bytes": 4 * (n * d + k * d + 2 * n), "flops": 2 * n * d * k}


def round_work(cfg: dict) -> dict:
    """Bytes and operations one Lloyd round must move and do: one assignment
    pass, then the per-cluster sums, which read the points once more and add
    each into its cluster.  Writing k * (d + 1) sums is negligible and left
    out."""
    n, d, k = sum(cfg["class_sizes"]), cfg["n_features"], cfg["k"]
    assign = _assign_work(n, d, k)
    return {"bytes": assign["bytes"] + 4 * (n * d + n), "flops": assign["flops"] + n * d}


def kernel_work(cfg: dict, kernel: str) -> dict:
    """Compulsory bytes and operations of one call of ``kernel``."""
    if kernel != "kmeans_assign":
        raise KeyError(kernel)
    return _assign_work(sum(cfg["class_sizes"]), cfg["n_features"], cfg["k"])


def to_host(data: dict) -> dict:
    return {"x": np.asarray(jax.device_get(data["x"]))}


def reference(host: dict, cfg: dict, seed: int) -> dict:
    """Lloyd's algorithm in float64 on the host.  An empty cluster's centre
    becomes 0 (its sum over a count floored at 1), as the program's does."""
    x = host["x"].astype(np.float64)
    k = cfg["k"]
    x2 = np.sum(x * x, axis=1, keepdims=True)
    centres = x[first_rows(x.shape[0], k, cfg["init_seed"])]
    for _ in range(cfg["iters"]):
        assign = np.argmin(x2 - 2.0 * x @ centres.T + np.sum(centres**2, axis=1), axis=1)
        onehot = np.zeros((x.shape[0], k))
        onehot[np.arange(x.shape[0]), assign] = 1.0
        centres = (onehot.T @ x) / np.maximum(onehot.sum(axis=0), 1.0)[:, None]
    return {"centres": centres}


def _dot_bf16(a, b):
    """``a @ b`` as Precision.DEFAULT computes f32 operands on a TPU, spelt
    out so that it rounds the same on every backend: both operands rounded
    to bfloat16, products accumulated in f32."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def control(data: dict, cfg: dict, seed: int) -> np.ndarray:
    """The reference in the program's place, on the device, with its matmuls
    at Precision.DEFAULT (one bfloat16 pass): one precision step below the
    program's float32 at HIGHEST."""
    k = cfg["k"]
    x = data["x"]
    c0 = x[first_rows(x.shape[0], k, cfg["init_seed"])]

    @jax.jit
    def run(x, centres):
        x2 = jnp.sum(x * x, axis=1, keepdims=True)

        def step(_, c):
            d2 = x2 - 2.0 * _dot_bf16(x, c.T) + jnp.sum(c * c, axis=1)[None]
            onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=jnp.float32)
            sums = _dot_bf16(onehot.T, x)
            return sums / jnp.maximum(jnp.sum(onehot, axis=0), 1.0)[:, None]
        return jax.lax.fori_loop(0, cfg["iters"], step, centres)

    return np.asarray(run(x, c0))


def compare(result, ref: dict, cfg: dict) -> dict:
    """``centres_gap``: the widest gap of any centre's coordinate to the
    reference, over the reference's largest coordinate."""
    want = ref["centres"]
    got = np.asarray(result, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"centres_gap": float("inf")}
    return {"centres_gap": float(np.max(np.abs(got - want)) / np.max(np.abs(want)))}
