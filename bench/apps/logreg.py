"""Sparse logistic regression cells: data, job, compulsory work, plain
reference and control.

The rows are hashed click-log rows in fixed-width ELL form: each of the
configuration's fields contributes one active feature, its value drawn from
the field's vocabulary with log-uniform (Zipf s = 1) frequency and hashed
with the field's number into ``n_features``; every value is 1.0.  Labels
come from a planted logistic model whose bias is solved for the
configuration's click rate.  Everything is made on the device from the seed.

The job is the public entry point, ``repro.analytics.logreg.fit`` on ELL
rows with mini-batch rounds, on a Session the harness builds.  The reference
and the control below import nothing from ``repro``: they restate mini-batch
gradient ascent from its definition, with the host backend's partition of
the rows among the threads.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the Pallas kernel on the job path, the host accumulator's sparsify→
# scatter-add of a pairs round: its HLO instruction's name in the device
# trace, and how many times a round that takes the pairs path calls it
KERNELS = {"fused_topk_scatter": {"calls_per_round": 1}}

BISECT_STEPS = 40       # halvings of the bias's bracket: far below f32's step


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def vocabularies(cfg: dict) -> tuple:
    """Each field's vocabulary size, the integer fields' bins first."""
    return (cfg["integer_bins"],) * cfg["integer_fields"] + tuple(cfg["categorical_vocab"])


def fmix32(h):
    """MurmurHash3's 32-bit finaliser, on uint32."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


@partial(jax.jit, static_argnames=("rows", "vocab", "n_features"))
def hashed_rows(key, *, rows: int, vocab: tuple, n_features: int):
    """``(rows, fields)`` int32 hashed feature indices: field ``f``'s value
    ``v = floor(V_f ** u) - 1`` for uniform ``u`` (P(v) ~ 1/(v+1)), hashed
    as ``fmix32(f * 2**24 + v) mod n_features``."""
    sizes = jnp.asarray(vocab, jnp.float32)
    u = jax.random.uniform(key, (rows, len(vocab)))
    v = jnp.floor(jnp.exp(u * jnp.log(sizes))) - 1.0
    v = jnp.clip(v, 0.0, sizes - 1.0).astype(jnp.uint32)
    field = jnp.arange(len(vocab), dtype=jnp.uint32) << 24
    return (fmix32(field | v) % jnp.uint32(n_features)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_features",))
def planted_labels(key, idx, *, n_features: int, weight_std: float, click_rate: float):
    """Labels of a planted logistic model: weights ``N(0, weight_std**2)``
    per feature, the bias bisected so that the mean click probability is
    ``click_rate``, then one Bernoulli draw per row."""
    k_w, k_y = jax.random.split(key)
    w = weight_std * jax.random.normal(k_w, (n_features,), jnp.float32)
    margin = jnp.sum(w[idx], axis=1)

    def halve(_, bracket):
        lo, hi = bracket
        mid = (lo + hi) / 2
        low = jnp.mean(jax.nn.sigmoid(margin + mid)) < click_rate
        return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

    lo, hi = jax.lax.fori_loop(0, BISECT_STEPS, halve, (jnp.float32(-20.0), jnp.float32(20.0)))
    p = jax.nn.sigmoid(margin + (lo + hi) / 2)
    return (jax.random.uniform(k_y, p.shape) < p).astype(jnp.float32)


def make_data(cfg: dict, seed: int) -> dict:
    k_rows, k_labels = jax.random.split(seed_key(seed))
    idx = hashed_rows(k_rows, rows=cfg["rows"], vocab=vocabularies(cfg),
                      n_features=cfg["n_features"])
    y = planted_labels(k_labels, idx, n_features=cfg["n_features"],
                       weight_std=cfg["planted_weight_std"], click_rate=cfg["click_rate"])
    val = jnp.ones(idx.shape, jnp.float32)
    return {"idx": idx, "val": val.block_until_ready(), "y": y.block_until_ready()}


def run_job(data: dict, cfg: dict, seed: int, session) -> np.ndarray:
    from repro.analytics import logreg
    if session.backend.n_threads != cfg["threads"]:
        raise ValueError(f"{cfg['threads']} threads configured, the session has "
                         f"{session.backend.n_threads}")
    theta, _ = logreg.fit((data["idx"], data["val"]), data["y"], n_features=cfg["n_features"],
                          batch=cfg["batch"], iters=cfg["iters"], lr=cfg["lr"],
                          mode=cfg["accum_mode"], session=session)
    return theta


def rounds(cfg: dict) -> int:
    return cfg["iters"]


def round_work(cfg: dict) -> dict:
    """Bytes and operations one round must move and do, whatever implements it.

    Read the round's rows once: per entry an int32 index and an f32 value,
    per row an f32 label; per entry a multiply and an add for the margin and
    a multiply and an add for the gradient.  The dense length-``n_features``
    vectors (theta, the threads' gradients, their sum) need not be read or
    written whole and are not counted.
    """
    rows, width = cfg["threads"] * cfg["batch"], len(vocabularies(cfg))
    return {"bytes": rows * (width * 8 + 4), "flops": 4 * rows * width}


def kernel_work(cfg: dict, kernel: str) -> dict:
    """Compulsory bytes and operations of one call of ``kernel``: the fused
    sparsify→scatter-add reads the threads' stacked gradients, writes their
    sum, and adds each thread's gradient into it."""
    if kernel != "fused_topk_scatter":
        raise KeyError(kernel)
    n, v = cfg["threads"], cfg["n_features"]
    return {"bytes": 4 * (n + 1) * v, "flops": (n - 1) * v}


def to_host(data: dict) -> dict:
    return {name: np.asarray(jax.device_get(data[name])) for name in ("idx", "val", "y")}


def partition_starts(n_rows: int, n_threads: int) -> list:
    """Where each thread's contiguous rows start: the remainder of an uneven
    split goes one row each to the lowest threads."""
    per, extra = divmod(n_rows, n_threads)
    return [t * per + min(t, extra) for t in range(n_threads)]


def batch_rows(n_rows: int, cfg: dict, i: int):
    """Round ``i``'s rows: each thread's ``[i·batch, (i+1)·batch)``."""
    b = cfg["batch"]
    return [slice(s + i * b, s + (i + 1) * b) for s in partition_starts(n_rows, cfg["threads"])]


def reference(host: dict, cfg: dict, seed: int) -> dict:
    """Mini-batch gradient ascent on the log-likelihood, in float64 on the
    host: each round, the threads' batches at one theta, their gradients
    summed, ``theta += lr * sum``."""
    idx, val, y = host["idx"], host["val"], host["y"]
    theta = np.zeros(cfg["n_features"])
    for i in range(cfg["iters"]):
        rows = batch_rows(len(y), cfg, i)
        ii = np.concatenate([idx[r] for r in rows]).astype(np.intp)
        vv = np.concatenate([val[r] for r in rows]).astype(np.float64)
        yy = np.concatenate([y[r] for r in rows]).astype(np.float64)
        residual = yy - 1.0 / (1.0 + np.exp(-np.sum(theta[ii] * vv, axis=1)))
        theta += cfg["lr"] * np.bincount(ii.ravel(), weights=(residual[:, None] * vv).ravel(),
                                         minlength=cfg["n_features"])
    return {"theta": theta}


def control(data: dict, cfg: dict, seed: int) -> np.ndarray:
    """The reference in the program's place, on the device, with the margins
    and the gradient's values held in bfloat16 (theta in float32)."""
    bf, b = jnp.bfloat16, cfg["batch"]
    starts = partition_starts(data["y"].shape[0], cfg["threads"])

    @jax.jit
    def run(idx, val, y):
        def take(a, i):
            return jnp.concatenate([jax.lax.dynamic_slice_in_dim(a, s + i * b, b)
                                    for s in starts])

        def step(i, theta):
            ii, vv, yy = take(idx, i), take(val, i), take(y, i)
            margin = jnp.sum((theta[ii] * vv).astype(bf), axis=1, dtype=bf)
            residual = (yy - jax.nn.sigmoid(margin.astype(jnp.float32))).astype(bf)
            grad = jnp.zeros((cfg["n_features"],), bf).at[ii].add(residual[:, None] * vv.astype(bf))
            return theta + cfg["lr"] * grad.astype(jnp.float32)
        return jax.lax.fori_loop(0, cfg["iters"], step,
                                 jnp.zeros((cfg["n_features"],), jnp.float32))

    return np.asarray(run(data["idx"], data["val"], data["y"]))


def compare(result, ref: dict, cfg: dict) -> dict:
    """``theta_gap``: the widest gap to the reference over its largest weight."""
    want = ref["theta"]
    got = np.asarray(result, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"theta_gap": float("inf")}
    return {"theta_gap": float(np.max(np.abs(got - want)) / np.max(np.abs(want)))}
