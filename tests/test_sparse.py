"""Sparse dispatch layer (core/sparse.py) + host↔SPMD sparse/auto/inc parity.

The tentpole contract: `blocked_topk_sparsify` routes to the Pallas
`topk_compress` kernel (interpret mode off-TPU) with the jnp path kept as a
reference, both backends select contributions' pairs with the *same* blocked
top-k (the host fuses it with the scatter-add), and `wire_traffic()` for a
sparse round is derived from the actual pair counts on both — so host and
SPMD sessions agree on results
(lossless iff nnz fits the budget, identical top-k selection otherwise) and
on the sparse wire figure.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess_devices
from repro.core import AccumMode, Session
from repro.core.sparse import (
    SparsePairs,
    block_layout,
    blocked_topk_sparsify,
    default_auto_k,
    densify,
    pair_capacity,
    sparse_beneficial,
)

pytestmark = pytest.mark.kernel  # exercises the Pallas kernel in interpret mode


# -- dispatch: Pallas kernel vs jnp reference ---------------------------------


@pytest.mark.parametrize("v,k,block", [
    (256, 16, 1024),    # single block, k < V
    (3000, 48, 1024),   # multi-block, ragged tail
    (100, 7, 64),       # small blocks
    (64, 64, 1024),     # k == V (fully lossless)
])
def test_pallas_and_jnp_paths_agree(v, k, block):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(v,)), jnp.float32)   # dense → lossy
    pk = blocked_topk_sparsify(x, k, block)               # pallas (interpret)
    pj = blocked_topk_sparsify(x, k, block, impl="jnp")
    assert isinstance(pk, SparsePairs) and isinstance(pj, SparsePairs)
    assert pk.num_pairs == pj.num_pairs == pair_capacity(v, k, block)
    np.testing.assert_allclose(np.asarray(pk.densify()),
                               np.asarray(pj.densify()), rtol=1e-6)


@pytest.mark.parametrize("m,v", [(50, 700), (200, 4096), (1, 64)])
def test_densify_matches_numpy_add_at(m, v):
    """The SPMD sparse collective's receive side: scatter-add of pairs."""
    rng = np.random.default_rng(4)
    idx = rng.integers(0, v, size=(m,)).astype(np.int32)
    vals = rng.normal(size=(m,)).astype(np.float32)
    want = np.zeros(v, np.float32)
    np.add.at(want, idx, vals)
    got = densify(jnp.asarray(idx), jnp.asarray(vals), v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_densify_sums_duplicate_indices():
    idx = jnp.asarray([3, 3, 3, 0], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 5.0], jnp.float32)
    out = np.asarray(densify(idx, vals, 8))
    np.testing.assert_allclose(out, [5.0, 0, 0, 6.0, 0, 0, 0, 0])


def test_pairs_format_and_tuple_compat():
    x = jnp.asarray(np.arange(10, dtype=np.float32))
    pairs = blocked_topk_sparsify(x, 4)
    idx, vals = pairs                         # legacy tuple-style unpacking
    assert idx.dtype == jnp.int32 and vals.dtype == x.dtype
    assert pairs.wire_elements == 2 * pairs.num_pairs
    assert int(jnp.max(idx)) < 10             # padded tail normalised in-range
    np.testing.assert_allclose(np.asarray(pairs.densify()),
                               [0, 0, 0, 0, 0, 0, 6, 7, 8, 9])


def test_lossless_iff_under_per_block_budget():
    v, k = 512, 8
    nblocks, block_eff, per_block = block_layout(v, k, 256)
    rng = np.random.default_rng(1)
    x = np.zeros(v, np.float32)
    pos = rng.choice(v, size=per_block, replace=False)    # worst-case packing
    x[pos] = rng.normal(size=per_block)
    got = blocked_topk_sparsify(jnp.asarray(x), k, 256).densify()
    np.testing.assert_allclose(np.asarray(got), x, rtol=1e-6)

    # one more nonzero in a single block than its quota → lossy
    y = np.zeros(v, np.float32)
    y[:per_block + 1] = np.arange(1, per_block + 2, dtype=np.float32)
    got_y = np.asarray(blocked_topk_sparsify(jnp.asarray(y), k, 256).densify())
    assert int(np.sum(got_y != 0)) == per_block           # smallest entry dropped
    assert got_y[0] == 0.0 and not bool(sparse_beneficial(jnp.asarray(y), k, 256))


def test_layout_and_capacity_invariants():
    for v, k, block in [(1, 1, 1024), (4096, 256, 1024), (3000, 750, 1024),
                        (10, 100, 1024), (1024, 1, 128)]:
        nblocks, block_eff, per_block = block_layout(v, k, block)
        assert 1 <= per_block <= block_eff
        assert nblocks * block_eff >= v
        assert pair_capacity(v, k, block) == nblocks * per_block
    assert 2 * pair_capacity(1024, default_auto_k(1024)) < 1024
    with pytest.raises(ValueError):
        block_layout(0, 4)
    with pytest.raises(ValueError):
        block_layout(16, 0)
    with pytest.raises(ValueError):
        blocked_topk_sparsify(jnp.ones(8), 2, impl="nope")


# -- host ↔ SPMD parity (the acceptance criterion) ----------------------------


def test_sparse_auto_backend_parity_single_device():
    """Same session code, 1 host thread vs a 1-device SPMD mesh: identical
    results and an identical pairs-derived sparse wire figure.  (Multi-way
    parity runs in the forced-device subprocess tests below.)"""
    V, k = 512, 8
    rows = np.zeros((1, V), np.float32)
    rows[0, 3:6] = 2.0
    rows = jnp.asarray(rows)

    def run(backend, mode):
        sess = Session(backend=backend, n_nodes=1, threads_per_node=1)
        out = sess.new_array("o", (V,), sparse_k=k)

        def proc(ctx, xs):
            return out.accumulate(xs[0], mode=mode)

        res = sess.run(proc, data=(rows,))
        return np.asarray(res[0]), sess.wire_traffic()

    for mode in ("sparse", "auto"):
        r_host, _ = run("host", mode)
        r_spmd, _ = run("spmd", mode)
        np.testing.assert_allclose(r_host, r_spmd, rtol=1e-6)
        np.testing.assert_allclose(r_host, np.asarray(rows)[0], rtol=1e-6)
    # wire parity is asserted for SPARSE (AUTO is accounted at its dense
    # upper bound at SPMD trace time — documented divergence)
    _, w_host = run("host", "sparse")
    _, w_spmd = run("spmd", "sparse")
    assert w_host == w_spmd == 2 * pair_capacity(V, k) + V


def test_sparse_auto_backend_parity_multidevice():
    """4 host threads vs a 4-device mesh: lossless and lossy sparse rounds,
    auto's crossover, and the pairs-derived wire figure all agree."""
    out = run_subprocess_devices("""
import jax.numpy as jnp, numpy as np
from repro.core import Session
from repro.core.sparse import pair_capacity

V, k, N = 1024, 8, 4
P = pair_capacity(V, k)

def run(backend, rows, mode):
    sess = Session(backend=backend, n_nodes=2, threads_per_node=2)
    out = sess.new_array("o", (V,), sparse_k=k)
    def proc(ctx, xs):
        return out.accumulate(xs[0], mode=mode)
    res = sess.run(proc, data=(rows,))
    return np.asarray(res[0]), sess.wire_traffic()

# lossless round: nnz per contribution <= per-block quota
rows = np.zeros((N, V), np.float32)
for t in range(N):
    rows[t, t * 3: t * 3 + 3] = float(t + 1)
rows = jnp.asarray(rows)
for mode in ("sparse", "auto"):
    r_h, w_h = run("host", rows, mode)
    r_s, w_s = run("spmd", rows, mode)
    np.testing.assert_allclose(r_h, r_s, rtol=1e-6)
    np.testing.assert_allclose(r_h, np.sum(np.asarray(rows), axis=0), rtol=1e-6)
r_h, w_h = run("host", rows, "sparse")
r_s, w_s = run("spmd", rows, "sparse")
assert w_h == w_s == N * 2 * P + V, (w_h, w_s, N * 2 * P + V)

# lossy round: dense contributions, nnz > capacity — identical top-k selection
rng = np.random.default_rng(1)
dense = jnp.asarray(np.round(rng.normal(size=(N, V)) * 8), jnp.float32)
r_h, w_h = run("host", dense, "sparse")
r_s, w_s = run("spmd", dense, "sparse")
np.testing.assert_allclose(r_h, r_s, rtol=1e-6)
assert int(np.sum(r_h != 0)) <= N * P
assert w_h == w_s == N * 2 * P + V

# auto crossover on dense data: both backends fall back to the dense sum
r_h, _ = run("host", dense, "auto")
r_s, _ = run("spmd", dense, "auto")
np.testing.assert_allclose(r_h, np.sum(np.asarray(dense), axis=0), rtol=1e-6)
np.testing.assert_allclose(r_s, np.sum(np.asarray(dense), axis=0), rtol=1e-5)
print("SPARSE_PARITY_OK")
""", n_devices=4)
    assert "SPARSE_PARITY_OK" in out


def test_sparse_parity_inside_iterate():
    """ctx.iterate: the sparse collective runs under lax.scan on SPMD; wire
    accounting multiplies by the trip count and still matches the host."""
    out = run_subprocess_devices("""
import jax.numpy as jnp, numpy as np
from repro.core import Session
from repro.core.sparse import pair_capacity

V, k, N, iters = 512, 8, 4, 3
rows = np.zeros((N, V), np.float32)
for t in range(N):
    rows[t, t * 5: t * 5 + 2] = float(t + 1)
rows = jnp.asarray(rows)

def run(backend):
    sess = Session(backend=backend, n_nodes=2, threads_per_node=2)
    out = sess.new_array("o", (V,), sparse_k=k)
    def proc(ctx, xs):
        def step(c):
            return c + out.accumulate(xs[0], mode="sparse")
        return ctx.iterate(step, jnp.zeros((V,)), iters)
    res = sess.run(proc, data=(rows,))
    return np.asarray(res[0]), sess.wire_traffic()

r_h, w_h = run("host")
r_s, w_s = run("spmd")
np.testing.assert_allclose(r_h, r_s, rtol=1e-6)
P = pair_capacity(V, k)
assert w_h == w_s == iters * (N * 2 * P + V), (w_h, w_s)
print("SPARSE_ITERATE_OK")
""", n_devices=4)
    assert "SPARSE_ITERATE_OK" in out


def test_fused_parity_modes_and_shards():
    """The fused sparsify→scatter-add reduce (the default host SPARSE/AUTO
    path) across AccumMode {SPARSE, AUTO} × shards {1, 8}, including inside
    ctx.iterate: results bit-exact everywhere, and the pairs-derived
    wire_traffic() figure identical host↔SPMD and across shard counts."""
    out = run_subprocess_devices("""
import jax.numpy as jnp, numpy as np
from repro.core import Session
from repro.core.sparse import pair_capacity

V, k, N, iters = 512, 8, 4, 3
P = pair_capacity(V, k)
# lossless rows so AUTO takes the sparse branch every round
rows = np.zeros((N, V), np.float32)
for t in range(N):
    rows[t, t * 5: t * 5 + 2] = float(t + 1)
rows = jnp.asarray(rows)

def run(backend, mode, shards):
    sess = Session(backend=backend, n_nodes=2, threads_per_node=2,
                   shards=shards)
    out = sess.new_array("o", (V,), sparse_k=k)
    def proc(ctx, xs):
        def step(c):
            return c + out.accumulate(xs[0], mode=mode)
        return ctx.iterate(step, jnp.zeros((V,)), iters)
    res = sess.run(proc, data=(rows,))
    return np.asarray(res[0]), sess.wire_traffic()

for mode in ("sparse", "auto"):
    results = {(b, s): run(b, mode, s)
               for b in ("host", "spmd") for s in (1, 8)}
    base_r, base_w = results[("host", 1)]
    for key, (r, w) in results.items():
        assert np.array_equal(base_r, r), (mode, key)     # bit-exact parity
        assert w == base_w == iters * (N * 2 * P + V), (mode, key, w)
print("FUSED_PARITY_OK")
""", n_devices=4)
    assert "FUSED_PARITY_OK" in out


def test_fused_kernel_path_and_owner_cache_counters():
    """Observability satellite: step.trace attributes the fused win — the
    reduce path lands in accum.kernel_path.{dense,fused} counters and
    memoised SharedRef owner handles in store.owner_cache_hit."""
    V, N = 256, 4
    rows = jnp.asarray(np.eye(N, V, dtype=np.float32))    # lossless under k=8
    sess = Session(backend="host", n_nodes=2, threads_per_node=2,
                   shards=2, trace=True)
    try:
        out = sess.new_array("o", (V,), sparse_k=8)

        def proc(ctx, xs):
            out.accumulate(xs[0], mode="sparse")
            out.accumulate(xs[0], mode="auto")            # resolves to sparse
            out.accumulate(xs[0], mode="reduce_scatter")
            return out.get()

        sess.run(proc, data=(rows,))
        counters = sess.metrics()["trace"]["counters"]
        assert counters["accum.kernel_path.fused"] == 2   # sparse + auto
        assert counters["accum.kernel_path.dense"] == 1
        # every out.get() after the first resolved its owner from the handle
        assert counters.get("store.owner_cache_hit", 0) > 0
    finally:
        sess.tracer.disable()


def test_inc_backend_parity():
    """N threads calling ref.inc(a) advance the value by N·a on BOTH backends
    (SPMD lowers to one psum of the per-thread amounts), inside and outside
    ctx.iterate."""
    out = run_subprocess_devices("""
import jax.numpy as jnp, numpy as np
from repro.core import Session

def run(backend):
    sess = Session(backend=backend, n_nodes=2, threads_per_node=2)
    c = sess.def_global("c", 0.0)
    def proc(ctx):
        c.inc(2.0)                      # outside the loop
        def step(_):
            c.inc(1.0)                  # inside: once per round per thread
            return _
        ctx.iterate(step, None, 3)
    sess.run(proc)
    return float(c.get())

h = run("host")
s = run("spmd")
assert h == s == 4 * 2.0 + 4 * 1.0 * 3, (h, s)
print("INC_PARITY_OK")
""", n_devices=4)
    assert "INC_PARITY_OK" in out


def test_logreg_sparse_gradients_parity():
    """The analytics opt-in: logreg with sparse/auto gradient accumulation
    converges identically across backends (auto) and across impls."""
    out = run_subprocess_devices("""
import numpy as np
from repro.analytics import logreg
from repro.data import logreg_dataset

x, y, _ = logreg_dataset(400, 24, seed=0)
ref = logreg.fit_reference(x, y, iters=8, lr=1e-3)
# auto is lossless by construction: must equal the dense reference
th_h, _ = logreg.fit(x, y, backend="host", n_nodes=2, threads_per_node=2,
                     iters=8, mode="auto", k=16)
th_s, _ = logreg.fit(x, y, backend="spmd", iters=8, mode="auto", k=16)
np.testing.assert_allclose(th_h, ref, rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(th_s, ref, rtol=1e-4, atol=1e-5)
# sparse with a tight budget is lossy but must be lossy the SAME way
th_hs, _ = logreg.fit(x, y, backend="host", n_nodes=2, threads_per_node=2,
                      iters=8, mode="sparse", k=8)
th_ss, _ = logreg.fit(x, y, backend="spmd", iters=8, mode="sparse", k=8)
np.testing.assert_allclose(th_hs, th_ss, rtol=1e-4, atol=1e-6)
print("LOGREG_SPARSE_OK")
""", n_devices=4)
    assert "LOGREG_SPARSE_OK" in out
