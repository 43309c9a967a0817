"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import unfused_reduce

pytestmark = pytest.mark.kernel  # interpret-mode kernel tests, in tier-1

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("BH,T,S,d,dv,causal,bq,bk", [
    (2, 128, 128, 64, 64, True, 64, 64),
    (1, 96, 160, 32, 16, False, 64, 64),
    (3, 64, 64, 128, 128, True, 32, 32),
    (1, 17, 33, 16, 16, True, 8, 16),
])
def test_flash_attention_sweep(dtype, BH, T, S, d, dv, causal, bq, bk):
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd
    from repro.kernels.flash_attention.ref import attention_bhsd_ref
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(BH, T, d)), dtype)
    k = jnp.asarray(rng.normal(size=(BH, S, d)), dtype)
    v = jnp.asarray(rng.normal(size=(BH, S, dv)), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, block_q=bq, block_k=bk,
                               interpret=True)
    ref = attention_bhsd_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_gqa_layout():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import naive_attention
    rng = np.random.default_rng(1)
    B, T, KH, G, dh = 2, 64, 2, 3, 32
    q = jnp.asarray(rng.normal(size=(B, T, KH, G, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KH, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KH, dh)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n,v,block", [(4, 1024, 256), (7, 3000, 512), (1, 128, 128)])
def test_fused_scatter_dense_round_matches_jnp(dtype, n, v, block):
    """With the quota at the whole block the fused kernel is the dense N-way
    sum: bit for bit the jnp reference (both fold in f32 and cast once)."""
    from repro.core.sparse import _fused_accumulate_jnp, block_layout
    from repro.kernels.accumulate.fused_scatter import fused_topk_scatter
    nblocks, block_eff, _ = block_layout(v, v, block)
    rng = np.random.default_rng(2)
    mat = jnp.asarray(rng.normal(size=(n, v)), dtype)
    got = fused_topk_scatter(mat, per_block=block_eff, block_eff=block_eff,
                             interpret=True)
    want = _fused_accumulate_jnp(mat, nblocks, block_eff, block_eff)
    assert got.dtype == dtype
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(mat, np.float32).sum(axis=0), **_tol(dtype))


@pytest.mark.parametrize("v,k,bv", [(900, 4, 256), (2048, 16, 512), (100, 2, 64)])
def test_topk_compress_sweep(v, k, bv):
    from repro.kernels.topk_compress.kernel import topk_compress_blocked
    from repro.kernels.topk_compress.ref import topk_compress_ref
    from repro.core.sparse import densify
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(v,)), jnp.float32)
    idx, vals = topk_compress_blocked(x, k_per_block=k, block_v=bv, interpret=True)
    ridx, rvals = topk_compress_ref(x, k_per_block=k, block_v=bv)
    np.testing.assert_allclose(np.asarray(densify(idx, vals, v)),
                               np.asarray(densify(ridx, rvals, v)), rtol=1e-6)


@pytest.mark.parametrize("v,k,bv", [(900, 4, 256), (2048, 16, 512), (100, 2, 64),
                                    (1000, 200, 256), (4096, 256, 1024)])
def test_topk_bitonic_matches_argmax_elementwise(v, k, bv):
    """The bitonic partial sort must reproduce the argmax loop's pair stream
    *element for element* — same indices in the same slots (ties at equal
    magnitude break toward the lower index in both), not just the same sum."""
    from repro.kernels.topk_compress.kernel import topk_compress_blocked
    rng = np.random.default_rng(7)
    x = rng.normal(size=(v,)).astype(np.float32)
    x[rng.random(v) < 0.5] = 0.0      # magnitude ties at zero
    x = jnp.asarray(x)
    ia, va = topk_compress_blocked(x, k_per_block=k, block_v=bv,
                                   interpret=True, method="argmax")
    ib, vb = topk_compress_blocked(x, k_per_block=k, block_v=bv,
                                   interpret=True, method="bitonic")
    assert np.array_equal(np.asarray(ia), np.asarray(ib))
    assert np.array_equal(np.asarray(va), np.asarray(vb))


def test_topk_method_auto_selection():
    from repro.kernels.topk_compress.kernel import BITONIC_MIN_K
    from repro.kernels.topk_compress.ops import topk_compress
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    # method=None must agree with both explicit methods on either side of the
    # crossover (they are element-wise identical, so this pins the dispatch
    # without reaching into kernel internals)
    for k in (BITONIC_MIN_K - 1, BITONIC_MIN_K):
        auto = topk_compress(x, k_per_block=k, block_v=1024, interpret=True)
        for method in ("argmax", "bitonic"):
            explicit = topk_compress(x, k_per_block=k, block_v=1024,
                                     interpret=True, method=method)
            assert np.array_equal(np.asarray(auto[0]), np.asarray(explicit[0]))
    with pytest.raises(ValueError, match="argmax|bitonic"):
        topk_compress(x, k_per_block=4, block_v=1024, interpret=True,
                      method="quicksort")


@pytest.mark.parametrize("n,v,k,block", [
    (4, 16384, 512, 1024),     # the accumulator bench shape
    (8, 1000, 50, 256),        # ragged tail (1000 = 3×256 + 232)
    (1, 100, 10, 1024),        # single thread, one short block
    (3, 900, 900, 256),        # quota ≥ block: selection degenerates to all
    (2, 7, 3, 1024),           # tiny vector, non-pow2 block
])
def test_fused_scatter_bitexact_vs_unfused(n, v, k, block):
    """The fused sparsify→scatter-add must be *bit-exact* against the
    compress→densify→add chain, for both impls, across densities (dense
    rounds, realistic sparse rounds, all-zero rounds)."""
    from repro.core.sparse import blocked_topk_accumulate
    rng = np.random.default_rng(9)
    for density in (0.0, 0.01, 0.3, 1.0):
        mat = rng.normal(size=(n, v)).astype(np.float32)
        mat[rng.random((n, v)) >= density] = 0.0
        mat = jnp.asarray(mat)
        ref, _, _ = unfused_reduce(mat, k, block)
        fused = blocked_topk_accumulate(mat, k, block, impl="pallas")
        fused_jnp = blocked_topk_accumulate(mat, k, block, impl="jnp")
        assert np.array_equal(np.asarray(ref), np.asarray(fused)), density
        assert np.array_equal(np.asarray(ref), np.asarray(fused_jnp)), density


def test_fused_scatter_ragged_last_block_matches_jnp_bit_for_bit():
    """A round whose length is no multiple of the block (V = 4·1024 + 576,
    the last block ragged as at V = 10**6): the Pallas kernel in interpret
    mode equals the jnp reference bit for bit, sparse rows and rows that
    overflow a block's quota alike."""
    from repro.core.sparse import _fused_accumulate_jnp, block_layout
    from repro.kernels.accumulate.fused_scatter import fused_topk_scatter
    v = 4 * 1024 + 576
    nblocks, block_eff, per_block = block_layout(v, v // 4)
    assert (nblocks, per_block) == (5, 234)
    rng = np.random.default_rng(16)
    for density in (0.05, 0.5):
        mat = rng.normal(size=(4, v)).astype(np.float32)
        mat[rng.random((4, v)) >= density] = 0.0
        mat[:, -7:] = 3.0                       # the ragged block's last lanes
        mat = jnp.asarray(mat)
        got = fused_topk_scatter(mat, per_block=per_block, block_eff=block_eff,
                                 interpret=True)
        want = _fused_accumulate_jnp(mat, nblocks, block_eff, per_block)
        assert np.array_equal(np.asarray(got), np.asarray(want)), density
        assert float(got[-1]) == 12.0


def test_fused_scatter_kernel_validation():
    from repro.kernels.accumulate.fused_scatter import fused_topk_scatter
    with pytest.raises(ValueError, match=r"\(N, V\)"):
        fused_topk_scatter(jnp.zeros((8,)), per_block=2, block_eff=8)
    with pytest.raises(ValueError, match="per_block"):
        fused_topk_scatter(jnp.zeros((2, 8)), per_block=0, block_eff=8)


@pytest.mark.parametrize("n,k,d,bn", [(500, 11, 24, 128), (1000, 3, 8, 256)])
def test_kmeans_assign_sweep(n, k, d, bn):
    from repro.kernels.kmeans_assign.kernel import kmeans_assign_blocked
    from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
    rng = np.random.default_rng(5)
    pts = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ctr = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    a, dist = kmeans_assign_blocked(pts, ctr, block_n=bn, interpret=True)
    ra, rd = kmeans_assign_ref(pts, ctr)
    assert np.array_equal(np.asarray(a), np.asarray(ra))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rd), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_ssd_scan_sweep(chunk):
    from repro.kernels.ssd_scan.ops import ssd
    from repro.kernels.ssd_scan.ref import ssd_sequential_ref
    rng = np.random.default_rng(6)
    b, T, H, P, G, N = 2, 64, 4, 8, 2, 16
    xs = jnp.asarray(rng.normal(size=(b, T, H, P)), jnp.float32) * 0.5
    dt = jnp.asarray(np.abs(rng.normal(size=(b, T, H))) * 0.5 + 0.1, jnp.float32)
    A_log = jnp.asarray(np.log(np.linspace(1.0, 4.0, H)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, T, G, N)), jnp.float32) * 0.3
    C = jnp.asarray(rng.normal(size=(b, T, G, N)), jnp.float32) * 0.3
    y, _ = ssd(xs, dt, A_log, B, C, chunk=chunk, interpret=True)
    ref = ssd_sequential_ref(xs, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=3e-4, atol=3e-4)
