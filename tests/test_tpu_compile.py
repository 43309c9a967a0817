"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the installed TPU compiler compiles each kernel for a chip that
is described, not attached, and the program must hold the Mosaic kernel
(``tpu_custom_call``).  Widths are those of the chip smoke test: PageRank's
credit vector at SNAP soc-LiveJournal1's vertex count, a host-backend round
of 4 threads, and k-means at UCI Covertype's shape.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V = 4_847_571                       # soc-LiveJournal1 vertices
COVTYPE = (581_012, 54)             # UCI Covertype rows x features
K = 7


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _topk(method, k_per_block):
    from repro.kernels.topk_compress.ops import topk_compress

    def run(x):
        return topk_compress(x, k_per_block=k_per_block, interpret=False,
                             method=method)
    return run, [((V,), jnp.float32)]


def _fused():
    from repro.kernels.accumulate.fused_scatter import fused_topk_scatter

    def run(x):
        return fused_topk_scatter(x, per_block=256, block_eff=1024,
                                  interpret=False)
    return run, [((4, V), jnp.float32)]


def _kmeans():
    from repro.kernels.kmeans_assign.ops import kmeans_assign

    def run(points, centers):
        return kmeans_assign(points, centers, interpret=False)
    return run, [(COVTYPE, jnp.float32), ((K, COVTYPE[1]), jnp.float32)]


CASES = {
    "topk_argmax_k16": lambda: _topk("argmax", 16),
    "topk_bitonic_k256": lambda: _topk("bitonic", 256),
    "fused_topk_scatter_4xV": _fused,
    "kmeans_assign_covtype": _kmeans,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
