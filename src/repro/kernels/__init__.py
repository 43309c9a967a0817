"""Pallas TPU kernels for the compute hot-spots.

flash_attention — online-softmax attention, VMEM accumulator over KV tiles
accumulate      — fused_scatter: the host DAddAccumulator's pairs reduce,
                  blocked top-k sparsify→scatter-add in one launch (§5.2)
topk_compress   — blocked top-k pairs (the SPMD collective's sparse mode)
kmeans_assign   — nearest-center assignment via distance GEMM (paper §6.5)
ssd_scan        — Mamba2 SSD: chunk GEMMs + VMEM-carried recurrent state

Each package but ``accumulate`` is kernel.py + ops.py + ref.py; the fused
reduce's jnp reference lives beside its dispatch in ``repro.core.sparse``.
All are validated on CPU with interpret=True against their references;
compiled (Mosaic) lowering engages on a real TPU backend.
"""
