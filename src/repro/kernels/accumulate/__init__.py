from repro.kernels.accumulate import fused_scatter
