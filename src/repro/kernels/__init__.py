"""Pallas TPU kernels for the paper's compute hot-spots.

  glcm_kernel       pair-stream + fused tiled GLCM voting (one-hot MXU,
                    R-copy VMEM privatization, halo via next-tile Ref), the
                    windowed texture-map kernel (window grid = kernel grid)
                    the depth-slab volumetric kernel (grid = (B, n_slabs),
                    halo via next-slab Ref, 13 3-D directions per pass) and
                    the stride-1 window-features kernel (features out, no
                    per-window counts in HBM)
  histogram_kernel  the paper §II.A histogram analogy
  ops               jit'd wrappers (interpret on CPU, Mosaic on TPU) and the
                    shared ``onehot_count`` primitive used by the MoE router
  ref               pure-jnp oracles for every kernel
"""

from repro.kernels import ops, ref
from repro.kernels.ops import (
    glcm_pallas,
    glcm_pallas_multi,
    glcm_pallas_volume,
    glcm_pallas_window_features,
    glcm_pallas_windowed,
    histogram,
    onehot_count,
)

__all__ = [
    "ops",
    "ref",
    "glcm_pallas",
    "glcm_pallas_multi",
    "glcm_pallas_volume",
    "glcm_pallas_window_features",
    "glcm_pallas_windowed",
    "histogram",
    "onehot_count",
]
