"""Device ms per request of the cell's Pallas GLCM kernels. The texture
cell launches one, the window-features kernel
(``glcm_window_features_pallas``), which turns every stride-1 window of a
request into its features; a change that serves the cell by another GLCM
kernel is read the same way."""

from chipbench.metrics import ANY_KERNEL, kernel_ms


def read(ctx):
    return kernel_ms(ctx, ANY_KERNEL)
