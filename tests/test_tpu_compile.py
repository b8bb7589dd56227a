"""Mosaic compiles of the GLCM Pallas kernels for a described TPU v5e.

Interpret-mode tests cannot see what the TPU compiler refuses: blocks that
break the (8, 128) rule, ops without a Mosaic lowering, unsupported vector
shape casts, VMEM overflows. Here each kernel that ``scheme="auto"`` reaches
on TPU is compiled with ``interpret=False`` at serving shapes for a v5e that
is described, not attached (``jax.experimental.topologies``), so nothing
runs and no chip is needed. The topology is described inside a fixture: only
the worker that runs this file loads the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.glcm_kernel import (
    glcm_fused_pallas,
    glcm_vote_pallas,
    glcm_window_pallas,
)

PAPER_PAIRS = ((1, 0), (1, 45), (4, 0), (4, 45))
OFFSETS_2D = tuple(kref.glcm_offsets(d, t) for d, t in PAPER_PAIRS)
LEVELS = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache but
    cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _vote(a, r):
    return glcm_vote_pallas(a, r, levels=LEVELS, interpret=False)


def _fused(x, *q):
    return glcm_fused_pallas(x, levels=LEVELS, offsets=OFFSETS_2D,
                             quant=q or None, interpret=False)


def _window(p, *q):
    return glcm_window_pallas(p, levels=LEVELS, offsets=OFFSETS_2D,
                              quant=q or None, interpret=False)


def _volume(v, *q):
    # through the ops wrapper, so its VMEM-sized default slab depth compiles
    return kops.glcm_pallas_volume(
        v, LEVELS, tuple((1, k) for k in range(13)), quant=q or None,
        interpret=False,
    )


_F32, _I32 = jnp.float32, jnp.int32
_PAIRS_1024 = 1024 * 1023  # one offset's pair stream of a 1024² image

# (kernel, input (shape, dtype), per-image quant (lo, span) batch or None)
CASES = {
    "vote_bucket_8x1024": (_vote, [((8, _PAIRS_1024), _I32)] * 2, None),
    "fused_bucket_8x1024": (_fused, [((8, 1024, 1024), _I32)], None),
    "fused_bucket_8x1024_quant": (_fused, [((8, 1024, 1024), _F32)], 8),
    "fused_16384_wide_quant": (_fused, [((1, 16384, 16384), _F32)], 1),
    "window_64x64_grid_of_64_quant": (_window, [((1, 64, 64, 64, 64), _F32)], 1),
    "window_64x64_grid_of_64": (_window, [((1, 64, 64, 64, 64), _I32)], None),
    "volume_128x512x512_quant": (_volume, [((1, 128, 512, 512), _F32)], 1),
    "volume_128x512x512": (_volume, [((1, 128, 512, 512), _I32)], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, inputs, quant_batch = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in inputs]
    if quant_batch is not None:
        args += [jax.ShapeDtypeStruct((quant_batch,), _F32, sharding=one_chip)] * 2
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not XLA
    mem = compiled.memory_analysis()
    assert mem is not None and mem.argument_size_in_bytes > 0
