"""Request pools made on the device from the seed.

A cell's requests are drawn from a small pool of distinct inputs, so that
the reference is computed once per pool entry and not once per request.
Each entry is made by one jitted call from a key folded out of the seed,
then copied to the host, where clients keep their requests.

Every entry is a uint8 image cut into a grid of ``REGIONS`` × ``REGIONS``
rectangles, as real images hold objects of their own brightness and
contrast: each rectangle draws its own window of gray levels (a width of
32 to 256 levels at a random place in 0..255) and fills it with the
entry's texture. No part of an image stands for the whole, so an answer
that leaves a block of the image uncounted moves its features. The image
is then stretched to the full range 0..255, as contrast normalization
leaves an 8-bit image: over a span of 255 levels no level sits exactly on
one of the 32 bin edges, where the served binning and the reference part
(PERF.md, Open questions). The textures:

  smooth_u8  a slowly varying field: coarse uniform noise on a grid 64
             pixels apart, interpolated bilinearly (two matmuls with
             interpolation matrices), plus slight fine noise (the paper's
             Fig. 1(a) regime: votes pile onto few bins)
  iid_u8     iid uniform levels (Fig. 1(b): votes scatter)
"""

from __future__ import annotations

import functools

import numpy as np

KINDS = ("smooth_u8", "iid_u8")
REGIONS = 4


def base_key(seed: int):
    """A threefry key from any whole seed (more than 32 bits too)."""
    import jax

    words = np.random.SeedSequence(int(seed) % 2**128).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _interp_matrix(n_out: int, n_in: int):
    """(n_out, n_in) linear interpolation weights, end points aligned."""
    import jax.numpy as jnp

    pos = jnp.linspace(0.0, n_in - 1.0, n_out)
    lo = jnp.clip(jnp.floor(pos), 0, n_in - 2)
    frac = pos - lo
    cols = jnp.arange(n_in)[None, :]
    lo = lo[:, None]
    return (jnp.where(cols == lo, 1.0 - frac[:, None], 0.0)
            + jnp.where(cols == lo + 1, frac[:, None], 0.0))


@functools.lru_cache(maxsize=None)
def _maker(kind: str, shape: tuple[int, ...]):
    import jax
    import jax.numpy as jnp

    if kind not in KINDS:
        raise ValueError(f"unknown pool kind {kind!r}; expected one of {KINDS}")
    h, w = shape

    def texture(key):
        """Values in [0, 1]."""
        if kind == "iid_u8":
            return jax.random.uniform(key, shape)
        k1, k2 = jax.random.split(key)
        coarse = jax.random.uniform(k1, (h // 64 + 2, w // 64 + 2))
        img = (_interp_matrix(h, coarse.shape[0]) @ coarse
               @ _interp_matrix(w, coarse.shape[1]).T)
        img = img + jax.random.uniform(k2, shape, minval=-0.01, maxval=0.01)
        return (img - img.min()) / (img.max() - img.min())

    def spread(table, ey, ex):
        """(REGIONS, REGIONS) → (h, w), each rectangle holding its value."""
        return jnp.dot(jnp.dot(ey, table, precision="highest"), ex.T, precision="highest")

    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        width = jax.random.uniform(k1, (REGIONS, REGIONS), minval=32.0, maxval=256.0)
        low = jax.random.uniform(k2, (REGIONS, REGIONS)) * (256.0 - width)
        grid = jnp.arange(REGIONS)
        ey = ((jnp.arange(h) * REGIONS) // h)[:, None] == grid
        ex = ((jnp.arange(w) * REGIONS) // w)[:, None] == grid
        ey, ex = ey.astype(jnp.float32), ex.astype(jnp.float32)
        img = spread(low, ey, ex) + spread(width, ey, ex) * texture(k3)
        img = (img - img.min()) / (img.max() - img.min()) * 255.0
        return jnp.clip(jnp.round(img), 0, 255).astype(jnp.uint8)

    return jax.jit(make)


def make_pool(pool_spec, shape, seed: int) -> list[np.ndarray]:
    """The host arrays of a cell's pool: ``pool_spec`` is a list of
    ``{"kind": ..., "count": n}``; entry i is made from the seed's key
    folded with i."""
    import jax

    key = base_key(seed)
    shape = tuple(int(s) for s in shape)
    pool = []
    for group in pool_spec:
        make = _maker(group["kind"], shape)
        for _ in range(int(group["count"])):
            pool.append(np.asarray(make(jax.random.fold_in(key, len(pool)))))
    return pool


def request_stream(pool: list, seed: int):
    """(pool index, array) of each request in turn: back-to-back seeded
    permutations of the pool, so every seed serves the same mix."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed) % 2**128))
    while True:
        for idx in rng.permutation(len(pool)):
            yield int(idx), pool[idx]
