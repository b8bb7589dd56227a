"""The fourteen Haralick texture features (Haralick, Shanmugam & Dinstein
1973, paper ref [2]) computed from a GLCM.

All features are computed from the *normalized* co-occurrence matrix
``p[i, j]`` (sums to 1). Input may be raw counts — normalization is applied
internally. Everything is pure jnp, jit/vmap-safe (vmap over leading GLCM
batch dims via ``haralick_features``), and numerically guarded (log/ division
epsilons) so downstream training pipelines can consume the features.

f1  Angular Second Moment (Energy)     f8  Sum Entropy
f2  Contrast                           f9  Entropy
f3  Correlation                        f10 Difference Variance
f4  Sum of Squares: Variance           f11 Difference Entropy
f5  Inverse Difference Moment          f12 Information Measure of Corr. 1
f6  Sum Average                        f13 Information Measure of Corr. 2
f7  Sum Variance                       f14 Max. Correlation Coefficient
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["haralick_features", "FEATURE_NAMES", "normalize_glcm"]

FEATURE_NAMES = (
    "asm_energy",
    "contrast",
    "correlation",
    "variance",
    "inverse_difference_moment",
    "sum_average",
    "sum_variance",
    "sum_entropy",
    "entropy",
    "difference_variance",
    "difference_entropy",
    "info_correlation_1",
    "info_correlation_2",
    "max_correlation_coefficient",
)

_EPS = 1e-12


def normalize_glcm(glcm: jax.Array) -> jax.Array:
    """Counts → joint probabilities (sum to 1)."""
    total = jnp.maximum(glcm.sum(axis=(-2, -1), keepdims=True), _EPS)
    return glcm / total


def _entropy(p: jax.Array, axis=None) -> jax.Array:
    return -jnp.sum(p * jnp.log(p + _EPS), axis=axis)


def _haralick_single(p: jax.Array, select: tuple[int, ...]) -> jax.Array:
    """(L, L) normalized GLCM → (len(select),) feature vector.

    ``select`` holds FEATURE_NAMES indices, output columns follow its order.
    f1–f13 are O(L²) and always computed; the O(L³) eigendecomposition of
    f14 (max_correlation_coefficient) is traced ONLY when index 13 is
    selected — for texture maps with thousands of windows per image it
    dominates feature cost.
    """
    L = p.shape[-1]
    i = jnp.arange(L, dtype=p.dtype)
    ii, jj = jnp.meshgrid(i, i, indexing="ij")

    px = p.sum(axis=1)  # marginal over j
    py = p.sum(axis=0)  # marginal over i
    mu_x = jnp.sum(i * px)
    mu_y = jnp.sum(i * py)
    sd_x = jnp.sqrt(jnp.maximum(jnp.sum((i - mu_x) ** 2 * px), 0.0))
    sd_y = jnp.sqrt(jnp.maximum(jnp.sum((i - mu_y) ** 2 * py), 0.0))

    # p_{x+y}(k), k = 0..2L-2  and  p_{x-y}(k), k = 0..L-1
    ks = jnp.arange(2 * L - 1, dtype=jnp.int32)
    sum_idx = (ii + jj).astype(jnp.int32)
    p_sum = jnp.zeros((2 * L - 1,), p.dtype).at[sum_idx.reshape(-1)].add(p.reshape(-1))
    diff_idx = jnp.abs(ii - jj).astype(jnp.int32)
    p_diff = jnp.zeros((L,), p.dtype).at[diff_idx.reshape(-1)].add(p.reshape(-1))

    f1 = jnp.sum(p**2)
    f2 = jnp.sum((ii - jj) ** 2 * p)
    # Centered covariance: Σ ij·p − μxμy cancels catastrophically in float32
    # when the levels sit far from 0 and the window's variance is small.
    cov = jnp.sum((ii - mu_x) * (jj - mu_y) * p)
    f3 = cov / jnp.maximum(sd_x * sd_y, _EPS)
    mu = jnp.sum(p * ii)  # Haralick's μ in f4 (mean of joint over i)
    f4 = jnp.sum((ii - mu) ** 2 * p)
    f5 = jnp.sum(p / (1.0 + (ii - jj) ** 2))
    f6 = jnp.sum(ks.astype(p.dtype) * p_sum)
    f8 = _entropy(p_sum)
    f7 = jnp.sum((ks.astype(p.dtype) - f6) ** 2 * p_sum)
    f9 = _entropy(p)
    kd = jnp.arange(L, dtype=p.dtype)
    diff_mean = jnp.sum(kd * p_diff)
    f10 = jnp.sum((kd - diff_mean) ** 2 * p_diff)
    f11 = _entropy(p_diff)

    # Information measures of correlation.
    hx = _entropy(px)
    hy = _entropy(py)
    hxy = f9
    pxy_outer = px[:, None] * py[None, :]
    hxy1 = -jnp.sum(p * jnp.log(pxy_outer + _EPS))
    hxy2 = -jnp.sum(pxy_outer * jnp.log(pxy_outer + _EPS))
    f12 = (hxy - hxy1) / jnp.maximum(jnp.maximum(hx, hy), _EPS)
    f13 = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(-2.0 * (hxy2 - hxy)), 0.0))

    feats = [f1, f2, f3, f4, f5, f6, f7, f8, f9, f10, f11, f12, f13]

    if 13 in select:
        # f14: sqrt of second-largest eigenvalue of Q, Q[i,j] = Σ_k p[i,k]
        # p[j,k]/(px[i]py[k]). Q = D_x^{-1/2} (A Aᵀ) D_x^{1/2} with
        # A = P/√(px py) — so Q's spectrum equals that of the symmetric PSD
        # matrix AAᵀ, which we hand to eigvalsh (stable, real, in [0, 1];
        # the largest is exactly 1).
        a_mat = p / jnp.sqrt(
            jnp.maximum(px[:, None], _EPS) * jnp.maximum(py[None, :], _EPS)
        )
        # HIGHEST: the TPU's default f32 matmul precision is one bf16 pass.
        gram = jnp.matmul(a_mat, a_mat.T, precision=jax.lax.Precision.HIGHEST)
        eig = jnp.linalg.eigvalsh(gram)
        feats.append(jnp.sqrt(jnp.clip(jnp.sort(eig)[-2], 0.0, None)))

    return jnp.stack([feats[i] for i in select])


def _select_indices(select: tuple[str, ...] | None) -> tuple[int, ...]:
    if select is None:
        return tuple(range(len(FEATURE_NAMES)))
    idx = []
    for name in select:
        if name not in FEATURE_NAMES:
            raise ValueError(
                f"unknown Haralick feature {name!r}; expected names from "
                f"{FEATURE_NAMES}"
            )
        idx.append(FEATURE_NAMES.index(name))
    if not idx:
        raise ValueError("select=() names no features")
    return tuple(idx)


def haralick_features(
    glcm: jax.Array,
    *,
    assume_normalized: bool = False,
    select: tuple[str, ...] | None = None,
) -> jax.Array:
    """GLCM(s) → Haralick features.

    Accepts (..., L, L); returns (..., n_feats). Raw counts are normalized
    unless ``assume_normalized``. ``select`` names a subset of
    :data:`FEATURE_NAMES` — output columns follow its order, and work the
    selection doesn't need is skipped (only the O(L³) eigendecomposition of
    ``max_correlation_coefficient`` is expensive enough to matter). The
    default ``None`` computes all 14 in canonical order.
    """
    idx = _select_indices(select)
    p = glcm if assume_normalized else normalize_glcm(glcm)
    flat = p.reshape((-1,) + p.shape[-2:])
    feats = jax.vmap(lambda q: _haralick_single(q, idx))(flat)
    return feats.reshape(p.shape[:-2] + (len(idx),))
