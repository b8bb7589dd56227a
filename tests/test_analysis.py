"""The plan-contract analyzer: every lint rule must FIRE on a deliberately
broken backend (one per rule), the live registry must sweep clean, the
capability→rule classification must be total, and ``compile_plan``'s
``check="lint"`` / ``REPRO_PLAN_LINT=1`` modes must enforce the verdict.

The broken backends are the analyzer's positive controls: a rule that never
fires is indistinguishable from a rule that checks nothing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import audit, contracts, jaxpr_lint
from repro.core import backends as _backends
from repro.core.plan import compile_plan, plan_cache_clear
from repro.core.schemes import glcm_multi, glcm_scatter_batch
from repro.core.spec import GLCMSpec


@pytest.fixture
def scratch(monkeypatch):
    """Register throwaway backends; guarantee they never leak past the test
    (they would poison registry sweeps and "auto" resolution)."""
    names = []

    def add(backend):
        _backends.register(backend)
        names.append(backend.name)
        return backend

    plan_cache_clear()
    yield add
    for name in names:
        _backends.unregister(name)
    plan_cache_clear()


def _delegate(img, spec, quant=None):
    return glcm_scatter_batch(img, spec.levels, spec.offsets(), quant=quant)


def _lint(scheme, spec, shape, *, dtype=None, features=False):
    plan = compile_plan(spec.replace(scheme=scheme), shape, features=features)
    return jaxpr_lint.lint_plan(plan, dtype=dtype)


def _rules_fired(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# One deliberately broken backend per rule
# ---------------------------------------------------------------------------


def test_fires_fused_no_int_image(scratch):
    """Claims fused_quantize but eagerly materializes the quantized image."""

    def eager(img, spec, quant=None):
        if quant is not None:
            lo, span = quant
            lo = jnp.asarray(lo, jnp.float32).reshape(
                (-1,) + (1,) * spec.ndim
            )
            span = jnp.asarray(span, jnp.float32).reshape(
                (-1,) + (1,) * spec.ndim
            )
            img = jnp.clip(
                jnp.floor((img - lo) / span * spec.levels),
                0, spec.levels - 1,
            ).astype(jnp.int32)
        return _delegate(img, spec)

    scratch(_backends.Backend(
        name="_lint_eager", compute=eager,
        caps=_backends.Capabilities(fused_quantize=True),
    ))
    spec = GLCMSpec(levels=16, pairs=((1, 0),), quantize="uniform")
    findings = _lint("_lint_eager", spec, (2, 32, 32), dtype=jnp.float32)
    assert "fused-no-int-image" in _rules_fired(findings)


def test_fires_identity_quantize_float_free(scratch):
    """Reintroduces floor/div binning on a provably-identity workload."""

    def rebinner(img, spec, quant=None):
        img = jnp.floor(img.astype(jnp.float32) / 1.0).astype(jnp.int32)
        return _delegate(img, spec, quant=quant)

    scratch(_backends.Backend(
        name="_lint_rebin", compute=rebinner,
        caps=_backends.Capabilities(fused_quantize=True),
    ))
    spec = GLCMSpec(levels=256, pairs=((1, 0),), quantize="uniform",
                    vrange=(0, 255))
    findings = _lint("_lint_rebin", spec, (24, 20), dtype=jnp.uint8)
    assert "identity-quantize-float-free" in _rules_fired(findings)


def test_fires_accum_exact_width(scratch):
    """Votes in float32 despite the spec demanding exact integer accum."""

    def float_votes(img, spec, quant=None):
        return glcm_multi(
            img, spec.levels, offsets=spec.offsets(), dtype=jnp.float32,
            quant=quant,
        )

    scratch(_backends.Backend(
        name="_lint_f32votes", compute=float_votes,
        caps=_backends.Capabilities(),
    ))
    spec = GLCMSpec(levels=16, pairs=((1, 0),), accum="int")
    findings = _lint("_lint_f32votes", spec, (2, 32, 32))
    assert "accum-exact-width" in _rules_fired(findings)


def test_fires_no_host_callback(scratch):
    """A device backend (no host_native cap) that round-trips to the host."""

    def cb_compute(img, spec, quant=None):
        out = jax.ShapeDtypeStruct(
            (img.shape[0], spec.n_pairs, spec.levels, spec.levels),
            jnp.float32,
        )

        def cb(x):
            import numpy as np

            return np.zeros(out.shape, np.float32)

        return jax.pure_callback(cb, out, img)

    scratch(_backends.Backend(
        name="_lint_callback", compute=cb_compute,
        caps=_backends.Capabilities(),
    ))
    spec = GLCMSpec(levels=8, pairs=((1, 0),))
    findings = _lint("_lint_callback", spec, (2, 16, 16))
    assert "no-host-callback" in _rules_fired(findings)


def test_fires_pruned_no_eigh(scratch):
    """Smuggles an eigendecomposition into a plan that selected none."""

    def eigy(img, spec, quant=None):
        counts = _delegate(img, spec, quant=quant)
        w = jnp.linalg.eigvalsh(jnp.eye(spec.levels, dtype=jnp.float32))
        return counts + 0.0 * w.sum()

    scratch(_backends.Backend(
        name="_lint_eigh", compute=eigy, caps=_backends.Capabilities(),
    ))
    spec = GLCMSpec(levels=8, pairs=((1, 0),))
    findings = _lint("_lint_eigh", spec, (2, 16, 16))
    assert "pruned-no-eigh" in _rules_fired(findings)


def test_fires_no_f64_promotion(scratch):
    """Promotes the counts through float64 (visible only when x64 is
    enabled — exactly the silent-promotion hazard the rule polices)."""

    def wide(img, spec, quant=None):
        counts = _delegate(img, spec, quant=quant)
        return counts.astype(jnp.float64).astype(jnp.float32)

    scratch(_backends.Backend(
        name="_lint_f64", compute=wide, caps=_backends.Capabilities(),
    ))
    spec = GLCMSpec(levels=8, pairs=((1, 0),))
    with jax.enable_x64(True):
        findings = _lint("_lint_f64", spec, (2, 16, 16))
    assert "no-f64-promotion" in _rules_fired(findings)


def test_fires_stream_signed_accum():
    """A rolling update carried in uint16: both the state-aval probe and the
    wrapping expiry-subtraction probe must fire."""
    levels, window = 8, 4
    cell = (1, levels, levels)

    def bad_update(counts, ring, pos, delta):
        expired = jax.lax.dynamic_index_in_dim(ring, pos, axis=0,
                                               keepdims=False)
        counts = counts + delta - expired  # uint16: wraps instead of borrows
        ring = jax.lax.dynamic_update_index_in_dim(ring, delta, pos, axis=0)
        return counts, ring, (pos + 1) % window

    avals = (
        jax.ShapeDtypeStruct(cell, jnp.uint16),
        jax.ShapeDtypeStruct((window, *cell), jnp.uint16),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    jx = jax.make_jaxpr(bad_update)(
        *avals, jax.ShapeDtypeStruct(cell, jnp.uint16)
    )
    ctx = jaxpr_lint.LintContext(
        jaxpr=jx,
        spec=GLCMSpec(levels=levels, pairs=((1, 0),), scheme="onehot"),
        backend=_backends.get_backend("onehot"),
        shape=(16, 16),
        dtype=jnp.int32,
        temporal_window=window,
        state_avals=avals,
    )
    msgs = jaxpr_lint.get_rule("stream-signed-accum").check(ctx)
    assert any("unsigned" in m and "state" in m for m in msgs)
    assert any("sub" in m for m in msgs)


def test_stream_rule_applies_only_to_temporal_plans():
    spec = GLCMSpec(levels=8, pairs=((1, 0),), scheme="onehot")
    kw = dict(
        jaxpr=None, spec=spec, backend=_backends.get_backend("onehot"),
        shape=(16, 16), dtype=jnp.int32,
    )
    plain = contracts.applicable_rules(jaxpr_lint.LintContext(**kw))
    stream = contracts.applicable_rules(
        jaxpr_lint.LintContext(**kw, temporal_window=4)
    )
    assert "stream-signed-accum" not in plain
    assert "stream-signed-accum" in stream


def test_stream_plan_lints_clean():
    """The shipped incremental plan (signed-int32 state by construction)
    must survive its own rule — and be traced as the update step."""
    plan_cache_clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),), scheme="onehot")
    plan = compile_plan(spec, (16, 16), temporal_window=3)
    assert jaxpr_lint.is_stream_plan(plan)
    assert not jaxpr_lint.is_stream_plan(compile_plan(spec, (16, 16)))
    assert jaxpr_lint.lint_plan(plan) == ()


# ---------------------------------------------------------------------------
# The live registry sweeps clean
# ---------------------------------------------------------------------------


def test_clean_registry_audit_is_green():
    report = audit.run_audit()
    assert report.checked, "audit traced nothing — the sweep is vacuous"
    assert report.ok, report.to_dict()


def test_audit_cli_fails_on_seeded_violation(scratch, capsys):
    """End-to-end CLI contract: exit 0 on the clean registry, exit 1 naming
    the backend and rule once a violating backend is registered."""
    assert audit.main(["--case", "2d/prequantized/int-accum"]) == 0

    def cb_compute(img, spec, quant=None):
        out = jax.ShapeDtypeStruct(
            (img.shape[0], spec.n_pairs, spec.levels, spec.levels),
            jnp.float32,
        )
        return jax.pure_callback(lambda x: x.mean(), out, img)

    scratch(_backends.Backend(
        name="_lint_cli_bad", compute=cb_compute,
        caps=_backends.Capabilities(),
    ))
    assert audit.main(["--case", "2d/prequantized/int-accum"]) == 1
    out = capsys.readouterr().out
    assert "_lint_cli_bad" in out and "no-host-callback" in out


# ---------------------------------------------------------------------------
# Contract classification totality
# ---------------------------------------------------------------------------


def test_capability_classification_is_total():
    """Every Capabilities field is classified exactly once — adding a field
    without deciding how it is audited must fail here."""
    fields = {f.name for f in dataclasses.fields(_backends.Capabilities)}
    traced = set(contracts.CAPABILITY_RULES)
    dynamic = set(contracts.DYNAMIC_CAPABILITIES)
    assert traced | dynamic == fields
    assert not traced & dynamic


def test_contract_rules_are_registered():
    names = set(jaxpr_lint.registered_rules())
    for rules in contracts.CAPABILITY_RULES.values():
        assert set(rules) <= names
    assert set(contracts.SPEC_RULES.values()) <= names


def test_rule_registry_rejects_duplicates_and_unknowns():
    with pytest.raises(ValueError, match="already registered"):
        jaxpr_lint.register_rule(jaxpr_lint.get_rule("pruned-no-eigh"))
    with pytest.raises(ValueError, match="unknown lint rule"):
        jaxpr_lint.get_rule("no-such-rule")


# ---------------------------------------------------------------------------
# compile_plan(check="lint") / REPRO_PLAN_LINT
# ---------------------------------------------------------------------------


def test_check_lint_passes_and_caches_verdict():
    plan_cache_clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),), quantize="uniform",
                    scheme="onehot")
    plan = compile_plan(spec, (2, 16, 16), check="lint")
    assert plan.lint == ()
    # verdict rides the cache entry: a later unchecked lookup sees it, and a
    # plan compiled WITHOUT check is linted lazily on its first linted hit
    assert compile_plan(spec, (2, 16, 16)).lint == ()
    plan_cache_clear()
    cold = compile_plan(spec, (2, 16, 16))
    assert cold.lint is None
    assert compile_plan(spec, (2, 16, 16), check="lint") is cold
    assert cold.lint == ()


def test_check_lint_raises_on_violation(scratch):
    def cb_compute(img, spec, quant=None):
        out = jax.ShapeDtypeStruct(
            (img.shape[0], spec.n_pairs, spec.levels, spec.levels),
            jnp.float32,
        )
        return jax.pure_callback(lambda x: x.mean(), out, img)

    scratch(_backends.Backend(
        name="_lint_gate_bad", compute=cb_compute,
        caps=_backends.Capabilities(),
    ))
    spec = GLCMSpec(levels=8, pairs=((1, 0),), scheme="_lint_gate_bad")
    with pytest.raises(jaxpr_lint.PlanContractError, match="no-host-callback"):
        compile_plan(spec, (2, 16, 16), check="lint")
    # the recorded verdict keeps failing on every subsequent linted lookup
    with pytest.raises(jaxpr_lint.PlanContractError):
        compile_plan(spec, (2, 16, 16), check="lint")
    # ...but an unchecked lookup still serves the plan (opt-in enforcement)
    assert compile_plan(spec, (2, 16, 16)).lint


def test_env_var_enables_lint(monkeypatch):
    plan_cache_clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),), scheme="scatter")
    monkeypatch.setenv("REPRO_PLAN_LINT", "1")
    assert compile_plan(spec, (2, 16, 16)).lint == ()
    # check="" opts a single call back out even with the env var set
    plan_cache_clear()
    assert compile_plan(spec, (2, 16, 16), check="").lint is None
    with pytest.raises(ValueError, match="unknown check mode"):
        compile_plan(spec, (2, 16, 16), check="bogus")


# ---------------------------------------------------------------------------
# Walker unit coverage (the shared API the test suite dedups onto)
# ---------------------------------------------------------------------------


def test_walker_descends_into_scan_and_pjit():
    def f(x):
        def body(c, v):
            return c + jnp.linalg.eigvalsh(jnp.eye(3) * v).sum(), v

        out, _ = jax.lax.scan(body, 0.0, x)
        return jax.jit(lambda y: y * 2.0)(out)

    jx = jax.make_jaxpr(f)(jnp.ones((4,)))
    prims = jaxpr_lint.primitive_names(jx)
    assert "scan" in prims
    assert jaxpr_lint.has_primitive(jx, "eigh")


def test_int_image_eqns_stops_at_pallas_boundary():
    """A kernel-internal integer block spanning the full spatial extent is
    VMEM, not a materialized image — the query must not flag it."""
    spec = GLCMSpec(levels=8, pairs=((1, 0), (1, 4)), quantize="uniform",
                    scheme="pallas_volume", ndim=3)
    plan = compile_plan(spec, (2, 8, 20, 24))
    jx = jaxpr_lint.trace_plan(plan, jnp.float32)
    assert jaxpr_lint.int_image_eqns(jx, (8, 20, 24)) == []
    # ...while the walker in full-descent mode CAN see inside the kernel
    assert "pallas_call" in jaxpr_lint.primitive_names(jx)
