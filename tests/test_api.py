"""repro.api contract tests: registry dispatch, ExecutionPolicy resolution,
and bit-for-bit equivalence with the legacy per-kernel kwarg surface."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import api
from repro.api.registry import KernelRegistry
from repro.kernels.aio_matmul import aio_matmul
from repro.kernels.aio_quant import aio_quantize
from repro.kernels.depthwise import depthwise_conv
from repro.kernels.flash_attention import attention
from repro.kernels.grouped_matmul import grouped_matmul, morphable_multi_gemm

RNG = np.random.RandomState(7)


def randn(*shape, scale=1.0):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32) * scale)


# ======================================================================
# ExecutionPolicy semantics
# ======================================================================

def test_policy_defaults_and_validation():
    pol = api.ExecutionPolicy()
    assert pol.format == "bf16" and pol.backend == "auto"
    assert not pol.use_pallas()                     # auto off a TPU -> ref
    with pytest.raises(ValueError):
        api.ExecutionPolicy(backend="cuda")
    with pytest.raises(ValueError):
        api.ExecutionPolicy(format="fp64")


def test_policy_is_hashable_static_arg():
    a = api.ExecutionPolicy(format="int8", backend="ref")
    b = api.ExecutionPolicy(format="int8", backend="ref")
    assert a == b and hash(a) == hash(b)
    assert a != api.ExecutionPolicy(format="int4", backend="ref")


def test_policy_context_nesting_inherits_unset_fields():
    with api.policy(format="int8"):
        assert api.current_policy().format == "int8"
        with api.policy(backend="ref", bm=64):
            inner = api.current_policy()
            assert inner.format == "int8"           # inherited from outer
            assert inner.backend == "ref" and inner.bm == 64
        assert api.current_policy().backend == "auto"   # popped
    assert api.current_policy() == api.default_policy


def test_policy_auto_backend_defers_to_legacy_flag(monkeypatch):
    """backend="auto" resolves from the platform alone: the kernels on a
    TPU backend, the XLA reference path on any other."""
    assert api.ExecutionPolicy().impl() == "ref"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert api.ExecutionPolicy().impl() == "pallas"
    assert api.ExecutionPolicy(backend="ref").impl() == "ref"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert api.ExecutionPolicy().impl() == "ref"
    assert api.ExecutionPolicy(backend="pallas").impl() == "pallas"


def test_policy_object_installable_verbatim():
    pol = api.ExecutionPolicy(format="fp8a", backend="ref", bk=64)
    with api.policy(pol):
        assert api.current_policy() == pol
    with api.policy(pol, format="int8"):
        assert api.current_policy() == pol.override(format="int8")


# ======================================================================
# Registry dispatch
# ======================================================================

def test_registry_lists_all_six_ops_with_both_impls():
    ops = api.registry.ops()
    assert ops == ["attention", "depthwise_conv", "grouped_matmul",
                   "matmul", "matmul_codes", "quantize"]
    for op in ops:
        want = ["pallas", "pallas-decode", "pallas-prefill", "ref"] \
            if op == "attention" else ["pallas", "ref"]
        assert api.registry.implementations(op) == want


def test_registry_unknown_key_raises_with_catalog():
    with pytest.raises(KeyError, match="matmul"):
        api.registry.lookup("matmul", "cuda")


def test_fresh_registry_dispatches_by_key():
    reg = KernelRegistry()
    reg._loaded = True                              # no kernel autoload

    @reg.register("op", "ref")
    def ref_impl(*, policy):
        return ("ref", policy.format)

    @reg.register("op", "pallas")
    def pallas_impl(*, policy):
        return ("pallas", policy.format)

    pol = api.ExecutionPolicy(format="int8")
    assert reg.dispatch("op", "ref", policy=pol) == ("ref", "int8")
    assert reg.dispatch("op", "pallas", policy=pol) == ("pallas", "int8")


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("op,args", [
    ("matmul", lambda: (randn(64, 64), randn(64, 64))),
    ("quantize", lambda: (randn(32, 64),)),
])
def test_dispatch_reaches_selected_impl(op, args, impl, monkeypatch):
    """`backend=` must route to exactly the registered (op, impl) callable."""
    sentinel = {}
    real = api.registry.lookup(op, impl)

    def spy(*a, **kw):
        sentinel["impl"] = impl
        return real(*a, **kw)

    monkeypatch.setitem(api.registry._impls, (op, impl), spy)
    backend = "pallas" if impl == "pallas" else "ref"
    getattr(api.ops, op)(*args(), backend=backend)
    assert sentinel.get("impl") == impl


# ======================================================================
# (op x format x impl) parity with the legacy kwarg surface
# ======================================================================

@pytest.mark.parametrize("fmt", ["bf16", "fp8a", "fp8b", "int8", "int4"])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_matmul_matches_legacy_prefer_pallas(fmt, impl):
    x, w = randn(96, 80), randn(80, 72)
    prefer = impl == "pallas"
    legacy = aio_matmul(x, w, mode=fmt, prefer_pallas=prefer)
    with api.policy(format=fmt, backend=impl):
        new = api.ops.matmul(x, w)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(new))


@pytest.mark.parametrize("fmt", ["fp8a", "int8", "int4"])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_quantize_matches_legacy(fmt, impl):
    x = randn(48, 96, scale=2.0)
    prefer = impl == "pallas"
    lc, ls = aio_quantize(x, fmt_name=fmt, prefer_pallas=prefer)
    with api.policy(format=fmt, backend=impl):
        nc, ns = api.ops.quantize(x)
    np.testing.assert_array_equal(np.asarray(lc), np.asarray(nc))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(ns))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_attention_matches_legacy(impl):
    q = randn(1, 4, 128, 32, scale=0.3)
    k = randn(1, 2, 128, 32, scale=0.3)
    v = randn(1, 2, 128, 32)
    prefer = impl == "pallas"
    legacy = attention(q, k, v, causal=True, prefer_pallas=prefer)
    with api.policy(backend=impl):
        new = api.ops.attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(new))


def test_attention_pallas_falls_back_on_unaligned_lq():
    q, k, v = randn(1, 2, 100, 16), randn(1, 2, 100, 16), randn(1, 2, 100, 16)
    with api.policy(backend="pallas"):
        out = api.ops.attention(q, k, v)           # Lq % 128 != 0 -> ref
    ref = api.ops.attention(q, k, v, backend="ref")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_depthwise_matches_legacy(impl):
    x, f = randn(1, 12, 10, 24), randn(3, 3, 24)
    prefer = impl == "pallas"
    legacy = depthwise_conv(x, f, prefer_pallas=prefer)
    with api.policy(backend=impl):
        new = api.ops.depthwise_conv(x, f)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(new))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_grouped_matmul_matches_legacy(impl):
    x = randn(256, 40)
    w = randn(2, 40, 48)
    prefer = impl == "pallas"
    legacy = grouped_matmul(x, w, (128, 128), prefer_pallas=prefer)
    with api.policy(backend=impl):
        new = api.ops.grouped_matmul(x, w, (128, 128))
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(new))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_morphable_multi_gemm_matches_legacy(impl):
    tenants = [(randn(100, 64), randn(64, 96)), (randn(60, 40), randn(40, 30))]
    prefer = impl == "pallas"
    legacy_res, legacy_util = morphable_multi_gemm(tenants,
                                                   prefer_pallas=prefer)
    with api.policy(backend=impl):
        new_res, new_util = api.ops.morphable_multi_gemm(tenants)
    assert legacy_util == new_util
    for a, b in zip(legacy_res, new_res):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ======================================================================
# One policy drives every op (the acceptance-criterion scenario)
# ======================================================================

def test_one_policy_changes_every_op_without_per_call_kwargs():
    x, w = randn(64, 64), randn(64, 64)
    gx, gw = randn(128, 32), randn(1, 32, 32)
    with api.policy(format="int4", backend="ref"):
        out_mm = api.ops.matmul(x, w)
        out_q, _ = api.ops.quantize(x)
        out_g = api.ops.grouped_matmul(gx, gw, (128,))
    # matmul really ran int4: identical to explicitly-int4, not to bf16
    np.testing.assert_array_equal(
        np.asarray(out_mm),
        np.asarray(aio_matmul(x, w, mode="int4", prefer_pallas=False)))
    assert not np.allclose(
        np.asarray(out_mm),
        np.asarray(aio_matmul(x, w, mode="bf16", prefer_pallas=False)))
    # quantize really ran int4: codes identical to the explicit-int4 path
    ref_q, _ = aio_quantize(x, fmt_name="int4", prefer_pallas=False)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(ref_q))
    assert out_g.shape == (128, 32)


def test_irrelevant_policy_fields_do_not_retrace_matmul():
    """Dispatch reduces the policy to the fields the op consumes, so e.g. an
    attention-only `chunk` override must not recompile matmuls."""
    from repro.kernels.aio_matmul.ops import _matmul_ref
    x, w = jnp.ones((16, 16)), jnp.ones((16, 16))
    api.ops.matmul(x, w, backend="ref")
    before = _matmul_ref._cache_size()
    with api.policy(backend="ref", chunk=4096, bh=4):   # matmul-irrelevant
        api.ops.matmul(x, w)
    assert _matmul_ref._cache_size() == before
    with api.policy(backend="ref", bk=64):              # matmul-relevant
        api.ops.matmul(x, w)
    assert _matmul_ref._cache_size() == before + 1


def test_per_call_override_beats_ambient_policy():
    x, w = randn(64, 64), randn(64, 64)
    with api.policy(format="bf16", backend="ref"):
        out = api.ops.matmul(x, w, format="int8")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(aio_matmul(x, w, mode="int8", prefer_pallas=False)))


# ===================================================== lookup error messages
def test_lookup_unknown_op_lists_registered_ops():
    with pytest.raises(KeyError, match="unknown op 'convolve3d'") as ei:
        api.registry.lookup("convolve3d", "pallas")
    msg = str(ei.value)
    assert "attention" in msg and "matmul" in msg and "quantize" in msg


def test_lookup_unknown_impl_lists_available_impls():
    with pytest.raises(KeyError, match="no 'cuda' implementation") as ei:
        api.registry.lookup("attention", "cuda")
    msg = str(ei.value)
    assert "pallas-decode" in msg and "pallas-prefill" in msg and "ref" in msg


# ========================================================== policy nesting
def test_policy_stack_pops_on_exception():
    base = api.current_policy()
    with pytest.raises(RuntimeError, match="boom"):
        with api.policy(format="int4"):
            assert api.current_policy().format == "int4"
            raise RuntimeError("boom")
    assert api.current_policy() == base


def test_policy_stack_unwinds_nested_exception_to_outer_level():
    with api.policy(format="int8"):
        with pytest.raises(ValueError, match="inner"):
            with api.policy(bm=64):
                raise ValueError("inner")
        assert api.current_policy().format == "int8"
        assert api.current_policy().bm == 128         # inner level gone
    assert api.current_policy() == api.default_policy


def test_override_ignores_none_and_leaves_original_frozen():
    p = api.ExecutionPolicy(format="int8")
    q = p.override(bm=64, bn=None)
    assert (q.bm, q.bn, q.format) == (64, 128, "int8")
    assert p.bm == 128                                # p untouched
    assert p.override() is p                          # no-op returns self


def test_current_policy_defaults_outside_any_context():
    assert api.current_policy() == api.default_policy
    assert api.current_policy().backend == "auto"


# ============================================================ policy sweep
def test_policy_sweep_is_cartesian_product_of_tile_values():
    pols = api.policy_sweep(("bm", "bkv"))
    assert {(p.bm, p.bkv) for p in pols} == {
        (128, 128), (128, 16), (64, 128), (64, 16)}
    assert all(p.bn == 128 for p in pols)             # unswept stays default


def test_policy_sweep_empty_fields_yields_base_only():
    (p,) = api.policy_sweep(())
    assert p == api.default_policy


def test_policy_sweep_rejects_non_tile_field():
    with pytest.raises(ValueError, match="format"):
        api.policy_sweep(("format",))


def test_policy_sweep_custom_values_on_custom_base():
    base = api.ExecutionPolicy(format="int4")
    pols = api.policy_sweep(("bm",), base=base, values={"bm": (32, 16)})
    assert [p.bm for p in pols] == [32, 16]
    assert all(p.format == "int4" for p in pols)
