"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Randomized property-based variants live in ``test_kernels_properties.py``
(skipped cleanly when ``hypothesis`` is unavailable).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aggregate import (
    masked_scaled_aggregate,
    masked_scaled_aggregate_ref,
)
from repro.kernels.aggregate.aggregate import masked_scaled_aggregate_kernel
from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssm_scan.ops import gla_scan
from repro.kernels.ssm_scan.ref import gla_scan_ref
from repro.kernels.ssm_scan.ssm_scan import gla_scan_kernel


# ------------------------------------------------------------- aggregate

@pytest.mark.parametrize("n,p,block_p", [
    (8, 64, 32), (40, 1000, 256), (3, 130, 128), (129, 257, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aggregate_sweep(n, p, block_p, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    g = jax.random.normal(k1, (n, p)).astype(dtype)
    w = jax.random.uniform(k2, (n,))
    out = masked_scaled_aggregate_kernel(g, w, block_p=block_p,
                                         interpret=True)
    ref = masked_scaled_aggregate_ref(g, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_aggregate_masking_zeroes_clients():
    g = jnp.ones((4, 16))
    w = jnp.asarray([0.0, 2.0, 0.0, 1.0])
    out = masked_scaled_aggregate(g, w)
    np.testing.assert_allclose(out, 3.0)


@pytest.mark.ragged
@pytest.mark.parametrize("in_dtype,out_dtype", [
    (jnp.float32, None), (jnp.bfloat16, jnp.float32),
])
def test_aggregate_mask_operand_rows_are_exact_zeros(in_dtype, out_dtype):
    """The mask operand is a row *select* on the tiled reduction: a
    masked row contributes exactly 0 — not an epsilon — even when its
    weight is nonzero and its contents are inf/NaN garbage (a ×0
    multiply would produce NaN)."""
    n, p = 6, 300
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    g = jax.random.normal(k1, (n, p)).astype(in_dtype)
    # rows 1 and 4 are dead: poison them with non-finite garbage
    garbage = jnp.full((p,), jnp.inf, in_dtype)
    g = g.at[1].set(garbage).at[4].set(jnp.nan)
    w = jax.random.uniform(k2, (n,)) + 0.5  # all weights nonzero
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    out = masked_scaled_aggregate_kernel(g, w, mask, block_p=128,
                                         interpret=True,
                                         out_dtype=out_dtype)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    ref = masked_scaled_aggregate_ref(
        jnp.where(mask[:, None] > 0, g, jnp.zeros((), in_dtype)), w)
    tol = 1e-6 if in_dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    # exactness, not epsilon: a masked-only change to g leaves the
    # output bit-identical
    g2 = g.at[1].set(-garbage).at[4].set(1e30)
    out2 = masked_scaled_aggregate_kernel(g2, w, mask, block_p=128,
                                          interpret=True,
                                          out_dtype=out_dtype)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out2, np.float32))


@pytest.mark.ragged
def test_aggregate_mask_none_is_bit_identical_to_unmasked():
    """mask=None keeps the original two-operand program (no behavior
    drift for uniform populations); an all-ones mask agrees exactly."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    g = jax.random.normal(k1, (7, 130))
    w = jax.random.uniform(k2, (7,))
    base = masked_scaled_aggregate_kernel(g, w, block_p=64, interpret=True)
    ones = masked_scaled_aggregate_kernel(g, w, jnp.ones((7,)), block_p=64,
                                          interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(ones))
    np.testing.assert_allclose(np.asarray(base),
                               np.asarray(masked_scaled_aggregate_ref(g, w)),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,h,hkv,s,dh,causal,window,bq,bk", [
    (1, 2, 1, 64, 16, True, 0, 16, 16),
    (2, 4, 2, 128, 32, True, 0, 32, 32),
    (1, 2, 2, 128, 16, True, 32, 32, 32),
    (1, 8, 1, 64, 64, True, 0, 16, 16),      # extreme GQA
    (1, 2, 1, 64, 16, False, 0, 16, 16),     # bidirectional
    (1, 1, 1, 256, 16, True, 64, 64, 64),    # long + window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, hkv, s, dh, causal, window, bq, bk,
                               dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, s, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, dh)).astype(dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-3 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_attention():
    """Kernel result == the model's _sdpa reference path."""
    from repro.models.attention import _sdpa, causal_mask
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, s, h, hkv, dh = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    out_k = flash_attention_kernel(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=True, window=0, block_q=16, block_k=16,
        interpret=True).swapaxes(1, 2)
    out_m = _sdpa(q, k, v, causal_mask(s))
    np.testing.assert_allclose(out_k, out_m, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------- ssm scan

@pytest.mark.parametrize("b,s,h,dk,dv,chunk", [
    (1, 32, 2, 8, 8, 8), (2, 64, 3, 16, 32, 16), (1, 50, 1, 4, 4, 16),
])
def test_gla_scan_sweep(b, s, h, dk, dv, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    a = jax.random.uniform(ks[0], (b, s, h), minval=0.6, maxval=1.0)
    k = jax.random.normal(ks[1], (b, s, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, dv))
    q = jax.random.normal(ks[3], (b, s, h, dk)) * 0.3
    y = gla_scan(a, k, v, q, chunk=chunk)
    fold = lambda x: x.swapaxes(1, 2).reshape((b * h, s) + x.shape[3:])
    ref = gla_scan_ref(fold(a), fold(k), fold(v), fold(q)) \
        .reshape(b, h, s, dv).swapaxes(1, 2)
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------------- compiler-params binding

def test_compiler_params_shim_resolves_installed_symbol():
    """``repro.kernels.CompilerParams`` is the installed jax's one TPU
    compiler-params dataclass, and the aggregation kernels' VMEM limit
    rides it."""
    from jax.experimental.pallas import tpu as pltpu

    import repro.kernels as rk
    from repro.kernels.aggregate import aggregate as agg

    assert rk.CompilerParams is pltpu.CompilerParams
    params = agg._compiler_params(1024, 1024, 4)
    assert isinstance(params, rk.CompilerParams)
    assert params.vmem_limit_bytes >= agg.vmem_bytes(1024, 1024, 4)


def test_kernel_modules_use_shared_shim():
    """No kernel module keeps a private getattr-probe: they all bind the
    package-level object."""
    import importlib

    import repro.kernels as rk

    fa = importlib.import_module("repro.kernels.flash_attention.flash_attention")
    ss = importlib.import_module("repro.kernels.ssm_scan.ssm_scan")
    ag = importlib.import_module("repro.kernels.aggregate.aggregate")
    assert fa._CompilerParams is rk.CompilerParams
    assert ss._CompilerParams is rk.CompilerParams
    assert ag.CompilerParams is rk.CompilerParams


def test_aggregate_vmem_limit_never_exceeds_physical_vmem():
    """A client axis too wide for one core's VMEM even at bp=128 is
    refused by name, not handed to Mosaic as an impossible limit."""
    from repro.kernels.aggregate import aggregate as agg

    ok = agg._compiler_params(10240, 128, 4)
    assert agg.VMEM_BUDGET < ok.vmem_limit_bytes <= agg.VMEM_PHYSICAL
    with pytest.raises(ValueError, match=r"N=100000, bp=128"):
        agg._compiler_params(100_000, 128, 4)


@pytest.mark.parametrize("variant", ["plain", "masked", "update", "delta"])
def test_aggregate_kernel_bodies_contract_at_f32(variant):
    """Every aggregation kernel body asks for ``Precision.HIGHEST``: Mosaic's
    default rounds f32 operands to bf16 on the MXU, which breaks the f32
    contract the jnp matvec keeps (DESIGN.md §9)."""
    from repro.kernels.aggregate.aggregate import (
        masked_scaled_aggregate_update_kernel,
    )

    g, w = jnp.ones((8, 256)), jnp.ones((8,))
    fn = {"plain": lambda: masked_scaled_aggregate_kernel(g, w),
          "masked": lambda: masked_scaled_aggregate_kernel(g, w, w),
          "update": lambda: masked_scaled_aggregate_update_kernel(
              g, w, 0.1, jnp.ones((256,))),
          "delta": lambda: masked_scaled_aggregate_update_kernel(
              g, w, 0.1)}[variant]

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(jax.make_jaxpr(fn)().jaxpr))
    assert found
    assert all(eqn.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
               for eqn in found)
