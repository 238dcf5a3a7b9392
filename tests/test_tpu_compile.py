"""Ahead-of-time compiles of the aggregation kernels for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests need no accelerator. They catch
what interpret mode cannot: a kernel whose blocks do not fit the chip's
scoped VMEM. The sizes are the paper CNN's full flat width (N=40,
P=316,554) and the wide-population shapes the block sizing must handle.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.aggregate import ops
from repro.kernels.aggregate.aggregate import (
    masked_scaled_aggregate_kernel,
    masked_scaled_aggregate_update_kernel,
)

# N=10240 f32 is the one case whose footprint tops the 16 MiB budget even
# at bp=128, so its VMEM limit is raised past the default.
CASES = [(40, 316_554), (1024, 1 << 20), (4096, 100_000),
         (10240, 100_000)]
VARIANTS = ["plain", "masked", "update", "delta"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lower(variant, n, p, dtype, sharding):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    g, vec = spec((n, p), dtype), spec((n,), jnp.float32)
    eta, params = spec((), jnp.float32), spec((p,), jnp.float32)
    bp = ops._fit_block(n, jnp.dtype(dtype).itemsize, 2048)
    if variant == "plain":
        fn = lambda g, w: masked_scaled_aggregate_kernel(g, w, block_p=bp)
        args = (g, vec)
    elif variant == "masked":
        fn = lambda g, w, m: masked_scaled_aggregate_kernel(g, w, m,
                                                            block_p=bp)
        args = (g, vec, vec)
    elif variant == "update":
        fn = lambda g, w, e, pr, m: masked_scaled_aggregate_update_kernel(
            g, w, e, pr, m, block_p=bp)
        args = (g, vec, eta, params, vec)
    else:
        fn = lambda g, w, e, m: masked_scaled_aggregate_update_kernel(
            g, w, e, None, m, block_p=bp)
        args = (g, vec, eta, vec)
    return jax.jit(fn).lower(*args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n,p", CASES, ids=[f"N{n}xP{p}" for n, p in CASES])
@pytest.mark.parametrize("variant", VARIANTS)
def test_aggregation_kernel_compiles_for_v5e(one_chip, variant, n, p, dtype):
    compiled = _lower(variant, n, p, dtype, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
