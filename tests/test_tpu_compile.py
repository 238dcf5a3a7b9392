"""Ahead-of-time compiles of the aggregation kernels for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests need no accelerator. They catch
what interpret mode cannot: a kernel whose blocks do not fit the chip's
scoped VMEM. The sizes are the paper CNN's full flat width (N=40,
P=316,554) and the wide-population shapes the block sizing must handle.
A small CNN's simulator step is compiled too, to see the layout copies
XLA would put around its client gradients, which no jaxpr shows.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this module.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import ClientSimulator, make_scheduler
from repro.core.energy import BinaryArrivals
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


def test_tree_gradient_step_makes_no_flat_client_buffer_on_v5e(one_chip):
    """The step of a CNN whose client gradients arrive as its parameter
    tree, vmapped over seeds as the engine runs it: the compiled program
    writes no f32 value shaped ``(..., N, P)`` or wider, so no pad and no
    ``dynamic-update-slice`` of a raveled ``(N, P)`` client buffer."""
    from repro.models.cnn import cnn_loss, init_cnn
    from repro.optim import sgd

    n, seeds, hw, batch = 8, 2, 8, 2
    grad_one = jax.grad(cnn_loss)

    def grads_fn(params, key, t):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (n, batch, hw, hw, 3))
        y = jax.random.randint(ky, (n, batch), 0, 10)
        return jax.vmap(lambda xi, yi: grad_one(params, xi, yi))(x, y)

    sim = ClientSimulator(grads_fn=grads_fn,
                          scheduler=make_scheduler("alg1", n),
                          energy=BinaryArrivals([0.5] * n),
                          p=jnp.full((n,), 1.0 / n), optimizer=sgd(0.05),
                          use_kernel=True)
    shapes = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0),
                                             image_hw=hw))
    p_total = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    keys = jax.ShapeDtypeStruct((seeds, 2), jnp.uint32, sharding=one_chip)
    run = jax.vmap(lambda k, p: sim.run(k, p, 3)[0], in_axes=(0, None))
    text = jax.jit(run).lower(keys, params).compile().as_text()
    flat = re.compile(rf"= f32\[(?:\d+,)*{n},(\d+)\]\S* ([\w-]+)\(")
    found = [m.group(2) for m in map(flat.search, text.splitlines())
             if m and int(m.group(1)) >= p_total]
    assert found == [], f"(..., {n}, >={p_total}) f32 values: {found}"
