"""Pallas TPU kernels for the compute hot-spots (validated in interpret
mode on the CPU backend; Mosaic-compiled on TPU):

* ``aggregate`` — masked/scaled client-gradient aggregation (the paper's
  server update, eq. 11/12) and the fused reduce-and-update step
* ``flash_attention`` — blockwise causal/sliding-window GQA attention
* ``ssm_scan`` — chunked gated-linear-recurrence (Mamba2 SSD / mLSTM)

Each ships ``ops.py`` (jit'd wrapper) and ``ref.py`` (pure-jnp oracle).
"""

import jax
from jax.experimental.pallas import tpu as pltpu

#: The TPU compiler-params dataclass every kernel passes to
#: ``pallas_call`` (``tests/test_kernels.py`` pins the binding).
CompilerParams = pltpu.CompilerParams


def interpret_default() -> bool:
    """Interpret mode for the CPU backend only (tests and
    ``JAX_PLATFORMS=cpu`` rehearsals). Every other backend gets the
    Mosaic-compiled kernel, so a run that expected a chip and did not
    get one fails instead of quietly running the interpreter."""
    return jax.default_backend() == "cpu"
