"""Element-dtype policy shared by every Pallas entry point.

The kernels compute in the caller's *element* dtype and accumulate in
f32: every ``jnp.dot`` pins ``preferred_element_type`` to the
accumulator dtype, and results are rounded back to the caller's dtype
exactly once at the ops boundary.  Two element tiers exist:

* ``float32`` — the default; bitwise-identical to the pre-tier kernels.
* ``bfloat16`` — the mixed-precision tier (bf16 elements through the
  MXU, f32 accumulation); gated by its own tolerance tier in
  ``tests/test_conformance.py``.

Anything else raises instead of silently downcasting.  Historically the
entry points did ``.astype(jnp.float32)`` unconditionally, so an f64
caller got f32 back with no warning — masking precision loss against
the dense f64 oracle.  f64 callers now get a ``ValueError`` pointing at
the jnp strategies (scatter/segment/blocked), which preserve f64
end to end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["SUPPORTED_KERNEL_DTYPES", "ACC_DTYPE", "check_kernel_dtype",
           "contract_precision"]

#: element dtypes the Pallas kernels accept (compute dtype == input dtype)
SUPPORTED_KERNEL_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))

#: accumulator dtype — pinned, never the element dtype
ACC_DTYPE = jnp.float32


def check_kernel_dtype(name: str, *arrays) -> jnp.dtype:
    """Common element dtype of ``arrays``, validated for the Pallas tier.

    Returns the shared dtype; raises ``ValueError`` when operands mix
    dtypes (the caller must state the precision tier explicitly), when
    the dtype is f64 (no silent downcast — use a jnp strategy), or when
    the dtype is outside :data:`SUPPORTED_KERNEL_DTYPES`.
    """
    dts = {jnp.dtype(a.dtype) for a in arrays if a is not None}
    if len(dts) != 1:
        raise ValueError(
            f"{name}: operands must share one element dtype, got "
            f"{sorted(str(d) for d in dts)}; cast inputs to the intended "
            f"precision tier before the call"
        )
    (dt,) = dts
    if dt == jnp.dtype(jnp.float64):
        raise ValueError(
            f"{name}: float64 is not supported by the Pallas kernels and "
            f"would previously have been silently downcast to float32; "
            f"use strategy='scatter'/'segment'/'blocked' for f64 solves"
        )
    if dt not in SUPPORTED_KERNEL_DTYPES:
        raise ValueError(
            f"{name}: unsupported element dtype {dt}; supported tiers: "
            f"{[str(d) for d in SUPPORTED_KERNEL_DTYPES]}"
        )
    return dt


def contract_precision(dtype):
    """``precision`` of an in-kernel ``jnp.dot`` over ``dtype`` elements.

    The TPU contracts f32 operands in one bf16 pass unless told otherwise,
    which put a 1e-3 relative error into Phi on a v5e; f32 elements ask for
    full f32 contraction.  bf16 elements need nothing: the MXU multiplies
    them exactly and accumulates in f32.
    """
    if jnp.dtype(dtype) == jnp.dtype(jnp.float32):
        return jax.lax.Precision.HIGHEST
    return None
