"""Matrix products of the references, in float32 or in the control's fp8.

Every product of a reference goes through :func:`mm`.  ``"f32"`` is the
reference itself (callers run it under ``default_matmul_precision
("highest")``, so the TPU does not round the operands to bfloat16).  ``"fp8"``
is the control: each operand is scaled per tensor so that its largest
magnitude maps to the top of float8_e4m3fn, rounded to that format and scaled
back, as an fp8 training path would do, and the product is then taken in
float32.  The rounding passes the gradient straight through, so the backward
products see the rounded operands and float32 cotangents (a cast's own
gradient would round the unscaled cotangents to fp8 and flush them to 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0                      # largest finite float8_e4m3fn


@jax.custom_vjp
def quantize_fp8(a):
    """a: float32."""
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, F8_MAX / amax, 1.0)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


quantize_fp8.defvjp(lambda a: (quantize_fp8(a), None), lambda _, g: (g,))


def mm(eq: str, a, b, prec: str = "f32"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = quantize_fp8(a), quantize_fp8(b)
    elif prec != "f32":
        raise ValueError(f"unknown reference precision {prec!r}")
    return jnp.einsum(eq, a, b)
