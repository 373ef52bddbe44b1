"""The control's rounding, shared by the plain references.

``round_to(precision)`` gives the function a reference applies to the
operands of every convolution and matrix product before the product
(accumulation stays float32).  ``float32``: nothing is rounded.  ``float8``:
the usual 8-bit training recipe, one step below bfloat16: operands are
rounded to e4m3 on the way forward and their cotangents to e5m2 on the way
back, each scaled by its tensor's largest magnitude so that nothing
overflows or is flushed to zero.  (A bfloat16 round trip is a no-op under XLA
on the TPU, which keeps the excess precision; float8 round trips are kept.)
"""

import jax
import jax.numpy as jnp

CONTROL = {"float8": ("float8_e4m3fn", 448.0, "float8_e5m2", 57344.0)}


def round_to(precision):
    if precision == "float32":
        return lambda a: a
    fwd, fwd_max, bwd, bwd_max = CONTROL[precision]

    def quantize(a, dtype, top):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return (a / scale).astype(dtype).astype(jnp.float32) * scale

    @jax.custom_vjp
    def rnd(a):
        return quantize(a, fwd, fwd_max)

    rnd.defvjp(lambda a: (rnd(a), None),
               lambda _, g: (quantize(g, bwd, bwd_max),))
    return rnd
