"""What the tests of the two models' rematerialisation share: the values a
rematerialised block keeps from its forward pass, and the kernels its
gradient calls."""

import math
import re
from collections import Counter

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals  # print_saved_residuals'


def _objective(block, mutable):
    """A plain sum of the block's output: it keeps nothing of its own."""
    def objective(variables, x):
        out = block.apply(variables, x, mutable=mutable)
        return jnp.sum(out[0] if mutable else out)
    return objective


def held(block, variables, x, mutable=False):
    """(what the block's gradient keeps from the forward pass beyond the
    arguments of ``apply``, as a count by (shape, dtype); the arguments it
    keeps, likewise)."""
    kept, args = Counter(), Counter()
    for aval, why in saved_residuals(_objective(block, mutable), variables,
                                     x):
        (args if "from the argument" in why else kept)[
            (tuple(aval.shape), jnp.dtype(aval.dtype).name)] += 1
    return kept, args


def held_bytes(kept):
    return sum(n * jnp.dtype(dtype).itemsize * math.prod(shape)
               for (shape, dtype), n in kept.items())


def kernel_calls(block, variables, x, mutable=False):
    """The ``pallas_call``s in the jaxpr of the block's gradient: (those
    with a ``flash_`` or ``grouped_mm`` name of their own, by name; all of
    them)."""
    text = str(jax.make_jaxpr(jax.grad(_objective(block, mutable)))(
        variables, x))
    return (Counter(re.findall(
        r"\bname=(flash_(?:fwd|bwd)\w*|grouped_mm\w*)", text)),
            text.count("pallas_call["))
