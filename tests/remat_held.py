"""What the tests of the two models' rematerialisation share: the values a
rematerialised block keeps from its forward pass, and the kernels its
gradient calls; and what of a layer's keys and values reaches them
(``kernel_operands``, ``assert_keys_reach_the_kernels_unspread``)."""

import math
import re
from collections import Counter

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
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


def kernel_operands(fn, args, source):
    """Follow the leaves of ``args`` whose tree path ``source`` takes (say
    the key and value projections' matrices) through the jaxpr of
    ``fn(*args)`` up to the ``pallas_call``s: (each call's operand shapes,
    in order; the shapes of every value computed from those leaves
    outside a kernel).  A kernel's results count as its own."""
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    closed = jax.make_jaxpr(fn)(*args)
    calls, derived = [], []

    def walk(jaxpr, tainted):
        env = dict(zip(jaxpr.invars, tainted))
        for eqn in jaxpr.eqns:
            ins = [not isinstance(v, Literal) and env.get(v, False)
                   for v in eqn.invars]
            subs = list(jax.core.jaxprs_in_params(eqn.params))
            if eqn.primitive.name == "pallas_call":
                calls.append([tuple(v.aval.shape) for v in eqn.invars])
                outs = [False] * len(eqn.outvars)
            elif len(subs) == 1 and len(subs[0].invars) == len(ins) \
                    and len(subs[0].outvars) == len(eqn.outvars):
                outs = walk(subs[0], ins)
            else:
                outs = [any(ins)] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
                if t:
                    derived.append(tuple(v.aval.shape))
        return [not isinstance(v, Literal) and env.get(v, False)
                for v in jaxpr.outvars]

    walk(closed.jaxpr, [source(jax.tree_util.keystr(path))
                        for path, _ in leaves])
    return calls, derived


def assert_keys_reach_the_kernels_unspread(fn, args, b, s, h, kv, d):
    """The gradient ``fn(*args)`` of a layer with ``h`` query heads over
    ``kv`` key-value heads of ``d``: its forward and backward kernels take
    keys and values of ``(b * kv, s, d)``, and nothing computed from the
    ``k_proj`` and ``v_proj`` matrices outside a kernel is as large as a
    ``(b, s, h, d)`` array (what ``jnp.repeat`` made of them)."""
    calls, derived = kernel_operands(
        fn, args, lambda path: "k_proj" in path or "v_proj" in path)
    assert len(calls) == 2, calls
    for shapes in calls:
        assert Counter(shapes)[(b * kv, s, d)] == 2, shapes
    assert derived and max(math.prod(shape) for shape in derived) \
        < b * s * h * d, sorted(set(derived))
