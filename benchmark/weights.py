"""Weights made on the device, from ``--seed``, in one jitted call.

A reference module (``benchmark/reference/<file>.py``) states the tree of
parameters its architecture has: ``param_specs(model) -> pytree of Spec``.
The same tree, made once here, is what the program serves or trains AND what
the reference computes with — neither makes weights of its own.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple


class Spec(NamedTuple):
    """One parameter leaf. ``init``: ``normal`` (std ``scale``), ``ones`` or
    ``zeros``; ``dtype``: the type it is served or trained in."""
    shape: Tuple[int, ...]
    dtype: str
    init: str
    scale: float = 1.0


def _is_spec(x):
    return isinstance(x, Spec)


def make(specs, seed, sharding=None):
    """The parameter tree of ``specs``: every leaf drawn on the device, all
    in one jitted program, committed to ``sharding`` when given. Normal
    leaves are drawn in float32 and cast, so a bf16 tree is the rounding of
    the float32 one."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)

    def build(key):
        out = []
        for i, sp in enumerate(leaves):
            dt = jnp.dtype(sp.dtype)
            if sp.init == "normal":
                v = (jax.random.normal(jax.random.fold_in(key, i), sp.shape,
                                       jnp.float32) * sp.scale).astype(dt)
            elif sp.init == "ones":
                v = jnp.ones(sp.shape, dt)
            elif sp.init == "zeros":
                v = jnp.zeros(sp.shape, dt)
            else:
                raise ValueError("unknown init %r" % (sp.init,))
            out.append(v)
        return out

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    fn = jax.jit(build) if sharding is None else jax.jit(
        build, out_shardings=[sharding] * len(leaves))
    return jax.tree_util.tree_unflatten(treedef, fn(key))

