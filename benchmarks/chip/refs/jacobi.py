"""Plain reference of the ``jacobi`` rule: the explicit heat step.

Every site becomes the mean of its (2g+1)^3 neighbourhood, itself
included, on a box that wraps on every axis. Written from that
definition alone, in ``jax.numpy``, and computed in the dtype of the
state it is given.
"""

import jax
import jax.numpy as jnp

CHANNELS = 1


def init_planes(key, planes, plane_shape):
    """Initial temperature, uniform on [0, 1), one draw per plane index."""
    def one(p):
        return jax.random.uniform(jax.random.fold_in(key, p), plane_shape,
                                  jnp.float32)
    return jax.vmap(one)(planes)[None]


def box_sum(x, g):
    """Sum over the (2g+1)^3 neighbourhood of the last three axes, one
    axis at a time, each wrapped by g sites."""
    for ax in range(x.ndim - 3, x.ndim):
        pad = [(0, 0)] * x.ndim
        pad[ax] = (g, g)
        window = [1] * x.ndim
        window[ax] = 2 * g + 1
        x = jax.lax.reduce_window(jnp.pad(x, pad, mode="wrap"), jnp.zeros((), x.dtype),
                                  jax.lax.add, tuple(window), (1,) * x.ndim, "VALID")
    return x


def step(x, params):
    """One step of the ``(1, K, I, J)`` box ``x``."""
    g = params["g"]
    return box_sum(x, g) / jnp.asarray((2 * g + 1) ** 3, x.dtype)
