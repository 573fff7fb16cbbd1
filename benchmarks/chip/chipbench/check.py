"""The comparison that decides ``correct``.

The initial state is drawn from the seed plane by plane (``init_planes``
of the rule's reference module), so the same seed gives the same state
whatever the box. :func:`advance` runs the plain reference for as many
steps as the program ran, on one device, in one state buffer that each
step overwrites a chunk of planes at a time, so that it fits beside the
program's result. :func:`gaps` then gives the widest gap between the
program's final state and the reference's, against the reference's
largest magnitude.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

MASK32 = 0xFFFFFFFF
# planes per piece of the reference's sweep: its temporaries are a few
# such slabs, small beside the state at every cell's size
REF_CHUNK_PLANES = 16


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: both 32-bit halves count."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & MASK32), seed >> 32)


@functools.partial(jax.jit, static_argnames=("init_planes", "box", "dtype"))
def initial_state(key, *, init_planes, box, dtype=jnp.float32):
    """The seeded ``(C, Gk, Gi, Gj)`` initial state."""
    gk, gi, gj = box
    return init_planes(key, jnp.arange(gk), (gi, gj)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("step", "params", "chunk"),
                   donate_argnums=0)
def advance(x, n_steps, *, step, params, chunk):
    """``n_steps`` reference steps of the periodic ``(C, Gk, Gi, Gj)`` box
    ``x`` (donated), computed in ``x``'s own dtype.

    One state buffer: a step overwrites it ``chunk`` planes at a time, in
    order, keeping aside the g old planes below the chunk being written
    and the g old planes at the start of the box, which the last chunk
    reads across the wrap.
    """
    pd = dict(params)
    g = pd["g"]
    gk = x.shape[1]
    if gk % chunk or chunk < g:
        raise ValueError(f"chunk {chunk} does not divide {gk} planes into g={g} or more")
    last = gk // chunk - 1

    def one_step(_, x):
        first = x[:, :g]

        def body(c, carry):
            x, below = carry
            k0 = c * chunk
            mid = jax.lax.dynamic_slice_in_dim(x, k0, chunk, axis=1)
            above = jnp.where(c == last, first, jax.lax.dynamic_slice_in_dim(
                x, jnp.minimum(k0 + chunk, gk - g), g, axis=1))
            new = step(jnp.concatenate([below, mid, above], axis=1), pd)
            x = jax.lax.dynamic_update_slice_in_dim(x, new[:, g:g + chunk], k0, axis=1)
            return x, mid[:, chunk - g:]

        return jax.lax.fori_loop(0, last + 1, body, (x, x[:, gk - g:]))[0]

    return jax.lax.fori_loop(0, n_steps, one_step, x)


@jax.jit
def gaps(got, want):
    """(widest |got - want|, largest |want|, non-finite sites of got)."""
    g32, w32 = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.max(jnp.abs(g32 - w32)), jnp.max(jnp.abs(w32)),
            jnp.sum(~jnp.isfinite(g32)))


def rule_params(config: dict) -> tuple:
    """The rule's parameters (``params`` and ``g``), hashable for jit."""
    return tuple(sorted(dict(config.get("params", {}), g=config["g"]).items()))


def reference(ref, config: dict, key, n_steps: int, box, dtype=jnp.float32,
              device=None):
    """The reference's state after ``n_steps`` from the seeded state,
    swept ``REF_CHUNK_PLANES`` planes at a time (fewer where they do not
    divide the box)."""
    with jax.default_device(device or jax.devices()[0]):
        x = initial_state(key, init_planes=ref.init_planes, box=tuple(box),
                          dtype=jnp.dtype(dtype))
        return advance(x, n_steps, step=ref.step, params=rule_params(config),
                       chunk=math.gcd(REF_CHUNK_PLANES, box[0]))


def readings(got, want) -> dict:
    """The numbers compared, from a program state and a reference state."""
    err, mag, bad = (float(v) for v in gaps(got, want))
    return {"max_rel_err": err / mag if mag > 0 else float("inf"),
            "nonfinite": int(bad)}


def judge(values: dict, limits: dict) -> bool:
    """Every number compared within its limit (NaN never is)."""
    return all(values[k] <= limits[k] for k in limits)
