"""Several chips: ``DistributedPipeline`` over a mesh of shard stores.

The state is sharded once (``shard_state`` in the store's own ordering,
so each shard's path state is its block store), every call of the
K-step program (``run_fn``) exchanges deep halos between the shards and
runs the fused kernel on each, and the state is gathered once
(``unshard_state``) for the comparison.
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

KERNEL = "stencil_step_fused"


class Driver:
    def __init__(self, config: dict, devices):
        from repro.core.layout import store_spec
        from repro.stencil import DistributedPipeline, make_stencil_mesh

        c = config
        self.mesh = make_stencil_mesh(tuple(c["mesh"]))
        self.spec = store_spec(c["kind"], c["T"])
        self.pipe = DistributedPipeline(mesh=self.mesh, spec=self.spec, M=c["M"],
                                        T=c["T"], g=c["g"], S=c["S"],
                                        rule=c["rule"], bc=c["bc"])
        self.channels = self.pipe.channels
        self.box = self.pipe.global_shape
        self.device = devices[0]

    def load(self, init):
        """(C, Gk, Gi, Gj) canonical state on one chip -> one shard per chip."""
        from repro.stencil import STENCIL_AXES, shard_state

        spec, procs, C = self.spec, self.pipe.procs, self.channels
        state = jax.jit(lambda x: shard_state(x[0] if C == 1 else x, spec, procs),
                        donate_argnums=0)(init)
        axes = STENCIL_AXES + ((None,) if C > 1 else ())
        return jax.device_put(state, NamedSharding(self.mesh, PartitionSpec(*axes)))

    def compile(self, n_steps: int, state):
        return self.pipe.run_fn(n_steps).lower(state).compile()

    def readback(self, state):
        """Shards -> (C, Gk, Gi, Gj) canonical state on the first chip."""
        from repro.stencil import unshard_state

        spec, shape = self.spec, (self.channels,) + self.box
        box = jax.jit(lambda s: unshard_state(s, spec, self.box).reshape(shape))(state)
        return jax.device_put(box, self.device)


def work(config: dict) -> dict:
    """What one timestep must do, whatever implements it: sites of the
    whole box, and per chip the HBM floor (one read and one write of the
    C-channel state per fused launch of S steps) and the tap-sum FLOPs."""
    c = config
    local = c["M"] ** 3
    return {"sites": local * math.prod(c["mesh"]),
            "hbm_bytes": 2 * c["C"] * local * jnp.dtype(c["dtype"]).itemsize / c["S"],
            "flops": 2 * (2 * c["g"] + 1) ** 3 * c["C"] * local}
