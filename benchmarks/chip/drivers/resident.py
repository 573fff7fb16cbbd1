"""One chip: ``ResidentPipeline`` over the curve-ordered block store.

The state enters the store once (``to_blocks``), stays there for every
call of the K-step program (``run_fn``, the fused kernel, the store
donated), and leaves it once (``to_cube``) for the comparison.
"""

import jax
import jax.numpy as jnp

KERNEL = "stencil_step_fused"


class Driver:
    def __init__(self, config: dict, devices):
        from repro.stencil import ResidentPipeline

        c = config
        self.pipe = ResidentPipeline(M=c["M"], T=c["T"], g=c["g"], kind=c["kind"],
                                     S=c["S"], rule=c["rule"], bc=c["bc"])
        self.channels = self.pipe.channels
        self.box = (c["M"],) * 3

    def load(self, init):
        """(C, M, M, M) canonical state -> the block store."""
        pipe, C = self.pipe, self.channels
        to_blocks = jax.jit(lambda x: pipe.to_blocks(x[0] if C == 1 else x),
                            donate_argnums=0)
        return to_blocks(init)

    def compile(self, n_steps: int, state):
        return self.pipe.run_fn(n_steps).lower(state).compile()

    def readback(self, state):
        """Block store -> (C, M, M, M) canonical state on the first chip."""
        pipe, shape = self.pipe, (self.channels,) + self.box
        to_cube = jax.jit(lambda s: pipe.to_cube(s).reshape(shape),
                          donate_argnums=0)
        return to_cube(state)


def work(config: dict) -> dict:
    """What one timestep must do, whatever implements it: sites of the
    whole box, and per chip the HBM floor (one read and one write of the
    C-channel state per fused launch of S steps) and the tap-sum FLOPs."""
    c = config
    local = c["M"] ** 3
    return {"sites": local,
            "hbm_bytes": 2 * c["C"] * local * jnp.dtype(c["dtype"]).itemsize / c["S"],
            "flops": 2 * (2 * c["g"] + 1) ** 3 * c["C"] * local}
