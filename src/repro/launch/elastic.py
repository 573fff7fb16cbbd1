"""Elastic re-scaling: restore a checkpoint onto a different mesh.

The fault-tolerance story at 1000+ nodes (DESIGN.md §6): when a pod (or
any 2^k slice) is lost, the job restarts on the surviving mesh; because
checkpoints store *logical* arrays, restore is a pure resharding. This
driver demonstrates/validates that end to end on host devices:

    python -m repro.launch.elastic --devices 8 --from-shape 4,2 --to-shape 2,2

It trains a few steps on mesh A, checkpoints, restores onto mesh B
(fewer "data" ways = a lost slice), continues, and asserts losses stay
finite and params match bit-exactly across the reshard.
"""

import os

if __name__ == "__main__":  # set before jax init — see dryrun.py
    import argparse

    _ap = argparse.ArgumentParser()
    _ap.add_argument("--devices", type=int, default=8)
    _ap.add_argument("--from-shape", default="4,2")
    _ap.add_argument("--to-shape", default="2,2")
    _ap.add_argument("--stencil", action="store_true",
                     help="elastic-reshard a checkpointed stencil run "
                          "instead of the training loop")
    _ap.add_argument("--from-mesh", default="2,2,2")
    _ap.add_argument("--to-mesh", default="1,1,1")
    _ap.add_argument("--local-M", type=int, default=8,
                     help="per-shard cube edge on the FROM mesh")
    _ap.add_argument("--steps", type=int, default=12)
    _ap.add_argument("--interval", type=int, default=4)
    _ap.add_argument("--kill-at", type=int, default=6)
    _ARGS = _ap.parse_args()
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_ARGS.devices}")

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint import ckpt  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data import TokenPipeline  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train import OptConfig, TrainConfig, make_train_step  # noqa: E402
from repro.train.optimizer import init_opt_state  # noqa: E402


def _mesh(shape):
    return jax.make_mesh(tuple(shape), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _shardings(mesh, model, params_abs):
    from repro.launch.dryrun import sanitize_specs
    pspecs = sanitize_specs(mesh, model.specs(), params_abs)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def main():
    enable_compile_cache()
    ckpt_dir = "/tmp/repro_elastic"
    import shutil
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    cfg = dataclasses.replace(
        get_config("smollm-360m"), n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
        activation_dtype="float32")
    model = build_model(cfg)
    pipe = TokenPipeline(vocab=cfg.vocab, batch=8, seq=32)
    tc = TrainConfig(opt=OptConfig(warmup_steps=2, total_steps=10))
    step = make_train_step(model, tc)

    from_shape = [int(x) for x in _ARGS.from_shape.split(",")]
    to_shape = [int(x) for x in _ARGS.to_shape.split(",")]

    # --- phase 1: train 3 steps on mesh A, checkpoint
    mesh_a = _mesh(from_shape)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    sh_a = _shardings(mesh_a, model, model.abstract())
    params = jax.device_put(params, sh_a)
    opt = {"m": jax.device_put(opt["m"], sh_a),
           "v": jax.device_put(opt["v"], sh_a), "step": opt["step"]}
    jstep = jax.jit(step)
    with mesh_a:
        for i in range(3):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            params, opt, m = jstep(params, opt, batch)
            print(f"[elastic] mesh {from_shape} step {i} "
                  f"loss {float(m['loss']):.4f}")
    ckpt.save(ckpt_dir, 3, {"params": params, "opt_state": opt},
              meta={"step": 3})
    host_before = jax.tree.map(np.asarray, params)

    # --- phase 2: restore onto mesh B (simulates losing a slice), continue
    mesh_b = _mesh(to_shape)
    sh_b = _shardings(mesh_b, model, model.abstract())
    tree, meta = ckpt.restore(ckpt_dir, shardings={
        "params": sh_b, "opt_state": {"m": sh_b, "v": sh_b}})
    params_b, opt_b = tree["params"], tree["opt_state"]
    opt_b["step"] = jnp.asarray(opt_b["step"])
    for a, b in zip(jax.tree.leaves(host_before),
                    jax.tree.leaves(jax.tree.map(np.asarray, params_b))):
        np.testing.assert_array_equal(a, b)
    print(f"[elastic] reshard {from_shape} -> {to_shape}: params bit-exact")
    with mesh_b:
        for i in range(meta["step"], meta["step"] + 3):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            params_b, opt_b, m = jstep(params_b, opt_b, batch)
            loss = float(m["loss"])
            print(f"[elastic] mesh {to_shape} step {i} loss {loss:.4f}")
            assert np.isfinite(loss)
    print("[elastic] OK")


def stencil_main(a):
    """Elastic reshard of a *stencil* run (DESIGN.md §10): kill a
    checkpointed run mid-flight on mesh A, resume it on mesh B with a
    different ordering/T/S, and assert the final state is bit-identical
    to an uninterrupted single-device run.

        python -m repro.launch.elastic --stencil --devices 8 \
            --from-mesh 2,2,2 --to-mesh 1,1,1 --local-M 8
    """
    import shutil

    from repro.launch.faults import (FaultPlan, SimulatedCrash,
                                     initial_state)
    from repro.stencil import (CheckpointedRun, DistributedPipeline,
                               ResidentPipeline, make_stencil_mesh)
    from repro.core import HILBERT, MORTON

    ckpt_dir = "/tmp/repro_elastic_stencil"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    procs_a = tuple(int(x) for x in a.from_mesh.split(","))
    procs_b = tuple(int(x) for x in a.to_mesh.split(","))
    gshape = tuple(p * a.local_M for p in procs_a)
    locals_b = {g // p for g, p in zip(gshape, procs_b)}
    if len(locals_b) != 1:
        raise SystemExit(f"to-mesh {procs_b} gives non-cubic locals over "
                         f"global {gshape}")
    local_b = locals_b.pop()
    state0 = initial_state("gol", gshape, seed=0)

    # --- phase 1: run on mesh A, die at --kill-at (before its checkpoint)
    pipe_a = DistributedPipeline(mesh=make_stencil_mesh(procs_a),
                                 spec=HILBERT, M=a.local_M, T=8, S=2)
    run_a = CheckpointedRun(pipe_a, ckpt_dir, interval=a.interval,
                            hooks=FaultPlan(kill_at_step=a.kill_at,
                                            kill_mode="raise").hooks())
    try:
        run_a.run(state0, a.steps)
        raise SystemExit("injected kill did not fire")
    except SimulatedCrash:
        print(f"[elastic] mesh {procs_a} killed at step {a.kill_at}")

    # --- phase 2: resume on mesh B (lost slice), new ordering/T/S
    pipe_b = DistributedPipeline(mesh=make_stencil_mesh(procs_b),
                                 spec=MORTON, M=local_b, T=4, S=1)
    out = CheckpointedRun(pipe_b, ckpt_dir,
                          interval=a.interval).run(state0, a.steps)
    print(f"[elastic] resumed on mesh {procs_b} to step {a.steps}")

    # --- reference: uninterrupted resident run over the same global box
    if len(set(gshape)) == 1:
        ref_pipe = ResidentPipeline(M=gshape[0], T=8, S=1, kind="hilbert")
        ref = np.asarray(ref_pipe.run(jnp.asarray(state0), a.steps))
        np.testing.assert_array_equal(out, ref)
        print(f"[elastic] reshard {procs_a} -> {procs_b}: "
              f"state bit-exact vs uninterrupted run")
    print("[elastic] OK")


if __name__ == "__main__":
    if _ARGS.stencil:
        stencil_main(_ARGS)
    else:
        main()
