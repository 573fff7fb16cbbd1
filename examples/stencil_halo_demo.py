"""Distributed gol3d: 2×2×2 device mesh, SFC halo packing, ppermute rings.

Part 1: the resident-block pipeline — blockize once,
run K steps entirely in curve order with in-kernel halo streaming and
S-deep temporal blocking (stencil/pipeline.py; S substeps per HBM
round-trip), verify it against the ordering-independent reference run
(kernels/ref.gol3d_step_ref), and print the modelled per-substep HBM
bytes plus the (T, S) the plan() autotuner picks.

Part 2: on eight devices (host devices on the CPU, forced before JAX
is imported), decomposes a 32³ cube onto a 2×2×2 mesh and runs 10
steps under each ordering two ways: the legacy per-step exchange (make_distributed_step) verified against the
single-device oracle, and the communication-avoiding DistributedPipeline
(one deep S·g exchange per S fused substeps, DESIGN.md §7) verified
bit-identical to the per-step form. This is the paper's parallel
experiment (§4, second set) as a shard_map program. The same matrix
then repeats under clamped neumann0 boundaries (DESIGN.md §8) — open
exchange rings, shell-block boundary fill — and the modelled ICI
savings table prints for both boundary contracts (mesh-edge shards
skip the wrap links, so clamped shards move strictly fewer wire bytes).

Part 3: the multi-field store (DESIGN.md §9) — the
C=2 FDTD-style wave rule rides the same fused resident pipeline at
S ∈ {2, 4}, bit-identical to its sequential global oracle
(kernels/ref.fields_step_ref), and the ×C bytes-model table prints the
2-field stream next to the PR 2/3 single-field numbers: HBM and ICI
both scale by exactly C, never more.

Run: PYTHONPATH=src python examples/stencil_halo_demo.py
(docs/quickstart.md walks through the output.)
"""

import os
import sys

# Eight host devices for part 2's mesh, set before JAX is imported: the
# whole demo runs in this one process (a child could not reach a chip
# this process already holds). The flag touches only the CPU platform.
_HOST_DEVICES = "--xla_force_host_platform_device_count=8"
if _HOST_DEVICES not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), _HOST_DEVICES) if f)


def resident_demo(M=32, g=1, T=8, steps=10, S=4):
    import time

    import numpy as np
    import jax

    from repro.core import HILBERT, MORTON, apply_ordering
    from repro.stencil import Gol3d, Gol3dConfig, ResidentPipeline, \
        resident_bytes_per_step

    print(f"[stencil_halo_demo] resident pipeline, M={M} g={g} T={T} "
          f"K={steps} steps, temporal blocking S={S}")
    for depth in (1, S):
        b = resident_bytes_per_step(M, T, g, steps, S=depth)
        print(f"  modelled HBM bytes/substep, S={depth}: {b / 1e6:.2f} MB")
    auto = ResidentPipeline.plan(M, g=g)
    print(f"  plan(M={M}, g={g}) -> T={auto.T} S={auto.S} "
          f"(vmem {auto.vmem_bytes() / 1024:.0f} KiB, "
          f"{auto.bytes_per_step(steps) / 1e6:.2f} MB/substep)")
    for spec in (MORTON, HILBERT):
        app = Gol3d(Gol3dConfig(M=M, g=g, ordering=spec, block_T=T,
                                substeps=S))
        want = np.asarray(app.reference_run(steps))
        # fused resident: ceil(K/S) launches over the persistent store
        pipe = app.resident_pipeline()
        run = pipe.run_fn(steps)
        jax.block_until_ready(run(pipe.to_blocks(app.cube)))
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(pipe.to_blocks(app.cube)))
        t_res = time.perf_counter() - t0
        got = pipe.to_cube(out)
        ok = np.array_equal(np.asarray(got), want)
        assert np.array_equal(np.asarray(app.run(steps)),
                              np.asarray(apply_ordering(got, spec)))
        print(f"  {spec.name:10s} fused S={pipe.S} "
              f"{t_res * 1e3 / steps:6.1f} ms/step  matches oracle: {ok}")
        assert ok
    print("resident pipeline OK")


def wave_demo(M=32, g=1, T=8, steps=8):
    """Part 3: the C=2 wave workload on the multi-field block store."""
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.ops import uniform_weights
    from repro.stencil import (ResidentPipeline, distributed_bytes_per_step,
                               exchange_bytes_per_step,
                               resident_bytes_per_step)

    C = 2
    print(f"[stencil_halo_demo] multi-field wave (C={C}), M={M} g={g} T={T} "
          f"K={steps} steps")
    rng = np.random.default_rng(0)
    fields = jnp.asarray(rng.normal(size=(C, M, M, M)).astype(np.float32))
    w = uniform_weights(g)
    want = fields
    for _ in range(steps):
        want = kref.fields_step_ref(want, w, g, rule="wave")
    want = np.asarray(want)
    for S in (2, 4):
        pipe = ResidentPipeline(M=M, T=T, g=g, kind="hilbert", S=S,
                                rule="wave")
        run = pipe.run_fn(steps)
        jax.block_until_ready(run(pipe.to_blocks(fields)))  # warm
        store = pipe.to_blocks(fields)
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(store))
        dt = time.perf_counter() - t0
        ok = np.array_equal(np.asarray(pipe.to_cube(out)), want)
        print(f"  wave fused S={S}: {dt * 1e3 / steps:6.1f} ms/step  "
              f"bit-identical to sequential oracle: {ok}")
        assert ok
    # the xC bytes model next to the PR 2/3 single-field numbers
    print(f"  modelled bytes/substep (M={M}, T={T}, g={g}): "
          "single-field vs C=2")
    print("    S   HBM C=1     HBM C=2     ICI C=1     ICI C=2    ratio")
    for S in (1, 2, 4):
        h1 = resident_bytes_per_step(M, T, g, steps, S=S)
        h2 = resident_bytes_per_step(M, T, g, steps, S=S, fields=C)
        i1 = exchange_bytes_per_step(M, g, S)
        i2 = exchange_bytes_per_step(M, g, S, fields=C)
        d2 = distributed_bytes_per_step(M, T, g, steps, S=S, fields=C)
        print(f"    {S}  {h1 / 1e6:7.2f} MB {h2 / 1e6:8.2f} MB "
              f"{i1 / 1e3:8.1f} KB {i2 / 1e3:8.1f} KB   x{h2 / h1:.2f} "
              f"(dist C=2 {d2 / 1e6:.2f} MB)")
    print("multi-field wave OK")


def mesh_demo():
    """Part 2: the distributed matrix on a 2×2×2 mesh of this process's
    devices (eight host devices on the CPU)."""
    import time
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core import ROW_MAJOR, MORTON, HILBERT, NEUMANN0, PERIODIC
    from repro.stencil import (make_stencil_mesh, make_distributed_step,
                               DistributedPipeline, shard_state, unshard_state,
                               distributed_bytes_per_step, exchange_bytes_per_step)
    from repro.kernels import ref as kref

    print("[stencil_halo_demo] distributed gol3d on a 2x2x2 mesh")
    mesh = make_stencil_mesh((2, 2, 2))
    procs = (2, 2, 2)
    local_M, g, GM, steps = 16, 1, 32, 10
    rng = np.random.default_rng(0)
    gcube = (rng.random((GM, GM, GM)) < 0.35).astype(np.float32)

    sharding = NamedSharding(mesh, P("dx", "dy", "dz"))
    for bc in (PERIODIC, NEUMANN0):
        print(f"  --- boundaries: {bc.kind} ---")
        want = jnp.asarray(gcube)
        for _ in range(steps):
            want = kref.gol3d_step_ref(want, g, bc=bc)
        want = np.asarray(want)
        for spec in (ROW_MAJOR, MORTON, HILBERT):
            st = jax.device_put(shard_state(jnp.asarray(gcube), spec, (2, 2, 2)),
                                sharding)
            # legacy reference: one exchange per step (S=1)
            step = make_distributed_step(mesh, spec, local_M, g, bc=bc)
            jax.block_until_ready(step(st))  # compile
            t0 = time.perf_counter()
            gs = st
            for _ in range(steps):
                gs = step(gs)
            out_seq = np.asarray(jax.block_until_ready(gs))
            dt_seq = (time.perf_counter() - t0) / steps
            ok = np.array_equal(np.asarray(unshard_state(jnp.asarray(out_seq), spec, GM)), want)
            line = f"  {spec.name:10s} per-step {dt_seq*1e3:6.1f} ms/step (oracle: {ok})"
            assert ok
            # communication-avoiding pipeline: one deep exchange per S substeps
            for S in (2, 4):
                pipe = DistributedPipeline(mesh=mesh, spec=spec, M=local_M, T=8,
                                           g=g, S=S, bc=bc)
                run = pipe.run_fn(steps)
                jax.block_until_ready(run(st))  # compile
                t0 = time.perf_counter()
                out = np.asarray(jax.block_until_ready(run(st)))
                dt = (time.perf_counter() - t0) / steps
                okS = np.array_equal(out, out_seq)  # bit-identical to S=1 reference
                line += f"  S={S} {dt*1e3:6.1f} ms/step (bit-identical: {okS})"
                assert okS
            print(line)

    # modelled ICI savings per mesh shard: deep exchange (S) x boundary contract.
    # Periodic torus shards send both faces on every axis; clamped mesh-edge
    # shards skip the wrap links (DESIGN.md §8) - on a 2x2x2 mesh every shard
    # is a corner, so the clamped column is exactly half the torus volume.
    print("  modelled ICI bytes/step/shard (local M=16, g=1):")
    print("    S   periodic   clamped(mean)   edge-shard   clamped/periodic")
    for S in (1, 2, 4):
        per = exchange_bytes_per_step(local_M, g, S)
        mean = exchange_bytes_per_step(local_M, g, S, bc=NEUMANN0, procs=procs)
        edge = exchange_bytes_per_step(local_M, g, S, bc=NEUMANN0, procs=procs,
                                       coords=(0, 0, 0))
        print(f"    {S}   {per/1e3:7.1f} KB {mean/1e3:10.1f} KB "
              f"{edge/1e3:9.1f} KB   x{mean/per:.2f}")
    b1 = distributed_bytes_per_step(local_M, 8, g, steps, S=1)
    b4 = distributed_bytes_per_step(local_M, 8, g, steps, S=4)
    b4c = distributed_bytes_per_step(local_M, 8, g, steps, S=4, bc=NEUMANN0,
                                     procs=procs)
    print(f"  modelled bytes/step/shard (HBM+ICI): S=1 {b1/1e3:.0f} KB -> "
          f"S=4 {b4/1e3:.0f} KB (x{b1/b4:.2f}); clamped S=4 {b4c/1e3:.0f} KB")
    print("distributed gol3d OK (periodic + clamped)")


def main():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    resident_demo()
    wave_demo()
    mesh_demo()


if __name__ == "__main__":
    main()
