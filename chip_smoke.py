#!/usr/bin/env python3
"""Drive the curve-ordered stencil engine once on a TPU and check its output.

    python chip_smoke.py           # one chip
    python chip_smoke.py --mesh    # four chips: the distributed path only

One chip: a seeded 3-D Game of Life cube at M=1024 (one 4 GiB f32 field)
runs K fused steps through ``ResidentPipeline`` on the compiled Pallas
kernel (blockize once, K/S launches, unblockize) and must equal K steps
of ``kernels.ref.gol3d_step_ref`` bit for bit. A short two-field ``wave``
run at M=256 must equal ``ref.fields_step_ref``. The final gol store is
then fronted by ``StencilQueryService`` and a few ROI boxes must equal
the dense slices of the cube.

``--mesh``: ``DistributedPipeline`` on a 2x2x1 mesh of all four chips,
over a (2L, 2L, L) box with L=512, compared bit for bit with the plain
oracle over the same box on one chip.

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The last line of standard output is one JSON object naming the
device. The phase functions take their sizes as arguments, so tests run
them at a tiny size on the CPU, where the kernels run interpreted.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
KIND = "hilbert"       # block-grid curve of every store here


def log(msg: str) -> None:
    print(msg, flush=True)


def _import_repro():
    """The program lives in ``src/`` beside this script."""
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import repro  # noqa: F401


@functools.partial(jax.jit, static_argnums=(1,))
def random_box(key, shape) -> jax.Array:
    """Seeded {0,1} f32 box (30% live), made on the device."""
    return jax.random.bernoulli(key, 0.3, shape).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_steps", "planes"))
def _oracle_slab(box, k0, *, n_steps: int, planes: int):
    """Planes [k0, k0+planes) of a periodic box after ``n_steps``
    gol3d_step_ref steps (g=1), computed from an n_steps-deep slab.

    The slab spans the whole i and j extents, so its wrap pad is exact
    there; along k the wrap is wrong only in the outer planes, and the
    error moves inward one plane per step, never reaching the centre.
    """
    from repro.kernels import ref

    idx = (k0 - n_steps + jnp.arange(planes + 2 * n_steps)) % box.shape[0]
    slab = jnp.take(box, idx, axis=0)
    slab = jax.lax.fori_loop(0, n_steps,
                             lambda _, s: ref.gol3d_step_ref(s, 1), slab)
    return slab[n_steps:n_steps + planes]


def oracle_mismatches(box, got, n_steps: int, planes: int) -> int:
    """Sites where ``got`` differs from ``n_steps`` periodic gol3d steps of
    ``box``, the oracle run slab by slab to bound its device memory."""
    gk = box.shape[0]
    planes = min(planes, gk)
    bad = 0
    for k0 in range(0, gk, planes):
        want = np.asarray(_oracle_slab(box, k0, n_steps=n_steps,
                                       planes=planes))
        bad += int(np.count_nonzero(want != np.asarray(got[k0:k0 + planes])))
    return bad


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def phase_gol(M: int, T: int, S: int, K: int, *, use_kernel=None):
    """K gol steps of a seeded M³ cube through ResidentPipeline.

    Returns (pipeline, final store, final cube, whether the K-step
    program holds a TPU kernel); :func:`check_gol` compares the cube
    with the oracle.
    """
    from repro.stencil import ResidentPipeline

    pipe = ResidentPipeline(M=M, T=T, g=1, kind=KIND, S=S, rule="gol",
                            use_kernel=use_kernel)
    log(f"gol: M={M} T={T} S={S} K={K} nb={pipe.nb} kind={KIND} "
        f"kernel={pipe.use_kernel} store_bytes={pipe.nb * T ** 3 * 4}")
    t0 = time.perf_counter()
    store = jax.jit(pipe.to_blocks)(random_box(jax.random.key(SEED),
                                               (M, M, M)))
    store.block_until_ready()
    blockize_s = time.perf_counter() - t0

    run, compile_s = _timed_compile(pipe.run_fn(K), store)
    has_kernel = "tpu_custom_call" in run.as_text()
    t0 = time.perf_counter()
    store = run(store)
    store.block_until_ready()
    run_s = time.perf_counter() - t0
    log(f"gol: compile_s={compile_s} run_s={run_s} blockize_s={blockize_s} "
        f"tpu_custom_call={has_kernel} "
        f"site_updates_per_s={M ** 3 * K / run_s} (information only)")

    t0 = time.perf_counter()
    cube = jax.jit(pipe.to_cube)(store)
    cube.block_until_ready()
    log(f"gol: unblockize_s={time.perf_counter() - t0}")
    return pipe, store, cube, has_kernel


def check_gol(cube, K: int, *, oracle_planes: int = 128) -> bool:
    """The final cube of :func:`phase_gol` against K gol3d_step_ref steps
    of the same seeded cube."""
    M = cube.shape[0]
    t0 = time.perf_counter()
    bad = oracle_mismatches(random_box(jax.random.key(SEED), (M, M, M)),
                            cube, K, oracle_planes)
    log(f"gol: oracle_s={time.perf_counter() - t0} mismatches={bad} "
        f"matches gol3d_step_ref: {bad == 0}")
    return bad == 0


def phase_roi(pipe, store, cube) -> bool:
    """Front the final store with StencilQueryService and compare a few
    ROI payloads with the dense slices of the cube."""
    from repro.serve.roi import ROI
    from repro.serve.service import StencilQueryService

    M, T = pipe.M, pipe.T
    t0 = time.perf_counter()
    svc = StencilQueryService.from_pipeline(pipe, store, deadline_s=120.0)
    build_s = time.perf_counter() - t0
    log(f"roi: service build (host copy + crc of {pipe.nb} blocks) "
        f"build_s={build_s}")
    boxes = {   # any M >= 2T
        "aligned": ROI((0, T, M - 2 * T), (T, 2 * T, M)),
        "unaligned": ROI((3, 5, M // 2 - 7), (T + 5, T // 2 + 5, M // 2 + 9)),
        "slab": ROI((M // 2, 0, 0), (M // 2 + 1, M, M)),
    }
    ok = True
    for name, roi in boxes.items():
        t0 = time.perf_counter()
        res = svc.query(roi)
        dt = time.perf_counter() - t0
        sl = tuple(slice(lo, hi) for lo, hi in zip(roi.lo, roi.hi))
        same = (res.status == "ok"
                and np.array_equal(res.payload, np.asarray(cube[sl])))
        ok &= same
        log(f"roi: {name} lo={roi.lo} hi={roi.hi} status={res.status} "
            f"query_s={dt} payload_equals_dense={same}")
    return ok


def phase_wave(M: int, T: int, S: int, K: int, *, use_kernel=None) -> bool:
    """K two-field wave steps through ResidentPipeline against K steps of
    fields_step_ref."""
    from repro.kernels import ref
    from repro.kernels.ops import uniform_weights
    from repro.stencil import ResidentPipeline

    pipe = ResidentPipeline(M=M, T=T, g=1, kind=KIND, S=S, rule="wave",
                            use_kernel=use_kernel)
    u = jax.random.normal(jax.random.key(SEED + 1), (M, M, M), jnp.float32)
    fields = jnp.stack([u, jnp.zeros_like(u)])
    got = pipe.run(fields, K)
    w = uniform_weights(1)
    want = jax.jit(lambda f: jax.lax.fori_loop(
        0, K, lambda _, x: ref.fields_step_ref(x, w, 1, rule="wave"), f))(
            fields)
    ok = bool(np.array_equal(np.asarray(got), np.asarray(want)))
    log(f"wave: M={M} T={T} S={S} K={K} C=2 kernel={pipe.use_kernel} "
        f"finite={bool(jnp.isfinite(got).all())} "
        f"matches fields_step_ref: {ok}")
    return ok


def phase_mesh(L: int, T: int, S: int, K: int, *, use_kernel=None,
               oracle_planes: int = 128) -> bool:
    """DistributedPipeline on a 2x2x1 mesh over a (2L, 2L, L) box against
    the plain oracle over the same box on the first device."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.layout import store_spec
    from repro.stencil import (STENCIL_AXES, DistributedPipeline,
                               make_stencil_mesh, shard_state, unshard_state)

    mesh = make_stencil_mesh((2, 2, 1))
    spec = store_spec(KIND, T)   # the shard state is the block store itself
    pipe = DistributedPipeline(mesh=mesh, spec=spec, M=L, T=T, g=1, S=S,
                               rule="gol", use_kernel=use_kernel)
    box_shape = pipe.global_shape
    key = jax.random.key(SEED)
    log(f"mesh: devices={[d.id for d in mesh.devices.flat]} "
        f"box={box_shape} L={L} T={T} S={S} K={K} kernel={pipe.use_kernel}")
    state = jax.jit(shard_state, static_argnums=(1, 2))(
        random_box(key, box_shape), spec, pipe.procs)
    state = jax.device_put(state, NamedSharding(mesh, PartitionSpec(*STENCIL_AXES)))
    run, compile_s = _timed_compile(pipe.run_fn(K), state)
    t0 = time.perf_counter()
    state = run(state)
    state.block_until_ready()
    run_s = time.perf_counter() - t0
    shards = sorted((s.device.id, s.data.nbytes)
                    for s in state.addressable_shards)
    spread = len({d for d, _ in shards}) == mesh.devices.size
    log(f"mesh: compile_s={compile_s} run_s={run_s} "
        f"tpu_custom_call={'tpu_custom_call' in run.as_text()} "
        f"shards(device, bytes)={shards} one_shard_per_device={spread}")
    got = np.asarray(unshard_state(state, spec, box_shape))
    del state
    t0 = time.perf_counter()
    bad = oracle_mismatches(random_box(key, box_shape), got, K, oracle_planes)
    log(f"mesh: oracle_s={time.perf_counter() - t0} mismatches={bad} "
        f"matches gol3d_step_ref: {bad == 0}")
    return bad == 0 and spread


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip DistributedPipeline path")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    _import_repro()
    from repro.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
        f"compile_cache={enable_compile_cache()}")

    if args.mesh:
        if len(jax.devices()) < 4:
            raise SystemExit(f"--mesh needs 4 chips, have {len(jax.devices())}")
        ok = phase_mesh(L=512, T=128, S=4, K=8)
    else:
        pipe, store, cube, has_kernel = phase_gol(M=1024, T=128, S=4, K=8)
        roi_ok = phase_roi(pipe, store, cube)
        # the store leaves the device once the service holds its copy,
        # so the oracle has room beside the 4 GiB cube
        del store
        gol_ok = check_gol(cube, K=8)
        del cube
        log(f"gol: peak_bytes_in_use={_peak_bytes()}")
        wave_ok = phase_wave(M=256, T=128, S=4, K=8)
        ok = has_kernel and gol_ok and roi_ok and wave_ok
        log(f"peak_bytes_in_use={_peak_bytes()}")
    if not ok:
        log("chip_smoke: a phase failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
