"""Compile the main path for a described TPU v5e chip, at chip_smoke.py's
sizes. Nothing runs: the TPU compiler refuses here what the chip would
refuse (block shapes off the (8, 128) tiling, SMEM or VMEM overflow, a
program over the chip's HBM), at no chip time.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the test workers all import
this file.
"""

import base64
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.neighbors import boundary_face_table, neighbor_table
from repro.kernels import backend, stencil3d
from repro.kernels.ops import uniform_weights
from repro.kernels.stencil3d import (VMEM_LIMIT_BYTES, fused_kernel_vmem_bytes,
                                     stencil_step_fused)
from repro.stencil import Gol3d, Gol3dConfig, ResidentPipeline

HBM_BYTES = int(15.75 * 2 ** 30)   # what XLA lets one v5e program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_path(monkeypatch):
    """The backend the kernels see is the TPU: no interpret mode, and
    the pipelines pick the fused kernel."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used <= HBM_BYTES, used


@pytest.mark.parametrize("rule,bc,M", [
    ("gol", "periodic", 1024),     # chip_smoke.py's main phase
    ("wave", "periodic", 256),     # its two-field phase
    ("jacobi", "neumann0", 256),
    ("gol", "dirichlet", 256),
])
def test_fused_kernel_compiles(one_chip, compiled_path, rule, bc, M):
    T, S, g = 128, 4, 1
    nt = M // T
    nb = nt ** 3
    C = 2 if rule == "wave" else 1
    shape = (nb, T, T, T) if C == 1 else (C, nb, T, T, T)

    def sds(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    clamped = bc != "periodic"
    nbr = neighbor_table("hilbert", nt, periodic=not clamped)
    bnd = boundary_face_table("hilbert", nt) if clamped else None
    fn = jax.jit(lambda store: stencil_step_fused(
        store, uniform_weights(g), nbr, bnd, g=g, S=S, rule=rule, bc=bc))
    _check(fn.lower(sds(shape)).compile())


def _fused_lowered(one_chip, rule, M, S, g, form="box"):
    """The fused kernel lowered for one chip at T=128. The box form takes
    the uniform weights as a constant; the weighted form takes them as
    an operand, whose values are unknown when the kernel is traced."""
    T = 128
    nt = M // T
    nbr = neighbor_table("hilbert", nt)
    store = _fused_store(one_chip, rule, M)
    if form == "box":
        return jax.jit(lambda store: stencil_step_fused(
            store, uniform_weights(g), nbr, g=g, S=S, rule=rule)).lower(store)
    w = jax.ShapeDtypeStruct((2 * g + 1,) * 3, jnp.float32, sharding=one_chip)
    return jax.jit(lambda store, w: stencil_step_fused(
        store, w, nbr, g=g, S=S, rule=rule)).lower(store, w)


def _fused_store(one_chip, rule, M):
    T, nb = 128, (M // 128) ** 3
    shape = (nb, T, T, T) if rule != "wave" else (2, nb, T, T, T)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


def test_fused_ring_compiles_two_channels(one_chip, compiled_path):
    """wave's two channels each take a ring, within the limit, in both
    tap-sum forms."""
    for form in ("box", "weighted"):
        assert fused_kernel_vmem_bytes(128, 4, 2, g=1, form=form) \
            <= VMEM_LIMIT_BYTES
        _check(_fused_lowered(one_chip, "wave", 512, 4, 1, form).compile())


def test_fused_ring_vmem_is_what_the_compiler_allocates(
        one_chip, compiled_path, monkeypatch):
    """In both tap-sum forms the kernel compiles with its scoped limit
    set 1/32 above ``fused_kernel_vmem_bytes`` (Mosaic takes some of the
    room it is given for itself) and is refused 1 MiB below it: the
    model counts what the kernel allocates, the ring of the form that
    runs included. The weighted form at g=2, S=2 carries the widest
    ring, 5 planes x 24 shifts (16 MiB of the kernel's 26 MiB); the box
    form at the cells' g=1, S=4 holds one ij-partial plane per slot and
    the j-partial's stage."""
    T = 128

    def compile_under(limit, form, S, g):
        monkeypatch.setattr(stencil3d, "VMEM_LIMIT_BYTES", limit)
        stencil3d._fused_launch.clear_cache()
        return _fused_lowered(one_chip, "jacobi", 256, S, g, form).compile()

    try:
        for form, S, g in (("weighted", 2, 2), ("box", 4, 1)):
            model = fused_kernel_vmem_bytes(T, S * g, g=g, form=form)
            assert model <= VMEM_LIMIT_BYTES
            _check(compile_under(model + model // 32, form, S, g))
            with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
                compile_under(model - 2 ** 20, form, S, g)
    finally:
        stencil3d._fused_launch.clear_cache()


def test_resident_pipeline_run_fn_compiles(one_chip, compiled_path):
    pipe = ResidentPipeline(M=1024, T=128, g=1, kind="hilbert", S=4)
    assert pipe.use_kernel is True
    store = jax.ShapeDtypeStruct((pipe.nb, 128, 128, 128), jnp.float32,
                                 sharding=one_chip)
    _check(pipe.run_fn(8).lower(store).compile())


def test_gol3d_resident_defaults_compile(one_chip, compiled_path):
    """Gol3d at the smoke's M with every default: the platform picks the
    compiled kernel and the lane-dense block edge, and the row-major
    state reaches the store by reshapes — no M³ permutation is embedded
    (it alone would be 4 GiB of program constants)."""
    M = 1024
    state = jax.ShapeDtypeStruct((M ** 3,), jnp.float32, sharding=one_chip)
    app = Gol3d(Gol3dConfig(M=M), state_path=state)
    assert app.cfg.use_kernel is True and app.cfg.block_T == 128
    lowered = app.resident_fn(8).lower(state)
    assert len(lowered.as_text()) < 2 ** 20
    _check(lowered.compile())


def _kernel_body(lowered) -> bytes:
    """The serialized Mosaic module of the program's one TPU kernel."""
    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                     lowered.as_text())
    return base64.b64decode(body.group(1))


def test_kernel_cache_key_ignores_checkout(one_chip, compiled_path,
                                           monkeypatch, tmp_path):
    """A TPU kernel's body embeds its source locations, which the
    persistent-cache key hashes. enable_compile_cache cuts the
    checkout's path from them, so every checkout of the same code finds
    the same cache entries."""
    from repro import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    name = "jax_hlo_source_file_canonicalization_regex"
    was = jax.config.jax_hlo_source_file_canonicalization_regex
    store = jax.ShapeDtypeStruct((1, 128, 128, 128), jnp.float32,
                                 sharding=one_chip)
    nbr = neighbor_table("hilbert", 1)
    root = str(compile_cache.CHECKOUT).encode()

    def body(S):
        return _kernel_body(jax.jit(lambda s: stencil_step_fused(
            s, uniform_weights(1), nbr, g=1, S=S)).lower(store))

    try:
        jax.config.update(name, None)
        assert root in body(2)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        cut = body(8)
        assert root not in cut and b"src/repro/kernels/stencil3d.py" in cut
    finally:
        jax.config.update(name, was)
