"""Compile the main path's TPU programs at the headline 8192x65536 shape
for a described (not attached) v5e, so a program the chip's compiler
refuses fails here at no chip time (on-chip-measurement guide, §2).

Nothing runs: these say nothing about results or times. The topology is
described inside a module-scoped fixture, never at import: only one
process at a time may load the TPU's library, and the driver's xdist
workers all import this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from sartsolver_tpu.config import SolverOptions
from sartsolver_tpu.models.sart import SARTProblem, _solve_normalized_batch_impl
from sartsolver_tpu.ops.fused_sweep import (
    fused_compile_options, fused_sweep, sharded_panel_sweep,
)
from sartsolver_tpu.ops.laplacian import LaplacianCOO

NPIX, NVOX = 8192, 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip lands in the persistent cache but can
    # never be read back without one: keep these compiles out of it. And
    # compile as the device profile runs, without conftest's x64 (Mosaic
    # refuses the kernel's index maps when they return int64).
    prev = {k: getattr(jax.config, k) for k in
            ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in prev:
        jax.config.update(k, False)
    yield desc
    for k, v in prev.items():
        jax.config.update(k, v)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, options=None):
    lowered = jax.jit(fn).lower(*args)
    return lowered.compile(options) if options else lowered.compile()


@pytest.mark.parametrize("rtm_dtype,batch", [
    (jnp.bfloat16, 1), (jnp.float32, 1), (jnp.int8, 1), (jnp.bfloat16, 32),
    (jnp.float32, 2), (jnp.float32, 3),
], ids=["bf16-B1", "fp32-B1", "int8-B1", "bf16-B32", "fp32-B2", "fp32-B3"])
def test_fused_sweep_compiles_for_v5e(one_chip, rtm_dtype, batch):
    itemsize = jnp.dtype(rtm_dtype).itemsize
    int8 = rtm_dtype == jnp.int8
    aux = [_spec((1, NVOX), jnp.float32, one_chip)] if int8 else []

    def sweep(rtm, w, f, *aux):
        if int8:
            return fused_sweep(rtm, w, f, aux,
                               lambda fp, bp, s: fp + bp * s, fwd_scale=0)
        return fused_sweep(rtm, w, f, aux, lambda fp, bp: fp + bp)

    compiled = _compile(
        sweep,
        _spec((NPIX, NVOX), rtm_dtype, one_chip),
        _spec((batch, NPIX), jnp.float32, one_chip),
        _spec((batch, NVOX), jnp.float32, one_chip),
        *aux,
        options=fused_compile_options(NPIX, NVOX, itemsize, batch),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_panel_sweep_compiles_on_4_chip_mesh(topo):
    mesh = Mesh(topo.devices, ("pixels",))
    rows = NamedSharding(mesh, P("pixels", None))
    cols = NamedSharding(mesh, P(None, "pixels"))
    rep = NamedSharding(mesh, P())

    def sweep(rtm, w, f):
        return sharded_panel_sweep(rtm, w, f, [], lambda fp, bp: fp + bp,
                                   axis_name="pixels")

    fn = jax.shard_map(sweep, mesh=mesh,
                   in_specs=(P("pixels", None), P(None, "pixels"), P()),
                   out_specs=(P(), P(None, "pixels")), check_vma=False)
    compiled = _compile(
        fn,
        _spec((NPIX, NVOX), jnp.float32, rows),
        _spec((1, NPIX), jnp.float32, cols),
        _spec((1, NVOX), jnp.float32, rep),
    )
    text = compiled.as_text()
    assert "all-reduce" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < NPIX * NVOX * 4 // 2  # the RTM is split, not copied


def test_single_chip_solve_program_compiles(one_chip):
    """The jitted solve phase (a) of chip_smoke.py runs on one chip: fp32,
    Laplacian on, Eq. 4 guess, the fused Pallas kernel engaged."""
    nnz = 3 * NVOX - 2
    problem = SARTProblem(
        _spec((NPIX, NVOX), jnp.float32, one_chip),
        _spec((NVOX,), jnp.float32, one_chip),
        _spec((NPIX,), jnp.float32, one_chip),
        LaplacianCOO(_spec((nnz,), jnp.int32, one_chip),
                     _spec((nnz,), jnp.int32, one_chip),
                     _spec((nnz,), jnp.float32, one_chip)),
    )
    opts = SolverOptions(max_iterations=2000, conv_tolerance=1e-5,
                         beta_laplace=2e-2, fused_sweep="on")
    fn = functools.partial(_solve_normalized_batch_impl, opts=opts,
                           axis_name=None, voxel_axis=None, use_guess=True,
                           _vmem_raised=True)
    compiled = _compile(
        fn, problem,
        _spec((1, NPIX), jnp.float32, one_chip),
        _spec((1,), jnp.float32, one_chip),
        _spec((1, NVOX), jnp.float32, one_chip),
        options=fused_compile_options(NPIX, NVOX, 4, 1),
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < NPIX * NVOX * 4  # no matrix-sized copy
