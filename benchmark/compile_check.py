"""Compile each cell's solve programs at full size for a described v5e.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py [cell ...]

Nothing runs: for the cold chain (frame 0 from the Eq. 4 guess) and the
warm chain the window drives, it lowers the program ``DistributedSARTSolver
._chain_fn`` builds, on the mesh ``choose_mesh_shape`` picks on a TPU (one
chip: the fused Pallas kernel; four: the voxel-major 1x4 mesh where the kernel
fuses a column block, else the (4,1) row-block panel scan), and
prints ``memory_analysis()``, the compile seconds and whether
``tpu_custom_call`` (the Pallas kernel) is in the program. ``fused_sweep``
is ``on`` here because ``auto`` engages only where the default backend is
a TPU, which it is not on this host.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_cell(name, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import spec, world
    from sartsolver_tpu.config import SolverOptions
    from sartsolver_tpu.models.sart import (
        SARTProblem, SolveResult, solve_chain_normalized,
    )
    from sartsolver_tpu.ops.fused_sweep import (
        fused_available, pick_block_voxels, pick_panel_voxels,
        raised_vmem_options,
    )
    from sartsolver_tpu.ops.laplacian import (
        LaplacianCOO, ShardedLaplacian, shard_laplacian_halo,
    )
    from sartsolver_tpu.parallel.mesh import (
        COL_ALIGN, PIXEL_AXIS, ROW_ALIGN, VOXEL_AXIS, padded_size,
    )

    cell = spec.load_cell(name)
    cfg = cell.config
    npix, nvox = int(cfg["npixel"]), int(cfg["nvoxel"])
    s = cfg["solver"]
    opts = SolverOptions(max_iterations=s["max_iterations"],
                         conv_tolerance=s["conv_tolerance"],
                         beta_laplace=s["beta_laplace"], fused_sweep="on")
    n = cell.chips
    # choose_mesh_shape on a TPU: voxel-major only where the kernel fuses
    # the per-chip column block; else the reference's row blocks
    fuses = n == 1 or fused_available(padded_size(npix, ROW_ALIGN),
                                      padded_size(nvox, n * COL_ALIGN) // n,
                                      4, 1)
    n_pix, n_vox = (1, n) if fuses else (n, 1)
    mesh = Mesh(np.array(topo.devices[:n]).reshape(n_pix, n_vox),
                (PIXEL_AXIS, VOXEL_AXIS))
    Pp = padded_size(npix, n_pix * ROW_ALIGN)
    Vp = padded_size(nvox, n_vox * COL_ALIGN)
    K = 8
    lap_np = shard_laplacian_halo(
        LaplacianCOO(*world.grid_laplacian(world.grid_shape(cfg))),
        n_vox, Vp // n_vox, jnp.float32)

    def sds(shape, dtype, spec_):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec_))

    lap_spec = P(VOXEL_AXIS, None)
    problem = SARTProblem(
        sds((Pp, Vp), jnp.float32, P(PIXEL_AXIS, VOXEL_AXIS)),
        sds((Vp,), jnp.float32, P(VOXEL_AXIS)),
        sds((Pp,), jnp.float32, P(PIXEL_AXIS)),
        ShardedLaplacian(*(sds(a.shape, a.dtype, lap_spec) for a in lap_np)),
    )
    problem_spec = SARTProblem(
        P(PIXEL_AXIS, VOXEL_AXIS), P(VOXEL_AXIS), P(PIXEL_AXIS),
        ShardedLaplacian(*(lap_spec,) * len(ShardedLaplacian._fields)))
    pixel_axis = PIXEL_AXIS if n_pix > 1 else None
    voxel_axis = VOXEL_AXIS if n_vox > 1 else None
    raised = pixel_axis is None
    out = {"cell": name, "mesh": f"{n_pix}x{n_vox}",
           "rtm_gib_per_chip": Pp * Vp * 4 / n / 2 ** 30,
           "kernel_panel_voxels": pick_block_voxels(Pp // n_pix, Vp // n_vox,
                                                    4, 1),
           "scan_panel_voxels": pick_panel_voxels(Pp // n_pix, Vp // n_vox,
                                                  4, 1)}
    for warm in (False, True):
        def run(problem, g, msq, f0, rescale, *fitted0):
            problem = problem._replace(laplacian=ShardedLaplacian(
                *(a[0] for a in problem.laplacian)))
            return solve_chain_normalized(
                problem, g, msq, f0, rescale, opts=opts,
                axis_name=pixel_axis, voxel_axis=voxel_axis,
                use_guess_first=not warm,
                fitted0=fitted0[0] if warm else None, _vmem_raised=raised)

        fn = jax.shard_map(
            run, mesh=mesh,
            in_specs=(problem_spec, P(None, PIXEL_AXIS), P(),
                      P(None, VOXEL_AXIS), P(),
                      *((P(None, PIXEL_AXIS),) if warm else ())),
            out_specs=(SolveResult(P(None, VOXEL_AXIS), P(), P(), P()),
                       P(None, PIXEL_AXIS)),
            check_vma=False)
        args = (problem, sds((K, Pp), jnp.float32, P(None, PIXEL_AXIS)),
                sds((K,), jnp.float32, P()),
                sds((1, Vp), jnp.float32, P(None, VOXEL_AXIS)),
                sds((K,), jnp.float32, P()),
                *((sds((1, Pp), jnp.float32, P(None, PIXEL_AXIS)),)
                  if warm else ()))
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*args)
        compiled = (lowered.compile(raised_vmem_options()) if raised
                    else lowered.compile())
        secs = time.perf_counter() - t0
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        out["warm" if warm else "cold"] = {
            "compile_s": secs,
            "tpu_custom_call": "tpu_custom_call" in text,
            "all_reduce_ops": text.count("all-reduce("),
            "argument_gib": mem.argument_size_in_bytes / 2 ** 30,
            "output_gib": mem.output_size_in_bytes / 2 ** 30,
            "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
            "generated_code_mib": mem.generated_code_size_in_bytes / 2 ** 20,
        }
    return out


def main(argv=None):
    import jax
    from jax.experimental import topologies

    from benchmark import spec

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    names = argv or [w["name"] for w in spec.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
