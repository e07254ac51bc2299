"""§Perf H3 reproduction: SpMV exchange strategy on the production mesh.

Standalone (needs 512 fake devices — do not import from benchmarks.run):

    PYTHONPATH=src python -m benchmarks.spmv_exchange

For each (matrix, reordering): lower the all-gather and halo-exchange
distributed SpMV programs on the 16x16 mesh and report compiled collective
bytes per shard — the ICI version of the paper's migration counts.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()


def run():
    import jax
    import jax.numpy as jnp

    from repro.core.spmv import (SpmvPlan, build_distributed, build_halo,
                                 make_halo_spmv_fn, make_spmv_fn)
    from repro.data.matrices import make_matrix
    from repro.launch.dryrun import collective_bytes_from_hlo
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()
    print("matrix,reorder,allgather_bytes,halo_bytes,halo_advantage,halo_H")
    for mname, sc in (("ford1", 1.0), ("cop20k_A", 0.2), ("audikw_1", 0.2)):
        A = make_matrix(mname, scale=sc)
        for reord in ("none", "bfs", "random"):
            plan = SpmvPlan(layout="block", distribution="nonzero",
                            reordering=reord, num_shards=16)
            d = build_distributed(A, plan)
            h = build_halo(d)
            per = d.x_layout.padded_length() // 16
            res = {}
            for name in ("allgather", "halo"):
                if name == "allgather":
                    fn = make_spmv_fn(d, mesh)
                    args = (jax.ShapeDtypeStruct(d.data.shape, jnp.float32),
                            jax.ShapeDtypeStruct(d.cols.shape, jnp.int32),
                            jax.ShapeDtypeStruct((16, per), jnp.float32))
                else:
                    fn = make_halo_spmv_fn(d, h, mesh)
                    args = (jax.ShapeDtypeStruct(d.data.shape, jnp.float32),
                            jax.ShapeDtypeStruct(h.cols_remap.shape, jnp.int32),
                            jax.ShapeDtypeStruct(h.send_idx.shape, jnp.int32),
                            jax.ShapeDtypeStruct((16, per), jnp.float32))
                with mesh:
                    comp = fn.lower(*args).compile()
                res[name] = collective_bytes_from_hlo(comp.as_text())["total"]
            adv = res["allgather"] / max(res["halo"], 1)
            print(f"{mname},{reord},{res['allgather']:.0f},{res['halo']:.0f},"
                  f"{adv:.2f},{h.halo}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
