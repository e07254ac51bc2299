"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

Every Pallas kernel family, and the device executor's jitted step, is
lowered and compiled by the TPU compiler against a ``v5e:2x2`` topology
at the widths the chip smoke runs (cop20k_A and audikw_1 at full size).
This catches what interpret mode cannot: blocks that are not (8, 128)
aligned, primitives Mosaic does not lower, and fast-memory overruns.
Nothing here runs; ``chip_smoke.py`` checks the results on the chip.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and only the worker
that runs this file loads it.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.program import build_program_step, lower
from repro.core.spmv import SpmvPlan
from repro.data.matrices import make_matrix, mixed_structure
from repro.kernels import ops, spmv_ell, spmv_seg, spmv_split, spmv_tile

COP_N = 120_000                 # cop20k_A rows at scale 1.0
AUDIKW_N = 943_000              # audikw_1 rows at scale 1.0
AUDIKW_CHUNKS = 156_136         # 80M non-zeros in 512-wide seg chunks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # pragma: no cover - no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


KERNEL_CASES = {
    # name: (fn, float shapes / int shapes in argument order)
    "ell_cop20k_A": (spmv_ell.ell_spmv,
                     [((COP_N, 128), "f"), ((COP_N, 128), "i"),
                      ((COP_N,), "f")]),
    "ell_audikw_1": (spmv_ell.ell_spmv,
                     [((AUDIKW_N, 128), "f"), ((AUDIKW_N, 128), "i"),
                      ((AUDIKW_N,), "f")]),
    "seg_cop20k_A": (spmv_seg.seg_psum,
                     [((5160, 512), "f"), ((5160, 512), "i"),
                      ((COP_N,), "f")]),
    "seg_audikw_1": (spmv_seg.seg_psum,
                     [((AUDIKW_CHUNKS, 512), "f"),
                      ((AUDIKW_CHUNKS, 512), "i"), ((AUDIKW_N,), "f")]),
    "split_psum": (spmv_split.split_psum,
                   [((64, 68, 512), "f"), ((64, 68, 512), "i"),
                    ((1 << 17,), "f")]),
    "split_combine": (spmv_split.split_combine, [((64, 1 << 17), "f")]),
    "tile_contrib": (spmv_tile.tile_contrib,
                     [((13_763, 8, 128), "f"), ((13_763, 128), "f")]),
    "tile_walk": (spmv_tile.tile_walk_spmv,
                  [((13_763, 8, 128), "f"), ((8192,), "i"),
                   ((8192, 4), "i"), ((8192, 4), "i"),
                   ((512 * 128,), "f")]),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_family_compiles_for_v5e(one_chip, case):
    fn, shapes = KERNEL_CASES[case]
    args = [_spec(one_chip, s, jnp.float32 if k == "f" else jnp.int32)
            for s, k in shapes]
    compiled, hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_batched_ell_compiles_for_v5e(one_chip):
    """The ops-level multi-RHS ELL call (``ops.ell_spmv`` on an (N, B)
    block) vmaps the kernel over the right-hand sides.  The executor does
    not take this path (it loops over the vectors); direct callers of the
    ops do."""
    _, hlo = _compile(ops.ell_spmv, _spec(one_chip, (COP_N, 128)),
                      _spec(one_chip, (COP_N, 128), jnp.int32),
                      _spec(one_chip, (COP_N, 8)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("chunks, n, pieces", [
    (5160, COP_N, 124_930),                             # cop20k_A_synth
    (AUDIKW_CHUNKS, AUDIKW_N, AUDIKW_N + AUDIKW_CHUNKS),  # audikw_1's size
], ids=["cop20k_A", "audikw_1"])
def test_block_chunk_pass_temporaries_stay_bounded(one_chip, chunks, n,
                                                   pieces):
    """The multi-RHS seg pass on an (n, 8) block walks its slab in groups
    of chunks, so its temporaries stay within the group budget and a
    margin whatever the chunk count: a gather of B-wide rows over the
    whole of audikw_1's slab, on lanes padded to 128, would need about
    41 GB."""
    B, L = 8, ops.SEG_CHUNK
    fn = functools.partial(ops._seg_scan_block, num_rows=n,
                           groups=ops._seg_groups(chunks, L, B))
    specs = [_spec(one_chip, (chunks, L)),
             _spec(one_chip, (chunks, L), jnp.int32),
             _spec(one_chip, (chunks, L), jnp.bool_)] + \
        [_spec(one_chip, (pieces,), jnp.int32)] * 4 + \
        [_spec(one_chip, (n, B))]
    compiled, _ = _compile(fn, *specs)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(ops.SEG_GROUP_BYTES * 3 // 2, 2 * 2**30), temp


def _step_specs(mesh, operands, x_shape):
    sharding = NamedSharding(mesh, P("model"))
    return [_spec(sharding, a.shape, a.dtype) for a in operands] + \
        [_spec(sharding, x_shape)]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("batch", [None, 8])
def test_executor_step_compiles_one_chip(topo, use_kernel, batch):
    """The served step for a 1-shard program, lowered from shapes: the jnp
    path by default, every Pallas family behind the ``lax.switch`` with
    ``use_kernel=True``."""
    A = make_matrix("cop20k_A", scale=0.05)
    prog = lower(A, SpmvPlan(kernel="seg", num_shards=1))
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    step, operands = build_program_step(prog, mesh, use_kernel=use_kernel)
    per = prog.x_layout.padded_length()
    x_shape = (1, per) if batch is None else (1, per, batch)
    hlo = step.lower(*_step_specs(mesh, operands, x_shape)) \
        .compile().as_text()
    assert ("tpu_custom_call" in hlo) == use_kernel


def test_executor_step_compiles_four_chips(topo):
    """Mixed per-shard kernels and exchanges over the 2x2 mesh: one
    all-to-all carries halo and full-replication readers alike."""
    A = mixed_structure(4096, 4096 * 8, seed=0)
    prog = lower(A, SpmvPlan(num_shards=4, exchange="halo",
                             shard_kernels=("ell", "seg", "hyb", "split"),
                             shard_exchanges=("halo", "allgather", "halo",
                                              "allgather")))
    mesh = Mesh(np.array(topo.devices[:4]), ("model",))
    step, operands = build_program_step(prog, mesh, use_kernel=True)
    per = prog.x_layout.padded_length() // 4
    hlo = step.lower(*_step_specs(mesh, operands, (4, per))) \
        .compile().as_text()
    assert "all-to-all" in hlo and "tpu_custom_call" in hlo


def test_executor_step_interpret_true_is_refused(topo):
    A = make_matrix("ford1", scale=0.05)
    prog = lower(A, SpmvPlan(num_shards=1))
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    with pytest.raises(TypeError, match="interpret"):
        build_program_step(prog, mesh, use_kernel=True, interpret=True)
