"""Ahead-of-time compiles for a described TPU v5e (``v5e:2x2``): the
chip's compiler refuses what interpret mode accepts (tiles that disagree
with XLA's layout, blocks that vmap squeezes, more HBM than the chip has),
so the served programs are compiled for it here, with no chip attached.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs these tests loads the TPU compiler library.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import algorithms as ALG
from repro.core import graph as G
from repro.core import partition as PT
from repro.core.engine import Engine
from repro.core.engine_shardmap import (ShardEngine, abstract_shard_data,
                                        build_shard_data)
from repro.kernels.edge_gather import segment_combine_windows

# the served tile sizes (Engine / ShardEngine / build_layout defaults)
TILE_E, TILE_R = 512, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("interpret", [None, False])
@pytest.mark.parametrize("batch", [None, 8])
@pytest.mark.parametrize("combiner,dtype", [
    ("min", jnp.int32), ("min", jnp.float32), ("add", jnp.float32)])
def test_kernel_compiles_for_v5e(one_chip, combiner, dtype, batch,
                                 interpret):
    """The windowed kernel at served tile sizes, alone and vmapped over a
    query batch as every served path calls it, lowers to a Mosaic
    ``tpu_custom_call`` — also when the platform picks the path."""
    n_tiles, n_windows = 4096, 1024
    lanes = n_tiles * TILE_E

    def f(wid, rel, written, vals):
        return segment_combine_windows(
            wid, rel, vals, combiner=combiner, tile_e=TILE_E,
            tile_r=TILE_R, n_windows=n_windows, window_written=written,
            num_segments=n_windows * TILE_R - 3, interpret=interpret)

    vals_shape = (lanes,) if batch is None else (batch, lanes)
    if batch is not None:
        f = jax.vmap(f, in_axes=(None, None, None, 0))
    compiled = jax.jit(f).lower(
        _sds((n_tiles,), jnp.int32, one_chip),
        _sds((lanes,), jnp.int32, one_chip),
        _sds((n_windows,), jnp.bool_, one_chip),
        _sds(vals_shape, dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_interpreted_kernel_refused_for_tpu(one_chip):
    """Asking for the interpreter while lowering for a TPU is an error,
    not a silent fallback."""
    def f(wid, rel, written, vals):
        return segment_combine_windows(
            wid, rel, vals, combiner="min", tile_e=TILE_E, tile_r=TILE_R,
            n_windows=4, window_written=written, num_segments=1000,
            interpret=True)

    with pytest.raises(Exception, match="platform"):
        jax.jit(f).lower(_sds((8,), jnp.int32, one_chip),
                         _sds((8 * TILE_E,), jnp.int32, one_chip),
                         _sds((4,), jnp.bool_, one_chip),
                         _sds((8 * TILE_E,), jnp.int32, one_chip))


@pytest.fixture(scope="module")
def small_pg():
    return PT.partition_graph(G.rmat(10, 8, seed=1).symmetrized(), 4)


def test_engine_batch_compiles_for_v5e(one_chip, small_pg):
    """Engine(backend="pallas")'s batched program for one chip: the
    kernel is compiled, never interpreted, although the process runs on
    CPU."""
    eng = Engine(ALG.bfs(), small_pg, backend="pallas")
    data = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                        eng._data)
    compiled = eng._make_batch_program(8).lower(
        data, _sds((), jnp.int32, one_chip),
        {"root": _sds((8,), jnp.int32, one_chip)}).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert 0 < m.argument_size_in_bytes + m.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("exchange", ["allgather", "combined"])
def test_shard_engine_batch_compiles_for_v5e_2x2(topo, small_pg, exchange):
    """One batched ShardEngine program on a 4-device mesh of the described
    chips: the exchange's collectives and the kernel are both there."""
    mesh = Mesh(np.array(topo.devices), ("graph",))
    _, meta = build_shard_data(small_pg)
    eng = ShardEngine(ALG.bfs(), meta, mesh=mesh, exchange=exchange,
                      backend="pallas")
    sharded = NamedSharding(mesh, PartitionSpec("graph"))
    eng._data = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, sharded),
        abstract_shard_data(meta, exchange=exchange))
    fn = eng._make_run_batch(100, ("root",), 8)
    compiled = fn.lower(eng._data,
                        {"root": jax.ShapeDtypeStruct((8,), jnp.int32)}
                        ).compile()
    text = compiled.as_text()
    want = {"allgather": "all-gather", "combined": "all-to-all"}[exchange]
    assert want in text
    assert "tpu_custom_call" in text


def test_sssp_batch_with_finalize_compiles_for_v5e(one_chip, small_pg):
    """SSSP's served batch-32 program for one chip, with the parent pass
    after the loop: the pass's tie-break stays one conditional for the
    batch. Made per query under the query vmap, it becomes a select that
    runs both branches' segment-mins every time, and here asks 27.9 MB
    of temporaries against 2.2 MB of inputs and outputs."""
    eng = Engine(ALG.sssp(), small_pg, backend="ref")
    data = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                        eng._data)
    compiled = eng._make_batch_program(32).lower(
        data, _sds((), jnp.int32, one_chip),
        {"root": _sds((32,), jnp.int32, one_chip)}).compile()
    assert " conditional(" in compiled.as_text()
    m = compiled.memory_analysis()
    io = m.argument_size_in_bytes + m.output_size_in_bytes
    assert 0 < m.temp_size_in_bytes <= 2 * io, (m.temp_size_in_bytes, io)


@pytest.mark.parametrize("exchange", ["allgather", "combined"])
def test_shard_sssp_finalize_compiles_for_v5e_2x2(topo, small_pg, exchange):
    """SSSP's batched ShardEngine program on a 4-device mesh: after the
    loop, the parent pass exchanges only the leaves it reads at the
    sources, one all-gather each time it reads one: dist, and inside the
    tie-break's conditional dist again and t; the state's activity bits
    never move. It then reads each shard's CSC lanes."""
    mesh = Mesh(np.array(topo.devices), ("graph",))
    _, meta = build_shard_data(small_pg)
    eng = ShardEngine(ALG.sssp(), meta, mesh=mesh, exchange=exchange,
                      backend="pallas")
    sharded = NamedSharding(mesh, PartitionSpec("graph"))
    # every exchange's data holds the CSC lanes the pass reads
    eng._data = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharded),
                             abstract_shard_data(meta, exchange=exchange))
    fn = eng._make_run_batch(100, ("root",), 8)
    text = fn.lower(eng._data,
                    {"root": jax.ShapeDtypeStruct((8,), jnp.int32)}
                    ).compile().as_text()
    gathers = [line for line in text.splitlines()
               if " all-gather(" in line and "gravfm.finalize" in line]
    assert sorted(line.split("= ")[1][:3] for line in gathers) == [
        "f32", "f32", "s32"], gathers
    assert " conditional(" in text


# the shard layout of the Graph500 scale-20 graph of the g500-s20-mesh
# configuration, greedy-partitioned over 4 shards (build_shard_data with
# exchange="allgather"): 1,048,576 vertices, 31,399,038 directed edges,
# at most 7,849,958 in-edges a shard
S20_MESH = dict(P=4, v_max=264704, e_pair_max=1963968, n_tiles=15848,
                n_windows=1035, tile_e=TILE_E, tile_r=TILE_R,
                num_vertices=1 << 20,
                frontier_capacities=(16544, 66176, 264704))
V5E_HBM = 15.75e9      # what the chip's compiler lets a program use


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_served_mesh_batch_fits_v5e_2x2_at_scale_20(topo, kernel):
    """The batch-32 program g500-s20.mix-mesh serves (allgather, the
    ref backend), compiled for four described chips at the real shard
    sizes: each chip's share fits its HBM with the graph's own arrays
    beside it. One chip cannot hold the graph's BFS batch of 32."""
    from repro.core.engine_shardmap import ShardMeta
    mesh = Mesh(np.array(topo.devices), ("graph",))
    meta = ShardMeta(**S20_MESH)
    eng = ShardEngine(getattr(ALG, kernel)(), meta, mesh=mesh,
                      exchange="allgather", backend="ref")
    sharded = NamedSharding(mesh, PartitionSpec("graph"))
    eng._data = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, sharded),
        abstract_shard_data(meta, exchange="allgather"))
    compiled = eng.lower(32).compile()
    m = compiled.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
    assert 0 < per_chip < V5E_HBM, per_chip
    assert "all-gather" in compiled.as_text()


# the one-chip engine's in-degree classes of the Graph500 scale-18 graph
# of the g500-s18 configuration, greedy-partitioned over the service's 4
# shards (build_classes): 262,144 vertices, 7,609,366 directed edges in
# 7,896,144 lanes, (class width, rows)
S18_CLASSES = (
    (1, 35360), (2, 19856), (3, 12976), (4, 10056), (5, 8536), (6, 7520),
    (7, 6168), (8, 4557), (9, 3104), (10, 2064), (11, 1504), (12, 1400),
    (13, 1504), (14, 1840), (15, 2232), (16, 2576), (18, 5896),
    (20, 5576), (22, 4432), (24, 2936), (26, 1640), (28, 856), (30, 376),
    (32, 109), (36, 80), (40, 86), (44, 368), (48, 1022), (52, 2136),
    (56, 3458), (60, 3768), (64, 3423), (72, 3547), (80, 682), (88, 67),
    (96, 3), (144, 10), (160, 311), (176, 2256), (192, 3887), (208, 1840),
    (224, 256), (240, 8), (512, 46), (576, 2312), (640, 702), (1536, 2),
    (1664, 621), (1792, 193), (4608, 153), (11264, 8), (12288, 10),
    (26624, 1))
S18 = dict(P=4, v_max=67584)


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_served_batch_fits_v5e_at_scale_18(one_chip, small_pg, kernel):
    """The batch-32 program g500-s18.mix serves (Engine, the ref
    backend), compiled for one described chip at the real class layout:
    it fits the chip's HBM with the graph's own arrays beside it, and its
    combine holds no scatter."""
    eng = Engine(getattr(ALG, kernel)(), small_pg, backend="ref")
    P, Vm = S18["P"], S18["v_max"]
    S, L = P * (Vm + 1), sum(w * n for w, n in S18_CLASSES)
    eng._P, eng._Vm = P, Vm
    eng._layout = dataclasses.replace(eng._layout, num_segments=S,
                                      classes=S18_CLASSES)

    def shape(field, a):
        n = (P, Vm) if a.ndim == 2 else (S,) if field == "inv" else (L,)
        return _sds(n, a.dtype, one_chip)

    data = eng._data._replace(**{f: shape(f, a) for f, a
                                 in eng._data._asdict().items()
                                 if a is not None})
    compiled = eng._make_batch_program(32).lower(
        data, _sds((), jnp.int32, one_chip),
        {"root": _sds((32,), jnp.int32, one_chip)}).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0 < total < V5E_HBM, total
    assert " scatter(" not in compiled.as_text()
