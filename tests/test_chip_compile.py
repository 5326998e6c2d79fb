"""Compile-only checks of the sort path's device programs for a TPU v5e.

Each test lowers one program at its production geometry for a *described*
``v5e:2x2`` topology and compiles it with the TPU compiler that ships with
jaxlib: what Mosaic or XLA would refuse on the chip (unaligned blocks,
scoped-VMEM overruns, unsupported vector ops) fails here, with no chip.
Nothing runs.  The launches are called with ``interpret=False`` directly:
the wrappers would see the CPU test backend and pick interpret mode.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hadoop_bam_tpu.ops.flate import DEV_LZ_PAYLOAD


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_chain_kernel_compiles(one_chip):
    """The record-chain walk over four 4 MiB chunks."""
    from hadoop_bam_tpu.ops.pallas import chain

    n_chunks = 4
    words = (n_chunks * chain.CHUNK + 256 * 4) // 4
    c = chain._chain_all.lower(
        _spec((words,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
        n_chunks=n_chunks,
        interpret=False,
    ).compile()
    assert _is_kernel(c)


def test_stream_words_stay_lane_dense(one_chip):
    """The chain kernel's int32 view of a split-sized stream (~0.5 GB):
    a ``[n/4, 4]`` byte view is tiled to 128 lanes on a TPU (32x the
    stream, which overran HBM on the chip), so the words are built
    lane-dense and HBM holds a small multiple of the stream."""
    from hadoop_bam_tpu.ops.pallas.chain import stream_to_words

    n = 120 * (4 << 20)
    m = stream_to_words.lower(
        _spec((n,), jnp.uint8, one_chip)
    ).compile().memory_analysis()
    assert m.output_size_in_bytes == n
    assert m.temp_size_in_bytes < 4 * n


def test_inflate_lanes_compiles_at_full_member_geometry(one_chip):
    """A full 64 KiB BGZF member's geometry (the worst case the lanes tier
    accepts), inside the scoped-VMEM limit the kernel asks for."""
    from hadoop_bam_tpu.ops.pallas import inflate_lanes as il

    geo = il.stream_geometry(0xFF00, 0x10000)
    assert il.accepts(0xFF00, 0x10000) == (True, "")
    assert geo["vmem_bytes"] <= il._VMEM_BUDGET_BYTES
    row = _spec((1, il.LANES), jnp.int32, one_chip)
    c = il._inflate_lanes_kernel.lower(
        _spec((geo["r_words"], il.LANES), jnp.int32, one_chip), row, row,
        r_words=geo["r_words"], oc_rows=geo["oc_rows"],
        ring_rows=geo["ring_rows"], n_chunks=geo["n_chunks"],
        t_step=geo["t_step"], max_blocks=12, max_far=64,
        far_dist=geo["far_dist"], interpret=False,
    ).compile()
    assert _is_kernel(c)


def test_deflate_lanes_compiles_at_part_writer_payload(one_chip):
    """The part writer's member blocking (``DEV_LZ_PAYLOAD``)."""
    from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl

    P = dl._round_up(DEV_LZ_PAYLOAD, dl._DEFAULT_CHUNK)
    assert dl.accepts(DEV_LZ_PAYLOAD) == (True, "")
    g = dl._geometry(P, dl._DEFAULT_CHUNK)
    c = dl._deflate_match_kernel.lower(
        _spec((g["w"], dl.LANES), jnp.int32, one_chip),
        _spec((1, dl.LANES), jnp.int32, one_chip),
        w=g["w"], h=g["h"], n_chunks=g["n_chunks"],
        tok_tile=g["tok_tile"], chunk=g["chunk"], t_step=g["t_step"],
        interpret=False,
    ).compile()
    assert _is_kernel(c)


def test_deflate_pack_compiles_at_part_writer_payload(one_chip):
    """The fixed-Huffman pack kernel over the match kernel's token tiles
    at the part writer's member blocking."""
    from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl

    P = dl._round_up(DEV_LZ_PAYLOAD, dl._DEFAULT_CHUNK)
    g = dl._geometry(P, dl._DEFAULT_CHUNK)
    rows = g["n_chunks"] * g["tok_tile"]
    c = dl._deflate_pack_kernel.lower(
        _spec((rows, dl.LANES), jnp.int32, one_chip),
        _spec((g["n_chunks"] * dl._CNT_ROWS, dl.LANES), jnp.int32, one_chip),
        tok_tile=g["tok_tile"], word_tile=g["word_tile"],
        n_chunks=g["n_chunks"], lanes_out=dl.LANES, interpret=False,
    ).compile()
    assert _is_kernel(c)


def test_keys_and_sort_compile(one_chip):
    """Key assembly + the single-chip key sort."""
    from hadoop_bam_tpu.ops.keys import make_keys
    from hadoop_bam_tpu.ops.sort import sort_keys

    n = 1 << 14

    def step(refid, pos, flag, hash32):
        hi, lo = make_keys(refid, pos, flag, hash32)
        return sort_keys(hi, lo)

    col = _spec((n,), jnp.int32, one_chip)
    jax.jit(step).lower(col, col, col, col).compile()


def test_distributed_sort_compiles_on_four_chips(topo):
    """The 4-device range-partitioned ``all_to_all`` sort step."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import DATA_AXIS
    from hadoop_bam_tpu.parallel.shuffle import DistributedSort

    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    rows = 4096
    ds = DistributedSort(mesh, rows_per_device=rows)
    sh = NamedSharding(mesh, P(DATA_AXIS))

    def col(dt):
        return _spec((4 * rows,), dt, sh)

    c = ds._step.lower(
        col(jnp.int32), col(jnp.uint32), col(jnp.bool_), col(jnp.int32)
    ).compile()
    assert "all-to-all" in c.as_text()
