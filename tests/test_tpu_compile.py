"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses (a misaligned slice, too much
fast memory, a program that does not fit) would otherwise surface on
the chip.  These compile the main path's programs at the shapes
chip_smoke.py serves: the md5 / sha256 / sha1 / sha512 kernels at
their MODEL_GEOMETRY in the chained step the ``pallas`` backend serves
through on one chip, the md5 persistent XLA serving step at batch
2^20, and the md5 chained pallas-mesh step over a 2x2 mesh.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and xdist workers that
collected different tests would run none.  The persistent compile
cache is off around the compiles (a described-chip compile is written
but cannot be read back without a chip).
"""

from __future__ import annotations

import os

import pytest

NONCE = b"\x01\x02\x03\x04"
WIDTH = 4
DIFFICULTY = 8
BATCH = 1 << 20
TBC = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(arrays, sharding):
    import jax

    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in arrays]


def _layout(model):
    """The served request pack (widths 0-4), WIDTH's row in it and its
    kernel layout."""
    from distpow_tpu.ops.search_step import pack_operands

    pack = pack_operands(NONCE, DIFFICULTY, 0, TBC, model)
    spec = pack.specs[WIDTH]
    _, tb_w, tb_s = spec.tb_loc
    return pack, spec, tb_w, tb_s, \
        tuple((w, s) for _, w, s in spec.chunk_locs)


@pytest.mark.parametrize("model_name", ["md5", "sha256", "sha1", "sha512"])
def test_kernel_compiles_at_serving_geometry(topo, model_name):
    """The ``pallas`` backend's served width-4 program on one chip: the
    chained kernel step over a one-device mesh at the model's
    MODEL_GEOMETRY, one dispatch of the model's cost-scaled budget cut
    into segments as parallel/mesh_search.py
    ``pallas_mesh_persistent_factory`` cuts it."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.ops.md5_pallas import LANES, MODEL_GEOMETRY, \
        SEGMENT_GRID_STEPS
    from distpow_tpu.parallel.mesh_search import AXIS, make_mesh, \
        pallas_mesh_persistent_step
    from distpow_tpu.parallel.search import launch_steps_for, \
        scaled_launch_candidates

    model = get_hash_model(model_name)
    mesh = make_mesh(topo.devices[:1])
    sublanes, inner = MODEL_GEOMETRY[model_name]
    tile = sublanes * LANES
    budget = scaled_launch_candidates(model.cost_ops)
    dispatch = BATCH * launch_steps_for(WIDTH, BATCH // TBC, TBC, budget)
    seg_local = -(-min(SEGMENT_GRID_STEPS * tile * inner, dispatch)
                  // tile) * tile
    tiles = seg_local // tile
    while tiles % inner:
        inner //= 2
    pack, spec, tb_w, tb_s, chunk_ws = _layout(model)
    step = pallas_mesh_persistent_step(
        mesh, AXIS, model_name, tb_w, tb_s, chunk_ws, tiles // inner,
        sublanes, inner, False, pack.mask_words, True, 0, seg_local,
        -(-dispatch // seg_local), len(spec.base_words[0]),
        pack.base_offs[WIDTH])
    u2 = jnp.zeros((2,), jnp.uint32)
    args = _shapes([pack.words, jnp.uint32(0), u2],
                   NamedSharding(mesh, PartitionSpec()))
    text = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "while" in text


def test_persistent_xla_step_compiles_at_batch_2_20(one_chip):
    """The default worker's serving program (JaxBackend, persistent
    loop) for a width-4 segment."""
    import jax.numpy as jnp

    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.ops.search_step import persistent_search_step
    from distpow_tpu.parallel.search import DEFAULT_LAUNCH_CANDIDATES, \
        launch_steps_for

    model = get_hash_model("md5")
    pack, spec, *_ = _layout(model)
    segments = launch_steps_for(WIDTH, BATCH // TBC, TBC,
                                DEFAULT_LAUNCH_CANDIDATES)
    step = persistent_search_step("md5", spec.n_blocks, spec.tb_loc,
                                  spec.chunk_locs, BATCH, None, segments,
                                  pack.mask_words, pack.base_offs[WIDTH])
    prev = jnp.zeros((2,), jnp.uint32)  # the chain operand
    args = _shapes([pack.words, jnp.uint32(0), prev], one_chip)
    compiled = step.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_pallas_mesh_persistent_step_compiles_on_2x2(topo):
    """The pallas-mesh backend's served width-4 program over four
    chips: a 2^30-candidate dispatch of 2^24-candidate segments a chip,
    each a kernel call and a found-index all-reduce inside the chained
    while loop."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from distpow_tpu.models.registry import get_hash_model
    from distpow_tpu.ops.md5_pallas import LANES, MODEL_GEOMETRY, \
        SEGMENT_GRID_STEPS
    from distpow_tpu.parallel.mesh_search import AXIS, make_mesh, \
        pallas_mesh_persistent_step
    from distpow_tpu.parallel.search import DEFAULT_LAUNCH_CANDIDATES

    model = get_hash_model("md5")
    mesh = make_mesh(topo.devices)
    n_dev = len(topo.devices)
    sublanes, inner = MODEL_GEOMETRY["md5"]
    seg_local = SEGMENT_GRID_STEPS * sublanes * LANES * inner
    assert seg_local == 1 << 24
    segments = DEFAULT_LAUNCH_CANDIDATES // n_dev // seg_local
    pack, spec, tb_w, tb_s, chunk_ws = _layout(model)
    step = pallas_mesh_persistent_step(
        mesh, AXIS, "md5", tb_w, tb_s, chunk_ws, SEGMENT_GRID_STEPS,
        sublanes, inner, False, pack.mask_words, True,
        n_dev.bit_length() - 1, seg_local, segments,
        len(spec.base_words[0]), pack.base_offs[WIDTH])
    u2 = jnp.zeros((2,), jnp.uint32)
    args = _shapes([pack.words, jnp.uint32(0), u2],
                   NamedSharding(mesh, PartitionSpec()))
    text = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    assert "while" in text
