"""The main path compiles for a TPU v5e at real widths.

Interpret mode on CPU runs a kernel's logic but not Mosaic, which refuses
what the interpreter accepts (an unsupported primitive such as cumsum, a
block shape off the (8, 128) tiling, more VMEM than the scoped limit). These
tests compile each kernel for one chip of a described, not attached,
``v5e:2x2`` topology and check that a Mosaic custom call is in the program,
and compile the sharded DLRM-UIH init for all four chips of it. No chip is
needed; nothing runs.
"""
import os

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.delta_decode.delta_decode import delta_decode_kernel
from repro.kernels.embedding_bag.embedding_bag import embedding_bag_kernel
from repro.kernels.fused.fused import densify_decode
from repro.kernels.fused.ops import fused_densify
from repro.kernels.jagged import jagged
from repro.kernels.jagged.jagged import jagged_to_padded_kernel

B, L, LANES = 64, 2048, 128


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2, with JAX's persistent
    compilation cache off: a compile for a described chip cannot be read
    back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else libtpu logs to /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return jax.sharding.SingleDeviceSharding(v5e_2x2[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("ts_col", [-1, 0], ids=["no_ts", "ts"])
def test_fused_densify_compiles(one_chip, ts_col):
    c = densify_decode.lower(
        _spec(one_chip, (1, (B + 1) * L, LANES), jnp.int32),
        _spec(one_chip, (B + 1,), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
        max_len=L, ts_col=ts_col).compile()
    _assert_mosaic(c)


def test_jagged_to_padded_compiles(one_chip):
    # two 128-lane column tiles: a 256-wide feature row
    c = jagged_to_padded_kernel.lower(
        _spec(one_chip, (2, (B + 1) * L, LANES), jnp.float32),
        _spec(one_chip, (B + 1,), jnp.int32), max_len=L).compile()
    _assert_mosaic(c)


def test_delta_decode_compiles(one_chip):
    c = delta_decode_kernel.lower(
        _spec(one_chip, (B, L), jnp.int32),
        _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_mosaic(c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_embedding_bag_compiles(one_chip, dtype):
    c = embedding_bag_kernel.lower(
        _spec(one_chip, (1, 1 << 20, LANES), dtype),
        _spec(one_chip, (B, L), jnp.int32),
        _spec(one_chip, (B, L), jnp.float32), bag_len=L).compile()
    _assert_mosaic(c)


def test_densify_vmem_limit_is_where_mosaic_stops(one_chip):
    """The longest window the VMEM check admits compiles; one 512-row step
    longer is refused by the check with an error naming the limit, before
    Mosaic sees it."""
    longest = jagged.SCOPED_VMEM_BYTES // (3 * LANES * 4)
    longest -= longest % 512
    c = densify_decode.lower(
        _spec(one_chip, (1, (B + 1) * longest, LANES), jnp.int32),
        _spec(one_chip, (B + 1,), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
        max_len=longest, ts_col=0).compile()
    _assert_mosaic(c)
    too_long = longest + 512
    with pytest.raises(ValueError, match=str(jagged.SCOPED_VMEM_BYTES)):
        fused_densify(jnp.zeros((1, 3), jnp.int32),
                      jnp.array([0, 1], jnp.int32), too_long)


@pytest.mark.parametrize("under_set_mesh", [False, True],
                         ids=["no_mesh_set", "mesh_set"])
def test_sharded_init_draws_only_each_chips_rows(v5e_2x2, under_set_mesh):
    """The full 10,000,384-row item table, row-sharded over a 1x4 mesh with
    Explicit axes: the random init each chip runs needs no temporaries the
    size of a table, whether or not the training mesh is set."""
    import contextlib

    from repro.configs import get_arch
    from repro.configs.dlrm_uih import FULL
    from repro.launch.steps import build_cell, sharded_init

    mesh = jax.sharding.Mesh(
        np.array(v5e_2x2).reshape(1, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Explicit,) * 2)
    arch = dataclasses.replace(get_arch("dlrm-uih"), shapes={
        "train": {"kind": "train", "batch": 128}})
    cell = build_cell(arch, "train", mesh, cfg_override=FULL)
    ctx = jax.set_mesh(mesh) if under_set_mesh else contextlib.nullcontext()
    with ctx:
        lowered = sharded_init(cell, mesh).lower(
            jax.ShapeDtypeStruct((), jnp.int32))
    mem = lowered.compile().memory_analysis()
    row_slice = FULL.item_vocab // 4 * FULL.embed_dim * 4
    assert mem.output_size_in_bytes >= row_slice
    assert mem.temp_size_in_bytes < row_slice // 8
