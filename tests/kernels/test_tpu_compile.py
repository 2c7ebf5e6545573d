"""Compile the main path's GC kernels for a TPU v5e, at a real cache's widths.

Interpret mode cannot see the chip's tiling rules or its VMEM budget; the
TPU compiler can, and it is installed even where no chip is attached.  These
tests hand it a described ``v5e:2x2`` topology and the widths of the paged
cache that ``chip_smoke.py`` runs: 1024 sequences, 8 descriptor versions
each, 8 reader lanes, 80 pages per sequence (8192 page-table versions of 81
columns), and the retire ring's 16384-row flush sweep.

The fused Mamba-2 state update compiles at the widths of the served
``granite-4.0-h-micro`` cell: a batch of 32 sequences, 64 heads of 64
channels, a state of 128 per channel, in bfloat16.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test worker that loads it at
import would stop the others from collecting.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact.kernel import compact_pallas, needed_pallas
from repro.kernels.ssm_update.kernel import ssm_update_pallas
from repro.kernels.version_search.kernel import (search_gather_pallas,
                                                 search_pallas)

SEQS, VERSIONS, LANES, MAX_PAGES = 1024, 8, 8, 80
TABLES = SEQS * VERSIONS
RING = 2 * TABLES


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [LANES, SEQS, RING],
                         ids=["hot", "store", "ring"])
def test_compact_compiles(one_chip, rows):
    mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    hlo = _compiled_text(
        compact_pallas, _i32(one_chip, rows, VERSIONS),
        _i32(one_chip, rows, VERSIONS), _i32(one_chip, rows, VERSIONS), mask,
        _i32(one_chip, LANES + 1), _i32(one_chip))
    assert "tpu_custom_call" in hlo


def test_needed_compiles(one_chip):
    hlo = _compiled_text(
        needed_pallas, _i32(one_chip, SEQS, VERSIONS),
        _i32(one_chip, SEQS, VERSIONS), _i32(one_chip, LANES),
        _i32(one_chip))
    assert "tpu_custom_call" in hlo


def test_search_compiles(one_chip):
    hlo = _compiled_text(
        search_pallas, _i32(one_chip, SEQS, VERSIONS),
        _i32(one_chip, SEQS, VERSIONS), _i32(one_chip, SEQS),
        _i32(one_chip, SEQS))
    assert "tpu_custom_call" in hlo


def test_search_gather_compiles(one_chip):
    hlo = _compiled_text(
        search_gather_pallas, _i32(one_chip, SEQS, VERSIONS),
        _i32(one_chip, SEQS, VERSIONS), _i32(one_chip, TABLES, MAX_PAGES + 1),
        _i32(one_chip, SEQS), _i32(one_chip, SEQS))
    assert "tpu_custom_call" in hlo


def test_ssm_update_compiles(one_chip):
    batch, heads, head_dim, d_state = 32, 64, 64, 128
    lanes = heads * head_dim

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    state = jax.ShapeDtypeStruct((batch, d_state, lanes), jnp.bfloat16,
                                 sharding=one_chip)
    compiled = jax.jit(ssm_update_pallas, donate_argnums=0).lower(
        state, f32(batch, 1, lanes), f32(batch, 1, lanes), f32(1, lanes),
        f32(1, lanes), f32(batch, d_state, 1), f32(batch, d_state, 1)
    ).compile()
    assert "%ssm_update" in compiled.as_text()
    # the state is written over its own buffer
    assert compiled.memory_analysis().alias_size_in_bytes == \
        batch * d_state * lanes * 2
