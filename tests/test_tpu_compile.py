"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

The TPU compiler is installed beside JAX, so every jitted step and Pallas
kernel of the DSE path is lowered and compiled here for one chip of a
described ``v5e:2x2`` topology, at the sizes users run: what the chip's
compiler would refuse (an unsupported Mosaic primitive, a block that does
not tile, more fast memory than a kernel may use) fails here, without a
chip. Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import characterize, corners, retention
from repro.hetero.system import METRIC_COLS, _score_jit
from repro.kernels.flash_attention import flash_attention
from repro.kernels.retention_kernel import retention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas
from repro.sim.engine import SIM_COLS, _sim_grid_xla


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn).lower(*args).compile().as_text()


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_characterize_batch_compiles(one_chip):
    _compile(characterize.characterize_batch, _f32(120, 7),
             sharding=one_chip)


def test_characterize_corner_compiles(one_chip):
    fn = characterize._characterize_vmap_jit(corners.resolve(corners.HOT))
    _compile(fn, _f32(120, 7), sharding=one_chip)


def test_corner_stack_and_one_fetch_join_compile(one_chip):
    """The (N, C) stack of three swept points' 19 columns, and the join
    that brings them to the host in one transfer, compile as pure data
    moves: no fusion computes anything."""
    from repro.transfer import _join
    per_corner = [{f"m{i}": _f32(120) for i in range(19)}] * 3
    _compile(characterize._stack_corners, per_corner, sharding=one_chip)
    hlo = _compile(_join, [_f32(120, 3)] * 19, sharding=one_chip)
    assert "concatenate" in hlo
    for op in ("add(", "multiply(", "divide("):
        assert op not in hlo, op


def test_score_grid_compiles(one_chip):
    J, S = 20_000, 5
    _compile(_score_jit, jax.ShapeDtypeStruct((J, S), jnp.int32),
             {k: _f32(120) for k in METRIC_COLS}, _f32(S), _f32(S),
             sharding=one_chip)


def test_sim_replay_grid_compiles(one_chip):
    J, T, S = 50_000, 32, 4
    params = {k: _f32(J, S) for k in SIM_COLS + ("tiles", "interval_s")}
    slot = {"cap_bits": _f32(S), "lifetime_s": _f32(S)}
    xs = (_f32(T), _f32(T, S), _f32(T, S), _f32(T, S))
    _compile(_sim_grid_xla, params, slot, xs, _f32(5), sharding=one_chip)


@pytest.mark.parametrize("rows", [120, 4096])
def test_retention_kernel_compiles(one_chip, rows):
    hlo = _compile(retention_pallas, _f32(rows, 10),
                   _f32(retention.N_STEPS + 1), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16)
    hlo = _compile(flash_attention, q, q, q, sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_ssm_scan_compiles(one_chip):
    B, S, di, n = 1, 2048, 3072, 16
    hlo = _compile(ssm_scan_pallas, _f32(B, S, di), _f32(B, S, di),
                   _f32(di, n), _f32(B, S, n), _f32(B, S, n), _f32(di),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo
