"""Main-path kernels compile for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib, so the programs the chip would run are
compiled here against a ``v5e:2x2`` topology description: the two Pallas
tiles (Mosaic refuses layouts interpret mode accepts) and, under
``jax.enable_x64``, the stage-2 scan and the stage-4 round-1 replay at the
datacenter switch's width.  Nothing runs, so these say nothing about results
or times; they catch what the chip's compiler would refuse, and programs
that do not fit the chip's 16 GB.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports every test file.  The persistent compilation cache is
off around these compiles — an entry written for a described chip cannot be
read back without one.
"""

import os

import pytest

V5E_HBM_BYTES = 16 * 1024**3
B, M_TILE, M_TRACE, N_PORTS = 256, 4096, 100_000, 32


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
            - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("n_pad", [N_PORTS, 128])
@pytest.mark.parametrize("tile", ["netsim", "xbar"])
def test_pallas_tile_compiles_for_v5e(one_chip, tile, n_pad):
    """Both tiles lower through Mosaic at B=256, m=4096 events, the port
    axis padded to the datacenter switch's 32 and to a full 128."""
    import jax.numpy as jnp

    from repro.kernels.netsim.kernel import netsim_replay_padded
    from repro.kernels.xbar.kernel import xbar_contend_padded

    f32, i32 = jnp.float32, jnp.int32
    timeline = (_shape(one_chip, (M_TILE,), f32),
                _shape(one_chip, (M_TILE,), i32),
                _shape(one_chip, (M_TILE,), i32))
    events = _shape(one_chip, (M_TILE, B), f32)
    if tile == "netsim":
        lowered = netsim_replay_padded.lower(
            *timeline, events, events, _shape(one_chip, (1, B), f32),
            n_pad=n_pad)
    else:
        lowered = xbar_contend_padded.lower(*timeline, events, n_pad=n_pad)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_stage2_engine_compiles_for_v5e(one_chip):
    """``surrogate.engine``: the float64 crossbar scan at n_ports=32,
    B=256, m=1e5 — the TPU emulates float64, and it must still compile."""
    import jax
    import jax.numpy as jnp

    from repro.sim.batched_surrogate import _engine

    with jax.enable_x64():
        f64, i32 = jnp.float64, jnp.int32
        compiled = _engine.lower(
            _shape(one_chip, (M_TRACE,), f64),
            _shape(one_chip, (M_TRACE,), i32),
            _shape(one_chip, (M_TRACE,), i32),
            _shape(one_chip, (B, M_TRACE), f64),
            _shape(one_chip, (M_TRACE,), f64),
            _shape(one_chip, (B,), f64),
            n_ports=N_PORTS, use_pallas=False, interpret=False).compile()
    _assert_fits(compiled)


def test_stage4_round1_compiles_for_v5e(one_chip):
    """``netsim.kernel.round1``: the fused float64 replay + fullness check
    of the stage-4 fixed point at n_ports=32, B=256, m=1e5."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.netsim.ops import _round1

    with jax.enable_x64():
        f64, i32 = jnp.float64, jnp.int32
        chain = [_shape(one_chip, (M_TRACE,), i32)] * 3
        compiled = _round1.lower(
            _shape(one_chip, (M_TRACE,), f64),
            _shape(one_chip, (M_TRACE,), i32),
            _shape(one_chip, (M_TRACE,), i32),
            _shape(one_chip, (M_TRACE, B), f64),
            _shape(one_chip, (B,), f64),
            _shape(one_chip, (B,), i32),
            *chain, n_ports=N_PORTS).compile()
    _assert_fits(compiled)
