"""Compile rehearsals for a described TPU v5e (2x2) — no chip attached.

The TPU compiler is installed with libtpu and compiles for a topology
that is described, not present. It refuses what interpret mode accepts:
kernel operand layouts Mosaic cannot tile, programs that do not fit the
chip's memory. These tests compile the engine's Pallas kernels at the
paper's widths (d = 602 and 64) and one whole super-tick program at the
shapes `chip_smoke.py` runs, and look at what the compiler produced.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu at a time, so describing it while
pytest-xdist workers import this file would fail the others.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import load_chip_smoke

HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip is written to a persistent
    # cache but cannot be read back here: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("block_e", [128, 512])
@pytest.mark.parametrize("d", [602, 64])
@pytest.mark.parametrize("mode", ["add", "set"])
def test_segment_deliver_compiles(one_chip, mode, d, block_e):
    """The delivery kernel at payload widths 602+2 and 64+2 and at both
    block sizes the engine passes (PallasDelivery's 128, the kernel
    default 512). A 1-D id block below XLA's 1024 tiling used to be
    refused: "XLA layout {0:T(1024)} does not match Mosaic layout"."""
    from repro.kernels.segment_reduce.ops import segment_deliver

    f = jax.jit(lambda i, v, c: segment_deliver(
        i, v, c, 4096, mode=mode, block_e=block_e, block_v=128,
        interpret=False))
    compiled = f.lower(_sds(one_chip, (1024,), jnp.int32),
                       _sds(one_chip, (1024, d), jnp.float32),
                       _sds(one_chip, (1024,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [602, 64])
def test_mean_rows_compiles(one_chip, d):
    from repro.kernels.segment_reduce.ops import mean_rows

    f = jax.jit(lambda s, c: mean_rows(s, c, interpret=False))
    compiled = f.lower(_sds(one_chip, (1000, d), jnp.float32),
                       _sds(one_chip, (1000,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_route_pack_pallas_placement_compiles(one_chip):
    """The routing plane's pallas placement: a [N, W] packed wire block
    (W = 64 + 5, a MsgBatch row at d = 64) into 4 x 1024 send slots."""
    from repro.kernels.route_pack.ops import route_pack

    f = jax.jit(lambda r, s: route_pack(r, s, 4096, backend="pallas",
                                        interpret=False))
    compiled = f.lower(_sds(one_chip, (3000, 69), jnp.float32),
                       _sds(one_chip, (3000,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _smoke_super_tick(**placement):
    """The xla-backend super-tick program at chip_smoke.py's shapes
    (GraphSAGE 602->64->64, deployment-sized caps for reddit's 232,965
    nodes, T = 8), compiled; returns (compiled, bytes per device)."""
    from repro.configs.d3gnn_sage import D_HID, D_IN
    from repro.configs.gnn_common import GNN_SHAPES
    from repro.core.pipeline import lower_super_tick
    from repro.graph.sage import GraphSAGE

    smoke = load_chip_smoke()
    sizes = smoke.full_sizes(GNN_SHAPES["minibatch_lg"].dims["global_nodes"])
    cfg = smoke.pipeline_config(sizes)
    model = GraphSAGE((D_IN, D_HID, D_HID))
    compiled = lower_super_tick(model, cfg, sizes.T, **placement).compile()
    m = compiled.memory_analysis()
    # the donated carry aliases its outputs: state is not held twice
    assert m.alias_size_in_bytes > 0.9 * m.output_size_in_bytes
    return compiled, smoke.program_bytes(compiled)


def test_super_tick_fits_one_chip(one_chip):
    _, total = _smoke_super_tick(sharding=one_chip)
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e"


def test_sharded_super_tick_compiles_on_four_chips(topo):
    """The same program on a 4-chip ("data",) mesh: MeshRouter's dense
    all_to_all wire is in it, and each chip holds a quarter of the parts
    — memory_analysis counts the bytes of one device."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    compiled, per_device = _smoke_super_tick(mesh=mesh)
    assert "all-to-all" in compiled.as_text()
    assert per_device < HBM_BYTES / 2, f"{per_device} bytes per chip"
