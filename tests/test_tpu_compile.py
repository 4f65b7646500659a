"""Compile rehearsals for the TPU v5e: the graphs the default device path
runs, compiled at deployment size for a described ``v5e:2x2`` topology
with no chip attached.  Nothing runs; a compile that the chip's compiler
refuses fails here.

* ``_fused_pipeline`` — the default lookup graph, narrow and wide keys,
  with CSR chains, at 2^25 slots and an 8192-query bucket;
* ``_ingest_place_xla`` — the default ingest placement, on a 2^16 batch;
* the ``ShardFanout`` ``shard_map`` graph on a 2x2 mesh of the described
  devices (four shards of 2^23 slots).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ops as _ops
from repro.kernels import ops_gap as _ops_gap
from repro.kernels.shard_fanout import ShardFanout

N_SLOTS = 1 << 25
W_TILE = 2048
M_PAD = N_SLOTS + W_TILE  # _freeze_numpy: padded slots + one +inf block
N_LINKS = 1 << 16
K_PAD = 1 << 12
BUCKET = 8192
RANK_ROWS = (1 << 16) + 1  # build_rank_router's default r_bits
TRIPS = 12
MAX_CHAIN = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip; keep these compiles in memory only
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_device_fit(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 10 ** 9, f"{used} bytes exceed one v5e's HBM"
    return mem


@pytest.mark.parametrize("key_wide", [False, True], ids=["narrow", "wide"])
def test_fused_lookup_graph_compiles_for_v5e(one_chip, key_wide):
    f32, i32 = jnp.float32, jnp.int32
    lo_m = M_PAD if key_wide else 0
    lo_l = N_LINKS if key_wide else 0
    hi_m = M_PAD if key_wide else 0
    hi_l = N_LINKS if key_wide else 0
    args = [
        _spec((BUCKET,), f32, one_chip),             # queries
        _spec((BUCKET if key_wide else 0,), f32, one_chip),
        _spec((M_PAD,), f32, one_chip),              # slot_key
        _spec((lo_m,), f32, one_chip),               # slot_key_lo
        _spec((M_PAD,), i32, one_chip),              # payload
        _spec((hi_m,), i32, one_chip),               # payload_hi
        _spec((M_PAD + W_TILE,), i32, one_chip),     # link_offsets
        _spec((N_LINKS,), f32, one_chip),            # link_keys
        _spec((lo_l,), f32, one_chip),               # link_keys_lo
        _spec((N_LINKS,), i32, one_chip),            # link_payloads
        _spec((hi_l,), i32, one_chip),               # link_payload_hi
        _spec((RANK_ROWS,), i32, one_chip),          # rank_table
        _spec((3,), f32, one_chip),                  # rank_scale
    ]
    compiled = _ops._fused_pipeline.lower(
        *args, trips=TRIPS, max_chain=MAX_CHAIN, wide=key_wide,
        key_wide=key_wide).compile()
    mem = _assert_device_fit(compiled)
    assert mem.argument_size_in_bytes >= 3 * 4 * M_PAD
    assert "tpu_custom_call" not in compiled.as_text()  # plain XLA graph


def test_ingest_place_graph_compiles_for_v5e(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    batch = 1 << 16
    args = [
        _spec((batch,), f32, one_chip), _spec((batch,), f32, one_chip),
        *[_spec((K_PAD,), f32, one_chip) for _ in range(6)],  # seg tables
        _spec((M_PAD,), f32, one_chip), _spec((M_PAD,), f32, one_chip),
        _spec((M_PAD + W_TILE,), i32, one_chip),
        _spec((N_LINKS,), f32, one_chip), _spec((N_LINKS,), f32, one_chip),
    ]
    compiled = _ops_gap._ingest_place_xla.lower(
        *args, n_slots=N_SLOTS).compile()
    _assert_device_fit(compiled)


def test_shard_fanout_graph_compiles_on_a_2x2_mesh(topo):
    """The fan-out builds its mesh from ``jax.devices()`` and places its
    arrays there; here the same graph is built over the described
    devices instead, and compiled from shapes."""
    S = D = 4
    m_pad = N_SLOTS // S + W_TILE
    n_links = N_LINKS // S
    mesh = Mesh(np.asarray(topo.devices[:D]).reshape(D, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shapes = {
        "slot_key": ((S, m_pad), jnp.float32),
        "slot_key_lo": ((S, m_pad), jnp.float32),
        "payload": ((S, m_pad), jnp.int32),
        "payload_hi": ((S, 0), jnp.int32),
        "link_offsets": ((S, m_pad + W_TILE), jnp.int32),
        "link_keys": ((S, n_links), jnp.float32),
        "link_keys_lo": ((S, n_links), jnp.float32),
        "link_payloads": ((S, n_links), jnp.int32),
        "link_payload_hi": ((S, 0), jnp.int32),
        "rank_table": ((S, RANK_ROWS), jnp.int32),
        "rank_scale": ((S, 3), jnp.float32),
    }
    fan = object.__new__(ShardFanout)
    fan.S, fan.D, fan.mesh = S, D, mesh
    fan.statics = {"n_shards": S, "trips": TRIPS, "max_chain": MAX_CHAIN,
                   "wide": False, "key_wide": True}
    fan.r_trips = int(np.ceil(np.log2(S - 1))) + 1
    fan.min_bucket, fan._cap_boost = 512, {}
    fan._specs = {k: P("data", None) for k in shapes}
    fan.stacked = {k: _spec(shp, dt, NamedSharding(mesh, fan._specs[k]))
                   for k, (shp, dt) in shapes.items()}
    bucket = fan._bucket(BUCKET)
    rep = NamedSharding(mesh, P())
    q = NamedSharding(mesh, P("data"))
    compiled = fan._build_fn(fan._cap_for(bucket)).lower(
        _spec((bucket,), jnp.float32, q), _spec((bucket,), jnp.float32, q),
        _spec((S - 1,), jnp.float32, rep), _spec((S - 1,), jnp.float32, rep),
        _spec((8,), jnp.float32, rep), fan.stacked).compile()
    _assert_device_fit(compiled)
    assert "all-to-all" in compiled.as_text()
