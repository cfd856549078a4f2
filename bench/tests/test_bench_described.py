"""The set-up and reference of a four-chip dense DLRM at the program's
``dlrm-dense`` sizes (26 tables x 2M ids x dim 128 in float32, 26.6 GB),
compiled for a described v5e:2x2 host.

Nothing runs, so no chip is needed: each test lowers and compiles for chips
that are described, not attached (``jax.experimental.topologies``), with the
shardings the harness gives a four-chip cell (``model._make_placed``), and
reads the bytes per device from ``memory_analysis()``.  A v5e chip holds
16 GB; the row-sharded weights and one table step of the reference have to
fit in it.  The persistent compilation cache is off around these compiles:
an entry written for a described chip cannot be read back.
"""

from __future__ import annotations

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import model as model_mod, peaks
from bench.reference import dlrm as reference
from bench.traffic import generator

CHIPS = 4
HBM = peaks.peaks("TPU v5 lite")["hbm_bytes"]


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield tuple(topo.devices[:CHIPS])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dense():
    """``dlrm-qr``'s sizes with dense tables, checked against the program's
    ``dlrm-dense``."""
    from repro.configs import registry

    m = dataclasses.replace(model_mod.load("dlrm-qr"), name="dlrm-dense",
                            registry_id="dlrm-dense", kind="dense", collision=0)
    model_mod.check_program_config(m, registry.get_dlrm("dlrm-dense"))
    return m


def _shardings(devices):
    mesh = Mesh(devices, (model_mod.ROW_AXIS,))
    return NamedSharding(mesh, P(model_mod.ROW_AXIS, None)), NamedSharding(mesh, P())


def _collectives(compiled) -> int:
    text = compiled.as_text()
    return sum(text.count(f" {op}(") for op in
               ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"))


def _weight_bytes_per_chip(m) -> int:
    """A chip's share of the tables plus the whole MLPs, by hand."""
    (rows, dim), = m.table_shapes().values()
    mlp = sum(i * o + o for dims in m.mlp_dims().values() for i, o in dims)
    return m.num_tables * (rows // CHIPS) * dim * 4 + mlp * 4


def test_dense_weights_are_made_in_place_on_four_chips(four_chips, dense):
    _, whole = _shardings(four_chips)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    compiled = model_mod._make_placed(dense, four_chips).lower(key).compile()
    ma = compiled.memory_analysis()
    want = _weight_bytes_per_chip(dense)
    assert want == 6_665_475_588
    # each output buffer is padded to its tile, a few KiB in all
    assert want <= ma.output_size_in_bytes <= want + 2**20
    assert ma.temp_size_in_bytes < 2**24
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < 0.5 * HBM
    assert _collectives(compiled) == 0


@pytest.mark.parametrize("precision", reference.PRECISIONS)
def test_reference_table_step_fits_beside_the_weights(four_chips, dense,
                                                      precision):
    rows_sharded, whole = _shardings(four_chips)
    (rows, dim), = dense.table_shapes().values()
    batch = generator.load("zipf-2k")["batch"]
    tab = {"table": jax.ShapeDtypeStruct((rows, dim), jnp.float32,
                                         sharding=rows_sharded)}
    ids = jax.ShapeDtypeStruct((batch, dense.pooling), jnp.int32, sharding=whole)
    with jax.default_matmul_precision("highest"):
        compiled = reference._table_pooled.lower(tab, ids, dense,
                                                 precision).compile()
    ma = compiled.memory_analysis()
    shard = rows // CHIPS * dim * 4
    ids_bytes = math.prod(ids.shape) * 4
    assert shard <= ma.argument_size_in_bytes <= shard + ids_bytes + 2**20
    assert ma.output_size_in_bytes == batch * dim * 4
    # at most one more copy of the chip's shard of the table (the controls
    # round it to a lower precision), beside every table's weights
    assert ma.temp_size_in_bytes <= shard + 2**20
    step = ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert _weight_bytes_per_chip(dense) + step < 0.5 * HBM
