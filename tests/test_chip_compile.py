"""Real-width compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached (``jax.experimental.topologies``), which is where the TPU
compiler refuses block shapes, layouts and VMEM use that interpret mode
accepts.  Shapes are the published ``dlrm-qr`` / ``dlrm-tt`` widths: 26
tables x 2M rows x dim 128, pooling 32.  Every compiled program must contain
the Pallas kernel (``tpu_custom_call``), so no reference path passes here.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and the fixture skips cleanly where it
cannot be described.  The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine as E
from repro.configs import registry
from repro.kernels import cached_gather, gnr_bag, packed_gather, qr_gather, tt_gather
from repro.models import dlrm

DIM, K = 128, 32
Q_ROWS = 812_501           # 26 QR quotient tables (2M / 64 rows) + zero row
R_ROWS = 26 * 64 + 1       # 26 remainder LUTs + zero row
SLOTS = 16_384             # 8 MiB of 512 B rows: the VMEM cache block
TT_DIMS = (4, 8, 4, 16)    # (d1, d2, d3, rank) for dim 128, rank 16
TT_ROWS = 36_037           # 26 middle cores (1386 rows) + zero row
TT_OUTER = 26 * 38         # 26 packed outer cores (v1 = v3 = 38)
TT_SLOTS = 1_024           # 8 MiB of 8 KiB middle-core rows
BAGS = 416                 # a (416, 32) bag stream: 16 samples x 26 tables


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _table_copies_at_most(compiled, table_bytes: int, copies: int) -> None:
    """The streamed table is read in place: at most ``copies`` temp buffers
    as large as it."""
    assert compiled.memory_analysis().temp_size_in_bytes < (copies + 1) * table_bytes


@pytest.mark.parametrize("kind,dtype", [
    ("dense", jnp.float32), ("qr", jnp.float32), ("qr", jnp.bfloat16),
])
def test_packed_row_bag_compiles(one_chip, kind, dtype):
    table = _sds(one_chip, (Q_ROWS, 1, DIM), dtype)       # the packed row view
    cache = _sds(one_chip, (SLOTS, DIM))
    stream = _sds(one_chip, (BAGS, K), jnp.int32)
    if kind == "dense":
        fn = functools.partial(packed_gather.packed_bag, interpret=False)
        compiled = _compile(fn, table, cache, stream, stream)
    else:
        fn = functools.partial(packed_gather.packed_qr_bag, interpret=False)
        r_lut = _sds(one_chip, (R_ROWS, DIM), dtype)
        compiled = _compile(fn, table, cache, r_lut, stream, stream, stream)
    if dtype == jnp.float32:
        _table_copies_at_most(compiled, Q_ROWS * DIM * 4, 0)


def test_packed_tt_bag_compiles(one_chip):
    d1, d2, d3, rank = TT_DIMS
    g2 = _sds(one_chip, (TT_ROWS, rank, d2 * rank))       # the packed G2 view
    cache = _sds(one_chip, (TT_SLOTS, rank, d2 * rank))
    g1 = _sds(one_chip, (TT_OUTER, d1 * rank))
    g3 = _sds(one_chip, (TT_OUTER, rank * d3))
    stream = _sds(one_chip, (BAGS, K), jnp.int32)
    fn = functools.partial(packed_gather.packed_tt_bag, dims=TT_DIMS, interpret=False)
    compiled = _compile(fn, g1, g2, g3, cache, stream, stream, stream, stream)
    _table_copies_at_most(compiled, TT_ROWS * rank * d2 * rank * 4, 0)


@pytest.mark.parametrize("name", [
    "cached_bag", "cached_qr_bag", "gnr_bag", "gnr_bag_dense", "qr_gather", "tt_bag",
])
def test_per_table_kernel_compiles(one_chip, name):
    """The per-table and training kernels at one table's real widths."""
    rows = 31_360                                         # one padded Q table
    table = _sds(one_chip, (rows, DIM))
    r_lut = _sds(one_chip, (64, DIM))
    cache = _sds(one_chip, (630, DIM))
    bags = _sds(one_chip, (2048, K), jnp.int32)
    flat = _sds(one_chip, (2048 * K,), jnp.int32)
    kw = {"interpret": False}
    if name == "cached_bag":
        _compile(functools.partial(cached_gather.cached_bag, **kw), table, cache, bags, bags)
    elif name == "cached_qr_bag":
        _compile(functools.partial(cached_gather.cached_qr_bag, **kw),
                 table, cache, r_lut, bags, bags, bags)
    elif name == "gnr_bag":
        _compile(functools.partial(gnr_bag.gnr_bag, **kw), table, r_lut, bags, bags)
    elif name == "gnr_bag_dense":
        _compile(functools.partial(gnr_bag.gnr_bag_dense, **kw), table, bags)
    elif name == "qr_gather":
        _compile(functools.partial(qr_gather.qr_gather, **kw), table, r_lut, flat, flat)
    else:
        d1, d2, d3, rank = TT_DIMS
        g1 = _sds(one_chip, (38, d1 * rank))
        g2 = _sds(one_chip, (1408, rank * d2 * rank))
        g3 = _sds(one_chip, (38, rank * d3))
        _compile(functools.partial(tt_gather.tt_bag, dims=TT_DIMS, **kw),
                 g1, g2, g3, bags, bags, bags)


@pytest.mark.parametrize("arch", ["dlrm-qr", "dlrm-tt"])
def test_serve_gather_step_compiles(one_chip, arch):
    """The jitted serving step (index packing + cache-block gather + the
    megakernel + combiner scale) at the serve_2k batch, for the chip."""
    cfg = registry.get_dlrm(arch)
    plan = E.plan(E.EngineSpec.from_dlrm(cfg, serving=True, duplication=False))
    layout = plan.layout
    tables = jax.eval_shape(
        lambda k: E.compile(plan).pack(dlrm.init_dlrm(k, cfg)[0]["tables"]),
        jax.random.PRNGKey(0),
    )
    packed = {k: _sds(one_chip, v.shape, v.dtype) for k, v in tables.items()}
    batch = (2048, cfg.num_tables, cfg.pooling)
    idx = _sds(one_chip, batch, jnp.int32)
    rows = _sds(one_chip, (layout.total_slots,), jnp.int32)
    fn = functools.partial(E.engine._serve_gather_jit, plan=plan, interpret=False)
    compiled = _compile(fn, packed, idx, idx, rows)
    big = packed[{"qr": "q", "tt": "g2"}[layout.kind]]
    # The cache-staging gather ``big[cache_rows]`` relayouts a (rows, 1, dim)
    # row view once per call (XLA gathers from (8, 128) tiles); the TT view
    # (rows, r, d2*r) is already tiled.
    copies = 1 if layout.kind == "qr" else 0
    _table_copies_at_most(compiled, big.size * big.dtype.itemsize, copies)
