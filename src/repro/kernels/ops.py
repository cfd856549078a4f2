"""Public jit'd wrappers around the Pallas kernels.

Dispatch rules:

* on CPU (this container) kernels run with ``interpret=True`` — the kernel body
  executes in Python, validating the exact TPU program;
* arbitrary leading index shapes are flattened to the kernel's (N,)/(B,K)
  layouts and restored;
* the lane tile (``dim_block``) is an explicit knob: callers may pass a tuned
  block (``repro.tune`` / ``EmbeddingPlan.dim_block``); ``None`` takes the
  heuristic ladder default.  Dims with no 8-aligned tile fall back to the
  jnp reference in interpret mode only (tests exercise it); a compiled
  kernel call with such a dim raises instead of silently leaving the device
  path (the assigned archs all have 128-aligned dims).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import blocks
from repro.kernels import cached_gather as _cg
from repro.kernels import gnr_bag as _gnr
from repro.kernels import qr_gather as _qr
from repro.kernels import ref
from repro.kernels.blocks import bag_grid, block_grid, tt_block_grid
from repro.tune import knobs as _knobs


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _flat(x: jax.Array) -> jax.Array:
    """A packed buffer in its row view (``packed_tables.stream_view``) ->
    the flat ``(rows, width)`` layout the jnp oracles take."""
    return x.reshape(x.shape[0], -1)


def _pick_dim_block(dim: int) -> int | None:
    """Heuristic lane-tile default — now sourced from the tuner's knob space
    (``repro.tune.knobs``), same ladder: largest of 512/256/128 dividing dim,
    else the whole dim as one padded tile when 8-aligned, else ``None`` (the
    caller takes the pure-jnp reference path).  Kept as the zero-knob
    fallback; tuned plans pass ``dim_block=`` explicitly instead."""
    return _knobs.default_dim_block(dim)


def _resolve_dim_block(
    dim: int, dim_block: int | None, interpret: bool
) -> int | None:
    """An explicit ``dim_block`` must be legal for ``dim``; ``None`` defers
    to the heuristic ladder.  A dim with no tile takes the jnp reference in
    interpret mode and raises for a compiled kernel."""
    if dim_block is None:
        bd = _knobs.default_dim_block(dim)
        if bd is None and not interpret:
            raise ValueError(
                f"dim {dim} has no 8-aligned lane tile: the compiled kernel "
                "cannot run it and there is no device fallback"
            )
        return bd
    valid = _knobs.valid_dim_blocks(dim)
    if dim_block not in valid:
        raise ValueError(
            f"dim_block={dim_block} is not valid for dim {dim}; "
            f"valid blocks: {list(valid) or '(none: jnp reference only)'}"
        )
    return dim_block


def qr_lookup(
    q_table: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    r_idx: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Fused QR reconstruction for any index shape: (...,) -> (..., D)."""
    interpret = _interpret_default() if interpret is None else interpret
    dim = q_table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.qr_lookup_ref(q_table, r_lut, q_idx, r_idx)
    shape = q_idx.shape
    out = _qr.qr_gather(
        q_table, r_lut, q_idx.reshape(-1), r_idx.reshape(-1),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*shape, dim)


def gnr_pooled(
    q_table: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    r_idx: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Pooled QR bag for index shape (..., K) -> (..., D)."""
    interpret = _interpret_default() if interpret is None else interpret
    dim = q_table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.gnr_bag_ref(q_table, r_lut, q_idx, r_idx)
    *lead, k = q_idx.shape
    out = _gnr.gnr_bag(
        q_table, r_lut, q_idx.reshape(-1, k), r_idx.reshape(-1, k),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*lead, dim)


def tt_pooled(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    interpret: bool | None = None,
) -> jax.Array:
    """Pooled TT-Rec bag for index shape (..., K) -> (..., D).

    ``dims`` = (d1, d2, d3, rank).  Dims with no 8-aligned output tile fall
    back to the jnp reference (assigned configs all have 128-aligned dims).
    """
    from repro.kernels import tt_gather as _tt

    interpret = _interpret_default() if interpret is None else interpret
    d1, d2, d3, _ = dims
    dim = d1 * d2 * d3
    if dim % 8:
        _resolve_dim_block(dim, None, interpret)
        return ref.tt_bag_ref(g1, g2, g3, i1, i2, i3, dims=dims)
    *lead, k = i1.shape
    out = _tt.tt_bag(
        g1, g2, g3,
        i1.reshape(-1, k), i2.reshape(-1, k), i3.reshape(-1, k),
        dims=dims, interpret=interpret,
    )
    return out.reshape(*lead, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _tt_pooled_diff(g1, g2, g3, i1, i2, i3, dims, interpret):
    """Kernel forward with a reference-recompute vjp (flash_attention idiom):
    pallas_call has no autodiff rule, so the backward pass re-derives the
    core cotangents through the jnp oracle — identical math, fp32 throughout.
    Keeps ``tt_exec="pallas"`` legal inside value_and_grad (training)."""
    return tt_pooled(g1, g2, g3, i1, i2, i3, dims=dims, interpret=interpret)


def _tt_pooled_diff_fwd(g1, g2, g3, i1, i2, i3, dims, interpret):
    out = _tt_pooled_diff(g1, g2, g3, i1, i2, i3, dims, interpret)
    return out, (g1, g2, g3, i1, i2, i3)


def _tt_pooled_diff_bwd(dims, interpret, res, ct):
    g1, g2, g3, i1, i2, i3 = res
    _, vjp = jax.vjp(
        lambda a, b, c: ref.tt_bag_ref(a, b, c, i1, i2, i3, dims=dims), g1, g2, g3
    )
    dg1, dg2, dg3 = vjp(ct)
    zero = lambda i: np.zeros(i.shape, jax.dtypes.float0)
    return dg1, dg2, dg3, zero(i1), zero(i2), zero(i3)


_tt_pooled_diff.defvjp(_tt_pooled_diff_fwd, _tt_pooled_diff_bwd)


def tt_pooled_auto(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    exec_mode: str = "jnp",
    interpret: bool | None = None,
) -> jax.Array:
    """Pooled TT bag with config-driven kernel dispatch (serving/jit path).

    ``exec_mode="pallas"`` routes to the fused gather-contract kernel on TPU
    (or in interpret mode when ``interpret=True`` is forced — tests); on CPU
    the pure-jnp oracle is the fallback, so the same config runs everywhere.
    ``exec_mode="jnp"`` always uses the oracle.  The kernel path is
    differentiable via a reference-recompute vjp, so the flag is safe in
    training configs too.
    """
    if exec_mode == "pallas" and (interpret or jax.default_backend() == "tpu"):
        return _tt_pooled_diff(g1, g2, g3, i1, i2, i3, dims, bool(interpret))
    return ref.tt_bag_ref(g1, g2, g3, i1, i2, i3, dims=dims)


def tt_lookup(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    interpret: bool | None = None,
) -> jax.Array:
    """Fused unpooled TT reconstruction for any index shape: (...,) -> (..., D)."""
    shape = i1.shape
    out = tt_pooled(
        g1, g2, g3,
        i1.reshape(-1, 1), i2.reshape(-1, 1), i3.reshape(-1, 1),
        dims=dims, interpret=interpret,
    )
    d1, d2, d3, _ = dims
    return out.reshape(*shape, d1 * d2 * d3)


def cached_pooled(
    table: jax.Array,
    cache: jax.Array,
    idx: jax.Array,
    slot: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Cached pooled bag for index shape (..., K) -> (..., D).

    ``cache`` is the prefetch scheduler's staged block; ``slot`` its per-access
    routing (-1 = miss -> the HBM row).
    """
    interpret = _interpret_default() if interpret is None else interpret
    dim = table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.cached_bag_ref(table, cache, idx, slot)
    *lead, k = idx.shape
    out = _cg.cached_bag(
        table, cache, idx.reshape(-1, k), slot.reshape(-1, k),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*lead, dim)


def cached_qr_pooled(
    q_table: jax.Array,
    cache: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    slot: jax.Array,
    r_idx: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Cached pooled QR bag for index shape (..., K) -> (..., D)."""
    interpret = _interpret_default() if interpret is None else interpret
    dim = q_table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.cached_qr_bag_ref(q_table, cache, r_lut, q_idx, slot, r_idx)
    *lead, k = q_idx.shape
    out = _cg.cached_qr_bag(
        q_table, cache, r_lut,
        q_idx.reshape(-1, k), slot.reshape(-1, k), r_idx.reshape(-1, k),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*lead, dim)


# ---------------------------------------------------------------------------
# packed-table megakernel wrappers (multi-table fused gather; see
# repro.kernels.packed_gather / repro.core.packed_tables)
# ---------------------------------------------------------------------------

def packed_dense_pooled(
    table: jax.Array,
    cache: jax.Array,
    idx: jax.Array,
    slot: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Packed dense megabag for index shape (..., K) -> (..., D).

    ``idx`` rows are global packed-buffer rows (per-table offsets applied by
    ``repro.core.packed_tables``); ``slot`` routes into the packed cache block
    (-1 = miss -> streamed HBM row)."""
    from repro.kernels import packed_gather as _pg

    interpret = _interpret_default() if interpret is None else interpret
    dim = table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.packed_bag_ref(_flat(table), _flat(cache), idx, slot)
    *lead, k = idx.shape
    out = _pg.packed_bag(
        table, cache, idx.reshape(-1, k), slot.reshape(-1, k),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*lead, dim)


def packed_qr_pooled(
    q_table: jax.Array,
    cache: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    slot: jax.Array,
    r_idx: jax.Array,
    *,
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Packed QR megabag for index shape (..., K) -> (..., D)."""
    from repro.kernels import packed_gather as _pg

    interpret = _interpret_default() if interpret is None else interpret
    dim = q_table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.packed_qr_bag_ref(
            _flat(q_table), _flat(cache), r_lut, q_idx, slot, r_idx
        )
    *lead, k = q_idx.shape
    out = _pg.packed_qr_bag(
        q_table, cache, r_lut,
        q_idx.reshape(-1, k), slot.reshape(-1, k), r_idx.reshape(-1, k),
        dim_block=bd, interpret=interpret,
    )
    return out.reshape(*lead, dim)


def packed_tt_pooled(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    cache: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    slot: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    interpret: bool | None = None,
) -> jax.Array:
    """Packed TT megabag for index shape (..., K) -> (..., D)."""
    from repro.kernels import packed_gather as _pg

    interpret = _interpret_default() if interpret is None else interpret
    d1, d2, d3, _ = dims
    if (d1 * d2 * d3) % 8:
        _resolve_dim_block(d1 * d2 * d3, None, interpret)
        return ref.packed_tt_bag_ref(
            g1, _flat(g2), g3, _flat(cache), i1, i2, i3, slot, dims=dims
        )
    *lead, k = i1.shape
    out = _pg.packed_tt_bag(
        g1, g2, g3, cache,
        i1.reshape(-1, k), i2.reshape(-1, k), i3.reshape(-1, k),
        slot.reshape(-1, k),
        dims=dims, interpret=interpret,
    )
    return out.reshape(*lead, d1 * d2 * d3)


# Differentiable megakernel entry points (reference-recompute vjp, the
# tt_pooled_auto idiom): pallas_call has no autodiff rule, so the backward
# pass re-derives table/cache cotangents through the packed jnp oracle —
# identical math, fp32 throughout.  Index streams get float0 cotangents.

def _zero_idx(*idxs):
    return tuple(np.zeros(i.shape, jax.dtypes.float0) for i in idxs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _packed_dense_diff(table, cache, idx, slot, interpret, dim_block=None):
    return packed_dense_pooled(
        table, cache, idx, slot, interpret=interpret, dim_block=dim_block
    )


def _packed_dense_diff_fwd(table, cache, idx, slot, interpret, dim_block=None):
    out = _packed_dense_diff(table, cache, idx, slot, interpret, dim_block)
    return out, (table, cache, idx, slot)


def _packed_dense_diff_bwd(interpret, dim_block, res, ct):
    table, cache, idx, slot = res
    _, vjp = jax.vjp(
        lambda t, c: ref.packed_bag_ref(_flat(t), _flat(c), idx, slot),
        table, cache,
    )
    dt, dc = vjp(ct.astype(table.dtype))
    return dt, dc, *_zero_idx(idx, slot)


_packed_dense_diff.defvjp(_packed_dense_diff_fwd, _packed_dense_diff_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _packed_qr_diff(q, cache, r, q_idx, slot, r_idx, interpret, dim_block=None):
    return packed_qr_pooled(
        q, cache, r, q_idx, slot, r_idx, interpret=interpret, dim_block=dim_block
    )


def _packed_qr_diff_fwd(q, cache, r, q_idx, slot, r_idx, interpret,
                        dim_block=None):
    out = _packed_qr_diff(q, cache, r, q_idx, slot, r_idx, interpret, dim_block)
    return out, (q, cache, r, q_idx, slot, r_idx)


def _packed_qr_diff_bwd(interpret, dim_block, res, ct):
    q, cache, r, q_idx, slot, r_idx = res
    _, vjp = jax.vjp(
        lambda a, c, b: ref.packed_qr_bag_ref(
            _flat(a), _flat(c), b, q_idx, slot, r_idx
        ),
        q, cache, r,
    )
    dq, dc, dr = vjp(ct.astype(q.dtype))
    return dq, dc, dr, *_zero_idx(q_idx, slot, r_idx)


_packed_qr_diff.defvjp(_packed_qr_diff_fwd, _packed_qr_diff_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _packed_tt_diff(g1, g2, g3, cache, i1, i2, i3, slot, dims, interpret):
    return packed_tt_pooled(
        g1, g2, g3, cache, i1, i2, i3, slot, dims=dims, interpret=interpret
    )


def _packed_tt_diff_fwd(g1, g2, g3, cache, i1, i2, i3, slot, dims, interpret):
    out = _packed_tt_diff(g1, g2, g3, cache, i1, i2, i3, slot, dims, interpret)
    return out, (g1, g2, g3, cache, i1, i2, i3, slot)


def _packed_tt_diff_bwd(dims, interpret, res, ct):
    g1, g2, g3, cache, i1, i2, i3, slot = res
    _, vjp = jax.vjp(
        lambda a, b, c, cc: ref.packed_tt_bag_ref(
            a, _flat(b), c, _flat(cc), i1, i2, i3, slot, dims=dims
        ),
        g1, g2, g3, cache,
    )
    dg1, dg2, dg3, dc = vjp(ct.astype(g2.dtype))
    return dg1, dg2, dg3, dc, *_zero_idx(i1, i2, i3, slot)


_packed_tt_diff.defvjp(_packed_tt_diff_fwd, _packed_tt_diff_bwd)


# The index streams of each kind's packed megakernel, in the order it takes
# them: ``packed_multi_pooled`` passes them so, and ``packed_grid`` counts
# the kernel's grid from their number.
PACKED_STREAMS = {
    "dense": ("idx", "slot"),
    "qr": ("q_idx", "slot", "r_idx"),
    "tt": ("i1", "i2", "i3", "slot"),
}


def packed_grid(kind: str, bags: int, k_steps: int, dim: int, *,
                dim_block: int | None = None, dtype=jnp.float32,
                dims: tuple[int, int, int, int] | None = None,
                ) -> tuple[int, int, int] | None:
    """The grid of the packed megakernel of ``kind`` over ``bags`` bags of
    ``k_steps`` entries, rows of ``dtype``: ``(chunk, n_chunks, steps)``, as
    the kernel runs it — for rows of 32 bits ``blocks.block_grid`` (dense
    and QR) or ``blocks.tt_block_grid`` (TT, whose middle-core rows ``dims``
    = (d1, d2, d3, rank) size), ``blocks.bag_grid`` otherwise; None for a
    dim that no lane tile fits (the jnp reference runs instead).  The TT
    kernel takes the whole row as its tile."""
    n_streams = len(PACKED_STREAMS[kind])
    if kind == "tt":
        if dim % 8:
            return None
        if _cg.bag_blocks(dtype):
            _, d2, _, rank = dims
            row_bytes = rank * d2 * rank * jnp.dtype(dtype).itemsize
            return tt_block_grid(bags, k_steps, n_streams, row_bytes)
        return bag_grid(bags, k_steps, n_streams, dim, dim)
    bd = _resolve_dim_block(dim, dim_block, True)
    if bd is None:
        return None
    grid = block_grid if _cg.bag_blocks(dtype) else bag_grid
    return grid(bags, k_steps, n_streams, dim, bd)


def _use_kernel(exec_mode: str, interpret: bool | None) -> bool:
    """Whether a packed dispatch runs its kernel (see ``packed_multi_pooled``)."""
    return {
        "auto": bool(interpret) or jax.default_backend() == "tpu",
        "kernel": True,
        "jnp": False,
    }[exec_mode]


def packed_multi_pooled(
    params: dict,
    streams: dict,
    *,
    kind: str,
    dims: tuple[int, int, int, int] | None = None,
    exec_mode: str = "auto",
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """One megakernel dispatch for every table's pooled bag (differentiable).

    ``params``: packed buffers — dense {"table", "cache"}, qr {"q", "cache",
    "r"}, tt {"g1", "g2", "g3", "cache"}; ``streams``: globally-offset int32
    index streams of shape (..., K) — dense {"idx", "slot"}, qr {"q_idx",
    "slot", "r_idx"}, tt {"i1", "i2", "i3", "slot"}.  Built by
    ``repro.core.packed_tables`` / ``repro.core.sharded_embedding``.

    ``exec_mode="auto"`` runs the Pallas megakernel on TPU (or when
    ``interpret=True`` is forced — tests); elsewhere the pure-jnp packed
    oracle, so the same config trains and serves on every backend.
    ``"kernel"`` always runs the kernel (interpret on CPU — the serving
    driver's validation mode); ``"jnp"`` always the oracle.  The kernel path
    carries a reference-recompute vjp, so all modes are training-safe.
    ``interpret=None`` compiles the kernel on TPU and interprets it
    elsewhere; an explicit ``False`` with ``exec_mode="kernel"`` compiles it
    whatever the backend (the described-chip compile tests).
    """
    use_kernel = _use_kernel(exec_mode, interpret)
    if kind not in PACKED_STREAMS:
        raise ValueError(f"packed_multi_pooled: unsupported kind {kind!r}")
    if not use_kernel:                       # the oracles take flat rows
        params = {k: _flat(v) for k, v in params.items()}
    interp = _interpret_default() if interpret is None else bool(interpret)
    ids = tuple(streams[k] for k in PACKED_STREAMS[kind])
    if kind == "qr":
        args = (params["q"], params["cache"], params["r"], *ids)
        if use_kernel:
            return _packed_qr_diff(*args, interp, dim_block)
        return ref.packed_qr_bag_ref(*args)
    if kind == "tt":
        args = (params["g1"], params["g2"], params["g3"], params["cache"], *ids)
        if use_kernel:
            return _packed_tt_diff(*args, dims, interp)
        return ref.packed_tt_bag_ref(*args, dims=dims)
    args = (params["table"], params["cache"], *ids)
    if use_kernel:
        return _packed_dense_diff(*args, interp, dim_block)
    return ref.packed_bag_ref(*args)


def packed_cache_block(big: jax.Array, rows: jax.Array, *, kind: str,
                       exec_mode: str = "auto",
                       interpret: bool | None = None) -> jax.Array:
    """The packed cache block ``big[rows]``: the prefetch cache's staging
    DMA.  Where the block megakernel serves (dense and QR rows of 32 bits,
    on the kernel path as ``packed_multi_pooled`` decides), a kernel copies
    the rows out of the buffer's row view (``blocks.stage_rows``): XLA's
    gather would first copy the whole buffer into another tiling.  Elsewhere
    XLA's gather."""
    use_kernel = _use_kernel(exec_mode, interpret)
    if use_kernel and kind != "tt" and _cg.bag_blocks(big.dtype):
        interp = _interpret_default() if interpret is None else bool(interpret)
        return blocks.stage_rows(big, rows, interpret=interp)
    return big[rows]


def gnr_pooled_dense(
    table: jax.Array, idx: jax.Array, *, interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """Pooled dense bag for index shape (..., K) -> (..., D)."""
    interpret = _interpret_default() if interpret is None else interpret
    dim = table.shape[-1]
    bd = _resolve_dim_block(dim, dim_block, interpret)
    if bd is None:
        return ref.dense_bag_ref(table, idx)
    *lead, k = idx.shape
    out = _gnr.gnr_bag_dense(table, idx.reshape(-1, k), dim_block=bd, interpret=interpret)
    return out.reshape(*lead, dim)


def flash_attention_fused(q, k, v, *, causal=True, interpret=None):
    """Fused VMEM-resident attention (Pallas) with reference-recompute vjp.

    q: (B, H, Sq, D); k/v: (B, KH, Skv, D); GQA via KH | H.
    """
    from repro.kernels.flash_attention import flash_mha

    interpret = _interpret_default() if interpret is None else interpret
    return flash_mha(q, k, v, causal, interpret)
