"""Plain DLRM forward in float32, the yardstick for ``correct``.

Naumov et al. (2019): dense features through the bottom MLP (ReLU after every
layer); each sparse feature's bag of ``pooling`` ids summed over its
embedding rows; the pairwise dot products of the 27 feature vectors (the
bottom output first, upper triangle, row-major) concatenated after the bottom
output; the top MLP (ReLU between layers, linear last) gives one logit.

Embedding rows are rebuilt from the compressed tables:
- QR trick (Shi et al., 2020), additive: ``row(i) = Q[i // c] + R[i % c]``;
- TT-Rec (Yin et al., 2021): ``i = (i1*v2 + i2)*v3 + i3`` and
  ``row(i)[a,b,c] = sum_{p,q} G1[i1][a,p] G2[i2][p,b,q] G3[i3][q,c]``, with
  the cores stored flat as ``(d1, r)``, ``(r, d2, r)`` and ``(r, d3)``.

Nothing here imports the program.  Tables and chunks of bags are taken one
at a time, so a batch needs the gathered rows of one chunk at once.

Two controls stand in for the program to show that the comparison fails a
path one step lower in precision than the configuration states:
- ``precision="bf16_tables"``: the tables rounded to bfloat16 (the
  configuration keeps them in float32), everything else as in ``"f32"``;
  the pooled embeddings are what it moves;
- ``precision="control"``: tables in bfloat16, pooled in bfloat16, and every
  matmul input rounded to float8 e4m3 (the configuration computes in
  bfloat16); the logits are what it moves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16_tables", "control")


def qr_split(idx, collision: int):
    """Logical id -> (quotient row, remainder row)."""
    return idx // collision, idx % collision


def tt_split(idx, v2: int, v3: int):
    """Logical id -> (i1, i2, i3), mixed radix over (v1, v2, v3)."""
    i3 = idx % v3
    rest = idx // v3
    return rest // v2, rest % v2, i3


def _round(x, precision):
    """Matmul inputs: float32 as they are; the control rounds to float8."""
    if precision == "control":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _tables(stacked, precision):
    """The stacked tables as the precision reads them, and the dtype their
    rows are summed in."""
    if precision == "f32":
        return stacked, jnp.float32
    low = {k: v.astype(jnp.bfloat16) for k, v in stacked.items()}
    if precision == "bf16_tables":
        return {k: v.astype(jnp.float32) for k, v in low.items()}, jnp.float32
    return low, jnp.bfloat16


def _bag_rows(tab, ids, m):
    """Rebuilt embedding rows (..., dim) of the ids of one table."""
    if m.kind == "qr":
        qi, ri = qr_split(ids, m.collision)
        return tab["q"][qi] + tab["r"][ri]
    (_, v2, v3), (d1, d2, d3), r = m.vocab_factors, m.dim_factors, m.rank
    i1, i2, i3 = tt_split(ids, v2, v3)
    a = tab["g1"][i1].reshape(*ids.shape, d1, r)
    b = tab["g2"][i2].reshape(*ids.shape, r, d2, r)
    c = tab["g3"][i3].reshape(*ids.shape, r, d3)
    rows = jnp.einsum("...ap,...pbq,...qc->...abc", a, b, c)
    return rows.reshape(*ids.shape, m.dim)


CHUNK = 256     # bags rebuilt at once: a TT chunk gathers 64 MiB of core rows


def pooled(tables, idx, m, precision="f32"):
    """(B, T, K) ids -> (B, T, dim) summed embedding rows, one table and
    one chunk of bags at a time."""
    stacked, dt = _tables({k: jnp.stack([t[k] for t in tables])
                           for k in tables[0]}, precision)
    b, _, k = idx.shape
    ch = CHUNK if b % CHUNK == 0 else b
    per_table = jnp.moveaxis(idx, 1, 0).reshape(m.num_tables, b // ch, ch, k)

    def one(_, xs):
        tab, ids = xs
        out = jax.lax.map(lambda c: _bag_rows(tab, c, m).sum(axis=1, dtype=dt),
                          ids)
        return None, out.reshape(b, m.dim)

    _, out = jax.lax.scan(one, None, (stacked, per_table))
    return jnp.moveaxis(out, 0, 1).astype(jnp.float32)      # (B, T, dim)


def _mlp(layers, x, precision, *, last_linear):
    for i, p in enumerate(layers):
        x = _round(x, precision) @ _round(p["w"], precision) + p["b"]
        if not (last_linear and i == len(layers) - 1):
            x = jax.nn.relu(x)
    return x


def interaction(bottom, pooled_, precision="f32"):
    """(B, dim), (B, T, dim) -> (B, F*(F-1)/2) pairwise dots, upper triangle."""
    feats = _round(jnp.concatenate([bottom[:, None, :], pooled_], axis=1),
                   precision)
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    iu, ju = jnp.triu_indices(feats.shape[1], k=1)
    return gram[:, iu, ju]


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _forward(params, dense, idx, m, precision):
    bottom = _mlp(params["bottom"], dense, precision, last_linear=False)
    emb = pooled(params["tables"], idx, m, precision)
    z = interaction(bottom, emb, precision)
    top = _mlp(params["top"], jnp.concatenate([bottom, z], axis=-1), precision,
               last_linear=True)
    return top[:, 0], emb


def forward_with_pooled(params, dense, idx, m, precision="f32"):
    """Logits (B,) and pooled embeddings (B, T, dim) of one batch, every
    matmul at float32's full precision."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    with jax.default_matmul_precision("highest"):
        return _forward(params, dense, idx, m, precision)


def forward(params, dense, idx, m, precision="f32"):
    """Logits (B,) of one batch."""
    return forward_with_pooled(params, dense, idx, m, precision)[0]
