"""A configuration file's sizes, and seeded weights in the layout the program
serves them in.

The weights are the benchmark's own, made from the seed: the program and the
plain reference are both handed the same arrays, and the reference reads
nothing the program made.  The tree is the one ``repro.models.dlrm.init_dlrm``
returns (``tests/test_bench_counts.py`` pins it); its scales follow the usual
fan-in rule so that activations stay O(1).
"""

from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp

DIR = Path(__file__).resolve().parent / "configs"

# The program stores the big subtable (QR quotient table, TT middle core) with
# its rows padded to a multiple of this, so a mesh axis divides them.
ROW_PAD = 128


def _pad_rows(rows: int) -> int:
    return -(-rows // ROW_PAD) * ROW_PAD


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of one configuration file (hashable: a static jit argument)."""

    name: str
    registry_id: str
    num_tables: int
    vocab_per_table: int
    dim: int
    pooling: int
    num_dense: int
    bottom_mlp: tuple
    top_mlp: tuple
    kind: str                    # "qr" | "tt"
    collision: int = 0           # QR
    rank: int = 0                # TT
    vocab_factors: tuple = ()    # TT (v1, v2, v3)
    dim_factors: tuple = ()      # TT (d1, d2, d3)
    plan_shards: int = 4

    @property
    def q_rows(self) -> int:
        return -(-self.vocab_per_table // self.collision)

    @property
    def num_features(self) -> int:
        return self.num_tables + 1

    @property
    def top_in(self) -> int:
        f = self.num_features
        return self.bottom_mlp[-1] + f * (f - 1) // 2

    def table_shapes(self) -> dict:
        """Leaf name -> (rows, width) of one table, as the program stores it."""
        if self.kind == "qr":
            return {"q": (_pad_rows(self.q_rows), self.dim),
                    "r": (self.collision, self.dim)}
        (v1, v2, v3), (d1, d2, d3), r = (self.vocab_factors, self.dim_factors,
                                         self.rank)
        return {"g1": (v1, d1 * r), "g2": (_pad_rows(v2), r * d2 * r),
                "g3": (v3, r * d3)}

    def mlp_dims(self) -> dict:
        """'bottom' / 'top' -> [(fan_in, fan_out), ...]."""
        out = {}
        for part, dims, d in (("bottom", self.bottom_mlp, self.num_dense),
                              ("top", self.top_mlp, self.top_in)):
            out[part] = []
            for o in dims:
                out[part].append((d, o))
                d = o
        return out


def load(name: str, root: Path | None = None) -> Model:
    """The sizes of configuration ``name`` (``<root>/<name>.json``)."""
    raw = json.loads(((root or DIR) / f"{name}.json").read_text())
    for key, want in (("combiner", "sum"), ("param_dtype", "float32"),
                      ("compute_dtype", "bfloat16")):
        if raw[key] != want:
            raise ValueError(f"config {name}: {key}={raw[key]!r}; the "
                             f"benchmark's reference knows only {want!r}")
    emb = raw["embedding"]
    if emb["kind"] == "qr":
        if emb.get("reconstruction", "add") != "add":
            raise ValueError(f"config {name}: only additive QR is supported")
        extra = {"collision": emb["collision"]}
    elif emb["kind"] == "tt":
        extra = {"rank": emb["rank"],
                 "vocab_factors": tuple(emb["vocab_factors"]),
                 "dim_factors": tuple(emb["dim_factors"])}
    else:
        raise ValueError(f"config {name}: unknown embedding kind {emb['kind']!r}")
    m = Model(name=name, registry_id=raw["registry_id"],
              num_tables=raw["num_tables"],
              vocab_per_table=raw["vocab_per_table"], dim=raw["dim"],
              pooling=raw["pooling"], num_dense=raw["num_dense"],
              bottom_mlp=tuple(raw["bottom_mlp"]), top_mlp=tuple(raw["top_mlp"]),
              kind=emb["kind"], plan_shards=raw["plan_shards"], **extra)
    return m


def check_program_config(m: Model, cfg) -> None:
    """Raise unless the program's registry config runs the sizes in the file."""
    want = {
        "num_tables": m.num_tables, "vocab_per_table": m.vocab_per_table,
        "dim": m.dim, "pooling": m.pooling, "num_dense": m.num_dense,
        "bottom_mlp": m.bottom_mlp, "top_mlp": m.top_mlp,
        "embedding_kind": m.kind, "param_dtype": "float32",
        "compute_dtype": "bfloat16",
    }
    if m.kind == "qr":
        want["qr_collision"] = m.collision
    else:
        want["tt_rank"] = m.rank
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"registry config {cfg.name} differs from the "
                         f"benchmark's {m.name}: {got} != {want}")
    if m.kind == "tt":
        emb_spec = _program_tt_spec(cfg)
        if (tuple(emb_spec.vocab_factors), tuple(emb_spec.dim_factors)) != (
                m.vocab_factors, m.dim_factors):
            raise ValueError(f"{cfg.name} factors {emb_spec.vocab_factors} x "
                             f"{emb_spec.dim_factors} differ from the file's")


def _program_tt_spec(cfg):
    from repro.models import dlrm

    return dlrm.make_bags(cfg)[0].emb.tt_spec


@functools.partial(jax.jit, static_argnames=("m",))
def _make(key, m: Model):
    kb, kt, ke = jax.random.split(key, 3)
    params = {}
    for part, k in (("bottom", kb), ("top", kt)):
        dims = m.mlp_dims()[part]
        keys = jax.random.split(k, len(dims))
        params[part] = [
            {"w": jax.random.normal(kk, (i, o), jnp.float32) * (i ** -0.5),
             "b": jnp.zeros((o,), jnp.float32)}
            for kk, (i, o) in zip(keys, dims)
        ]
    shapes = m.table_shapes()
    if m.kind == "qr":
        scales = {"q": m.dim ** -0.5, "r": m.dim ** -0.5}
    else:
        # a reconstructed entry sums rank**2 products of three core entries
        s = (m.dim * m.rank ** 2) ** (-1.0 / 6.0)
        scales = {"g1": s, "g2": s, "g3": s}
    tables = []
    for kt_ in jax.random.split(ke, m.num_tables):
        leaf_keys = jax.random.split(kt_, len(shapes))
        tables.append({
            leaf: jax.random.normal(lk, shape, jnp.float32) * scales[leaf]
            for lk, (leaf, shape) in zip(leaf_keys, shapes.items())
        })
    params["tables"] = tables
    return params


def make_params(m: Model, seed: int):
    """Every weight of ``m`` from ``seed``, on the device, in one jitted call."""
    from bench.traffic.generator import base_key

    return _make(jax.random.fold_in(base_key(seed), 0x5EED), m)
