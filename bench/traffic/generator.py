"""The benchmark's one batch generator: multi-hot DLRM batches with Zipf keys.

A traffic mix is a JSON file beside this module (``<name>.json``) holding the
parameters read here.  The generator is a copy of the program's
``repro.data.synthetic.dlrm_batch`` / ``zipf_batch_jax`` with one change:
the key is made from every bit of the seed (``PRNGKey`` alone keeps the low
32 bits, so seeds 2**32 apart would draw the same batches).
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DIR = Path(__file__).resolve().parent
MODES = ("overlap", "sequential")
KEYS = {"mode", "batch", "alpha", "warmup_batches"}


def load(name: str, root: Path | None = None) -> dict:
    """The traffic mix ``name``: the parameters of ``<root>/<name>.json``."""
    path = (root or DIR) / f"{name}.json"
    mix = json.loads(path.read_text())
    missing = KEYS - set(mix)
    if missing:
        raise ValueError(f"traffic {name}: missing keys {sorted(missing)}")
    if mix["mode"] not in MODES:
        raise ValueError(f"traffic {name}: mode must be one of {MODES}")
    if mix["alpha"] == 1.0 or mix["alpha"] < 0:
        # the inverse CDF below divides by 1 - alpha
        raise ValueError(f"traffic {name}: alpha must be >= 0 and != 1")
    if mix["warmup_batches"] < 2:
        raise ValueError(f"traffic {name}: warmup_batches must be >= 2 "
                         "(batch 0 compiles, the rest time a batch)")
    return {"name": name, **mix}


def base_key(seed: int) -> jax.Array:
    """A key from all bits of a non-negative seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _key(base: jax.Array, step, tag: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(base, step), tag)


def zipf_keys(key: jax.Array, vocab: int, shape: tuple, alpha: float) -> jax.Array:
    """Inverse-CDF sample of the density x**-alpha on [1, vocab], scattered
    by a fixed multiplicative shuffle; ``alpha=0`` gives uniform keys."""
    u = jax.random.uniform(key, shape, jnp.float32, 1e-6, 1.0)
    a = 1.0 - alpha
    x = ((vocab ** a - 1.0) * u + 1.0) ** (1.0 / a)
    idx = jnp.clip(x.astype(jnp.int32) - 1, 0, vocab - 1)
    return ((idx.astype(jnp.uint32) * np.uint32(2654435761))
            % np.uint32(vocab)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "batch", "tables", "pooling", "vocab", "num_dense", "alpha"))
def _batch(base, step, *, batch, tables, pooling, vocab, num_dense, alpha):
    dense = jax.random.normal(_key(base, step, 3), (batch, num_dense), jnp.float32)
    idx = zipf_keys(_key(base, step, 2), vocab, (batch, tables, pooling), alpha)
    labels = jax.random.bernoulli(_key(base, step, 4), 0.25, (batch,))
    return {"dense": dense, "idx": idx, "labels": labels.astype(jnp.float32)}


def dlrm_batch(cfg, batch: int, *, seed: int, step: int, alpha: float) -> dict:
    """One batch: dense features, (batch, tables, pooling) keys, labels —
    the signature of ``repro.data.synthetic.dlrm_batch``."""
    return _batch(base_key(seed), step, batch=batch, tables=cfg.num_tables,
                  pooling=cfg.pooling, vocab=cfg.vocab_per_table,
                  num_dense=cfg.num_dense, alpha=float(alpha))


class Seam:
    """Stands in for ``repro.data.synthetic.dlrm_batch`` while a cell runs.

    Every call is checked against the cell's mix and recorded, so the harness
    can compare against the very batches the program served and can tell
    when the program drew a different number of batches than it was asked
    for (a refactor that bypasses this seam would measure other traffic).
    """

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = seed
        self.batches: list[dict] = []

    def __call__(self, cfg, batch, *, seed=0, step=0, alpha=1.05):
        if batch != self.mix["batch"] or alpha != self.mix["alpha"] or seed != self.seed:
            raise RuntimeError(
                f"program asked for batch={batch} alpha={alpha} seed={seed}; "
                f"the cell serves batch={self.mix['batch']} "
                f"alpha={self.mix['alpha']} seed={self.seed}")
        out = dlrm_batch(cfg, batch, seed=seed, step=step, alpha=alpha)
        self.batches.append(out)
        return out

    def take(self) -> list[dict]:
        """The batches drawn since the last ``take``."""
        out, self.batches = self.batches, []
        return out
