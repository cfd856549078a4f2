"""Distribution substrate: spec resolution, two-level GnR on a real (host)
mesh, compressed collectives, elastic resharding.  Mesh tests run in a child
process so this test session keeps its single CPU device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as SH


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_resolve_spec_divisibility():
    mesh = _FakeMesh({"data": 4, "model": 8})
    rules = {"rows": ("model",), "cols": ("data",)}
    assert SH.resolve_spec(mesh, (64, 16), ("rows", "cols"), rules) == P("model", "data")
    # 63 rows not divisible by 8 -> replicated
    assert SH.resolve_spec(mesh, (63, 16), ("rows", "cols"), rules) == P(None, "data")


def test_resolve_spec_duplicate_axis_dropped():
    mesh = _FakeMesh({"data": 4, "model": 16})
    rules = {"experts": ("model",), "ffn": ("model",), "embed": ("data",)}
    # experts takes `model`; ffn wants it too -> dropped (replicated dim)
    spec = SH.resolve_spec(mesh, (64, 32, 32), ("experts", "embed", "ffn"), rules)
    assert spec == P("model", "data", None)
    # when experts doesn't divide (40 % 16 != 0), ffn picks `model` up instead
    spec = SH.resolve_spec(mesh, (40, 32, 32), ("experts", "embed", "ffn"), rules)
    assert spec == P(None, "data", "model")


def test_resolve_spec_multi_axis_fsdp():
    mesh = _FakeMesh({"pod": 2, "data": 4, "model": 8})
    rules = {"embed": ("pod", "data")}
    assert SH.resolve_spec(mesh, (64,), ("embed",), rules) == P(("pod", "data"))
    # 6 divides by pod=2 but not by pod*data=8 -> partial acceptance
    assert SH.resolve_spec(mesh, (6,), ("embed",), rules) == P("pod")


def test_multi_pod_rules():
    r = SH.multi_pod_rules()
    assert r["batch"] == ("pod", "data")
    pr = SH.multi_pod_param_rules()
    assert pr["embed"] == ("pod", "data")


def test_two_level_gnr_matches_oracle(mesh_runner):
    mesh_runner(
        """
import jax, jax.numpy as jnp, numpy as np
from repro import engine as E
from repro.core import sharded_embedding as SE, embedding_bag as EB, qr_embedding as QE
from repro.core.qr_embedding import EmbeddingConfig
from repro.core.embedding_bag import BagConfig
from repro.engine import EngineSpec
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = EmbeddingConfig(vocab=1024, dim=64, kind="qr", collision=8, compute_dtype=jnp.float32)
bag = BagConfig(emb=cfg, pooling=4)
params = QE.init(jax.random.PRNGKey(0), cfg)
idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 1024)
oracle = EB.multi_bag_lookup([params, params], idx, [bag, bag])
sp = SE.shard_qr_params(params, cfg, mesh)
spec = EngineSpec.from_bags((bag, bag))
fn = E.compile(E.plan(spec, mesh=mesh)).gnr(mesh)
out = fn([sp, sp], idx)
np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=1e-5, atol=1e-6)

# token path
fn2 = SE.build_token_embed(mesh, cfg)
tok = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 1024)
np.testing.assert_allclose(np.asarray(fn2(sp, tok)),
                           np.asarray(QE.lookup(params, tok, cfg)), rtol=1e-5)
print("OK")
""",
        n_devices=8,
    )


def test_hot_tier_gnr_matches_oracle(mesh_runner):
    mesh_runner(
        """
import jax, jax.numpy as jnp, numpy as np
from repro import engine as E
from repro.core import sharded_embedding as SE, embedding_bag as EB, qr_embedding as QE
from repro.core import placement
from repro.core.qr_embedding import EmbeddingConfig
from repro.core.embedding_bag import BagConfig
from repro.data.synthetic import zipf_trace
from repro.core import hashing
from repro.engine import EngineSpec
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = EmbeddingConfig(vocab=4096, dim=32, kind="qr", collision=8, compute_dtype=jnp.float32)
bag = BagConfig(emb=cfg, pooling=4)
params = QE.init(jax.random.PRNGKey(0), cfg)

trace = zipf_trace(4096, 20000, seed=3)
q_idx, _ = hashing.qr_decompose(jnp.asarray(trace), 8)
counts = placement.profile_counts(np.asarray(q_idx), cfg.qr_spec.q_rows)
plan = placement.plan_tiers(counts, request_share=0.8)
padded = SE.pad_q_table(params["q"], cfg)
hot, cold = placement.split_table(padded, placement.TierPlan(
    hot_rows=plan.hot_rows, hot_slot=np.pad(plan.hot_slot, (0, padded.shape[0]-plan.hot_slot.size), constant_values=-1),
    hot_fraction=plan.hot_fraction, expected_hot_hit=plan.expected_hot_hit))
tier = {"hot_table": hot, "hot_slot": jnp.asarray(
    np.pad(plan.hot_slot, (0, padded.shape[0]-plan.hot_slot.size), constant_values=-1))}
sp = SE.shard_qr_params({"q": cold, "r": params["r"]}, cfg, mesh)

idx = jax.random.randint(jax.random.PRNGKey(1), (8, 1, 4), 0, 4096)
oracle = EB.multi_bag_lookup([params], idx, [bag])
spec = EngineSpec.from_bags((bag,))
fn = E.compile(E.plan(spec, mesh=mesh)).gnr(mesh, hot=True)
out = fn([sp], idx, [tier])
np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=1e-4, atol=1e-5)
print("OK")
""",
        n_devices=8,
    )


def test_compressed_psum_close_to_exact(mesh_runner):
    mesh_runner(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum, ef_step
from jax import shard_map
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("d",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))

exact = shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
    in_specs=P("d"), out_specs=P("d"), check_vma=False)(x)
approx = shard_map(lambda v: compressed_psum(v, "d"), mesh=mesh,
    in_specs=P("d"), out_specs=P("d"), check_vma=False)(x)
err = float(jnp.abs(exact - approx).max() / (jnp.abs(exact).max() + 1e-9))
assert err < 0.05, err

# error feedback: residual carried across steps shrinks accumulated bias
def two_steps(v):
    r = jnp.zeros_like(v)
    g1, r = ef_step(v, r, "d")
    g2, r = ef_step(v, r, "d")
    return g1 + g2
efsum = shard_map(two_steps, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
    check_vma=False)(x)
err_ef = float(jnp.abs(2*exact - efsum).max() / (jnp.abs(exact).max() + 1e-9))
assert err_ef < 0.08, err_ef
print("OK")
""",
        n_devices=4,
    )


def test_elastic_reshard_roundtrip(mesh_runner):
    mesh_runner(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import elastic, sharding as SH
from repro.launch.mesh import make_mesh

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
axes = {"w": ("ffn", "embed"), "b": ("ffn",)}
m1 = make_mesh((2, 4), ("data", "model"))
placed = elastic.reshard_tree(tree, axes, m1, SH.PARAM_RULES)
m2 = make_mesh((4, 2), ("data", "model"))
moved = elastic.reshard_tree(placed, axes, m2, SH.PARAM_RULES)
np.testing.assert_array_equal(np.asarray(moved["w"]), np.asarray(tree["w"]))
np.testing.assert_array_equal(np.asarray(moved["b"]), np.asarray(tree["b"]))
print("OK")
""",
        n_devices=8,
    )


def test_heartbeat_and_async_policy():
    from repro.distributed.elastic import Heartbeat, PodAsyncState, degraded_mesh_shapes

    hb = Heartbeat(deadline_s=10.0)
    hb.beat(0, 5, now=100.0)
    hb.beat(1, 5, now=100.0)
    assert hb.failed_hosts(now=105.0) == []
    assert hb.failed_hosts(now=111.0) == [0, 1]
    hb.beat(0, 6, now=112.0)
    assert hb.failed_hosts(now=115.0) == [1]
    assert hb.min_step() == 5

    st = PodAsyncState(stale_limit=2, last_sync=0)
    assert st.should_sync(0, pod_slow=True) is False
    assert st.should_sync(2, pod_slow=True) is True   # staleness bound hit
    assert st.should_sync(1, pod_slow=False) is True  # fast path: always sync

    shapes = degraded_mesh_shapes(256, 16)
    assert (16, 16) in shapes and shapes[-1][0] >= 1


def test_quantize_roundtrip():
    from repro.distributed.collectives import dequantize_int8, quantize_int8

    x = jax.random.normal(jax.random.PRNGKey(0), (512,)) * 3.0
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x)).max()
    assert err <= float(scale) * 0.5 + 1e-6
