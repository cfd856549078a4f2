"""What the serving loop tells a trace: the gather megakernel's grid steps
(``blocks.block_grid`` for dense and QR, ``blocks.tt_block_grid`` for TT,
both on 32-bit rows, ``blocks.bag_grid`` for 16-bit rows, pinned by hand at
the benchmark cells' shapes) and the spans ``run_pipeline`` emits
around the prefetch schedulers' two phases."""

import collections
import dataclasses

import pytest

from repro import engine as engine_mod
from repro import obs
from repro.configs import registry
from repro.kernels import ops
from repro.kernels.blocks import bag_grid, block_grid, tt_block_grid


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.tracer().reset()
    yield
    obs.disable()
    obs.registry().reset()
    obs.tracer().reset()


# 26 tables, pooling 32, dim 128.  QR: 3 streams of 4 B a bag entry -> 1,360
# bags a chunk of 512 KiB; TT: 4 streams -> 1,024.
@pytest.mark.parametrize("streams,batch,chunk,n_chunks,steps", [
    (3, 2048, 1360, 40, 1_740_800),
    (3, 256, 1360, 5, 217_600),
    (4, 2048, 1024, 52, 1_703_936),
])
def test_bag_grid_by_hand(streams, batch, chunk, n_chunks, steps):
    assert bag_grid(batch * 26, 32, streams, 128, 128) == (chunk, n_chunks, steps)


def test_bag_grid_small_batches_and_lane_tiles():
    assert bag_grid(5, 4, 2, 64, 64) == (5, 1, 20)       # one chunk, no pad
    assert bag_grid(5, 4, 2, 64, 16) == (5, 1, 80)       # 4 lane tiles a row


# Bag blocks of f32 rows at K 32, dim 128: G = 2 MiB / (2 x 32 x 128 x 4 B)
# = 64 bags a step.  QR: 3 streams fit 1,365 bags a chunk, 21 whole blocks =
# 1,344; 53,248 bags -> 40 chunks x 21 = 840 steps, 6,656 -> 5 x 21 = 105.
# Dense: 2 streams fit 2,048 bags = 32 blocks; 26 chunks exactly -> 832.
@pytest.mark.parametrize("streams,batch,chunk,n_chunks,steps", [
    (3, 2048, 1344, 40, 840),
    (3, 256, 1344, 5, 105),
    (2, 2048, 2048, 26, 832),
])
def test_block_grid_by_hand(streams, batch, chunk, n_chunks, steps):
    assert block_grid(batch * 26, 32, streams, 128, 128) == (chunk, n_chunks, steps)


def test_block_grid_small_batches_and_lane_tiles():
    assert block_grid(5, 4, 2, 64, 64) == (8, 1, 1)      # one block, 3 pads
    assert block_grid(5, 4, 2, 64, 16) == (8, 1, 4)      # 4 lane tiles a row
    # K 33, dim 256: G = 2 MiB / (2 x 33 x 256 x 4 B) = 31 -> 24 bags
    assert block_grid(50, 33, 3, 256, 256) == (72, 1, 3)


# TT bag blocks of f32 rows at K 32, rank 16, d2 8: a middle-core row is
# 16 x 128 x 4 B = 8 KiB, so G = 4 MiB / (2 x 32 x 8 KiB) = 8 bags a step.
# 4 streams fit 1,024 bags a chunk = 128 blocks; 53,248 bags -> 52 chunks x
# 128 = 6,656 steps; 6,656 bags -> 7 chunks (512 pad bags) x 128 = 896.
@pytest.mark.parametrize("batch,chunk,n_chunks,steps", [
    (2048, 1024, 52, 6_656),
    (256, 1024, 7, 896),
])
def test_tt_block_grid_by_hand(batch, chunk, n_chunks, steps):
    assert tt_block_grid(batch * 26, 32, 4, 8192) == (chunk, n_chunks, steps)


def test_tt_block_grid_small_batches_and_rows():
    assert tt_block_grid(5, 4, 4, 8192) == (8, 1, 1)     # one block, 3 pads
    # K 33: 4 MiB / (2 x 33 x 8 KiB) < 8, so the floor of 8 bags -> 7 blocks
    assert tt_block_grid(50, 33, 4, 8192) == (56, 1, 7)
    # 256 B rows (rank 4, d2 4): G would be 2,048, cut to the 56 bags there are
    assert tt_block_grid(50, 4, 4, 256) == (56, 1, 1)


@pytest.mark.parametrize("arch,batch,steps", [
    ("dlrm-qr", 2048, 840),
    ("dlrm-qr", 256, 105),
    ("dlrm-tt", 2048, 6_656),
    ("dlrm-tt", 256, 896),
])
def test_engine_grid_steps_at_published_widths(arch, batch, steps):
    cfg = registry.get_dlrm(arch)
    spec = dataclasses.replace(
        engine_mod.EngineSpec.from_dlrm(cfg, serving=True), duplication=False)
    eng = engine_mod.compile(engine_mod.plan(spec, num_shards=1))
    assert eng.grid_steps(batch) == steps
    layout = eng.plan.layout
    assert ops.packed_grid(layout.kind, batch * 26, 32, 128,
                           dims=layout.tt_dims)[2] == steps


@pytest.fixture(scope="module")
def served():
    """Spans of a smoke ``run_pipeline`` in each mode, with its engine."""
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    state = serve_rec.build_serve_state(cfg, shards=1, alpha=1.05, seed=0)
    out = {}
    for mode in ("overlap", "sequential"):
        obs.enable()
        serve_rec.run_pipeline(cfg, batch=8, batches=4, mode=mode,
                               state=state, seed=0)
        out[mode] = [e for e in obs.tracer().events if e["ph"] == "X"]
        obs.disable()
    return out, state.engine


@pytest.mark.parametrize("mode", ["overlap", "sequential"])
def test_pipeline_spans_the_two_scheduler_phases(served, mode):
    events, _eng = served
    spans = events[mode]
    for name in ("cache_rank", "cache_update"):
        got = [e["args"]["batch"] for e in spans if e["name"] == name]
        assert sorted(got) == [0, 1, 2, 3], name
    by = collections.defaultdict(dict)
    for e in spans:
        if "batch" in e["args"]:
            by[e["name"]][e["args"]["batch"]] = e
    for t in range(4):
        pre, rank, upd = (by[n][t] for n in ("prefetch", "cache_rank",
                                             "cache_update"))
        assert pre["ts"] <= rank["ts"] <= upd["ts"]
        assert upd["ts"] + upd["dur"] <= pre["ts"] + pre["dur"] + 1e-3
        assert rank["ts"] + rank["dur"] <= upd["ts"] + 1e-3
        args = upd["args"]
        assert min(args["staged"], args["kept"], args["evicted"]) >= 0
    first = by["cache_update"][0]["args"]
    assert first["kept"] == first["evicted"] == 0 < first["staged"]


@pytest.mark.parametrize("mode", ["overlap", "sequential"])
def test_dispatch_spans_carry_the_grid_and_the_window_start_is_kept(
        served, mode):
    events, eng = served
    spans = events[mode]
    steps = [e["args"]["grid_steps"] for e in spans if e["name"] == "dispatch"]
    assert steps == [eng.grid_steps(8)] * 4 and steps[0] > 0
    # the benchmark's window opens at the earliest span of batch >= 1; the
    # scheduler phases nest inside prefetch and never open it
    first = min((e for e in spans if e["args"].get("batch", -1) >= 1),
                key=lambda e: e["ts"])
    want = {"overlap": "prefetch", "sequential": "batch"}[mode]
    assert (first["name"], first["args"]["batch"]) == (want, 1)


def test_serve_rec_profile_dir_holds_the_steady_state_spans(tmp_path, capsys):
    import glob

    from jax.profiler import ProfileData

    from repro.launch import serve_rec

    rc = serve_rec.main(["--arch", "dlrm-qr", "--tiny", "--batches", "3",
                         "--profile-dir", str(tmp_path)])
    assert rc == 0 and "wrote the profile" in capsys.readouterr().out
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    host = collections.Counter(
        e.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events)
    for name in ("prefetch", "cache_rank", "cache_update", "pack", "h2d",
                 "dispatch", "interact"):
        assert host[name] == 2, name          # batches 1 and 2, not batch 0
    assert host["batch"] == 2 and host["compile_warmup"] == 0


def test_serve_rec_profile_dir_refuses_a_fenced_run(tmp_path):
    from repro.launch import serve_rec

    with pytest.raises(SystemExit):
        serve_rec.main(["--arch", "dlrm-qr", "--tiny", "--profile-dir",
                        str(tmp_path), "--trace-out", str(tmp_path / "t.json")])
