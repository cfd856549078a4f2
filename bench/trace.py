"""Reduction from a profiler trace of the window to device numbers.

``extract`` reads a ``jax.profiler`` trace (``*.xplane.pb``) into plain
lists of ``[name, start_ns, duration_ns]``: the device's XLA ops and XLA
modules, and the host's annotations.  Everything after that works on those
lists alone, so ``tests/test_bench_trace.py`` checks it on a small recorded
trace.  All times are on the trace's own clock; the program's ``obs`` spans,
which use ``time.perf_counter``, are moved onto it through a
``TraceAnnotation`` mark whose ``perf_counter`` reading the harness keeps.
"""

from __future__ import annotations

import bisect
import collections
import glob
import re

SYNC = "bench_clock_sync"      # the annotation that ties the two clocks
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def device_plane(i: int) -> str:
    """The trace plane of the host's ``i``-th TPU chip."""
    return f"/device:TPU:{i}"


def extract(log_dir: str) -> dict:
    """The device planes and host annotations of the newest trace under
    ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if e.name == SYNC)
    return out


def op_name(raw: str) -> str:
    """An XLA op event's name without its HLO text:
    ``%fusion.5 = f32[...] fusion(...)`` -> ``fusion.5``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def sync_ns(tr: dict) -> float:
    """Trace-clock start of the first clock mark."""
    marks = sorted(s for name, s, _ in tr["host"] if name == SYNC)
    if not marks:
        raise ValueError(f"trace holds no {SYNC} annotation")
    return marks[0]


def spans_on_trace_clock(events: list[dict], origin_pc: float, sync_pc: float,
                         sync_trace_ns: float) -> list[list]:
    """``obs`` tracer events (``ts``/``dur`` in us from ``origin_pc``) as
    ``[name, start_ns, duration_ns, args]`` on the trace clock."""
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        pc = origin_pc + e["ts"] * 1e-6
        out.append([e["name"], sync_trace_ns + (pc - sync_pc) * 1e9,
                    e["dur"] * 1e3, e.get("args") or {}])
    return out


def window_of(spans: list[list]) -> tuple[float, float]:
    """[start, end) of the steady window: from the first span of batch 1 to
    the end of the last span that belongs to a window batch or the drain."""
    inside = [s for s in spans
              if s[3].get("batch", -1) >= 1 or s[0] == "tail_sync"]
    if not inside:
        raise ValueError("no span of the window")
    return min(s[1] for s in inside), max(s[1] + s[2] for s in inside)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """[name, start, dur] intervals cut to [lo, hi), as (start, end)."""
    out = []
    for _name, s, d in intervals:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some device op ran."""
    return sum(b - a for a, b in union(clip(ops, lo, hi)))


def module_name(raw: str) -> str:
    """``jit_foo(12)`` -> ``jit_foo``: the program id is not stable."""
    return re.sub(r"\(\d+\)$", "", raw)


def module_ns(modules, pattern: str, lo: float, hi: float) -> tuple[float, int]:
    """Device time and count of the modules whose name holds ``pattern`` and
    that start in [lo, hi)."""
    hits = [(s, d) for name, s, d in modules
            if pattern in name and lo <= s < hi]
    return sum(d for _, d in hits), len(hits)


def top_ops(dev: dict, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` device ops (``module/op``) that took most time in [lo, hi)."""
    mods = sorted((s, s + d, module_name(name)) for name, s, d in dev["modules"])
    starts = [m[0] for m in mods]
    total: dict[str, float] = collections.defaultdict(float)
    for name, s, d in dev["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
        total[f"{mod}/{name}"] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


LEAF_SKIP = {"batch"}          # parents of the per-stage spans


def idle_gaps(dev: dict, spans: list[list], lo: float, hi: float,
              n: int = 10) -> list[list]:
    """Device idle time in [lo, hi), summed by what the host was doing: the
    stage span that covers most of each gap, or ``unattributed``."""
    busy = union(clip(dev["ops"], lo, hi))
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    leaves = sorted((s, s + d, name) for name, s, d, _ in spans
                    if name not in LEAF_SKIP)
    starts = [s for s, _, _ in leaves]
    reach, far = [], float("-inf")      # running max of the ends, for bisect
    for _, e, _ in leaves:
        far = max(far, e)
        reach.append(far)
    total: dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        best, best_cover = "unattributed", 0.0
        for s, e, name in leaves[bisect.bisect_right(reach, a):
                                 bisect.bisect_left(starts, b)]:
            cover = min(b, e) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
        total[best] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def summarize(tr: dict, spans: list[list], chips: int = 1) -> dict | None:
    """Window, busy time, module times and breakdown of a reduced trace, from
    the planes of the cell's ``chips`` chips alone: busy time is their mean,
    module times and the breakdown are chip 0's.  None when the trace holds
    no device plane."""
    if not tr["devices"]:
        return None
    names = [device_plane(i) for i in range(chips)]
    missing = [n for n in names if n not in tr["devices"]]
    if missing:
        raise ValueError(f"trace holds no plane {missing}; it has "
                         f"{sorted(tr['devices'])}")
    lo, hi = window_of(spans)
    devs = [tr["devices"][n] for n in names]
    busy = sum(busy_ns(d["ops"], lo, hi) for d in devs) / len(devs)
    first = devs[0]
    return {
        "lo": lo, "hi": hi,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "modules": first["modules"],
        "breakdown": {"device_ops": top_ops(first, lo, hi),
                      "idle_gaps": idle_gaps(first, spans, lo, hi)},
    }
