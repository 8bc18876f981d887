"""Reduce a profiler trace to device busy time, idle gaps and op totals.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's ``ProfileData``. Device planes are ``/device:TPU:<n>``; their "XLA Ops"
line holds one event per operation that ran, and an operation that holds
others (a ``while`` loop and its body) encloses their events. The host plane
has one line per thread, with JAX's own host events and the bench's
``TraceAnnotation`` spans. All planes share one time base, in nanoseconds.

* window: the span the bench marks on the host (``WINDOW_SPAN``) around
  the stretch it means to trace, so that a device idle at either end of it
  counts as idle; in a trace without that span, from the first to the last
  operation on any device;
* busy: the union of a device's op intervals, cut to the window;
* op totals: each op's self time, the time in which it was the innermost
  op running, so that the totals add up to the busy time;
* idle gaps: the stretches of device 0's window outside its busy union,
  each named by what the dispatching thread (the one that carries the
  bench's ``bench.`` spans) was doing: its innermost event that covers at
  least half of the gap, else the one that overlaps it most.
"""
from __future__ import annotations

import heapq
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
BENCH_SPAN = "bench."
# not under BENCH_SPAN: the thread that marks the window does not dispatch
WINDOW_SPAN = "bench-window"
TOP = 10


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _events(line) -> list[tuple[str, int, int]]:
    """(name, start, end); an op's name is its HLO instruction's name, the
    text before " = " in the event's name."""
    return [(ev.name.split(" = ", 1)[0], int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def load(path: Path) -> dict:
    """Planes as plain data: {"devices": {n: [(name, start, end)]},
    "host": [(name, start, end)], "window": (start, end) or None}, the
    host's events being those of the thread that dispatches (all threads'
    where none carries a bench span)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[int, list] = {}
    threads: list[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            ops = [e for line in plane.lines if line.name == OPS_LINE
                   for e in _events(line)]
            devices[int(plane.name[len(DEVICE_PREFIX):])] = ops
        elif plane.name == HOST_PLANE:
            threads += [_events(line) for line in plane.lines]
    dispatching = [t for t in threads
                   if any(n.startswith(BENCH_SPAN) for n, _, _ in t)]
    host = [e for t in (dispatching or threads) for e in t]
    marks = [(s, e) for t in threads for n, s, e in t if n == WINDOW_SPAN]
    return {"devices": devices, "host": host,
            "window": marks[0] if marks else None}


def self_times(ops: list[tuple[str, int, int]]) -> dict[str, int]:
    """Per op name, the time in which it was the innermost op running: each
    stretch of the busy union goes to the running op that started last. The
    totals add up to the busy time."""
    bounds = sorted([(s, 1, i) for i, (_, s, _) in enumerate(ops)]
                    + [(e, 0, i) for i, (_, _, e) in enumerate(ops)])
    totals: dict[str, int] = {}
    running: list[tuple[int, int]] = []  # heap of (-start, op index)
    ended: set[int] = set()
    last = None
    for t, starts, i in bounds:
        while running and running[0][1] in ended:
            heapq.heappop(running)
        if running and t > last:
            name = ops[running[0][1]][0]
            totals[name] = totals.get(name, 0) + (t - last)
        last = t
        if starts:
            heapq.heappush(running, (-ops[i][1], i))
        else:
            ended.add(i)
    return totals


def reduce(planes: dict) -> dict:
    devices, host = planes["devices"], planes["host"]
    if planes.get("window"):
        t0, t1 = planes["window"]
        devices = {n: [(name, max(s, t0), min(e, t1)) for name, s, e in d
                       if s < t1 and e > t0] for n, d in devices.items()}
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    if not planes.get("window"):
        ops = [e for d in devices.values() for e in d]
        t0 = min(s for _, s, _ in ops)
        t1 = max(e for _, _, e in ops)
    window_s = (t1 - t0) / 1e9
    per_device = []
    for n in sorted(devices):
        busy = _union([(s, e) for _, s, e in devices[n]])
        per_device.append({"device": n, "window_s": window_s,
                           "busy_s": sum(e - s for s, e in busy) / 1e9})
    d0 = devices[min(devices)]
    totals = self_times(d0)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    busy0 = _union([(s, e) for _, s, e in d0])
    edges = [t0] + [x for iv in busy0 for x in iv] + [t1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_activity(host, s, e), (e - s) / 1e9]
             for s, e in gaps[:TOP]]
    return {"window_s": window_s,
            "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
            "devices": per_device,
            "ops_s": {k: v / 1e9 for k, v in totals.items()},
            "breakdown": {"device_ops": [[k, v / 1e9] for k, v in top_ops],
                          "idle_gaps": named}}


def _host_activity(host, s: int, e: int) -> str:
    covering, best, most = None, "host idle", 0
    for name, hs, he in host:
        overlap = min(e, he) - max(s, hs)
        if overlap <= 0:
            continue
        if 2 * overlap >= e - s and (covering is None
                                     or he - hs < covering[1]):
            covering = (name, he - hs)
        if overlap > most:
            best, most = name, overlap
    return covering[0] if covering else best


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory: Path) -> dict:
    return reduce(load(find_xplane(directory)))
