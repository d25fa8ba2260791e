"""Reduction of a profiler trace (``.xplane.pb``) to the device metrics:
busy and idle time, time by operation and by kernel, and the host
activity during the device's idle gaps. Reads the trace with
``jax.profiler.ProfileData`` alone.

Device planes are those named ``/device:TPU:<n>``; on each, the ``XLA
Ops`` line holds one event per operation run, named by its HLO text
(fusions, ``while`` loops with their body's ops nested inside, custom
calls such as the Pallas kernels), and the ``XLA Modules`` line one event
per executable run, named ``jit_<function>(<id>)``. The harness marks the
profiled slice with a host span named ``bench.slice`` (``SLICE``), on the
profiler's clock like the device's events; every device number is
clipped to it, each interval counting only its part in ``[start, end)``.
Busy time is the union of a device's clipped operation intervals, so it
never exceeds the slice; idle gaps are the rest of the slice, each
attributed to the innermost host event (any thread of the host plane,
Python frames included) that covers the middle of the gap. The
breakdown's device operations are ranked by self time, nested ops taken
out of the ops that hold them.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE = "bench.slice"
TOP = 10


def peak_for(device_kind: str, bench_dir: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; unknown is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(s: int, e: int, window) -> int:
    """Nanoseconds of [s, e) inside ``window`` (all of them if None)."""
    if window is None:
        return e - s
    return max(0, min(e, window[1]) - max(s, window[0]))


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for ev in line.events]


def op_name(text: str) -> str:
    """Short name of an XLA op event, whose name is its HLO text: the
    instruction name without ``%`` and its numeric suffix, and the
    result type (``_paged_decode_attention bf16[32,4,2,32]``)."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%")
    base, dot, num = name.rpartition(".")
    if dot and num.isdigit():
        name = base
    kind = rest.split(" ", 1)[0] if rest else ""
    kind = kind.split("{", 1)[0]
    return f"{name} {kind[:48]}".strip()


def self_times(events, window=None) -> Dict[str, float]:
    """Seconds by short op name inside ``window``, each event counted
    without the time of the events nested inside it (an XLA ``while``
    holds its body's ops). Nesting is read from the whole events, so an
    op that straddles an edge keeps its children."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [end, name, self_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if window is not None and (s + d <= window[0] or s >= window[1]):
            continue    # outside the window, as is every op nested in it
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out[n] += own * 1e-9
        if stack:
            stack[-1][2] -= clip(s, min(s + d, stack[-1][0]), window)
        stack.append([s + d, op_name(name), clip(s, s + d, window)])
    for end, n, own in stack:
        out[n] += own * 1e-9
    return out


def read_planes(path: str):
    """({device plane name: {line name: [(name, start_ns, dur_ns)]}},
    [host events], (start_ns, end_ns) of the ``SLICE`` span or None).
    The span is taken out of the host events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {line.name: _events(line)
                                   for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    spans = [(s, s + d) for name, s, d in host if name == SLICE]
    if len(spans) > 1:
        raise ValueError(f"{len(spans)} {SLICE} spans in {path}")
    host = [h for h in host if h[0] != SLICE]
    return devices, host, (spans[0] if spans else None)


def reduce_planes(devices: Dict[str, Dict[str, list]], host: list,
                  n_devices: int, window=None) -> dict:
    """The trace's numbers from already-read planes (see ``read_planes``),
    every interval clipped to ``window`` = (start_ns, end_ns) where it is
    given. ``busy_s`` is averaged over the first ``n_devices`` device
    planes; ``window_s`` is the window's length, None without one."""
    names = sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):]
                                              .split()[0])
                   if n[len(DEVICE_PREFIX):].split()[0].isdigit() else 1e9)
    names = names[:n_devices]
    op_time: Dict[str, float] = defaultdict(float)
    own_time: Dict[str, float] = defaultdict(float)
    module_time: Dict[str, float] = defaultdict(float)
    busy_ns, gaps = 0, []
    for n in names:
        lines = devices[n]
        ops = lines.get(OPS_LINE, [])
        for name, s, d in ops:
            op_time[name.partition(" = ")[0].lstrip("%")] += \
                clip(s, s + d, window) * 1e-9
        for name, v in self_times(ops, window).items():
            own_time[name] += v
        for name, s, d in lines.get(MODULES_LINE, []):
            module_time[name] += clip(s, s + d, window) * 1e-9
        spans = [(s, s + d) for _, s, d in ops]
        if window is not None:
            lo, hi = window
            spans = [(max(s, lo), min(e, hi)) for s, e in spans
                     if min(e, hi) > max(s, lo)]
        u = union(spans)
        busy_ns += sum(e - s for s, e in u)
        # inside a window, its edges bound the first and the last gap
        edges = u if window is None else [(lo, lo)] + u + [(hi, hi)]
        gaps += [(a[1], b[0]) for a, b in zip(edges, edges[1:])
                 if b[0] > a[1]]
    idle_by: Dict[str, float] = defaultdict(float)
    h_start = np.asarray([h[1] for h in host], np.int64)
    h_end = h_start + np.asarray([h[2] for h in host], np.int64)
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:2000]:
        mid = (s + e) // 2
        cover = np.flatnonzero((h_start <= mid) & (h_end > mid))
        what = (host[int(cover[np.argmin(h_end[cover] - h_start[cover])])][0]
                if cover.size else "(no host event)")
        idle_by[what] += (e - s) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    window_s = None if window is None else (window[1] - window[0]) * 1e-9
    return {
        # the mean in whole nanoseconds first: never above the window
        "busy_s": busy_ns / max(len(names), 1) * 1e-9,
        "window_s": window_s,
        "devices": len(names),
        "op_time": dict(op_time),
        "module_time": dict(module_time),
        "breakdown": {"device_ops": top(own_time),
                      "idle_gaps": top(idle_by)},
    }


def reduce(trace_dir: str, n_devices: int) -> dict:
    """The numbers of the trace under ``trace_dir``, clipped to its
    ``SLICE`` span where it has one."""
    devices, host, window = read_planes(find_xplane(trace_dir))
    return reduce_planes(devices, host, n_devices, window)


def time_matching(times: Dict[str, float], *patterns: str) -> float:
    """Seconds of every event whose name contains one of ``patterns``."""
    return sum(v for k, v in times.items()
               if any(p in k for p in patterns))

