"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced window is the host span ``WINDOW`` that the harness opens around
its traced steps. Within it, for each device plane:

* busy time: the union of the intervals of the device's XLA ops;
* collective time: the summed durations of its collective ops;
* self time by op name (ops nest: a loop holds its branches, which hold
  kernels), for the breakdown;
* idle gaps: the complement of the busy union, each named by the innermost
  host event open on the harness's thread at the gap's midpoint.

Everything is averaged over the devices that ran ops in the window.
"""
from __future__ import annotations

import bisect
import collections
import re

WINDOW = "chipbench.window"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I)
IDLE_HOST = "host idle"
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.(?:\d+|clone))*\s*=")


def op_name(hlo: str) -> str:
    """``%block_trsm.15 = f32[...] custom-call(...)`` -> ``block_trsm``: the
    op's name without its numbering or ``.clone``, stable from one compile
    to the next."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo[:64]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_segments(events: list) -> tuple[list, list]:
    """Flatten properly nested (start, end, name) events of one thread into
    (starts, names) of the innermost event at each instant; None = no event."""
    marks: list = []  # (time, kind, order, name): kind 0 = end, 1 = start
    for k, (s, e, name) in enumerate(events):
        marks.append((s, 1, -(e - s), k, name))
        marks.append((e, 0, 0, k, name))
    marks.sort()
    starts, names, stack = [], [], []
    for t, kind, _, k, name in marks:
        if kind:
            stack.append((k, name))
        else:
            for j in range(len(stack) - 1, -1, -1):
                if stack[j][0] == k:
                    del stack[j]
                    break
        starts.append(t)
        names.append(stack[-1][1] if stack else None)
    return starts, names


def reduce_profile(pd, window: str = WINDOW, top: int = 10) -> dict:
    """``pd`` is a ``jax.profiler.ProfileData``. Returns seconds throughout."""
    host = next((p for p in pd.planes if p.name == HOST_PLANE), None)
    if host is None:
        raise ValueError(f"trace has no {HOST_PLANE} plane")
    w0 = w1 = None
    thread = None
    for line in host.lines:
        for ev in line.events:
            if ev.name == window:
                w0, w1, thread = ev.start_ns, ev.end_ns, line
                break
        if thread is not None:
            break
    if thread is None:
        raise ValueError(f"trace has no {window!r} span")
    host_events = [(ev.start_ns, ev.end_ns, ev.name) for ev in thread.events
                   if ev.end_ns > w0 and ev.start_ns < w1 and ev.name != window]
    seg_starts, seg_names = _host_segments(host_events)

    devices = []
    op_ns: collections.Counter = collections.Counter()
    gap_ns: collections.Counter = collections.Counter()
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = next((ln for ln in plane.lines if ln.name == OPS_LINE), None)
        if ops is None:
            continue
        spans, coll = [], 0.0
        for ev in ops.events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                spans.append((s, e, op_name(ev.name)))
        if not spans:
            continue
        # ops nest (a while loop holds its conditionals, which hold kernels):
        # attribute each op its self time, the part no op inside it covers
        spans.sort(key=lambda t: (t[0], -t[1]))
        stack: list = []  # [start, end, name, time covered by children]
        for s, e, name in spans + [(w1 + 1, w1 + 1, "")]:
            while stack and stack[-1][1] <= s:
                ps, pe, pname, covered = stack.pop()
                op_ns[pname] += max(0, pe - ps - covered)
            if not name:
                break
            if stack:
                stack[-1][3] += min(e, stack[-1][1]) - s
            stack.append([s, e, name, 0])
            if COLLECTIVE.search(name):
                coll += e - s
        n_ops = len(spans)
        busy = _union([(s, e) for s, e, _ in spans])
        prev = w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                mid = (prev + s) / 2
                i = bisect.bisect_right(seg_starts, mid) - 1
                name = seg_names[i] if i >= 0 and seg_names[i] else IDLE_HOST
                gap_ns[name] += s - prev
            prev = max(prev, e)
        devices.append({"name": plane.name, "n_ops": n_ops,
                        "busy_s": sum(e - s for s, e in busy) / 1e9,
                        "collective_s": coll / 1e9})
    nd = max(1, len(devices))
    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / nd,
        "collective_s": sum(d["collective_s"] for d in devices) / nd,
        "device_ops": [[k, v / 1e9 / nd] for k, v in op_ns.most_common(top)],
        "idle_gaps": [[k, v / 1e9 / nd] for k, v in gap_ns.most_common(top)],
    }


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
