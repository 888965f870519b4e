"""Idle time named by the program's spans, and the runtime's own
host-to-device byte count, from a profiler trace (``.xplane.pb``).

Both read the window that :func:`chipbench.xplane.reduce_profile` reads:

* ``idle_by_span``: the same idle gaps as its ``idle_gaps``, each named by
  the innermost ``sptrsv.*`` span open on the harness's thread at the gap's
  midpoint (the phases of ``DistributedSolver.solve``: ``sptrsv.stage_in``,
  ``sptrsv.launch``, ``sptrsv.fetch``, ``sptrsv.stage_out``), else
  ``outside_spans``;
* ``h2d_bytes``: the summed ``size`` of the TPU runtime's
  ``tpu::System::TransferToDevice`` events, on any host thread, that start
  inside the window. The runtime pads a small transfer to 512 bytes.
"""
from __future__ import annotations

import bisect
import collections

from chipbench import xplane

SPAN_PREFIX = "sptrsv."
OUTSIDE = "outside_spans"
TRANSFER_TO_DEVICE = "tpu::System::TransferToDevice"


def _window(pd, window: str):
    """(host plane, harness thread, start ns, end ns) of the window span."""
    host = next((p for p in pd.planes if p.name == xplane.HOST_PLANE), None)
    if host is None:
        raise ValueError(f"trace has no {xplane.HOST_PLANE} plane")
    for line in host.lines:
        for ev in line.events:
            if ev.name == window:
                return host, line, ev.start_ns, ev.end_ns
    raise ValueError(f"trace has no {window!r} span")


def idle_by_span(pd, window: str = xplane.WINDOW) -> list:
    """``[[span name, idle seconds], ...]``, largest first, mean over the
    devices that ran ops in the window."""
    _, thread, w0, w1 = _window(pd, window)
    spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in thread.events
             if ev.name.startswith(SPAN_PREFIX)
             and ev.end_ns > w0 and ev.start_ns < w1]
    seg_starts, seg_names = xplane._host_segments(spans)
    gap_ns: collections.Counter = collections.Counter()
    n_devices = 0
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        ops = next((ln for ln in plane.lines if ln.name == xplane.OPS_LINE), None)
        if ops is None:
            continue
        busy = xplane._union([(max(ev.start_ns, w0), min(ev.end_ns, w1))
                              for ev in ops.events
                              if min(ev.end_ns, w1) > max(ev.start_ns, w0)])
        if not busy:
            continue
        n_devices += 1
        prev = w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                i = bisect.bisect_right(seg_starts, (prev + s) / 2) - 1
                gap_ns[seg_names[i] if i >= 0 and seg_names[i] else OUTSIDE] += s - prev
            prev = max(prev, e)
    nd = max(1, n_devices)
    return [[k, v / 1e9 / nd] for k, v in gap_ns.most_common()]


def h2d_bytes(pd, window: str = xplane.WINDOW) -> int:
    """Bytes the runtime transferred host to device in the window."""
    host, _, w0, w1 = _window(pd, window)
    return sum(dict(ev.stats).get("size", 0)
               for line in host.lines for ev in line.events
               if ev.name == TRANSFER_TO_DEVICE and w0 <= ev.start_ns < w1)
