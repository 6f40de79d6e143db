"""Reduce a JAX profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports: busy time, per-op time, kernel time, and the longest
idle gaps labelled by what the host was doing.

Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line holds
one event per operation run. Host spans are the harness's own
``TraceAnnotation`` events (names starting ``bench.``) on the host plane.
All events of one trace share one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"[.\-_]\d+$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                # union of op intervals, mean over devices
    op_s: dict                   # op name (numeric suffix stripped) -> s
    idle_gaps: list              # [(host span name, s)], longest first
    n_devices: int


def op_family(name: str) -> str:
    """``fusion.123`` → ``fusion``; ``copy.4`` → ``copy``. A TPU trace names
    an op by its HLO text (``%copy.4 = f32[...] copy(...)``): the
    instruction name before `` = `` is taken."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(device_ops: dict, host_spans: list, window: tuple,
                  top: int = 10) -> Reduced:
    """``device_ops``: device id -> [(name, start_ns, dur_ns)];
    ``host_spans``: [(name, start_ns, dur_ns)]; ``window``: (start_ns,
    end_ns) of the traced window. Events are clipped to the window."""
    w0, w1 = window
    busy, op_s, gaps = [], {}, []
    for dev, events in sorted(device_ops.items()):
        iv = []
        for name, st, dur in events:
            s, e = max(st, w0), min(st + dur, w1)
            if e <= s:
                continue
            iv.append((s, e))
            fam = op_family(name)
            op_s[fam] = op_s.get(fam, 0.0) + (e - s) / len(device_ops)
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i], edges[i + 1]))
    spans = sorted(host_spans, key=lambda x: x[1])
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:top]:
        best, cover = "none", 0.0
        for name, st, dur in spans:
            c = min(e, st + dur) - max(s, st)
            if c > cover:
                best, cover = name[len(HOST_PREFIX):], c
        labelled.append((best, (e - s) / 1e9))
    n = max(len(device_ops), 1)
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / n / 1e9,
                   op_s={k: v / 1e9 for k, v in op_s.items()},
                   idle_gaps=labelled, n_devices=len(device_ops))


def load_events(path: str) -> tuple[dict, list, tuple | None]:
    """Device ops and harness host spans of one ``.xplane.pb``, and the
    window the ``bench.window`` span marks (None when absent)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == HOST_PREFIX + "window":
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    return device_ops, host, window


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str) -> Reduced | None:
    """None when the trace holds no device ops or no window span."""
    device_ops, host, window = load_events(path)
    if not device_ops or window is None:
        return None
    return reduce_events(device_ops, host, window)
