"""Reduce a profiler trace (``.xplane.pb``) to device time.

``reduce_trace`` reads the planes of the devices a cell used and the host
plane, clips every device operation to the window (the host span named
``window``), and returns:

- ``window_s``: the window's length;
- ``busy_s``: per device, the union of the intervals in which an operation
  ran; ``busy_s_mean`` over the devices;
- ``ops``: per operation name, [count, seconds] summed over the devices,
  with ``details`` holding the text a kernel can be matched by (the event's
  name and its string stats);
- ``gaps``: per device, the idle gaps inside the window, longest first,
  each named by what the host was doing (``host_activity``).

Kernels carry no names of their own yet, so ``op_seconds`` matches
operations by regular expression over the event name and its string stats.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

#: device op lines, in order of preference
OP_LINES = ("XLA Ops",)
#: host events that say nothing about what the host was doing
_HOST_NOISE = re.compile(r"^(ThreadpoolListener|\$)")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: Dict[str, float]
    ops: Dict[str, List[float]]
    details: Dict[str, str]
    gaps: Dict[str, List[Tuple[str, float]]]
    devices: List[str]

    @property
    def busy_s_mean(self) -> float:
        return sum(self.busy_s.values()) / max(1, len(self.busy_s))

    def op_seconds(self, pattern: str) -> Tuple[int, float]:
        """(events, seconds summed over the devices) of the operations whose
        name or details match ``pattern``."""
        rx = re.compile(pattern)
        count = secs = 0.0
        for name, (c, s) in self.ops.items():
            if rx.search(name) or rx.search(self.details.get(name, "")):
                count += c
                secs += s
        return int(count), secs

    def top_ops(self, k: int = 10) -> List[List]:
        items = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:k]
        return [[short_name(name), s] for name, (_, s) in items]

    def top_gaps(self, k: int = 10) -> List[List]:
        allg = [g for gs in self.gaps.values() for g in gs]
        return [[n, s] for n, s in sorted(allg, key=lambda g: -g[1])[:k]]


def short_name(name: str, width: int = 160) -> str:
    """An op's HLO text without layouts and operands, cut to ``width``:
    ``%pallas_call.2 = (f32[524288,512], f32[524288,128]) custom-call``."""
    name = re.sub(r"\{[^{}]*\}", "", name)
    name = re.sub(r"\{[^{}]*\}", "", name)
    head = re.split(r"(?<=[a-z-])\(", name, maxsplit=1)[0]
    return head[:width]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _stat_text(event) -> str:
    parts = []
    for k, v in event.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0 and not _HOST_NOISE.match(ev.name):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def host_activity(spans, a: float, b: float) -> str:
    """The innermost host span that covers the gap [a, b)'s middle, or
    ``idle host``."""
    mid = 0.5 * (a + b)
    best = None
    for s, e, name in spans:
        if s <= mid < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "idle host"


def reduce_trace(path: str, device_ids: Optional[List[int]] = None,
                 window_name: str = "window",
                 max_gaps: int = 10) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, n in spans if n == window_name]
    if not windows:
        raise ValueError(f"no host span named {window_name!r} in {path}")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])

    wanted = None if device_ids is None else \
        {f"/device:TPU:{i}" for i in device_ids}
    busy, ops, details, gaps, devices = {}, {}, {}, {}, []
    for plane in planes:
        if not re.fullmatch(r"/device:[A-Z]+:\d+", plane.name):
            continue
        if wanted is not None and plane.name not in wanted:
            continue
        lines = [ln for ln in plane.lines if ln.name in OP_LINES]
        if not lines:
            continue
        devices.append(plane.name)
        ivals = []
        for line in lines:
            for ev in line.events:
                a = max(ev.start_ns, w0)
                b = min(ev.start_ns + ev.duration_ns, w1)
                if b <= a:
                    continue
                ivals.append((a, b))
                c, s = ops.get(ev.name, (0, 0.0))
                ops[ev.name] = [c + 1, s + (b - a) * 1e-9]
                if ev.name not in details:
                    details[ev.name] = _stat_text(ev)
        merged = _union(ivals)
        busy[plane.name] = sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle.sort(key=lambda g: g[0] - g[1])
        gaps[plane.name] = [(host_activity(spans, a, b), (b - a) * 1e-9)
                            for a, b in idle[:max_gaps]]
    if not devices:
        raise ValueError(f"no device op lines {OP_LINES} in {path}")
    return Reduction((w1 - w0) * 1e-9, busy, ops, details, gaps, devices)

