"""Reduce a profiler trace (``.xplane.pb``) to device busy time, device
time per op and idle gaps named by the harness's own host spans.

Read with ``jax.profiler.ProfileData``.  A device is a plane whose name
starts with ``/device:`` (``/device:TPU:0``...); its ``XLA Ops`` line
holds one event per executed HLO op, named by the op's HLO text (shapes
included; a ``long_name`` stat, where a trace has one, takes its place),
and its ``XLA Modules`` line one event per program run
(``jit_fn(<id>)``).  An op belongs to the program run whose interval
holds its start.  Host and device events share one clock.  Host spans are events named ``bench.*`` on the host
planes (``jax.profiler.TraceAnnotation`` around submit, ``engine.run``,
result delivery and waiting).  Everything is clipped to the window
span, ``bench.window``, when the trace has one.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    long_name: str
    module: str


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Op]
    modules: List[Op]
    busy_ns: float


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]          # ns
    devices: List[Device]
    spans: List[Tuple[str, float, float]]  # (name, start_ns, end_ns)
    gaps: List[Tuple[str, float]]        # (host span, seconds), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) * 1e-9 \
            / len(self.devices)

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per op name, summed over devices and calls."""
        out: Dict[str, float] = {}
        for d in self.devices:
            for o in d.ops:
                out[o.name] = out.get(o.name, 0.0) + o.dur_ns * 1e-9
        return out

    def module_seconds(self) -> Dict[str, Tuple[float, int]]:
        """(device seconds, runs) per program name."""
        out: Dict[str, Tuple[float, int]] = {}
        for d in self.devices:
            for o in d.modules:
                s, n = out.get(o.name, (0.0, 0))
                out[o.name] = (s + o.dur_ns * 1e-9, n + 1)
        return out


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float,
                                                               float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_profile(pd, n_gaps: int = 10) -> Optional[Summary]:
    """The summary of a loaded ``ProfileData``; None without a device."""
    spans: List[Tuple[str, float, float]] = []
    raw_devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dst = ops if line.name == OPS_LINE else mods
                for ev in line.events:
                    st = _stats(ev)
                    dst.append(Op(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  str(st.get("long_name", ev.name)), ""))
            if ops or mods:
                _assign_modules(ops, mods)
                raw_devices.append((plane.name, ops, mods))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((ev.name, s, s + float(ev.duration_ns)))
    if not raw_devices:
        return None
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        evs = [o for _, ops, mods in raw_devices for o in ops + mods]
        lo = min(o.start_ns for o in evs)
        hi = max(o.start_ns + o.dur_ns for o in evs)
    devices, gaps = [], []
    for name, ops, mods in raw_devices:
        inside = lambda o: lo <= o.start_ns < hi          # noqa: E731
        ops = [o for o in ops if inside(o)]
        mods = [o for o in mods if inside(o)]
        busy = _clip(_union([(o.start_ns, o.start_ns + o.dur_ns)
                             for o in (ops or mods)]), lo, hi)
        devices.append(Device(name, ops, mods,
                              sum(e - s for s, e in busy)))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_host_span(spans, s, e), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary((lo, hi), devices, spans, gaps[:n_gaps])


def _assign_modules(ops: List[Op], mods: List[Op]) -> None:
    """Name each op's program: the module run whose interval holds the
    op's start (runs on one device do not overlap)."""
    import bisect
    mods.sort(key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    for o in ops:
        i = bisect.bisect_right(starts, o.start_ns) - 1
        if i >= 0 and o.start_ns < mods[i].start_ns + mods[i].dur_ns:
            o.module = mods[i].name


def _host_span(spans, s: float, e: float) -> str:
    """The innermost harness span that covers most of [s, e)."""
    best, best_cover, best_len = "outside any span", 0.0, float("inf")
    for name, a, b in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(b, e) - max(a, s)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and b - a < best_len):
            best, best_cover, best_len = name, cover, b - a
    return best


def load(trace_dir: str) -> Optional[Summary]:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_profile(ProfileData.from_file(path))
