"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: the device's busy time (the union of its op intervals) and idle
gaps inside the traced window, each op's device time, and what the host
was doing in each gap.

The window is the span of the harness's own ``bench.step`` annotations
on the host; device ops are clipped to it.  ``load`` reads an
``.xplane.pb``; everything after it works on plain lists, so a test can
feed a small recorded trace.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float              # seconds on the profile's clock
    dur: float
    text: str = ""            # name and string stats, for matching kernels

    @property
    def end(self) -> float:
        return self.start + self.dur


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def load(tdir: str) -> dict:
    """``{"devices": [{"ops": [Event], "modules": [Event]}], "host":
    [Event]}`` from the profile under ``tdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [Event(e.name, e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9, _text(e))
                                 for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns * 1e-9,
                               e.duration_ns * 1e-9)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return {"devices": devices, "host": host}


def union(intervals: list) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


@dataclasses.dataclass
class Summary:
    window: tuple             # (start, end) of the traced steps
    steps: int                # bench.step spans in it
    devices: list             # per device: {"ops", "modules", "busy"}
    host: list                # bench.* spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the chips used."""
        return sum(sum(e - s for s, e in d["busy"])
                   for d in self.devices) / max(len(self.devices), 1)

    def ops(self, pattern: str = "") -> list:
        """Op events in the window (every device) whose name or stats
        match ``pattern``."""
        rx = re.compile(pattern)
        return [e for d in self.devices for e in d["ops"]
                if rx.search(e.text)]

    def module_s(self) -> float:
        return sum(e.dur for d in self.devices for e in d["modules"]) \
            / max(len(self.devices), 1)

    def gaps(self) -> list:
        """``(host span or "none", seconds)`` of every idle gap of the
        first device inside the window."""
        if not self.devices:
            return []
        busy = self.devices[0]["busy"]
        edges = [self.window[0]] + [t for iv in busy for t in iv] \
            + [self.window[1]]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            inner = [h for h in self.host if h.start <= mid <= h.end]
            label = min(inner, key=lambda h: h.dur).name if inner else "none"
            out.append((label, e - s))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The ``top`` ops by device time, each named by its HLO
        instruction without the operands, and the ``top`` longest gaps."""
        per_op = collections.Counter()
        for e in self.ops():
            per_op[e.name.split(" = ", 1)[0]] += e.dur
        gaps = sorted(self.gaps(), key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in per_op.most_common(top)],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(raw: dict) -> Summary:
    steps = [h for h in raw["host"] if h.name == "bench.step"]
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    w0, w1 = min(h.start for h in steps), max(h.end for h in steps)

    def clip(evs):
        return [dataclasses.replace(e, start=max(e.start, w0),
                                    dur=min(e.end, w1) - max(e.start, w0))
                for e in evs if e.end > w0 and e.start < w1]

    devices = []
    for d in raw["devices"]:
        ops = clip(d["ops"])
        devices.append({"ops": ops, "modules": clip(d["modules"]),
                        "busy": union([(e.start, e.end) for e in ops])})
    host = [h for h in raw["host"] if h.end > w0 and h.start < w1]
    return Summary((w0, w1), len(steps), devices, host)


def summarize(tdir: str) -> Summary:
    return reduce(load(tdir))
