"""Reduction of a profiler trace to the benchmark's device numbers.

A trace taken with ``jax.profiler`` is read with ``jax.profiler.ProfileData``
into three lists, all on the profiler's one clock (nanoseconds):

- per device (planes ``/device:TPU:<n>``): the operation events of its
  ``XLA Ops`` line and the executable events of its ``XLA Modules`` line;
- the harness's own spans (host events named ``chipbench.*``), which say
  what the host was doing, such as running a fit.

Busy time is the union of a device's operation intervals inside a window;
the idle share is one minus busy over the window.  Everything here is plain
arithmetic on those lists, so it can be checked on a hand-built trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def short_name(hlo: str) -> str:
    """``%fusion.98 = f32[4096,4096]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.98 f32[4096,4096]``: the operation and its result shape."""
    lhs, _, rhs = hlo.partition(" = ")
    if not rhs:
        return hlo
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    return f"{lhs.lstrip('%')} {shape}"


def _add(tot: dict, entry: list) -> None:
    _, name, ns = entry
    tot[name] = tot.get(name, 0.0) + ns


@dataclasses.dataclass
class Device:
    name: str
    ops: list        # [(name, start_ns, end_ns)]
    modules: list    # [(name, start_ns, end_ns)]


@dataclasses.dataclass
class Trace:
    devices: list    # [Device]
    spans: list      # [(name, start_ns, end_ns)], host spans of the harness

    # -- construction ---------------------------------------------------
    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        devs = [Device(name=x["name"],
                       ops=[tuple(e) for e in x.get("ops", [])],
                       modules=[tuple(e) for e in x.get("modules", [])])
                for x in d["devices"]]
        return cls(devices=devs, spans=[tuple(e) for e in d["spans"]])

    @classmethod
    def from_profile(cls, path: str) -> "Trace":
        """Read one ``.xplane.pb`` file."""
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices, spans = [], []
        for plane in data.planes:
            if _DEVICE_PLANE.match(plane.name):
                dev = Device(name=plane.name, ops=[], modules=[])
                for line in plane.lines:
                    into = {"XLA Ops": dev.ops,
                            "XLA Modules": dev.modules}.get(line.name)
                    if into is None:
                        continue
                    for ev in line.events:
                        into.append((short_name(ev.name), ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        devices.sort(key=lambda d: d.name)
        return cls(devices=devices, spans=spans)

    @classmethod
    def from_dir(cls, directory: str) -> "Trace | None":
        """The newest trace under a ``jax.profiler.start_trace`` directory."""
        files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        return cls.from_profile(max(files, key=os.path.getmtime))

    # -- spans ------------------------------------------------------------
    def span_intervals(self, name: str) -> list:
        return sorted((s, e) for n, s, e in self.spans if n == name)

    def window(self, name: str = SPAN_PREFIX + "window") -> tuple | None:
        iv = self.span_intervals(name)
        return (iv[0][0], iv[-1][1]) if iv else None

    # -- device time ------------------------------------------------------
    def busy_intervals(self, device: Device, start, end) -> np.ndarray:
        """Merged (M, 2) intervals in which ``device`` ran an operation,
        clipped to [start, end]."""
        if not device.ops:
            return np.zeros((0, 2))
        iv = np.array([(s, e) for _, s, e in device.ops], dtype=np.float64)
        iv = np.clip(iv, start, end)
        iv = iv[iv[:, 1] > iv[:, 0]]
        if iv.size == 0:
            return np.zeros((0, 2))
        iv = iv[np.argsort(iv[:, 0], kind="stable")]
        # A new merged interval starts wherever an op begins after every
        # earlier op has ended.
        reach = np.maximum.accumulate(iv[:, 1])
        new = np.ones(len(iv), bool)
        new[1:] = iv[1:, 0] > reach[:-1]
        starts = iv[new, 0]
        ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
        return np.stack([starts, ends], axis=1)

    def busy_s(self, start, end) -> float:
        """Seconds with an operation running, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = [float(np.sum(np.diff(self.busy_intervals(d, start, end),
                                    axis=1))) for d in self.devices]
        return float(np.mean(tot)) * 1e-9

    def idle_share(self, start, end) -> float | None:
        if not self.devices or end <= start:
            return None
        return 1.0 - self.busy_s(start, end) / ((end - start) * 1e-9)

    def top_ops(self, start, end, n: int = 10) -> list:
        """[[op, seconds]]: self time by operation inside the window
        (clipped), averaged over devices, largest first.  An operation's
        self time leaves out the operations nested in it (a ``while`` holds
        its body's), so no time is counted twice."""
        tot: dict = {}
        for d in self.devices:
            ops = sorted(((max(s, start), min(e, end), name)
                          for name, s, e in d.ops if e > start and s < end),
                         key=lambda o: (o[0], -o[1]))
            stack: list = []              # [end, name, self ns] of open ops
            for s, e, name in ops:
                while stack and stack[-1][0] <= s:
                    _add(tot, stack.pop())
                if stack:
                    stack[-1][2] -= min(e, stack[-1][0]) - s
                stack.append([e, name, e - s])
            while stack:
                _add(tot, stack.pop())
        k = max(len(self.devices), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in ranked]

    def idle_gaps(self, start, end, n: int = 10) -> list:
        """[[what the host was doing, seconds]] for the longest stretches in
        which the first device ran nothing, each named by the innermost
        harness span around its midpoint ("none" outside every span)."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0], start, end)
        edges = np.concatenate([[start], busy.ravel(), [end]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:n]
        out = []
        for gs, ge in gaps:
            mid = 0.5 * (gs + ge)
            around = [(e - s, name) for name, s, e in self.spans
                      if s <= mid <= e and name != SPAN_PREFIX + "window"]
            label = min(around)[1] if around else "none"
            out.append([label, float(ge - gs) * 1e-9])
        return out

    def modules_in(self, start, end, device: int = 0) -> list:
        """[(name, start, end)] executables of one device that overlap the
        window."""
        if not self.devices:
            return []
        return [m for m in self.devices[device].modules
                if m[2] > start and m[1] < end]
