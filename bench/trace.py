"""Reduction of a profiler trace to the numbers the readers take.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain (name, start_ns, end_ns) events:
the operations and the programs that ran on each device, and the host
spans the driver wrote (``bench.*``). :class:`Summary` does the arithmetic
on those lists alone, so it can be checked on a synthetic trace:

- busy time is the union of the device's operation intervals inside the
  window, idle time the rest;
- an operation's or a program's time is the sum of its events' durations,
  clipped to the window;
- each idle gap is named for the host span that covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
# An operation's event is named by its HLO text: "%name.12 = <shape>
# opcode(operands), attributes". Control-flow operations span their body's
# operations, so they count neither as busy time nor as an operation.
HLO_OP = re.compile(r"^%([A-Za-z_][\w.-]*?)(?:\.\d+)? = .*?\s([a-z][\w-]*)\(")
KERNEL_NAME = re.compile(r"kernel_name[\\\"':= ]+([A-Za-z_]\w*)")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
PALLAS = "tpu_custom_call"
CONTAINERS = {"while", "conditional", "call"}


def op_name(text: str) -> tuple[str, bool]:
    """(short name, is a container) of an operation's event: for a Pallas
    kernel its ``kernel_name`` where the program gives one, else
    ``tpu_custom_call``; for other operations the instruction's name
    without its instance number; program names lose their id in ()."""
    m = HLO_OP.match(text)
    if not m:
        return re.sub(r"\(\d+\)$", "", text), False
    name, opcode = m.group(1), m.group(2)
    if opcode == "custom-call" and PALLAS_TARGET in text:
        k = KERNEL_NAME.search(text)
        return (k.group(1) if k else PALLAS), False
    return name, opcode in CONTAINERS


def qualify_kernels(ops: list, modules: list) -> list:
    """Name each unnamed Pallas kernel event for the program it ran in,
    ``tpu_custom_call[<program>]``: the only handle on a kernel whose
    ``pallas_call`` has no name."""
    spans = sorted((t0, t1, name) for name, t0, t1 in modules)
    starts = [s[0] for s in spans]
    out = []
    for name, t0, t1 in ops:
        if name == PALLAS:
            i = bisect.bisect_right(starts, t0) - 1
            if i >= 0 and spans[i][1] >= t0:
                name = f"{PALLAS}[{spans[i][2]}]"
        out.append((name, t0, t1))
    return out


@dataclass
class Summary:
    """Events of one traced window. Times are in nanoseconds on the
    profiler's clock; ``ops`` and ``modules`` hold one list per device."""
    window: tuple
    ops: list = field(default_factory=list)        # [[(name, t0, t1)]]
    modules: list = field(default_factory=list)
    host: list = field(default_factory=list)       # [(name, t0, t1)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, t0, t1):
        return max(t0, self.window[0]), min(t1, self.window[1])

    def busy_intervals(self, device: int = 0) -> list:
        """Merged intervals in which some operation ran on ``device``."""
        out = []
        for _, t0, t1 in sorted(self.ops[device], key=lambda e: e[1]):
            t0, t1 = self._clip(t0, t1)
            if t1 <= t0:
                continue
            if out and t0 <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t1)
            else:
                out.append([t0, t1])
        return out

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices traced."""
        per = [sum(t1 - t0 for t0, t1 in self.busy_intervals(d))
               for d in range(len(self.ops))]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def seconds(self, events, pattern: str) -> float:
        """Clipped seconds of the events whose name matches ``pattern``,
        summed over devices and averaged over them."""
        rx = re.compile(pattern)
        total = 0
        for dev in events:
            for name, t0, t1 in dev:
                if rx.search(name):
                    a, b = self._clip(t0, t1)
                    total += max(0, b - a)
        return total / max(len(events), 1) * 1e-9

    def op_seconds(self, pattern: str) -> float:
        return self.seconds(self.ops, pattern)

    def module_seconds(self, pattern: str) -> float:
        return self.seconds(self.modules, pattern)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operation names that took most device time."""
        acc: dict = {}
        for dev in self.ops:
            for name, t0, t1 in dev:
                a, b = self._clip(t0, t1)
                if b > a:
                    acc[name] = acc.get(name, 0) + (b - a)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / max(len(self.ops), 1)] for k, v in top]

    def idle_gaps(self, n: int = 10, device: int = 0) -> list:
        """The ``n`` longest idle gaps of ``device`` in the window, each
        named for the host span that covers most of it."""
        busy = self.busy_intervals(device)
        gaps, t = [], self.window[0]
        for b0, b1 in busy:
            if b0 > t:
                gaps.append((t, b0))
            t = max(t, b1)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for g0, g1 in gaps[:n]:
            out.append([self.host_span_at(g0, g1), (g1 - g0) * 1e-9])
        return out

    def host_span_at(self, t0, t1) -> str:
        best, name = 0, "none"
        for span, s0, s1 in self.host:
            if span == WINDOW_SPAN:
                continue
            cover = min(t1, s1) - max(t0, s0)
            if cover > best:
                best, name = cover, span
        return name


def latest_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def load(directory: str, devices: int = 1) -> Summary:
    """Read the newest trace under ``directory`` into a :class:`Summary`
    whose window is the driver's ``bench.window`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(latest_xplane(directory))
    ops, modules, host = {}, {}, []
    names: dict = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                evs = []
                for e in line.events:
                    text = e.name
                    if text not in names:
                        names[text] = op_name(text)
                    short, container = names[text]
                    if not container:
                        evs.append((short, e.start_ns, e.end_ns))
                dest = ops if line.name == OPS_LINE else modules
                dest[int(m.group(1))] = evs
            elif not m:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace has no bench.window span")
    ids = sorted(ops)[:devices]
    return Summary(window=windows[0][1:],
                   ops=[qualify_kernels(ops[i], modules.get(i, []))
                        for i in ids],
                   modules=[modules.get(i, []) for i in ids], host=host)
