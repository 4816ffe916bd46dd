"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
`jax.profiler.ProfileData` reads it. In it:

  * each chip is a plane ``/device:TPU:<i>``, whose line ``XLA Ops`` holds
    one event per operation run on the chip, named by its HLO instruction
    (``%acam_match_serve.1 = (f32[64,2048]{...}, ...) custom-call(...)``
    for the serve kernel). An operation is kept here as its instruction
    name and first output shape: ``acam_match_serve.1 (f32[64,2048]``.
    Asynchronous copies (line ``Async XLA Ops``) are not counted as busy;
  * the host is the plane ``/host:CPU``, one line per thread. The thread
    that ran the window holds the ``jax.profiler.TraceAnnotation`` spans of
    the benchmark (``bench.*``) and of the program (``acam_fused_dispatch``)
    with the runtime's own spans inside them; only that thread is kept.

All times are nanoseconds on one clock. The traced window is the span of
the benchmark's ``bench.window`` annotation.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the serve mega-kernel's custom call (`acam_match_serve`)
SERVE_KERNEL = re.compile(r"^acam_match_serve\b")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)")
_SHAPE = re.compile(r"\(?[a-z0-9]+\[[0-9,]*\]")


def op_name(hlo: str) -> str:
    """``%copy.1 = f32[8,2]{1,0} copy(...)`` -> ``copy.1 f32[8,2]``."""
    head, _, tail = hlo.partition(" = ")
    m = _SHAPE.match(tail)
    return head.lstrip("%") + (" " + m.group(0) if m else "")


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]  # (start, end) ns
    ops: dict  # device index -> [(start, end, name)] sorted by start
    host: list  # [(start, end, name)] the window thread's events, sorted

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def device_ops(self, device: int = 0) -> list:
        """The device's operations inside the window (clipped to it)."""
        lo, hi = self.window
        return [(max(s, lo), min(e, hi), n) for s, e, n in
                self.ops.get(device, []) if e > lo and s < hi]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: dict = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         op_name(e.name)) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                if any(n == WINDOW for _, _, n in evs):
                    host = evs
    for v in ops.values():
        v.sort()
    host.sort()
    marks = [(s, e) for s, e, n in host if n == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW!r} span in the trace {path}")
    return Trace(window=(marks[0][0], marks[-1][1]), ops=ops, host=host)


def union(intervals) -> list:
    """Merge (start, end, ...) intervals into disjoint (start, end)."""
    out: list = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, device: int = 0) -> float:
    """Seconds of the window in which some operation ran on the device."""
    return sum(e - s for s, e in union(trace.device_ops(device))) * 1e-9


def idle_share(trace: Trace, device: int = 0) -> float:
    """1 - busy / window, of one device."""
    return 1.0 - busy_s(trace, device) / trace.window_s


def op_events(trace: Trace, match, device: int = 0) -> list:
    """Durations (ns) of the device's operations whose name ``match``es (a
    compiled regex or a predicate on the name)."""
    pred = match.search if hasattr(match, "search") else match
    return [e - s for s, e, n in trace.device_ops(device) if pred(n)]


def host_spans(trace: Trace, name: str) -> list:
    """Durations (ns) of the host spans called ``name`` in the window."""
    lo, hi = trace.window
    return [e - s for s, e, n in trace.host if n == name and s >= lo
            and e <= hi]


def top_ops(trace: Trace, device: int = 0, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time."""
    tot: dict = {}
    for s, e, name in trace.device_ops(device):
        tot[name] = tot.get(name, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(trace: Trace, device: int = 0, n: int = 10) -> list:
    """[[name, seconds]]: the device's idle time inside the window, by the
    innermost host span open at the middle of each gap ("none" where no
    span is open), the largest totals first."""
    lo, hi = trace.window
    busy = union(trace.device_ops(device))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = [(s, e, name) for s, e, name in trace.host
             if name != WINDOW and e > s and e > lo and s < hi]
    tot: dict = {}
    active: list = []
    k = 0
    for g0, g1 in gaps:  # in time order: sweep the spans once
        mid = 0.5 * (g0 + g1)
        while k < len(spans) and spans[k][0] <= mid:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp[1] >= mid]
        key = max(active)[2] if active else "none"  # latest start: innermost
        tot[key] = tot.get(key, 0) + (g1 - g0)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]
