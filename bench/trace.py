"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A TPU trace has one plane per chip (``/device:TPU:<n>``).  Its ``XLA
Modules`` line holds one event per program execution; its ``XLA Ops`` line
one event per operation, named by the operation's HLO text, including
every operation inside a loop (millions a second here), so of those only
the Pallas kernels (``custom_call_target="tpu_custom_call"``) are kept.
The host plane (``/host:CPU``) holds the benchmark's own spans, the
``jax.profiler.TraceAnnotation`` names that start with ``bench.``.

A CPU trace has no device plane: operations run on host threads and carry
``hlo_module`` and ``run_id`` stats, and one ``(hlo_module, run_id)`` group
is one program execution, from its first operation's start to its last
one's end.

Everything is cut to the ``bench.window`` span.  Device busy time is the
union of the program executions' intervals inside it, and the idle share
is one minus busy over the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16|c64|c128)\[([\d,]*)\]")


def profile_options():
    """Profiler options of a traced run: no Python tracer (it would trace
    every Python call of the host-bound dispatch loop) and host events at
    level 1, which keeps the benchmark's annotations."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(log_dir) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


@dataclasses.dataclass
class Event:
    name: str
    start: int      # ns
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


def shapes(hlo_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, dims)`` of every array in an HLO instruction's text, the
    result first and then its operands."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(hlo_text)]


def _union(intervals) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]
    programs: Dict[str, List[Event]]     # device -> program executions
    kernels: List[Event]                 # Pallas kernel executions
    spans: List[Event]                   # the benchmark's host spans

    def _in_window(self, events) -> list:
        lo, hi = self.window
        return [x for x in events if x.end > lo and x.start < hi]

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: str) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return _union((max(p.start, lo), min(p.end, hi))
                      for p in self._in_window(self.programs[device]))

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the devices."""
        if not self.programs:
            return 0.0
        total = sum(e - s for d in self.programs
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / len(self.programs)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def program_count(self) -> float:
        """Program executions that start in the window, per device."""
        if not self.programs:
            return 0.0
        lo, hi = self.window
        n = sum(1 for d in self.programs for p in self.programs[d]
                if lo <= p.start < hi)
        return n / len(self.programs)

    def kernels_named(self, pattern: str) -> List[Event]:
        """Kernel executions in the window whose HLO text matches
        ``pattern``."""
        rx = re.compile(pattern)
        return [k for k in self._in_window(self.kernels) if rx.search(k.name)]

    def span_at(self, t: int) -> str:
        """The innermost benchmark span open at time ``t``."""
        best: Optional[Event] = None
        for s in self.spans:
            if s.start <= t < s.end and (best is None or s.start >= best.start):
                best = s
        return best.name if best else "outside"

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took most device time in the window, and the
        window's idle time by the benchmark span the host was in."""
        lo, hi = self.window
        n = max(len(self.programs), 1)
        per_program: Dict[str, int] = {}
        gaps: Dict[str, int] = {}
        for d, progs in self.programs.items():
            for p in self._in_window(progs):
                per_program[p.name] = per_program.get(p.name, 0) + (
                    min(p.end, hi) - max(p.start, lo))
            t = lo
            for s, e in self.busy_intervals(d) + [(hi, hi)]:
                if s > t:
                    label = self.span_at((t + s) // 2)
                    gaps[label] = gaps.get(label, 0) + (s - t)
                t = max(t, e)

        def ranked(totals):
            return sorted(([k, v * 1e-9 / n] for k, v in totals.items()),
                          key=lambda kv: -kv[1])[:top]
        return {"device_ops": ranked(per_program), "idle_gaps": ranked(gaps)}


def reduce(path: str) -> Reduced:
    """Read an ``.xplane.pb`` into a :class:`Reduced` trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    programs: Dict[str, List[Event]] = {}
    kernels: List[Event] = []
    spans: List[Event] = []
    host_runs: Dict[tuple, List[int]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs[plane.name] = [
                        Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        if KERNEL_TARGET in name:
                            kernels.append(Event(name, int(ev.start_ns),
                                                 int(ev.duration_ns)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append(Event(name, s, int(ev.duration_ns)))
                        continue
                    stats = dict(ev.stats)
                    if "hlo_module" in stats and "run_id" in stats:
                        s = int(ev.start_ns)
                        run = host_runs.setdefault(
                            (str(stats["hlo_module"]), stats["run_id"]),
                            [s, s])
                        run[0] = min(run[0], s)
                        run[1] = max(run[1], s + int(ev.duration_ns))
    if not programs and host_runs:
        programs["/host:CPU"] = [Event(k[0], s, e - s)
                                 for k, (s, e) in host_runs.items()]
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    w = max(windows, key=lambda s: s.dur)
    return Reduced((w.start, w.end), programs, kernels, spans)
