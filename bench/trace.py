"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
three lists of events, each ``[name, start_ns, end_ns, detail]``:

* ``ops``: the device's ``XLA Ops`` line, one event per HLO operation or
  kernel as it ran, named by its HLO instruction (``paged_ragged_attention.41``
  for the profiler's ``%paged_ragged_attention.41 = bf16[...]
  custom-call(...)``), ``detail`` any program name the profiler gives;
* ``modules``: the device's ``XLA Modules`` line, one event per run of a
  compiled program;
* ``host``: every host thread's events, ``detail`` the thread's name.

The traced window is the host span named :data:`WINDOW`, which the
benchmark opens right after the trace starts and closes right before it
stops.  Everything below works on those lists alone, so a recorded
excerpt (:meth:`Trace.to_json`) reduces the same way as a live trace.
"""
from __future__ import annotations

import glob
import json
from typing import List, Optional, Sequence, Tuple

WINDOW = "bench.window"


class Trace:
    def __init__(self, ops, modules, host, window: Tuple[float, float]):
        self.ops, self.modules, self.host = ops, modules, host
        self.window = window

    # -- i/o ---------------------------------------------------------------
    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["ops"], d["modules"], d["host"], tuple(d["window"]))

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules,
                "host": self.host, "window": list(self.window)}

    # -- reductions --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _inside(self, events) -> list:
        """The events that overlap the window, clipped to it."""
        a, b = self.window
        return [(ev[0], max(ev[1], a), min(ev[2], b), ev[3])
                for ev in events if ev[2] > a and ev[1] < b]

    def busy_intervals(self) -> List[Tuple[float, float]]:
        """The union of device op intervals inside the window, sorted."""
        out: List[List[float]] = []
        for s, e in sorted((s, e) for _, s, e, _ in self._inside(self.ops)):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy_intervals()) / 1e9

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest spans of the window with no device op, each named
        by the host event (``thread: name``) that covers most of it."""
        a, b = self.window
        edges, t = [], a
        for s, e in self.busy_intervals():
            if s > t:
                edges.append((t, s))
            t = max(t, e)
        if t < b:
            edges.append((t, b))
        edges.sort(key=lambda g: g[0] - g[1])
        return [(self._host_label(s, e), (e - s) / 1e9)
                for s, e in edges[:top]]

    def _host_label(self, s: float, e: float) -> str:
        best, label = (0.0, 0.0), "no host event"
        for name, hs, he, thread in self.host:
            if name == WINDOW:
                continue
            # most of the gap covered; among equals, the innermost event
            key = (min(he, e) - max(hs, s), hs - he)
            if key[0] > 0 and key > best:
                best, label = key, f"{thread}: {name}"
        return label

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by op kind (the instruction name without its
        number) inside the window, largest first."""
        tot: dict = {}
        for name, s, e, _ in self._inside(self.ops):
            k = op_kind(name)
            tot[k] = tot.get(k, 0.0) + (e - s) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:top]

    def op_time_s(self, kinds: Sequence[str]) -> Tuple[float, int]:
        """Seconds and count of the device ops in the window whose kind
        is one of ``kinds``."""
        hits = [ev for ev in self._inside(self.ops)
                if op_kind(ev[0]) in kinds]
        return sum(e - s for _, s, e, _ in hits) / 1e9, len(hits)

    def module_runs(self, patterns: Sequence[str]) -> List[float]:
        """Durations (s) of the runs of the compiled programs whose name
        contains one of ``patterns``, wholly inside the window."""
        a, b = self.window
        return [(e - s) / 1e9 for name, s, e, _ in self.modules
                if any(p in name for p in patterns) and s >= a and e <= b]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """``paged_ragged_attention.41`` -> ``paged_ragged_attention``."""
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def _stat(ev, key) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    window = None
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                rec = [ev.name, float(ev.start_ns), float(ev.end_ns)]
                if device and line.name == "XLA Ops":
                    rec[0] = op_name(ev.name)
                    ops.append(rec + [_stat(ev, "hlo_module") or ""])
                elif device and line.name == "XLA Modules":
                    modules.append(rec + [_stat(ev, "hlo_module") or ""])
                elif plane.name.startswith("/host:"):
                    if ev.name == WINDOW:
                        window = (rec[1], rec[2])
                    host.append(rec + [line.name])
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return Trace(ops, modules, host, window)


def structure(trace_dir: str, per_line: int = 3) -> dict:
    """Plane and line names with a few events each, to look at a trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(paths[-1])
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            out[f"{plane.name} | {line.name} ({len(evs)})"] = [
                [ev.name, ev.duration_ns, [list(s) for s in ev.stats][:8]]
                for ev in evs[:per_line]]
    return json.loads(json.dumps(out, default=str))


def excerpt(t: Trace, max_ops: int = 400) -> Trace:
    """A small slice of ``t``: the first ``max_ops`` device ops of the
    window with the modules and host events around them."""
    ops = [ev for ev in t.ops if ev[1] >= t.window[0]][:max_ops]
    if not ops:
        return Trace([], [], [], t.window)
    a, b = ops[0][1], ops[-1][2]
    keep = lambda evs: [ev for ev in evs if ev[2] > a and ev[1] < b]
    host = [ev for ev in keep(t.host) if ev[0] != WINDOW]
    return Trace(ops, keep(t.modules), host, (a, b))
