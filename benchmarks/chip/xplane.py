"""A profiler trace as plain event lists, and the reductions the metrics use.

``Trace.from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes:
every event of each TPU's op and module lines, and the benchmark's own host
spans (``bench.*``, ``serve.*``, ``loadgen.*``). Device and host events are
on one clock there. ``to_json``/``from_json`` keep a trimmed trace as a test
fixture. Times are nanoseconds.

On a v5e an op event is named by its whole HLO instruction
(``%streamsvm_fit_many.1 = (...) custom-call(...), custom_call_target=
"tpu_custom_call", ...``) and carries no module; the module line holds one
event per program run (``jit_streamsvm_fit_many(<id>)``). So each op keeps
its instruction name (``streamsvm_fit_many.1``), its kind (the HLO opcode,
or ``tpu_custom_call`` for a Mosaic kernel) and the module whose run
contains it.
"""
from __future__ import annotations

import glob
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

#: Host spans the benchmark records around its calls into each layer.
SPAN_PREFIXES = ("bench.", "serve.", "loadgen.")
#: The span around the measured window; every reduction is cut to it.
WINDOW_SPAN = "bench.window"
#: Device lines kept: one event per HLO op, and one per program run.
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
MOSAIC = "tpu_custom_call"


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    module: str = ""
    kind: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    #: device plane name -> {line name -> events}
    devices: dict[str, dict[str, list[Event]]]
    spans: list[Event]
    window: tuple[float, float] = field(default=(0.0, 0.0))

    # -- reading --------------------------------------------------------------

    @classmethod
    def from_xplane(cls, trace_dir) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        devices: dict[str, dict[str, list[Event]]] = {}
        spans: list[Event] = []
        for plane in ProfileData.from_file(paths[-1]).planes:
            if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
                lines = devices.setdefault(plane.name, {})
                for line in plane.lines:
                    if line.name in (OP_LINE, MODULE_LINE):
                        lines[line.name] = [
                            Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIXES)]
        return cls._windowed(devices, spans)

    @classmethod
    def _windowed(cls, devices, spans) -> "Trace":
        spans.sort(key=lambda e: e.start)
        win = [s for s in spans if s.name == WINDOW_SPAN]
        window = (win[0].start, win[0].end) if win else (0.0, 0.0)
        devices = {p: _normalize(lines) for p, lines in
                   sorted(devices.items(), key=_plane_key)}
        return cls(devices=devices, spans=spans, window=window)

    def to_json(self) -> dict:
        ev = lambda e: [e.name, e.start, e.end, e.module, e.kind]
        return {
            "devices": {p: {ln: [ev(e) for e in evs] for ln, evs in lines.items()}
                        for p, lines in self.devices.items()},
            "spans": [ev(e) for e in self.spans],
        }

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        mk = lambda row: Event(*row)
        devices = {p: {ln: [mk(r) for r in evs] for ln, evs in lines.items()}
                   for p, lines in data["devices"].items()}
        return cls._windowed(devices, [mk(r) for r in data["spans"]])

    # -- reductions -----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, plane: str) -> list[Event]:
        """One device's op events that start inside the window."""
        lo, hi = self.window
        return [e for e in self.devices[plane].get(OP_LINE, [])
                if lo <= e.start < hi]

    def busy_s(self, plane: str) -> float:
        """Seconds of the window in which some op ran on this device."""
        return union_ns(self.devices[plane].get(OP_LINE, []),
                        *self.window) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(p) for p in self.devices) / len(self.devices)

    def spans_named(self, name: str) -> list[Event]:
        lo, hi = self.window
        return [s for s in self.spans if s.name == name and lo <= s.start < hi]


def _normalize(lines: dict) -> dict:
    """Short op names, their kinds, and the module run each op lies in."""
    mods = sorted((Event(m.name.split("(")[0], m.start, m.end)
                   for m in lines.get(MODULE_LINE, [])), key=lambda m: m.start)
    starts = [m.start for m in mods]
    ops = []
    for e in lines.get(OP_LINE, []):
        name, kind = e.name, e.kind
        if " = " in name:
            head, text = name.split(" = ", 1)
            name = head.lstrip("%")
            m = _OPCODE.search(text)
            kind = MOSAIC if MOSAIC in text else (m.group(1) if m else "")
        module = e.module
        if not module and mods:
            i = _bisect(starts, e.start)
            if i >= 0 and e.start < mods[i].end:
                module = mods[i].name
        ops.append(Event(name, e.start, e.end, module, kind))
    return {**lines, MODULE_LINE: mods, OP_LINE: ops}


def _bisect(starts, t) -> int:
    import bisect

    return bisect.bisect_right(starts, t) - 1


def _plane_key(item):
    return int(item[0].rsplit(":", 1)[1])


def union_ns(events, lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, cut to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(trace: Trace, plane: str, top: int = 10) -> list[list]:
    """The longest stretches of the window with nothing on ``plane``, each
    named by the innermost benchmark span that covers its middle."""
    return [[name, (t - s) * 1e-9] for name, s, t in _gaps(trace, plane, top)]


def _gaps(trace: Trace, plane: str, top: int) -> list[tuple]:
    lo, hi = trace.window
    evs = sorted(trace.devices[plane].get(OP_LINE, []), key=lambda e: e.start)
    gaps, t = [], lo
    for e in evs:
        if e.end <= t:
            continue
        if e.start > t:
            gaps.append((t, min(e.start, hi)))
        t = max(t, e.end)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + t)
        cover = [sp for sp in trace.spans
                 if sp.start <= mid < sp.end and sp.name != WINDOW_SPAN]
        name = min(cover, key=lambda sp: sp.dur).name if cover else "host:other"
        out.append((name, s, t))
    return out


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    """The device ops that took most time in the window, in seconds per
    device (summed over devices, divided by their number)."""
    tot: dict[str, float] = {}
    for plane in trace.devices:
        for e in trace.ops(plane):
            key = f"{e.module}:{e.name}" if e.module else e.name
            tot[key] = tot.get(key, 0.0) + e.dur
    n = max(1, len(trace.devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9 / n] for k, v in ranked]


def dump(trace_dir, out_path, per_line: int = 40) -> None:
    """Write what a raw trace holds (planes, lines, a few events with all
    their stats) to ``out_path``: for reading kernel names by hand."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "line": line.name, "n_events": len(evs),
                "names": sorted({e.name for e in evs})[:200],
                "sample": [{"name": e.name, "start_ns": e.start_ns,
                            "dur_ns": e.duration_ns,
                            "stats": {k: str(v)[:300] for k, v in e.stats}}
                           for e in evs[:per_line]],
            })
        out.append({"plane": plane.name, "lines": lines})
    Path(out_path).write_text(json.dumps(out, indent=1))


def host_events_during(trace_dir, intervals, limit: int = 400) -> list[dict]:
    """Every host event (any thread) that overlaps one of ``intervals``
    (start_ns, end_ns): what the host did while the device sat idle."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                for lo, hi in intervals:
                    if s < hi and t > lo and e.duration_ns > 0.02 * (hi - lo):
                        out.append({"thread": line.name, "name": e.name[:200],
                                    "start_ns": s, "dur_ns": e.duration_ns,
                                    "gap": [lo, hi]})
                        break
    out.sort(key=lambda r: -r["dur_ns"])
    return out[:limit]
