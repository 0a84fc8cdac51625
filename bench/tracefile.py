"""Profiler capture and the reduction from a device trace to numbers.

A ``--trace 1`` run records the measured window with ``jax.profiler`` and
reduces the ``.xplane.pb`` file here, with nothing but JAX's own reader:

* device busy time: the union of the intervals in which an operation ran
  on a device (line ``XLA Ops`` of each ``/device:TPU:<i>`` plane), clipped
  to the window, so nested or overlapping events are counted once;
* device time under a named scope: the same union over the operations
  whose op metadata names the scope (the program's ``jax.named_scope``
  labels, e.g. ``stage/final/`` or ``kernel/round/``). A TPU trace names
  each operation by its HLO instruction only; the op metadata comes from
  the HLO protos the profiler stores in the ``/host:metadata`` plane, which
  ``ProfileData`` does not expose, so ``hlo_scopes`` reads them from the
  file's protobuf wire format. An operation belongs to the program
  (``XLA Modules`` line) whose execution encloses it;
* rounds: executions of the loop bodies that hold a scope's operations;
* the top operations, and the longest idle gaps with the host span (the
  benchmark's and the engine's, see ``AnnotatingTracer``) that was open
  when the device went idle.

The window is the host span ``bench/window`` that the harness opens around
the measured traffic.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench/window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    start: int  # ns
    end: int    # ns
    name: str
    scope: str  # the op metadata's name stack ('' where none)
    body: str = ""  # program and HLO computation the instruction sits in


@dataclasses.dataclass
class Trace:
    """One traced window: per-device operation events and host spans."""

    devices: list[list[Event]]
    host: list[Event]
    window: tuple[int, int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, events):
        lo, hi = self.window
        return [(max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(union_ns(self._clip(ev)) for ev in self.devices) \
            * 1e-9 / len(self.devices)

    def scope_s(self, prefixes, reduce=max) -> float:
        """Seconds of device time under any of the named-scope
        ``prefixes``; ``reduce`` folds the per-device values (``max``: the
        slowest device)."""
        per = [union_ns(self._clip([e for e in ev
                                    if in_scope(e, prefixes)])) * 1e-9
               for ev in self.devices]
        return reduce(per) if per else 0.0

    def iterations(self, prefixes, device: int = 0) -> int:
        """Executions of the loop bodies that hold the scope's operations
        (a forest round): every instruction of a loop body runs once per
        iteration, so each HLO computation that holds operations under the
        scope counts its events over its distinct instructions. Assumes no
        loop nested inside the scope (the XLA round has none)."""
        events: dict[str, int] = {}
        names: dict[str, set] = {}
        for e in self.devices[device]:
            if self.window[0] <= e.start < self.window[1] \
                    and in_scope(e, prefixes):
                events[e.body] = events.get(e.body, 0) + 1
                names.setdefault(e.body, set()).add(e.name)
        return round(sum(events[b] / len(names[b]) for b in events))

    def top_ops(self, k: int = 10) -> list[list]:
        """The operations that took most device time (seconds summed over
        their executions, averaged over the devices)."""
        lo, hi = self.window
        tot: dict[str, float] = {}
        for ev in self.devices:
            for e in ev:
                if e.end > lo and e.start < hi:
                    key = op_label(e)
                    tot[key] = tot.get(key, 0.0) + (
                        min(e.end, hi) - max(e.start, lo)) * 1e-9
        n = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s / n] for name, s in top]

    def idle_gaps(self, k: int = 10, device: int = 0) -> list[list]:
        """The ``k`` longest idle gaps of a device in the window, each named
        by the innermost host span open at its middle."""
        lo, hi = self.window
        busy = merge_intervals(self._clip(self.devices[device]))
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            open_spans = [h for h in self.host
                          if h.start <= mid < h.end and h.name != WINDOW_SPAN]
            name = (min(open_spans, key=lambda h: h.end - h.start).name
                    if open_spans else "host/unannotated")
            out.append([name, (e - s) * 1e-9])
        return out


def in_scope(e: Event, prefixes) -> bool:
    return any(p in e.scope for p in prefixes)


def op_label(e: Event) -> str:
    """An operation's HLO name with the named scope it ran under, where
    the op metadata gives one."""
    m = re.search(r"((?:stage|kernel|merge)/[\w./-]*)", e.scope)
    return f"{m.group(1)[:100]}:{e.name}" if m else e.name


def merge_intervals(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(iv) -> int:
    return sum(e - s for s, e in merge_intervals(iv))


@contextlib.contextmanager
def capture(directory: Path):
    """Record a profiler trace of the block into ``directory``."""
    import jax

    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _wire(b: bytes):
    """(field, wire type, value) of each field of one protobuf message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, t = key >> 3, key & 7
        if t == 0:
            v, i = _varint(b, i)
        elif t == 1:
            v, i = b[i:i + 8], i + 8
        elif t == 5:
            v, i = b[i:i + 4], i + 4
        elif t == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {t} is not supported")
        yield f, t, v


def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _first(msg: bytes, field: int, default=b""):
    for f, _, v in _wire(msg):
        if f == field:
            return v
    return default


# field numbers: tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_MD, _XPLANE_STAT_MD = 2, 4, 5
_XEVENT_MD_NAME, _XEVENT_MD_STATS = 2, 5
_XSTAT_MD_ID, _XSTAT_MD_NAME = 1, 2
_XSTAT_MD, _XSTAT_BYTES = 1, 6
_HLO_PROTO_MODULE, _MODULE_COMPUTATIONS = 1, 3
_COMPUTATION_NAME, _COMPUTATION_INSTRUCTIONS = 1, 2
_INSTR_NAME, _INSTR_METADATA, _OPMETA_OP_NAME = 1, 7, 2


def hlo_scopes(xspace: bytes) -> dict[str, dict[str, tuple[str, str]]]:
    """{program name: {HLO instruction name: (op_name, computation)}} from
    the HLO protos of the ``/host:metadata`` plane. The op_name is the
    jaxpr's name stack, which carries the ``jax.named_scope`` labels."""
    out: dict[str, dict[str, tuple[str, str]]] = {}
    for f, _, plane in _wire(xspace):
        if f != _XSPACE_PLANES or _first(plane, _XPLANE_NAME) \
                != b"/host:metadata":
            continue
        stat_names = {}
        for f2, _, entry in _wire(plane):
            if f2 == _XPLANE_STAT_MD:
                md = _first(entry, 2)
                stat_names[_first(md, _XSTAT_MD_ID, 0)] = \
                    _first(md, _XSTAT_MD_NAME).decode()
        for f2, _, entry in _wire(plane):
            if f2 != _XPLANE_EVENT_MD:
                continue
            md = _first(entry, 2)
            program = _first(md, _XEVENT_MD_NAME).decode()
            for f3, _, stat in _wire(md):
                if f3 != _XEVENT_MD_STATS or stat_names.get(
                        _first(stat, _XSTAT_MD, 0)) != "Hlo Proto":
                    continue
                module = _first(_first(stat, _XSTAT_BYTES), _HLO_PROTO_MODULE)
                names = out.setdefault(program, {})
                for f4, _, comp in _wire(module):
                    if f4 != _MODULE_COMPUTATIONS:
                        continue
                    comp_name = _first(comp, _COMPUTATION_NAME).decode()
                    for f5, _, ins in _wire(comp):
                        if f5 != _COMPUTATION_INSTRUCTIONS:
                            continue
                        meta = _first(ins, _INSTR_METADATA)
                        op_name = _first(meta, _OPMETA_OP_NAME) if meta \
                            else b""
                        names[_first(ins, _INSTR_NAME).decode()] = (
                            op_name.decode(), comp_name)
    return out


_INSTR = re.compile(r"^%?([\w.\-]+)")


def read(directory: Path) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    raw = files[-1].read_bytes()
    scopes = hlo_scopes(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            programs = sorted((int(e.start_ns), int(e.end_ns), e.name)
                              for e in lines.get(_MODULES_LINE, []))
            starts = [p[0] for p in programs]
            evs = devices.setdefault(int(m.group(1)), [])
            for ev in lines.get(_OPS_LINE, []):
                s = int(ev.start_ns)
                k = bisect.bisect_right(starts, s) - 1
                prog = programs[k][2] if k >= 0 and s < programs[k][1] \
                    else ""
                im = _INSTR.match(ev.name)
                instr = im.group(1) if im else ev.name
                op_name, comp = scopes.get(prog, {}).get(instr, ("", ""))
                evs.append(Event(s, s + int(ev.duration_ns), instr, op_name,
                                 f"{prog}/{comp}"))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if "/" in ev.name:
                        s = int(ev.start_ns)
                        host.append(Event(s, s + int(ev.duration_ns),
                                          ev.name, ""))
    windows = [h for h in host if h.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    w = max(windows, key=lambda h: h.end - h.start)
    return Trace([devices[i] for i in sorted(devices)], host,
                 (w.start, w.end))
