"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU its device planes are named ``/device:TPU:<n>``.  On each, the
line ``XLA Modules`` has one event per program execution, named
``<module>(<program id>)``, and the line ``XLA Ops`` one event per
operation, named by the operation's HLO text, ``%<op> = <shape> ...``.  A
Pallas kernel is a ``tpu_custom_call`` operation; which one it is shows
only in the compiled program (see ``mosaic_ops``).  The host plane
``/host:CPU`` holds the benchmark's ``TraceAnnotation`` spans, all named
``bench.*``: ``bench.window`` brackets the measured window, and
``bench.prefill``, ``bench.decode`` and ``bench.argmax`` each dispatch one
program.

Times are nanoseconds.  The device's clock in the trace runs ahead of the
host's by a fraction of a millisecond, so device times are moved onto the
host's clock first: by the least shift that has no program start before
the span that dispatched it.  Busy time is then the union of the intervals
in which an operation ran, clipped to the window; idle is the rest.
"""

from __future__ import annotations

import base64
import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DISPATCH_SPANS = ("bench.prefill", "bench.decode", "bench.argmax")
NO_SPAN = "host:outside-bench-spans"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """An operation's HLO name: ``fusion.3`` of ``%fusion.3 = ...``."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def module(self) -> str:
        """A program's module name: ``jit_f`` of ``jit_f(123)``."""
        return self.name.split("(", 1)[0]

    def shifted(self, ns: float) -> "Event":
        return Event(self.name, self.start_ns + ns, self.dur_ns)


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]        # device plane -> operations
    modules: dict[str, list[Event]]    # device plane -> program executions
    spans: list[Event]                 # the benchmark's host spans


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files under {log_dir}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = sorted((Event(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events),
                                 key=lambda e: e.start_ns)
                    (ops if line.name == OPS_LINE else modules)[
                        plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, sorted(spans, key=lambda e: e.start_ns))


def mosaic_ops(hlo_text: str, kernel: str) -> set[str]:
    """HLO names of the ``tpu_custom_call`` operations in a compiled
    program's text whose Mosaic body holds the kernel function ``kernel``."""
    names = set()
    for line in hlo_text.split("\n"):
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'\\?"body\\?":\\?"([A-Za-z0-9+/=]+)', line)
        if body is None:
            continue
        raw = body.group(1)
        if kernel.encode() in base64.b64decode(raw + "=" * (-len(raw) % 4)):
            names.add(line.split("=", 1)[0].strip().lstrip("%").split()[-1])
    return names


# ---------------------------------------------------------------------------
# Interval arithmetic.
# ---------------------------------------------------------------------------


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the events' intervals, clipped to [lo, hi], in order."""
    out: list[list[float]] = []
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no event covers."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def covered_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def within(events, lo: float, hi: float) -> list[Event]:
    """Events that start inside [lo, hi]."""
    return [e for e in events if lo <= e.start_ns <= hi]


def clock_shift(modules: list[Event], spans: list[Event]) -> float:
    """Nanoseconds to add to device times: the least shift with no program
    starting before the host span that dispatched it.  The k-th dispatch
    span dispatched the k-th program, so the two counts have to agree."""
    dispatch = [s for s in spans if s.name in DISPATCH_SPANS]
    if not dispatch or len(dispatch) != len(modules):
        raise ValueError(f"{len(dispatch)} dispatch spans but "
                         f"{len(modules)} program executions: the device "
                         f"clock cannot be placed on the host's")
    return max(0.0, max(s.start_ns - m.start_ns
                        for s, m in zip(dispatch, modules)))


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # averaged over the devices
    shift_ns: float                        # device clock moved by this
    modules: dict[str, list[Event]]        # per device, in the window
    ops: dict[str, list[tuple[str, Event]]]  # per device, in the window,
                                             # with each one's module
    device_ops: list[tuple[str, float]]    # top operations, seconds
    idle_gaps: list[tuple[str, float]]     # longest idle gaps, seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, name: str) -> tuple[float, int]:
        """(seconds, executions) of the program whose module is ``name``,
        per device."""
        evs = [e for plane in self.modules.values() for e in plane
               if e.module == name]
        ndev = max(1, len(self.modules))
        return sum(e.dur_ns for e in evs) / 1e9 / ndev, len(evs) // ndev

    def kernel(self, module: str, ops: set[str]) -> tuple[float, int]:
        """(seconds, events) of the operations named in ``ops`` inside
        executions of the program ``module``, per device."""
        evs = [e for plane in self.ops.values() for m, e in plane
               if m == module and e.op in ops]
        ndev = max(1, len(self.ops))
        return sum(e.dur_ns for e in evs) / 1e9 / ndev, len(evs) // ndev


def _span_at(spans: list[Event], t: float) -> str:
    """The innermost benchmark span (other than the window) covering t."""
    best = None
    for s in spans:
        if s.name != WINDOW_SPAN and s.start_ns <= t <= s.end_ns:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best else NO_SPAN


def _placed(ops: list[Event], modules: list[Event]):
    """Each operation with its module: the program execution that covers
    its start ("?" where none does)."""
    starts = [m.start_ns for m in modules]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        inside = i >= 0 and e.start_ns <= modules[i].end_ns
        out.append((modules[i].module if inside else "?", e))
    return out


def summarize(trace: Trace, top: int = 10) -> Summary:
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    if not trace.ops:
        raise ValueError("no device operations in the trace")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    first = sorted(trace.ops)[0]
    shift = clock_shift(trace.modules.get(first, []), trace.spans)
    ops = {p: [e.shifted(shift) for e in evs] for p, evs in trace.ops.items()}
    mods = {p: within([e.shifted(shift) for e in evs], lo, hi)
            for p, evs in trace.modules.items()}
    busy = [covered_ns(evs, lo, hi) for evs in ops.values()]
    placed = {p: _placed(within(evs, lo, hi), mods.get(p, []))
              for p, evs in ops.items()}

    totals: dict[str, float] = {}
    for plane in placed.values():
        for m, e in plane:
            key = f"{m}/{e.op}"
            totals[key] = totals.get(key, 0.0) + e.dur_ns / 1e9 / len(placed)
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    idle = sorted(gaps(ops[first], lo, hi), key=lambda g: g[0] - g[1])
    idle_gaps = [(_span_at(trace.spans, (s + e) / 2), (e - s) / 1e9)
                 for s, e in idle[:top]]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9, shift_ns=shift,
                   modules=mods, ops=placed,
                   device_ops=device_ops, idle_gaps=idle_gaps)
