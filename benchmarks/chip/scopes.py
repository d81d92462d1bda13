"""Device time by the program's named scopes (``jax.named_scope``).

The program names its layers' device work with scopes (``embed``,
``layers``, ``attn_core``, ...).  The compiled program's HLO text gives
each operation the scopes it ran under, in its ``op_name`` metadata
(``jit(decode)/layers/while/body/attn_core/dot_general``); a fusion
without metadata takes that of its fused computation's ROOT.  An
operation's scope is the innermost of ``SCOPES`` in its ``op_name``; one
under none of them is ``unscoped``.

The trace's operations nest: a ``while`` runs its body's operations inside
its own interval.  So each operation counts its self time: each instant of
a program's execution goes to the innermost operation running then (the
one that started last).  A program's self times add up to the union of its
operations' intervals, its busy time.  The operations are the summary's,
already on the host's clock.

``diagnose`` prints each program's seconds per scope to standard error.
"""

from __future__ import annotations

import functools
import json
import re
import sys

from benchmarks.chip import trace as tr

# The scopes the metrics know, as the program names them.  A scope the
# program adds later counts as the known scope around it.
SCOPES = ("embed", "layers", "norm", "attn_qkv", "kv_write", "attn_core",
          "attn_out", "mlp", "moe", "logits")
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The innermost known scope of an ``op_name``; of several names joined
    by ``;`` (an operation merged from several), the first that has one."""
    for name in op_name.split(";"):
        for component in reversed(name.split("/")):
            if component in SCOPES:
                return component
    return UNSCOPED


@functools.lru_cache(maxsize=4)
def op_scopes(hlo_text: str) -> dict[str, str]:
    """Each instruction of a compiled program's HLO text, by name, with its
    scope."""
    names: list[str] = []
    op_name: dict[str, str] = {}
    calls: dict[str, str] = {}
    root: dict[str, str] = {}          # computation -> its ROOT instruction
    computation = None
    for line in hlo_text.split("\n"):
        if line and not line[0].isspace() and line.endswith("{"):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(2)
        names.append(name)
        if m.group(1) and computation:
            root[computation] = name
        meta = _OP_NAME.search(line)
        if meta:
            op_name[name] = meta.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def name_of(instr: str) -> str:
        if instr in op_name:
            return op_name[instr]
        comp = calls.get(instr)
        return name_of(root[comp]) if comp in root else ""

    return {n: scope_of(name_of(n)) for n in names}


def self_ns(events: list[tr.Event]) -> list[float]:
    """Each event's self time: the instants at which it is the innermost
    event running, the one that started last (the longer first, of two that
    start together).  The self times add up to the events' union."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].end_ns))
    out = [0.0] * len(events)
    stack: list[int] = []           # events running, innermost last
    t = float("-inf")
    for i in [*order, None]:
        to = float("inf") if i is None else events[i].start_ns
        while stack and t < to:
            end = events[stack[-1]].end_ns
            if end > t:
                out[stack[-1]] += min(end, to) - t
                t = min(end, to)
            if end <= t:
                stack.pop()
        t = to
        if i is not None:
            stack.append(i)
    return out


def module_events(summary: tr.Summary, module: str):
    """The module's operations in the window, per device plane."""
    return [[e for m, e in plane if m == module]
            for plane in summary.ops.values()]


def scope_times(summary: tr.Summary, module: str,
                hlo_text: str) -> dict[str, float]:
    """Seconds of self time per scope in the program ``module``'s
    executions in the window, per device.  ``hlo_text`` is the compiled
    program's text; an operation it does not name is ``unscoped``."""
    by_op = op_scopes(hlo_text)
    planes = module_events(summary, module)
    out: dict[str, float] = {}
    for evs in planes:
        for e, ns in zip(evs, self_ns(evs)):
            scope = by_op.get(e.op, UNSCOPED)
            out[scope] = out.get(scope, 0.0) + ns / 1e9 / max(1, len(planes))
    return out


def busy_s(summary: tr.Summary, module: str) -> float:
    """The union of the module's operation intervals, per device."""
    planes = module_events(summary, module)
    return sum(tr.covered_ns(evs, float("-inf"), float("inf"))
               for evs in planes) / 1e9 / max(1, len(planes))


# ---------------------------------------------------------------------------
# What the readers read.
# ---------------------------------------------------------------------------


def decode_hlo(ctx) -> str | None:
    """The compiled decode step's text: the context's ``decode_hlo`` where
    it has one, else the text of the session that the run reading this
    context holds, found on the call stack by its ``decode_module``."""
    text = getattr(ctx, "decode_hlo", None)
    if text:
        return text
    frame = sys._getframe(1)
    while frame is not None:
        for value in frame.f_locals.values():
            if (type(value).__name__ == "Session"
                    and getattr(value, "decode_module", None)
                    == ctx.decode_module):
                return value.decode.as_text()
        frame = frame.f_back
    return None


def _program(ctx, program: str):
    """(module, compiled text, executions the run made) of ``program``,
    "prefill" or "decode"."""
    if program == "decode":
        return ctx.decode_module, decode_hlo(ctx), ctx.decode_steps
    return ctx.prefill_module, ctx.prefill_hlo, ctx.prefills


def diagnose(ctx) -> None:
    """Print one ``diagnostic scopes`` line per program to standard error:
    its executions and their device time, its busy time, the sum of its
    self times, and seconds per scope."""
    for program in ("prefill", "decode"):
        module, text, _ = _program(ctx, program)
        if text is None:
            continue
        times = scope_times(ctx.summary, module, text)
        seconds, n = ctx.summary.module(module)
        print(f"diagnostic scopes {module} executions {n} module_s "
              f"{seconds!r} busy_s {busy_s(ctx.summary, module)!r} sum_s "
              f"{sum(times.values())!r} {json.dumps(times, sort_keys=True)}",
              file=sys.stderr, flush=True)


def per_call_ms(ctx, program: str, names) -> float | None:
    """Milliseconds of self time under the scopes ``names`` per execution of
    ``program`` ("prefill" or "decode").  None where the executions in the
    trace are not the run's, or where the program names its scopes and
    these hold nothing; 0.0 for a program that names none of ``SCOPES``
    (one older than them), since a reader that finds nothing fails the run."""
    module, text, calls = _program(ctx, program)
    _, n = ctx.summary.module(module)
    if text is None or n == 0 or n != len(calls):
        return None
    times = scope_times(ctx.summary, module, text)
    if set(times) <= {UNSCOPED}:
        print(f"diagnostic scopes {module} names none of the scopes",
              file=sys.stderr, flush=True)
        return 0.0
    seconds = sum(times.get(s, 0.0) for s in names)
    return 1e3 * seconds / n if seconds > 0 else None
