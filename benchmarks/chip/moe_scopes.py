"""Device time under the expert layer's own scopes, and what its grouped
matmuls need.

The program names three scopes inside ``moe`` (``models/moe.py``):
``moe_route`` (router, top-k, the sort by expert, group sizes and the
gather of the sorted rows), ``moe_experts`` (the gate, up and down
projections, one grouped matmul each) and ``moe_combine`` (back to token
order and the gated sum).  ``scopes.py`` counts them all as ``moe``; here
an operation takes the innermost of ``SUBSCOPES`` in its ``op_name``, and
otherwise its scope as ``scopes.py`` gives it, with two exceptions that a
TPU's compiled program needs:

- The compiler makes each ``ragged_dot`` into Mosaic custom calls that it
  names ``ragged-dot-*``, and gives them that name alone as their
  ``op_name``: no scope survives.  The program's only ``ragged_dot`` is the
  expert layer's, so these are ``moe_experts``.
- The grouped matmuls read each layer's expert weights from a copy that
  the compiler makes of the scan's slice of the stacked weights (scope
  ``layers``; on a TPU, a copy into the fast on-chip memory that the
  kernels read).  That copy is the layer's read of its expert weights from
  HBM.  An operation of ``layers`` whose result has the shape of one
  layer's expert weights is ``moe_weights``.

Self time as in ``scopes.py``: each instant goes to the innermost
operation running.

The counts are what the algorithm needs, as in ``counts.py``: the FLOPs of
the B*S*k routed rows through one expert each, and the bytes of the
distinct experts that a decode step's tokens touch, read once.
"""

from __future__ import annotations

import functools
import json
import re
import sys

from benchmarks.chip import counts
from benchmarks.chip import scopes

SUBSCOPES = ("moe_route", "moe_experts", "moe_combine")
GROUPED = "ragged-dot"          # the compiler's name for its kernels
WEIGHTS = "moe_weights"
# what the grouped matmuls take: the kernels, and the copies they read
EXPERTS = ("moe_experts", WEIGHTS)
# what moe_route_ms reads: the expert layer's time outside its matmuls
ROUTE = ("moe", "moe_route", "moe_combine")
_SHAPE = re.compile(r" = \w+\[([\d,]*)\]")


def label(op_name: str) -> str:
    """The innermost of ``SUBSCOPES`` in an ``op_name``, else its scope;
    ``moe_experts`` for the compiler's grouped-matmul kernels."""
    if op_name.startswith(GROUPED):
        return "moe_experts"
    for name in op_name.split(";"):
        for component in reversed(name.split("/")):
            if component in SUBSCOPES:
                return component
    return scopes.scope_of(op_name)


def weight_shapes(d: counts.Dims) -> tuple[tuple[int, ...], ...]:
    """One layer's expert weights: (E, D, F) for gate and up, (E, F, D)
    for down."""
    return ((d.experts, d.d_model, d.d_ff), (d.experts, d.d_ff, d.d_model))


@functools.lru_cache(maxsize=4)
def op_labels(hlo_text: str, weights=()) -> dict[str, str]:
    """Each instruction of a compiled program's text, by name, with its
    label; a fusion without metadata takes that of its ROOT.  ``weights``
    are the shapes of one layer's expert weights (``weight_shapes``)."""
    op_name: dict[str, str] = {}
    copies: set[str] = set()
    calls: dict[str, str] = {}
    root: dict[str, str] = {}
    computation = None
    for line in hlo_text.split("\n"):
        if line and not line[0].isspace() and line.endswith("{"):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = scopes._INSTR.match(line)
        if m is None:
            continue
        name = m.group(2)
        op_name[name] = ""
        shape = _SHAPE.search(line)
        if shape and tuple(int(n) for n in shape.group(1).split(",")
                           if n) in weights:
            copies.add(name)
        if m.group(1) and computation:
            root[computation] = name
        meta = scopes._OP_NAME.search(line)
        if meta:
            op_name[name] = meta.group(1)
        called = scopes._CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def name_of(instr: str) -> str:
        if op_name.get(instr) or instr not in calls:
            return op_name.get(instr, "")
        comp = calls[instr]
        return name_of(root[comp]) if comp in root else ""

    out = {n: label(name_of(n)) for n in op_name}
    out.update({n: WEIGHTS for n in copies if out[n] == "layers"})
    return out


def label_times(summary, module: str, hlo_text: str,
                weights=()) -> dict[str, float]:
    """Seconds of self time per label in the program ``module``'s
    executions in the window, per device."""
    by_op = op_labels(hlo_text, weights)
    planes = scopes.module_events(summary, module)
    out: dict[str, float] = {}
    for evs in planes:
        for e, ns in zip(evs, scopes.self_ns(evs)):
            key = by_op.get(e.op, scopes.UNSCOPED)
            out[key] = out.get(key, 0.0) + ns / 1e9 / max(1, len(planes))
    return out


def _program(ctx, program: str):
    if program == "decode":
        return ctx.decode_module, scopes.decode_hlo(ctx), ctx.decode_steps
    return ctx.prefill_module, ctx.prefill_hlo, ctx.prefills


def seconds(ctx, program: str, names) -> float | None:
    """Seconds of self time under the labels ``names`` in all executions
    of ``program`` ("prefill" or "decode") in the window.  None where the
    executions in the trace are not the run's, where the program names
    none of ``SUBSCOPES`` (one older than them), or where these hold
    nothing."""
    module, text, calls = _program(ctx, program)
    _, n = ctx.summary.module(module)
    if text is None or n == 0 or n != len(calls):
        return None
    times = label_times(ctx.summary, module, text, weight_shapes(ctx.dims))
    if not set(SUBSCOPES) & set(times):
        print(f"diagnostic moe_scopes {module} names none of {SUBSCOPES}",
              file=sys.stderr, flush=True)
        return None
    total = sum(times.get(s, 0.0) for s in names)
    return total if total > 0 else None


def diagnose(ctx) -> None:
    """``scopes.diagnose``'s lines, and one ``diagnostic moe_scopes`` line
    per program: seconds per label in the window."""
    scopes.diagnose(ctx)
    for program in ("prefill", "decode"):
        module, text, _ = _program(ctx, program)
        if text is None:
            continue
        times = label_times(ctx.summary, module, text,
                            weight_shapes(ctx.dims))
        print(f"diagnostic moe_scopes {module} "
              f"{json.dumps(times, sort_keys=True)}",
              file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# What the grouped matmuls need.
# ---------------------------------------------------------------------------


def expert_params(d: counts.Dims) -> int:
    """One expert's weights: gate, up and down, D x F each."""
    return (3 if d.gated else 2) * d.d_model * d.d_ff


def expert_flops(d: counts.Dims, batch: int, seq: int) -> int:
    """The grouped matmuls of one pass over ``batch`` x ``seq`` tokens, every
    layer: each of the B*S*k routed rows through one expert."""
    return d.layers * 2 * batch * seq * d.top_k * expert_params(d)


def expert_bytes(d: counts.Dims, batch: int) -> float:
    """Expert weights that one decode step of ``batch`` tokens reads, every
    layer: the distinct experts its tokens touch (``counts.
    expected_experts``), once each."""
    return (d.layers * counts.expected_experts(d, batch) * expert_params(d)
            * d.itemsize)
