"""Splits a profile of one run's window by the program's own marks.

Scopes: the model traces each layer under a ``jax.named_scope``, so an
HLO instruction of a compiled program carries its layer in its
``op_name`` metadata ("jit(_decode)/while/body/closed_call/attn/dot").
The trace names an operation by its instruction ("%fusion.131 = f32[…]
fusion(…)") and carries no metadata, so the map from operation to scope
is read from the program's compiled text (``Compiled.as_text()``) and
joined on the instruction name within each run of that program on the
device's ``XLA Modules`` line. An operation's self time goes to the
outermost known scope on its ``op_name`` path, else to ``(unscoped)``.

Host spans: each stretch of an idle gap of the window goes to the
innermost of the given host spans (``BatchServer.serve``'s ``serve.*``)
on the window's thread that covers that stretch, so a gap that runs
from sampling through dispatch is shared out among their spans.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass

import trace_reduce

UNSCOPED = "(unscoped)"
NO_SPAN = "(no host event)"     # as trace_reduce names a gap outside all

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUN_ID = re.compile(r"\(\d+\)$")
_HEAD = 60      # characters of an instruction that tell programs apart


@dataclass
class ScopeTimes:
    # (program, scope, operation) -> self seconds over the window
    op_s: dict[tuple[str, str, str], float]
    runs: dict[str, int]          # program -> runs in the window
    module_s: dict[str, float]    # program -> seconds of its runs
    unknown: dict[str, int]       # program -> ops not in its compiled text

    def per_run_ms(self, part: str, scope: str) -> float | None:
        """Mean milliseconds a run of the programs whose name has
        ``part`` spends in ``scope``; None where none ran, or where no
        operation of theirs carries the scope."""
        runs = sum(n for prog, n in self.runs.items() if part in prog)
        secs = self.by_scope(part).get(scope)
        return 1e3 * secs / runs if runs and secs is not None else None

    def by_scope(self, part: str) -> dict[str, float]:
        """Self seconds of the programs whose name has ``part``, by
        scope."""
        out: dict[str, float] = defaultdict(float)
        for (prog, sc, _), s in self.op_s.items():
            if part in prog:
                out[sc] += s
        return dict(out)

    def top_ops(self, part: str, scope: str,
                n: int | None = 3) -> list[list]:
        ops: dict[str, float] = defaultdict(float)
        for (prog, sc, op), s in self.op_s.items():
            if part in prog and sc == scope:
                ops[op] += s
        return trace_reduce.top(ops, n)


def scope_of(op_name: str, scopes) -> str:
    """The outermost of ``scopes`` on the ``op_name`` path."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return UNSCOPED


def instructions(hlo_text: str, scopes) -> dict[str, tuple[str, str]]:
    """Instruction name -> (its text, its scope), over every computation
    of one compiled module (instruction names are unique in a module)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = (line[m.start(1):],
                               scope_of(op.group(1), scopes) if op
                               else UNSCOPED)
    return out


def _same(a: str, b: str) -> bool:
    """Whether two texts of one instruction agree in name, shape and
    opcode (the trace's text leaves out what follows the operands)."""
    a, b = trace_reduce.op_name(a), trace_reduce.op_name(b)
    n = min(len(a), len(b), _HEAD)
    return a[:n] == b[:n]


def _program_of(ops, candidates):
    """Of the compiled modules named like the run, the one whose
    instructions read as the run's operations (one module per program
    shape; a name alone does not tell the shapes apart)."""
    if len(candidates) == 1:
        return candidates[0]
    return max(candidates, key=lambda ins: sum(
        _same(ins[k][0], name) for name in ops
        if (k := name.split(" ", 1)[0]) in ins))


def attribute(profile, hlo_texts: list[str], scopes) -> ScopeTimes:
    """Self time of each operation run inside the window, by program and
    scope; ``hlo_texts`` are the compiled texts of the programs to split
    (a program without one counts as ``(unscoped)``)."""
    t0, t1, _ = trace_reduce._window(profile)
    modules = defaultdict(list)
    for text in hlo_texts:
        modules[_MODULE.search(text).group(1)].append(
            instructions(text, scopes))
    op_s: dict[tuple[str, str, str], float] = defaultdict(float)
    runs: dict[str, int] = defaultdict(int)
    module_s: dict[str, float] = defaultdict(float)
    unknown: dict[str, int] = defaultdict(int)
    for plane in profile.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.OPS_LINE not in lines or \
                trace_reduce.MODULES_LINE not in lines:
            continue
        ops = sorted(lines[trace_reduce.OPS_LINE].events,
                     key=lambda e: e.start_ns)
        starts = [e.start_ns for e in ops]
        for run in lines[trace_reduce.MODULES_LINE].events:
            a, b = run.start_ns, run.start_ns + run.duration_ns
            if a < t0 or b > t1:
                continue
            prog = _RUN_ID.sub("", run.name)
            inside = ops[bisect.bisect_left(starts, a):
                         bisect.bisect_left(starts, b)]
            own = trace_reduce._self_times(inside)
            ins = (_program_of([n for n, _ in own], modules[prog])
                   if modules.get(prog) else None)
            runs[prog] += 1
            module_s[prog] += run.duration_ns / 1e9
            for name, secs in own:
                key = name.split(" ", 1)[0]
                if ins is None:
                    scope = UNSCOPED
                elif key in ins:
                    scope = ins[key][1]
                else:
                    scope = UNSCOPED
                    unknown[prog] += 1
                op_s[prog, scope, trace_reduce.op_name(name)] += secs
    return ScopeTimes(op_s=dict(op_s), runs=dict(runs),
                      module_s=dict(module_s), unknown=dict(unknown))


def _pieces(spans, t0: int, t1: int) -> list[tuple[int, int, str]]:
    """[t0, t1) cut where a span starts or ends, each piece named by the
    innermost span covering it (spans of one thread nest)."""
    out, stack, t = [], [], t0

    def emit(upto):
        nonlocal t
        upto = min(upto, t1)
        if upto > t:
            out.append((t, upto, stack[-1][2] if stack else NO_SPAN))
            t = upto

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((s, e, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(t1)
    return out


def host_gaps(profile, spans) -> dict[str, float]:
    """Idle seconds of the window's first device, by the innermost of
    ``spans`` on the window's thread covering each stretch of a gap."""
    t0, t1, host = trace_reduce._window(profile)
    for plane in profile.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX) and \
                trace_reduce.OPS_LINE in lines:
            busy = trace_reduce._union(
                [(int(e.start_ns), min(int(e.start_ns + e.duration_ns), t1))
                 for e in lines[trace_reduce.OPS_LINE].events
                 if t0 <= e.start_ns < t1])
            break
    else:
        raise ValueError("the trace holds no device operation")
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    pieces = _pieces([(int(e.start_ns), int(e.start_ns + e.duration_ns),
                       e.name) for e in host if e.name in spans], t0, t1)
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in idle:
        while pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            out[name] += (min(b, e) - max(a, s)) / 1e9
            k += 1
    return dict(out)
