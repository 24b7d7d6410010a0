"""The profiler's module: the step-range trigger, and the map from a
compiled program's instructions to the parts of the model.

**The trigger** traces a WINDOW instead of the whole run.

``profile_trace`` (utils/profiling.py) wraps the entire run — fine for a
smoke run, useless for "steady-state steps 10..12 of a 10-hour job" where
a whole-run trace is gigabytes of mostly-identical timelines.  Here the
``jax.profiler`` trace is armed by the run-local step counter: the CLI's
``--trace-steps A:B`` (python slice semantics: first traced step A,
first untraced step B) starts the trace when step A begins and stops it
when step B begins, so the artifact holds exactly ``B - A`` steps.

**The map.**  The device trace names an op event by its HLO text
(``%fusion.36 = bf16[16,64,2048]{...} fusion(...)``) and carries no
``jax.named_scope``; the compiled program's own text does, on every
instruction: ``metadata={op_name="jit(decode)/attn.core/dot_general"}``.
The name before the ``=`` is the one the event carries, so ``scope_map``
of ``compiled.as_text()`` joins the two, and ``part_of`` reads the part of
the model (``models/lm_blocks.py::PARTS``) out of an ``op_name``.  A fusion
carries its ROOT's ``op_name``: an op fused across a scope's edge is
counted with the part its root belongs to.  ``program_scopes`` is what an
engine records of one program (``LMEngine``, when a tracer is active: the
span ``program.scopes``); ``tools/trace_export.py`` and the benchmark's
``harness/program_scopes.py`` read it.
"""

from __future__ import annotations

import re
import time
from collections.abc import Mapping
from typing import Dict, Optional, Tuple

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RESULT = re.compile(r"(\w+\[[\d,]*\])")   # right after the ``name = ``
# computations that run INSIDE the instruction that names them
_INLINED = {"fusion": re.compile(r"\bcalls=%?([\w.\-]+)"),
            "custom-call": re.compile(r"called_computations=\{([^}]*)\}")}
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
# a loop: the instruction, and the two computations it runs
_LOOP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*? while\([^\n]*?"
                   r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", re.M)
# instructions that never run as a device op of their own
_NO_OP = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                    "bitcast"})


def _instructions(hlo_text: str):
    """-> ([(computation, name, opcode, op_name, operand names, the
    result's type ``bf16[4,8]`` or "" for a tuple)] in the text's order, the
    computations that run inside an instruction)."""
    inlined, rows, current = set(), [], None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            if line.rstrip().endswith("{") and "->" in line:
                current = line.split("(", 1)[0].split()[-1].lstrip("%")
            continue
        opcode = _OPCODE.search(line, m.end())
        operands = ()
        if opcode:   # operands hold no parenthesis: up to the first ")"
            operands = _OPERAND.findall(
                line, opcode.end(), max(line.find(")", opcode.end()), 0))
        opcode = opcode.group(1) if opcode else ""
        called = _INLINED.get(opcode, _TO_APPLY if opcode != "call" else None)
        if called is not None:
            for c in called.finditer(line):
                inlined.update(n.strip().lstrip("%")
                               for n in c.group(1).split(","))
        name = _OP_NAME.search(line)
        made = _RESULT.match(line, m.end())
        rows.append((current, m.group(1), opcode,
                     name.group(1) if name else "", operands,
                     made.group(1) if made else ""))
    return rows, inlined


def instruction_of(event_name: str) -> str:
    """A device op event's name, which is its HLO text (``%fusion.4 =
    bf16[16,64]{...} fusion(...)``), -> the instruction's (``fusion.4``)."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: ``op_name``} of a compiled module's text
    (``compiled.as_text()``), over every computation that RUNS: the entry,
    the bodies and conditions of ``while`` loops (a scan's ops are events of
    their own), the branches of conditionals, called computations.  Left
    out: what runs inside another instruction (a fusion's body, a reduce's
    or a sort's ``to_apply``) and what runs not at all (parameters,
    constants, tuples, bitcasts).  An instruction without metadata (a copy
    the compiler put in) maps to ``""``."""
    rows, inlined = _instructions(hlo_text)
    return {inst: op_name for comp, inst, opcode, op_name, _, _ in rows
            if comp not in inlined and opcode not in _NO_OP}


def part_of(op_name: str, parts) -> Optional[str]:
    """The INNERMOST name of ``parts`` on the path ``op_name``
    (``jit(decode)/attn.core/jit(_where)/select_n`` -> ``attn.core``), or
    None where the path names none.  ``parts``: the names, or a mapping
    {name on a path: the part it stands for} (``LMPrograms.parts``: the
    compiler's own names for what it rewrote stand for a part too)."""
    for scope in reversed(op_name.split("/")):
        if scope in parts:
            return parts[scope] if isinstance(parts, Mapping) else scope
    return None


def program_scopes(hlo_text: str, parts) -> dict:
    """What a ``program.scopes`` span says of one compiled program:
    ``parts`` {instruction name: its part, None where none is found} over
    ``scope_map``'s instructions, ``instructions`` how many those are,
    ``unscoped`` how many of them have no part, and ``inherited``: the
    instructions whose own ``op_name`` names no part (the compiler's copies
    and prefetches of an operand carry no metadata, or the argument's name)
    and that took the part of the FIRST op that uses their result, through
    tuples and bitcasts (the wait for an operand is charged to the op that
    needs it), or, where nothing uses it (the program's output), of the op
    that made their operand, or, where that has none either and they run
    in a loop's body or condition (the compiler's copies of a loop's carried
    state between memory spaces), of the ``while`` that runs them."""
    rows, inlined = _instructions(hlo_text)
    # tuples, bitcasts and the like have no part of their own: they hand on
    # their neighbours'
    own = {inst: None if opcode in _NO_OP else part_of(op_name, parts)
           for _, inst, opcode, op_name, _, _ in rows}
    users: Dict[str, list] = {}
    for _, inst, _, _, operands, _ in rows:
        for operand in operands:
            users.setdefault(operand, []).append(inst)

    def handed(order, neighbours, never) -> dict:
        """Each instruction's own part, else the first neighbour's that has
        one; ``order`` visits the neighbours first, so one pass hands a part
        along a whole chain of copies.  The two directions are kept apart:
        nothing passes from one user of a value to another."""
        got = dict(own)
        for _, inst, opcode, _, operands, _ in order:
            if got[inst] is None and opcode not in never:
                got[inst] = next((got[n] for n in neighbours(inst, operands)
                                  if got.get(n) is not None), None)
        return got

    # users come later in the text, producers earlier.  An argument or a
    # constant takes no part; a tuple hands on to its elements' producers
    # but not from them (which element a user reads is not looked at)
    down = handed(reversed(rows), lambda inst, _: users.get(inst, ()),
                  ("parameter", "constant"))
    up = handed(rows, lambda _, operands: operands,
                ("parameter", "constant", "tuple"))
    found = {inst: own[inst] or down[inst] or up[inst]
             for comp, inst, opcode, _, _, _ in rows
             if comp not in inlined and opcode not in _NO_OP}
    # callers come after their computations in the text: the outermost loop
    # first, so that a loop without a part of its own has its caller's
    ran_by = {comp: loop for loop, *ran in _LOOP.findall(hlo_text)
              for comp in ran}
    for comp, inst, _, _, _, _ in reversed(rows):
        if found.get(inst, "") is None and comp in ran_by:
            found[inst] = found.get(ran_by[comp])
    return {"parts": found, "instructions": len(found),
            "unscoped": sum(p is None for p in found.values()),
            "inherited": [i for i, p in found.items()
                          if p is not None and own[i] is None]}


def hlo_type(shape, dtype) -> str:
    """An array's type as a compiled program's text writes it:
    ``(64, 4, 1280, 128)``, ``bfloat16`` -> ``bf16[64,4,1280,128]``."""
    import numpy as np

    dt = np.dtype(dtype)
    head = ("bf16" if dt.name == "bfloat16" else "pred" if dt.kind == "b"
            else {"f": "f", "i": "s", "u": "u"}[dt.kind] + str(8 * dt.itemsize))
    return f"{head}[{','.join(str(int(n)) for n in shape)}]"


def cache_copies(hlo_text: str, leaves) -> int:
    """How many ``copy`` instructions of a compiled program's text, among
    those that run as ops of their own (``scope_map``'s), have the shape and
    dtype of one of ``leaves`` (the decoding cache's arrays, or their
    ``ShapeDtypeStruct``s).  A donated cache that is written in place has
    none; each one is a whole leaf moved into another layout, or back
    (PERF.md section 6, PR 37)."""
    types = {hlo_type(a.shape, a.dtype) for a in leaves}
    rows, inlined = _instructions(hlo_text)
    return sum(opcode == "copy" and comp not in inlined and made in types
               for comp, _, opcode, _, _, made in rows)


def operator_profile_options():
    """``ProfileOptions`` for an operator's trace (``--profile-dir``,
    ``--trace-steps``): the device alone, as the benchmark traces.  The
    host tracer records one "Transpose" event per chunk of the runtime's
    host-side layout change of a float32 batch, at its default level 2
    and at level 1 alike: 3,594,316 events and a 119 MB file for 4
    serving launches at level 1, ``predict_batch`` 5.7 times slower, 19 s
    to stop the trace (PERF.md section 6, PR 24).  The program's spans
    (``obs/spans.py``) are placed beside the device plane afterwards, by
    ``tools/trace_export.py --profile``, through the ``profile.window``
    span the trigger records."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts


def record_profile_window(spans, log_dir: str, start: float,
                          end: float) -> None:
    """One ``profile.window`` span (``perf_counter`` ends) for a trace
    that ran from ``start`` to ``end``: what lets the exporter put the
    host's spans on the device trace's clock."""
    if spans is not None:
        spans.emit(trace_id=spans.new_trace_id("profile"),
                   name="profile.window", start=start, end=end,
                   log_dir=str(log_dir))


def parse_trace_steps(spec: str) -> Optional[Tuple[int, int]]:
    """``"10:13"`` -> ``(10, 13)``; empty/None -> None.  Slice semantics:
    steps ``[10, 13)`` are traced.  Raises ValueError on malformed specs
    (argparse ``type=`` surfaces it as a usage error before any work)."""
    if not spec:
        return None
    try:
        lo_s, hi_s = spec.split(":")
        lo, hi = int(lo_s), int(hi_s)
    except ValueError:
        raise ValueError(
            f"--trace-steps wants START:STOP (e.g. 10:13), got {spec!r}")
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"--trace-steps window must satisfy 0 <= START < STOP, "
            f"got {spec!r}")
    return lo, hi


class StepTraceWindow:
    """Start/stop a ``jax.profiler`` trace on run-local step boundaries.

    ``on_step(step)`` is called once per step (step counts from 1, see
    ``Telemetry.step_tick``; the window is interpreted on the 0-based step
    ORDINAL, so ``--trace-steps 0:2`` traces the first two steps).  Safe to
    call after the window has passed — both branches are a pair of integer
    compares.  ``close()`` stops a still-open trace (a window extending
    past the last step must still flush its file).  ``spans``: the run's
    ``SpanTracer`` when it has one (``build_telemetry`` sets it); the
    window then records its own ``profile.window`` span."""

    def __init__(self, log_dir: str, start: int, stop: int,
                 *, profiler=None, spans=None):
        if not log_dir:
            raise ValueError("StepTraceWindow needs a log_dir "
                             "(pass --profile-dir with --trace-steps)")
        self.log_dir = log_dir
        self.start = int(start)
        self.stop = int(stop)
        self._active = False
        self._done = False
        self._profiler = profiler  # test seam; defaults to jax.profiler
        self.spans = spans
        self._t_start = 0.0

    def _jax_profiler(self):
        if self._profiler is None:
            import jax.profiler

            self._profiler = jax.profiler
        return self._profiler

    def on_step(self, step: int) -> None:
        ordinal = step - 1  # step_tick counts from 1
        if (not self._active and not self._done
                and self.start <= ordinal < self.stop):
            self._jax_profiler().start_trace(
                self.log_dir, profiler_options=operator_profile_options())
            self._t_start = time.perf_counter()
            self._active = True
        elif self._active and ordinal >= self.stop:
            self._stop_trace()

    def _stop_trace(self) -> None:
        t_stop = time.perf_counter()
        try:
            self._jax_profiler().stop_trace()
            record_profile_window(self.spans, self.log_dir, self._t_start,
                                  t_stop)
        finally:
            self._active = False
            self._done = True  # one window per run: never re-arm

    def close(self) -> None:
        if self._active:
            self._stop_trace()
