"""The program's own names for where its time goes.

* :func:`scope` names a phase of a device program.  It is a
  ``jax.named_scope``: it changes only the ``op_name`` metadata of the
  instructions traced under it, never the program.
* :func:`span` names a phase of host code.  It is a
  ``jax.profiler.TraceAnnotation``, which lands on the profile's clock,
  beside the device planes.
* :func:`register` keeps a compiled program; :func:`phase_of` says which
  phases an instruction of it belongs to.

A device profile names each op by its HLO instruction and program
(``jit_train_step``, ``fusion.12``) but carries no framework scope; the
compiled program's optimized HLO does, in each instruction's
``metadata={op_name="jit(train_step)/seedflood.ge/…"}``.  So to attribute
a profile, run under ``jax.profiler.trace`` and ask
``phase_of(program, instruction)`` for each op.  The text is parsed on the
first question about a program and never when no one asks.
"""
from __future__ import annotations

import re

import jax

PREFIX = "seedflood."

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(PREFIX) + r"(\w+)")
_REFERENCE = re.compile(r"%([\w.\-]+)")

#: program name -> the compiled object, until it is first asked about
_programs: dict[str, object] = {}
#: program name -> {instruction: phases}, once parsed
_phases: dict[str, dict[str, tuple[str, ...]]] = {}


def scope(name: str):
    """Name a device-side phase: ``with obs.scope("ge"): ...``."""
    return jax.named_scope(PREFIX + name)


def span(name: str):
    """Name a host-side phase: ``with obs.span("server.decode"): ...``."""
    return jax.profiler.TraceAnnotation(name)


def register(compiled) -> str:
    """Keep ``compiled`` (a ``jax.stages.Compiled``) under its HLO module
    name, which a profile's ``XLA Modules`` line carries; returns the
    name.  A later program of the same name replaces the earlier."""
    name = compiled.runtime_executable().hlo_modules()[0].name
    _programs[name] = compiled
    _phases.pop(name, None)
    return name


def phase_of(module: str, op: str) -> tuple[str, ...]:
    """The phases of instruction ``op`` of program ``module``, outermost
    first (``("ge", "mlp")``); ``()`` for an unknown program or op."""
    if module not in _phases:
        compiled = _programs.pop(module, None)
        if compiled is None:
            return ()
        _phases[module] = parse(hlo_text(compiled))
    return _phases[module].get(op, ())


def hlo_text(compiled) -> str:
    """The optimized HLO of ``compiled``, metadata included.  An executable
    loaded from the persistent compile cache may give no text; its runtime
    executable still holds the modules."""
    text = compiled.as_text()
    if not text:
        text = "\n\n".join(m.to_string() for m in
                           compiled.runtime_executable().hlo_modules())
    return text


def parse(text: str) -> dict[str, tuple[str, ...]]:
    """{instruction: phases} of an HLO module's text.  An instruction with
    no phase of its own takes that of the instruction that calls its
    computation (a copy XLA put into a loop body takes the loop's)."""
    own: dict[str, tuple[str, ...]] = {}
    calls: dict[str, list[str]] = {}        # instruction -> computations
    body: dict[str, list[str]] = {}         # computation -> instructions
    entry, comp = None, None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            body[comp].append(name)
            op_name = _OP_NAME.search(rest)
            own[name] = tuple(_SCOPE.findall(op_name.group(1))) \
                if op_name else ()
            calls[name] = _REFERENCE.findall(rest)
            continue
        m = _COMPUTATION.match(line)
        if m and not line.startswith("HloModule"):
            comp = m.group(2)
            body[comp] = []
            if m.group(1):
                entry = comp
        elif line.startswith("}"):
            comp = None
    phases: dict[str, tuple[str, ...]] = {}
    stack = [(entry, ())] if entry is not None else []
    seen = set()
    while stack:
        comp, inherited = stack.pop()
        if (comp, inherited) in seen:
            continue
        seen.add((comp, inherited))
        for name in body.get(comp, ()):
            ph = own[name] or inherited
            phases.setdefault(name, ph)
            stack.extend((c, ph) for c in calls[name] if c in body)
    return phases
