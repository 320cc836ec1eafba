"""Device time by the program's own phases: which ``blades/*`` scope each
operation of a round's compiled programs belongs to, and what each program
needs of the device's memory.

The round bodies name their phases with ``jax.named_scope``
(``blades/step``, ``blades/attn_kernel``, ``blades/dispatch``, ...):
trace-time metadata that the compiler carries into an instruction's
``op_name``.  A fusion carries its root's; a backward operation reads
``transpose(jvp(blades/<x>))``, and a ``custom_vjp``'s backward lands under
its call's scope.  What the TPU compiler builds without an ``op_name`` (a
Mosaic kernel's call, an outlined fusion, a layout copy) takes the phase of
its neighbours (:func:`phases_of_text`).  A device trace names each
operation by its instruction, so ``(program, instruction) -> phase`` joins
a trace's operations to the phases (``perfbench/readers/device_phase.py``).

:func:`note` is called where a round dispatches an executable.  At the first
dispatch of each distinct one it keeps the executable that
``fn.lower(*args, **kwargs).compile()`` gives: the compile the call itself
would run, which the call then finds in the jit's in-memory cache.  It keeps
no array.  Every later call is a set lookup.  Nothing is parsed until
someone asks: :func:`op_phases` reads each kept program's text once,
:func:`hbm_need_bytes` the compiler's memory account.  The registry is the
process's, as the profiler it is read beside is: the last
``MAX_PROGRAMS`` executables noted.
"""

from __future__ import annotations

import collections
import re
import weakref

__all__ = ["MAX_PROGRAMS", "hbm_need_bytes", "note", "noted", "op_phases",
           "phase_of", "phases_of_text"]

MAX_PROGRAMS = 32

_SCOPE = re.compile(r"blades/(\w+)")
_INSTRUCTION = re.compile(r"\s*(ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"HloModule\s+([^\s,]+)")


class _Program:
    """One noted executable; its text is parsed on the first question."""

    __slots__ = ("executable", "_parsed")

    def __init__(self, executable):
        self.executable = executable
        self._parsed = None

    def parsed(self) -> tuple:
        if self._parsed is None:
            self._parsed = phases_of_text(self.executable.get_hlo_text())
        return self._parsed


_seen = weakref.WeakKeyDictionary()   # jitted function -> dispatch keys
_programs = collections.deque(maxlen=MAX_PROGRAMS)
_count = [0]


def note(fn, args, kwargs) -> None:
    """Keep the executable ``fn(*args, **kwargs)`` runs, once per distinct
    static arguments and argument shapes of ``fn``.  Call it before the
    call, while donated buffers still stand."""
    key = (tuple(sorted(kwargs.items())),
           tuple(getattr(a, "shape", None) for a in args))
    seen = _seen.get(fn)
    if seen is not None and key in seen:
        return
    _seen.setdefault(fn, set()).add(key)
    compiled = fn.lower(*args, **kwargs).compile()
    _programs.append(_Program(compiled.runtime_executable()))
    _count[0] += 1


def noted() -> int:
    """How many executables this process has noted."""
    return _count[0]


def phase_of(op_name: str):
    """The innermost ``blades/<x>`` scope of an instruction's ``op_name``
    (``x``), through the ``jvp(``, ``transpose(`` and ``vmap(`` wrappers;
    ``None`` where it wears none."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def _group_end(text: str, start: int) -> int:
    """The index after the parenthesised group that opens at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if not depth:
                return i + 1
    return len(text)


def _operands(rest: str) -> list:
    """The instructions an HLO line reads: the references inside the
    opcode's parentheses, after the result's type."""
    at = _group_end(rest, 0) if rest.startswith("(") else rest.find(" ")
    at = rest.find("(", at)
    return _REF.findall(rest[at:_group_end(rest, at)]) if at >= 0 else []


def phases_of_text(text: str) -> tuple:
    """``(module name, {instruction: phase or None})`` of a compiled
    program's HLO text, every computation's instructions included.

    An instruction's phase is the innermost scope of its own ``op_name``,
    or ``None`` where the operation path there wears none.  The TPU
    compiler builds some instructions with no path (a Mosaic kernel's
    call, an outlined fusion, an asynchronous slice; a layout copy of a
    parameter carries the parameter's name): such an instruction takes
    the phase of what it calls (the root of the called computation, else
    the phase most of its instructions wear), else of what it reads (its
    operands, first to last), else of what reads it."""
    head = _MODULE.match(text)
    phase, operands, called, roots, members = {}, {}, {}, {}, {}
    pathless, computation = [], None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name, rest = m.group(2), line[m.end():]
        at = rest.find('op_name="')
        path = "" if at < 0 else rest[at + 9:rest.find('"', at + 9)]
        phase[name] = phase_of(path)
        if "/" not in path and " parameter(" not in rest:
            pathless.append(name)
        operands[name] = _operands(rest)
        called[name] = _CALLED.findall(rest) + [
            c for group in _BRANCHES.findall(rest)
            for c in _REF.findall(group)]
        members.setdefault(computation, []).append(name)
        if m.group(1):
            roots[computation] = name

    def of_computation(c):
        p = phase.get(roots.get(c))
        if p is None:
            worn = collections.Counter(phase[n] for n in members.get(c, ())
                                       if phase[n])
            p = worn.most_common(1)[0][0] if worn else None
        return p

    def inherit(names, neighbours):
        changed = True
        while changed:
            changed = False
            for n in names:
                if phase[n] is None:
                    p = next(filter(None, neighbours(n)), None)
                    if p is not None:
                        phase[n], changed = p, True

    text_order = [n for n in pathless if phase[n] is None]
    inherit(text_order, lambda n: [of_computation(c) for c in called[n]]
            + [phase.get(o) for o in operands[n]])
    users = {}
    for n, reads in operands.items():
        for o in reads:
            users.setdefault(o, []).append(n)
    inherit(text_order[::-1],
            lambda n: [phase.get(u) for u in users.get(n, ())])
    return (head.group(1) if head else "?"), phase


def _keyed(parts) -> dict:
    """``{module name: value}`` over the kept programs, the newest under its
    plain name and an older one of the same name under ``name(fingerprint)``
    (the form of the device trace's module events; the id in them is this
    fingerprint on the CPU, another on a TPU, where a reader tells the
    programs of one name apart by their instructions)."""
    out = {}
    for prog, value in reversed(parts):
        name = prog.parsed()[0]
        if name in out:
            name = f"{name}({_fingerprint(prog.executable)})"
        out[name] = value
    return out


def _fingerprint(executable) -> str:
    fp = executable.fingerprint
    if isinstance(fp, bytes):
        return fp.decode() if fp.isdigit() else fp.hex()
    return str(fp)


def op_phases() -> dict:
    """``{module name: {instruction: phase or None}}`` of the kept
    programs."""
    return _keyed([(p, p.parsed()[1]) for p in _programs])


def hbm_need_bytes() -> dict:
    """``{module name: bytes}``: what each kept program needs of the device
    by the compiler's account, arguments + outputs - aliased + temporaries
    + code."""
    parts = []
    for prog in _programs:
        m = prog.executable.get_compiled_memory_stats()
        parts.append((prog, m.argument_size_in_bytes + m.output_size_in_bytes
                      - m.alias_size_in_bytes + m.temp_size_in_bytes
                      + m.generated_code_size_in_bytes))
    return _keyed(parts)
