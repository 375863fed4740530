"""Step-phase scopes: stable ``jax.named_scope`` names at the train step's
layer boundaries, and the one function that maps a compiled op back to
its phase.

Every train-step realization (``train.make_train_step`` with and without a
distributed update, ``train.make_overlapped_train_step``, the
``optim.dist.UpdatePlan`` phases and the ``comm.overlap`` reduce hooks)
opens these scopes:

    fwd               the loss, inside the differentiated function; its
                      transpose (``transpose(jvp(fwd))``) is the backward
    clip              the global gradient norm and the clip scale
    update/reduce     the gradient part-reduce, with the bucket pack
    update/apply      the optimizer on this member's strips: pack, slice,
                      the serial ``optimizer.update``, the learning rate
    update/broadcast  the part-broadcast of the new params, with the unpack

Scopes change only the ops' ``op_name`` metadata (the name stack), never
the arithmetic.  :func:`phase_of` reads that name back — from an HLO
instruction's ``metadata={op_name=...}`` or a profiler event — so a trace
reduction splits device time by phase without re-deriving the names.

The compiler drops the metadata of some instructions it makes itself: the
tuple-rooted multi-output fusions, and the relayout copies, slices and
loops that move a tensor into a fusion buffer's layout.
:func:`hlo_phases` gives every instruction of a compiled program a phase:
its own ``op_name``'s, else that of the computation it calls (a fusion's
ops), else that of the nearest op it feeds (a relayout serves its
consumer) or is fed by, else that of the instruction that calls its
computation (a loop's body).  A collective without one is one the compiler
combined or rewrote from the step's own (a reduce-scatter lowered to an
all-reduce, several all-reduces made one): it takes the phase its
operands come from, the ``update/*`` phases first, as in :func:`phase_of`.
"""
from __future__ import annotations

import re
from collections import Counter, deque
from typing import Dict, List, NamedTuple, Optional

FWD = "fwd"
CLIP = "clip"
REDUCE = "update/reduce"
APPLY = "update/apply"
BROADCAST = "update/broadcast"

PHASES = ("fwd", "bwd", "clip", "reduce", "apply", "broadcast", "other")
_PRECEDENCE = ("reduce", "apply", "broadcast", "clip", "bwd", "fwd")
_UPDATE = ("reduce", "apply", "broadcast")
# one name-stack component: transform wrappers around a scope name, as in
# ``transpose(jvp(fwd))``
_COMPONENT = re.compile(r"^((?:[\w-]+\()*)([^()]*)\)*$")


def phase_of(op_name: str) -> str:
    """The step phase of an op, from its ``op_name``: ``"reduce"``,
    ``"apply"`` or ``"broadcast"`` under ``update/*`` (which takes
    precedence: the overlapped reduces run inside the backward pass),
    ``"clip"``, ``"bwd"`` for ``fwd`` under a ``transpose``, ``"fwd"``,
    else ``"other"``."""
    cores, transposed = [], False
    for part in op_name.split("/"):
        m = _COMPONENT.match(part)
        if m is None:
            cores.append(part)
            continue
        cores.append(m.group(2))
        if m.group(2) == FWD and "transpose(" in m.group(1):
            transposed = True
    for a, b in zip(cores, cores[1:]):
        if a == "update" and b in _UPDATE:
            return b
    if CLIP in cores:
        return "clip"
    if FWD in cores:
        return "bwd" if transposed else "fwd"
    return "other"


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


class Instr(NamedTuple):
    """One instruction of a compiled HLO module's text."""
    name: str
    opcode: str
    op_name: Optional[str]      # its metadata's, when the compiler kept one
    callees: List[str]          # computations it calls, body or applies
    operands: List[str]


def _operands(rest: str, start: int) -> List[str]:
    depth = 0
    for i in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return _OPERAND.findall(rest[start:i])
    return []


def parse_hlo(text: str) -> Dict[str, List[Instr]]:
    """Computation name -> its instructions, in order, from the text of a
    compiled program (``compiled.as_text()``)."""
    comps: Dict[str, List[Instr]] = {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if comp is None or not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        code = _OPCODE.search(" " + rest)
        comp.append(Instr(
            name, code.group(1) if code else "", op.group(1) if op else None,
            _CALLEE.findall(rest),
            _operands(" " + rest, code.end() - 1) if code else []))
    return comps


def hlo_phases(text: str) -> Dict[str, str]:
    """Instruction name -> phase, for every instruction of the compiled
    program ``text`` (``compiled.as_text()``); see the module docstring
    for how an instruction without ``op_name`` is placed."""
    parsed = parse_hlo(text)
    comps = {c: [i.name for i in instrs] for c, instrs in parsed.items()}
    instrs = [i for c in parsed.values() for i in c]
    own = {i.name: phase_of(i.op_name) if i.op_name is not None else "other"
           for i in instrs}
    callees = {i.name: i.callees for i in instrs}
    operands = {i.name: i.operands for i in instrs}
    opcode = {i.name: i.opcode for i in instrs}

    memo: Dict[str, Counter] = {}

    def inside(c: str) -> Counter:
        if c not in memo:
            memo[c] = Counter()
            for n in comps.get(c, ()):
                if own[n] != "other":
                    memo[c][own[n]] += 1
                for callee in callees[n]:
                    memo[c].update(inside(callee))
        return memo[c]

    phase: Dict[str, str] = {}
    for n, p in own.items():
        if p == "other":
            found = Counter()
            for callee in callees[n]:
                found.update(inside(callee))
            p = found.most_common(1)[0][0] if found else "other"
        phase[n] = p

    users: Dict[str, List[str]] = {n: [] for n in own}
    for n, ops in operands.items():
        for o in ops:
            if o in users:
                users[o].append(n)

    def nearest(start: str, edges) -> str:
        seen, todo = {start}, deque(edges[start])
        while todo:
            n = todo.popleft()
            if n in seen or n not in phase:
                continue
            seen.add(n)
            if phase[n] != "other":
                return phase[n]
            todo.extend(edges[n])
        return "other"

    found = {}
    for n in (n for n in own if phase[n] == "other"):
        if COLLECTIVE.match(opcode[n]):
            came = {phase[o] if phase[o] != "other" else nearest(o, operands)
                    for o in operands[n] if o in phase}
            found[n] = next((p for p in _PRECEDENCE if p in came), "other")
        else:
            p = nearest(n, users)
            found[n] = p if p != "other" else nearest(n, operands)
    phase.update(found)
    # a called computation's unplaced ops take their caller's phase,
    # outer computations first
    comp_of = {n: c for c, names in comps.items() for n in names}
    callers = {c: n for n in own for c in callees[n]}

    def depth(c: str) -> int:
        d = 0
        while c in callers and d < len(comps):
            c, d = comp_of[callers[c]], d + 1
        return d

    for c in sorted(comps, key=depth):
        caller = callers.get(c)
        if caller is None or phase[caller] == "other":
            continue
        for n in comps[c]:
            if phase[n] == "other":
                phase[n] = phase[caller]
    return phase
