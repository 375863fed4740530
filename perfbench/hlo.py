"""What each instruction of a compiled program does, read from its HLO text
(``compiled.as_text()``), so that a trace's ops can be attributed.

The TPU compiler lowers matrix products to ``convolution`` too, so an
instruction is told apart by the JAX op it came from (its
``metadata={op_name=...}``): ``conv`` when it computes a
``conv_general_dilated``, ``matmul`` for a ``dot_general``.  A fusion takes
the kind of what its fused computation holds (a convolution first).
Collectives are ``collective`` by their opcode.
"""
from __future__ import annotations

import re
from typing import Dict

COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
CALLS = re.compile(r"calls=%([\w.\-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def _own_kind(opcode: str, rest: str) -> str:
    if COLLECTIVE.match(opcode):
        return "collective"
    if opcode == "convolution":
        m = OP_NAME.search(rest)
        src = m.group(1) if m else ""
        if "conv_general_dilated" in src:
            return "conv"
        if "dot_general" in src:
            return "matmul"
    return "other"


def kinds(text: str) -> Dict[str, str]:
    """Instruction name -> ``conv``, ``matmul``, ``collective`` or
    ``other``, for every instruction of every computation in ``text``."""
    own: Dict[str, Dict[str, str]] = {}        # computation -> instr -> kind
    calls: Dict[str, Dict[str, str]] = {}      # computation -> instr -> callee
    comp = None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            own[comp], calls[comp] = {}, {}
            continue
        m = INSTRUCTION.match(line)
        if comp is None or not m:
            continue
        name, rest = m.groups()
        op = OPCODE.search(rest)
        own[comp][name] = _own_kind(op.group(1) if op else "", rest)
        c = CALLS.search(rest)
        if c:
            calls[comp][name] = c.group(1)

    memo: Dict[str, str] = {}

    def comp_kind(c: str) -> str:
        if c not in memo:
            memo[c] = "other"
            found = set()
            for name, k in own.get(c, {}).items():
                callee = calls[c].get(name)
                found.add(comp_kind(callee) if callee and k == "other"
                          else k)
            for k in ("conv", "matmul", "collective"):
                if k in found:
                    memo[c] = k
                    break
        return memo[c]

    out: Dict[str, str] = {}
    for c in own:
        for name, k in own[c].items():
            callee = calls[c].get(name)
            if k == "other" and callee and not name.startswith(("while",
                                                                "call")):
                k = comp_kind(callee)
            out[name] = k
    return out
