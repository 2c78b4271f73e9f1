"""From the compiled step's HLO text to the phase of each instruction, and
from a trace's per-instruction device time to device milliseconds per step
in each phase.

The program names its phases with ``jax.named_scope``: ``step.dequantize``
and ``step.update`` in ``train/step.py``, ``lm.embed`` and ``lm.head`` in
``models/lm.py``. XLA keeps each scope in the ``op_name`` of every HLO
instruction's metadata, wrapped by the transforms that made the instruction:
``jit(train_step)/jvp(lm.head)/dot_general`` is the head's forward,
``transpose(jvp(lm.head))/...`` its backward, and a segment
``rematted_computation`` marks a forward recomputed for the backward.

* ``instruction_ops`` maps every instruction to an ``op_name``. A fusion
  without metadata takes its fused computation's root's, else the first one
  in that body; an instruction that still has none (``copy``, ``broadcast``,
  an async pair) takes its first operand producer's, else its first user's,
  following either chain through instructions that have none, else the
  op_name of the instruction that runs its computation (a loop, a branch);
  a neighbour whose op_name names no phase (a parameter) gives way to the
  next. HLO is acyclic, so no chain followed here comes back.
* ``classify`` puts an ``op_name`` in one phase of ``PHASES`` and says
  whether it is vocabulary-wide work (``lm.embed`` or ``lm.head``).
* ``phase_ms`` sums a ``trace_reduce.Summary``'s device time by phase, per
  step. The six phases partition the time: an event whose instruction is not
  in the program counts as ``unscoped``. Where the program names no
  ``step.dequantize`` or no ``step.update`` scope it returns None, so a
  scope that goes missing reads as no value, not as zero time.
"""
from __future__ import annotations

import dataclasses
import re

DEQUANTIZE_SCOPE = "step.dequantize"
UPDATE_SCOPE = "step.update"
VOCAB_SCOPES = ("lm.embed", "lm.head")
REMAT_SEGMENT = "rematted_computation"
PHASES = ("dequantize", "forward", "recompute", "backward", "update", "unscoped")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_OPCODE = re.compile(r"\s*(?P<op>[\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_WRAPPER = re.compile(r"^(?P<t>[\w.]+)\((?P<inner>.*)\)$")


def _closing(text: str, i: int) -> int:
    """The index just past the bracket that closes the one at ``text[i]``."""
    pairs = {"(": ")", "{": "}", "[": "]"}
    stack = []
    for j in range(i, len(text)):
        c = text[j]
        if c in pairs:
            stack.append(pairs[c])
        elif stack and c == stack[-1]:
            stack.pop()
            if not stack:
                return j + 1
    return len(text)


@dataclasses.dataclass
class _Instr:
    opcode: str
    op_name: str | None
    operands: list
    callees: list  # the computations it runs: a fusion's body, a loop's, branches


def _parse(hlo_text: str):
    """``({instruction: _Instr}, {computation: [instruction, ...]},
    {computation: root instruction})``, in the order of the text."""
    instrs, bodies, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if comp is None:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group("name")
                bodies[comp] = []
            continue
        if line.startswith("}"):
            comp = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group("rest")
        # the result type: a tuple in parentheses, or one token
        i = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
        op = _OPCODE.match(rest, max(i, 0))
        if not op:
            continue
        start = op.end() - 1
        end = _closing(rest, start)
        attrs = rest[end:]
        name_m = _OP_NAME.search(attrs)
        name = m.group("name")
        instrs[name] = _Instr(opcode=op.group("op"), op_name=name_m.group(1) if name_m else None,
                              operands=_OPERAND.findall(rest[start:end]),
                              callees=_OPERAND.findall(_OP_NAME.sub("", attrs)))
        bodies[comp].append(name)
        if m.group("root"):
            roots[comp] = name
    return instrs, bodies, roots


def instruction_ops(hlo_text: str) -> dict:
    """``{instruction name: op_name or None}`` for every instruction of every
    computation of ``hlo_text`` (``compiled.as_text()``)."""
    instrs, bodies, roots = _parse(hlo_text)
    memo = {}

    def own(name):
        """The instruction's op_name, or for a fusion without one its body's
        root's, else the body's first."""
        if name not in memo:
            ins = instrs.get(name)
            found = ins.op_name if ins is not None else None
            if found is None and ins is not None and ins.callees:
                body = ins.callees[0]
                for other in [roots.get(body)] + bodies.get(body, []):
                    found = own(other)
                    if found is not None:
                        break
            memo[name] = found
        return memo[name]

    first_operand = {n: ins.operands[0] for n, ins in instrs.items() if ins.operands}
    caller = {}  # the instruction that runs a computation (while, conditional, call)
    for name, ins in instrs.items():
        for c in ins.callees:
            for other in bodies.get(c, []):
                caller.setdefault(other, name)
    first_user = {}
    for name, ins in instrs.items():
        for o in ins.operands:
            first_user.setdefault(o, name)

    def follow(name, step: dict):
        while name is not None:
            found = own(name)
            if found is not None:
                return found
            name = step.get(name)
        return None

    def resolve(name):
        found = own(name)
        if found is not None:
            return found
        # a neighbour's, preferring one that names a phase: a copy of a
        # parameter takes its consumer's
        near = [follow(first_operand.get(name), first_operand),
                follow(first_user.get(name), first_user)]
        if name in caller:
            near.append(resolve(caller[name]))
        phased = [op for op in near if op is not None and classify(op)[0] != "unscoped"]
        return (phased or [op for op in near if op is not None] or [None])[0]

    return {name: resolve(name) for name in instrs}


def segments(op_name: str) -> tuple:
    """``(wrappers, scopes)`` of an ``op_name``: the transforms wrapped round
    its segments (``transpose(jvp(lm.head))`` -> ``transpose``, ``jvp``) and
    the segments they wrap (``lm.head``). The final segment, the operation
    itself, is no scope."""
    wrappers, scopes = set(), set()
    for seg in op_name.split("/")[:-1]:
        m = _WRAPPER.match(seg)
        while m:
            wrappers.add(m.group("t"))
            seg = m.group("inner")
            m = _WRAPPER.match(seg)
        scopes.add(seg)
    return wrappers, scopes


def classify(op_name: str | None) -> tuple:
    """``(phase, vocab)`` of one instruction's ``op_name``."""
    if op_name is None:
        return "unscoped", False
    wrappers, scopes = segments(op_name)
    vocab = any(s in scopes for s in VOCAB_SCOPES)
    if DEQUANTIZE_SCOPE in scopes:
        phase = "dequantize"
    elif UPDATE_SCOPE in scopes:
        phase = "update"
    elif "jvp" in wrappers and REMAT_SEGMENT in scopes:
        phase = "recompute"
    elif "transpose" in wrappers:
        phase = "backward"
    elif "jvp" in wrappers:
        phase = "forward"
    else:
        phase = "unscoped"
    return phase, vocab


@dataclasses.dataclass
class PhaseMap:
    phase: dict  # instruction name -> one of PHASES
    vocab: set  # instruction names of vocabulary-wide work
    scoped: bool  # the program names both step scopes


def phase_map(hlo_text: str) -> PhaseMap:
    ops = instruction_ops(hlo_text)
    phase, vocab, seen = {}, set(), set()
    for name, op_name in ops.items():
        phase[name], v = classify(op_name)
        if v:
            vocab.add(name)
        if op_name is not None:
            seen |= segments(op_name)[1]
    return PhaseMap(phase=phase, vocab=vocab,
                    scoped=DEQUANTIZE_SCOPE in seen and UPDATE_SCOPE in seen)


def phase_ms(summary, pmap: PhaseMap, steps: int) -> dict | None:
    """Device milliseconds per step in each of ``PHASES`` and in ``vocab``:
    the summed device time of the window's events of that class over the
    window's steps. None where the program names no step scopes."""
    if not pmap.scoped:
        return None
    out = dict.fromkeys(PHASES + ("vocab",), 0.0)
    for name, secs in summary.op_s.items():
        out[pmap.phase.get(name, "unscoped")] += secs
        if name in pmap.vocab:
            out["vocab"] += secs
    return {k: 1e3 * v / steps for k, v in out.items()}


def read(ctx, key: str) -> float | None:
    """One per-layer reader's value: ``key`` of ``phase_ms`` for the traced
    window, from the context's compiled HLO text (``ctx.hlo``) and steps
    (``ctx.steps``)."""
    ms = phase_ms(ctx.summary, phase_map(ctx.hlo), ctx.steps)
    return None if ms is None else ms[key]
