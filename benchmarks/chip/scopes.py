"""From a compiled replay's HLO text to the phase of the training step each
of its instructions belongs to.

The program opens a ``jax.named_scope`` over each phase of the replay
(``repro.analysis.tracing.SCOPES``); XLA keeps the name stack in every
instruction's ``metadata={op_name=...}``, fusions included (a fusion
carries its root's).  The profiler names a device op by its instruction,
so this map puts each op of a trace down to its phase.

An instruction's scope is the last ``replay.<phase>`` token of its
op_name.  ``replay.grad`` splits into ``replay.grad.fwd`` and
``replay.grad.bwd`` by whether the name stack past the token holds
``transpose(``, which is how JAX names the backward pass.  An instruction
with no token is ``unscoped``.

A model marks its own parts with ``jax.named_scope("model.<name>")``; no
list of them is kept here.  An instruction's model scope is the innermost
(last) ``model.<name>`` token of its op_name, with ``.bwd`` where the name
that holds it has ``transpose(`` (JAX writes a differentiated scope as
``jvp(model.<name>)`` and its backward as ``transpose(jvp(model.<name>))``)
and ``.fwd`` otherwise.  Model scopes are read apart from the replay's:
a model token inside ``replay.grad`` leaves that instruction in
``replay.grad.fwd`` or ``.bwd``.
"""
from __future__ import annotations

import re
from typing import NamedTuple

# the program's scope names; a test holds this copy equal to the program's
SCOPES = ("replay.unpack", "replay.grad", "replay.pack", "replay.update",
          "replay.record", "replay.mix", "replay.gossip")
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_TOKEN = re.compile(r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?![\w.])")
_MODEL = re.compile(r"(?<![\w.\-])model(?:\.[\w\-]+)+")


def scope_of(op_name: str) -> str:
    """The scope of one instruction from its op_name metadata."""
    last = None
    for m in _TOKEN.finditer(op_name):
        last = m
    if last is None:
        return UNSCOPED
    name = last.group(1)
    if name != "replay.grad":
        return name
    rest = op_name[last.end():].split(";")[0]
    return "replay.grad.bwd" if "transpose(" in rest else "replay.grad.fwd"


def model_scope_of(op_name: str) -> str | None:
    """The model scope of one instruction, ``model.<name>.fwd`` or
    ``.bwd``, or None where its op_name holds no ``model.`` token."""
    last = None
    for m in _MODEL.finditer(op_name):
        last = m
    if last is None:
        return None
    start = op_name.rfind(";", 0, last.start()) + 1
    name = op_name[start:].split(";")[0]
    return last.group(0) + (".bwd" if "transpose(" in name else ".fwd")


def leaf(name: str) -> str:
    """An op's name as the map keys it: the profiler's ``%`` dropped."""
    return name.lstrip("%")


def _op_names(hlo_text: str):
    """(instruction name, its op_name or "") of every instruction."""
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(m.group(2))
            yield m.group(1), op.group(1) if op else ""


def scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope, for every instruction of the module."""
    return {instr: scope_of(op) for instr, op in _op_names(hlo_text)}


def model_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> model scope, for the instructions that have
    one."""
    out = {}
    for instr, op in _op_names(hlo_text):
        scope = model_scope_of(op)
        if scope is not None:
            out[instr] = scope
    return out


class ScopeMaps(NamedTuple):
    """Both maps of one compiled program, as ``trace_reduce.reduce`` takes
    them."""
    replay: dict[str, str]
    model: dict[str, str]


def maps(hlo_text: str) -> ScopeMaps:
    return ScopeMaps(scope_map(hlo_text), model_map(hlo_text))


def fused_scopes(hlo_text: str) -> dict[str, set[str]]:
    """Instruction name -> the scopes of the instructions in the
    computations it calls, nested calls included (a fusion's body), for
    each instruction that calls one.  ``unscoped`` is left out: a fusion's
    parameters carry no metadata, and the constants it holds carry the
    name stack they were hoisted to."""
    body: dict[str, list[str]] = {}
    calls: dict[str, list[str]] = {}
    tagged: dict[str, str] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            if current is not None:
                body[current].append(m.group(1))
            calls[m.group(1)] = _CALLS.findall(m.group(2))
            op = _OP_NAME.search(m.group(2))
            if op and scope_of(op.group(1)) != UNSCOPED:
                tagged[m.group(1)] = scope_of(op.group(1))
        elif line.rstrip().endswith("{"):
            c = _COMPUTATION.match(line)
            if c:
                current = c.group(1)
                body.setdefault(current, [])

    def within(comp, seen):
        out = set()
        for instr in body.get(comp, ()):
            if instr in tagged:
                out.add(tagged[instr])
            for callee in calls.get(instr, ()):
                if callee not in seen:
                    seen.add(callee)
                    out |= within(callee, seen)
        return out

    return {instr: set().union(*(within(c, {c}) for c in callees))
            for instr, callees in calls.items() if callees}


def scope_seconds(record: dict, scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds per scope in the traced window of ``record``
    (``trace_reduce.extract``), averaged over chips as ``gossip_s`` is: the
    ops that hold no other op (a loop's time is its body's), each put down
    to its instruction's scope; an op the map does not know is
    ``unscoped``.  Every scope of the map is a key."""
    from trace_reduce import _leaves

    lo, dur = next(s[1:] for s in record["host"] if s[0] == "window")
    out = dict.fromkeys(set(scopes.values()) | {UNSCOPED}, 0.0)
    chips = len(record["devices"])
    for dev in record["devices"]:
        live = [o for o in dev["ops"] if o[1] + o[2] > lo and o[1] < lo + dur]
        for o in _leaves(live):
            out[scopes.get(leaf(o[0]), UNSCOPED)] += o[2] * 1e-9 / chips
    return out


def scoped_ops(device_ops, scopes: dict[str, str], fused=None) -> list:
    """``trace_reduce``'s top device ops as [name [scope], seconds], and
    with ``fused`` (``fused_scopes``) the scopes inside each fusion."""
    out = []
    for name, seconds in device_ops:
        row = [f"{name} [{scopes.get(leaf(name), UNSCOPED)}]", seconds]
        if fused is not None:
            row.append(sorted(fused.get(leaf(name), ())))
        out.append(row)
    return out


GRAD = ("replay.grad.fwd", "replay.grad.bwd")
BANK = ("replay.unpack", "replay.pack", "replay.update", "replay.mix")
RECORD = ("replay.record",)


def _seconds(scope_s: dict, names):
    """Summed seconds of ``names``; None where the program has none of
    them."""
    if not any(n in scope_s for n in names):
        return None
    return sum(scope_s.get(n, 0.0) for n in names)


def grad_mfu(scope_s: dict, facts: dict):
    """Model FLOPs of the window's gradient ticks over the device time of
    ``replay.grad``, over the chips' bf16 peak, in percent: the model's own
    share of the chip, the bookkeeping around it taken out."""
    t = _seconds(scope_s, GRAD)
    if t is None or t <= 0 or facts["grad_ticks"] <= 0:
        return None
    rate = facts["traced_units"] * facts["flops_per_unit"] / t
    return 100.0 * rate / (facts["chips"] * facts["peak_flops"])


def _ms_per_tick(scope_s: dict, facts: dict, names):
    """Device milliseconds of ``names`` per gradient tick of the window."""
    t = _seconds(scope_s, names)
    if t is None or facts["grad_ticks"] <= 0:
        return None
    return 1e3 * t / facts["grad_ticks"]


def bank_ms_per_tick(scope_s: dict, facts: dict):
    """The flat-bank work per gradient tick: bank to pytree, gradients to
    bank, the SGD step on x and x~, and the mixing sweeps."""
    return _ms_per_tick(scope_s, facts, BANK)


def record_ms_per_tick(scope_s: dict, facts: dict):
    """The per-tick SimTrace reductions (loss, consensus, mean norm) per
    gradient tick: the program's own in-scan bookkeeping."""
    return _ms_per_tick(scope_s, facts, RECORD)
