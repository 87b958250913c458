"""The step program's named scopes, read back from a device trace.

The program puts a ``jax.named_scope`` at each layer boundary of its
step (table below). XLA keeps the scopes in the ``op_name`` metadata of
the instructions it compiles from that code; the profiler's "XLA Ops"
events carry the instruction names alone. So a traced run joins the two
by name: ``op_scopes`` reads each instruction's op_name from the
compiled step's HLO text (``Compiled.as_text()``), and ``shares`` and
``scope_shares`` sum the own time (``own_time``) of the device
operations whose op_name a test accepts.

==============  ==================================================
scope           holds
==============  ==================================================
``dsgd.grad``   loss, forward, backward, recomputation, grad_accum
``dsgd.update`` the SGD/momentum update
``dsgd.gossip`` the mixing, every transport
``dsgd.probes`` the health probes
``embed``       token lookup
``attn``        attention block: ``qkv``, ``sdpa``, ``out``
``mlp``         MLP or MoE block
``lm_head``     head and loss
==============  ==================================================
"""

from __future__ import annotations

import re
from typing import Callable

from . import reduce

GRAD, UPDATE, GOSSIP, PROBES = "dsgd.grad", "dsgd.update", "dsgd.gossip", "dsgd.probes"
SCOPES = (GRAD, UPDATE, GOSSIP, PROBES, "embed", "attn", "qkv", "sdpa", "out", "mlp",
          "lm_head")
PHASES = ("forward", "backward", "remat", "update", "gossip", "probes")
REMAT = "rematted_computation"  # jax.checkpoint's recomputation in the backward

# the per-layer shares: metric name -> test on an op_name
SHARES: dict[str, Callable[[str], bool]] = {
    "forward_share": lambda n: phase(n) == "forward",
    "backward_share": lambda n: phase(n) == "backward",
    "remat_share": lambda n: phase(n) == "remat",
    "update_share": lambda n: phase(n) == "update",
    "attention_share": lambda n: part(n) == "sdpa",
    "lm_head_share": lambda n: part(n) == "lm_head",
}

_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def scope_names(op_name: str) -> set[str]:
    """The segments of an op_name's path (the first of ``;``-joined
    ones), each unwrapped from the ``jvp(...)`` / ``transpose(...)`` that
    autodiff puts round the outermost scope inside ``value_and_grad``."""
    out = set()
    for seg in op_name.split(";")[0].split("/"):
        while (m := _WRAPPED.match(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


def phase(op_name: str) -> str | None:
    """The step phase of an op_name: one of ``PHASES``, or None (unscoped).

    The first of ``;``-joined op_names counts. In order: under
    ``dsgd.update``, update; under ``dsgd.gossip``, gossip; under
    ``dsgd.probes``, probes; recomputed under ``jax.checkpoint``, remat;
    under autodiff's ``transpose(``, backward; any other path under
    ``dsgd.grad``, forward.
    """
    path = op_name.split(";")[0]
    names = scope_names(op_name)
    if UPDATE in names:
        return "update"
    if GOSSIP in names:
        return "gossip"
    if PROBES in names:
        return "probes"
    if REMAT in path.split("/"):
        return "remat"
    if "transpose(" in path:
        return "backward"
    if GRAD in names:
        return "forward"
    return None


def part(op_name: str) -> str | None:
    """``sdpa`` or ``lm_head`` where that scope is on the path, in any
    phase; else None."""
    names = scope_names(op_name)
    for p in ("sdpa", "lm_head"):
        if p in names:
            return p
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\((?:%([\w.\-]+))?")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# a fusion's root through which its work is named: a bitcast takes the
# op_name of where it was put (the step's ``x[None]`` round the update and
# the gossip, outside every scope), not of the work it carries out
LOOK_THROUGH = {"bitcast"}


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} over every computation of an HLO
    module's text.

    A fusion keeps the op_name XLA gave it where that holds a phase.
    Where it holds none, the fusion is named by its fused computation's
    root, looked through ``LOOK_THROUGH`` and through instructions
    without an op_name along their first operand; where that leads to no
    op_name, by the last op_name in its fused computation (XLA gave a few
    fusions none). ``renamed`` lists the fusions these two rules move.
    """
    return _read(hlo_text)[0]


def renamed(hlo_text: str) -> dict[str, tuple[str, str, str]]:
    """{fusion: (rule, XLA's op_name or "", ``op_scopes``'s op_name)} for
    each fusion whose phase or part ``op_scopes`` changes from the one
    XLA's own op_name gives; ``rule`` is ``root`` (named by its root) or
    ``last`` (by the last op_name of its fused computation)."""
    return _read(hlo_text)[1]


def _read(hlo_text):
    names: dict[str, str] = {}
    insts: dict[str, tuple[str, str | None, str | None]] = {}
    roots: dict[str, str] = {}  # computation -> its root instruction
    last: dict[str, str] = {}  # computation -> its last op_name
    fusions: dict[str, str] = {}  # fusion -> its fused computation
    comp = None
    for line in hlo_text.splitlines():
        if " = " not in line and (m := _COMPUTATION.match(line)):
            comp = m.group(1)
            continue
        if not (m := _INSTRUCTION.match(line)):
            continue
        root, name, opcode, operand = m.groups()
        op = _OP_NAME.search(line)
        op = op.group(1) if op else None
        insts[name] = (opcode, operand, op)
        if op is not None:
            names[name] = op
            last[comp] = op
        if root and comp is not None:
            roots[comp] = name
        if opcode == "fusion" and (c := _CALLS.search(line)):
            fusions[name] = c.group(1)
    moved = {}
    for name, comp in fusions.items():
        own = names.get(name, "")
        if phase(own) is not None:
            continue
        at, seen, rule, op = roots.get(comp), set(), "last", last.get(comp)
        while at in insts and at not in seen:
            seen.add(at)
            opcode, operand, at_op = insts[at]
            if at_op is not None and opcode not in LOOK_THROUGH:
                rule, op = "root", at_op
                break
            at = operand
        if op is None:
            continue
        names[name] = op
        if (phase(own), part(own)) != (phase(op), part(op)):
            moved[name] = (rule, own, op)
    return names, moved


def enable_cache() -> str:
    """The program's persistent compilation cache (``repro.launch.cache``),
    its entries keyed by the program's metadata too: the step's scopes
    live only there, and an executable loaded from an entry that a build
    without them wrote reports that build's op_names in ``as_text()``.
    For the process that reads ``as_text()``; the program's own key, on
    the instructions alone, spares it a cold compile for a moved line."""
    import jax

    from repro.launch.cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def scoped(scopes: dict[str, str]) -> bool:
    """Whether the program put its scopes into the module at all (a
    program without them leaves every share unread)."""
    return any(GRAD in scope_names(op) for op in scopes.values())


def own_time(trace: reduce.Trace) -> dict[str, float]:
    """{device operation: own time (``reduce.leaves_and_self``) clipped to
    the traced window, mean over devices, as a share of the window}. The
    base is ``reduce.idle_share``'s, so the shares and the idle share add
    up to 1, less the time that overlapping operations (async copies)
    count twice."""
    lo, hi = trace.window
    out: dict[str, float] = {}
    for ops in trace.devices.values():
        _, own = reduce.leaves_and_self(
            [(max(s, lo), min(e, hi), n) for s, e, n in ops if min(e, hi) > max(s, lo)])
        for name, ns in own.items():
            out[name] = out.get(name, 0.0) + ns / len(trace.devices) / (hi - lo)
    return out


def shares(trace: reduce.Trace, scopes: dict[str, str]) -> dict[str, float]:
    """``SHARES`` and the unscoped rest (``unscoped_share``) in %; empty
    where the trace has no device or the module no scope."""
    if not trace.devices or not scoped(scopes):
        return {}
    own = own_time(trace)
    tests = dict(SHARES, unscoped_share=lambda n: phase(n) is None)
    return {key: 100.0 * sum(v for name, v in own.items() if test(scopes.get(name, "")))
            for key, test in tests.items()}


def scope_shares(trace: reduce.Trace, scopes: dict[str, str]) -> dict[str, float]:
    """{scope: % of the window} for every name of ``SCOPES``: the own time
    of the operations whose path holds the scope, in any phase. A scope's
    share holds those of the scopes inside it (``attn`` holds ``qkv``,
    ``sdpa`` and ``out``; ``dsgd.grad`` the model's). Empty where the
    trace has no device or the module no scope."""
    if not trace.devices or not scoped(scopes):
        return {}
    own = own_time(trace)
    return {s: 100.0 * sum(v for name, v in own.items()
                           if s in scope_names(scopes.get(name, "")))
            for s in SCOPES}
