"""Device time per phase of the program's step, read from its named scopes.

The program names the phases of its training step with ``jax.named_scope``:
``fwd`` and ``accumulate`` (``runtime/trainer.py``), ``push``, ``apply`` and
``pull`` (``core/exchange.py``; the trainer's layout of the pulled
parameters is ``pull`` too), and the transformer's blocks ``attn``, ``mlp``
and ``lm_head`` (``models/transformer.py``). Under
``value_and_grad`` the forward's scope reads ``jvp(fwd)``, the backward
``transpose(jvp(fwd))``, and a forward recomputed under ``jax.checkpoint``
adds ``rematted_computation`` to the backward's path.

The compiled step's HLO text carries each instruction's scope path in its
``metadata={op_name=...}``, and a device event of the trace is named by its
instruction (``bench.trace.op_name``; on the CPU an event holds the name and
not the path). So a leaf's phase is read from the step's HLO by the leaf's
name, the same way on every platform. A traced window's context holds the
trace and not the compiled step: ``step_scopes`` finds the step among the
executables the process holds, as the one whose instructions cover most
of the window's leaf time. (The program keys its persistent compile cache
on the metadata, ``launch/compile_cache.py``, so an executable it loads
carries its own scopes and not those of another tree.)
"""
from __future__ import annotations

import re

from bench import trace as tr

PHASES = ("fwd", "bwd", "remat", "accumulate", "push", "apply", "pull")
BLOCKS = ("attn", "mlp", "lm_head")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_NAMES = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLEES = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def scopes_from_hlo(text: str) -> dict:
    """Instruction name -> scope path of an HLO module's text.

    An instruction's path is its own ``metadata={op_name=...}`` (where that
    joins several paths with ``;``, the first), or, for a fusion without
    one, that of the fused computation's root (else of the last instruction
    in it that has one). The compiler's passes leave instructions that no
    scope names: a reshape turned into a relayout, memory-space copies, a
    collective combined from several or rewritten from a reduce-scatter, the
    loop that an all-gather over one device becomes. Such an instruction
    is charged to the phase of its first consumer in the scheduled order
    that names one, and else to that of the instruction that calls its
    computation (a loop's body and condition)."""
    own, order, users, calls, callers, roots, comp_paths = ({} for _ in range(7))
    comp_of: dict = {}
    comp = None
    for i, line in enumerate(text.splitlines()):
        m = _INSTR.match(line)
        if not m:
            if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
                head = line.split()
                comp = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            continue
        root, name, rest = m.groups()
        order[name], comp_of[name] = i, comp
        p = _OP_NAME.search(rest)
        if p:
            own[name] = p.group(1).split(";", 1)[0]
            comp_paths.setdefault(comp, []).append(own[name])
        if root:
            roots[comp] = name
        c = _CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
        for callee in _CALLEES.findall(rest):
            callers.setdefault(callee, name)
        for ref in set(_REF.findall(rest)):
            users.setdefault(ref, []).append(name)

    phases = set(PHASES)
    memo: dict = {}

    def resolve(name, depth=0):
        if name in memo:
            return memo[name]
        memo[name] = ""  # while its consumers and caller are looked at
        p = own.get(name, "")
        if not p and name in calls:
            c = calls[name]
            p = own[roots[c]] if roots.get(c) in own else (
                comp_paths.get(c, [""])[-1])
        if not scope_names(p) & phases and depth < 64:
            for u in sorted(users.get(name, ()), key=order.get):
                q = resolve(u, depth + 1)
                if scope_names(q) & phases:
                    p = q
                    break
            else:
                caller = callers.get(comp_of[name])
                q = resolve(caller, depth + 1) if caller else ""
                if scope_names(q) & phases:
                    p = q
        memo[name] = p
        return p

    return {n: p for n in order if (p := resolve(n))}


def scope_names(path: str) -> set:
    """The phases and blocks (``PHASES``, ``BLOCKS``) a scope path names: a
    recomputed forward (``rematted_computation``) is ``remat`` and not
    ``bwd``; the backward (``transpose(jvp(fwd))``) is ``bwd`` and not
    ``fwd``; ``fwd`` is ``jvp(fwd)`` with no ``transpose(`` in the path. A
    block counts wherever it appears: in the forward, the backward and the
    recomputation alike."""
    parts = path.split("/")
    names = {p for p in ("push", "pull", "apply", "accumulate") if p in parts}
    if "rematted_computation" in parts:
        names.add("remat")
    elif "transpose(jvp(fwd))" in parts:
        names.add("bwd")
    elif "jvp(fwd)" in parts and not any(
            p.startswith("transpose(") for p in parts):
        names.add("fwd")
    return names | {b for b in BLOCKS if b in parts}


def scope_times(trace: tr.Trace, lo: float, hi: float,
                op_scopes: dict) -> dict:
    """Device time inside ``[lo, hi]`` per phase and block, in ns per chip:
    for each name, the union of the leaf operations whose scope path
    (``op_scopes``, instruction name -> path) names it, averaged over the
    chips. Leaves that name no phase (an instruction absent from
    ``op_scopes`` among them) count under ``"unscoped"``. A name that no
    leaf in the window carries is left out."""
    per_name: dict = {}
    named: dict = {}  # instruction name -> the names it counts under
    phases = set(PHASES)
    for chip, ops in trace.ops.items():
        ivs: dict = {}
        for n, s, e, _ in tr.leaves(ops):
            if n not in named:
                names = scope_names(op_scopes.get(n, ""))
                named[n] = names if names & phases else names | {"unscoped"}
            for name in named[n]:
                ivs.setdefault(name, []).append((s, e))
        for name, iv in ivs.items():
            cut = tr.merge(tr.clip(iv, lo, hi))
            if cut:
                per_name.setdefault(name, 0.0)
                per_name[name] += tr.length(cut)
    n_chips = max(len(trace.ops), 1)
    return {name: ns / n_chips for name, ns in per_name.items()}


def step_scopes(trace: tr.Trace, lo: float, hi: float) -> dict:
    """``scopes_from_hlo`` of the executable, among those this process
    holds, whose instructions cover most of the leaf time in ``[lo, hi]``:
    the step that ran there."""
    import jax

    leaf_ns: dict = {}
    for ops in trace.ops.values():
        for n, s, e, _ in tr.leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                leaf_ns[n] = leaf_ns.get(n, 0.0) + d
    best, best_ns = "", 0.0
    for ex in jax.devices()[0].client.live_executables():
        text = ex.get_hlo_text()
        names = set(_NAMES.findall(text))
        covered = sum(v for n, v in leaf_ns.items() if n in names)
        if covered > best_ns:
            best, best_ns = text, covered
    return scopes_from_hlo(best)


def scope_ms(ctx, name: str):
    """Device ms per step under ``name`` in the traced window ``ctx`` (see
    ``bench/metrics/__init__.py``), or ``None`` where no leaf carries it.
    The readers of one window share one reading, kept in ``ctx.phase_ns``."""
    if getattr(ctx, "phase_ns", None) is None:
        ctx.phase_ns = scope_times(ctx.trace, ctx.lo, ctx.hi,
                                   step_scopes(ctx.trace, ctx.lo, ctx.hi))
    ns = ctx.phase_ns.get(name)
    return None if ns is None else ns * 1e-6 / ctx.steps
