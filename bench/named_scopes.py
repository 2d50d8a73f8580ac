"""Device time under any ``jax.named_scope`` of the program's step.

``bench/scopes.py`` reads the step's phases and its three blocks
(``scope_names``). This reads any other component of a leaf's scope path:
the time of the leaves whose path (``scopes.step_scopes``: the step's HLO
metadata, with unnamed instructions charged to their consumers) holds
``name`` as one of its ``/``-separated parts, clipped to the window, merged
and averaged over the chips exactly as ``scopes.scope_times`` does. The
scopes read here:

- ``moe_route``, ``moe_experts``, ``moe_shared``: the parts of the held
  experts' layer (``models/moe.moe_ffn_held``);
- ``attn_kernel``: the attention kernel's call (``models/transformer._attention``);
- ``mla_latent``: the latent projections (``models/transformer._mla_block``).
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import scopes
from bench import trace as tr

BENCH = Path(__file__).resolve().parent


def scope_ns(trace: tr.Trace, lo: float, hi: float, op_scopes: dict,
             name: str) -> float | None:
    """Device ns per chip inside ``[lo, hi]`` of the leaves whose scope path
    holds ``name``; ``None`` where no leaf in the window does."""
    total, seen = 0.0, False
    for ops in trace.ops.values():
        iv = [(s, e) for n, s, e, _ in tr.leaves(ops)
              if name in op_scopes.get(n, "").split("/")]
        cut = tr.merge(tr.clip(iv, lo, hi))
        if cut:
            seen = True
            total += tr.length(cut)
    return total / max(len(trace.ops), 1) if seen else None


def named_ms(ctx, name: str):
    """Device ms per step under the scope ``name`` in the traced window
    ``ctx``, or ``None``. The readers of one window share one look at the
    step's scopes, kept in ``ctx.op_scopes``."""
    if getattr(ctx, "op_scopes", None) is None:
        ctx.op_scopes = scopes.step_scopes(ctx.trace, ctx.lo, ctx.hi)
    ns = scope_ns(ctx.trace, ctx.lo, ctx.hi, ctx.op_scopes, name)
    return None if ns is None else ns * 1e-6 / ctx.steps


def share_of_peak(ctx, name: str, flops_per_step: float):
    """``flops_per_step`` over the device time under ``name`` per step and
    one chip's bf16 peak, in %; ``None`` where nothing ran under ``name``."""
    ms = named_ms(ctx, name)
    if not ms:
        return None
    return 100.0 * flops_per_step / (ms * 1e-3 * ctx.peak_flops)


def cell_counts(config: str, traffic: str):
    """(sizes, mix, reference module) of a configuration and a mix, read by
    path: the readers' context holds no sizes."""
    from bench import harness

    sizes = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    ref = harness.load_module(BENCH / "configs" / f"{config}.py",
                              f"bench_counts_{config}")
    return sizes, mix, ref
