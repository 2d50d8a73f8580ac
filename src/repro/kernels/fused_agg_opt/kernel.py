"""Pallas TPU kernel: K-way gradient aggregation fused with the optimizer.

This is the PHub hot loop ("locality-preserving, vectorized implementation of
aggregator and optimizer"): each PS micro-shard sums the K worker gradient
slabs for the chunks it owns and applies the optimizer update in the *same*
VMEM-resident pass -- gradients, parameters and optimizer state are each read
from HBM exactly once and written at most once, which is the paper's
locality argument transplanted from CPU cache lines to the TPU HBM->VMEM
hierarchy.

Layout: a slab of N elements (N a multiple of the 8*128 f32 tile) is viewed
as (N/128, 128).  Blocks are (block_rows, 128) with block_rows a multiple of
8, one grid step per block; the K gradient slabs are delivered as a single
(K, block_rows, 128) block so the aggregation loop is fully unrolled in
registers.

Traced scalars (lr*schedule, Adam bias corrections) arrive via a (1, 4) SMEM
operand; static hyperparameters (betas, eps, weight decay, momentum) are
closed over as Python constants.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.optim.optimizers import OptimizerSpec

LANES = 128
SUBLANES = 8


def _block_rows(rows: int, target: int = 256) -> int:
    """Largest multiple of SUBLANES*8=64 that divides rows, capped at target."""
    unit = SUBLANES * 8
    chunks = rows // unit
    best = unit
    for d in range(1, target // unit + 1):
        if chunks % d == 0:
            best = unit * d
    return min(best, rows)


def fence(x: jax.Array, tok: jax.Array) -> jax.Array:
    """Force ``x`` to round to f32 before any consumer sees it.

    f32 mul-then-add must stay two rounded ops for the fused wire path's
    cross-program bit-parity invariant (tests/test_wire_path.py): whether
    the backend contracts ``a*b + c`` into a single-rounding FMA depends
    on the surrounding fusion shape, so the same optimizer body can give
    different last bits in two different programs.  Routing every product
    that feeds an add through this fence pins strict mul-then-add
    semantics in *every* program that shares these bodies.

    The mechanism is a ``lax.cond`` on a runtime token: conditional
    branches are separate XLA computations, so the branch result is a
    rounded f32 value by the time the enclosing computation adds it —
    contraction cannot reach across the boundary.  Nothing weaker
    survives this backend: ``optimization_barrier``, ``reduce_precision``
    (an f32->f32 no-op), trip-count-1 loop carries and
    ``--xla_cpu_enable_fast_math=false`` all still produce FMAs here.
    ``tok`` is the scalar packet's fence token (see ``ops.scalar_packet``):
    always ``0.0`` at runtime but opaque to constant folding, so the
    predicate ``tok < 1`` is not simplifiable and the taken branch
    returns ``x`` unchanged.
    """
    return jax.lax.cond(tok < jnp.float32(1.0), lambda v: v, lambda v: v + tok, x)


def _agg(grads_ref, inv_k: float, tok) -> jax.Array:
    k = grads_ref.shape[0]
    acc = grads_ref[0].astype(jnp.float32)
    for i in range(1, k):
        acc = acc + grads_ref[i].astype(jnp.float32)
    return fence(acc * inv_k, tok)


# -- elementwise optimizer bodies -------------------------------------------
# Shared between this kernel and kernels/wire_path: both must run the SAME
# op sequence on the aggregated gradient for the fused wire path's
# bit-parity invariant to hold structurally (tests/test_wire_path.py), so
# the update math lives in exactly one place.  All values are f32.

def sgd_body(spec: OptimizerSpec, lr, tok, g, p) -> jax.Array:
    """One SGD element update; returns the new param value."""
    if spec.weight_decay:
        g = g + fence(spec.weight_decay * p, tok)
    return p - fence(lr * g, tok)


def momentum_body(spec: OptimizerSpec, lr, tok, g, p, m) -> tuple:
    """One (Nesterov-capable) momentum update; returns (param, momentum)."""
    if spec.weight_decay:
        g = g + fence(spec.weight_decay * p, tok)
    m = fence(spec.momentum * m, tok) + g
    upd = g + fence(spec.momentum * m, tok) if spec.nesterov else m
    return p - fence(lr * upd, tok), m


def adam_body(spec: OptimizerSpec, lr, bc1, bc2, tok, g, p, m, v) -> tuple:
    """One Adam/AdamW update; returns (param, m, v).

    ``bc1``/``bc2`` are the step's bias corrections ``1/(1-beta^t)``,
    computed outside the kernel (see ops.scalar_packet)."""
    if spec.name == "adam" and spec.weight_decay:
        g = g + fence(spec.weight_decay * p, tok)
    m = fence(spec.beta1 * m, tok) + fence((1.0 - spec.beta1) * g, tok)
    v = fence(spec.beta2 * v, tok) + fence((1.0 - spec.beta2) * (g * g), tok)
    mhat = m * bc1
    vhat = v * bc2
    upd = mhat / (jnp.sqrt(vhat) + spec.eps)
    if spec.name == "adamw" and spec.weight_decay:
        upd = upd + fence(spec.weight_decay * p, tok)
    return p - fence(lr * upd, tok), m, v


def _sgd_kernel(spec: OptimizerSpec, inv_k, scal_ref, grads_ref, param_ref, p_out):
    tok = scal_ref[0, 3]
    g = _agg(grads_ref, inv_k, tok)
    p = param_ref[...].astype(jnp.float32)
    new_p = sgd_body(spec, scal_ref[0, 0], tok, g, p)
    p_out[...] = new_p.astype(p_out.dtype)


def _momentum_kernel(
    spec: OptimizerSpec, inv_k, scal_ref, grads_ref, param_ref, m_ref, p_out, m_out
):
    tok = scal_ref[0, 3]
    g = _agg(grads_ref, inv_k, tok)
    p = param_ref[...].astype(jnp.float32)
    new_p, new_m = momentum_body(spec, scal_ref[0, 0], tok, g, p, m_ref[...])
    p_out[...] = new_p.astype(p_out.dtype)
    m_out[...] = new_m


def _adam_kernel(
    spec: OptimizerSpec,
    inv_k,
    scal_ref,
    grads_ref,
    param_ref,
    m_ref,
    v_ref,
    p_out,
    m_out,
    v_out,
):
    tok = scal_ref[0, 3]
    g = _agg(grads_ref, inv_k, tok)
    p = param_ref[...].astype(jnp.float32)
    new_p, new_m, new_v = adam_body(
        spec, scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2], tok, g, p,
        m_ref[...], v_ref[...],
    )
    p_out[...] = new_p.astype(p_out.dtype)
    m_out[...] = new_m
    v_out[...] = new_v


def fused_agg_opt_pallas(
    grads: jax.Array,  # (K, N)
    param: jax.Array,  # (N,)
    state: tuple,  # num_state_slots arrays of (N,) f32
    scalars: jax.Array,  # (1, 4) f32: [lr_t, bc1, bc2, pad]
    spec: OptimizerSpec,
    *,
    average: bool = True,
    interpret: bool,
    block_target: int = 256,
) -> tuple[jax.Array, tuple]:
    """Pallas fused aggregate+optimize over an (K, N) gradient slab.

    One grid step owns a (bm, 128) register block: sum the K worker slabs
    in f32, scale by 1/K (``average``), and apply ``spec``'s optimizer body
    in the same pass — gradients, parameters and state cross HBM once.
    ``scalars`` is the (1, 4) SMEM packet from ``scalar_packet`` ([lr_t,
    bc1, bc2, fence token]); N must be a multiple of the 8·128·8 register
    block.  Returns (new_param, new_state)."""
    k, n = grads.shape
    if n % (SUBLANES * LANES * 8) != 0:
        raise ValueError(f"slab size {n} not a multiple of {SUBLANES*LANES*8}")
    rows = n // LANES
    bm = _block_rows(rows, block_target)
    grid = (rows // bm,)
    inv_k = 1.0 / k if average else 1.0

    g2 = grads.reshape(k, rows, LANES)
    p2 = param.reshape(rows, LANES)
    s2 = tuple(s.reshape(rows, LANES) for s in state)

    scal_spec = pl.BlockSpec((1, 4), lambda i: (0, 0))
    grad_spec = pl.BlockSpec((k, bm, LANES), lambda i: (0, i, 0))
    slab_spec = pl.BlockSpec((bm, LANES), lambda i: (i, 0))

    n_state = spec.num_state_slots
    kern = {
        0: partial(_sgd_kernel, spec, inv_k),
        1: partial(_momentum_kernel, spec, inv_k),
        2: partial(_adam_kernel, spec, inv_k),
    }[n_state]

    out_shape = [jax.ShapeDtypeStruct((rows, LANES), param.dtype)] + [
        jax.ShapeDtypeStruct((rows, LANES), jnp.float32) for _ in range(n_state)
    ]
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[scal_spec, grad_spec, slab_spec] + [slab_spec] * n_state,
        out_specs=[slab_spec] * (1 + n_state),
        out_shape=out_shape,
        interpret=interpret,
    )(scalars, g2, p2, *s2)
    new_p = outs[0].reshape(n)
    new_state = tuple(o.reshape(n) for o in outs[1:])
    return new_p, new_state
