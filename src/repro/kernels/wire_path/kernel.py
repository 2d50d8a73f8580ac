"""Pallas TPU kernel: codec decode + K-way aggregate + optimizer, one pass.

The paper's "streamlined gradient processing pipeline" argument, applied
to the wire: the unfused receive path runs a dequantize program per
stream (kernels/quant), materializes the decoded f32 gradients in HBM,
then re-reads them in the aggregate+optimize program
(kernels/fused_agg_opt).  This kernel consumes the wire bytes directly —
int8 payload + per-chunk f32 scales, bf16, or raw f32 — so the decoded
gradients live only in VMEM and each HBM buffer is touched exactly once.

Layout: K streams of C chunks (chunk_elems = R*128 elements each) arrive
as a (K, C*R, 128) payload in wire dtype, plus a per-chunk f32 scale
operand for int8, viewed as (C, K, 1) so that its (cb, K, 1) block ends in
the array's own last two dimensions (the TPU compiler refuses a (K, cb)
block of a (K, C) array, whose lane dimension is not a multiple of 128).
One grid step covers a *block* of ``cb`` chunks (cb divides C, so no
padding is ever needed); params/optimizer state ride in matching
(cb*R, 128) f32 blocks.

Double-buffered chunk staging: inside a grid step, chunks pipeline
through a 2-slot VMEM scratch buffer (2, K, R, 128) — the decode of chunk
``i+1`` into slot ``(i+1)%2`` is issued *before* the aggregate+optimize
of chunk ``i`` drains slot ``i%2``, so on hardware the VPU decode of the
next chunk overlaps the fold/update of the current one (the overlap
``core/fabric.py``'s event clock models with its one-chunk-in-flight wire
stage).  The loop is unrolled (cb is a small static), so slots are
resolved at trace time and no dynamic indexing is needed.

Bit-parity with the unfused path is structural, not accidental: the
staged decode is the exact expression of ``kernels/quant``'s dequant
kernel, the fold is ascending-stream left addition exactly like
``fused_agg_opt._agg``, and the optimizer math is literally shared
(``fused_agg_opt.kernel``'s ``*_body`` helpers).  Every product that
feeds an add — the int8 decode multiply included — goes through
``fused_agg_opt.kernel.fence``, which pins strict mul-then-add rounding
in both programs so backend FMA contraction cannot change the bits (the
staging write plays the role of the unfused path's HBM round-trip).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_agg_opt.kernel import (
    LANES,
    adam_body,
    fence,
    momentum_body,
    sgd_body,
)
from repro.optim.optimizers import OptimizerSpec

WIRE_DTYPES = {"none": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


def _chunks_per_block(c: int, rows_per_chunk: int, target_rows: int = 512) -> int:
    """Largest divisor of ``c`` keeping the block within ~``target_rows``
    rows of 128 lanes (VMEM budget); at least 1 chunk per block."""
    best = 1
    limit = max(1, target_rows // rows_per_chunk)
    for d in range(1, min(c, limit) + 1):
        if c % d == 0:
            best = d
    return best


def _wire_kernel(
    spec: OptimizerSpec,
    inv_k: float,
    codec: str,
    k: int,
    r: int,
    cb: int,
    *refs,
):
    """One grid step: decode+apply ``cb`` chunks through the 2-slot stage."""
    scal_ref, pay_ref = refs[0], refs[1]
    idx = 2
    scale_ref = None
    if codec == "int8":
        scale_ref = refs[idx]
        idx += 1
    n_state = spec.num_state_slots
    param_ref = refs[idx]
    state_refs = refs[idx + 1 : idx + 1 + n_state]
    p_out = refs[idx + 1 + n_state]
    s_outs = refs[idx + 2 + n_state : idx + 2 + 2 * n_state]
    stage_ref = refs[-1]
    tok = scal_ref[0, 3]

    def stage(j: int, slot: int) -> None:
        """Decode chunk ``j`` of the block into VMEM slot ``slot``."""
        # the exact expression of the unfused dequant kernel
        # (q.astype(f32) * scale for int8; dtype widening otherwise)
        blk = pay_ref[:, j * r : (j + 1) * r, :].astype(jnp.float32)
        if codec == "int8":
            blk = blk * scale_ref[j].reshape(k, 1, 1)
        # the fence pins the decoded value to rounded f32 before the fold
        # reads it back — the staging slot is the kernel's stand-in for
        # the unfused path's HBM materialization, so it must be a real
        # rounding point, not something fusion can see through
        stage_ref[slot] = fence(blk, tok)

    def drain(j: int, slot: int) -> None:
        """Aggregate staged chunk ``j`` and apply the optimizer body."""
        # ascending-stream left fold (fused_agg_opt._agg's add order),
        # then the same fenced inv_k multiply as fused_agg_opt._agg
        # (see ``fence`` there for why)
        acc = stage_ref[slot, 0]
        for i in range(1, k):
            acc = acc + stage_ref[slot, i]
        g = fence(acc * inv_k, tok)
        lo, hi = j * r, (j + 1) * r
        p = param_ref[lo:hi, :].astype(jnp.float32)
        lr = scal_ref[0, 0]
        if n_state == 0:
            new_p = sgd_body(spec, lr, tok, g, p)
            p_out[lo:hi, :] = new_p.astype(p_out.dtype)
        elif n_state == 1:
            new_p, new_m = momentum_body(spec, lr, tok, g, p, state_refs[0][lo:hi, :])
            p_out[lo:hi, :] = new_p.astype(p_out.dtype)
            s_outs[0][lo:hi, :] = new_m
        else:
            new_p, new_m, new_v = adam_body(
                spec,
                lr,
                scal_ref[0, 1],
                scal_ref[0, 2],
                tok,
                g,
                p,
                state_refs[0][lo:hi, :],
                state_refs[1][lo:hi, :],
            )
            p_out[lo:hi, :] = new_p.astype(p_out.dtype)
            s_outs[0][lo:hi, :] = new_m
            s_outs[1][lo:hi, :] = new_v

    # software pipeline: decode of chunk j+1 is issued before the
    # aggregate of chunk j consumes its slot
    stage(0, 0)
    for j in range(cb):
        if j + 1 < cb:
            stage(j + 1, (j + 1) % 2)
        drain(j, j % 2)


def wire_fused_pallas(
    payload: jax.Array,  # (K, N) wire dtype (int8 / bf16 / f32)
    scales: jax.Array | None,  # (K, N/chunk_elems) f32, int8 codec only
    param: jax.Array,  # (N,) f32
    state: tuple,  # num_state_slots arrays of (N,) f32
    scalars: jax.Array,  # (1, 4) f32: [lr_t, bc1, bc2, pad]
    spec: OptimizerSpec,
    *,
    codec: str,
    chunk_elems: int,
    average: bool = True,
    interpret: bool,
    block_chunks: int | None = None,
) -> tuple[jax.Array, tuple]:
    """Run the fused wire kernel; returns ``(new_param, new_state)``."""
    if codec not in WIRE_DTYPES:
        raise ValueError(f"unknown wire codec {codec!r}")
    k, n = payload.shape
    if chunk_elems % LANES:
        raise ValueError(f"chunk_elems {chunk_elems} not a multiple of {LANES}")
    if n == 0 or n % chunk_elems:
        raise ValueError(f"slab size {n} not whole chunks of {chunk_elems}")
    c = n // chunk_elems
    r = chunk_elems // LANES
    cb = block_chunks if block_chunks is not None else _chunks_per_block(c, r)
    if cb < 1 or c % cb:
        raise ValueError(f"block_chunks {cb} does not divide {c} chunks")
    rows = c * r
    inv_k = 1.0 / k if average else 1.0

    pay2 = payload.reshape(k, rows, LANES)
    p2 = param.reshape(rows, LANES)
    s2 = tuple(s.reshape(rows, LANES) for s in state)

    scal_spec = pl.BlockSpec((1, 4), lambda i: (0, 0))
    pay_spec = pl.BlockSpec((k, cb * r, LANES), lambda i: (0, i, 0))
    slab_spec = pl.BlockSpec((cb * r, LANES), lambda i: (i, 0))

    in_specs = [scal_spec, pay_spec]
    operands: list = [scalars, pay2]
    if codec == "int8":
        if scales is None:
            raise ValueError("int8 wire streams need per-chunk scales")
        in_specs.append(pl.BlockSpec((cb, k, 1), lambda i: (i, 0, 0)))
        operands.append(scales.reshape(k, c).T.reshape(c, k, 1))

    n_state = spec.num_state_slots
    in_specs += [slab_spec] * (1 + n_state)
    operands += [p2, *s2]

    out_shape = [jax.ShapeDtypeStruct((rows, LANES), param.dtype)] + [
        jax.ShapeDtypeStruct((rows, LANES), jnp.float32) for _ in range(n_state)
    ]
    outs = pl.pallas_call(
        partial(_wire_kernel, spec, inv_k, codec, k, r, cb),
        grid=(c // cb,),
        in_specs=in_specs,
        out_specs=[slab_spec] * (1 + n_state),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, k, r, LANES), jnp.float32)],
        interpret=interpret,
    )(*operands)
    new_p = outs[0].reshape(n)
    new_state = tuple(o.reshape(n) for o in outs[1:])
    return new_p, new_state
