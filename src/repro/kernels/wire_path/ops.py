"""jit'd public wrappers for the fused wire-path kernel.

Three entry points:

``fused_wire_update``
    the single-pass path: wire payload -> (decode+aggregate+optimize) in
    one Pallas program (or the pure-jnp reference with
    ``use_pallas=False``).

``unfused_wire_update``
    the three-program baseline the fused kernel must match bit-for-bit:
    a dequantize program per int8 stream (kernels/quant), the decoded f32
    gradients materialized between programs, then the aggregate+optimize
    program (kernels/fused_agg_opt).  The fabric's fallback path and the
    parity oracle for tests/benchmarks.

``wire_path_supported``
    the static codec x optimizer x chunk-geometry support matrix the
    fabric's ``fused_wire_path=`` knob consults before routing a push
    through the fused kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fused_agg_opt.ops import fused_aggregate_update, scalar_packet
from repro.kernels.quant.ops import dequantize_chunks
from repro.kernels.wire_path.kernel import LANES, wire_fused_pallas
from repro.kernels.wire_path.ref import fused_wire_update_ref
from repro.optim.optimizers import OptimizerSpec

# per-codec chunk-size granularity for the fused kernel: a chunk's rows
# must fill whole native tiles of the wire dtype so the payload block can
# be staged without repacking — f32 tiles are (8, 128), bf16 (16, 128),
# int8 (32, 128)
_CHUNK_GRANULE = {"none": 8 * LANES, "bf16": 16 * LANES, "int8": 32 * LANES}
_SUPPORTED_OPTS = ("sgd", "momentum", "adam", "adamw")


def wire_path_supported(
    codec: str, spec: OptimizerSpec, chunk_elems: int
) -> bool:
    """Whether the fused kernel can consume this wire format directly.

    True iff the codec is one it decodes in-register (``bf16``/``int8`` —
    codec ``"none"`` has no decode stage to fuse, the raw-f32 path
    already runs single-pass through kernels/fused_agg_opt), the
    optimizer is one of the fused bodies (sgd/momentum/adam/adamw), and
    ``chunk_elems`` fills whole native wire-dtype tiles.  The fabric
    falls back to the unfused three-program path whenever this is False.
    """
    if codec not in ("bf16", "int8"):
        return False
    if spec.name not in _SUPPORTED_OPTS:
        return False
    return chunk_elems > 0 and chunk_elems % _CHUNK_GRANULE[codec] == 0


@partial(
    jax.jit,
    static_argnames=(
        "spec",
        "codec",
        "chunk_elems",
        "average",
        "use_pallas",
        "interpret",
        "block_chunks",
    ),
)
def fused_wire_update(
    payload: jax.Array,  # (K, N) wire-dtype streams
    scales: jax.Array | None,  # (K, N/chunk_elems) f32 (int8), else None
    param: jax.Array,  # (N,) f32
    state: tuple,  # opt state slots, each (N,) f32
    spec: OptimizerSpec,
    step: jax.Array,  # scalar, 1-based
    lr_scale: jax.Array | float = 1.0,
    *,
    codec: str,
    chunk_elems: int,
    average: bool = True,
    use_pallas: bool = True,
    interpret: bool | None = None,
    block_chunks: int | None = None,
) -> tuple[jax.Array, tuple]:
    """Apply K wire streams to ``param``/``state`` in a single pass.

    ``payload`` rows are whole codec'd slabs in ascending stream order
    (the fold order — it is load-bearing for bit-parity with the unfused
    left fold); ``N`` must be a whole number of ``chunk_elems`` chunks.
    Returns ``(new_param, new_state)``, f32, same shapes as the inputs.
    """
    if not use_pallas:
        return fused_wire_update_ref(
            payload,
            scales,
            param,
            state,
            spec,
            step,
            lr_scale,
            codec=codec,
            chunk_elems=chunk_elems,
            average=average,
        )
    scalars = scalar_packet(spec, step, lr_scale)
    return wire_fused_pallas(
        payload,
        scales,
        param,
        state,
        scalars,
        spec,
        codec=codec,
        chunk_elems=chunk_elems,
        average=average,
        interpret=interpret_mode(interpret),
        block_chunks=block_chunks,
    )


def unfused_wire_update(
    payload: jax.Array,
    scales: jax.Array | None,
    param: jax.Array,
    state: tuple,
    spec: OptimizerSpec,
    step: jax.Array,
    lr_scale: jax.Array | float = 1.0,
    *,
    codec: str,
    chunk_elems: int,
    average: bool = True,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, tuple]:
    """The unfused three-program pipeline (decode -> HBM -> agg+opt).

    Deliberately *not* jitted as a whole: each stream's decode runs as
    its own program and the decoded f32 gradients are materialized
    between programs, exactly like the pre-fusion fabric receive path.
    Same signature and return contract as ``fused_wire_update``.
    """
    if codec == "none" or codec == "bf16":
        grads = payload.astype(jnp.float32)
    elif codec == "int8":
        if scales is None:
            raise ValueError("int8 wire streams need per-chunk scales")
        grads = jnp.stack(
            [
                dequantize_chunks(
                    payload[i],
                    scales[i],
                    chunk_elems,
                    use_pallas=use_pallas,
                    interpret=interpret,
                )
                for i in range(payload.shape[0])
            ]
        )
    else:
        raise ValueError(f"unknown wire codec {codec!r}")
    grads = jax.block_until_ready(grads)  # the HBM materialization point
    # the agg+opt kernel wants whole 8*128*8 vector-register slabs; pad
    # with zero grad/param/state rows exactly like PBoxShard.apply (a
    # zero fixed point for every optimizer here)
    n = param.shape[0]
    pad = (-n) % (8 * LANES * 8) if use_pallas else 0
    gf, pf, sf = grads, param, state
    if pad:
        k = grads.shape[0]
        gf = jnp.concatenate([gf, jnp.zeros((k, pad), gf.dtype)], axis=1)
        pf = jnp.concatenate([pf, jnp.zeros((pad,), pf.dtype)])
        sf = tuple(jnp.concatenate([s, jnp.zeros((pad,), s.dtype)]) for s in sf)
    new_p, new_s = fused_aggregate_update(
        gf,
        pf,
        sf,
        spec,
        step,
        lr_scale,
        average=average,
        use_pallas=use_pallas,
        interpret=interpret,
    )
    return new_p[:n], tuple(s[:n] for s in new_s)
