"""jit'd public wrapper for the fused aggregate+optimize kernel.

Chooses the Pallas kernel (compiled on a TPU, interpreted elsewhere — see
``repro.kernels.interpret_mode``) or the pure-jnp reference,
and computes the traced scalar packet (lr*schedule, Adam bias corrections)
outside the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fused_agg_opt.kernel import fused_agg_opt_pallas
from repro.kernels.fused_agg_opt.ref import fused_aggregate_update_ref
from repro.optim.optimizers import OptimizerSpec


def scalar_packet(spec: OptimizerSpec, step, lr_scale) -> jax.Array:
    """The (1, 4) f32 traced-scalar operand ``[lr_t, bc1, bc2, tok]``.

    ``lr_t`` is the scheduled learning rate (``spec.lr * lr_scale``);
    ``bc1``/``bc2`` are Adam's bias corrections ``1/(1-beta^t)`` for
    1-based ``step`` (1.0 for stateless/momentum optimizers).  ``tok`` is
    the fence token (see ``kernel.fence``): always ``0.0`` at runtime,
    but computed as ``step * 0.0`` so constant folding cannot see through
    it (``0 * x`` is not foldable under strict FP, and ``step`` is a
    traced operand in every caller).  Shared by this kernel and
    kernels/wire_path so both fused programs see bit-identical scalars.
    """
    t = jnp.asarray(step, jnp.float32)
    lr_t = jnp.asarray(spec.lr * lr_scale, jnp.float32)
    if spec.num_state_slots == 2:
        bc1 = 1.0 / (1.0 - spec.beta1**t)
        bc2 = 1.0 / (1.0 - spec.beta2**t)
    else:
        bc1 = jnp.float32(1.0)
        bc2 = jnp.float32(1.0)
    tok = t * jnp.float32(0.0)
    return jnp.stack([lr_t, bc1, bc2, tok]).reshape(1, 4)


@partial(
    jax.jit,
    static_argnames=("spec", "average", "use_pallas", "interpret", "block_target"),
)
def fused_aggregate_update(
    grads: jax.Array,  # (K, N) worker slabs
    param: jax.Array,  # (N,)
    state: tuple,  # opt state slots
    spec: OptimizerSpec,
    step: jax.Array,  # scalar, 1-based
    lr_scale: jax.Array | float = 1.0,
    *,
    average: bool = True,
    use_pallas: bool = True,
    interpret: bool | None = None,
    block_target: int = 256,
) -> tuple[jax.Array, tuple]:
    """Aggregate K worker gradient slabs and apply the server optimizer.

    The public fused hot-loop entry point: sums ``grads`` in f32, averages
    by 1/K when ``average``, then applies ``spec`` at ``step`` (1-based,
    drives Adam bias correction) with ``lr_scale`` folded into the rate.
    Dispatches to the Pallas kernel or, when ``use_pallas=False``, to the
    bit-compatible jnp reference.  Returns (new_param, new_state)."""
    if not use_pallas:
        return fused_aggregate_update_ref(
            grads, param, state, spec, step, lr_scale, average=average
        )
    scalars = scalar_packet(spec, step, lr_scale)
    return fused_agg_opt_pallas(
        grads,
        param,
        state,
        scalars,
        spec,
        average=average,
        interpret=interpret_mode(interpret),
        block_target=block_target,
    )
