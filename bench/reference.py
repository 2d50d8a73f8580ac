"""The plain reference of a training cell's first steps.

Given a configuration's reference model (``bench/configs/<name>.py``: its
``init_params`` and ``ref_loss``), this runs the first steps of training in
straightforward ``jax.numpy``: the loss and gradient of the whole global
batch, taken in blocks of rows so that it fits one chip, and the optimizer
the configuration states, written out here. It imports nothing of the
program and takes nothing that the program made: the weights come from the
configuration's own ``init_params`` and the batches from the traffic
generator, both from the seed.

``mode`` names the precision of the model's arithmetic: ``"f32"`` is the
reference (float32 at matmul precision "highest"); a lower one makes the
control (see ``bench/check.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

STEPS = 3  # the steps whose results are compared
FAULTS = (None, "half_batch", "one_worker")


@dataclasses.dataclass
class Readings:
    """What is compared after the first steps of one seed."""

    losses: list  # loss of each step, before its update
    grad1: np.ndarray  # per leaf: norm of the first step's gradient
    dparam: np.ndarray  # per leaf: norm of (params after STEPS - initial)


# ---------------------------------------------------------------------------
# the optimizers the configurations state
# ---------------------------------------------------------------------------

def opt_init(opt: dict, params):
    import jax
    import jax.numpy as jnp

    n = {"momentum": 1, "adamw": 2}[opt["name"]]
    return tuple(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                 for _ in range(n))


def opt_step(opt: dict, p, g, state, t: int):
    """One update of f32 ``p`` by f32 gradient ``g`` at 1-based step ``t``."""
    import jax
    import jax.numpy as jnp

    lr, wd = opt["lr"], opt.get("weight_decay", 0.0)
    if opt["name"] == "momentum":
        (m,) = state
        g = jax.tree.map(lambda g_, p_: g_ + wd * p_, g, p)
        m = jax.tree.map(lambda m_, g_: opt["momentum"] * m_ + g_, m, g)
        return jax.tree.map(lambda p_, m_: p_ - lr * m_, p, m), (m,)
    if opt["name"] == "adamw":
        b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
        m, v = state
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1**t, 1 - b2**t

        def upd(p_, m_, v_):
            u = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p_
            return p_ - lr * u

        return jax.tree.map(upd, p, m, v), (m, v)
    raise ValueError(f"unknown optimizer {opt['name']!r}")


def grad_from_first_state(opt: dict, slot0, p0):
    """The first step's gradient, as the optimizer got it, from its first
    state slot after that step (zero-initialised slots)."""
    if opt["name"] == "momentum":  # m1 = g + wd * p0
        return slot0 - opt.get("weight_decay", 0.0) * p0
    if opt["name"] == "adamw":  # m1 = (1 - beta1) * g
        return slot0 / (1.0 - opt["beta1"])
    raise ValueError(f"unknown optimizer {opt['name']!r}")


def round_to(x, dtype):
    """``x`` (float32) rounded to the nearest value of ``dtype``, kept in
    float32. Written as ``reduce_precision``: on a TPU the compiler may drop
    a float32 -> bfloat16 -> float32 round trip of ``astype`` (it allows
    itself the excess precision), and the value is then not rounded."""
    import jax.numpy as jnp
    from jax import lax

    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return lax.reduce_precision(x, exponent_bits=fi.nexp,
                                mantissa_bits=fi.nmant)


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# the first steps
# ---------------------------------------------------------------------------

def fault_rows(fault: str | None, gb: int, chips: int) -> int:
    """Rows of the global batch that a faulty step still takes."""
    if fault is None:
        return gb
    if fault == "half_batch":  # half of the batch left out, mean over the rest
        return gb // 2
    if fault == "one_worker":  # no exchange: chip 0 applies its own share
        return gb // chips
    raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


def run(ref, sizes: dict, batches: list, seed_key, mode: str = "f32",
        fault: str | None = None, chips: int = 1) -> Readings:
    """The reference's first ``STEPS`` steps on ``batches`` (host dicts)."""
    import jax
    import jax.numpy as jnp

    opt = sizes["optimizer"]
    pdt = jnp.dtype(sizes["param_dtype"])
    gb = next(iter(batches[0].values())).shape[0]
    rows = fault_rows(fault, gb, chips)
    block = min(ref.REF_BLOCK_ROWS, rows)
    if rows % block:
        raise ValueError(f"{rows} rows do not split into blocks of {block}")
    dev = jax.devices()[0]
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        # params live as f32 arrays that hold values of the stated dtype
        init = jax.jit(lambda k: jax.tree.map(
            lambda x: round_to(x.astype(f32), pdt), ref.init_params(sizes, k)))
        grad = jax.jit(jax.value_and_grad(
            lambda p, b: ref.ref_loss(p, b, sizes, mode)))
        acc = jax.jit(lambda a, b, w: jax.tree.map(lambda x, y: x + w * y,
                                                   a, b), donate_argnums=0)
        scale = jax.jit(lambda a, w: jax.tree.map(lambda x: w * x, a),
                        donate_argnums=0)

        @jax.jit(donate_argnums=(0, 2))
        def update(p, g, state, t):
            new, state = opt_step(opt, p, g, state, t)
            return jax.tree.map(lambda x: round_to(x, pdt), new), state

        change = jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, init(k))))

        p = init(seed_key)
        state = opt_init(opt, p)
        losses, grad1 = [], None
        for t in range(1, STEPS + 1):
            b = batches[t - 1]
            loss, g = 0.0, None
            for r in range(0, rows, block):
                blk = jax.device_put({k: v[r:r + block] for k, v in b.items()},
                                     dev)
                lb, gbk = grad(p, blk)
                w = block / rows
                loss += float(lb) * w
                g = scale(gbk, w) if g is None else acc(g, gbk, w)
                del gbk
            losses.append(loss)
            if t == 1:
                grad1 = np.asarray(leaf_norms(g))
            p, state = update(p, g, state, t)
            del g
        dp = np.asarray(change(p, seed_key))
    return Readings(losses=losses, grad1=grad1, dparam=dp)
