"""Mixture-of-Experts FFN: two expert layers.

**Held experts, dropless** (``MoEConfig.experts_held`` set; the DeepSeek-V3
block).  The layer is told which experts this chip holds (``first``,
``count``).  It routes every token over all ``n_experts`` (sigmoid or
softmax scores, a selection bias that picks experts but does not weight
them, normalized top-k weights times ``routed_scale``), computes only the
held experts' part of the result, and drops no assignment whatever the
imbalance: the assignments are sorted by expert, the held ones first, and a
grouped matmul over the held experts (``gmm``, JAX's megablox kernel) does
work that follows the rows routed to them.  Its buffers are static and
sized for the worst case, every assignment held.  What the experts held on
other chips would add is left out; the shared experts are computed on every
chip.  The rows move to and from the sorted order by gathers whose backward
is a gather too, through the inverse permutation (:func:`take_rows`), and
the router's pick of its top-k scores differentiates by a compare
(:func:`pick_columns`): the layer's gradient scatters no floating-point
rows.  :func:`moe_ffn_held`.

**Capacity, tensor-parallel** (``experts_held`` unset; granite and
qwen2-moe), :func:`moe_ffn`:

Experts are *tensor-parallel* over the ``model`` axis (every device holds a
1/tp slice of every expert's hidden dim): routing and dispatch are computed
identically on all model-shards, expert matmuls produce partial outputs, and
one ``psum`` (shared with the dense path) completes the block.  This keeps
expert count free of mesh-divisibility constraints (60 experts on a 16-way
axis) and adds no all-to-all; an expert-parallel dispatch variant is a
planned beyond-paper optimization (see EXPERIMENTS.md §Perf).

Dispatch uses the GShard/Switch capacity pattern, built from argsort (no
(T, E, C) one-hot): sort assignments by expert, compute each assignment's
rank within its expert group, drop overflow beyond capacity, and
scatter-gather through an (E, C, d) buffer.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import interpret_mode
from repro.models.common import Dist, act_fn

GMM_ROWS = 512  # row tile of the grouped matmul


def _tile(dim: int) -> int:
    """The grouped matmul's tile of a contracted or output dimension: the
    largest of 512, 256 and 128 that divides it (on a v5e at d 2048, f 1408
    this ran the SwiGLU's forward and backward 3.9x faster than tiles of
    128; PERF.md)."""
    return next((t for t in (512, 256, 128) if dim % t == 0), min(128, dim))


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    shared_d_ff: int = 0  # 0 = no shared expert
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_dtype: str = "float32"
    # the held-experts path (all of these are read only where experts_held
    # is set; the capacity path keeps softmax, normalized top-k, scale 1)
    score_func: str = "softmax"  # "softmax" | "sigmoid"
    norm_topk: bool = True  # renormalize the selected experts' scores to 1
    routed_scale: float = 1.0  # times the routed experts' weights
    seq_aux_coef: float = 0.0  # alpha of the sequence-wise balance loss
    experts_held: tuple[int, int] | None = None  # (first, count) on this chip
    select_bias: tuple[float, ...] | None = None  # b: picks, never weights

    def capacity(self, tokens: int) -> int:
        c = int(self.capacity_factor * tokens * self.top_k / self.n_experts)
        return max(8, -(-c // 8) * 8)


def route_topk(logits: jax.Array, cfg: MoEConfig):
    """logits (T, E) -> (weights (T,k), experts (T,k), aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)
    vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    t = logits.shape[0]
    onehot = jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32)
    f = jnp.mean(onehot, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = cfg.aux_loss_coef * cfg.n_experts * jnp.sum(f * p)
    return vals, idx, aux


def dispatch_indices(experts: jax.Array, cfg: MoEConfig, capacity: int):
    """experts (T, k) -> (buf_pos (T*k,), keep (T*k,)) where buf_pos indexes a
    flattened (E*C) expert buffer."""
    tk = experts.size
    flat_e = experts.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)  # assignments grouped by expert
    sorted_e = flat_e[order]
    # rank within the expert group
    counts = jnp.bincount(flat_e, length=cfg.n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(tk) - starts[sorted_e]
    rank = jnp.zeros((tk,), rank_sorted.dtype).at[order].set(rank_sorted)
    keep = rank < capacity
    buf_pos = jnp.where(keep, flat_e * capacity + rank, 0)
    return buf_pos, keep


def moe_ffn(
    x: jax.Array,  # (T, d) tokens
    weights: dict,  # router (d,E); we1/we3 (E,d,Fe_loc); we2 (E,Fe_loc,d);
    # optional ws1/ws3 (d,Fs_loc), ws2 (Fs_loc,d)
    cfg: MoEConfig,
    dist: Dist,
    act: str = "silu",
):
    """Returns (partial output (T, d) — caller psums over model —, aux_loss)."""
    t, d = x.shape
    logits = x.astype(jnp.float32) @ weights["router"].astype(jnp.float32)
    gate_w, gate_e, aux = route_topk(logits, cfg)

    capacity = cfg.capacity(t)
    buf_pos, keep = dispatch_indices(gate_e, cfg, capacity)
    tok_of_assign = jnp.repeat(jnp.arange(t), cfg.top_k)

    # scatter tokens into the (E*C, d) buffer (dropped assignments write to a
    # scratch row which is ignored on the way back)
    buf = jnp.zeros((cfg.n_experts * capacity, d), x.dtype)
    src = jnp.where(keep, buf_pos, cfg.n_experts * capacity - 1)
    buf = buf.at[src].set(
        jnp.where(keep[:, None], x[tok_of_assign], 0.0).astype(x.dtype)
    )
    buf = buf.reshape(cfg.n_experts, capacity, d)

    # expert SwiGLU over the local hidden slice
    a = act_fn(act)
    h = a(jnp.einsum("ecd,edf->ecf", buf, weights["we1"])) * jnp.einsum(
        "ecd,edf->ecf", buf, weights["we3"]
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, weights["we2"])
    out_buf = out_buf.reshape(cfg.n_experts * capacity, d)

    # combine: weighted gather back to tokens
    per_assign = out_buf[buf_pos] * (gate_w.reshape(-1) * keep)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(per_assign, tok_of_assign, num_segments=t)

    if cfg.shared_d_ff:
        hs = a(x @ weights["ws1"]) * (x @ weights["ws3"])
        out = out + hs @ weights["ws2"]
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# held experts, dropless (DeepSeek-V3)
# ---------------------------------------------------------------------------

def pick_columns(s: jax.Array, idx: jax.Array) -> jax.Array:
    """``jnp.take_along_axis(s, idx, axis=-1)`` for ``s`` (T, E) and ``idx``
    (T, k) of distinct columns in each row (top-k's).  The backward places
    each row's ``k`` cotangents by comparing ``idx`` with the column ids, where
    autodiff of the take would scatter-add T * k scalars into (T, E)."""
    return _pick_columns(s, idx, s.shape[-1])


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pick_columns(s, idx, e):
    return jnp.take_along_axis(s, idx, axis=-1)


def _pick_columns_fwd(s, idx, e):
    return jnp.take_along_axis(s, idx, axis=-1), idx


def _pick_columns_bwd(e, idx, ct):
    hit = idx[..., None] == jnp.arange(e, dtype=idx.dtype)  # (T, k, E)
    return jnp.sum(jnp.where(hit, ct[..., None], 0), axis=1), None


_pick_columns.defvjp(_pick_columns_fwd, _pick_columns_bwd)


def route_held(logits: jax.Array, cfg: MoEConfig, seq_len: int):
    """logits (T, E) f32 over all experts -> (weights (T, k), experts (T, k),
    sequence-wise balance loss).

    Scores ``s`` are ``sigmoid`` (or ``softmax``) of the logits; the experts
    are the top-k of ``s + b`` (``cfg.select_bias``, zero where unset); the
    weights are ``s`` at the selected experts, normalized to sum 1 where
    ``cfg.norm_topk``, times ``cfg.routed_scale``.  The balance loss
    (DeepSeek-V3, arXiv:2412.19437, eqs. 17-20) is, per sequence of
    ``seq_len`` tokens, ``alpha * sum_i f_i * P_i`` with ``f_i`` the share
    of the sequence's selections that went to expert ``i`` times ``E / k``
    and ``P_i`` the mean over the sequence of ``s_i / sum_j s_j``; it is the
    mean over sequences."""
    t, e = logits.shape
    k = cfg.top_k
    if cfg.score_func == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif cfg.score_func == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown score_func {cfg.score_func!r}")
    pick = s if cfg.select_bias is None else s + jnp.asarray(
        cfg.select_bias, jnp.float32)
    _, idx = jax.lax.top_k(pick, k)
    w = pick_columns(s, idx)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scale
    if cfg.seq_aux_coef:
        nseq = t // seq_len
        chosen = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1)
        f = jnp.mean(chosen.reshape(nseq, seq_len, e), axis=1) * (e / k)
        sn = s / jnp.sum(s, axis=-1, keepdims=True)
        p = jnp.mean(sn.reshape(nseq, seq_len, e), axis=1)
        aux = cfg.seq_aux_coef * jnp.mean(jnp.sum(f * p, axis=-1))
    else:
        aux = jnp.float32(0.0)
    return w, idx, aux


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        interpret: bool | None = None) -> jax.Array:
    """Grouped matmul: rows ``[o_g, o_g + n_g)`` of ``lhs`` (M, K) times
    ``rhs[g]`` (G, K, N) for the first G of ``group_sizes``; rows of a last,
    extra group come out zero and cost nothing.  Output in ``lhs``'s dtype,
    accumulated in f32.  JAX's megablox kernel, differentiable in both
    operands; compiled on a TPU, interpreted elsewhere (``interpret``, as
    ``kernels.interpret_mode`` reads it)."""
    k, n = rhs.shape[1:]
    tiling = (min(GMM_ROWS, lhs.shape[0]), _tile(k), _tile(n))
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None,
                        False, interpret_mode(interpret))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(src: jax.Array, fwd: jax.Array, bwd: jax.Array, k: int):
    """``jnp.take(src, fwd, axis=0)`` where ``fwd`` lists every row of
    ``src`` exactly ``k`` times, row ``r`` at the positions
    ``bwd[r * k : (r + 1) * k]``.  The backward gathers the cotangent's rows
    through ``bwd`` and sums each row's ``k`` in float32, where autodiff of
    the take would scatter-add them (on a v5e the held layer's two
    scatter-adds of 98,304 rows of 2,048 took 7-11 ms a call, about 10x
    their HBM bound; PERF.md)."""
    return jnp.take(src, fwd, axis=0)


def _take_rows_fwd(src, fwd, bwd, k):
    return jnp.take(src, fwd, axis=0), bwd


def _take_rows_bwd(k, bwd, ct):
    parts = [jnp.take(ct, bwd[j::k], axis=0, mode="clip") for j in range(k)]
    if k == 1:
        return parts[0], None, None
    acc = sum(p.astype(jnp.float32) for p in parts)
    return acc.astype(ct.dtype), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def moe_ffn_held(x: jax.Array, weights: dict, cfg: MoEConfig, act: str = "silu"):
    """The held experts' part of a DeepSeek-V3 expert layer, plus the shared
    experts, for x (B, S, d).

    ``weights``: ``router`` (d, E) over all experts; ``we1``/``we3``
    (count, d, F) and ``we2`` (count, F, d) for the held experts
    ``first .. first + count - 1``; ``ws1``/``ws3`` (d, Fs) and ``ws2``
    (Fs, d) for the shared experts.  Returns (out (B, S, d), balance loss,
    counters): ``rows``, the assignments routed to the held experts, and
    ``max_load``, the largest held expert's rows over their mean."""
    b, s, d = x.shape
    t, k = b * s, cfg.top_k
    first, count = cfg.experts_held
    a = act_fn(act)
    xt = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        logits = xt.astype(jnp.float32) @ weights["router"].astype(jnp.float32)
        w, idx, aux = route_held(logits, cfg, s)
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)  # the rest sort last, as group `count`
        order = jnp.argsort(key, stable=True)
        rows = t * k
        pad = -rows % min(GMM_ROWS, rows)
        sizes = jnp.bincount(key, length=count + 1).astype(jnp.int32)
        sizes = sizes.at[count].add(pad)
        tok = order // k  # the token of each sorted assignment
        # each assignment's place in the sorted order
        inv = jnp.zeros((rows,), order.dtype).at[order].set(
            jnp.arange(rows, dtype=order.dtype))
        xs = take_rows(xt, tok, inv, k)
        if pad:
            xs = jnp.concatenate([xs, jnp.zeros((pad, d), xs.dtype)])
    with jax.named_scope("moe_experts"):
        h = a(gmm(xs, weights["we1"], sizes)) * gmm(xs, weights["we3"], sizes)
        ys = gmm(h, weights["we2"], sizes)[:rows]
    with jax.named_scope("moe_route"):
        # back to (token, slot) order, weighted; the rest's rows are zero
        y = take_rows(ys, inv, order, 1).reshape(t, k, d)
        gate = jnp.where(held, w.reshape(-1), 0.0).reshape(t, k)
        out = jnp.einsum("tkd,tk->td", y, gate.astype(y.dtype))
    if cfg.shared_d_ff:
        with jax.named_scope("moe_shared"):
            hs = a(xt @ weights["ws1"]) * (xt @ weights["ws3"])
            out = out + hs @ weights["ws2"]
    n_held = jnp.sum(sizes[:count]).astype(jnp.float32)
    counters = {"rows": n_held,
                "max_load": jnp.max(sizes[:count]).astype(jnp.float32)
                * count / jnp.maximum(n_held, 1.0)}
    return out.reshape(b, s, d), aux, counters
