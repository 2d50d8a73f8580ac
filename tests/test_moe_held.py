"""The DeepSeek-V3 block's parts against plain float32 statements of their
equations, at small sizes on the CPU (matmul precision "highest"): the
held-experts layer (``models/moe.moe_ffn_held``) and its grouped matmul,
latent attention (``models/transformer._mla_block``), and the arch on the
training path.

Tolerances: where the program and the plain statement do the same float32
arithmetic in another order, they agree to float32 rounding, and 1e-5 of
the output's scale (relative) holds with room; the capacity path's drops
move its output by a tenth or more of that scale.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.common import Dist
from repro.models.moe import (MoEConfig, gmm, moe_ffn, moe_ffn_held,
                              pick_columns, route_topk, take_rows)

D, F, FS = 128, 128, 256
TOL = 1e-5  # float32 arithmetic in another order (see the module's docstring)


def _cfg(**kw):
    base = dict(n_experts=16, top_k=4, d_ff_expert=F, shared_d_ff=FS,
                score_func="sigmoid", norm_topk=True, routed_scale=2.446,
                seq_aux_coef=1e-2, experts_held=(4, 4))
    return MoEConfig(**{**base, **kw})


def _weights(cfg, key, scale=1.0):
    count = cfg.experts_held[1] if cfg.experts_held else cfg.n_experts
    ks = jax.random.split(key, 7)
    n = lambda k, shape, fan: jax.random.normal(k, shape) / np.sqrt(fan)  # noqa: E731
    return {"router": scale * n(ks[0], (D, cfg.n_experts), D),
            "we1": n(ks[1], (count, D, F), D), "we3": n(ks[2], (count, D, F), D),
            "we2": n(ks[3], (count, F, D), F), "ws1": n(ks[4], (D, FS), D),
            "ws3": n(ks[5], (D, FS), D), "ws2": n(ks[6], (FS, D), FS)}


def _plain_layer(x, w, cfg, experts, bias=None):
    """The expert layer as published, every token through every listed
    expert (index into the held weights, global id), weighted by its gate;
    plus the shared experts. x (T, d)."""
    s = jax.nn.sigmoid(x @ w["router"])
    pick = s if bias is None else s + bias
    top = jnp.argsort(-pick, axis=-1)[:, :cfg.top_k]
    sel = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], top].set(1.0)
    g = s * sel
    g = g / jnp.sum(g, axis=-1, keepdims=True) * cfg.routed_scale
    out = jnp.zeros_like(x)
    for j, e in experts:
        h = jax.nn.silu(x @ w["we1"][j]) * (x @ w["we3"][j])
        out = out + g[:, e:e + 1] * (h @ w["we2"][j])
    return out + (jax.nn.silu(x @ w["ws1"]) * (x @ w["ws3"])) @ w["ws2"]


@jax.jit(static_argnums=0)
def _out_and_grads(fn, x, w, ct):
    out, vjp = jax.vjp(fn, x, w)
    return out, vjp(ct)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_held_layer_with_a_selection_bias_matches_the_plain_layer():
    """A nonzero bias changes which experts are picked, never their
    weights; output and gradients of x and of every weight agree."""
    bias = tuple(float(v) for v in np.linspace(-0.4, 0.4, 16))
    cfg = _cfg(select_bias=bias)
    w = _weights(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, D))
    held = [(j, 4 + j) for j in range(4)]
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        got, g = _out_and_grads(lambda x, w: moe_ffn_held(x, w, cfg)[0], x, w, ct)
        want, gp = _out_and_grads(lambda x, w: _plain_layer(
            x.reshape(-1, D), w, cfg, held, jnp.asarray(bias)).reshape(x.shape),
            x, w, ct)
        unbiased = jax.jit(lambda x, w: _plain_layer(
            x.reshape(-1, D), w, cfg, held).reshape(x.shape))(x, w)
    assert _rel(got, want) < TOL
    assert _rel(got, unbiased) > 10 * TOL  # the bias did change the picks
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gp)):
        assert _rel(a, b) < TOL


def _imbalanced(cfg):
    """Weights under which every token picks experts 4-7 (the held ones)."""
    w = _weights(cfg, jax.random.PRNGKey(3), scale=0.01)
    w["router"] = w["router"].at[:, 4:8].add(1.0)
    return w


def test_held_layer_drops_nothing_under_forced_imbalance():
    cfg = _cfg()
    w = _imbalanced(cfg)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 32, D)))
    with jax.default_matmul_precision("highest"):
        got, _, counters = moe_ffn_held(x, w, cfg)
        want = _plain_layer(x.reshape(-1, D), w, cfg,
                            [(j, 4 + j) for j in range(4)]).reshape(x.shape)
    assert float(counters["rows"]) == 64 * 4  # every assignment is held
    assert float(counters["max_load"]) == 1.0
    assert _rel(got, want) < TOL


def test_the_capacity_path_fails_the_same_check():
    """The capacity path (factor 1.25) under the same routing drops the
    assignments beyond its capacity: its output is far from the layer's."""
    cfg = MoEConfig(n_experts=16, top_k=4, d_ff_expert=F, shared_d_ff=FS,
                    capacity_factor=1.25)
    w = _imbalanced(_cfg(experts_held=None))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (64, D)))
    with jax.default_matmul_precision("highest"):
        got, _ = moe_ffn(x, w, cfg, Dist.none())
        gates, experts, _ = route_topk(x @ w["router"], cfg)
        want = (jax.nn.silu(x @ w["ws1"]) * (x @ w["ws3"])) @ w["ws2"]
        for e in range(16):
            g = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
            h = jax.nn.silu(x @ w["we1"][e]) * (x @ w["we3"][e])
            want = want + g[:, None] * (h @ w["we2"][e])
    assert _rel(got, want) > 0.1


def test_eight_shares_add_up_to_the_uncut_layer():
    """The 8 chips' shares of 64 experts (8 each), with the shared experts
    counted once, add up to the layer that holds all 64."""
    full = _cfg(n_experts=64, top_k=6, experts_held=(0, 64))
    w = _weights(full, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, D))
    with jax.default_matmul_precision("highest"):
        uncut = moe_ffn_held(x, w, full)[0]
        shared = ((jax.nn.silu(x @ w["ws1"]) * (x @ w["ws3"])) @ w["ws2"])
        total = shared
        for c in range(8):
            share = _cfg(n_experts=64, top_k=6, experts_held=(8 * c, 8))
            ws = {**w, **{k: w[k][8 * c:8 * c + 8] for k in ("we1", "we3", "we2")}}
            total = total + moe_ffn_held(x, ws, share)[0] - shared
        plain = _plain_layer(x.reshape(-1, D), w, full,
                             [(e, e) for e in range(64)]).reshape(x.shape)
    assert _rel(total, uncut) < TOL
    assert _rel(uncut, plain) < TOL


def _sorted_assignments(key, t, k, n_experts=16, held=(4, 4)):
    """The layer's indices for t tokens routed to k experts each (drawn at
    random, so a token may pick one expert twice): the assignments sorted by
    held expert, the rest last; each sorted assignment's token; each
    assignment's place in the sorted order; whether each sorted assignment
    is held."""
    experts = jax.random.randint(key, (t * k,), 0, n_experts)
    local = experts - held[0]
    is_held = (local >= 0) & (local < held[1])
    order = jnp.argsort(jnp.where(is_held, local, held[1]), stable=True)
    inv = jnp.zeros((t * k,), order.dtype).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    return order, order // k, inv, is_held[order]


def _gather_forms(form, t=48, k=6):
    """(src, fwd, bwd, fan-in, cotangent) of the layer's two gathers: the
    dispatch of each token to its k sorted assignments, whose cotangent is
    zero on the rows of experts not held; the combine's permutation back."""
    order, tok, inv, held_sorted = _sorted_assignments(jax.random.PRNGKey(10),
                                                       t, k)
    ct = jax.random.normal(jax.random.PRNGKey(11), (t * k, D))
    if form == "dispatch":
        src = jax.random.normal(jax.random.PRNGKey(12), (t, D))
        assert not bool(jnp.all(held_sorted))  # some rows are not held
        return src, tok, inv, k, jnp.where(held_sorted[:, None], ct, 0.0)
    src = jax.random.normal(jax.random.PRNGKey(12), (t * k, D))
    return src, inv, order, 1, ct


@pytest.mark.parametrize("where", ["direct", "remat_scan"])
@pytest.mark.parametrize("form", ["dispatch", "combine"])
def test_take_rows_vjp_matches_autodiff_of_the_take(form, where):
    """The gather's own backward (a gather through ``bwd``, summed over the
    fan-in in float32) gives what autodiff of ``jnp.take`` gives, alone and
    inside a ``jax.checkpoint``-ed ``lax.scan`` over two layers, as the
    model runs it."""
    src, fwd, bwd, k, ct = _gather_forms(form)
    mine = lambda s, f, b: take_rows(s, f, b, k)  # noqa: E731
    plain = lambda s, f, b: jnp.take(s, f, axis=0)  # noqa: E731
    if where == "remat_scan":
        layers = lambda a: jnp.stack([a, a[::-1]])  # noqa: E731
        src, ct = jnp.stack([src, 2.0 * src]), layers(ct)
        fwd, bwd = jnp.stack([fwd, fwd]), jnp.stack([bwd, bwd])

        def scanned(fn):
            body = jax.checkpoint(lambda c, xs: (c, fn(*xs)))
            return lambda s, f, b: jax.lax.scan(body, None, (s, f, b))[1]

        mine, plain = scanned(mine), scanned(plain)
    got, g = _out_and_grads(lambda s, _: mine(s, fwd, bwd), src, 0.0, ct)
    want, gp = _out_and_grads(lambda s, _: plain(s, fwd, bwd), src, 0.0, ct)
    assert bool(jnp.all(got == want))
    assert _rel(g[0], gp[0]) < 1e-6


def test_pick_columns_vjp_matches_autodiff_of_take_along_axis():
    s = jax.random.normal(jax.random.PRNGKey(13), (64, 16))
    _, idx = jax.lax.top_k(s, 6)
    ct = jax.random.normal(jax.random.PRNGKey(14), (64, 6))
    got, g = _out_and_grads(lambda s, i: pick_columns(s, i), s, idx, ct)
    want, gp = _out_and_grads(
        lambda s, i: jnp.take_along_axis(s, i, axis=-1), s, idx, ct)
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(g[0] == gp[0]))  # one term each: exact


_SCATTER = re.compile(r'"stablehlo\.scatter"\(.*?\n\s*\}\) : \(tensor<([^>]*)>',
                      re.S)


def test_the_held_layer_gradient_scatters_no_floating_point_rows():
    """No scatter of floating-point rows is left in the lowered gradient of
    the layer (x and every weight): the gathers' backward gathers, the
    router's pick compares.  What scatters is integer (the inverse
    permutation, the experts' counts) or megablox's 1-D histogram of row
    tiles in the grouped matmul's metadata."""
    cfg = _cfg(top_k=6)
    w = _weights(cfg, jax.random.PRNGKey(15))
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 32, D))
    loss = lambda x, w: jnp.sum(moe_ffn_held(x, w, cfg)[0])  # noqa: E731
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).as_text()
    operands = [t.split("x") for t in _SCATTER.findall(text)]
    assert [str(x.size // D * 6), "i32"] in operands  # the inverse permutation
    floats = [o for o in operands if re.fullmatch(r"b?f\d+", o[-1])]
    assert all(len(o) == 2 for o in floats), floats  # 1-D only


def test_grouped_matmul_against_ragged_dot_and_the_dense_loop():
    """Rows of each group through its expert; rows of the extra last group
    come out zero; gradients of both operands agree."""
    sizes = jnp.array([100, 0, 156, 30, 226], jnp.int32)  # 4 experts + rest
    lhs = jax.random.normal(jax.random.PRNGKey(7), (512, D))
    rhs = jax.random.normal(jax.random.PRNGKey(8), (4, D, F))
    starts = np.concatenate([[0], np.cumsum(np.asarray(sizes))])
    ct = jax.random.normal(jax.random.PRNGKey(9), (512, F))
    with jax.default_matmul_precision("highest"):
        got, g1 = _out_and_grads(lambda a, b: gmm(a, b, sizes), lhs, rhs, ct)
        ragged, g2 = _out_and_grads(
            lambda a, b: jax.lax.ragged_dot(a, b, sizes[:4]), lhs, rhs, ct)
        dense = jnp.zeros((512, F))
        for g in range(4):
            rows = slice(int(starts[g]), int(starts[g + 1]))
            dense = dense.at[rows].set(lhs[rows] @ rhs[g])
    assert bool(jnp.all(got[int(starts[4]):] == 0))
    assert _rel(got, dense) < TOL and _rel(ragged, dense) < TOL
    for a, b in zip(g1, g2):
        assert _rel(a, b) < TOL


def _mla_cfg():
    return T.TransformerConfig(
        name="mla", n_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
        head_dim=24, d_ff=64, vocab=64, rope_theta=5e4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8)


def _plain_mla(x, lp, cfg):
    """Latent attention written out head by head over the whole S x S
    matrix, RoPE on the two halves of the rope dims."""
    b, s, _ = x.shape
    r, dn, dr, dv = 16, 16, 8, 12
    pos = jnp.arange(s, dtype=jnp.float32)
    inv = 1.0 / 5e4 ** (jnp.arange(dr // 2) / (dr // 2))
    cos, sin = jnp.cos(pos[:, None] * inv), jnp.sin(pos[:, None] * inv)

    def rope(z):  # (B, S, dr)
        z1, z2 = z[..., :dr // 2], z[..., dr // 2:]
        return jnp.concatenate([z1 * cos - z2 * sin, z1 * sin + z2 * cos], -1)

    kva = x @ lp["wkv_a"]
    c = kva[..., :r]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6) * (1 + lp["kv_ln"])
    k_pe = rope(kva[..., r:])
    heads = []
    for h in range(4):
        qh = (x @ lp["wq"])[..., h * 24:(h + 1) * 24]
        kvh = (c @ lp["wkv_b"])[..., h * 28:(h + 1) * 28]
        q = jnp.concatenate([qh[..., :dn], rope(qh[..., dn:])], -1)
        k = jnp.concatenate([kvh[..., :dn], k_pe], -1)
        sc = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(24.0)
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        heads.append(jax.nn.softmax(sc, -1) @ kvh[..., dn:])
    return jnp.concatenate(heads, -1) @ lp["wo"]


def test_mla_block_output_and_gradients_match_the_plain_block():
    cfg = _mla_cfg()
    lp = jax.tree.map(lambda a: a[0], T.init_params(cfg, jax.random.PRNGKey(0))["layers"])
    lp["kv_ln"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), lp["kv_ln"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    keys = ("wq", "wkv_a", "kv_ln", "wkv_b", "wo")
    sub = {k: lp[k] for k in keys}
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    with jax.default_matmul_precision("highest"):
        got, g = _out_and_grads(lambda x, p: T._mla_block(x, p, cfg, pos),
                                x, sub, ct)
        want, gp = _out_and_grads(lambda x, p: _plain_mla(x, p, cfg), x, sub, ct)
    assert _rel(got, want) < TOL
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gp)):
        assert _rel(a, b) < TOL


@pytest.mark.parametrize("builder", ["prefill", "decode", "tp2"])
def test_the_deepseek_block_refuses_what_it_does_not_support(builder):
    from repro.configs.registry import get_arch

    cfg = get_arch("moonlight-16b-a3b").smoke_config
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="DeepSeek-V3"):
        if builder == "prefill":
            T.prefill(params, toks, cfg, Dist.none(), 1, 16)
        elif builder == "decode":
            T.decode_step(params, toks[:, 0], {}, jnp.int32(0), cfg,
                          Dist.none(), 1)
        else:
            T.make_param_specs(cfg, 2)


def test_moonlight_trains_through_the_ps_and_its_loss_falls():
    """``launch/train.py --arch moonlight-16b-a3b`` (SMOKE) through the PS
    exchange: the counters are logged, every assignment of the smoke batch
    is held, and the loss falls."""
    from repro.launch import train

    out = train.main(["--arch", "moonlight-16b-a3b", "--steps", "12",
                      "--log-every", "100"])
    losses = out["losses"]
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.02, losses
