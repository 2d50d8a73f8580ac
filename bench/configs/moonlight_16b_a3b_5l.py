"""Plain reference of ``moonlight_16b_a3b_5l.json``: Moonlight-16B-A3B, the
DeepSeek-V3 block, cut in depth, experts held and vocabulary.

Straightforward ``jax.numpy`` of the published equations (DeepSeek-V3,
arXiv:2412.19437, sections 2.1.1-2.1.2; the model's config.json), written
from them and not from the program:

- token embedding over the vocabulary slice;
- per layer RMSNorm, multi-head latent attention with ``q_lora_rank`` null:
  ``q = h W_q`` split per head into 128 dims without and 64 with RoPE,
  ``[c_kv | k_pe] = h W_kva``, ``c_kv`` RMS-normed, ``[k_nope | v] = c_kv
  W_kvb`` per head, RoPE (the two halves of the 64 dims) on ``q_pe`` and on
  ``k_pe``, which every head shares, causal softmax at ``1/sqrt(192)``, the
  output projection; a residual; RMSNorm; then the FFN and a residual;
- the FFN of the first layer is a SwiGLU of width ``intermediate_size``;
  that of the others is the expert layer: router scores ``s = sigmoid(h
  W_r)`` over all 64 experts in float32, the 6 experts with the largest
  ``s + b`` (``b`` zero), their scores normalized to sum 1 and scaled by
  ``routed_scaling_factor``; the held experts' part of the result, here a
  plain loop over the held experts on every token, each weighted by its
  gate (zero where the token did not select it); plus the shared experts, a
  SwiGLU of width ``n_shared_experts x moe_intermediate_size``;
- a final RMSNorm and the untied head; the loss is the mean next-token
  cross entropy plus, per MoE layer, the sequence-wise balance loss
  ``alpha * sum_i f_i P_i`` (eqs. 17-20) averaged over the sequences.

Norm weights follow the ``(1 + w)`` convention of the parameter tree. To
fit one chip it is computed in blocks: attention by blocks of queries, the
head by blocks of tokens, each layer recomputed in the backward pass. That
changes no result beyond float32 rounding.

``mode`` is the precision of the arithmetic: ``"f32"`` for the reference,
``"fp8"`` for the control (every operand of the configuration's bfloat16
matmuls rounded to float8 e4m3; the router, float32 in the configuration,
is left at float32).
"""
from __future__ import annotations

INPUT = "tokens"
REF_BLOCK_ROWS = 1  # sequences per reference block
CONTROL = "fp8"
Q_BLOCK = 512  # queries per attention block
T_BLOCK = 1024  # tokens per head block


def _dims(sizes: dict) -> dict:
    H = sizes["num_attention_heads"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    return dict(d=sizes["hidden_size"], H=H, dn=dn, dr=dr, dv=dv,
                r=sizes["kv_lora_rank"], ff=sizes["intermediate_size"],
                E=sizes["n_routed_experts"], k=sizes["num_experts_per_tok"],
                held=sizes["n_experts_held"], f=sizes["moe_intermediate_size"],
                fs=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
                V=sizes["vocab"], dense=sizes["first_k_dense_replace"],
                moe=sizes["n_layers"] - sizes["first_k_dense_replace"])


def _shapes(sizes: dict) -> dict:
    m = _dims(sizes)
    d, H, r = m["d"], m["H"], m["r"]

    def attn(n):
        return {"ln1": (n, d), "ln2": (n, d),
                "wq": (n, d, H * (m["dn"] + m["dr"])),
                "wkv_a": (n, d, r + m["dr"]), "kv_ln": (n, r),
                "wkv_b": (n, r, H * (m["dn"] + m["dv"])),
                "wo": (n, H * m["dv"], d)}

    n, held, f, fs = m["moe"], m["held"], m["f"], m["fs"]
    return {
        "embed": (m["V"], d),
        "dense": {**attn(m["dense"]), "w1": (m["dense"], d, m["ff"]),
                  "w3": (m["dense"], d, m["ff"]),
                  "w2": (m["dense"], m["ff"], d)},
        "layers": {**attn(n), "router": (n, d, m["E"]),
                   "we1": (n, held, d, f), "we3": (n, held, d, f),
                   "we2": (n, held, f, d),
                   "ws1": (n, d, fs), "ws3": (n, d, fs), "ws2": (n, fs, d)},
        "ln_f": (d,),
        "head": (m["V"], d),
    }


def init_params(sizes: dict, key):
    """Weights from ``key``: dense weights normal with std 1/sqrt(fan_in),
    embedding and head normal with std 0.02, norm weights 0."""
    import zlib

    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(sizes["param_dtype"])

    def make(path, shape):
        name = jax.tree_util.keystr(path)
        if "ln" in name:
            return jnp.zeros(shape, dt)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        std = 0.02 if name in ("['embed']", "['head']") else shape[-2] ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    return jax.tree_util.tree_map_with_path(
        make, _shapes(sizes), is_leaf=lambda x: isinstance(x, tuple))


def _rounder(mode: str):
    import jax
    import jax.numpy as jnp

    if mode == "f32":
        return lambda x: x
    if mode == "fp8":
        def fp8(x):  # forward rounding, gradient passed straight through
            r = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            return x + jax.lax.stop_gradient(r - x)
        return fp8
    raise ValueError(f"unknown mode {mode!r}")


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1 + w)


def _rope(x, theta):
    """Rotary embedding of (B, S, H, dr) over its two halves."""
    import jax.numpy as jnp

    s, dr = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(dr // 2, dtype=jnp.float32) / (dr // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, dr/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def route(logits, sizes: dict):
    """Router logits (T, E) -> (gates (T, E): the weight of each expert for
    each token, zero where it was not selected; the selection (T, E) as 0/1;
    scores (T, E))."""
    import jax
    import jax.numpy as jnp

    m = _dims(sizes)
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((m["E"],), jnp.float32)  # b: picks, never weights
    _, idx = jax.lax.top_k(s + bias, m["k"])
    sel = jnp.sum(jax.nn.one_hot(idx, m["E"], dtype=jnp.float32), axis=1)
    w = s * sel
    if sizes["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * sizes["routed_scaling_factor"], sel, s


def balance_loss(sel, s, sizes: dict, seq_len: int):
    """DeepSeek-V3's sequence-wise balance loss over (T, E) selections and
    scores, averaged over the T // seq_len sequences."""
    import jax.numpy as jnp

    m = _dims(sizes)
    e, k = m["E"], m["k"]
    sel = sel.reshape(-1, seq_len, e)
    s = s.reshape(-1, seq_len, e)
    f = e / (k * seq_len) * jnp.sum(sel, axis=1)
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
    return sizes["seq_aux_alpha"] * jnp.mean(jnp.sum(f * p, axis=-1))


def expert_layer(h, lp, sizes: dict, mm, experts=None):
    """The expert layer's output for h (T, d), without the balance loss:
    the held experts (or those of ``experts``: (index into the held
    weights, global expert id) pairs) looped over every token, each weighted
    by its gate, plus the shared experts."""
    import jax
    import jax.numpy as jnp

    m = _dims(sizes)
    logits = h @ lp["router"]  # float32, as the configuration states
    gates, _, _ = route(logits, sizes)
    experts = experts if experts is not None else [(j, j) for j in range(m["held"])]
    out = jnp.zeros_like(h)
    for j, e in experts:
        y = mm(jax.nn.silu(mm(h, lp["we1"][j])) * mm(h, lp["we3"][j]),
               lp["we2"][j])
        out = out + gates[:, e:e + 1] * y
    shared = mm(jax.nn.silu(mm(h, lp["ws1"])) * mm(h, lp["ws3"]), lp["ws2"])
    return out + shared


def ref_loss(params, batch, sizes: dict, mode: str = "f32"):
    """Mean next-token cross entropy of ``batch`` (tokens, labels), plus the
    expert layers' balance losses."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    q8 = _rounder(mode)
    m = _dims(sizes)
    eps, H, dn, dr, dv, r = (sizes["rms_norm_eps"], m["H"], m["dn"], m["dr"],
                             m["dv"], m["r"])
    theta = float(sizes["rope_theta"])
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    mm = lambda a, w: q8(a) @ q8(w)  # noqa: E731
    scale = 1.0 / jnp.sqrt(float(dn + dr))

    def attention(q, k, v):
        """Causal attention by blocks of queries: q, k (B,S,H,dn+dr), v
        (B,S,H,dv)."""
        nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
        cq = s // nq
        qb = q.reshape(b, nq, cq, *q.shape[2:]).swapaxes(0, 1)

        def one(args):
            i, qc = args
            sc = jnp.einsum("bqhd,bkhd->bhqk", q8(qc), q8(k)) * scale
            qpos = i * cq + jnp.arange(cq)
            mask = jnp.arange(s)[None, :] <= qpos[:, None]
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", q8(pr), q8(v))

        out = lax.map(jax.checkpoint(one), (jnp.arange(nq), qb))
        return out.swapaxes(0, 1).reshape(b, s, H * dv)

    def mla(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = mm(h, lp["wq"]).reshape(b, s, H, dn + dr)
        kva = mm(h, lp["wkv_a"])
        c = _rms(kva[..., :r], lp["kv_ln"], sizes["latent_rms_eps"])
        kv = mm(c, lp["wkv_b"]).reshape(b, s, H, dn + dv)
        k_pe = _rope(kva[..., None, r:], theta)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, H, dr))], -1)
        return x + mm(attention(q, k, kv[..., dn:]), lp["wo"])

    @jax.checkpoint
    def dense_layer(x, lp):
        x = mla(x, lp)
        h = _rms(x, lp["ln2"], eps)
        return x + mm(jax.nn.silu(mm(h, lp["w1"])) * mm(h, lp["w3"]),
                      lp["w2"]), None

    @jax.checkpoint
    def moe_layer(carry, lp):
        x, aux = carry
        x = mla(x, lp)
        h = _rms(x, lp["ln2"], eps).reshape(b * s, -1)
        _, sel, sc = route(h @ lp["router"], sizes)
        y = expert_layer(h, lp, sizes, mm)
        return (x + y.reshape(b, s, -1),
                aux + balance_loss(sel, sc, sizes, s)), None

    x = params["embed"][tokens]
    x, _ = lax.scan(dense_layer, x, params["dense"])
    (x, aux), _ = lax.scan(moe_layer, (x, jnp.float32(0.0)), params["layers"])
    x = _rms(x, params["ln_f"], eps).reshape(b * s, -1)
    nt = b * s // T_BLOCK if (b * s) % T_BLOCK == 0 else 1
    xt = x.reshape(nt, -1, x.shape[-1])
    lt = labels.reshape(nt, -1)

    @jax.checkpoint
    def ce(args):
        xc, lc = args
        z = mm(xc, params["head"].T)
        lse = jax.nn.logsumexp(z, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(z, lc[:, None], -1)[:, 0])

    return jnp.sum(lax.map(ce, (xt, lt))) / (b * s) + aux


def matmul_params(sizes: dict) -> float:
    """Parameters that take part in a matmul per token: attention's
    projections, the dense layer's MLP, the router, the shared experts, the
    held routed experts at their expected share (``top_k x held / E`` of an
    expert per token) and the head; not the embedding lookup or the norms."""
    m = _dims(sizes)
    d, H = m["d"], m["H"]
    attn = (d * H * (m["dn"] + m["dr"]) + d * (m["r"] + m["dr"])
            + m["r"] * H * (m["dn"] + m["dv"]) + H * m["dv"] * d)
    routed = 3 * d * m["f"] * m["k"] * m["held"] / m["E"]
    moe = d * m["E"] + routed + 3 * d * m["fs"]
    return (m["dense"] * (attn + 3 * d * m["ff"]) + m["moe"] * (attn + moe)
            + m["V"] * d)


def mla_attention_flops(sizes: dict, mix: dict) -> float:
    """FLOPs of causal MLA attention in one step, whatever implements it:
    per sequence and layer S^2 H (Dqk + Dv) for the forward, counted twice
    (the recomputed forward), and S^2 H (3 Dqk + 2 Dv) for the backward."""
    m = _dims(sizes)
    s, dqk, dv = mix["seq_len"], m["dn"] + m["dr"], m["dv"]
    per = s * s * m["H"] * (2 * (dqk + dv) + 3 * dqk + 2 * dv)
    return float(per * mix["global_batch"] * sizes["n_layers"])


def expert_gmm_flops(sizes: dict, mix: dict) -> float:
    """FLOPs of the held experts' grouped matmuls in one step at the
    expected load: R = B_mb S k held / E rows per microbatch and MoE layer,
    2 R d f per matmul, 3 matmuls, each counted 4 times (forward, the
    recomputed forward, and 2 in the backward)."""
    m = _dims(sizes)
    rows = mix["global_batch"] * mix["seq_len"] * m["k"] * m["held"] / m["E"]
    return float(rows * 2 * m["d"] * m["f"] * 3 * 4 * m["moe"])


def model_flops(sizes: dict, mix: dict) -> float:
    """Model FLOPs of one training step: 6 x matmul parameters per token
    (PaLM, arXiv:2204.02311, App. B), plus causal attention's
    3 x S x H x (Dqk + Dv) per token and layer (half of the PaLM term for
    a full mask); recomputation not counted."""
    m = _dims(sizes)
    s = mix["seq_len"]
    attn = 3.0 * s * m["H"] * (m["dn"] + m["dr"] + m["dv"]) * sizes["n_layers"]
    return (6.0 * matmul_params(sizes) + attn) * mix["global_batch"] * s
