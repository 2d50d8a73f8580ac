"""Plain reference of ``internlm2_1_8b_3l.json``: InternLM2-1.8B, cut in depth.

Straightforward ``jax.numpy`` of the published decoder (arXiv:2403.17297),
written from its description and not from the program: token embedding;
per layer RMSNorm, grouped-query attention (16 query heads over 8 key/value
heads, head size 128, rotary embedding on the two halves of each head,
causal softmax), a residual, RMSNorm, the SwiGLU MLP and a residual; a final
RMSNorm and the untied LM head; the loss is the mean next-token cross
entropy. Norm weights follow the ``(1 + w)`` convention of the parameter
tree, which is the published ``x * w`` with ``w`` shifted by one.

To fit one chip it is computed in blocks: attention by blocks of queries,
the LM head by blocks of tokens, each layer recomputed in the backward pass.
That changes no result beyond float32 rounding.

``mode`` is the precision of the arithmetic: ``"f32"`` for the reference,
``"fp8"`` for the control (every matmul operand rounded to float8 e4m3,
the step below the configuration's bfloat16).
"""
from __future__ import annotations

INPUT = "tokens"
REF_BLOCK_ROWS = 1  # sequences per reference block
CONTROL = "fp8"
Q_BLOCK = 512  # queries per attention block
T_BLOCK = 1024  # tokens per LM-head block


def _shapes(sizes: dict) -> dict:
    L, d, ff, V = (sizes["n_layers"], sizes["d_model"], sizes["d_ff"],
                   sizes["vocab"])
    q, kv = sizes["n_heads"] * sizes["head_dim"], (sizes["n_kv_heads"]
                                                    * sizes["head_dim"])
    return {
        "embed": (V, d),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
            "wo": (L, q, d),
            "w1": (L, d, ff), "w3": (L, d, ff), "w2": (L, ff, d),
        },
        "ln_f": (d,),
        "head": (V, d),
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
    """Rotary embedding of (B, S, H, hd) over its two halves."""
    import jax.numpy as jnp

    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def ref_loss(params, batch, sizes: dict, mode: str = "f32"):
    """Mean next-token cross entropy of ``batch`` (tokens, labels)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    q8 = _rounder(mode)
    eps, hd = sizes["rms_eps"], sizes["head_dim"]
    group = sizes["n_heads"] // sizes["n_kv_heads"]
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    mm = lambda a, w: q8(a) @ q8(w)  # noqa: E731

    def attention(q, k, v):
        """Causal attention, by blocks of queries. q (B,S,H,hd); k, v
        (B,S,H,hd)."""
        nq = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
        cq = s // nq
        qb = q.reshape(b, nq, cq, *q.shape[2:]).swapaxes(0, 1)

        def one(args):
            i, qc = args
            sc = jnp.einsum("bqhd,bkhd->bhqk", q8(qc), q8(k)) / jnp.sqrt(
                float(hd))
            qpos = i * cq + jnp.arange(cq)
            mask = jnp.arange(s)[None, :] <= qpos[:, None]
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", q8(pr), q8(v))

        out = lax.map(jax.checkpoint(one), (jnp.arange(nq), qb))
        return out.swapaxes(0, 1).reshape(b, s, -1)

    @jax.checkpoint
    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rope(mm(h, lp["wq"]).reshape(b, s, -1, hd), sizes["rope_theta"])
        k = _rope(mm(h, lp["wk"]).reshape(b, s, -1, hd), sizes["rope_theta"])
        v = mm(h, lp["wv"]).reshape(b, s, -1, hd)
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        x = x + mm(attention(q, k, v), lp["wo"])
        h = _rms(x, lp["ln2"], eps)
        return x + mm(jax.nn.silu(mm(h, lp["w1"])) * mm(h, lp["w3"]),
                      lp["w2"]), None

    x = params["embed"][tokens]
    x, _ = lax.scan(layer, x, params["layers"])
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

    return jnp.sum(lax.map(ce, (xt, lt))) / (b * s)


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and MLP, and the LM head; not the embedding lookup and not
    the norms."""
    d, q = sizes["d_model"], sizes["n_heads"] * sizes["head_dim"]
    kv = sizes["n_kv_heads"] * sizes["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * sizes["d_ff"]
    return sizes["n_layers"] * layer + sizes["vocab"] * d


def model_flops(sizes: dict, mix: dict) -> float:
    """Model FLOPs of one training step (PaLM, arXiv:2204.02311, App. B):
    6 x matmul parameters per token plus attention's
    12 x layers x heads x head size x sequence length per token."""
    s = mix["seq_len"]
    attn = 12.0 * sizes["n_layers"] * sizes["n_heads"] * sizes["head_dim"] * s
    return (6.0 * matmul_params(sizes) + attn) * mix["global_batch"] * s
