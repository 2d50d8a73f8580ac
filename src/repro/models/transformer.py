"""Decoder-only transformer with manual tensor parallelism (shard_map SPMD).

Supports the five assigned LM architectures: GQA (with kv-replication when
n_kv_heads < tp), optional QKV bias (qwen2), sliding-window/global layer
interleaving (gemma3), and MoE FFN (granite/qwen2-moe); and the DeepSeek-V3
block (Moonlight): multi-head latent attention (MLA), leading dense layers
before the expert layers, and an expert layer that holds a chip's share of
the routed experts (``models/moe.moe_ffn_held``).  That block trains at
``tp`` 1 only; its stack is two scanned groups with a tree each
(``params["dense"]``, then ``params["layers"]``).

Tensor-parallel layout over the ``model`` axis (size ``tp``):
  * q/o projections: heads sharded ``tp_attn = min(tp, n_heads)`` ways; if
    tp > n_heads the head shards are *duplicated* R = tp/tp_attn times in
    the stored layout (each duplicate stays bit-identical because the block
    output is psum'd over the full model axis and divided by R; duplicate
    grads are rescaled by R — see ``grad_sync``).
  * k/v projections: sharded if n_kv_heads >= tp, else fully replicated
    (grads then need a psum over the model axis — tagged "psum_model").
  * FFN / experts: hidden dim sharded tp ways; one psum per block.
  * embeddings / LM head: vocab sharded tp ways; logits combined by a
    distributed softmax cross-entropy (pmax + psum), never materializing
    the full vocab on one device.
  * decode KV cache: *sequence*-sharded over the model axis with all kv
    heads resident (byte-equivalent to head sharding but uniform across
    archs); decode attention uses a flash-decoding-style distributed
    log-sum-exp combine.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_mode
from repro.kernels.attention import causal_attention, kernel_fits
from repro.models.common import (
    Dist,
    apply_rope,
    dense_init,
    embed_init,
    rms_norm,
    split_keys,
)
from repro.models.moe import MoEConfig, moe_ffn, moe_ffn_held


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    rope_theta: float = 1e6
    sliding_window: int | None = None  # window for local layers
    global_every: int = 0  # 0 = all layers global; k = layers k-1, 2k-1,... global
    moe: MoEConfig | None = None
    act: str = "silu"
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_chunk: int = 1024  # q-block size for chunked attention
    eps: float = 1e-6
    embed_scale: bool = False  # gemma-style sqrt(d) embedding scale
    # Megatron-style sequence parallelism (training path): the residual
    # stream and every saved activation are sharded over the model axis on
    # the sequence dim; block psums become all-gather/psum-scatter conjugate
    # pairs (same wire bytes, 1/tp activation memory, no redundant norms).
    seq_parallel: bool = False
    # multi-head latent attention (DeepSeek-V2/V3): kv_lora_rank > 0 turns it
    # on; q and k heads are qk_nope_head_dim + qk_rope_head_dim wide, v heads
    # v_head_dim; head_dim, n_kv_heads and qkv_bias are then unused
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    latent_eps: float = 1e-6  # the latent RMSNorm's epsilon
    # leading dense layers (of width d_ff) before the MoE layers
    first_k_dense: int = 0

    def __post_init__(self):
        held = self.moe is not None and self.moe.experts_held is not None
        if (self.first_k_dense or held) and not self.kv_lora_rank:
            raise ValueError(f"{self.name}: a dense prefix and held experts "
                             f"come with the DeepSeek-V3 block (MLA)")

    @property
    def deepseek_block(self) -> bool:
        """The DeepSeek-V3 block (MLA, with a dense prefix and held experts
        where set): the two-group stack, which trains at tp 1 only and has
        no prefill or decode."""
        return self.kv_lora_rank > 0

    # ---- TP derived quantities -------------------------------------
    def tp_attn(self, tp: int) -> int:
        return min(tp, self.n_heads)

    def attn_replicas(self, tp: int) -> int:
        return tp // self.tp_attn(tp)

    def heads_local(self, tp: int) -> int:
        return self.n_heads // self.tp_attn(tp)

    def kv_sharded(self, tp: int) -> bool:
        return self.n_kv_heads >= tp

    def kv_heads_local(self, tp: int) -> int:
        return self.n_kv_heads // tp if self.kv_sharded(tp) else self.n_kv_heads

    def vocab_padded(self, tp: int) -> int:
        return -(-self.vocab // (tp * 128)) * (tp * 128)

    def is_global_layer(self, layer: int):
        if self.global_every <= 0 or self.sliding_window is None:
            return True
        return (layer + 1) % self.global_every == 0

    @property
    def q_group(self) -> int:
        return self.n_heads // self.n_kv_heads

    def param_count(self) -> int:
        """Exact parameter count (excluding vocab padding)."""
        if self.deepseek_block:
            return sum(math.prod(x.shape) for x in jax.tree.leaves(
                jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))))
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        if self.qkv_bias:
            attn += self.n_heads * hd + 2 * self.n_kv_heads * hd
        if self.moe is not None:
            m = self.moe
            ffn = d * m.n_experts + 3 * d * m.d_ff_expert * m.n_experts
            if m.shared_d_ff:
                ffn += 3 * d * m.shared_d_ff
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * self.vocab * d + d

    def active_param_count(self) -> int:
        """Per-token active params (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        m = self.moe
        if m.experts_held is not None:  # the held experts' expected share
            count = m.experts_held[1]
            held = 3 * d * m.d_ff_expert * count
            active = 3 * d * m.d_ff_expert * m.top_k * count / m.n_experts
            return int(self.param_count() - (self.n_layers - self.first_k_dense)
                       * (held - active))
        full_ffn = d * m.n_experts + 3 * d * m.d_ff_expert * m.n_experts
        act_ffn = d * m.n_experts + 3 * d * (m.d_ff_expert * m.top_k + m.shared_d_ff)
        return self.param_count() - self.n_layers * (full_ffn - act_ffn) + (
            0 if not m.shared_d_ff else 0
        )


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _init_deepseek(cfg: TransformerConfig, key) -> dict:
    """The DeepSeek-V3 block's tree: ``dense`` (the first ``first_k_dense``
    layers, SwiGLU of width ``d_ff``) and ``layers`` (the MoE layers), each
    stacked on its own leading axis."""
    d, H, pdt = cfg.d_model, cfg.n_heads, cfg.param_dtype
    r, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    dqk, dkv = cfg.qk_nope_head_dim + dr, cfg.qk_nope_head_dim + dv
    kd, km, ke, kh = jax.random.split(key, 4)

    def group(n, moe, key):
        ks = iter(split_keys(key, 16))
        lp = {"ln1": jnp.zeros((n, d), pdt), "ln2": jnp.zeros((n, d), pdt),
              "wq": dense_init(next(ks), (n, d, H * dqk), d, pdt),
              "wkv_a": dense_init(next(ks), (n, d, r + dr), d, pdt),
              "kv_ln": jnp.zeros((n, r), pdt),
              "wkv_b": dense_init(next(ks), (n, r, H * dkv), r, pdt),
              "wo": dense_init(next(ks), (n, H * dv, d), H * dv, pdt)}
        if not moe:
            lp["w1"] = dense_init(next(ks), (n, d, cfg.d_ff), d, pdt)
            lp["w3"] = dense_init(next(ks), (n, d, cfg.d_ff), d, pdt)
            lp["w2"] = dense_init(next(ks), (n, cfg.d_ff, d), cfg.d_ff, pdt)
            return lp
        m = cfg.moe
        count, f = m.experts_held[1], m.d_ff_expert
        lp["router"] = dense_init(next(ks), (n, d, m.n_experts), d, jnp.float32)
        lp["we1"] = dense_init(next(ks), (n, count, d, f), d, pdt)
        lp["we3"] = dense_init(next(ks), (n, count, d, f), d, pdt)
        lp["we2"] = dense_init(next(ks), (n, count, f, d), f, pdt)
        if m.shared_d_ff:
            fs = m.shared_d_ff
            lp["ws1"] = dense_init(next(ks), (n, d, fs), d, pdt)
            lp["ws3"] = dense_init(next(ks), (n, d, fs), d, pdt)
            lp["ws2"] = dense_init(next(ks), (n, fs, d), fs, pdt)
        return lp

    vp = cfg.vocab_padded(1)
    params = {
        "embed": embed_init(ke, (vp, d), pdt),
        "layers": group(cfg.n_layers - cfg.first_k_dense, cfg.moe is not None,
                        km),
        "ln_f": jnp.zeros((d,), pdt),
        "head": embed_init(kh, (vp, d), pdt),
    }
    if cfg.first_k_dense:
        params["dense"] = group(cfg.first_k_dense, False, kd)
    return params


def _tp1_only(cfg: TransformerConfig, tp: int):
    if tp > 1:
        raise ValueError(f"{cfg.name}: the DeepSeek-V3 block (MLA, held "
                         f"experts) trains at tp 1 only; the mesh has a "
                         f"model axis of {tp}")


def init_params(cfg: TransformerConfig, key, tp: int = 1) -> dict:
    """Global param arrays (the duplicated q/o layout is materialized)."""
    if cfg.deepseek_block:
        _tp1_only(cfg, tp)
        return _init_deepseek(cfg, key)
    L, d, hd = cfg.n_layers, cfg.d_model, cfg.head_dim
    R = cfg.attn_replicas(tp)
    vp = cfg.vocab_padded(tp)
    ks = iter(split_keys(key, 24))
    pdt = cfg.param_dtype

    def tile_r(x):  # duplicate head layout R times on the last dim
        return jnp.tile(x, (1,) * (x.ndim - 1) + (R,)) if R > 1 else x

    qdim = cfg.n_heads * hd
    kvdim = cfg.n_kv_heads * hd
    layers: dict[str, Any] = {
        "ln1": jnp.zeros((L, d), pdt),
        "ln2": jnp.zeros((L, d), pdt),
        "wq": tile_r(dense_init(next(ks), (L, d, qdim), d, pdt)),
        "wk": dense_init(next(ks), (L, d, kvdim), d, pdt),
        "wv": dense_init(next(ks), (L, d, kvdim), d, pdt),
        "wo": jnp.swapaxes(
            tile_r(dense_init(next(ks), (L, d, qdim), qdim, pdt)), 1, 2
        ),
    }
    if cfg.qkv_bias:
        layers["bq"] = tile_r(jnp.zeros((L, qdim), pdt))
        layers["bk"] = jnp.zeros((L, kvdim), pdt)
        layers["bv"] = jnp.zeros((L, kvdim), pdt)
    if cfg.moe is None:
        layers["w1"] = dense_init(next(ks), (L, d, cfg.d_ff), d, pdt)
        layers["w3"] = dense_init(next(ks), (L, d, cfg.d_ff), d, pdt)
        layers["w2"] = dense_init(next(ks), (L, cfg.d_ff, d), cfg.d_ff, pdt)
    else:
        m = cfg.moe
        layers["router"] = dense_init(next(ks), (L, d, m.n_experts), d, jnp.float32)
        layers["we1"] = dense_init(next(ks), (L, m.n_experts, d, m.d_ff_expert), d, pdt)
        layers["we3"] = dense_init(next(ks), (L, m.n_experts, d, m.d_ff_expert), d, pdt)
        layers["we2"] = dense_init(
            next(ks), (L, m.n_experts, m.d_ff_expert, d), m.d_ff_expert, pdt
        )
        if m.shared_d_ff:
            layers["ws1"] = dense_init(next(ks), (L, d, m.shared_d_ff), d, pdt)
            layers["ws3"] = dense_init(next(ks), (L, d, m.shared_d_ff), d, pdt)
            layers["ws2"] = dense_init(next(ks), (L, m.shared_d_ff, d), m.shared_d_ff, pdt)
    # draw vocab tables at the tp-independent canonical size and zero-pad
    # the extra tp-layout rows: init is layout-invariant (tp=1 and tp=N
    # models are the *same* random model), and padded rows are dead (tokens
    # never index them; the loss masks their logits)
    vp1 = cfg.vocab_padded(1)

    def vocab_init(k):
        w = embed_init(k, (vp1, d), pdt)
        if vp > vp1:
            w = jnp.concatenate([w, jnp.zeros((vp - vp1, d), pdt)])
        return w

    return {
        "embed": vocab_init(next(ks)),
        "layers": layers,
        "ln_f": jnp.zeros((d,), pdt),
        "head": vocab_init(next(ks)),
    }


def _tp1_tree(cfg: TransformerConfig, leaf) -> dict:
    return jax.tree.map(lambda _: leaf, jax.eval_shape(
        lambda: _init_deepseek(cfg, jax.random.PRNGKey(0))))


def make_param_specs(cfg: TransformerConfig, tp: int, axis: str = "model") -> dict:
    if cfg.deepseek_block:
        _tp1_only(cfg, tp)
        return _tp1_tree(cfg, P())
    M = axis if tp > 1 else None
    kvs = cfg.kv_sharded(tp)
    kv = P(None, None, M) if kvs else P()
    kvb = P(None, M) if kvs else P()
    layers: dict[str, Any] = {
        "ln1": P(),
        "ln2": P(),
        "wq": P(None, None, M),
        "wk": kv,
        "wv": kv,
        "wo": P(None, M, None),
    }
    if cfg.qkv_bias:
        layers["bq"] = P(None, M)
        layers["bk"] = kvb
        layers["bv"] = kvb
    if cfg.moe is None:
        layers["w1"] = P(None, None, M)
        layers["w3"] = P(None, None, M)
        layers["w2"] = P(None, M, None)
    else:
        layers["router"] = P()
        layers["we1"] = P(None, None, None, M)
        layers["we3"] = P(None, None, None, M)
        layers["we2"] = P(None, None, M, None)
        if cfg.moe.shared_d_ff:
            layers["ws1"] = P(None, None, M)
            layers["ws3"] = P(None, None, M)
            layers["ws2"] = P(None, M, None)
    return {
        "embed": P(M, None),
        "layers": layers,
        "ln_f": P(),
        "head": P(M, None),
    }


def grad_sync(cfg: TransformerConfig, tp: int) -> dict:
    """Per-tensor gradient correction before the PS exchange.

    Semantics (verified in tests/test_grad_equivalence.py): per-device
    autodiff inside a manual shard_map computes d(sum over devices of the
    per-device loss)/d(local param) — collective transposes (psum -> psum,
    psum_scatter -> all_gather) route cross-device cotangent paths.  With
    the per-device loss divided by tp, *sharded* params therefore get exact
    grads ("none").  Remaining corrections:

    "psum_model"  — replicated copies whose per-copy grads cover only the
                    local head/branch slice (kv when replicated, norms,
                    router): psum makes them complete AND keeps copies
                    bit-identical.
    "scale_R"     — q/o duplicated-layout copies: each copy's grad is
                    true/R (the forward psum/R); rescale by R so the
                    underlying head weights follow the same trajectory as
                    the non-duplicated model.
    """
    if cfg.deepseek_block:
        _tp1_only(cfg, tp)
        return _tp1_tree(cfg, "none")
    R = cfg.attn_replicas(tp)
    rep = "psum_model" if tp > 1 else "none"
    qsync = f"scale_{R}" if R > 1 else "none"
    kvsync = "none" if cfg.kv_sharded(tp) else rep
    layers: dict[str, Any] = {
        "ln1": rep,
        "ln2": rep,
        "wq": qsync,
        "wk": kvsync,
        "wv": kvsync,
        "wo": qsync,
    }
    if cfg.qkv_bias:
        layers["bq"] = qsync
        layers["bk"] = kvsync
        layers["bv"] = kvsync
    if cfg.moe is None:
        layers.update({"w1": "none", "w3": "none", "w2": "none"})
    else:
        layers["router"] = rep
        layers.update({"we1": "none", "we3": "none", "we2": "none"})
        if cfg.moe.shared_d_ff:
            layers.update({"ws1": "none", "ws3": "none", "ws2": "none"})
    return {"embed": "none", "layers": layers, "ln_f": rep, "head": "none"}


# ---------------------------------------------------------------------------
# building blocks (per-device code)
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg: TransformerConfig, dist: Dist,
           scatter_seq: bool = False):
    """Vocab-sharded lookup: mask + local take + psum (the PS 'pull').
    scatter_seq: combine partials AND shard the sequence in one collective
    (sequence-parallel entry)."""
    table = params["embed"]
    vloc = table.shape[0]
    midx = dist.model_index()
    local = tokens - midx * vloc
    ok = (local >= 0) & (local < vloc)
    emb = jnp.take(table, jnp.clip(local, 0, vloc - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0).astype(cfg.dtype)
    emb = dist.psum_scatter_model(emb, axis=1) if scatter_seq else dist.psum_model(emb)
    if cfg.embed_scale:
        emb = emb * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    return emb


def _qkv(x, lp, cfg: TransformerConfig, dist: Dist, positions):
    """Returns q (B,S,Hloc,hd) rope'd, k/v (B,S,Hkv_res,hd) rope'd k."""
    hd = cfg.head_dim
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    b, s = x.shape[0], x.shape[1]
    q = q.reshape(b, s, -1, hd)
    k = k.reshape(b, s, -1, hd)
    v = v.reshape(b, s, -1, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _kv_for_local_q(k, v, cfg: TransformerConfig, dist: Dist, tp: int):
    """Select, per local q head, its kv head (resident or replicated)."""
    tpa = cfg.tp_attn(tp)
    hloc = cfg.heads_local(tp)
    midx = dist.model_index()
    qh_global = (midx % tpa) * hloc + jnp.arange(hloc)
    kv_global = qh_global // cfg.q_group
    if cfg.kv_sharded(tp):
        kv_local = kv_global - midx * cfg.kv_heads_local(tp)
    else:
        kv_local = kv_global
    k_used = jnp.take(k, kv_local, axis=2)
    v_used = jnp.take(v, kv_local, axis=2)
    return k_used, v_used  # (B, S, Hloc, hd)


def _chunked_attention(q, k, v, cfg: TransformerConfig, is_global, q0: int = 0):
    """Causal (optionally windowed) attention, scanned over q chunks.

    q/k: (B, Sq|Sk, H, hd); v: (B, Sk, H, hd or another head size) already
    per-q-head; the scale is 1/sqrt(hd).
    ``is_global`` may be a traced bool (layer-type select inside scan).
    q0 = absolute position of q[0] (prefill continuation unused: 0).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    cq = min(cfg.attn_chunk, sq)
    n_chunks = sq // cq if sq % cq == 0 else 1
    if sq % cq != 0:
        cq = sq
        n_chunks = 1
    kpos = jnp.arange(sk)
    win = cfg.sliding_window or sk

    qr = q.reshape(b, n_chunks, cq, h, hd)

    def chunk(carry, inputs):
        i, qc = inputs  # qc: (B, cq, H, hd)
        qpos = q0 + i * cq + jnp.arange(cq)
        causal = kpos[None, :] <= qpos[:, None]
        local = kpos[None, :] > qpos[:, None] - win
        mask = jnp.where(is_global, causal, causal & local)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k).astype(jnp.float32) * scale
        scores = jnp.where(mask[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return carry, out

    _, outs = lax.scan(chunk, None, (jnp.arange(n_chunks), jnp.swapaxes(qr, 0, 1)))
    out = jnp.swapaxes(outs, 0, 1).reshape(b, sq, h, v.shape[-1])
    return out


def _attention(q, k, v, cfg: TransformerConfig, is_global, q0: int = 0):
    """Causal attention of q over k/v, (B, S, H, hd) each.

    On a TPU, a global causal layer over a whole sequence from position 0
    whose length and head size the flash kernel tiles
    (``kernels.attention.kernel_fits``, padding any head size up to 256)
    runs the kernel: its scores stay in VMEM and the blocks above the
    diagonal are skipped. Everything else
    (windowed layers, ``q0 > 0``, shapes that do not tile, other backends)
    takes ``_chunked_attention``."""
    sq, hd = q.shape[1], q.shape[3]
    if (not interpret_mode() and cfg.sliding_window is None and q0 == 0
            and k.shape[1] == sq and kernel_fits(sq, hd, v.shape[3])):
        with jax.named_scope("attn_kernel"):
            return causal_attention(q, k, v)
    return _chunked_attention(q, k, v, cfg, is_global, q0)


def _attn_block(x, lp, cfg: TransformerConfig, dist: Dist, tp: int, is_global,
                positions, combine=None):
    b, s, _ = x.shape
    R = cfg.attn_replicas(tp)
    combine = combine or dist.psum_model
    q, k, v = _qkv(x, lp, cfg, dist, positions)
    k, v = _kv_for_local_q(k, v, cfg, dist, tp)
    out = _attention(q, k, v, cfg, is_global)
    out = out.reshape(b, s, -1) @ lp["wo"]
    out = combine(out)
    if R > 1:
        out = out / R
    return out.astype(x.dtype)


def _mla_block(x, lp, cfg: TransformerConfig, positions):
    """Multi-head latent attention (DeepSeek-V2/V3, ``q_lora_rank`` null).

    ``q = h W_q``, split per head into ``q_nope`` (``qk_nope_head_dim``) and
    ``q_pe`` (``qk_rope_head_dim``); ``[c_kv | k_pe] = h W_kva``; ``c_kv``
    through an RMSNorm; ``[k_nope | v] = c_kv W_kvb`` per head; RoPE on
    ``q_pe`` and on the one ``k_pe`` that all heads share; causal softmax at
    scale ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``; output ``W_o``.

    RoPE here rotates the two halves of the 64 rope dims (x[:32] with
    x[32:]), not interleaved pairs (x[0::2] with x[1::2]) as the published
    modeling code does: published ``W_q``'s rope columns and ``W_kva``'s
    ``k_pe`` columns would be permuted (evens first, then odds) to load here.
    The latent projections run under the scope ``mla_latent``."""
    b, s, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla_latent"):
        q = (x @ lp["wq"]).reshape(b, s, H, dn + dr)
        kva = x @ lp["wkv_a"]
        c_kv = rms_norm(kva[..., :r], lp["kv_ln"], cfg.latent_eps)
        kv = (c_kv @ lp["wkv_b"]).reshape(b, s, H, dn + dv)
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_pe = apply_rope(kva[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, H, dr))],
                        axis=-1)
    out = _attention(q, k, kv[..., dn:], cfg, jnp.bool_(True))
    return (out.reshape(b, s, H * dv) @ lp["wo"]).astype(x.dtype)


def _ffn_block(x, lp, cfg: TransformerConfig, dist: Dist, combine=None,
               moe: bool | None = None):
    """Dense or MoE FFN; returns (out, aux_loss).  ``moe=False`` takes the
    dense SwiGLU in a configuration whose other layers are MoE."""
    b, s, d = x.shape
    combine = combine or dist.psum_model
    if cfg.moe is None or moe is False:
        a = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = a(x @ lp["w1"]) * (x @ lp["w3"])
        out = h @ lp["w2"]
        return combine(out).astype(x.dtype), jnp.float32(0.0)
    tok = x.reshape(b * s, d)
    weights = {k2: lp[k2] for k2 in ("router", "we1", "we3", "we2") if k2 in lp}
    for k2 in ("ws1", "ws3", "ws2"):
        if k2 in lp:
            weights[k2] = lp[k2]
    out, aux = moe_ffn(tok, weights, cfg.moe, dist, cfg.act)
    out = combine(out.reshape(b, s, d))
    # aux loss is computed identically on every model shard (routing is
    # replicated) — no psum.
    return out.astype(x.dtype), aux


def _layer(x, lp, layer_idx, cfg: TransformerConfig, dist: Dist, tp: int,
           positions, moe: bool | None = None):
    """One decoder layer; returns (x, aux_loss, counters).  ``counters``
    holds the held experts' ``rows`` and ``max_load`` on that path and is
    empty elsewhere."""
    moe = cfg.moe is not None if moe is None else moe
    is_global = (
        jnp.bool_(True)
        if (cfg.global_every <= 0 or cfg.sliding_window is None)
        else ((layer_idx + 1) % cfg.global_every == 0)
    )
    sp = cfg.seq_parallel and dist.model_axis is not None

    def block_in(x):
        # SP: norm on the seq shard (no redundancy), then gather full seq
        h = rms_norm(x, lp["ln1"], cfg.eps)
        return dist.all_gather_model(h, axis=1) if sp else h

    def block_out(y):
        # SP: combine partial outputs AND re-shard the sequence in one
        # collective (the conjugate of block_in's all-gather)
        return dist.psum_scatter_model(y, axis=1) if sp else dist.psum_model(y)

    h = block_in(x)
    with jax.named_scope("attn"):
        if cfg.deepseek_block:
            a_out = _mla_block(h, lp, cfg, positions)
        else:
            a_out = _attn_block(h, lp, cfg, dist, tp, is_global, positions,
                                combine=block_out)
    x = x + a_out
    h = rms_norm(x, lp["ln2"], cfg.eps)
    if sp:
        h = dist.all_gather_model(h, axis=1)
    counters = {}
    with jax.named_scope("mlp"):
        if moe and cfg.moe.experts_held is not None:
            f, aux, counters = moe_ffn_held(h, lp, cfg.moe, cfg.act)
        else:
            f, aux = _ffn_block(h, lp, cfg, dist, combine=block_out, moe=moe)
    return x + f, aux, counters


def forward(params, tokens, cfg: TransformerConfig, dist: Dist, tp: int):
    """tokens (B, S) -> (hidden (B, S or S/tp if seq_parallel, d), aux loss,
    counters).  The layers run as one remat'd ``lax.scan`` per stacked
    group: ``params["dense"]`` (the DeepSeek-V3 block's dense prefix) where
    present, then ``params["layers"]``.  ``counters`` holds each held-experts
    layer's ``rows`` and ``max_load`` (stacked over the layers), and is
    empty on every other path."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    sp = cfg.seq_parallel and dist.model_axis is not None
    x = _embed(params, tokens, cfg, dist, scatter_seq=sp)
    aux = jnp.float32(0.0)
    groups = [("dense", False, 0)] if "dense" in params else []
    groups.append(("layers", cfg.moe is not None, cfg.first_k_dense))
    counters = {}
    for name, moe, first in groups:
        n = jax.tree.leaves(params[name])[0].shape[0]

        def body(carry, inputs, moe=moe):
            x, aux = carry
            lp, li = inputs
            x, a, cnt = _layer(x, lp, li, cfg, dist, tp, positions, moe=moe)
            return (x, aux + a), cnt

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), cnt = lax.scan(body_fn, (x, aux),
                                 (params[name], jnp.arange(first, first + n)))
        counters.update(cnt)
    return x, aux, counters


def lm_loss(params, tokens, labels, cfg: TransformerConfig, dist: Dist, tp: int):
    """Distributed-softmax CE over the vocab-sharded head. Returns scalar
    per-worker mean loss (caller pmeans over workers)."""
    x, aux, counters = forward(params, tokens, cfg, dist, tp)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["ln_f"], cfg.eps)
        if cfg.seq_parallel and dist.model_axis is not None:
            # re-assemble the full sequence for the vocab-sharded head
            x = dist.all_gather_model(x, axis=1)
        head = params["head"]  # (Vloc, d)
        vloc = head.shape[0]
        logits = (x @ head.T).astype(jnp.float32)  # (B, S, Vloc)
        midx = dist.model_index()
        # mask vocab-padding rows out of the softmax
        gid = midx * vloc + jnp.arange(vloc)
        logits = jnp.where(gid < cfg.vocab, logits, -1e30)
        local = labels - midx * vloc
        ok = (local >= 0) & (local < vloc)
        lab = jnp.clip(local, 0, vloc - 1)
        lab_logit = jnp.take_along_axis(logits, lab[..., None],
                                        axis=-1)[..., 0]
        lab_logit = dist.psum_model(jnp.where(ok, lab_logit, 0.0))
        # stability max is gradient-free (exact: d lse/d logits is softmax);
        # stop_gradient *before* pmax — pmax has no differentiation rule
        mx = dist.pmax_model(jnp.max(lax.stop_gradient(logits), axis=-1))
        lse = mx + jnp.log(
            dist.psum_model(jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1))
        )
        ce = jnp.mean(lse - lab_logit)
    met = {"ce": ce, "aux": aux}
    if counters:  # the held experts: rows and load per layer, and the loss
        met.update(moe_rows=jnp.mean(counters["rows"]),
                   moe_max_load=jnp.max(counters["max_load"]), moe_aux=aux)
    return ce + aux, met


# ---------------------------------------------------------------------------
# serving: prefill + decode with a sequence-sharded KV cache
# ---------------------------------------------------------------------------

def _no_serving(cfg: TransformerConfig):
    if cfg.deepseek_block:
        raise ValueError(f"{cfg.name}: the DeepSeek-V3 block (MLA, held "
                         f"experts) has no prefill or decode path")


def init_cache(cfg: TransformerConfig, batch_local: int, max_seq: int, tp: int):
    """Per-device cache: (L, B, S/tp, Hkv, hd) seq-sharded over model."""
    sloc = max_seq // tp if tp > 1 else max_seq
    shape = (cfg.n_layers, batch_local, sloc, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _full_kv(k, v, cfg, dist: Dist, tp: int):
    """Make all kv heads resident (gather over model if weights sharded)."""
    if cfg.kv_sharded(tp) and tp > 1:
        k = dist.all_gather_model(k, axis=2)
        v = dist.all_gather_model(v, axis=2)
    return k, v


def prefill(params, tokens, cfg: TransformerConfig, dist: Dist, tp: int, max_seq: int):
    """Returns (greedy next-token ids (B,), cache filled with S tokens)."""
    _no_serving(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens, cfg, dist)
    sloc = max_seq // tp if tp > 1 else max_seq
    midx = dist.model_index()

    def body(carry, inputs):
        x = carry
        lp, li = inputs
        is_global = (
            jnp.bool_(True)
            if (cfg.global_every <= 0 or cfg.sliding_window is None)
            else ((li + 1) % cfg.global_every == 0)
        )
        h = rms_norm(x, lp["ln1"], cfg.eps)
        q, k, v = _qkv(h, lp, cfg, dist, positions)
        kf, vf = _full_kv(k, v, cfg, dist, tp)
        # local cache slice: my seq shard (pad to max_seq first)
        pad = ((0, 0), (0, max_seq - s), (0, 0), (0, 0))
        kc = lax.dynamic_slice_in_dim(jnp.pad(kf, pad), midx * sloc, sloc, axis=1)
        vc = lax.dynamic_slice_in_dim(jnp.pad(vf, pad), midx * sloc, sloc, axis=1)
        ku, vu = _kv_for_local_q(k, v, cfg, dist, tp)
        out = _attention(q, ku, vu, cfg, is_global)
        out = out.reshape(x.shape[0], s, -1) @ lp["wo"]
        out = dist.psum_model(out)
        R = cfg.attn_replicas(tp)
        if R > 1:
            out = out / R
        x = x + out.astype(x.dtype)
        h = rms_norm(x, lp["ln2"], cfg.eps)
        f, _ = _ffn_block(h, lp, cfg, dist)
        return x + f, (kc.astype(cfg.dtype), vc.astype(cfg.dtype))

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, (ck, cv) = lax.scan(
        body_fn, x, (params["layers"], jnp.arange(cfg.n_layers))
    )
    nxt = _greedy_logits(params, x[:, -1], cfg, dist)
    return nxt, {"k": ck, "v": cv}


def _greedy_logits(params, xlast, cfg, dist: Dist):
    """Greedy next token over the vocab-sharded head. xlast: (B, d)."""
    x = rms_norm(xlast, params["ln_f"], cfg.eps)
    head = params["head"]
    vloc = head.shape[0]
    logits = (x @ head.T).astype(jnp.float32)  # (B, Vloc)
    midx = dist.model_index()
    gid = midx * vloc + jnp.arange(vloc)
    logits = jnp.where(gid < cfg.vocab, logits, -1e30)
    loc_max = jnp.max(logits, axis=-1)
    loc_arg = (jnp.argmax(logits, axis=-1) + midx * vloc).astype(jnp.int32)
    if dist.model_axis is None:
        return loc_arg
    glob_max = dist.pmax_model(loc_max)
    cand = jnp.where(loc_max >= glob_max, loc_arg, jnp.iinfo(jnp.int32).max)
    return -dist.pmax_model(-cand)  # pmin: lowest winning id (tie-break)


def _decode_attn_distributed(
    q, k_loc, v_loc, pos, cfg: TransformerConfig, dist: Dist, tp: int,
    is_global=True,
):
    """Flash-decoding combine over the seq-sharded cache.

    q: (B, Hloc, hd) — the *local* q heads; k_loc/v_loc: (B, Sloc, Hkv, hd)
    — this device's sequence shard with all kv heads resident.

    Every seq shard must serve every q head, so: all-gather q over the model
    axis (tiny: one token), compute all-head partial attention + log-sum-exp
    stats against the local shard, psum-combine across shards, then return
    the local q heads' slice.  Returns (B, Hloc, hd).
    """
    b, hloc, hd = q.shape
    sloc = k_loc.shape[1]
    tpa = cfg.tp_attn(tp)
    hq = cfg.n_heads
    midx = dist.model_index()
    scale = 1.0 / math.sqrt(hd)

    if dist.model_axis is not None:
        # gathered layout = [replica0 heads.., replica1 heads..]: keep one copy
        q_all = dist.all_gather_model(q, axis=1)[:, :hq]  # (B, Hq, hd)
    else:
        q_all = q

    kv_idx = jnp.arange(hq) // cfg.q_group
    k_used = jnp.take(k_loc, kv_idx, axis=2)  # (B, Sloc, Hq, hd)
    v_used = jnp.take(v_loc, kv_idx, axis=2)

    gpos = (midx * sloc if dist.model_axis is not None else 0) + jnp.arange(sloc)
    valid = gpos <= pos
    if cfg.sliding_window is not None:
        # local layers only attend within the window (scan-mode decode keeps
        # a full-length cache for shape uniformity; masking enforces the
        # window — long_500k uses the unrolled path with true window caches)
        in_win = gpos > pos - cfg.sliding_window
        valid = valid & jnp.where(jnp.asarray(is_global), True, in_win)
    scores = jnp.einsum("bhd,bshd->bhs", q_all, k_used).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, :], scores, -1e30)
    m_loc = jnp.max(scores, axis=-1)  # (B, Hq)
    e = jnp.exp(scores - m_loc[..., None])
    den_loc = jnp.sum(e, axis=-1)
    num_loc = jnp.einsum("bhs,bshd->bhd", e.astype(q.dtype), v_used).astype(jnp.float32)

    if dist.model_axis is None:
        return (num_loc / den_loc[..., None]).astype(q.dtype)

    m_glob = dist.pmax_model(m_loc)  # (B, Hq)
    r = jnp.exp(m_loc - m_glob)
    num = dist.psum_model(num_loc * r[..., None])
    den = dist.psum_model(den_loc * r)
    out_all = num / den[..., None]  # (B, Hq, hd), all shards combined
    qh_global = (midx % tpa) * hloc + jnp.arange(hloc)
    return jnp.take(out_all, qh_global, axis=1).astype(q.dtype)


def decode_step(params, token, cache, pos, cfg: TransformerConfig, dist: Dist, tp: int):
    """One greedy decode step.  token (B,) int32; pos: scalar count of tokens
    already in the cache.  Returns (next_token (B,), new cache)."""
    _no_serving(cfg)
    b = token.shape[0]
    x = _embed(params, token[:, None], cfg, dist)[:, 0]  # (B, d)
    sloc = cache["k"].shape[2]
    midx = dist.model_index()
    owner = pos // sloc
    lpos = pos - owner * sloc

    def body(carry, inputs):
        x = carry
        lp, li, kc, vc = inputs
        is_global = (
            jnp.bool_(True)
            if (cfg.global_every <= 0 or cfg.sliding_window is None)
            else ((li + 1) % cfg.global_every == 0)
        )
        h = rms_norm(x, lp["ln1"], cfg.eps)
        q, k, v = _qkv(h[:, None], lp, cfg, dist, jnp.full((b, 1), pos))
        kf, vf = _full_kv(k, v, cfg, dist, tp)  # (B,1,Hkv,hd)
        # O(1) masked write into my seq shard
        mine = owner == midx if dist.model_axis is not None else jnp.bool_(True)
        old_k = lax.dynamic_slice(kc, (0, lpos, 0, 0), (b, 1, kf.shape[2], kf.shape[3]))
        old_v = lax.dynamic_slice(vc, (0, lpos, 0, 0), old_k.shape)
        kc = lax.dynamic_update_slice(kc, jnp.where(mine, kf, old_k), (0, lpos, 0, 0))
        vc = lax.dynamic_update_slice(vc, jnp.where(mine, vf, old_v), (0, lpos, 0, 0))
        out = _decode_attn_distributed(q[:, 0], kc, vc, pos, cfg, dist, tp,
                                       is_global)
        out = out.reshape(b, -1) @ lp["wo"]
        out = dist.psum_model(out)
        R = cfg.attn_replicas(tp)
        if R > 1:
            out = out / R
        x = x + out.astype(x.dtype)
        h = rms_norm(x, lp["ln2"], cfg.eps)
        f, _ = _ffn_block(h[:, None], lp, cfg, dist)
        return x + f[:, 0], (kc, vc)

    x, (ck, cv) = lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.n_layers), cache["k"], cache["v"])
    )
    nxt = _greedy_logits(params, x, cfg, dist)
    return nxt, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# unrolled decode with per-layer cache sizes (sliding-window archs, long ctx)
# ---------------------------------------------------------------------------

def init_cache_unrolled(cfg: TransformerConfig, batch_local: int, max_seq: int, tp: int):
    """Per-layer caches: window-sized rolling for local layers (replicated
    over model — tiny), seq-sharded full-length for global layers."""
    caches = []
    sloc = max_seq // tp if tp > 1 else max_seq
    for li in range(cfg.n_layers):
        if cfg.is_global_layer(li) is True or (
            cfg.global_every > 0 and (li + 1) % cfg.global_every == 0
        ) or cfg.sliding_window is None:
            s = sloc
        else:
            s = cfg.sliding_window
        shape = (batch_local, s, cfg.n_kv_heads, cfg.head_dim)
        caches.append({"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)})
    return caches


def decode_step_unrolled(
    params, token, caches, pos, cfg: TransformerConfig, dist: Dist, tp: int
):
    """Decode with heterogeneous per-layer caches (gemma3 long-context)."""
    _no_serving(cfg)
    b = token.shape[0]
    x = _embed(params, token[:, None], cfg, dist)[:, 0]
    new_caches = []
    R = cfg.attn_replicas(tp)
    for li in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        cache = caches[li]
        glob = cfg.sliding_window is None or (
            cfg.global_every > 0 and (li + 1) % cfg.global_every == 0
        )
        h = rms_norm(x, lp["ln1"], cfg.eps)
        q, k, v = _qkv(h[:, None], lp, cfg, dist, jnp.full((b, 1), pos))
        kf, vf = _full_kv(k, v, cfg, dist, tp)
        kc, vc = cache["k"], cache["v"]
        if glob:
            sloc = kc.shape[1]
            midx = dist.model_index()
            owner = pos // sloc
            lpos = pos - owner * sloc
            mine = owner == midx if dist.model_axis is not None else jnp.bool_(True)
            old_k = lax.dynamic_slice(kc, (0, lpos, 0, 0), (b, 1, kf.shape[2], kf.shape[3]))
            old_v = lax.dynamic_slice(vc, (0, lpos, 0, 0), old_k.shape)
            kc = lax.dynamic_update_slice(kc, jnp.where(mine, kf, old_k), (0, lpos, 0, 0))
            vc = lax.dynamic_update_slice(vc, jnp.where(mine, vf, old_v), (0, lpos, 0, 0))
            out = _decode_attn_distributed(q[:, 0], kc, vc, pos, cfg, dist, tp)
        else:
            # rolling window cache, replicated over model: local attention
            w = kc.shape[1]
            slot = pos % w
            kc = lax.dynamic_update_slice(kc, kf, (0, slot, 0, 0))
            vc = lax.dynamic_update_slice(vc, vf, (0, slot, 0, 0))
            out = _window_decode_attn(q[:, 0], kc, vc, pos, cfg, dist, tp)
        out = out.reshape(b, -1) @ lp["wo"]
        out = dist.psum_model(out)
        if R > 1:
            out = out / R
        x = x + out.astype(x.dtype)
        h = rms_norm(x, lp["ln2"], cfg.eps)
        f, _ = _ffn_block(h[:, None], lp, cfg, dist)
        x = x + f[:, 0]
        new_caches.append({"k": kc, "v": vc})
    nxt = _greedy_logits(params, x, cfg, dist)
    return nxt, new_caches


def _window_decode_attn(q, k_roll, v_roll, pos, cfg, dist: Dist, tp: int):
    """Attention over a rolling window cache (replicated; no collectives)."""
    b, hloc, hd = q.shape
    w = k_roll.shape[1]
    tpa = cfg.tp_attn(tp)
    midx = dist.model_index()
    scale = 1.0 / math.sqrt(hd)
    qh_global = (midx % tpa) * hloc + jnp.arange(hloc)
    kv_idx = qh_global // cfg.q_group
    k_used = jnp.take(k_roll, kv_idx, axis=2)
    v_used = jnp.take(v_roll, kv_idx, axis=2)
    slot_age = (pos % w - jnp.arange(w)) % w  # age of each slot
    valid = slot_age <= jnp.minimum(pos, w - 1)
    scores = jnp.einsum("bhd,bshd->bhs", q, k_used).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", p, v_used)
