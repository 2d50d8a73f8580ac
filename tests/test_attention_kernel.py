"""The causal flash-attention kernel against its oracle, and the model's rule
for when it runs.

The kernel runs in Pallas interpret mode here, at tiles of 128 so that the
blocks above the diagonal are skipped; ``tests/test_tpu_compile.py``
compiles it for a described v5e at the LM's widths.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.attention import causal_attention, kernel_block, kernel_fits
from repro.kernels.attention.ref import causal_attention_ref
from repro.models import transformer
from repro.models.transformer import TransformerConfig

HEADS, KV_HEADS, HEAD_DIM = 4, 2, 128
# relative error (Frobenius) of the output and of dq, dk, dv
TOL = {jnp.bfloat16: 1e-2, jnp.float32: 1e-5}


def _inputs(seq, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(ks[0], (1, seq, HEADS, HEAD_DIM), dtype)
    k = jax.random.normal(ks[1], (1, seq, KV_HEADS, HEAD_DIM), dtype)
    v = jax.random.normal(ks[2], (1, seq, KV_HEADS, HEAD_DIM), dtype)
    do = jax.random.normal(ks[3], (1, seq, HEADS, HEAD_DIM), dtype)
    return (q, k, v), do


def _out_and_grads(fn, args, do):
    out, pull = jax.vjp(fn, *args)
    return (out, *pull(do.astype(out.dtype)))


def _rel_err(x, y):
    x, y = x.astype(jnp.float32).ravel(), y.astype(jnp.float32).ravel()
    return float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("seq", [256, 512])
def test_kernel_matches_reference(seq, dtype):
    args, do = _inputs(seq, dtype)
    got = _out_and_grads(lambda q, k, v: causal_attention(q, k, v, block=128),
                         args, do)
    oracle = _out_and_grads(causal_attention_ref, args, do)
    with jax.default_matmul_precision("highest"):
        plain = _out_and_grads(
            lambda *a: causal_attention_ref(*(x.astype(jnp.float32) for x in a)),
            args, do)
    primal = causal_attention(*args, block=128)  # no gradient taken
    assert _rel_err(primal, oracle[0]) < TOL[dtype]
    for name, g, o, p in zip(("out", "dq", "dk", "dv"), got, oracle, plain):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert _rel_err(g, o) < TOL[dtype], (name, _rel_err(g, o))
        assert _rel_err(g, p) < TOL[dtype], (name, _rel_err(g, p))


def _cfg(**kw):
    return TransformerConfig(name="t", n_layers=1, d_model=HEADS * HEAD_DIM,
                             n_heads=HEADS, n_kv_heads=HEADS, head_dim=HEAD_DIM,
                             d_ff=64, vocab=64, dtype=jnp.float32,
                             param_dtype=jnp.float32, attn_chunk=64, **kw)


@pytest.mark.parametrize("case, seq, q0, cfg_kw, tpu, kernel", [
    ("tiles", 1024, 0, {}, True, True),
    ("sliding_window", 1024, 0, {"sliding_window": 256, "global_every": 2},
     True, False),
    ("q0", 1024, 128, {}, True, False),
    ("untiled", 640, 0, {}, True, False),
    ("not_a_tpu", 1024, 0, {}, False, False),
])
def test_attention_dispatch(monkeypatch, case, seq, q0, cfg_kw, tpu, kernel):
    """The model takes the kernel only on a TPU, for a global causal layer
    from position 0 whose sequence tiles; the rest stays on the jnp path."""
    taken = []
    monkeypatch.setattr(transformer, "interpret_mode", lambda: not tpu)
    monkeypatch.setattr(transformer, "causal_attention",
                        lambda q, k, v: taken.append("kernel") or q)
    chunked = transformer._chunked_attention
    monkeypatch.setattr(
        transformer, "_chunked_attention",
        lambda *a, **kw: taken.append("chunked") or chunked(*a, **kw))
    x = jnp.ones((1, seq, HEADS, HEAD_DIM), jnp.float32)
    out = transformer._attention(x, x, x, _cfg(**cfg_kw), jnp.bool_(True), q0)
    assert out.shape == x.shape
    assert taken == ["kernel" if kernel else "chunked"], case


@pytest.mark.parametrize("seq, head_dim, fits", [
    (4096, 128, True), (512, 128, True), (256, 256, True), (128, 128, True),
    (640, 128, False), (96, 128, False), (4096, 64, True), (8192, 192, True),
    (4096, 320, False),
])
def test_kernel_fits(seq, head_dim, fits):
    """Any head size that pads to at most 256 lanes fits; the sequence must
    tile."""
    assert kernel_fits(seq, head_dim) is fits
    assert kernel_block(seq) == min(512, seq)


@pytest.mark.parametrize("shapes, dtypes, block", [
    (((1, 256, 4, 128), (1, 256, 3, 128), (1, 256, 3, 128)), None, None),
    (((1, 256, 4, 128), (1, 128, 2, 128), (1, 128, 2, 128)), None, None),
    (((1, 256, 4, 64), (1, 256, 2, 128), (1, 256, 2, 128)), None, None),
    (((1, 256, 4, 128), (1, 256, 2, 128), (1, 256, 2, 128)), None, 96),
    (((1, 256, 4, 128),) * 3, (jnp.bfloat16, jnp.float32, jnp.float32), None),
], ids=["heads", "kv_len", "head_dim", "block", "dtype"])
def test_kernel_rejects(shapes, dtypes, block):
    dtypes = dtypes or (jnp.float32,) * 3
    args = [jnp.zeros(s, d) for s, d in zip(shapes, dtypes)]
    with pytest.raises(ValueError):
        causal_attention(*args, block=block)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_kernel_with_latent_head_sizes(dtype):
    """Latent attention's heads: q and k of 192, v of 128, padded to 256
    inside the kernel and scaled by 1/sqrt(192); the output and dq, dk, dv
    against the oracle at the same tolerances as above."""
    seq, dqk, dv = 256, 192, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, seq, HEADS, dqk), dtype)
    k = jax.random.normal(ks[1], (1, seq, HEADS, dqk), dtype)
    v = jax.random.normal(ks[2], (1, seq, HEADS, dv), dtype)
    do = jax.random.normal(ks[3], (1, seq, HEADS, dv), dtype)
    got = _out_and_grads(lambda q, k, v: causal_attention(q, k, v, block=128),
                         (q, k, v), do)
    oracle = _out_and_grads(causal_attention_ref, (q, k, v), do)
    assert got[0].shape == (1, seq, HEADS, dv)
    for name, g, o in zip(("out", "dq", "dk", "dv"), got, oracle):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        assert _rel_err(g, o) < TOL[dtype], (name, _rel_err(g, o))


def test_latent_attention_at_8k_takes_the_kernel_on_a_tpu(monkeypatch):
    """At the Moonlight cell's shapes (S 8192, Dqk 192, Dv 128) the rule
    sends attention to the kernel on a TPU, never to the chunked path; the
    kernel is replaced here, so nothing runs at that size."""
    taken = []
    monkeypatch.setattr(transformer, "interpret_mode", lambda: False)
    monkeypatch.setattr(transformer, "causal_attention",
                        lambda q, k, v: taken.append("kernel") or v)
    monkeypatch.setattr(transformer, "_chunked_attention",
                        lambda *a, **kw: taken.append("chunked"))
    q = jax.ShapeDtypeStruct((1, 8192, 16, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16)
    out = jax.eval_shape(lambda q, k, v: transformer._attention(
        q, k, v, _cfg(), jnp.bool_(True)), q, q, v)
    assert taken == ["kernel"] and out.shape == v.shape
