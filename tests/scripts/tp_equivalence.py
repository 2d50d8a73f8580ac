import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.models.transformer import (TransformerConfig, init_params, lm_loss, prefill,
    decode_step, make_param_specs)
from repro.models.moe import MoEConfig
from repro.models.common import Dist

mesh = make_mesh((2,4), ("data","model"))
TP = 4

def run_case(name, cfg):
    # --- single device reference (tp=1 model) ---
    cfg1 = cfg
    p1 = init_params(cfg1, jax.random.PRNGKey(0), tp=1)
    dist1 = Dist.none()
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    labs = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab)
    loss1 = jax.jit(lambda p,t,l: lm_loss(p,t,l,cfg1,dist1,1)[1]["ce"])(p1, toks, labs)
    nxt1, cache1 = jax.jit(lambda p,t: prefill(p,t,cfg1,dist1,1,32))(p1, toks)
    nxt1b, _ = jax.jit(lambda p,t,c: decode_step(p,t,c,jnp.int32(16),cfg1,dist1,1))(p1, nxt1, cache1)
    # decode-vs-prefill consistency: prefill 17 tokens = toks + nxt1
    toks17 = jnp.concatenate([toks, nxt1[:,None]], axis=1)
    nxt1c, _ = jax.jit(lambda p,t: prefill(p,t,cfg1,dist1,1,32))(p1, toks17)
    assert np.array_equal(np.array(nxt1b), np.array(nxt1c)), f"{name} decode!=prefill: {nxt1b} vs {nxt1c}"

    # --- TP=4 distributed (duplicate-layout init with same base key) ---
    pT = init_params(cfg, jax.random.PRNGKey(0), tp=TP)
    # check duplicated layout matches: wq tiled
    dist = Dist(model_axis="model", data_axes=("data",), tp=TP)
    specs = make_param_specs(cfg, TP)
    def tl(p, t, l):
        loss, met = lm_loss(p, t, l, cfg, dist, TP)
        return jax.lax.pmean(met["ce"], ("data",))
    f = jax.jit(jax.shard_map(tl, mesh=mesh, in_specs=(specs, P("data",None), P("data",None)),
                              out_specs=P(), check_vma=False))
    lossT = f(pT, toks, labs)
    np.testing.assert_allclose(float(lossT), float(loss1), rtol=2e-5, atol=1e-5)

    # TP prefill+decode
    def pf(p, t):
        return prefill(p, t, cfg, dist, TP, 32)
    cache_specs = {"k": P(None, "data", "model", None, None), "v": P(None, "data", "model", None, None)}
    fpf = jax.jit(jax.shard_map(pf, mesh=mesh, in_specs=(specs, P("data",None)),
                  out_specs=(P("data"), cache_specs), check_vma=False))
    nxtT, cacheT = fpf(pT, toks)
    assert np.array_equal(np.array(nxtT), np.array(nxt1)), f"{name} prefill TP mismatch {nxtT} vs {nxt1}"
    def dc(p, t, c):
        return decode_step(p, t, c, jnp.int32(16), cfg, dist, TP)
    fdc = jax.jit(jax.shard_map(dc, mesh=mesh, in_specs=(specs, P("data"), cache_specs),
                  out_specs=(P("data"), cache_specs), check_vma=False))
    nxtTb, _ = fdc(pT, nxtT, cacheT)
    assert np.array_equal(np.array(nxtTb), np.array(nxt1b)), f"{name} decode TP mismatch {nxtTb} vs {nxt1b}"
    print(name, "TP==single OK, loss", float(loss1))

# case 1: heads 8 >= tp 4, kv 2 < tp -> kv replicated, R=1
run_case("gqa_kvrep", TransformerConfig("a", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=256, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8))
# case 2: heads 2 < tp 4 -> R=2 duplication; kv=1 replicated
run_case("dup_R2", TransformerConfig("b", n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, head_dim=16,
        d_ff=128, vocab=256, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8))
# case 3: kv sharded (kv=4=tp), qkv bias
run_case("kvshard_bias", TransformerConfig("c", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab=256, qkv_bias=True, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8))
# case 4: MoE
run_case("moe", TransformerConfig("d", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=0, vocab=256, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, shared_d_ff=64, capacity_factor=4.0)))
print("ALL TP CASES OK")
