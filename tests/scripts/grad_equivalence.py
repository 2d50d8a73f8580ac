import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.models import transformer as T
from repro.models.common import Dist
from repro.core.exchange import ExchangeConfig, PSExchange
from repro.optim.optimizers import sgd, make_optimizer
from repro.runtime.trainer import make_ps_train_step, init_train_state

mesh = make_mesh((2,4), ("data","model"))
TP = 4
spec = sgd(1e-1)

def check(name, cfg, strategy="pbox"):
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    labs = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab)

    # ---------- reference: single device, 2 logical workers ----------
    p1 = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
    dist1 = Dist.none()
    init_fn, upd_fn = make_optimizer(spec)
    st = init_fn(p1)
    ref_p = p1
    for it in range(2):
        g_acc = None
        for w in range(2):
            tw, lw = toks[w*2:(w+1)*2], labs[w*2:(w+1)*2]
            g = jax.grad(lambda p: T.lm_loss(p, tw, lw, cfg, dist1, 1)[0])(ref_p)
            g_acc = g if g_acc is None else jax.tree.map(jnp.add, g_acc, g)
        g_mean = jax.tree.map(lambda x: x/2, g_acc)
        ref_p, st = upd_fn(ref_p, g_mean, st)

    # ---------- distributed PS pipeline ----------
    dist = Dist(model_axis="model", data_axes=("data",), tp=TP)
    specs = T.make_param_specs(cfg, TP)
    tags = T.grad_sync(cfg, TP)
    ex = PSExchange(spec, ExchangeConfig(strategy=strategy), worker_axes=("data",),
                    pod_axis=None)
    gshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=TP))
    def loss_fn(params, batch, dist):
        return T.lm_loss(params, batch["tokens"], batch["labels"], cfg, dist, TP)
    step, space, sspecs, ng = make_ps_train_step(
        mesh, loss_fn=loss_fn, param_specs=specs, sync_tags=tags,
        global_param_template=gshape, exchange=ex, dist=dist,
        batch_spec={"tokens": P("data"), "labels": P("data")}, donate=False)
    state = init_train_state(mesh, init_params_fn=lambda k: T.init_params(cfg, k, tp=TP),
        param_specs=specs, exchange=ex, space=space, n_groups=ng,
        key=jax.random.PRNGKey(0))
    pflat, slots, ef, stc = state.pflat, state.slots, state.ef, state.step
    for it in range(2):
        pflat, slots, ef, stc, met = step(pflat, slots, ef, stc,
            {"tokens": toks, "labels": labs})
    # compare group 0's local params vs reference's corresponding shard
    out_local = space.unflatten(np.asarray(pflat)[0])
    def take_local(x, sp, g=0):
        idx = [slice(None)]*x.ndim
        for i, s in enumerate(sp):
            if s is None: continue
            axes = s if isinstance(s, tuple) else (s,)
            if "model" in axes:
                n = x.shape[i] // TP
                idx[i] = slice(g*n, (g+1)*n)
        return x[tuple(idx)]
    # reference params in TP layout (duplicated q/o): re-init TP-layout from same key,
    # then apply the same trajectory? Instead: compare ref (tp=1 trained) mapped to tp layout
    refT = T.init_params(cfg, jax.random.PRNGKey(0), tp=TP)  # for structure
    # build tp-layout trained reference from ref_p: re-tile q/o
    R = cfg.attn_replicas(TP)
    def tile_r(x): return jnp.tile(x, (1,)*(x.ndim-1)+(R,)) if R>1 else x
    ref_tp = dict(ref_p)
    ref_tp = jax.tree.map(lambda x: x, ref_p)
    lay = dict(ref_p["layers"])
    lay["wq"] = tile_r(ref_p["layers"]["wq"])
    if "bq" in lay: lay["bq"] = tile_r(ref_p["layers"]["bq"])
    wo = jnp.swapaxes(tile_r(jnp.swapaxes(ref_p["layers"]["wo"],1,2)),1,2)
    lay["wo"] = wo
    ref_tp = {**ref_p, "layers": lay}
    errs = {}
    for k, v in out_local.items():
        if k == "layers":
            for k2, v2 in v.items():
                refl = take_local(ref_tp["layers"][k2], specs["layers"][k2])
                errs[f"layers.{k2}"] = float(jnp.max(jnp.abs(v2.astype(jnp.float32)-refl.astype(jnp.float32))))
        elif k in ("embed", "head"):
            # group 0 local rows [0, Vp/tp) overlap ref rows [0, ...): compare prefix
            n = min(v.shape[0], ref_tp[k].shape[0])
            errs[k] = float(jnp.max(jnp.abs(v[:n].astype(jnp.float32)-ref_tp[k][:n].astype(jnp.float32))))
        else:
            refl = take_local(ref_tp[k], specs[k])
            errs[k] = float(jnp.max(jnp.abs(v.astype(jnp.float32)-refl.astype(jnp.float32))))
    bad = {k: e for k, e in errs.items() if e > 2e-6}
    print(name, strategy, "max param err:", max(errs.values()))
    if bad: print("  BAD:", bad)
    return not bad

ok = True
ok &= check("dense_gqa", T.TransformerConfig("a", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
    head_dim=16, d_ff=128, vocab=256, qkv_bias=True, dtype=jnp.float32, param_dtype=jnp.float32,
    attn_chunk=8, remat=False))
ok &= check("dup_R2", T.TransformerConfig("b", n_layers=2, d_model=64, n_heads=2, n_kv_heads=1,
    head_dim=16, d_ff=128, vocab=256, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8, remat=False))
print("ALL GRAD-EQUIV:", "PASS" if ok else "FAIL")
