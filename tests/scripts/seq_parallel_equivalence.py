import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.models import transformer as T
from repro.models.common import Dist

mesh = make_mesh((2,4), ("data","model"))
cfg0 = T.TransformerConfig("a", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab=256, qkv_bias=True, dtype=jnp.float32, param_dtype=jnp.float32, attn_chunk=8)
cfg_sp = dataclasses.replace(cfg0, seq_parallel=True)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
labs = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 256)
pT = T.init_params(cfg0, jax.random.PRNGKey(0), tp=4)
dist = Dist(model_axis="model", data_axes=("data",), tp=4)
specs = T.make_param_specs(cfg0, 4)

def tl(cfg):
    def f(p, t, l):
        loss, met = T.lm_loss(p, t, l, cfg, dist, 4)
        return jax.lax.pmean(met["ce"], ("data",))
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(specs, P("data",None), P("data",None)),
                   out_specs=P(), check_vma=False))

l0 = tl(cfg0)(pT, toks, labs)
l1 = tl(cfg_sp)(pT, toks, labs)
print("baseline ce:", float(l0), "SP ce:", float(l1))
np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)

# grads equivalence through the full PS pipeline: SP vs non-SP, SGD 1 step
from repro.core.exchange import ExchangeConfig, PSExchange
from repro.optim.optimizers import sgd
from repro.runtime.trainer import make_ps_train_step, init_train_state
outs = []
for cfg in (cfg0, cfg_sp):
    ex = PSExchange(sgd(0.1), ExchangeConfig("pbox"), ("data",))
    gshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=4))
    step, space, ss, ng = make_ps_train_step(
        mesh, loss_fn=lambda p,b,d: T.lm_loss(p, b["tokens"], b["labels"], cfg, d, 4),
        param_specs=specs, sync_tags=T.grad_sync(cfg, 4),
        global_param_template=gshape, exchange=ex, dist=dist,
        batch_spec={"tokens": P("data"), "labels": P("data")}, donate=False)
    st = init_train_state(mesh, init_params_fn=lambda k: T.init_params(cfg, k, tp=4),
        param_specs=specs, exchange=ex, space=space, n_groups=ng, key=jax.random.PRNGKey(0))
    pf, sl, ef, sc, met = step(st.pflat, st.slots, st.ef, st.step, {"tokens": toks, "labels": labs})
    outs.append(np.asarray(pf))
err = np.abs(outs[0] - outs[1]).max()
print("param diff SP vs baseline after 1 SGD step:", err)
assert err < 2e-6
print("SEQ-PARALLEL EXACT OK")
