import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

from repro.core.exchange import ExchangeConfig, PSExchange
from repro.core.compression import CompressionConfig
from repro.optim.optimizers import adam, make_optimizer

mesh = make_mesh((2,2,2), ("pod","data","model"))
spec = adam(1e-2)

# toy model: params = dict of two tensors; grads differ per worker (batch-sharded)
params = {"w": jnp.arange(24., dtype=jnp.float32).reshape(4,6)/10, "b": jnp.ones((5,), jnp.float32)}

def make_grads(widx):  # deterministic per-worker grads
    return {"w": jnp.full((4,6), widx+1.0), "b": jnp.arange(5.)*(widx+1)}

def run_strategy(strategy, worker_axes, pod_axis, codec="none", steps=3):
    cfg = ExchangeConfig(strategy=strategy, compression=CompressionConfig(codec=codec))
    ex = PSExchange(spec, cfg, worker_axes, pod_axis)
    space = ex.build_space(params, dict(mesh.shape))
    state = ex.init_slab_state(space)

    def body(pflat, slots, step):
        widx = jax.lax.axis_index(ex.worker_axes).astype(jnp.float32)
        st = {"slots": slots, "ef": None, "step": step}
        for _ in range(steps):
            g = space.flatten(make_grads(widx))
            pflat, st = ex.device_update(g, pflat, st)
        return pflat, st["slots"]

    n_owner = max(space.num_owners, 1) if strategy != "allreduce" else 1
    slab_spec = P(ex.owner_axes) if ex.owner_axes else P()
    slots_specs = tuple(slab_spec for _ in range(spec.num_state_slots))
    f = jax.jit(jax.shard_map(body, mesh=mesh,
        in_specs=(P(), slots_specs, P()),
        out_specs=(P(), slots_specs), check_vma=False))
    pflat0 = space.flatten(params)
    glob_slab = space.flat_elems  # slots global size: slab*owners = flat (pbox), flat (allreduce, replicated)
    slots0 = tuple(jnp.zeros((glob_slab,), jnp.float32) for _ in range(spec.num_state_slots))
    pf, _ = f(pflat0, slots0, jnp.zeros((), jnp.int32))
    return space.unflatten(pf)

# reference: tree-wise optimizer on mean grad over 8 workers (all-axes worker set)
init_fn, upd_fn = make_optimizer(spec)
ref_p, ref_s = params, init_fn(params)
nw = 8
for _ in range(3):
    gsum = jax.tree.map(lambda *gs: sum(gs)/nw, *[make_grads(float(w)) for w in range(nw)])
    ref_p, ref_s = upd_fn(ref_p, gsum, ref_s)

for strat, wa, pa in [("allreduce", ("pod","data","model"), None),
                      ("pbox", ("pod","data","model"), None),
                      ("pbox_hier", ("pod","data","model"), "pod")]:
    out = run_strategy(strat, wa, pa)
    for k in params:
        np.testing.assert_allclose(np.array(out[k]), np.array(ref_p[k]), rtol=2e-5, atol=2e-6)
    print(strat, "== reference DP-Adam  OK")

out = run_strategy("pbox_hier", ("pod","data","model"), "pod", codec="int8")
err = max(float(jnp.max(jnp.abs(out[k]-ref_p[k]))) for k in params)
print("pbox_hier+int8 max abs diff vs ref:", err, "(expected small but nonzero)")
