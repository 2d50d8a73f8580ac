import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.models.gnn import equiformer_v2 as EQ
from repro.models.common import Dist
from repro.data.graphs import random_graph

mesh = make_mesh((2,4), ("data","model"))
cfg0 = EQ.EquiformerConfig("t", n_layers=2, channels=16, l_max=2, m_max=1, n_heads=4,
                           n_rbf=8, d_in=12, n_out=5, task="node_class", remat=False)
cfg_ep = dataclasses.replace(cfg0, edge_parallel=True)

# single-device reference
g = random_graph(24, 64, 12, 5, l_max=2, n_rbf=8, seed=3)
gj = jax.tree.map(jnp.asarray, g)
p0 = EQ.init_params(cfg0, jax.random.PRNGKey(0), 1)
ref, _ = EQ.loss_fn(p0, gj, cfg0, Dist.none())

# ep distributed: graph replicated per worker (full_graph mode); edges sharded over model
dist = Dist(model_axis="model", data_axes=("data",), tp=4)
specs = EQ.make_param_specs(cfg_ep, 4)  # all replicated
bspec = {k: (P("model") if k in ("edge_src","edge_dst","edge_mask","wigner","rbf") else P())
         for k in gj}
def f(p, g):
    loss, met = EQ.loss_fn(p, g, cfg_ep, dist)
    return loss * 4  # undo /tp for comparison
fj = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(specs, bspec), out_specs=P(), check_vma=False))
lep = fj(p0, gj)
print("ref:", float(ref), "edge-parallel:", float(lep))
np.testing.assert_allclose(float(ref), float(lep), rtol=1e-5)

# grads: ep tags + /tp -> psum over model must equal single-device grads
from repro.runtime.trainer import apply_grad_sync
tags = EQ.grad_sync(cfg_ep, 4)
def gradf(p, g):
    gr = jax.grad(lambda p_: EQ.loss_fn(p_, g, cfg_ep, dist)[0])(p)
    gr = apply_grad_sync(gr, tags, dist)
    return gr
gj_fn = jax.jit(jax.shard_map(gradf, mesh=mesh, in_specs=(specs, bspec),
               out_specs=jax.tree.map(lambda _: P(), specs), check_vma=False))
g_ep = gj_fn(p0, gj)
g_ref = jax.grad(lambda p_: EQ.loss_fn(p_, gj, cfg0, Dist.none())[0])(p0)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32))))
          for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_ref)))
print("grad max err:", err)
assert err < 1e-4
print("EDGE-PARALLEL EXACT OK")
