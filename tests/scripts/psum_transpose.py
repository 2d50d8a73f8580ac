import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
mesh = make_mesh((4,), ("model",))

# per-device: y = psum(x * w_local); loss_local = y * c_local (device-varying)
# truth: L_total interpretation? We compute grad of the PER-DEVICE loss function
# as shard_map'd program and inspect w grads.
def f(w, c):
    x = 2.0
    y = jax.lax.psum(x * w, "model")   # scalar replicated
    return y * c                        # device-varying loss

def gradfn(w, c):
    g = jax.grad(lambda w_: f(w_, c))(w)
    return g[None] if g.ndim == 0 else g

w = jnp.arange(1., 5.)  # w_j = j+1 per device
c = jnp.array([10., 20., 30., 40.])
g = jax.jit(jax.shard_map(lambda w, c: jax.grad(lambda w_: f(w_[0], c[0]))(w), mesh=mesh,
    in_specs=(P("model"), P("model")), out_specs=P("model"), check_vma=False))(w, c)
print("per-device dw:", np.array(g))
print("if transpose(psum)=psum -> each dw_j = 2*sum(c) = 200")
print("if transpose(psum)=identity/broadcast -> dw_j = 2*c_j = [20,40,60,80]")
