"""Distributed checks: hierarchical collectives == flat; ZeroComputeEngine
runs and its pbox collective bytes are invariant in worker count."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.exchange import ExchangeConfig, PSExchange
from repro.core.hierarchy import hierarchical_pmean, hierarchical_psum
from repro.core.zero_compute import init_zero_compute_state, make_zero_compute_step
from repro.optim.optimizers import momentum

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

# hierarchical psum == flat psum
def f(x):
    a = jax.lax.psum(x, ("data", "pod"))
    b = hierarchical_psum(x, ("data",), "pod")
    c = hierarchical_pmean(x, ("data",), "pod")
    return a, b, c

g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=(P(None), P(None), P(None)), check_vma=False))
x = jnp.arange(32.0).reshape(4, 8)
a, b, c = g(x.reshape(-1))
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
np.testing.assert_allclose(np.asarray(a) / 4, np.asarray(c), rtol=1e-6)
print("hierarchical == flat OK")

# zero-compute engine: one exchange step, params move as SGD on the grads
for strategy, pod in [("pbox", None), ("pbox_hier", "pod"), ("allreduce", None)]:
    ex = PSExchange(momentum(0.1, 0.9), ExchangeConfig(strategy=strategy),
                    ("pod", "data", "model"), pod)
    flat = 8192 * 8
    step = make_zero_compute_step(mesh, ex, flat)
    state = init_zero_compute_state(mesh, ex, flat)
    p = jnp.zeros((flat,))
    gflat = jnp.ones((flat,))
    p2, state = step(p, gflat, state)
    # momentum step 1: m = g, p -= lr*m = -0.1 (grads identical on workers)
    np.testing.assert_allclose(np.asarray(p2), -0.1, rtol=1e-5)
    print(f"zero-compute {strategy} OK")
print("ALL OK")
