"""The ResNet cell at a test's size on four devices: a sound run, then one
with the exchange between chips left out (``test_bench_faults.py`` runs this
in a process of its own, which sees four virtual CPU devices)."""
import time

import jax
from jax import lax

from bench import harness
from bench.tests import tiny
from repro.core.exchange import PSExchange
from repro.kernels.fused_agg_opt.ops import fused_aggregate_update


def no_exchange(self, gflat, pflat, state, lr_scale=1.0):
    """pbox with a push that aggregates nothing: each owner applies the
    gradient of its own share to its slab."""
    step = state["step"] + 1
    widx = lax.axis_index(self.worker_axes)
    n = gflat.shape[0] // lax.axis_size(self.worker_axes)
    slab = lax.dynamic_slice_in_dim(gflat, widx * n, n)
    pslab = lax.dynamic_slice_in_dim(pflat, widx * n, n)
    new_slab, slots = fused_aggregate_update(
        slab[None], pslab, state["slots"], self.spec, step, lr_scale,
        average=False, use_pallas=False)
    new_p = lax.all_gather(new_slab, self.worker_axes, axis=0, tiled=True)
    return new_p, {"slots": slots, "ef": state["ef"], "step": step}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    cell = tiny.cell("resnet50.b256.4chip", chips=4)
    for name in ("sound", "no_exchange"):
        if name == "no_exchange":
            PSExchange.device_update = no_exchange
        r = harness.run(cell, 2**31 + 3, 0.2, False, time.perf_counter(),
                        log=lambda m: None)
        print(name, r["correct"], r["checks"], flush=True)


if __name__ == "__main__":
    main()
