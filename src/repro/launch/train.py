"""End-to-end training driver.

Runs real steps (synthetic data) on the devices that exist: smoke-scale
configs on a CPU host, full configs (``--full``) on accelerators.  The mesh
must fit the devices present; nothing is emulated.  Demonstrates the full
runtime: PS exchange, prefetching pipeline, async checkpointing,
crash-restart (--resume), and elastic owner-count changes.

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --steps 50 \
      --mesh 1x1 --smoke --ckpt-dir /tmp/ckpt --ckpt-every 20
  PYTHONPATH=src python -m repro.launch.train --arch resnet50 --full \
      --steps 3 --log-every 1
"""
from __future__ import annotations

import argparse
import math
import time
from collections.abc import Callable

import jax

from repro.checkpoint import Checkpointer
from repro.checkpoint.checkpointer import flat_to_train_state, train_state_to_flat
from repro.configs.registry import get_arch
from repro.core.exchange import ExchangeConfig
from repro.data.pipeline import Prefetcher
from repro.data.synthetic import image_batches, lm_batches, recsys_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell, make_exchange
from repro.runtime.trainer import TrainState, init_train_state


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--shape", default=None, help="defaults to the train cell")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--strategy", default="pbox",
                    choices=["allreduce", "pbox", "pbox_hier"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _init_fn_and_specs(arch, cfg, m: int):
    """(key -> global params, param PartitionSpecs) for one family."""
    if arch.family == "lm":
        from repro.models import transformer as T
        return (lambda k: T.init_params(cfg, k, tp=m)), T.make_param_specs(cfg, m)
    if arch.family == "recsys":
        from repro.launch.steps import _RS_FNS
        fi, fs = _RS_FNS[arch.arch_id][0], _RS_FNS[arch.arch_id][1]
        return (lambda k: fi(cfg, k, m)), fs(cfg, m)
    if arch.family == "vision":
        from repro.models.resnet import init_params as ip
        specs = jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(),
            jax.eval_shape(lambda: ip(cfg, jax.random.PRNGKey(0))),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return (lambda k: ip(cfg, k)), specs
    import dataclasses as dc

    from repro.models.gnn.equiformer_v2 import init_params as ip
    from repro.models.gnn.equiformer_v2 import make_param_specs as mps
    gcfg = dc.replace(cfg, d_in=cfg.d_in, n_out=1, task="graph_reg")
    return (lambda k: ip(gcfg, k, m)), mps(gcfg, m)


def _batches(arch, cfg, bt: dict, seed: int):
    """Endless synthetic batches shaped like the plan's batch ``bt``."""
    if arch.family == "lm":
        gb, s = bt["tokens"].shape
        return lm_batches(cfg.vocab, gb, s, seed)
    if arch.family == "recsys":
        return recsys_batches(arch.arch_id, cfg, bt["sparse"].shape[0], seed)
    if arch.family == "vision":
        return image_batches(bt["images"].shape[0], bt["images"].shape[1],
                             cfg.n_classes, seed)
    from repro.data.graphs import random_molecule_batch

    def gen():
        i = 0
        while True:
            yield random_molecule_batch(
                bt["targets"].shape[0], 8,
                bt["edge_src"].shape[0] // bt["targets"].shape[0],
                cfg.d_in, cfg.l_max, cfg.n_rbf, seed=seed + i)
            i += 1
    return gen()


def main(argv=None, exchange_cfg: ExchangeConfig | None = None,
         on_step: Callable[[int, jax.Array], None] | None = None) -> dict:
    """Train ``--steps`` steps; returns what the run measured.

    ``exchange_cfg`` replaces the strategy's default ``ExchangeConfig``
    (``--strategy`` still names the strategy when it is ``None``).
    ``on_step(step, pflat)`` is called after each timed step with the step's
    number (from 1) and the new flat parameters; the next step donates
    them, so a caller that keeps them copies them.  The result holds the
    per-step ``losses``, the per-step wall time ``step_s`` (host clock
    around each step, after ``block_until_ready``), the AOT ``compile_s``,
    the final flat parameters ``pflat``, the plan's ``meta`` and the
    compiled ``step``."""
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    arch = get_arch(args.arch)
    shape = args.shape or {
        "lm": "train_4k", "recsys": "train_batch", "gnn": "molecule",
        "vision": "imagenet_train",
    }[arch.family]
    if args.shape is None and shape not in (c.name for c in arch.cells):
        shape = next(c.name for c in arch.cells if c.kind == "train")
    if exchange_cfg is None:
        exchange_cfg = ExchangeConfig(strategy=args.strategy)
    plan = build_cell(args.arch, shape, mesh, exchange_cfg=exchange_cfg,
                      smoke=args.smoke)
    cfg = arch.smoke_config if args.smoke else arch.config
    exchange = make_exchange(mesh, arch.family, exchange_cfg=exchange_cfg)
    shardings = jax.tree.map(
        lambda a: a.sharding, plan.abstract_args,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    data = Prefetcher(
        _batches(arch, cfg, plan.abstract_args[4], args.seed), depth=2,
        transform=lambda b: jax.device_put(b, shardings[4]))

    # ---- state (fresh or restored) ----
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.resume and ckpt and ckpt.latest_step() is not None:
        host, _ = ckpt.restore()
        state = flat_to_train_state(host, TrainState)
        start = int(host["step"])
        print(f"resumed from step {start}")
    else:
        init_fn, specs = _init_fn_and_specs(arch, cfg, m)
        state = init_train_state(
            mesh, init_params_fn=init_fn, param_specs=specs, exchange=exchange,
            space=plan.meta["space"], n_groups=plan.meta["n_groups"],
            key=jax.random.PRNGKey(args.seed),
            ps_dtype=plan.abstract_args[0].dtype)
    pflat, slots, ef, stc = jax.device_put(
        (state.pflat, state.slots, state.ef, state.step), shardings[:4])

    losses, step_s = [], []
    try:
        batch = next(data)
        t0 = time.perf_counter()
        step = plan.fn.lower(pflat, slots, ef, stc, batch).compile()
        compile_s = time.perf_counter() - t0
        print(f"compiled {args.arch}/{shape} on {args.mesh} "
              f"({mesh.devices.flat[0].platform}) in {compile_s:.2f} s",
              flush=True)
        for i in range(start, args.steps):
            if i > start:
                batch = next(data)
            t0 = time.perf_counter()
            pflat, slots, ef, stc, met = step(pflat, slots, ef, stc, batch)
            jax.block_until_ready((pflat, met))
            step_s.append(time.perf_counter() - t0)
            met = jax.tree.map(float, jax.device_get(met))
            losses.append(met["loss"])
            if (i + 1) % args.log_every == 0 or i == start:
                print(f"step {i+1:5d} loss={met['loss']:.4f} "
                      + " ".join(f"{k}={v:.4f}" for k, v in met.items()
                                 if k != "loss")
                      + f" ({step_s[-1]*1e3:.1f} ms/step)", flush=True)
            if on_step:
                on_step(i + 1, pflat)
            if ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
                st = TrainState(pflat=pflat, slots=slots, ef=ef, step=stc)
                ckpt.save_async(i + 1, train_state_to_flat(st))
        if ckpt:
            st = TrainState(pflat=pflat, slots=slots, ef=ef, step=stc)
            ckpt.save(args.steps, train_state_to_flat(st))
            ckpt.wait()
    finally:
        data.close()
    bad = [i for i, x in enumerate(losses) if not math.isfinite(x)]
    if bad:
        raise FloatingPointError(f"non-finite loss at steps {bad}: {losses}")
    print("done")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "pflat": pflat, "meta": plan.meta, "step": step}


if __name__ == "__main__":
    main()
