"""Serving driver: batched LM generation against the fabric's read plane.

The model is served the way the PS serves it — not from a freestanding
param pytree, but through ``core/serving.ReadPlane``: the parameters live
in a ``PBoxFabric`` (optionally chain-replicated, optionally mid-training)
or in a checkpoint, and generation pulls a *version-stamped,
staleness-bounded* read whose bits are asserted identical to the fabric's
flat space at the stamped round.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --mesh 1x2 \
      --tokens 16 --batch 4 --source fabric --train-rounds 2

The mesh must fit the devices present (``--mesh 1x2`` needs two).

Sources:
  fabric      build a PBoxFabric over the model, run ``--train-rounds``
              rounds of (deterministic, seeded) synthetic-gradient
              training, then serve reads from the chain replica tails
              (``--serve-replication`` >= 2) or the primary slabs.
  checkpoint  the same fabric, persisted through ``checkpoint.Checkpointer``
              and served back via a ``SnapshotSource`` — the
              checkpoint-warmed serving tier.  With ``--train-rounds 0``
              and an existing ``--checkpoint`` dir, serves it as-is.
  model       the legacy freestanding path (no read plane): generation
              straight off the init params.

``main(argv)`` returns a result dict (generated ids, read provenance,
timings) so tests can drive it in-process; the CLI prints the same.
"""
from __future__ import annotations

import argparse
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # read-plane source (core/serving.py)
    ap.add_argument("--source", default="fabric",
                    choices=("fabric", "checkpoint", "model"),
                    help="where generation's parameters come from: a live "
                         "PBox fabric's read plane, a checkpointed read "
                         "plane, or the legacy freestanding model")
    ap.add_argument("--serve-shards", type=int, default=2)
    ap.add_argument("--serve-racks", type=int, default=1)
    ap.add_argument("--serve-replication", type=int, default=2,
                    help=">= 2 serves reads from chain replica tails")
    ap.add_argument("--serve-workers", type=int, default=2,
                    help="synthetic training workers pushing to the fabric")
    ap.add_argument("--train-rounds", type=int, default=2,
                    help="synthetic-gradient rounds to run before serving "
                         "(the 'live training' the reads happen under)")
    ap.add_argument("--max-staleness", type=int, default=0)
    ap.add_argument("--frontends", type=int, default=1)
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="checkpoint directory (source=checkpoint)")
    return ap


def _build_fabric(args, space, flat):
    """The serving-side fabric: the model's flat space on a small sharded,
    optionally replicated box under synthetic training load."""
    from repro.core.config import FabricConfig, FaultConfig, WireConfig
    from repro.core.fabric import PBoxFabric
    from repro.core.topology import NetworkTopology
    from repro.optim.optimizers import sgd

    workers = max(1, args.serve_workers)
    topology = None
    if args.serve_racks > 1 and workers > 1:
        topology = NetworkTopology(num_workers=workers,
                                   num_racks=min(args.serve_racks, workers))
    config = FabricConfig(
        num_shards=max(1, args.serve_shards),
        num_workers=workers,
        wire=WireConfig(topology=topology),
        faults=FaultConfig(replication=max(1, args.serve_replication)),
    )
    return PBoxFabric(space, sgd(1e-3), flat, config=config)


def _train_rounds(args, fabric, space) -> None:
    """Deterministic synthetic-gradient rounds: the live training the
    serve reads contend with.  Seeded — the same invocation always serves
    the same bits."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(args.seed + 1)
    for _ in range(args.train_rounds):
        grads = [
            jnp.asarray(1e-3 * rng.standard_normal(space.flat_elems),
                        jnp.float32)
            for _ in range(fabric.num_workers)
        ]
        for w in range(fabric.num_workers):
            fabric.pull(w)
        for w in range(fabric.num_workers):
            fabric.push(w, grads[w])


def _serve_params(args, params, space):
    """Route the model's parameters through a read plane per ``--source``.

    Returns (served param pytree, provenance dict).  The headline check
    runs here: the read's bits must be identical to the source's flat
    space at the stamped version."""
    import numpy as np

    from repro.core.config import ServeConfig
    from repro.core.serving import ReadPlane, SnapshotSource

    flat = space.flatten(params)
    fabric = _build_fabric(args, space, flat)
    _train_rounds(args, fabric, space)

    if args.source == "checkpoint":
        from repro.checkpoint.checkpointer import (
            Checkpointer,
            flat_to_fabric_snapshot,
        )

        if args.checkpoint is None:
            raise SystemExit("--source checkpoint needs --checkpoint DIR")
        ckpt = Checkpointer(args.checkpoint)
        restore_step = None  # latest, when serving an existing dir as-is
        if ckpt.latest_step() is None or args.train_rounds > 0:
            ckpt.save_fabric(fabric.step, fabric)
            # pin the restore to the step just saved: the dir may hold a
            # later checkpoint from a longer previous run, and serving
            # that would silently hand out another invocation's bits
            restore_step = fabric.step
        state, _meta = ckpt.restore(restore_step)
        snap = flat_to_fabric_snapshot(state)
        source = SnapshotSource.from_snapshot(
            snap, chunk_elems=space.chunk_elems)
        plane = ReadPlane(source, config=ServeConfig(
            max_staleness=args.max_staleness,
            num_frontends=args.frontends))
        expect = np.asarray(snap["params"])
    else:
        plane = ReadPlane(fabric, config=ServeConfig(
            max_staleness=args.max_staleness,
            num_frontends=args.frontends))
        expect = np.asarray(fabric.params)

    read = plane.read(0)
    if not np.array_equal(np.asarray(read.flat), expect):
        raise AssertionError(
            f"read at version {read.version} is not bit-identical to the "
            "source's flat parameter space — the read plane's headline "
            "invariant broke"
        )
    info = {
        "version": read.version,
        "staleness": read.staleness,
        "cache_hit": read.cache_hit,
        "plane": plane.describe(),
        "replication": fabric.replication,
        "shards": fabric.num_shards,
    }
    return space.unflatten(read.flat), info


def main(argv=None) -> dict:
    args = build_argparser().parse_args(argv)

    d, m = (int(x) for x in args.mesh.split("x"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.core.chunking import ParamSpace
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models.common import Dist
    from repro.models import transformer as T
    from jax.sharding import PartitionSpec as P

    enable_compile_cache()
    mesh = make_mesh((d, m), ("data", "model"))
    arch = get_arch(args.arch)
    cfg = arch.smoke_config
    if arch.family != "lm":
        raise SystemExit("serve.py drives LM archs; recsys serving is "
                         "exercised via launch/steps.py serve cells")
    tp = m
    dist = Dist(model_axis="model" if m > 1 else None,
                data_axes=("data",) if d > 1 else (), tp=tp)
    specs = T.make_param_specs(cfg, tp)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed), tp=tp)
    max_seq = args.prompt_len + args.tokens
    max_seq = -(-max_seq // tp) * tp

    read_info: dict | None = None
    if args.source != "model":
        space = ParamSpace.build(params)
        params, read_info = _serve_params(args, params, space)
        print(f"read plane [{args.source}]: version {read_info['version']}, "
              f"staleness {read_info['staleness']}, "
              f"{read_info['shards']} shards, "
              f"R={read_info['replication']} — bits verified against the "
              "source")
        print(read_info["plane"])

    wa = ("data",) if d > 1 else ()
    bspec = P(wa) if wa else P()
    cache_spec = {"k": P(None, wa, "model" if m > 1 else None),
                  "v": P(None, wa, "model" if m > 1 else None)}

    pf = jax.jit(jax.shard_map(
        lambda p, t: T.prefill(p, t, cfg, dist, tp, max_seq),
        mesh=mesh, in_specs=(specs, bspec),
        out_specs=(bspec, cache_spec), check_vma=False))
    dc = jax.jit(jax.shard_map(
        lambda p, t, c, pos: T.decode_step(p, t, c, pos, cfg, dist, tp),
        mesh=mesh, in_specs=(specs, bspec, cache_spec, P()),
        out_specs=(bspec, cache_spec), check_vma=False))

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    t0 = time.time()
    nxt, cache = pf(params, prompts)
    t_prefill = time.time() - t0
    out = [np.asarray(nxt)]
    t0 = time.time()
    for i in range(args.tokens - 1):
        nxt, cache = dc(params, nxt, cache, jnp.int32(args.prompt_len + i))
        out.append(np.asarray(nxt))
    t_dec = time.time() - t0
    gen = np.stack(out, axis=1)
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f} ms; "
          f"{args.tokens-1} decode steps in {t_dec*1e3:.1f} ms "
          f"({t_dec/max(1, args.tokens-1)*1e3:.2f} ms/tok)")
    print("generated ids:\n", gen)
    return {
        "generated": gen,
        "source": args.source,
        "read": read_info,
        "prefill_ms": t_prefill * 1e3,
        "decode_ms": t_dec * 1e3,
    }


if __name__ == "__main__":
    main()
