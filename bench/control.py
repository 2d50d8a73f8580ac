#!/usr/bin/env python3
"""Readings from which the limits of ``bench/limits/<cell>.json`` are set.

The benchmark's own runs do not run this. On the chip, at the cell's own
size and in one process:

  python3 bench/control.py --workload internlm2_1_8b_3l.s4k.1chip \
      --seeds 11,12,13 --control-seeds 11,12,13 --faults half_batch

- every seed of ``--seeds``: the program's first steps through its timed
  step against the plain reference (the lower readings: sound runs);
- every seed of ``--control-seeds``: the control, the reference computed
  in the precision below the configuration's (the configuration module's
  ``CONTROL``), put in the program's place, against the reference;
- every fault of ``--faults`` on the control seeds, planted in the
  reference put in the program's place: ``half_batch`` (half of the batch
  left out, the mean taken over the rest) and ``one_worker`` (the exchange
  left out: chip 0 applies the gradient of its own share alone).

Each reading is one JSON line on standard output; the last line sums them
up per number: the largest sound reading and the smallest of the control
and of each fault.
"""
import sys
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(CHECKOUT))


def readings(cell, seeds, control_seeds, faults, log=print) -> list:
    """The readings, one dict per (kind, seed)."""
    from bench import check, harness, reference, traffic

    out = []
    names = leaf_names(cell)
    prog = harness.Program(cell, log) if seeds else None
    for seed in sorted(set(seeds) | set(control_seeds)):
        key = traffic.seed_key(seed, 0)
        ring = traffic.make_ring(cell.ref.INPUT, cell.sizes, cell.mix,
                                 seed)[:reference.STEPS]
        ref = reference.run(cell.ref, cell.sizes, ring, key)
        kinds = []
        if seed in seeds:
            prog.start(seed)
            got = prog.first_steps()
            prog.close()
            kinds.append(("program", got))
        if seed in control_seeds:
            kinds.append(("control", reference.run(
                cell.ref, cell.sizes, ring, key, mode=cell.ref.CONTROL)))
            for f in faults:
                kinds.append((f, reference.run(cell.ref, cell.sizes, ring, key,
                                               fault=f, chips=cell.chips)))
        for kind, got in kinds:
            row = {"kind": kind, "seed": seed, "gaps": check.gaps(got, ref),
                   "losses": got.losses, "ref_losses": ref.losses,
                   "worst": worst_leaves(names, got, ref),
                   "norms": {"leaves": names,
                             "grad1": [got.grad1.tolist(), ref.grad1.tolist()],
                             "dparam": [got.dparam.tolist(),
                                        ref.dparam.tolist()]}}
            log(json.dumps(row))
            out.append(row)
    return out


def leaf_names(cell) -> list:
    """The reference's leaves by path, in the order of their norms."""
    import jax

    tree = jax.eval_shape(lambda: cell.ref.init_params(
        cell.sizes, jax.random.PRNGKey(0)))
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def worst_leaves(names, got, ref) -> dict:
    """The leaf with the largest gap of each norm compared, with its gap
    and its reference norm over the median leaf's."""
    import numpy as np

    from bench import check

    g = np.asarray(ref.grad1, np.float64)
    keep = g >= check.NEGLIGIBLE * np.median(g)
    out = {}
    for key, p, r, idx in (
            ("grad1", got.grad1, ref.grad1, np.arange(len(g))),
            ("dparam3", np.asarray(got.dparam)[keep],
             np.asarray(ref.dparam)[keep], np.flatnonzero(keep))):
        gaps = check.leaf_gaps(p, r)
        i = int(np.argmax(gaps))
        r = np.asarray(r, np.float64)
        out[key] = [names[idx[i]], float(gaps[i]),
                    float(r[i] / np.median(r))]
    return out


def summary(rows: list) -> dict:
    """Per number: the largest program reading, the smallest of the rest."""
    from bench import check

    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r["gaps"] for r in rows if r["kind"] == kind]
        pick = max if kind == "program" else min
        out[kind] = {n: pick(g[n] for g in sel) for n in check.NUMBERS}
        out[kind]["seeds"] = len(sel)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    from bench import harness

    cell = harness.resolve(args.workload)

    import jax

    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"device_count={len(devs)}", file=sys.stderr, flush=True)
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU chips", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = readings(cell, ints(args.seeds), ints(args.control_seeds),
                    [f for f in args.faults.split(",") if f],
                    log=lambda m: print(m, flush=True))
    print(json.dumps({"summary": summary(rows),
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
