#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

  python3 bench/run.py --workload resnet50.b256.4chip --seed 7 \
      --seconds 30 --trace 0

Prints the platform, device kind and device count, and exits non-zero with
no result where JAX finds no TPU or fewer chips than the cell asks for.
Otherwise it builds the cell's step through the program's own builders,
with weights and batches from ``--seed``, compiles it (JAX's persistent
compilation cache lives in the checkout), checks its first steps against
the plain reference, measures for ``--seconds`` seconds (``--trace 1``:
traces a short window instead and reports the per-layer metrics), and
prints one JSON object as the last line of standard output. The numbers
compared for ``correct`` are the last lines of standard error.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(CHECKOUT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import check, harness

    cell = harness.resolve(args.workload)

    import jax

    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"device_count={len(devs)}", file=sys.stderr, flush=True)
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform}", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program of the run, the small ones too, comes from the cache
    # after the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {cache}", file=sys.stderr, flush=True)

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    print("\n".join(check.lines(result["checks"])), file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
