"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the production sharding config is coherent without hardware: for each
cell we lower the full step with ShapeDtypeStruct inputs (no allocation),
compile the SPMD partition, and record memory_analysis / cost_analysis /
per-collective byte counts for EXPERIMENTS.md §Dry-run and §Roofline.

The production meshes need 512 devices; on a CPU host the caller makes
them virtual before JAX starts:

  export XLA_FLAGS=--xla_force_host_platform_device_count=512
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod both]
Results are cached as JSON under artifacts/dryrun/.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from partitioned HLO.

    For each collective op we record (a) the raw output-buffer bytes and
    (b) a wire-byte estimate using ring-algorithm factors with the op's
    replica-group size g:
        all-reduce       2 * (g-1)/g * size
        all-gather       (g-1)/g * size          (size = gathered output)
        reduce-scatter   (g-1) * size            (size = scattered output)
        all-to-all       (g-1)/g * size
        collective-permute  size
    """
    dt_bytes = {
        "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
        "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
    }
    coll_re = re.compile(
        r"(\S+) = (?:\([^)]*\) )?((?:f|bf|s|u|pred)[\w]*)\[([\d,]*)\][^=]*?"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(.*?replica_groups=(\{\{[^}]*\}|\[[\d,]+\]<=\[\d+\])"
    )
    out: dict[str, float] = {}
    wire: dict[str, float] = {}
    seen = set()
    for m in coll_re.finditer(hlo_text):
        name, dtype, dims, kind, groups = m.groups()
        if name in seen:
            continue
        seen.add(name)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        size = n * dt_bytes.get(dtype, 4)
        # replica group size
        if groups.startswith("{{"):
            g = groups[2:].split("}")[0].count(",") + 1
        else:  # iota form [n_groups,g,...]<=[N]: group size = prod/dims[0]
            inner = [int(d) for d in groups[1:].split("]")[0].split(",")]
            prod = 1
            for d in inner:
                prod *= d
            g = prod // max(inner[0], 1)
        g = max(g, 2)
        factor = {
            "all-reduce": 2.0 * (g - 1) / g,
            "all-gather": (g - 1) / g,
            "reduce-scatter": float(g - 1),
            "all-to-all": (g - 1) / g,
            "collective-permute": 1.0,
        }[kind]
        out[kind] = out.get(kind, 0.0) + size
        wire[kind] = wire.get(kind, 0.0) + size * factor
    out["total"] = sum(out.values())
    res = {f"raw_{k}": v for k, v in out.items()}
    res.update({f"wire_{k}": v for k, v in wire.items()})
    res["total"] = res.pop("raw_total")
    res["wire_total"] = sum(wire.values())
    return res


def run_cell(arch_id: str, shape: str, multi_pod: bool, strategy: str,
             out_dir: Path, force: bool = False,
             variant: str | None = None) -> dict:

    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell

    tag = f"{arch_id}__{shape}__{'multi' if multi_pod else 'single'}__{strategy}"
    if variant:
        tag += f"__{variant}"
    out_file = out_dir / f"{tag}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())

    rec = {"arch": arch_id, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16", "strategy": strategy}
    arch = get_arch(arch_id)
    cell = arch.cell(shape)
    if cell.skip_reason:
        rec["status"] = "skipped"
        rec["reason"] = cell.skip_reason
        out_file.write_text(json.dumps(rec, indent=1))
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        plan = build_cell(arch_id, shape, mesh, strategy=strategy,
                          variant=variant)
        lowered = plan.fn.lower(*plan.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        colls = collective_bytes(txt)
        from repro.launch.hlo_analysis import analyze_hlo

        # trip-count-aware analysis: XLA's cost_analysis counts while bodies
        # (lax.scan layers/microbatches) ONCE — see hlo_analysis.py
        deep = analyze_hlo(txt)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "n_devices": mesh.devices.size,
            # per-device, trip-aware (primary numbers)
            "flops_per_device": deep["flops"],
            "bytes_per_device": deep["bytes"],
            "bytes_min_per_device": deep["bytes_min"],
            "collective_bytes_per_device": {
                **{f"raw_{k}": v for k, v in deep["collective_raw"].items()},
                **{f"wire_{k}": v for k, v in deep["collective_wire"].items()},
                "total": sum(deep["collective_raw"].values()),
                "wire_total": deep["collective_wire_total"],
            },
            # XLA module-level numbers (loop bodies counted once), for
            # reference/debugging
            "xla_flops_once": cost.get("flops", 0.0),
            "xla_bytes_once": cost.get("bytes accessed", 0.0),
            "xla_collectives_once": colls,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate": mem.argument_size_in_bytes
                + mem.output_size_in_bytes + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            "meta": {k: v for k, v in plan.meta.items()
                     if isinstance(v, (int, float, str))},
        })
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--strategy", default="pbox")
    ap.add_argument("--variant", default=None,
                    help="optimized variant, e.g. 'sp' (sequence parallel)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multipod]

    from repro.configs.registry import list_cells

    cells = (list_cells() if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch_id, shape in cells:
        for mp in pods:
            rec = run_cell(arch_id, shape, mp, args.strategy, out_dir,
                           force=args.force, variant=args.variant)
            status = rec["status"]
            extra = ""
            if status == "ok":
                gb = rec["memory"]["peak_estimate"] / 2**30
                extra = (f" flops/dev={rec['flops_per_device']:.3g}"
                         f" peak={gb:.2f}GiB"
                         f" coll={rec['collective_bytes_per_device']['total']/2**20:.1f}MiB"
                         f" compile={rec['compile_s']}s")
            elif status == "error":
                failures += 1
                extra = " " + rec["error"][:160]
            print(f"[{status:7s}] {arch_id:22s} {shape:14s} "
                  f"{'multi ' if mp else 'single'}{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
