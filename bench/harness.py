"""The benchmark's harness: one cell of ``BENCHMARK.json``, run once.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name that ``BENCHMARK.json``
gives it:

- ``bench/configs/<config>.json``: the sizes as run; beside it
  ``bench/configs/<config>.py``, the plain reference and the model-FLOP
  count; ``bench/programs/<program>.py`` builds the program's step from the
  sizes;
- ``bench/traffic/<traffic>.json``: the mix, read by ``bench/traffic.py``;
- ``bench/limits/<cell>.json``: the limits of the numbers compared;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run builds the program's compiled step with its state from the seed,
drives it through its first ``reference.STEPS`` steps (whose results are
checked), measures for ``seconds`` seconds through the same loop (or traces
a short window), and then runs the plain reference for the check.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace


BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# events that JAX records when it traces or compiles, or fetches a compiled
# program from its cache: none may come inside the measured window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
TRACE_SECONDS = 3.0  # longest traced window
TRACE_MIN_STEPS = 4
PREFETCH_DEPTH = 2


def make_mesh(chips: int):
    """The program's (data, model) mesh over the first ``chips`` devices."""
    from repro.launch.mesh import make_mesh as program_mesh

    return program_mesh((chips, 1), ("data", "model"))


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    sizes: dict
    ref: ModuleType
    program: ModuleType
    mix: dict
    limits: dict
    end_to_end: list  # entries of BENCHMARK.json's end_to_end
    per_layer: list  # (entry, reader module) pairs


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(checkout: Path = CHECKOUT) -> dict:
    return json.loads((checkout / "BENCHMARK.json").read_text())


def resolve(workload: str, bench: dict | None = None,
            root: Path = BENCH) -> Cell:
    """The cell ``workload`` with its files; raises if one is missing."""
    from bench import check, traffic

    bench = bench if bench is not None else load_benchmark(root.parent)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"{workload}: no configuration {w['config']!r}")
    cfg_path = root.parent / configs[w["config"]]["file"]
    if not cfg_path.is_file():
        raise FileNotFoundError(cfg_path)
    sizes = json.loads(cfg_path.read_text())
    ref = load_module(cfg_path.with_suffix(".py"), f"bench_ref_{w['config']}")
    program = load_module(root / "programs" / f"{sizes['program']}.py",
                          f"bench_program_{sizes['program']}")
    mix = traffic.load(root / "traffic" / f"{w['traffic']}.json")
    limits = check.load_limits(root / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = []
    for m in bench["per_layer"]:
        if workload in m["workloads"]:
            reader = load_module(root / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            per_layer.append((m, reader))
    return Cell(name=workload, chips=w["chips"], sizes=sizes, ref=ref,
                program=program, mix=mix, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


class CompileCounter:
    """Counts JAX's trace, compile and compile-cache events."""

    def __init__(self):
        import jax

        self.n = 0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on_duration)
        self._mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_):
        if event in COMPILE_EVENTS:
            self.n += 1

    def _on_event(self, event, **_):
        if event in COMPILE_EVENTS:
            self.n += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def _leaf_norms_of_flat(space, flat):
    """Per-leaf norms of a flat parameter-space vector (the program's
    layout: one slot per leaf, at its offset)."""
    import jax.numpy as jnp
    from jax import lax

    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(lax.dynamic_slice_in_dim(
            flat, s.offset, s.size).astype(jnp.float32))))
        for s in space.slots])


class Program:
    """The program's compiled step, its state built from a seed, and the
    loop that drives it."""

    def __init__(self, cell: Cell, log):
        import jax
        import jax.numpy as jnp

        from bench import reference

        self.cell = cell
        sizes = cell.sizes
        self.mesh = make_mesh(cell.chips)
        plan = cell.program.plan(sizes, cell.mix, self.mesh)
        self.space = space = plan.meta["space"]
        self.args = plan.abstract_args
        self.shard = jax.tree.map(
            lambda a: a.sharding, self.args,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        self._check_layout(jax.eval_shape(
            lambda: cell.ref.init_params(sizes, jax.random.PRNGKey(0))))

        t = time.perf_counter()
        with jax.default_matmul_precision(sizes["matmul_precision"]):
            self.step = plan.fn.lower(*self.args).compile()
        self.compile_s = time.perf_counter() - t
        log(f"compiled the step in {self.compile_s:.2f} s")

        pdt = self.args[0].dtype
        self.init_flat = jax.jit(lambda k: space.flatten(
            cell.ref.init_params(sizes, k), pdt)[None],
            out_shardings=self.shard[0])
        self.zeros = jax.jit(lambda: tuple(
            jnp.zeros(a.shape, a.dtype) for a in self.args[1]),
            out_shardings=self.shard[1])
        opt = sizes["optimizer"]

        def p0(k):  # the initial parameters, as the program holds them
            return reference.round_to(
                self.init_flat(k)[0].astype(jnp.float32), pdt)

        self.first_grad_norms = jax.jit(lambda slot, k: _leaf_norms_of_flat(
            space, reference.grad_from_first_state(opt, slot[0], p0(k))))
        self.change_norms = jax.jit(lambda p, k: _leaf_norms_of_flat(
            space, p[0].astype(jnp.float32) - p0(k)))
        self.model_flops = cell.ref.model_flops(sizes, cell.mix)
        self.feed = None

    def start(self, seed: int):
        """Fresh state and batch ring from ``seed``, and the input thread."""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from bench import traffic
        from repro.data.pipeline import Prefetcher

        self.key = traffic.seed_key(seed, 0)
        self.pflat = self.init_flat(self.key)
        self.slots = self.zeros()
        self.ef = None
        self.stc = jax.device_put(np.int32(0), NamedSharding(self.mesh, P()))
        self.ring = traffic.make_ring(self.cell.ref.INPUT, self.cell.sizes,
                                      self.cell.mix, seed)
        bshard = self.shard[4]
        self.feed = Prefetcher(traffic.cycle(self.ring), depth=PREFETCH_DEPTH,
                               transform=lambda b: jax.device_put(b, bshard))

    def _check_layout(self, tree):
        import jax

        leaves = jax.tree.leaves(tree)
        ok = (jax.tree.structure(tree) == self.space.treedef
              and len(leaves) == len(self.space.slots)
              and all(tuple(x.shape) == s.shape
                      for x, s in zip(leaves, self.space.slots)))
        if not ok:
            raise ValueError("the reference's parameter tree does not match "
                             "the program's parameter space")

    def step_once(self) -> tuple:
        """One step as the window takes it; returns (start, end, loss)."""
        import jax
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("input"):
            batch = next(self.feed)
        with TraceAnnotation("dispatch"):
            out = self.step(self.pflat, self.slots, self.ef, self.stc, batch)
        with TraceAnnotation("sync"):
            jax.block_until_ready(out)
        with TraceAnnotation("metrics_get"):
            met = jax.device_get(out[4])
        t2 = time.perf_counter()
        self.pflat, self.slots, self.ef, self.stc = out[:4]
        return t0, t2, float(met["loss"])

    def first_steps(self):
        """The checked steps: ``reference.Readings`` of the program."""
        import numpy as np

        from bench import reference

        losses, grad1 = [], None
        for i in range(reference.STEPS):
            losses.append(self.step_once()[2])
            if i == 0:
                grad1 = np.asarray(self.first_grad_norms(self.slots[0],
                                                         self.key))
        dp = np.asarray(self.change_norms(self.pflat, self.key))
        return reference.Readings(losses=losses, grad1=grad1, dparam=dp)

    def window(self, seconds: float, min_steps: int = 1) -> SimpleNamespace:
        """Steps until ``seconds`` have passed (and ``min_steps`` are done)."""
        starts, ends, losses = [], [], []
        t_start = time.perf_counter()
        while True:
            t0, t2, loss = self.step_once()
            starts.append(t0)
            ends.append(t2)
            losses.append(loss)
            if t2 - t_start >= seconds and len(ends) >= min_steps:
                break
        return SimpleNamespace(start=t_start, end=ends[-1], starts=starts,
                               ends=ends, losses=losses)

    def memory_peak(self) -> int:
        peaks = []
        for d in self.mesh.devices.flat:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def close(self):
        """Stop the input thread and free the state of this seed."""
        self.feed.close()
        self.feed.t.join(timeout=60)
        if self.feed.t.is_alive():
            raise RuntimeError("the input thread did not stop")
        for a in (self.pflat, self.stc, *self.slots):
            a.delete()
        self.pflat = self.slots = self.stc = self.feed = None


def _trace_window(prog: Program, seconds: float, log) -> SimpleNamespace:
    """A short traced window, reduced to what the per-layer readers need."""
    import shutil

    import jax

    from bench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the benchmark's spans, not every call
    opts.enable_hlo_proto = False
    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        jax.profiler.start_trace(str(tmp), profiler_options=opts)
        try:
            w = prog.window(min(seconds, TRACE_SECONDS), TRACE_MIN_STEPS)
        finally:
            jax.profiler.stop_trace()
        files = sorted(tmp.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        t = tr.load(files[-1], jax.devices()[0].platform)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    n = len(w.ends)
    spans = [s for s in t.spans if s[0] in tr.HOST_SPANS]
    inputs = [s for s in spans if s[0] == "input"]
    gets = [s for s in spans if s[0] == "metrics_get"]
    if len(inputs) < n or len(gets) < n:
        raise RuntimeError(f"the trace holds {len(inputs)} input and "
                           f"{len(gets)} metrics_get spans for {n} steps")
    lo, hi = inputs[-n][1], gets[-1][2]
    if not t.ops:
        raise RuntimeError("the trace holds no device operations")
    times = tr.mean_chip_times(t, lo, hi)
    log(f"traced {n} steps over {(hi - lo) * 1e-9:.3f} s")
    return SimpleNamespace(trace=t, lo=lo, hi=hi, steps=n,
                           window_s=(hi - lo) * 1e-9, times=times,
                           input_s=sum(e - s for _, s, e in inputs[-n:]) * 1e-9,
                           host=w)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        log=None) -> dict:
    """Run ``cell`` once; returns the result line's object."""
    import jax

    from bench import check, peaks, reference

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    devs = jax.devices()
    kind = devs[0].device_kind
    counter = CompileCounter()
    try:
        prog = Program(cell, log)
        prog.start(seed)
        readings = prog.first_steps()
        log(f"first steps: losses {readings.losses}")
        setup_s = time.perf_counter() - t0
        before = counter.n
        if trace:
            tw = _trace_window(prog, seconds, log)
            w = tw.host
        else:
            w = prog.window(seconds)
        in_window = counter.n - before
    finally:
        counter.close()
    memory_peak = prog.memory_peak()
    prog.close()
    log(f"window: {len(w.ends)} steps in {w.end - w.start:.3f} s, "
        f"{in_window} compilations inside it")

    ref = reference.run(cell.ref, cell.sizes, prog.ring[:reference.STEPS],
                        prog.key, mode="f32")
    values = check.gaps(readings, ref)
    ok, checks = check.judge(values, cell.limits)
    bad = [x for x in w.losses if not math.isfinite(x)]
    ok = ok and not bad and in_window == 0
    checks["compilations_in_window"] = {"value": in_window, "limit": 0}
    checks["nonfinite_losses"] = {"value": len(bad), "limit": 0}

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    result = {"correct": ok, "attempted": reference.STEPS + len(w.ends),
              "failed": len(bad)}
    if trace:
        peak = peaks.lookup(kind)
        ctx = SimpleNamespace(
            compile_s=prog.compile_s, chips=cell.chips,
            model_flops=prog.model_flops, peak_flops=peak["bf16_flops"],
            **vars(tw))
        metrics = {}
        for entry, reader in cell.per_layer:
            v = reader.read(ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        device["busy_s"] = tw.times.busy * 1e-9
        device["window_s"] = tw.window_s
        result["metrics"] = metrics
        result["device"] = device
        from bench import trace as tr
        result["breakdown"] = {
            "device_ops": tr.top_ops(tw.trace, tw.lo, tw.hi),
            "idle_gaps": tr.idle_gaps(tw.trace, tw.lo, tw.hi)}
    else:
        steps = [e - s for s, e in zip(w.starts, w.ends)]
        values_e2e = {
            "step_ms": (w.end - w.start) / len(steps) * 1e3,
            "step_p90_ms": (statistics.quantiles(steps, n=10)[-1] * 1e3
                            if len(steps) >= 2 else steps[0] * 1e3),
            "setup_s": setup_s,
        }
        result["metrics"] = {m["name"]: {"value": values_e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": _finite(c["value"]),
                            "limit": c["limit"]} for k, c in checks.items()}
    return result
