"""The program's phase scopes and their reduction: scope paths read from
HLO text, phases and blocks named by a path, device time per phase on
hand-built traces, the scopes found in the compiled steps of the test-size
cells and in a traced run, and the input queue's fill span."""
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import harness, peaks
from bench import scopes as sc
from bench import trace as tr
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/apply/mul"}
}

%body.7 (param.2: (u32[], f32[8])) -> (u32[], f32[8]) {
  %param.2 = (u32[], f32[8]{0}) parameter(0)
  %gte.9 = f32[8]{0} get-tuple-element(%param.2), index=1
  %dynamic-slice.8 = f32[8]{0} dynamic-slice(%gte.9, %c.2), dynamic_slice_sizes={8}
  ROOT %tuple.9 = (u32[], f32[8]{0}) tuple(%c.2, %dynamic-slice.8)
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1
  %all-reduce.1 = (f32[8]{0}, f32[]) all-reduce(%fusion.2, %c.1), channel_id=2, to_apply=%add
  %gte.1 = f32[8]{0} get-tuple-element(%all-reduce.1), index=0
  %div.1 = f32[8]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(step)/push/div" stack_frame_id=3}
  %sub.2 = f32[8]{0} multiply(%gte.1, %div.1), metadata={op_name="jit(step)/apply/sub"}
  %dot.3 = f32[8]{0} dot(%p.1, %p.1), metadata={op_name="jit(step)/transpose(jvp(fwd))/attn/dot_general;jit(step)/jvp(fwd)/mlp/x"}
  %reshape.5 = f32[8]{0} bitcast(%sub.2), metadata={op_name="jit(step)/reshape"}
  %tuple.8 = (u32[], f32[8]{0}) tuple(%c.1, %sub.2)
  %while.8 = (u32[], f32[8]{0}) while(%tuple.8), condition=%cond.7, body=%body.7
  %gte.10 = f32[8]{0} get-tuple-element(%while.8), index=1
  %reshape.6 = f32[1,8]{1,0} bitcast(%gte.10), metadata={op_name="jit(step)/pull/reshape"}
  ROOT %copy.4 = f32[8]{0} copy(%reshape.5)
}
"""


def test_scope_paths_from_hlo_text():
    s = sc.scopes_from_hlo(HLO)
    # own metadata; of a ';'-joined path, the first
    assert s["div.1"] == "jit(step)/push/div"
    assert s["dot.3"] == "jit(step)/transpose(jvp(fwd))/attn/dot_general"
    # a fusion without metadata: its fused computation's root
    assert s["fusion.2"] == "jit(step)/apply/mul"
    # a combined collective without metadata: its first consumer that
    # names a phase, through a get-tuple-element without metadata
    assert s["all-reduce.1"] == "jit(step)/push/div"
    # a loop without metadata: its consumer's; the ops of its body, which
    # feed nothing that names a phase: the loop's
    assert s["while.8"] == "jit(step)/pull/reshape"
    assert s["dynamic-slice.8"] == "jit(step)/pull/reshape"
    # own metadata that names no phase, and nothing to take: kept, or absent
    assert s["reshape.5"] == "jit(step)/reshape"
    assert "copy.4" not in s


@pytest.mark.parametrize("path,names", [
    ("jit(s)/while/body/jvp(fwd)/while/body/mlp/dot", {"fwd", "mlp"}),
    ("jit(s)/transpose(jvp(fwd))/lm_head/dot", {"bwd", "lm_head"}),
    ("jit(s)/transpose(jvp(fwd))/while/body/checkpoint/"
     "rematted_computation/attn/exp", {"remat", "attn"}),
    ("jit(s)/transpose(jvp(fwd))/while/body/checkpoint/attn/exp",
     {"bwd", "attn"}),
    ("jit(s)/jvp(fwd)/transpose(jvp(g))/x", set()),
    ("jit(s)/shard_map/push/psum_scatter", {"push"}),
    ("jit(s)/while/body/closed_call/accumulate/add", {"accumulate"}),
    ("jit(s)/apply/jit(fused_aggregate_update)/mul", {"apply"}),
    ("jit(s)/pull/all_gather", {"pull"}),
    ("jit(s)/pushed/attention/x", set()),
    ("", set()),
])
def test_phases_and_blocks_a_path_names(path, names):
    assert sc.scope_names(path) == names


def _phase_trace():
    scopes = {"f.1": "j/jvp(fwd)/attn/a", "f.2": "j/jvp(fwd)/mlp/b",
              "b.3": "j/transpose(jvp(fwd))/attn/c",
              "r.4": "j/transpose(jvp(fwd))/checkpoint/"
                     "rematted_computation/attn/d",
              "w.5": "j/while", "m.6": "j/while/body/reduce_sum"}
    ops = {0: [("w.5", 0, 20, ""), ("f.1", 0, 4, ""), ("f.2", 2, 6, ""),
               ("r.4", 6, 8, ""), ("b.3", 8, 12, ""), ("m.6", 12, 13, ""),
               ("x.7", 14, 16, "")],
           1: [("f.1", 0, 2, ""), ("b.3", 2, 3, "")]}
    return tr.Trace(ops=ops, spans=[]), scopes


def test_device_time_per_phase_and_block():
    t, scopes = _phase_trace()
    st = sc.scope_times(t, 0, 20, scopes)
    # fwd: a union of two overlapping leaves on chip 0 (0-6), 0-2 on chip 1
    assert st["fwd"] == pytest.approx((6 + 2) / 2)
    assert st["bwd"] == pytest.approx((4 + 1) / 2)
    assert st["remat"] == pytest.approx(2 / 2)
    # attn counts across the forward, the recomputation and the backward
    assert st["attn"] == pytest.approx((4 + 2 + 4 + 2 + 1) / 2)
    assert st["mlp"] == pytest.approx(4 / 2)
    # leaves naming no phase: one without one, one absent from the HLO;
    # the while that holds them is no leaf
    assert st["unscoped"] == pytest.approx((1 + 2) / 2)
    assert "apply" not in st and "lm_head" not in st
    # the window cuts the leaves
    assert sc.scope_times(t, 3, 20, scopes)["fwd"] == pytest.approx(3 / 2)


def test_leaves_absent_from_the_hlo_are_unscoped():
    t, _ = _phase_trace()
    st = sc.scope_times(t, 0, 20, {})
    assert set(st) == {"unscoped"}
    assert st["unscoped"] == pytest.approx((6 + 2 + 4 + 1 + 2 + 2 + 1) / 2)


def test_a_window_reads_the_step_among_the_live_executables():
    import jax.numpy as jnp

    def step(w, x):
        def loss(w):
            with jax.named_scope("fwd"):
                return jnp.sum(jnp.tanh(x @ w))

        val, g = jax.value_and_grad(loss)(w)
        with jax.named_scope("apply"):
            return w - 0.1 * g, val

    compiled = jax.jit(step).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile()
    paths = sc.scopes_from_hlo(compiled.as_text())
    # every instruction of the step runs for 1 ns, one after the other
    ops = [(n, i, i + 1, "") for i, n in enumerate(paths)]
    ctx = SimpleNamespace(trace=tr.Trace(ops={0: ops}, spans=[]), lo=0,
                          hi=len(ops), steps=2)
    n_fwd = sum("fwd" in sc.scope_names(p) for p in paths.values())
    n_apply = sum("apply" in sc.scope_names(p) for p in paths.values())
    assert n_fwd and n_apply
    assert sc.scope_ms(ctx, "fwd") == pytest.approx(n_fwd * 1e-6 / 2)
    assert sc.scope_ms(ctx, "apply") == pytest.approx(n_apply * 1e-6 / 2)
    assert sc.scope_ms(ctx, "push") is None
    assert compiled is not None  # held until the window is read


def test_the_prefetcher_records_its_fills(tmp_path):
    from jax.profiler import ProfileData

    from repro.data.pipeline import Prefetcher

    jax.profiler.start_trace(str(tmp_path))
    try:
        p = Prefetcher(iter(range(5)), depth=2, transform=lambda x: x * 2)
        assert [next(p) for _ in range(5)] == [0, 2, 4, 6, 8]
        p.t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    fills = [e for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "input_fill"]
    # one per item, and one for the look that found the iterator exhausted
    assert len(fills) == 6
    assert all(e.duration_ns >= 0 for e in fills)


def test_the_lm_step_names_every_phase_and_block():
    cell = tiny.cell("internlm2_1_8b_3l.s4k.1chip", chips=1)
    plan = cell.program.plan(cell.sizes, cell.mix, harness.make_mesh(1))
    text = plan.fn.lower(*plan.abstract_args).compile().as_text()
    found = set().union(*map(sc.scope_names,
                             sc.scopes_from_hlo(text).values()))
    assert {"fwd", "bwd", "remat", "accumulate", "apply", "attn", "mlp",
            "lm_head"} <= found


def test_the_four_chip_resnet_step_names_push_and_pull():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": f"{ROOT / 'src'}{os.pathsep}{ROOT}"}
    p = subprocess.run([sys.executable, "-m", "bench.tests.four_chip_scopes"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    found = set(p.stdout.split("scopes:", 1)[1].split())
    assert {"fwd", "bwd", "accumulate", "push", "apply", "pull"} <= found


def test_a_traced_run_reports_the_phases(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny.cell("internlm2_1_8b_3l.s4k.1chip")
    r = harness.run(cell, 2**31 + 13, 0.5, True, time.perf_counter(),
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("fwd_ms", "bwd_ms", "remat_ms", "accumulate_ms", "apply_ms",
                 "attn_ms", "mlp_ms", "lm_head_ms"):
        assert m[name]["value"] > 0, name
        assert m[name]["unit"] == "ms"
    busy_ms = r["device"]["busy_s"] * 1e3 / (r["attempted"] - 3)
    assert m["fwd_ms"]["value"] + m["bwd_ms"]["value"] <= busy_ms * 1.01
