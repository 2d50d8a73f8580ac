"""The Moonlight cell at a test's size on the CPU: the program's PS step
through ``bench/programs/lm_moe.py`` against the plain reference
``bench/configs/moonlight_16b_a3b_5l.py``, the counts its metrics divide
by, and the reader of named scopes on a synthetic trace.

The tiny sizes keep every published mechanism (MLA with a shared rope key,
a dense first layer, sigmoid routing over all experts with 4 of 16 held,
shared experts, the balance loss) at float32, so the program and the
reference differ only by float32 rounding in another order: every compared
number's limit is 1e-4, against readings near 1e-6 (a program that drops
the balance loss, or routes over the held experts only, reads 1e-3 and up).
"""
import dataclasses
import json
import time
from pathlib import Path

import pytest

from bench import harness, named_scopes, peaks
from bench import trace as tr

CELL = "moonlight_16b_a3b_5l.s8k.b4.1chip"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=32, intermediate_size=256, n_routed_experts=16,
            num_experts_per_tok=4, n_experts_held=4, moe_intermediate_size=128,
            vocab=512, n_layers=3, dtype="float32", param_dtype="float32",
            seq_aux_alpha=0.01, attn_chunk=32)
LIMIT = 1e-4


def _tiny_cell():
    c = harness.resolve(CELL)
    return dataclasses.replace(
        c, sizes={**c.sizes, **TINY},
        mix={**c.mix, "global_batch": 2, "seq_len": 64},
        limits={n: LIMIT for n in c.limits})


def test_the_ps_step_matches_the_reference(monkeypatch):
    """A traced run: ``correct`` against the reference, and the expert
    layer's scopes read. On the CPU the chunked attention path runs, so
    ``attn_kernel`` and its share are absent."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = _tiny_cell()
    assert {m["name"] for m in cell.end_to_end} == {"step_ms", "setup_s"}
    r = harness.run(cell, 2**31 + 11, 0.3, True, time.perf_counter(),
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert {"moe_route_ms", "moe_experts_ms", "expert_gmm_share"} <= set(m)
    assert "mla_kernel_share" not in m
    assert m["moe_route_ms"]["value"] > 0 and m["moe_experts_ms"]["value"] > 0


def _sizes():
    return json.loads((CONFIGS / "moonlight_16b_a3b_5l.json").read_text())


MIX = {"global_batch": 4, "seq_len": 8192, "microbatches": 2}


def test_counts_against_the_hand_count():
    ref = harness.load_module(CONFIGS / "moonlight_16b_a3b_5l.py", "t_moon")
    s = _sizes()
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    dense = attn + 3 * 2048 * 11264
    moe = attn + 2048 * 64 + 3 * 2048 * 1408 * 6 * 8 // 64 + 3 * 2048 * 2816
    n = dense + 4 * moe + 20480 * 2048
    assert ref.matmul_params(s) == n == 275_644_416
    tokens = 4 * 8192
    assert ref.model_flops(s, MIX) == (6 * n + 3 * 8192 * 16 * 320 * 5) * tokens
    assert ref.model_flops(s, MIX) == pytest.approx(74.8e12, rel=1e-3)
    # per sequence and layer: forward twice, backward once
    per = 8192**2 * 16 * (2 * (192 + 128) + 3 * 192 + 2 * 128)
    assert ref.mla_attention_flops(s, MIX) == per * 4 * 5
    rows = 2 * 8192 * 6 * 8 / 64  # per microbatch and MoE layer
    assert rows == 12288
    assert ref.expert_gmm_flops(s, MIX) == rows * 2 * 2048 * 1408 * 3 * 4 * 4 * 2


def test_the_configuration_keeps_the_published_config():
    s = _sizes()
    cat = {"hidden_size": 2048, "num_hidden_layers": 27, "vocab_size": 163840,
           "n_routed_experts": 64, "num_experts_per_tok": 6,
           "moe_intermediate_size": 1408, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64, "routed_scaling_factor": 2.446}
    assert {k: s[k] for k in cat} == cat
    assert s["reduced"] == ["n_layers", "n_experts_held", "vocab"]
    assert (s["n_layers"], s["n_experts_held"], s["vocab"]) == (5, 8, 20480)
    ref = harness.load_module(CONFIGS / "moonlight_16b_a3b_5l.py", "t_moon2")
    import jax

    tree = jax.eval_shape(lambda: ref.init_params(s, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == 568_484_352


def test_named_scope_time_on_a_synthetic_trace():
    """Leaves whose path holds the name, clipped to the window, merged and
    averaged over the chips; a parent event counts through its leaves."""
    ops = {0: [("while.1", 0, 100, ""), ("a", 0, 30, ""), ("b", 20, 50, ""),
               ("c", 60, 90, "")],
           1: [("a", 10, 40, ""), ("c", 95, 130, "")]}
    t = tr.Trace(ops=ops, spans=[])
    paths = {"a": "jvp(fwd)/mlp/moe_route/sort",
             "b": "transpose(jvp(fwd))/mlp/moe_route",
             "c": "jvp(fwd)/mlp/moe_experts/gmm",
             "while.1": "jvp(fwd)/mlp/moe_route"}
    # chip 0: a and b merge to [0, 50); chip 1: a [10, 40)
    assert named_scopes.scope_ns(t, 0, 120, paths, "moe_route") == (50 + 30) / 2
    # c is clipped at 120 on chip 1: 30 + 25
    assert named_scopes.scope_ns(t, 0, 120, paths, "moe_experts") == (30 + 25) / 2
    # a component, not a substring
    assert named_scopes.scope_ns(t, 0, 120, paths, "moe") is None
    ctx = type("Ctx", (), {})()
    ctx.trace, ctx.lo, ctx.hi, ctx.steps, ctx.op_scopes = t, 0, 120, 2, paths
    ctx.peak_flops = 1e12
    assert named_scopes.named_ms(ctx, "moe_route") == pytest.approx(40e-6 / 2)
    assert named_scopes.share_of_peak(ctx, "moe_route", 1e-2) == pytest.approx(
        100 * 1e-2 / (20e-9 * 1e12))
    assert named_scopes.share_of_peak(ctx, "nothing_here", 1.0) is None

