#!/usr/bin/env python3
"""Bring-up check on the chip: ResNet-50 at full width through the normal
training path, then every Pallas kernel compiled for the chip.

  python chip_smoke.py              # one chip: phases 1-4
  python chip_smoke.py --chips 4    # four chips: phases 1 and 5 only

1. Devices: print platform, kind and count; exit 1 unless JAX finds a TPU.
2. ResNet-50 (25.56M parameters, 224x224, 1000 classes, global batch 256
   as 2 x 128 microbatches) for a few steps through ``launch/train.py`` ->
   ``build_cell`` -> ``make_ps_train_step`` -> ``PSExchange`` on a 1x1 mesh:
   compile time, per-step time after ``block_until_ready``, and a finite
   loss at every step.
3. The same steps with the exchange's apply run by the compiled
   ``fused_agg_opt`` kernel (``use_pallas=True``); its parameters must match
   phase 2's within ``PARAM_TOL``.
4. Every kernel with ``interpret=False`` at the sizes of
   ``tests/test_tpu_compile.py``, checked against its ``ref.py`` (causal
   attention: its output and the gradients of q, k and v).
5. (``--chips 4``) phases 2 and 3 on a 4x1 mesh with strategy ``pbox``,
   checked after step 1 against strategy ``allreduce`` on the same seed and
   batches, with the collectives of each compiled step; at matmul precision
   "highest", so that the runs differ only by f32 rounding.

Any failure raises and exits non-zero.  Only a run in which every phase
passed prints its last line, one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Two programs that take the same step from the same parameters and batch
# differ only by rounding (another apply kernel, another reduction order,
# another fusion of the forward): after one step that is orders of magnitude
# under 1e-4 in f32, while a wrong update moves some of the 25.56M
# parameters by lr * |grad|, far above it.  Later steps amplify rounding:
# the published lr 0.1 from random init first raises the loss (7.8 to 9.5),
# and there two programs that round differently drift apart about 100x per
# step.  So programs that round differently (pbox against allreduce) are
# compared after step 1; the apply kernel against the jnp apply, which agree
# bit for bit, after the last step.
PARAM_TOL = 1e-4
STEPS = 3

# kernel sizes: the same as tests/test_tpu_compile.py
FLAT = 25_559_040  # ResNet-50's flat chunk space
STREAMS = 4
CHUNK = 8192
CODEC_ELEMS = CHUNK * 64
TABLE_ROWS, EMB_DIM, BAGS, BAG_LEN = 100_000, 128, 256, 8
SEQ, HEADS, HEAD_DIM = 4096, 16, 128  # the LM cell's attention, one sequence
ATTN_TOL = 1e-2  # relative (Frobenius) error of bf16 outputs and gradients


def device_info() -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"phase 1: devices {info}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {info['platform']}")
    return info


def train(mesh: str, strategy: str, *, use_pallas: bool = False) -> dict:
    """ResNet-50 steps through ``launch/train.py``; returns its result."""
    from repro.core.exchange import ExchangeConfig
    from repro.launch import train as train_mod

    argv = ["--arch", "resnet50", "--full", "--mesh", mesh,
            "--strategy", strategy, "--steps", str(STEPS), "--log-every", "1"]
    first = []

    def keep_first(step, pflat):
        if step == 1:  # a copy: the next step donates pflat
            first.append(np.asarray(pflat))

    res = train_mod.main(argv, exchange_cfg=ExchangeConfig(
        strategy=strategy, use_pallas=use_pallas), on_step=keep_first)
    res["pflat_step1"] = first[0]
    apply = "fused_agg_opt kernel" if use_pallas else "jnp"
    print(f"  resnet50 mesh={mesh} strategy={strategy} apply={apply}: "
          f"flat params={res['meta']['space'].flat_elems} "
          f"global batch={res['meta']['examples']} "
          f"microbatches={res['meta']['microbatches']} "
          f"compile_s={res['compile_s']!r} step_s={res['step_s']!r} "
          f"losses={res['losses']!r}", flush=True)
    return res


def param_diff(a: dict, b: dict, key: str = "pflat") -> float:
    """Max |a - b| over the parameters ``a[key]`` and ``b[key]``.

    Compared tensor by tensor: the flat spaces of two strategies may pad
    their chunks differently (one owner against four)."""
    import jax
    import jax.numpy as jnp

    def tensors(res):
        return jax.tree.leaves(res["meta"]["space"].unflatten(
            jnp.asarray(res[key][0])))

    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(tensors(a), tensors(b), strict=True))


def compare_params(a: dict, b: dict, what: str,
                   key: str = "pflat") -> float:
    """``param_diff``, printed; raises beyond PARAM_TOL."""
    diff = param_diff(a, b, key)
    print(f"  {what}: max |dparam| = {diff!r} (tolerance {PARAM_TOL})",
          flush=True)
    if not diff <= PARAM_TOL:
        raise AssertionError(f"{what}: parameters differ by {diff}")
    return diff


def collectives(res: dict) -> dict:
    """Bytes per collective kind in the step as the chip's compiler
    emitted it."""
    from repro.launch.hlo_analysis import analyze_hlo

    return analyze_hlo(res["step"].as_text())["collective_raw"]


def _check(name: str, got, want, *, rtol: float, atol: float) -> None:
    import jax

    for i, (g, w) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        g, w = np.asarray(g), np.asarray(w)
        diff = float(np.max(np.abs(g.astype(np.float64) - w.astype(np.float64))))
        print(f"  {name}[{i}] shape={g.shape} max |diff|={diff!r} "
              f"bit-exact={bool(np.array_equal(g, w))}", flush=True)
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol, err_msg=name)


def kernels() -> None:
    """Every kernel compiled for the chip (``interpret=False``) against its
    ``ref.py`` on the same device."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.embedding_bag.ops import embedding_bag
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    from repro.kernels.fused_agg_opt.ops import fused_aggregate_update
    from repro.kernels.fused_agg_opt.ref import fused_aggregate_update_ref
    from repro.kernels.quant.ops import dequantize_chunks, quantize_chunks
    from repro.kernels.quant.ref import dequantize_chunks_ref, quantize_chunks_ref
    from repro.kernels.wire_path.ops import fused_wire_update
    from repro.kernels.wire_path.ref import fused_wire_update_ref
    from repro.optim.optimizers import adamw, momentum

    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    step = jnp.int32(3)
    for spec in (momentum(0.1, 0.9), adamw(1e-3, weight_decay=0.1)):
        g = jax.random.normal(next(ks), (STREAMS, FLAT))
        p = jax.random.normal(next(ks), (FLAT,))
        st = tuple(jnp.abs(jax.random.normal(next(ks), (FLAT,)))
                   for _ in range(spec.num_state_slots))
        _check(f"fused_agg_opt/{spec.name}",
               fused_aggregate_update(g, p, st, spec, step, interpret=False),
               fused_aggregate_update_ref(g, p, st, spec, step),
               rtol=1e-6, atol=1e-6)

    x = 3.0 * jax.random.normal(next(ks), (CODEC_ELEMS,))
    q, s = quantize_chunks(x, CHUNK, interpret=False)
    q_ref, s_ref = quantize_chunks_ref(x, CHUNK)
    _check("quant/scales", s, s_ref, rtol=1e-6, atol=0.0)
    # x / scale may round the other way where it lands within an ulp of a
    # .5 boundary, so a code may differ by one step, and only rarely
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    print(f"  quant/codes max |dq|={int(dq.max())} "
          f"differing={int((dq > 0).sum())} of {dq.size}", flush=True)
    if dq.max() > 1 or (dq > 0).sum() > dq.size * 1e-4:
        raise AssertionError("quant: int8 codes differ from ref.py")
    _check("dequant", dequantize_chunks(q_ref, s_ref, CHUNK, interpret=False),
           dequantize_chunks_ref(q_ref, s_ref, CHUNK), rtol=1e-6, atol=0.0)

    spec = momentum(0.1, 0.9)
    p = jax.random.normal(next(ks), (CODEC_ELEMS,))
    m = jax.random.normal(next(ks), (CODEC_ELEMS,))
    grads = jax.random.normal(next(ks), (STREAMS, CODEC_ELEMS))
    enc = [quantize_chunks_ref(grads[i], CHUNK) for i in range(STREAMS)]
    wires = {
        "bf16": (grads.astype(jnp.bfloat16), None),
        "int8": (jnp.stack([e[0] for e in enc]), jnp.stack([e[1] for e in enc])),
    }
    for codec, (pay, sc) in wires.items():
        _check(f"wire_path/{codec}",
               fused_wire_update(pay, sc, p, (m,), spec, step, codec=codec,
                                 chunk_elems=CHUNK, interpret=False),
               fused_wire_update_ref(pay, sc, p, (m,), spec, step, codec=codec,
                                     chunk_elems=CHUNK),
               rtol=1e-6, atol=1e-6)

    table = jax.random.normal(next(ks), (TABLE_ROWS, EMB_DIM))
    idx = jax.random.randint(next(ks), (BAGS, BAG_LEN), 0, TABLE_ROWS)
    w = jax.random.normal(next(ks), (BAGS, BAG_LEN))
    with jax.default_matmul_precision("highest"):  # the ref's einsum in f32
        want = embedding_bag_ref(table, idx, w, "sum")
    _check("embedding_bag",
           embedding_bag(table, idx, w, "sum", use_pallas=True, interpret=False),
           want, rtol=1e-5, atol=1e-5)

    attention()


def attention() -> None:
    """The causal attention kernel compiled for the chip against its
    ``ref.py`` at the LM cell's widths: the output and the gradients of q,
    k and v."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention.ops import causal_attention
    from repro.kernels.attention.ref import causal_attention_ref

    ks = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    qkv = [jax.random.normal(next(ks), (1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
           for _ in range(3)]
    do = jax.random.normal(next(ks), (1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16)

    def out_and_grads(fn):
        out, pull = jax.vjp(fn, *qkv)
        return (out, *pull(do))

    got = out_and_grads(lambda q, k, v: causal_attention(q, k, v, interpret=False))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got,
                          out_and_grads(causal_attention_ref)):
        g, w = (np.asarray(x, np.float64) for x in (g, w))
        err = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        print(f"  attention/{name} shape={g.shape} relative error={err!r}",
              flush=True)
        if not err < ATTN_TOL:
            raise AssertionError(f"attention/{name}: {err} against ref.py")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the four-chip exchange phase only")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    info = device_info()
    if args.chips == 4:
        if info["count"] < 4:
            raise SystemExit(f"--chips 4 needs 4 devices, JAX found {info['count']}")
        print("phase 5: ResNet-50 on a 4x1 mesh, pbox against allreduce, "
              "matmul precision highest", flush=True)
        # At the default precision a TPU runs f32 convolutions as one bf16
        # pass, so two differently compiled steps round differently from
        # the first forward on, and 3 steps at lr 0.1 grow that to 1e-2;
        # at "highest" they differ by f32 rounding, and what is compared
        # is the exchange.
        with jax.default_matmul_precision("highest"):
            pbox = train("4x1", "pbox")
            pbox_k = train("4x1", "pbox", use_pallas=True)
            allreduce = train("4x1", "allreduce")
        for name, res in (("pbox", pbox), ("allreduce", allreduce)):
            print(f"  collective bytes in the compiled {name} step: "
                  f"{collectives(res)}", flush=True)
        compare_params(pbox, pbox_k, "pbox: kernel apply vs jnp apply")
        compare_params(pbox, allreduce, "pbox vs allreduce after step 1",
                       key="pflat_step1")
        print(f"  pbox vs allreduce after step {STEPS} (not checked, see "
              f"PARAM_TOL): max |dparam| = {param_diff(pbox, allreduce)!r}",
              flush=True)
    else:
        print("phase 2: ResNet-50 through launch/train.py, jnp apply",
              flush=True)
        ref = train("1x1", "pbox")
        print("phase 3: the same steps, fused_agg_opt kernel apply",
              flush=True)
        compare_params(ref, train("1x1", "pbox", use_pallas=True),
                       "kernel apply vs jnp apply")
        print("phase 4: kernels compiled for the chip against ref.py",
              flush=True)
        kernels()
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
