"""With the timed path broken underneath, a run's ``correct`` is false:
for a step that returns its state unchanged, for half of the batch left out
(the mean over the rest), and for the exchange between chips left out."""
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["resnet50.b256.4chip", "internlm2_1_8b_3l.s4k.1chip"]


def _run(cell):
    return harness.run(cell, 2**31 + 11, 0.2, False, time.perf_counter(),
                       log=lambda m: None)


@pytest.mark.parametrize("workload", CELLS)
def test_state_returned_unchanged(monkeypatch, workload):
    from repro.core.exchange import PSExchange

    def unchanged(self, gflat, pflat, state, lr_scale=1.0):
        return pflat, {**state, "step": state["step"] + 1}

    monkeypatch.setattr(PSExchange, "device_update", unchanged)
    r = _run(tiny.cell(workload, chips=1))
    assert not r["correct"]
    assert r["checks"]["dparam3"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", CELLS)
def test_half_of_the_batch_left_out(monkeypatch, workload):
    from repro.models import resnet, transformer

    if workload.startswith("resnet"):
        full = resnet.loss_fn

        def half(params, batch, *a, **k):
            n = batch["labels"].shape[0] // 2
            return full(params, {k_: v[:n] for k_, v in batch.items()}, *a, **k)

        monkeypatch.setattr(resnet, "loss_fn", half)
    else:  # one sequence per microbatch: half of its tokens
        full = transformer.lm_loss

        def half(params, tokens, labels, *a, **k):
            n = tokens.shape[1] // 2
            return full(params, tokens[:, :n], labels[:, :n], *a, **k)

        monkeypatch.setattr(transformer, "lm_loss", half)
    r = _run(tiny.cell(workload, chips=1))
    assert not r["correct"]


def test_exchange_between_chips_left_out():
    """On four virtual CPU devices: a sound run is correct, and one whose
    push aggregates nothing (each owner applies its own gradient) is not."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": f"{ROOT / 'src'}{os.pathsep}{ROOT}"}
    p = subprocess.run([sys.executable, "-m", "bench.tests.four_chips"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "sound True" in p.stdout and "no_exchange False" in p.stdout
