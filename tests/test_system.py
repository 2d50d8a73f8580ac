"""End-to-end behaviour tests for the PBoxAX system."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch, list_cells
from repro.core.chunking import ParamSpace
from repro.core.compression import CompressionConfig, decode, encode, init_ef_state
from repro.core.server import PHubServer, WorkerHarness
from repro.data.synthetic import lm_batches
from repro.models.common import Dist
from repro.models.transformer import init_params, lm_loss
from repro.optim.optimizers import adamw, momentum


def test_cell_matrix_is_complete():
    cells = list_cells()
    # 5 LM x 4 + 1 GNN x 4 + 4 recsys x 4 + Moonlight's one training cell
    assert len(cells) == 41
    skips = [
        (a, s) for a, s in cells
        if get_arch(a).cell(s).skip_reason is not None
    ]
    # long_500k skipped exactly for the 4 pure full-attention LMs
    assert sorted(skips) == sorted([
        ("internlm2-1.8b", "long_500k"), ("qwen2-72b", "long_500k"),
        ("granite-moe-1b-a400m", "long_500k"), ("qwen2-moe-a2.7b", "long_500k"),
    ])


def test_single_device_training_learns():
    """Tiny LM through the PHub server: loss decreases over 30 steps."""
    cfg = get_arch("gemma3-1b").smoke_config
    dist = Dist.none()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=1)
    space = ParamSpace.build(params, num_owners=1)
    srv = PHubServer(space, adamw(3e-3), space.flatten(params), num_workers=2)
    data = [lm_batches(cfg.vocab, 4, 16, seed=w) for w in range(2)]
    batches = [[next(d) for _ in range(30)] for d in data]

    lossg = jax.jit(jax.value_and_grad(
        lambda p, t, l: lm_loss(p, t, l, cfg, dist, 1)[0]))

    losses = []

    def grad_fn(p, wb):
        w, step = wb
        b = batches[w][step]
        loss, g = lossg(p, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        losses.append(float(loss))
        return g

    h = WorkerHarness(srv, grad_fn, lambda w, s: (w, s))
    h.run(30)
    first = np.mean(losses[:4])
    last = np.mean(losses[-4:])
    assert last < first - 0.5, f"no learning: {first:.3f} -> {last:.3f}"


def test_compression_error_feedback_unbiased():
    """With EF, the long-run sum of decoded grads tracks the true sum."""
    cfg = CompressionConfig(codec="int8", chunk_elems=1024,
                            error_feedback=True)
    rng = np.random.default_rng(0)
    n = 4096
    ef = init_ef_state(cfg, n)
    true_sum = np.zeros(n)
    dec_sum = np.zeros(n)
    for i in range(50):
        g = jnp.asarray(rng.normal(size=n).astype(np.float32) * 0.1)
        payload, ef = encode(cfg, g, ef)
        d = decode(cfg, payload)
        true_sum += np.asarray(g)
        dec_sum += np.asarray(d)
    # residual bounded by the EF state, not growing with steps
    resid = np.abs(true_sum - dec_sum).max()
    assert resid < 0.02, resid


def test_compression_wire_bytes():
    assert CompressionConfig(codec="none").wire_bytes_per_elem == 4.0
    assert CompressionConfig(codec="bf16").wire_bytes_per_elem == 2.0
    assert CompressionConfig(codec="int8", chunk_elems=8192).wire_bytes_per_elem < 1.01


def test_modeled_bytes_hierarchy_reduces_cross_pod():
    from repro.core.exchange import ExchangeConfig, PSExchange

    spec = momentum(0.1)
    flat = 1 << 20
    flat_b = flat * 4
    pb = PSExchange(spec, ExchangeConfig("pbox"), ("pod", "data"))
    hi = PSExchange(spec, ExchangeConfig("pbox_hier"), ("pod", "data"), "pod")
    m_pb = pb.modeled_bytes(flat, 2, 16)
    m_hi = hi.modeled_bytes(flat, 2, 16)
    # hierarchical cross-pod bytes ~ G/n_data vs pbox's ~G-scale push
    assert m_hi["xpod"] < m_pb["push"] / 4
    # int8 compression shrinks the cross-pod stage further
    hi8 = PSExchange(
        spec,
        ExchangeConfig("pbox_hier",
                       compression=CompressionConfig(codec="int8")),
        ("pod", "data"), "pod")
    assert hi8.modeled_bytes(flat, 2, 16)["xpod"] < m_hi["xpod"] / 3
