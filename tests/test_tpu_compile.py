"""Every Pallas kernel compiles for a TPU v5e at real widths, without a chip.

The TPU compiler is installed on CPU hosts too and compiles for a described
``v5e:2x2`` topology.  It refuses what interpret mode accepts: blocks not
aligned to the (8, 128) tile, too much VMEM.  Each case compiles the kernel
with ``interpret=False`` through its public wrapper and checks that the
compiled program holds the kernel (``tpu_custom_call``).  The sizes are
the ones ``chip_smoke.py`` runs on the chip; attention's are the LM
benchmark cell's.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the fixture runs only in the worker
that is given this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention.ops import causal_attention
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.fused_agg_opt.ops import fused_aggregate_update
from repro.kernels.quant.ops import dequantize_chunks, quantize_chunks
from repro.kernels.wire_path.ops import fused_wire_update
from repro.models.moe import gmm
from repro.optim.optimizers import adamw, momentum

FLAT = 25_559_040  # ResNet-50's flat chunk space
STREAMS = 4
CHUNK = 8192
CODEC_ELEMS = CHUNK * 64
TABLE_ROWS, EMB_DIM, BAGS, BAG_LEN = 100_000, 128, 256, 8
SEQ, HEADS, HEAD_DIM = 4096, 16, 128  # the LM cell's attention, one sequence
# the Moonlight cell: latent attention at 8k (q/k heads of 192, v of 128), and
# the held experts' grouped matmul over a microbatch's 98,304 assignments
MLA_SEQ, MLA_QK, MLA_V = 8192, 192, 128
ROWS, D_MODEL, D_EXPERT, HELD = 98_304, 2048, 1408, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to the persistent cache cannot be read back on
    # a CPU host, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_kernel_compiles(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec", [momentum(0.1, 0.9), adamw(1e-3, weight_decay=0.1)],
                         ids=["momentum", "adamw"])
def test_fused_agg_opt_compiles_for_v5e(sds, spec):
    slots = tuple(sds((FLAT,), jnp.float32) for _ in range(spec.num_state_slots))
    _assert_kernel_compiles(
        lambda g, p, s, t: fused_aggregate_update(g, p, s, spec, t,
                                                  interpret=False),
        sds((STREAMS, FLAT), jnp.float32), sds((FLAT,), jnp.float32), slots,
        sds((), jnp.int32))


@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_wire_path_compiles_for_v5e(sds, codec):
    spec = momentum(0.1, 0.9)
    wire = {"bf16": jnp.bfloat16, "int8": jnp.int8}[codec]
    scales = (sds((STREAMS, CODEC_ELEMS // CHUNK), jnp.float32)
              if codec == "int8" else None)
    _assert_kernel_compiles(
        lambda pay, sc, p, m, t: fused_wire_update(
            pay, sc, p, (m,), spec, t, codec=codec, chunk_elems=CHUNK,
            interpret=False),
        sds((STREAMS, CODEC_ELEMS), wire), scales,
        sds((CODEC_ELEMS,), jnp.float32), sds((CODEC_ELEMS,), jnp.float32),
        sds((), jnp.int32))


def test_quantize_compiles_for_v5e(sds):
    _assert_kernel_compiles(
        lambda x: quantize_chunks(x, CHUNK, interpret=False),
        sds((CODEC_ELEMS,), jnp.float32))


def test_dequantize_compiles_for_v5e(sds):
    _assert_kernel_compiles(
        lambda q, s: dequantize_chunks(q, s, CHUNK, interpret=False),
        sds((CODEC_ELEMS,), jnp.int8), sds((CODEC_ELEMS // CHUNK,), jnp.float32))


def test_embedding_bag_compiles_for_v5e(sds):
    _assert_kernel_compiles(
        lambda t, i, w: embedding_bag(t, i, w, "sum", use_pallas=True,
                                      interpret=False),
        sds((TABLE_ROWS, EMB_DIM), jnp.float32),
        sds((BAGS, BAG_LEN), jnp.int32), sds((BAGS, BAG_LEN), jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_causal_attention_compiles_for_v5e(sds, grad):
    def fwd(q, k, v):
        return causal_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    qkv = [sds((1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16) for _ in range(3)]
    _assert_kernel_compiles(
        jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd, *qkv)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_latent_attention_compiles_for_v5e(sds, grad):
    def fwd(q, k, v):
        return causal_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    q = sds((1, MLA_SEQ, HEADS, MLA_QK), jnp.bfloat16)
    v = sds((1, MLA_SEQ, HEADS, MLA_V), jnp.bfloat16)
    _assert_kernel_compiles(
        jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd, q, q, v)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_grouped_matmul_compiles_for_v5e(sds, grad):
    def fwd(x, w, sizes):
        return gmm(x, w, sizes, interpret=False)

    def loss(x, w, sizes):
        return jnp.sum(fwd(x, w, sizes).astype(jnp.float32))

    _assert_kernel_compiles(
        jax.grad(loss, argnums=(0, 1)) if grad else fwd,
        sds((ROWS, D_MODEL), jnp.bfloat16),
        sds((HELD, D_MODEL, D_EXPERT), jnp.bfloat16),
        sds((HELD + 1,), jnp.int32))
