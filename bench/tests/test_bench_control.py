"""The control (the reference in the precision below the configuration's,
put in the program's place) comes out as not correct against each cell's
limits, at a test's size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control
from bench.configs import resnet50
from bench.tests import tiny


def test_three_passes_drop_only_the_low_by_low_product():
    ka, kb, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(ka, (16, 32))
    b = jax.random.normal(kb, (32, 8))
    high = resnet50.three_pass(jnp.matmul)
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(a @ b, np.float64)
        # operands that bfloat16 holds exactly lose nothing
        a16 = a.astype(jnp.bfloat16).astype(jnp.float32)
        b16 = b.astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_allclose(high(a16, b16), a16 @ b16, rtol=1e-6)
        got = np.asarray(high(a, b), np.float64)
        g = jax.random.normal(kg, (16, 8))
        da, db = jax.vjp(high, a, b)[1](g)
        ra, rb = jax.vjp(jnp.matmul, a, b)[1](g)
    err = np.abs(got - exact).max() / np.abs(exact).max()
    assert 0 < err < 1e-4  # about 2**-16, far above float32's 2**-24
    for x, y in ((da, ra), (db, rb)):
        rel = np.abs(np.asarray(x) - np.asarray(y)).max() / np.abs(y).max()
        assert 0 < rel < 1e-4


@pytest.mark.parametrize("workload", ["resnet50.b256.4chip",
                                      "internlm2_1_8b_3l.s4k.1chip"])
def test_the_control_fails(workload):
    cell = tiny.cell(workload, chips=1)
    rows = control.readings(cell, [], [2**31 + 21], [], log=lambda m: None)
    (row,) = rows
    assert row["kind"] == "control"
    ok, checks = check.judge(row["gaps"], cell.limits)
    assert not ok, checks


def test_round_to_gives_the_nearest_value_of_the_stated_dtype():
    from bench import reference

    x = jax.random.normal(jax.random.PRNGKey(5), (4096,)) * 0.02
    want = np.asarray(x).astype(jnp.bfloat16).astype(np.float32)
    got = np.asarray(jax.jit(lambda v: reference.round_to(v, jnp.bfloat16))(x))
    np.testing.assert_array_equal(got, want)
    assert reference.round_to(x, jnp.float32) is x
    hi, lo = resnet50._split(x)
    np.testing.assert_array_equal(np.asarray(hi), want)
    assert np.all(np.asarray(lo) == np.asarray(reference.round_to(x - hi, jnp.bfloat16)))
