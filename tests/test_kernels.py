"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Sweeps shapes/dtypes (parametrized + hypothesis) per the framework's
kernel contract: every Pallas kernel must match ref.py bit-for-bit for
integer dtypes and to tight tolerances for floats.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


# ------------------------------ exscan ------------------------------


@pytest.mark.parametrize(
    "n,d",
    [(8, 128), (7, 5), (256, 128), (1000, 33), (64, 1), (513, 300), (1, 1)],
)
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_blelloch_exscan_shapes(n, d, dtype):
    rng = np.random.default_rng(n * 1000 + d)
    if np.issubdtype(dtype, np.integer):
        x = rng.integers(-100, 100, (n, d)).astype(dtype)
    else:
        x = (rng.standard_normal((n, d)) * 10).astype(dtype)
    got = np.asarray(ops.exscan(jnp.asarray(x), interpret=True))
    want = np.asarray(ref.exscan_ref(jnp.asarray(x)))
    if np.issubdtype(dtype, np.integer):
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_blelloch_exscan_1d():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 100, 37).astype(np.int32)
    got = np.asarray(ops.exscan(jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, np.concatenate([[0], np.cumsum(x)[:-1]]))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=700),
    d=st.integers(min_value=1, max_value=200),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_blelloch_exscan_property(n, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(-1000, 1000, (n, d)).astype(np.int32)
    got = np.asarray(ops.exscan(jnp.asarray(x), interpret=True))
    want = np.zeros_like(x)
    want[1:] = np.cumsum(x[:-1], axis=0)
    np.testing.assert_array_equal(got, want)


# ------------------------------ ssm scan ------------------------------


@pytest.mark.parametrize("T,D", [(16, 8), (300, 100), (512, 128), (1, 1)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_ssm_scan_shapes(T, D, dtype):
    rng = np.random.default_rng(T * 131 + D)
    a = rng.uniform(0.8, 1.0, (T, D)).astype(dtype)
    b = rng.standard_normal((T, D)).astype(dtype)
    h0 = rng.standard_normal(D).astype(dtype)
    h, hf = ops.ssm_scan(jnp.asarray(a), jnp.asarray(b), jnp.asarray(h0),
                         interpret=True)
    hr, hfr = ref.ssm_scan_ref(jnp.asarray(a), jnp.asarray(b), jnp.asarray(h0))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr), rtol=2e-4, atol=2e-4)


def test_ssm_chunk_summary_is_affine_monoid_element():
    """h_out == A_total * h_in + B_total for random h_in — the property
    the cross-device exscan composition relies on."""
    rng = np.random.default_rng(7)
    T, D = 130, 70
    a = rng.uniform(0.7, 1.0, (T, D)).astype(np.float32)
    b = rng.standard_normal((T, D)).astype(np.float32)
    at, bt = ops.ssm_chunk_summary(jnp.asarray(a), jnp.asarray(b), interpret=True)
    for _ in range(3):
        h_in = rng.standard_normal(D).astype(np.float32)
        _, hf = ref.ssm_scan_ref(jnp.asarray(a), jnp.asarray(b), jnp.asarray(h_in))
        np.testing.assert_allclose(
            np.asarray(at) * h_in + np.asarray(bt),
            np.asarray(hf),
            rtol=3e-4,
            atol=3e-4,
        )


@settings(max_examples=15, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=400),
    D=st.integers(min_value=1, max_value=150),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_ssm_scan_property(T, D, seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.5, 1.0, (T, D)).astype(np.float32)
    b = rng.standard_normal((T, D)).astype(np.float32)
    h, hf = ops.ssm_scan(jnp.asarray(a), jnp.asarray(b), interpret=True)
    hr, hfr = ref.ssm_scan_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=3e-4, atol=3e-4)


# ------------------------------ moe routing ------------------------------


@pytest.mark.parametrize(
    "T,K,E", [(16, 2, 4), (300, 4, 60), (256, 8, 40), (100, 2, 128), (1, 1, 2)]
)
def test_moe_routing_shapes(T, K, E):
    rng = np.random.default_rng(T * 7 + K * 3 + E)
    assign = rng.integers(0, E, (T, K)).astype(np.int32)
    pos, counts = ops.moe_routing(jnp.asarray(assign), E, interpret=True)
    pr, cr = ref.moe_routing_ref(jnp.asarray(assign), E)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cr))


@settings(max_examples=20, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=500),
    K=st.integers(min_value=1, max_value=8),
    E=st.integers(min_value=1, max_value=130),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_moe_routing_property(T, K, E, seed):
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, E, (T, K)).astype(np.int32)
    pos, counts = ops.moe_routing(jnp.asarray(assign), E, interpret=True)
    pos, counts = np.asarray(pos), np.asarray(counts)
    # invariants (stronger than allclose): positions within an expert are
    # a permutation of 0..count-1 in arrival order, counts match histogram
    np.testing.assert_array_equal(counts, np.bincount(assign.reshape(-1), minlength=E))
    flat = assign.reshape(-1)
    flat_pos = pos.reshape(-1)
    for e in range(E):
        mine = flat_pos[flat == e]
        np.testing.assert_array_equal(mine, np.arange(len(mine)))
