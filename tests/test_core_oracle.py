"""Tests of the paper's algorithms against the message-schedule oracle.

These verify the *claims of the paper* (Theorem 1 and the costs of the
two baselines) on a faithful rank-by-rank simulation, for every p up to
260 and a sample of larger p, under the free monoid (the most
discriminating associative operator — catches reordering, duplication
and omission, and does not assume commutativity).
"""

import math

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import oracle

ALL_P = list(range(1, 261)) + [511, 512, 513, 1023, 1024, 1025, 4096, 4097]


@pytest.mark.parametrize("p", ALL_P)
def test_123_correct_and_theorem1(p):
    stats = oracle.verify(p, "123")
    # Theorem 1: q = ceil(log2(p-1) + log2(4/3)) rounds ...
    assert stats.rounds == oracle.q_123(p)
    # ... and q-1 applications of ⊕ on the result path (last rank).
    assert stats.result_path_ops == max(0, stats.rounds - 1)
    # No rank applies ⊕ more than q times (mid ranks add one send-side
    # prep in round 1 — see EXPERIMENTS.md §Fidelity).
    assert stats.max_ops <= stats.rounds


@pytest.mark.parametrize("p", ALL_P)
def test_1doubling_correct_and_costs(p):
    stats = oracle.verify(p, "1doubling")
    assert stats.rounds == oracle.rounds_1doubling(p)
    if p > 2:
        expected_ops = math.ceil(math.log2(p - 1))
        assert stats.result_path_ops == expected_ops
        assert stats.max_ops == expected_ops
        # pays exactly one more round than 123-doubling for most p
        assert stats.rounds >= oracle.q_123(p)


@pytest.mark.parametrize("p", ALL_P)
def test_two_op_correct_and_costs(p):
    stats = oracle.verify(p, "two_op")
    assert stats.rounds == oracle.rounds_two_op(p)
    if p > 2:
        # max over ranks of total ⊕ is 2*ceil(log2 p) - 2 (send-prep +
        # combine per round after round 0); the paper quotes
        # 2*ceil(log2 p) - 1 as the upper bound.
        assert stats.max_ops <= 2 * math.ceil(math.log2(p)) - 1


@pytest.mark.parametrize("p", ALL_P)
def test_123_round_advantage(p):
    """The new algorithm never loses to 1-doubling, and saves a round
    whenever frac(log2(p-1)) > log2(3/2) — e.g. p=36: 6 vs 7 rounds."""
    if p <= 2:
        return
    q = oracle.q_123(p)
    assert q <= oracle.rounds_1doubling(p)
    assert q >= oracle.rounds_two_op(p)  # never beats log2 p lower bound - 1
    assert q >= math.ceil(math.log2(p - 1))  # the paper's lower bound


def test_paper_table_counts_p36():
    """The paper's own cluster: p=36 nodes."""
    assert oracle.q_123(36) == 6
    assert oracle.rounds_1doubling(36) == 7
    assert oracle.rounds_two_op(36) == 6
    st_123 = oracle.verify(36, "123")
    st_two = oracle.verify(36, "two_op")
    assert st_123.result_path_ops == 5  # q-1
    assert st_two.max_ops == 8  # ~2 log p: more ⊕ for the same rounds


def test_message_counts_monotone():
    """123-doubling sends no more messages than 1-doubling."""
    for p in range(2, 200):
        m123 = oracle.verify(p, "123").messages
        m1 = oracle.verify(p, "1doubling").messages
        assert m123 <= m1 + p  # at most the extra round-1 sends


# --------------------------- property-based ---------------------------


@settings(max_examples=60, deadline=None)
@given(
    p=st.integers(min_value=1, max_value=300),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    algorithm=st.sampled_from(["123", "1doubling", "two_op"]),
)
def test_property_random_matrix_monoid(p, seed, algorithm):
    """Non-commutative 2x2 integer-matrix monoid with random inputs:
    result must equal the sequential left fold exactly."""
    rng = np.random.default_rng(seed)
    inputs = [rng.integers(-3, 4, size=(2, 2)).astype(object) for _ in range(p)]
    op = lambda lo, hi: hi @ lo  # lo applied first
    identity = np.eye(2, dtype=object)
    got, _ = oracle.SIMULATORS[algorithm](inputs, op, identity)
    acc = identity
    for r in range(p):
        assert np.array_equal(got[r], acc), (algorithm, p, r)
        acc = inputs[r] @ acc


@settings(max_examples=60, deadline=None)
@given(p=st.integers(min_value=2, max_value=100_000))
def test_property_round_count_formula(p):
    """Coverage argument: the window width reached by the 123 skip
    schedule covers p-1 inputs after its last round and not before (the
    schedule is tight), and its length equals Theorem 1's q."""
    skips = oracle.skips_123(p)
    # window width after round k: 1, 3, then doubling (3·2^(k-1))
    widths = []
    for i in range(len(skips)):
        widths.append(1 if i == 0 else (3 if i == 1 else 2 * skips[i]))
    assert widths[-1] >= p - 1  # rank p-1 complete after the last round
    if len(widths) >= 2:
        assert widths[-2] < p - 1  # ... and not a round earlier
    assert len(skips) == oracle.q_123(p)
