"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp ref
oracle, swept over shapes and content distributions (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed in this environment")
from hypothesis import given, settings, strategies as st

from repro.core.tape import AOP
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.data.doc_table import key_lanes


# ---------------------------------------------------------------------------
# hash_match
# ---------------------------------------------------------------------------


def _random_lanes(rng, n, pool):
    """Lanes drawn from a pool of real key hashes (forces collisions)."""
    idx = rng.integers(0, len(pool), n)
    return np.stack([pool[i] for i in idx]), idx


_KEYS = ["a", "b", "name", "kind", "value", "x" * 40, "y" * 40, "nested", "tags", ""]
_POOL = [key_lanes(k) for k in _KEYS]


class TestHashMatch:
    @pytest.mark.parametrize("n,m", [(1, 1), (7, 5), (128, 64), (300, 130), (513, 257)])
    def test_shapes_match_ref(self, n, m):
        rng = np.random.default_rng(n * 1000 + m)
        q_lanes, _ = _random_lanes(rng, n, _POOL)
        t_lanes, _ = _random_lanes(rng, m, _POOL)
        q_owner = rng.integers(0, 4, n).astype(np.int32)
        t_owner = rng.integers(0, 4, m).astype(np.int32)
        got = kops.hash_match(
            jnp.asarray(q_lanes), jnp.asarray(q_owner),
            jnp.asarray(t_lanes), jnp.asarray(t_owner),
            block_n=128, block_m=128,
        )
        want = kref.hash_match_ref(
            jnp.asarray(q_lanes), jnp.asarray(q_owner),
            jnp.asarray(t_lanes), jnp.asarray(t_owner),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=30, deadline=None)
    @given(
        n=st.integers(1, 40),
        m=st.integers(1, 40),
        seed=st.integers(0, 2**16),
    )
    def test_property_sweep(self, n, m, seed):
        rng = np.random.default_rng(seed)
        q_lanes, _ = _random_lanes(rng, n, _POOL)
        t_lanes, _ = _random_lanes(rng, m, _POOL)
        q_owner = rng.integers(-1, 3, n).astype(np.int32)
        t_owner = rng.integers(0, 3, m).astype(np.int32)
        got = kops.hash_match(
            jnp.asarray(q_lanes), jnp.asarray(q_owner),
            jnp.asarray(t_lanes), jnp.asarray(t_owner),
            block_n=8, block_m=8,
        )
        want = kref.hash_match_ref(
            jnp.asarray(q_lanes), jnp.asarray(q_owner),
            jnp.asarray(t_lanes), jnp.asarray(t_owner),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_no_match_returns_minus_one(self):
        q = jnp.asarray(np.stack([key_lanes("zzz")]))
        t = jnp.asarray(np.stack([key_lanes("aaa")]))
        got = kops.hash_match(
            q, jnp.zeros(1, jnp.int32), t, jnp.zeros(1, jnp.int32)
        )
        assert int(got[0]) == -1

    def test_owner_mismatch_blocks_match(self):
        lanes = jnp.asarray(np.stack([key_lanes("k")]))
        got = kops.hash_match(
            lanes, jnp.array([1], jnp.int32), lanes, jnp.array([2], jnp.int32)
        )
        assert int(got[0]) == -1

    def test_first_match_wins(self):
        lanes = np.stack([key_lanes("k")] * 3)
        got = kops.hash_match(
            jnp.asarray(lanes[:1]),
            jnp.zeros(1, jnp.int32),
            jnp.asarray(lanes),
            jnp.zeros(3, jnp.int32),
        )
        assert int(got[0]) == 0


# ---------------------------------------------------------------------------
# assertion_eval_window
# ---------------------------------------------------------------------------


def _random_nodes(rng, n):
    return {
        "type": jnp.asarray(rng.integers(0, 7, n).astype(np.int32)),
        "is_int": jnp.asarray(rng.integers(0, 2, n).astype(np.int32)),
        "num": jnp.asarray(rng.normal(0, 10, n).astype(np.float32)),
        "size": jnp.asarray(rng.integers(0, 20, n).astype(np.int32)),
        "acquired": jnp.asarray(rng.integers(0, 2**16, n).astype(np.int32)),
        "str_hash": jnp.asarray(
            np.stack([_POOL[i] for i in rng.integers(0, len(_POOL), n)])
        ),
        "str_prefix": jnp.asarray(rng.integers(0, 2**32, (n, 2), dtype=np.uint64).astype(np.uint32)),
    }


def _random_window(rng, n, w):
    """Per-node (n, w) window operands; about a fifth of the slots are
    masked (op -1), as past a location's last row."""
    op = rng.integers(0, 19, (n, w)).astype(np.int32)
    op[rng.random((n, w)) < 0.2] = -1
    return {
        "op": jnp.asarray(op),
        "f0": jnp.asarray(rng.normal(0, 5, (n, w)).astype(np.float32)),
        "i0": jnp.asarray(rng.integers(0, 0xFF, (n, w)).astype(np.int32)),
        "i1": jnp.asarray(rng.integers(0, 2, (n, w)).astype(np.int32)),
        "u0": jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint64).astype(np.uint32)),
        "u1": jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint64).astype(np.uint32)),
        "hash": jnp.asarray(
            np.stack([_POOL[i] for i in rng.integers(0, len(_POOL), n * w)]).reshape(n, w, 8)
        ),
    }


def _one_slot(op, *, f0=0.0, i0=0, u0=0, u1=0):
    """A one-node window: slot 0 holds the row, slot 1 is masked."""
    return {
        "op": jnp.asarray([[op, -1]], jnp.int32),
        "f0": jnp.asarray([[f0, 0.0]], jnp.float32),
        "i0": jnp.asarray([[i0, 0]], jnp.int32),
        "i1": jnp.zeros((1, 2), jnp.int32),
        "u0": jnp.asarray([[u0, 0]], jnp.uint32),
        "u1": jnp.asarray([[u1, 0]], jnp.uint32),
        "hash": jnp.zeros((1, 2, 8), jnp.uint32),
    }


class TestAssertionEval:
    @pytest.mark.parametrize("n,a", [(1, 1), (5, 17), (128, 128), (200, 70), (257, 129)])
    def test_shapes_match_ref(self, n, a):
        rng = np.random.default_rng(n * 31 + a)
        nodes, window = _random_nodes(rng, n), _random_window(rng, n, a)
        got = kops.assertion_eval_window(nodes, window, block_n=128)
        want = kref.assertion_eval_window_ref(nodes, window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 30), a=st.integers(1, 30), seed=st.integers(0, 2**16))
    def test_property_sweep(self, n, a, seed):
        rng = np.random.default_rng(seed)
        nodes, window = _random_nodes(rng, n), _random_window(rng, n, a)
        got = kops.assertion_eval_window(nodes, window, block_n=8)
        want = kref.assertion_eval_window_ref(nodes, window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_precondition_semantics(self):
        """Wrong-typed nodes pass AND rows (paper §5.2); masked slots
        evaluate to 0."""
        nodes = {
            "type": jnp.asarray([4], jnp.int32),  # string
            "is_int": jnp.zeros(1, jnp.int32),
            "num": jnp.zeros(1, jnp.float32),
            "size": jnp.asarray([3], jnp.int32),
            "str_hash": jnp.zeros((1, 8), jnp.uint32),
            "str_prefix": jnp.zeros((1, 2), jnp.uint32),
        }
        window = _one_slot(AOP.NUM_GE, f0=100.0)
        for use_pallas in (True, False):
            got = kops.assertion_eval_window(nodes, window, use_pallas=use_pallas)
            assert np.asarray(got).tolist() == [[1, 0]], use_pallas

    def test_str_prefix_check(self):
        from repro.data.doc_table import _text_tables

        p0, p1 = (int(p) for p in _text_tables([b"x-hello"])[1][0])
        nodes = {
            "type": jnp.asarray([4], jnp.int32),
            "is_int": jnp.zeros(1, jnp.int32),
            "num": jnp.zeros(1, jnp.float32),
            "size": jnp.asarray([7], jnp.int32),
            "str_hash": jnp.zeros((1, 8), jnp.uint32),
            "str_prefix": jnp.asarray([[p0, p1]], jnp.uint32),
        }
        pfx = b"x-".ljust(8, b"\x00")
        window = _one_slot(
            AOP.STR_PREFIX,
            i0=2,
            u0=int.from_bytes(pfx[:4], "big"),
            u1=int.from_bytes(pfx[4:], "big"),
        )
        for use_pallas in (True, False):
            got = kops.assertion_eval_window(nodes, window, use_pallas=use_pallas)
            assert np.asarray(got).tolist() == [[1, 0]], use_pallas
