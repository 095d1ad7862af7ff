"""Property tests for the ring-collective Algorithm 2
(`kernels/ring_wavg`, `averaging.weighted_average_psum(impl="ring")`).

Same in-process harness as tests/test_averaging_property.py: the
collectives (`lax.ppermute`, `lax.all_gather`, `lax.psum`) run under
`jax.vmap(..., axis_name=...)`, which gives them a real named axis of
size K on one CPU device — the real shard_map execution is pinned by
the mesh equivalence matrix in tests/test_driver_equivalence.py.

Invariants pinned here:
  * ring == per-leaf psum reference == flat pallas path (round-off)
  * ring == the order-independent float64 numpy ref (ref.py), seeded
    twins — including the QUANTIZED wire (same device_uplink_key
    streams as the flat path's roundtrip)
  * the result is replicated on every slice
  * BLOCK/chunk edges: payload sizes 1, BLOCK_N +- 1, chunk-count
    boundaries (n_blocks = 1, chunks, chunks + 1), K not a power of two
  * zero total weight returns the fallback tree (no-survivor rounds)

Hypothesis runs when importable (requirements-dev.txt); every generated
case derives from a drawn SEED, so shrunk failures reproduce from the
seed alone, and the same check functions run on seeded twins in every
environment.
"""
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize
from repro.core.averaging import weighted_average_psum
from repro.kernels.ring_wavg.kernel import BLOCK_N, ROWS, ring_accum_pallas
from repro.kernels.ring_wavg.ops import (DEFAULT_CHUNKS, _chunk_bounds,
                                         ring_average_psum,
                                         ring_wire_bytes_per_rank)
from repro.kernels.ring_wavg.ref import ring_average_ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

AXIS = "k"


def run_ring(tree_stacked, weights, **kw):
    out = jax.vmap(
        lambda t, w: ring_average_psum(t, w, axis_names=AXIS, **kw),
        axis_name=AXIS)(tree_stacked, weights)
    return out, jax.tree.map(lambda x: x[0], out)


def run_flat(tree_stacked, weights, impl):
    out = jax.vmap(
        lambda t, w: weighted_average_psum(t, w, axis_names=AXIS,
                                           impl=impl),
        axis_name=AXIS)(tree_stacked, weights)
    return jax.tree.map(lambda x: x[0], out)


def make_case(seed: int, *, k=None, sizes=None, dtypes=None,
              zero_weights=False):
    """Random stacked pytree + weights, fully determined by `seed`
    (the tests/test_averaging_property.py recipe)."""
    rng = np.random.default_rng(seed)
    k = k or int(rng.integers(1, 9))
    if sizes is None:
        sizes = [int(rng.integers(1, 300))
                 for _ in range(int(rng.integers(1, 4)))]
    if dtypes is None:
        dtypes = [jnp.float32 if rng.integers(2) else jnp.bfloat16
                  for _ in sizes]
    tree = {
        f"leaf{i}": jnp.asarray(
            rng.standard_normal((k, n)) * rng.uniform(0.1, 10.0),
            dt)
        for i, (n, dt) in enumerate(zip(sizes, dtypes))
    }
    if zero_weights:
        w = jnp.zeros(k, jnp.float32)
    else:
        w = jnp.asarray(rng.uniform(0.0, 5.0, k), jnp.float32)
        w = jnp.where(jnp.asarray(rng.uniform(size=k) < 0.3), 0.0, w)
    return tree, w


# ---------------------------------------------------------------------------
# Shared checks
# ---------------------------------------------------------------------------

def check_ring_matches_references(tree, w):
    """ring == per-leaf psum == flat pallas == float64 numpy ref, with
    structure/shape/dtype preserved."""
    _, ring = run_ring(tree, w)
    psum_ref = run_flat(tree, w, "jnp")
    ref64 = ring_average_ref(tree, w)
    assert (jax.tree_util.tree_structure(ring)
            == jax.tree_util.tree_structure(psum_ref))
    for a, b, c in zip(jax.tree_util.tree_leaves(ring),
                       jax.tree_util.tree_leaves(psum_ref),
                       jax.tree_util.tree_leaves(ref64)):
        assert a.dtype == b.dtype and a.shape == b.shape
        atol = 2e-5 if a.dtype == jnp.float32 else 0.02
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), atol=atol)


def check_quantized_ring_matches_ref(tree, w, seed, bits=16):
    """The encoded wire must realize the SAME quantized values as the
    flat path's per-device roundtrip streams (ref.py reuses
    quantize_tree with device_uplink_key): the only deviation allowed
    is f32-vs-f64 accumulation order."""
    k = jax.tree_util.tree_leaves(tree)[0].shape[0]
    round_key = jax.random.PRNGKey(seed)
    keys = jnp.stack([quantize.device_uplink_key(round_key, i)
                      for i in range(k)])
    out = jax.vmap(
        lambda t, wi, kk: ring_average_psum(t, wi, axis_names=AXIS,
                                            quantize_key=kk, bits=bits),
        axis_name=AXIS)(tree, w, keys)
    ring = jax.tree.map(lambda x: x[0], out)
    ref64 = ring_average_ref(tree, w, round_key=round_key, bits=bits)
    for a, c in zip(jax.tree_util.tree_leaves(ring),
                    jax.tree_util.tree_leaves(ref64)):
        atol = 2e-5 if a.dtype == jnp.float32 else 0.02
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), atol=atol)


def check_replicated(tree, w):
    stacked, _ = run_ring(tree, w)
    for leaf in jax.tree_util.tree_leaves(stacked):
        first = np.asarray(leaf[0:1], np.float32)
        np.testing.assert_allclose(
            np.broadcast_to(first, leaf.shape),
            np.asarray(leaf, np.float32), atol=1e-6)


# ---------------------------------------------------------------------------
# Seeded twins (always run)
# ---------------------------------------------------------------------------

class TestRingSeeded:
    @pytest.mark.parametrize("seed", range(6))
    def test_matches_references(self, seed):
        tree, w = make_case(seed)
        check_ring_matches_references(tree, w)

    @pytest.mark.parametrize("seed", range(4))
    def test_quantized_matches_ref(self, seed):
        tree, w = make_case(seed + 100,
                            dtypes=None if seed % 2 else [jnp.float32])
        check_quantized_ring_matches_ref(tree, w, seed)

    @pytest.mark.parametrize("k", [1, 2, 3, 5, 7])
    def test_k_not_power_of_two(self, k):
        tree, w = make_case(11, k=k, sizes=[513, 40],
                            dtypes=[jnp.float32, jnp.float32])
        check_ring_matches_references(tree, w)
        check_quantized_ring_matches_ref(tree, w, 17)
        check_replicated(tree, w)

    @pytest.mark.parametrize("n", [1, BLOCK_N - 1, BLOCK_N, BLOCK_N + 1])
    def test_block_edges(self, n):
        tree, w = make_case(13, k=4, sizes=[n], dtypes=[jnp.float32])
        check_ring_matches_references(tree, w)

    @pytest.mark.parametrize("blocks",
                             [1, DEFAULT_CHUNKS, DEFAULT_CHUNKS + 1,
                              2 * DEFAULT_CHUNKS + 3])
    def test_chunk_count_edges(self, blocks):
        """n_blocks below / at / past the chunk count exercises the
        single-chunk path and the ragged last chunk."""
        tree, w = make_case(29, k=3, sizes=[blocks * BLOCK_N - 7],
                            dtypes=[jnp.float32])
        check_ring_matches_references(tree, w)
        check_quantized_ring_matches_ref(tree, w, 31)

    def test_zero_weights_returns_fallback(self):
        tree, w = make_case(41, k=4, zero_weights=True)
        fb = jax.tree.map(lambda x: jnp.ones_like(x[0]), tree)
        out = jax.vmap(
            lambda t, wi: ring_average_psum(t, wi, axis_names=AXIS,
                                            fallback=fb),
            axis_name=AXIS)(tree, w)
        for a, f in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(fb)):
            np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                          np.asarray(f, np.float32))

    def test_multi_axis_rejected(self):
        tree, w = make_case(43, k=2)
        with pytest.raises(NotImplementedError):
            jax.vmap(lambda t, wi: ring_average_psum(
                t, wi, axis_names=(AXIS, "m")), axis_name=AXIS)(tree, w)

    def test_ring_does_not_compose_with_robust(self):
        from repro.kernels.robust_avg import RobustConfig
        tree, w = make_case(47, k=2)
        with pytest.raises(ValueError):
            jax.vmap(lambda t, wi: weighted_average_psum(
                t, wi, axis_names=AXIS, impl="ring",
                robust=RobustConfig(method="trimmed_mean")),
                axis_name=AXIS)(tree, w)


# ---------------------------------------------------------------------------
# Kernel + helpers (no collectives)
# ---------------------------------------------------------------------------

class TestRingAccumKernel:
    @pytest.mark.parametrize("dtype,seed", [(jnp.int16, 0),
                                            (jnp.int32, 1),
                                            (jnp.float32, 2)])
    def test_accumulate_matches_numpy(self, dtype, seed):
        rng = np.random.default_rng(seed)
        nb = 3
        acc = rng.standard_normal((nb, BLOCK_N)).astype(np.float32)
        coef = rng.standard_normal(nb).astype(np.float32)
        if dtype == jnp.float32:
            q = rng.standard_normal((nb, BLOCK_N)).astype(np.float32)
        else:
            q = rng.integers(-1000, 1000, (nb, BLOCK_N)).astype(
                np.dtype(dtype))
        out = ring_accum_pallas(jnp.asarray(acc),
                                jnp.asarray(q, dtype),
                                jnp.asarray(coef), interpret=True)
        expect = acc + coef[:, None] * q.astype(np.float32)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6,
                                   atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.int16, jnp.float32])
    def test_accumulate_ragged_grid_matches_numpy(self, dtype):
        """More wire blocks than one grid step takes, with a ragged last
        step: every row is accumulated once and only once."""
        rng = np.random.default_rng(3)
        nb = 2 * ROWS + 5
        acc = rng.standard_normal((nb, BLOCK_N)).astype(np.float32)
        coef = rng.standard_normal(nb).astype(np.float32)
        q = rng.integers(-1000, 1000, (nb, BLOCK_N)).astype(np.dtype(dtype))
        out = ring_accum_pallas(jnp.asarray(acc), jnp.asarray(q),
                                jnp.asarray(coef), interpret=True)
        expect = acc + coef[:, None] * q.astype(np.float32)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6,
                                   atol=1e-5)

    def test_chunk_bounds_cover_exactly(self):
        for nb in (1, 2, 4, 5, 9, 64):
            for nc in (1, 2, 4, 7):
                bounds = _chunk_bounds(nb, nc)
                assert bounds[0][0] == 0 and bounds[-1][1] == nb
                for (a0, a1), (b0, b1) in zip(bounds, bounds[1:]):
                    assert a1 == b0 and a1 > a0
                assert len(bounds) == min(nc, nb)

    def test_wire_bytes_formula(self):
        tree = {"a": jnp.zeros((BLOCK_N + 1,)), "b": jnp.zeros((5,))}
        # 2 blocks for a, 1 for b; int16 wire + f32 scale per block
        assert ring_wire_bytes_per_rank(tree, 16, 8) == \
            7 * 3 * (BLOCK_N * 2 + 4)
        assert ring_wire_bytes_per_rank(tree, 32, 8) == \
            7 * 3 * (BLOCK_N * 4 + 4)


# ---------------------------------------------------------------------------
# Hypothesis sweep (guarded)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_ring_matches_references(seed):
        tree, w = make_case(seed)
        check_ring_matches_references(tree, w)

    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_hypothesis_quantized_ring(seed):
        tree, w = make_case(seed)
        check_quantized_ring_matches_ref(tree, w, seed % 1000)
