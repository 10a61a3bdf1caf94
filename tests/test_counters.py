"""core/counters.py: exact running totals past f32's 2**24."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import counters


def _increments(n_steps, n_shards, seed=0):
    # about one step's synaptic events of a 24x24x1240 grid, split over
    # the shards of a mesh
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1_300_000 // n_shards, (n_steps, n_shards),
                        dtype=np.int32)


def _run(incs):
    """Scan one counter per shard over the steps, like the run loops."""
    def body(count, inc):
        return jax.vmap(counters.add)(count, inc), None

    zero = jnp.tile(counters.zero(), (incs.shape[1], 1))
    return jax.jit(lambda x: jax.lax.scan(body, zero, x)[0])(incs)


@pytest.mark.parametrize("n_shards", [1, 4, 16])
def test_total_is_exact_and_split_invariant(n_shards):
    """However the increments are split over shards, the word-wise sum of
    the shards' counters gives the exact integer, and its f32 value is
    bitwise that of the single running total."""
    incs = _increments(300, n_shards)
    per_shard = _run(jnp.asarray(incs))
    total = per_shard.sum(axis=0)                 # what psum does
    want = int(incs.astype(np.int64).sum())
    assert want > 2 ** 24 * 10                    # well past f32 exactness
    hi, lo = (int(x) for x in np.asarray(total))
    assert hi * 2 ** counters.LO_BITS + lo == want
    single = _run(jnp.asarray(incs.sum(axis=1, keepdims=True)))[0]
    got = np.asarray(counters.value(total))
    assert got == np.float32(want)
    assert got.tobytes() == np.asarray(counters.value(single)).tobytes()


def test_value_of_a_stack_of_counters():
    stack = jnp.asarray([[0, 5], [3, (1 << counters.LO_BITS) + 1]],
                        jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(counters.value(stack)),
        np.float32([5, 4 * 2 ** counters.LO_BITS + 1]))
