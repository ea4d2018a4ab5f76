"""``partition_sum``: per-tick sums over partitions in a fixed order.

A lane's bits must not depend on how many lanes share its launch (one-lane
``api.run`` vs a sweep group, a fleet wave of width 2 vs 64).  The sum is a
left fold in index order: a reduce on XLA:CPU, an explicit chain of adds on
every other backend, whose lowering is checked here without a chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import types

# Association matters for the first two rows: in f32 (1e8 + 1) - 1e8 == 0,
# while (1e8 - 1e8) + 1 == 1.
ROWS = np.asarray([[1e8, 1.0, -1e8],
                   [1e8, -1e8, 1.0],
                   [0.1, 0.2, 0.3],
                   [3.0, 0.0, 0.0]], np.float32)


def _left_fold(rows):
    acc = rows[:, 0].copy()
    for i in range(1, rows.shape[1]):
        acc = (acc + rows[:, i]).astype(np.float32)
    return acc


@pytest.mark.parametrize("fn", [types._fold_partitions, types.partition_sum],
                         ids=["fold", "partition_sum"])
def test_sums_in_index_order(fn):
    got = np.asarray(jax.jit(jax.vmap(fn))(ROWS))
    np.testing.assert_array_equal(got, _left_fold(ROWS))
    assert got[0] == 0.0 and got[1] == 1.0


@pytest.mark.parametrize("width", [1, 2, 3, 8])
def test_a_lane_does_not_depend_on_its_batch_width(width):
    fn = jax.jit(jax.vmap(types.partition_sum))
    want = np.asarray(jax.jit(types.partition_sum)(ROWS[1]))
    batch = np.repeat(ROWS[1:2], width, axis=0)
    np.testing.assert_array_equal(np.asarray(fn(batch)),
                                  np.full(width, want))


@pytest.mark.parametrize("platform,reduces", [("cpu", True), ("tpu", False)])
def test_lowering_per_platform(platform, reduces):
    x = jnp.zeros((4, 3), jnp.float32)
    text = jax.jit(jax.vmap(types.partition_sum)).trace(x).lower(
        lowering_platforms=(platform,)).as_text()
    assert ("stablehlo.reduce" in text) == reduces
