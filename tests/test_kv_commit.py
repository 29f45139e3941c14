"""The paged KV commit alone (`models/forward.commit_block_rows`) against the
scatter formula it replaced in PR 37, kept here as the reference.

The commit moves data and computes nothing, so the comparison is bit for
bit, at every (block, offset) a row's table maps. Idle and parked rows all
point at the one scratch block (block 0; `BatchEngine._park_positions`) and
write the SAME (block, offset) in one dispatch: there the two forms may
leave different rows' values (neither promises which of colliding writes
stays) and nobody reads them, so the scratch block is left out of the
comparison in the colliding cases and held to it in the others.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.forward import commit_block_rows

BT, LAYERS, BLOCKS, ROWS, TABLE = 16, 3, 48, 4, 10
# (hk, w): keys or values of 8 and of 4 kv heads, and a latent row (one
# "head", a width that is no power of two)
POOLS = {"hk8": (8, 32), "hk4": (4, 32), "latent": (1, 40)}
# where each row's chunk starts: on a block's edge, three positions before
# one (a chunk of 4 and more straddles it), on a block's last position, and
# deep in the row's table
STARTS = (0, BT - 3, 2 * BT - 1, 5 * BT + 7)


def scatter_reference(pool, rows, tables, start, bt):
    """`forward()`'s paged commit until PR 37: one scatter through the block
    and offset axes."""
    t = rows.shape[3]
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    blk = jnp.take_along_axis(
        tables, jnp.minimum(pos // bt, tables.shape[1] - 1), axis=1)
    return pool.at[:, blk, :, pos % bt, :].set(
        jnp.transpose(rows, (1, 3, 0, 2, 4)).astype(pool.dtype))


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def _case(pool, t, collide, seed=0):
    hk, w = POOLS[pool]
    rng = np.random.default_rng(seed)
    old = rng.normal(size=(LAYERS, BLOCKS, hk, BT, w)).astype(np.float32)
    new = rng.normal(size=(LAYERS, ROWS, hk, t, w)).astype(np.float32)
    # distinct blocks a row, none the scratch block
    tables = rng.permutation(np.arange(1, BLOCKS))[:ROWS * TABLE].reshape(
        ROWS, TABLE).astype(np.int32)
    start = np.array(STARTS, np.int32)
    if collide:  # the last two rows idle: parked at 0 on the scratch block
        tables[ROWS - 2:] = 0
        start[ROWS - 2:] = 0
    return (jnp.asarray(old, jnp.bfloat16), jnp.asarray(new, jnp.bfloat16),
            jnp.asarray(tables), jnp.asarray(start))


@pytest.mark.parametrize("collide", [False, True], ids=["distinct", "collide"])
@pytest.mark.parametrize("t", [1, 8, 64])
@pytest.mark.parametrize("pool", list(POOLS))
def test_commit_equals_the_scatter(pool, t, collide):
    old, new, tables, start = _case(pool, t, collide)
    got = jax.jit(commit_block_rows)(old, new, tables, start)
    want = scatter_reference(old, new, tables, start, BT)
    first = 1 if collide else 0  # block 0 took colliding writes
    np.testing.assert_array_equal(_f32(got[:, first:]), _f32(want[:, first:]))
    # and something was written: every live row's first new position
    live = ROWS - 2 if collide else ROWS
    for r in range(live):
        p = int(start[r])
        np.testing.assert_array_equal(
            _f32(got[:, tables[r, p // BT], :, p % BT]), _f32(new[:, r, :, 0]))


@pytest.mark.parametrize("pool", list(POOLS))
def test_chunk_that_straddles_a_blocks_edge(pool):
    """Eight positions from a block's offset 12: four land at the end of
    one block, four at the start of the row's next, and every other offset
    of both blocks keeps what it held."""
    old, new, tables, start = _case(pool, 8, False, seed=1)
    start = start.at[0].set(2 * BT + 12)
    got = _f32(commit_block_rows(old, new, tables, start))
    old_f, new_f = _f32(old), _f32(new)
    a, b = int(tables[0, 2]), int(tables[0, 3])
    np.testing.assert_array_equal(got[:, a, :, 12:], new_f[:, 0, :, :4])
    np.testing.assert_array_equal(got[:, b, :, :4], new_f[:, 0, :, 4:])
    np.testing.assert_array_equal(got[:, a, :, :12], old_f[:, a, :, :12])
    np.testing.assert_array_equal(got[:, b, :, 4:], old_f[:, b, :, 4:])
    want = scatter_reference(old, new, tables, start, BT)
    np.testing.assert_array_equal(got, _f32(want))


def test_empty_second_side_of_a_latent_pool():
    """A latent spec's second cache side has no values: nothing to write."""
    pool = jnp.zeros((LAYERS, BLOCKS, 1, BT, 0), jnp.bfloat16)
    rows = jnp.zeros((LAYERS, ROWS, 1, 8, 0), jnp.bfloat16)
    out = commit_block_rows(pool, rows, jnp.zeros((ROWS, TABLE), jnp.int32),
                            jnp.zeros((ROWS,), jnp.int32))
    assert out.shape == pool.shape
