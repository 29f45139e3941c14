"""Fused decode-attention kernel vs the XLA deferred-layout oracle (interpret mode),
and the paged-attention kernel against the XLA gather path over ragged lengths.

The kernel must reproduce ops/attention.gqa_attention over the deferred-write key
layout ([window slots ++ current token], stale slots masked) for every (pos, window)
relationship decode meets: empty cache, partially filled window, full window.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops.attention import gqa_attention
from distributed_llama_tpu.ops.pallas_attention import fused_decode_attention
from distributed_llama_tpu.ops.pallas_paged_attention import (
    head_group, paged_attention, paged_attention_xla, pages_per_step,
    visited_keys)


def _oracle(q_btgh, kc, vc, k_new, v_new, layer_idx, pos, window):
    """XLA composition: windowed slice + concat current token + masked attention."""
    l, b, hk, s, hs = kc.shape
    win = min(window, s)
    kw = kc[layer_idx, :, :, :win]  # (B, hk, win, hs)
    vw = vc[layer_idx, :, :, :win]
    slot = jnp.arange(win)
    slot_pos = jnp.where(slot < pos, slot, s + 1)
    key_pos = jnp.concatenate([slot_pos, jnp.asarray([pos])])
    kfull = jnp.concatenate([kw, k_new[None]], axis=2)  # (1, hk, win+1, hs)
    vfull = jnp.concatenate([vw, v_new[None]], axis=2)
    return gqa_attention(q_btgh, kfull, vfull, jnp.asarray([pos]),
                         key_positions=key_pos)


@pytest.mark.parametrize("pos,window", [(0, 16), (5, 16), (15, 16), (16, 32), (40, 64)])
@pytest.mark.parametrize("g", [1, 4])
def test_fused_decode_attention_matches_oracle(pos, window, g):
    hk, hs, s, l = 4, 32, 64, 3
    hq = hk * g
    rng = np.random.RandomState(pos * 7 + g)
    kc = jnp.asarray(rng.randn(l, 1, hk, s, hs).astype(np.float32))
    vc = jnp.asarray(rng.randn(l, 1, hk, s, hs).astype(np.float32))
    k_new = jnp.asarray(rng.randn(hk, 1, hs).astype(np.float32))
    v_new = jnp.asarray(rng.randn(hk, 1, hs).astype(np.float32))
    q = jnp.asarray(rng.randn(hk, g, hs).astype(np.float32))
    layer_idx = 1

    got = fused_decode_attention(q, kc, vc, k_new, v_new, layer_idx, pos,
                                 window=window, interpret=True)
    # oracle consumes (B, T, hq, hs) and returns (B, T, hq*hs)
    q_btgh = q.reshape(1, 1, hq, hs)
    want = _oracle(q_btgh, kc, vc, k_new, v_new, layer_idx, pos, window)
    np.testing.assert_allclose(np.asarray(got).reshape(1, 1, hq * hs),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


def test_fused_decode_attention_bf16_cache():
    hk, g, hs, s, l = 2, 2, 32, 32, 2
    rng = np.random.RandomState(0)
    kc = jnp.asarray(rng.randn(l, 1, hk, s, hs).astype(np.float32)).astype(jnp.bfloat16)
    vc = jnp.asarray(rng.randn(l, 1, hk, s, hs).astype(np.float32)).astype(jnp.bfloat16)
    k_new = jnp.asarray(rng.randn(hk, 1, hs)).astype(jnp.bfloat16)
    v_new = jnp.asarray(rng.randn(hk, 1, hs)).astype(jnp.bfloat16)
    q = jnp.asarray(rng.randn(hk, g, hs).astype(np.float32))
    got = fused_decode_attention(q, kc, vc, k_new, v_new, 0, 7, window=16,
                                 interpret=True)
    want = _oracle(q.reshape(1, 1, hk * g, hs), kc, vc, k_new, v_new, 0, 7, 16)
    np.testing.assert_allclose(np.asarray(got).reshape(1, 1, -1), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_tiled_window_matches_one_block(monkeypatch):
    """The window-tiled (flash-carry) form must reproduce the single-block
    kernel exactly on the same inputs — forced by shrinking the one-block VMEM
    budget so a small window takes the tiled branch (with a tile size that
    yields several tiles plus a padded tail)."""
    import distributed_llama_tpu.ops.pallas_attention as pa

    rng = np.random.RandomState(7)
    L, hk, g, s, hs = 2, 2, 3, 96, 16
    q = jnp.asarray(rng.randn(hk, g, hs).astype(np.float32))
    kc = jnp.asarray(rng.randn(L, 1, hk, s, hs).astype(np.float32))
    vc = jnp.asarray(rng.randn(L, 1, hk, s, hs).astype(np.float32))
    kn = jnp.asarray(rng.randn(hk, 1, hs).astype(np.float32))
    vn = jnp.asarray(rng.randn(hk, 1, hs).astype(np.float32))

    want = pa.fused_decode_attention(q, kc, vc, kn, vn, 1, 37, window=96)
    monkeypatch.setattr(pa, "_FUSED_ONE_BLOCK_LIMIT", 1)
    monkeypatch.setattr(pa, "_WT", 40)  # 96 -> tiles of 40/40/16(padded)
    pa.fused_decode_attention._clear_cache()
    got = pa.fused_decode_attention(q, kc, vc, kn, vn, 1, 37, window=96)
    pa.fused_decode_attention._clear_cache()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------------- the paged-attention kernel


def _paged_case(t, g, bt, dtype, seed, lens=None, nb=None, hk=2, window=None,
                real_hs=None):
    """Rows of committed lengths `lens` (by default six that straddle every
    edge of a kernel step: nothing, one key, a block less one, exactly a
    step, one past it, the whole window) in a window of `nb` blocks (by
    default two steps and three blocks, so the last step is short), and NaN
    in every pool position and table entry past a row's length. `window`:
    the layer's sliding window, a traced scalar to the kernel. `real_hs`: the
    values of a head that are real in heads padded with zeros to 128 lanes.
    Returns the kernel's output and the float32 reference's, which reads the
    same pool with the NaN taken out."""
    rng = np.random.default_rng(seed)
    layers, hs, layer = 2, 32, 1
    pp = 128 // bt
    if lens is None:
        nb = 2 * pp + 3
        assert pages_per_step(nb, bt) == pp and nb % pp
        step = pp * bt
        lens = [0, 1, bt - 1, step, step + 1, nb * bt]
    if real_hs:
        hs = 128
    b, n = len(lens), len(lens) * nb + 1

    def mk(shape):
        a = rng.normal(size=shape).astype(np.float32)
        if real_hs:
            a[..., real_hs:] = 0.0
        return a

    kc, vc = mk((layers, n, hk, bt, hs)), mk((layers, n, hk, bt, hs))
    ids = np.arange(1, n)
    rng.shuffle(ids)
    clean = ids.reshape(b, nb).astype(np.int32)
    planted = clean.copy()
    pos = np.arange(nb * bt).reshape(nb, bt)
    for r, ln in enumerate(lens):
        blk, off = (pos >= ln).nonzero()  # positions past the length
        for pool in (kc, vc):
            pool[:, clean[r, blk], :, off] = np.nan
        planted[r, -(-ln // bt):] = 0  # dead entries: the NaN scratch block
    kc[:, 0] = vc[:, 0] = np.nan
    q, kn, vn = mk((b, t, hk * g, hs)), mk((b, hk, t, hs)), mk((b, hk, t, hs))
    lengths = jnp.asarray(lens, jnp.int32)

    def cast(a):
        return jnp.asarray(a, dtype)

    out = paged_attention(cast(q), cast(kc), cast(vc), cast(kn), cast(vn),
                          jnp.asarray(planted), lengths, layer, n_read=nb,
                          interpret=True, head_size=real_hs,
                          window=None if window is None else jnp.int32(window))
    # the reference sees the real values of a padded head alone
    ref = paged_attention_xla(
        *(cast(np.nan_to_num(a)).astype(jnp.float32)[..., :real_hs or hs]
          for a in (q, kc, vc, kn, vn)),
        jnp.asarray(clean), lengths, layer, n_read=nb, window=window or 0)
    out = np.asarray(out)
    assert not real_hs or not out[..., real_hs:].any()
    return out[..., :real_hs or hs], np.asarray(ref)


@pytest.mark.parametrize("bt", [8, 16])
@pytest.mark.parametrize("g", [1, 4, 6])
@pytest.mark.parametrize("t", [1, 5, 8, 64])
def test_paged_attention_matches_the_gather_path_over_ragged_lengths(t, g, bt):
    out, ref = _paged_case(t, g, bt, jnp.float32, seed=t * 100 + g * 10 + bt)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() < 2e-5


def test_paged_attention_takes_bfloat16_operands_as_they_are():
    """bf16 q, pool and chunk: the products of bf16 values are exact in
    float32, so the kernel stands as close to the float32 reference (run on
    the same bf16-rounded values) as with float32 operands: 4.8e-7 read
    here, the float32 cases 2.4e-7 to 7.5e-7."""
    out, ref = _paged_case(8, 4, 16, jnp.bfloat16, seed=7)
    assert out.dtype == np.float32 and np.isfinite(out).all()
    assert np.abs(out - ref).max() < 2e-5


# what the kernel's own pipeline can break (PR 50): row b starts row b + 1's
# first copies into the buffer its own last step is not reading, and a step's
# heads are one batched product, in groups where T*g is large. Rows by their
# steps of 128 keys in a window of three: odd then even, even then odd, a row
# of length 0 between live rows and last, a row that fills the window
_TURNS = [100, 200, 0, 300, 256, 128, 384, 0]
PIPELINE_CASES = {
    **{f"turns-g{g}-t{t}": dict(t=t, g=g, lens=_TURNS)
       for g in (4, 7, 9) for t in (1, 8, 64)},
    # eight heads in two groups of four at a 64-token chunk (576 query rows
    # a head)
    "turns-hk8-g9-t64": dict(t=64, g=9, hk=8, lens=_TURNS),
    "one-row": dict(t=1, g=4, lens=[300]),
    "one-empty-row": dict(t=1, g=4, lens=[0]),
    "empty-rows-first": dict(t=1, g=4, lens=[0, 0, 257, 0, 0, 130]),
    # behind a window of 100 the row of 300 starts at its SECOND step (the
    # copy the row of 50 before it issues), the row of 384 at its third, the
    # row of 290 after an empty one at its second
    **{f"window-j0-g{g}-t{t}": dict(t=t, g=g, window=100,
                                    lens=[50, 300, 384, 0, 290, 128])
       for g, t in ((7, 1), (7, 8), (9, 64))},
    # a window that leaves a row NO step of committed keys is not a case: the
    # first query always reads its own row's last step; window 0 is "none"
    "window-none-g7-t1": dict(t=1, g=7, window=0, lens=_TURNS),
    # heads of 64 in lanes of 128
    **{f"padded-heads-t{t}": dict(t=t, g=4, real_hs=64, lens=_TURNS)
       for t in (1, 64)},
    "padded-heads-bf16-t1": dict(t=1, g=4, real_hs=64, lens=_TURNS,
                                 dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(PIPELINE_CASES))
def test_paged_attention_matches_the_gather_path_across_rows(case):
    kw = dict(PIPELINE_CASES[case])
    out, ref = _paged_case(kw.pop("t"), kw.pop("g"), 16,
                           kw.pop("dtype", jnp.float32),
                           seed=list(PIPELINE_CASES).index(case), nb=24,
                           **kw)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() < 2e-5


@pytest.mark.parametrize("rows,hg", [(64, 2), (16, 1)])
def test_paged_attention_in_head_groups_is_the_ungrouped_result(monkeypatch,
                                                                rows, hg):
    """The same call with its four heads taken two at a time and one at a
    time (the group's bound shrunk) gives the bits the whole batch gives."""
    import distributed_llama_tpu.ops.pallas_paged_attention as ppa

    kw = dict(t=8, g=4, bt=16, dtype=jnp.float32, seed=3, lens=_TURNS, nb=24,
              hk=4, window=100)
    assert head_group(8, 4, 4) == 4
    whole, ref = _paged_case(**kw)
    monkeypatch.setattr(ppa, "_GROUP_ROWS", rows)
    assert head_group(8, 4, 4) == hg
    paged_attention.clear_cache()
    try:
        grouped, _ = _paged_case(**kw)
    finally:
        paged_attention.clear_cache()
    np.testing.assert_array_equal(grouped, whole)
    assert np.abs(whole - ref).max() < 2e-5


@pytest.mark.parametrize("t,g,hk,want", [
    (1, 4, 8, 8), (1, 7, 4, 4), (1, 9, 8, 8), (8, 4, 8, 8), (8, 9, 8, 8),
    (5, 4, 8, 8), (64, 4, 8, 8), (64, 4, 2, 2), (64, 6, 8, 8), (64, 7, 4, 4),
    (64, 9, 8, 4), (64, 9, 2, 2), (64, 32, 8, 1), (64, 4, 1, 1)])
def test_head_group_follows_the_query_rows(t, g, hk, want):
    """All heads at once up to 3072 query rows a group, a divisor of hk that
    keeps the group's score block under that where T*g is larger."""
    assert head_group(t, g, hk) == want


@pytest.mark.parametrize("length,n_read,bt,want", [
    (0, 64, 16, 0), (1, 64, 16, 128), (128, 64, 16, 128), (129, 64, 16, 256),
    (640, 64, 16, 640), (1024, 64, 16, 1024),  # 8 blocks a step at bt 16
    (300, 19, 16, 304),  # a short last step: never past the window
    (5, 4, 16, 64), (5, 35, 8, 128), (65, 4, 64, 128), (1, 2, 256, 256)])
def test_visited_keys_are_whole_steps_up_to_the_length(length, n_read, bt,
                                                       want):
    assert visited_keys(length, n_read, bt) == want
