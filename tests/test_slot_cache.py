"""The cache manager behind the scheduler (runtime/slot_cache.py, ISSUE 47).

(a) Both implementations, the device block pool and the dense per-slot rows,
answer every call the scheduler makes, on the tiny dense toy: one engine a
kind for the whole module, the same requests through both.
(b) The seam stays where it is: an AST check that runtime/batch_engine.py
branches nowhere on the cache kind or on the state layers outside the
places named below, and that runtime/slot_cache.py does not look upward.
"""

import ast
import inspect
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime import slot_cache
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler

KINDS = ("pool", "dense")
BT = 8
PROMPT = [(7 * i + 3) % 250 + 3 for i in range(37)]


@pytest.fixture(scope="module")
def engines():
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=128, rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=3)
    made = {kind: BatchEngine(
        spec, params, None, slots=2, superstep=4, tp=1, dtype=jnp.float32,
        paged_kv=kind == "pool", kv_block_tokens=BT,
        prefix_block_tokens=BT, prefix_cache_blocks=16)
        for kind in KINDS}
    # the scheduler's thread is never started: the tests are the scheduler
    for be in made.values():
        be._ensure_thread = lambda: None
    yield made
    for be in made.values():
        be.close()


def _drive(be, prompt, n):
    """One request to its end, the test being the scheduler."""
    req = be.submit(prompt, n, Sampler(256, temperature=0.0))
    for _ in range(200):
        if req.done.is_set():
            return req
        be._loop_once()
    raise AssertionError("request not finished")


@pytest.mark.parametrize("kind", KINDS)
def test_the_factory_makes_the_kind_the_engine_was_given(engines, kind):
    be = engines[kind]
    sc = be.slot_cache
    assert type(sc) is (slot_cache.PoolSlotCache if kind == "pool"
                        else slot_cache.DenseSlotCache)
    assert (be.kv_pool is not None) == (kind == "pool")
    assert be.kv_pool is sc.kv_pool and be.prefix_cache is sc.prefix_cache
    assert sc.block_tokens == (BT if kind == "pool" else 0)
    assert sc.stride == 0 and sc.no_stream is None  # no state layers
    assert (be.seed_bytes, be.seed_ms) == (sc.seed_bytes, sc.seed_ms)


@pytest.mark.parametrize("kind", KINDS)
def test_cover_then_the_table(engines, kind):
    be = engines[kind]
    sc, slot = be.slot_cache, be._slots[1]
    sc.release(slot)
    sc.cover(slot, 2 * BT + 1)
    tables, resent = sc.table()
    if kind == "dense":
        assert (tables, resent, slot.blocks) == (None, False, [])
        return
    assert resent and len(slot.blocks) == 3
    assert np.asarray(tables)[1, :4].tolist() == slot.blocks + [0]
    assert sc.table() == (tables, False)  # nothing edited: nothing re-sent
    sc.cover(slot, 2 * BT + 1)  # covered already
    assert sc.table() == (tables, False)
    sc.release(slot)
    assert slot.blocks == [] and sc.table()[1]
    assert sc.tables_np[1].tolist() == [0] * (128 // BT)


@pytest.mark.parametrize("kind", KINDS)
def test_the_trivial_answers_of_a_model_without_state(engines, kind):
    sc = engines[kind].slot_cache
    slot = engines[kind]._slots[0]
    assert sc.chunk_limit(5) >= 64
    assert sc.state_word([(slot, None)], [0, 0], [1, 0]) == ([], {})
    sc.settle_state([], True)
    sc.settle_state([], False, scan=True)
    sc.settle()
    sc.settle(force=True)
    assert sc.pending_bytes() == 0


STREAMS: dict = {}


@pytest.mark.parametrize("kind", KINDS)
def test_admission_after_a_harvest_reuses_the_same_length(engines, kind):
    """A finished request is harvested; the same prompt with another tail,
    admitted on the OTHER slot, reuses the harvested blocks: the same reuse
    length and the same next tokens from both implementations."""
    be = engines[kind]
    first = _drive(be, PROMPT, 4)
    used = next(s for s in be._slots if s.history[:8] == PROMPT[:8])
    assert used.lease is None  # unpinned at the finish
    hold = _drive(be, [9, 8, 7] + PROMPT[:5], 1)  # takes no slot for long
    be.slot_cache.release(used)  # pool: the rewind stock goes, the
    used.history = []            # directory's references stay
    again = _drive(be, PROMPT[:36] + [11, 12, 13], 5)
    STREAMS[kind] = (first.out, again.out, again.stats.reused_tokens)
    assert hold.done.is_set()
    assert again.stats.reused_tokens == 32  # four whole blocks of eight
    if kind == KINDS[-1]:
        assert STREAMS["pool"] == STREAMS["dense"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_clamped_park_truncates_and_an_idle_pool_slot_is_emptied(engines,
                                                                  kind):
    be = engines[kind]
    sc = be.slot_cache
    slot = next(s for s in be._slots if len(s.history) >= 16)
    n = len(slot.history)
    assert slot.req is None
    kept = sc.park(slot, n - 3, n)
    if kind == "pool":  # idle: the rewind stock is dropped, the row parks at 0
        assert not kept and slot.blocks == [] and slot.history == []
    else:
        assert kept and len(slot.history) == n - 3
    sc.own(slot, 0, 8)  # nothing shared (dense: nothing at all)


@pytest.mark.parametrize("kind", KINDS)
def test_export_then_import_covers_the_span(engines, kind):
    be = engines[kind]
    sc = be.slot_cache
    req = _drive(be, [5, 6] + PROMPT[:20], 2)
    slot = next(s for s in be._slots if s.history[:2] == [5, 6])
    tokens, blocks, bt = sc.export_blocks(slot, len(req.prompt))
    assert bt == BT and tokens == ([5, 6] + PROMPT[:20])[:16]
    assert len(blocks) == 2 and blocks[0][0].shape[-2] == BT
    shifted = [t + 1 for t in tokens]
    assert be.import_kv_blocks(shifted, blocks) == 16
    assert be.import_kv_blocks(shifted[:7], blocks) == 0  # no whole block


@pytest.mark.parametrize("kind", KINDS)
def test_reset_leaves_no_block_referenced(engines, kind):
    be = engines[kind]
    sc = be.slot_cache
    for slot in be._slots:
        sc.unpin(slot)
        sc.release(slot)
        assert slot.lease is None
    sc.reset()
    if kind == "dense":
        return
    assert all(s.blocks == [] for s in be._slots)
    assert be.kv_pool.used_blocks() == 0
    assert be.prefix_cache.total_refs() == 0
    assert not sc.tables_np.any() and sc.table()[1]


# ------------------------------------------------------------- the seam

SRC = os.path.dirname(inspect.getfile(slot_cache))


def _functions(tree):
    """(the enclosing function's name or None, node) for every node."""
    def walk(node, fn):
        for child in ast.iter_child_nodes(node):
            inner = child.name if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else fn
            yield inner, child
            yield from walk(child, inner)
    return walk(tree, None)


def test_the_scheduler_keeps_no_branch_on_the_cache_kind():
    tree = ast.parse(open(os.path.join(SRC, "batch_engine.py")).read())
    kind, state = [], []
    for fn, node in _functions(tree):
        if (isinstance(node, ast.Compare)
                and isinstance(node.ops[0], (ast.Is, ast.IsNot))
                and isinstance(node.left, ast.Attribute)
                and node.left.attr == "kv_pool" and fn != "__init__"):
            kind.append((fn, node.lineno))
        if (isinstance(node, ast.Attribute)
                and node.attr in ("ssm", "mixed", "_stride")
                and fn not in ("__init__", "_count_work")):
            state.append((fn, node.attr, node.lineno))
    assert not kind, f"`kv_pool is (not) None` outside __init__: {kind}"
    assert not state, f"state layers known outside the manager: {state}"


@pytest.mark.parametrize("name", ["batch_engine.py", "slot_cache.py"])
def test_no_private_name_of_the_cache_layer_is_imported_in_a_function(name):
    tree = ast.parse(open(os.path.join(SRC, name)).read())
    found = [(fn, a.name, node.lineno) for fn, node in _functions(tree)
             if isinstance(node, ast.ImportFrom) and fn is not None
             and "cache" in (node.module or "")
             for a in node.names if a.name.startswith("_")]
    assert not found, found


def test_the_manager_does_not_import_the_scheduler():
    tree = ast.parse(open(os.path.join(SRC, "slot_cache.py")).read())
    for node in ast.walk(tree):
        names = ([a.name for a in node.names]
                 if isinstance(node, (ast.Import, ast.ImportFrom)) else [])
        module = getattr(node, "module", None) or ""
        assert "batch_engine" not in module, node.lineno
        assert not any("batch_engine" in n for n in names), node.lineno
