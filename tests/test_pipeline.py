"""Pipelined super-step scheduler tests (8-device CPU mesh via conftest).

The pipelined scheduler (runtime/batch_engine.py, docs/SERVING.md "Pipelined
decode") eagerly issues super-step N+1 chained from N's device-resident carry
(last token, positions, xorshift* RNG) while N's block is delivered host-side.
Load-bearing properties:

- TOKEN IDENTITY with the unpipelined scheduler — greedy and seeded
  stochastic, mixed budgets, concurrent rows — including through every flush
  path (mid-block EOS, cancellation, admission);
- the device-carried RNG round-trips bit-exactly through flushes: a sampler
  reused across requests sees one unbroken xorshift* stream either way;
- a flush discards exactly the speculated tokens (free frontier rewind) and
  the engine keeps serving;
- the argpartition top-p host sampler is bit-identical to the full-sort path
  it replaced (it sits on the overlapped delivery loop).
"""

import threading

import numpy as np
import pytest

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler


def _spec(seq_len=128):
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=seq_len,
                     rope_type=RopeType.LLAMA).resolved()


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0)


def _flushes() -> dict:
    return dict(metrics.snapshot().get("batch_pipeline_flushes_total") or {})


def _flush_delta(before: dict, reason: str | None = None) -> float:
    after = _flushes()
    keys = [k for k in after if reason is None or reason in k]
    return sum(after[k] - before.get(k, 0.0) for k in keys)


@pytest.fixture(scope="module")
def params():
    return init_random_params(_spec(), FloatType.Q40, seed=11)


def _engines(params, **kw):
    """A (pipelined, unpipelined) engine pair over the same weights."""
    spec = _spec()
    on = BatchEngine(spec, params, slots=2, tp=2, superstep=4,
                     pipeline=True, **kw)
    off = BatchEngine(spec, params, slots=2, tp=2, superstep=4,
                      pipeline=False, **kw)
    return spec, on, off


# ------------------------------------------------------------- token identity


def test_pipeline_engages_and_greedy_identity(params):
    """Steady-state greedy decode must chain dispatches (pipeline actually ON:
    depth-2 issues observed as zero-gap) and emit exactly the unpipelined
    scheduler's tokens, with max_tokens NOT a multiple of K."""
    spec, on, off = _engines(params)
    try:
        assert on.pipeline and not off.pipeline
        prompt = [1, 7, 23, 5]
        want = off.submit(list(prompt), 11, _greedy(spec)).wait(timeout=120)
        hist0 = metrics.snapshot().get("batch_dispatch_gap_seconds") or {}
        got = on.submit(list(prompt), 11, _greedy(spec)).wait(timeout=120)
        hist1 = metrics.snapshot().get("batch_dispatch_gap_seconds") or {}
        assert got == want
        # chained issues record a literal 0.0 gap in the first bucket
        b0 = (hist0.get("buckets") or {}).get("0.0001", 0)
        b1 = (hist1.get("buckets") or {}).get("0.0001", 0)
        assert b1 > b0, "no chained (zero-gap) dispatch was issued"
    finally:
        on.close()
        off.close()


def test_pipeline_mixed_budgets_concurrent_rows(params):
    """Two concurrent requests with different max_tokens (mixed per-row
    budgets: one row parks mid-scan while the other keeps decoding) must both
    match the unpipelined engine."""
    spec, on, off = _engines(params)
    try:
        outs = {}
        for label, be in (("off", off), ("on", on)):
            r1 = be.submit([1, 7, 23, 5], 13, _greedy(spec))
            r2 = be.submit([1, 9, 2], 6, _greedy(spec))
            outs[label] = (r1.wait(timeout=120), r2.wait(timeout=120))
        assert outs["on"] == outs["off"]
    finally:
        on.close()
        off.close()


def test_pipeline_stochastic_identity_and_rng_state(params):
    """Seeded stochastic decode: tokens AND the final sampler state must be
    identical pipelined vs unpipelined — the device-carried RNG chain must be
    indistinguishable from the per-dispatch upload/writeback."""
    spec, on, off = _engines(params)
    try:
        for temp, topp in ((0.8, 0.9), (1.3, 0.5)):
            outs, states = {}, {}
            for label, be in (("off", off), ("on", on)):
                s = Sampler(spec.vocab_size, temperature=temp, topp=topp,
                            seed=777)
                outs[label] = be.submit([1, 7, 23], 12, s).wait(timeout=120)
                states[label] = int(s.state)
            assert outs["on"] == outs["off"], (temp, topp, outs)
            assert states["on"] == states["off"], (temp, topp, states)
    finally:
        on.close()
        off.close()


# ------------------------------------------------------------------- flushes


def test_mid_block_eos_flushes_and_stays_identical(params):
    """A stop firing mid-block invalidates the chained dispatch: it must be
    flushed (counted by reason), the output must equal the unpipelined run,
    and a sampler reused for a follow-up request must see ONE unbroken
    xorshift* stream (the flush must not consume or skip coins)."""
    spec, on, off = _engines(params)
    try:
        results = {}
        for label, be in (("off", off), ("on", on)):
            smp = Sampler(spec.vocab_size, temperature=0.9, topp=0.9, seed=99)
            first = be.submit([1, 7, 23], 16, smp,
                              stop_check=lambda t, seen=[]: (
                                  seen.append(t) or len(seen) >= 6)
                              ).wait(timeout=120)
            second = be.submit([1, 5, 2], 8, smp).wait(timeout=120)
            results[label] = (first, second, int(smp.state))
        assert results["on"] == results["off"], results

        # greedy mid-block stop: deep enough to land mid-super-step, with the
        # successor already in flight -> a "stop" flush must be counted
        full = off.submit([1, 2, 3], 12, _greedy(spec)).wait(timeout=120)
        stop_at = full[5]
        before = _flushes()
        got = on.submit([1, 2, 3], 12, _greedy(spec),
                        stop_check=lambda t: t == stop_at).wait(timeout=120)
        assert got == full[:6]
        assert _flush_delta(before, "stop") >= 1, _flushes()
        # the engine keeps serving, and the slot state survived the flush:
        # the same prompt reuses the prefix and reproduces the full output
        again = on.submit([1, 2, 3], 12, _greedy(spec)).wait(timeout=120)
        assert again == full
    finally:
        on.close()
        off.close()


def test_admission_breaks_the_chain(params):
    """A request arriving while the pipeline is full must break the chain
    (reason "admission"), admit promptly, and both requests must still match
    the unpipelined engine token-for-token."""
    spec, on, off = _engines(params)
    try:
        outs = {}
        flush_delta = None
        for label, be in (("off", off), ("on", on)):
            before = _flushes()
            started = threading.Event()
            seen = []

            def on_token(t):
                # the second token is a scan block's: from there on the
                # pipeline is full (the first one comes from the prompt's
                # last step, behind which nothing is in flight)
                seen.append(t)
                if len(seen) == 2:
                    started.set()

            r1 = be.submit([1, 7, 23, 5], 40, _greedy(spec),
                           on_token=on_token)
            assert started.wait(timeout=120)
            r2 = be.submit([1, 9, 2, 40, 41, 42, 43, 44], 12, _greedy(spec))
            outs[label] = (r1.wait(timeout=120), r2.wait(timeout=120))
            if label == "on":
                flush_delta = _flush_delta(before, "admission")
        assert outs["on"] == outs["off"]
        assert flush_delta and flush_delta >= 1
    finally:
        on.close()
        off.close()


def test_cancel_during_inflight_dispatch(params):
    """cancel() while a chained dispatch is in flight: delivery stops at the
    token boundary, the in-flight speculation is discarded, the slot frees,
    and the engine keeps serving."""
    spec, on, _off = _engines(params)
    _off.close()
    try:
        rollback0 = (metrics.snapshot().get("batch_rollback_tokens_total")
                     or 0.0)
        req_box = []

        def on_token(_t):
            if len(req_box[0].out) == 2:
                req_box[0].cancel()

        req = on.submit([1, 8, 2], 40, _greedy(spec), on_token=on_token)
        req_box.append(req)
        out = req.wait(timeout=120)
        assert req.finish == "cancelled"
        assert len(out) == 2
        rollback1 = (metrics.snapshot().get("batch_rollback_tokens_total")
                     or 0.0)
        assert rollback1 > rollback0  # the speculated tail was discarded
        ok = on.submit([1, 8, 2], 4, _greedy(spec)).wait(timeout=120)
        assert len(ok) == 4
    finally:
        on.close()


# ------------------------------------------------------------- context end


def test_pipeline_context_end_clamp(params):
    """Rows running out of context mid-chain park clamped at seq_len-1; the
    pipelined run must match the unpipelined one and leave slot bounds
    intact (the clamp_pos machinery under speculation)."""
    spec = _spec(seq_len=16)
    params16 = init_random_params(spec, FloatType.Q40, seed=3)
    outs = {}
    for pipeline in (False, True):
        be = BatchEngine(spec, params16, slots=2, tp=1, superstep=8,
                         pipeline=pipeline)
        try:
            req = be.submit([1, 2, 3, 4], 100, _greedy(spec))
            outs[pipeline] = req.wait(timeout=120)
            assert req.finish == "length"
            for slot in be._slots:
                assert slot.pos <= spec.seq_len
                assert len(slot.history) <= spec.seq_len
        finally:
            be.close()
    assert outs[True] == outs[False]


# ------------------------------------------------------- host top-p sampler


def _tie_heavy_probs(rs, n):
    """Distributions with many exactly-equal probabilities — the adversarial
    case for the argpartition boundary (ties straddling the pivot)."""
    logits = np.round(rs.standard_normal(n).astype(np.float32) * 2) / 2
    e = np.exp(logits - logits.max())
    return (e / e.sum()).astype(np.float32)


def test_topp_argpartition_bit_identity():
    """_sample_topp (argpartition selection) must pick the SAME token as the
    full-survivor-sort oracle for every coin, topp, and tie pattern —
    including selections that must widen past the first M."""
    rs = np.random.RandomState(5)
    for n in (300, 4096):
        for topp in (0.05, 0.5, 0.9, 0.97):
            s = Sampler(n, temperature=1.0, topp=topp)
            for trial in range(8):
                probs = (_tie_heavy_probs(rs, n) if trial % 2
                         else rs.dirichlet(np.full(n, 0.05)).astype(np.float32))
                for coin in (0.0, 0.1, 0.5, 0.9, 0.999):
                    a = s._sample_topp(probs, coin)
                    b = s._sample_topp_full(probs, coin)
                    assert a == b, (n, topp, trial, coin, a, b)


def test_topp_widening_path_bit_identity():
    """A near-uniform distribution forces the selection to double past
    _TOPP_SELECT (the first M can't cover topp mass) — the widening loop must
    still be bit-identical with the oracle."""
    n = 2048
    probs = np.full(n, 1.0 / n, np.float32)
    probs[:10] += 1e-5  # tiny tilt so the prefilter keeps everything
    probs /= probs.sum()
    s = Sampler(n, temperature=1.0, topp=0.95)
    assert s._TOPP_SELECT < n
    for coin in (0.01, 0.4, 0.8, 0.99):
        assert s._sample_topp(probs, coin) == s._sample_topp_full(probs, coin)


def test_sampler_end_to_end_identity_old_vs_new():
    """Sampler.sample with the argpartition path must reproduce the exact
    token stream of the full-sort path from the same seed (state evolution
    included — one coin per sample either way)."""
    n = 1024
    rs = np.random.RandomState(9)
    a = Sampler(n, temperature=0.9, topp=0.9, seed=42)
    b = Sampler(n, temperature=0.9, topp=0.9, seed=42)
    b._sample_topp = b._sample_topp_full  # pin the oracle path
    for _ in range(64):
        logits = rs.standard_normal(n).astype(np.float32)
        ta = a.sample(logits)
        tb = b.sample(logits)
        assert ta == tb
    assert int(a.state) == int(b.state)


# ------------------------------------------------------------ stats honesty


def test_overlap_ms_recorded_only_when_pipelined(params):
    """dispatch_ms stays one-entry-per-dispatch; overlap_ms entries appear
    for pipelined super-steps (hidden host time > 0 somewhere) and stay
    all-zero when pipelining is off."""
    spec, on, off = _engines(params)
    try:
        r_off = off.submit([1, 7, 23, 5], 12, _greedy(spec))
        r_off.wait(timeout=120)
        assert all(o == 0.0 for o in r_off.stats.overlap_ms)
        r_on = on.submit([1, 7, 23, 5], 12, _greedy(spec))
        r_on.wait(timeout=120)
        assert len(r_on.stats.overlap_ms) > 0
        assert any(o > 0.0 for o in r_on.stats.overlap_ms), \
            r_on.stats.overlap_ms
        assert len(r_on.stats.dispatch_ms) >= len(r_on.stats.overlap_ms)
    finally:
        on.close()
        off.close()
