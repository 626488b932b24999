"""Overlapped decode pipeline (pipeline_depth > 1): dispatch-ahead must
be invisible in outputs — token- and logprob-identical to the serial
depth-1 scheduler across staggered admissions, stop sequences, cancels
mid-block, and chunked prefill — while the new overlap observability
(inflight_depth, drain_stalls, overlap_hidden) actually records, and a
threaded submit/cancel/close storm neither deadlocks nor drops waiters.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig, generate
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tensorflowonspark_tpu.serving.engine import _PrefixStore


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, model, params


def _reference(model, params, tokens, n):
    out = generate(model, params, jnp.asarray([tokens], jnp.int32), n)
    return np.asarray(out)[0].tolist()


# Mixed seeded traffic: sampled rows (seeded — reproducible), greedy
# riders, per-row truncation knobs, different budgets. Staggered
# arrivals land admissions while earlier rows are mid-decode, which at
# depth>1 forces window drains.
_REQS = [
    dict(tokens=[1, 2, 3], n=9, temperature=0.9, seed=11),
    dict(tokens=[7, 5], n=6),  # greedy
    dict(tokens=[9, 9, 9, 4], n=11, temperature=0.7, top_k=5, seed=3),
    dict(tokens=[3], n=7, temperature=0.8, top_p=0.9, seed=5),
    dict(tokens=[2, 8], n=8),  # greedy
    dict(tokens=[6, 1, 4], n=10, temperature=1.1, seed=42),
]


def _run_traffic(eng, reqs, stagger=0.02):
    results: dict = {}
    errors: dict = {}

    def fire(i):
        r = reqs[i]
        time.sleep(stagger * i)
        try:
            kw = {k: v for k, v in r.items() if k not in ("tokens", "n")}
            results[i] = eng.submit(
                r["tokens"], r["n"], return_logprobs=True, **kw
            )
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors[i] = e

    threads = [
        threading.Thread(target=fire, args=(i,)) for i in range(len(reqs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "traffic thread wedged"
    if errors:
        raise next(iter(errors.values()))
    return [results[i] for i in range(len(reqs))]


def test_pipeline_depth_parity_seeded(tiny):
    """depth 2 and 3 vs depth 1 on identical seeded traffic: tokens AND
    logprobs exactly equal — the device computation chain is the same
    regardless of when the host fetches it."""
    cfg, model, params = tiny
    outs = {}
    for depth in (1, 2, 3):
        eng = ContinuousBatcher(
            model, params, slots=2, prompt_widths=(8,),
            decode_block=4, pipeline_depth=depth,
        )
        try:
            outs[depth] = _run_traffic(eng, _REQS)
            st = eng.stats()
            assert st["pipeline_depth"] == depth
            if depth > 1:
                # An admission under a live window forces a drain. Not
                # read off the traffic above: its threads stagger by the
                # clock, and on a busy host they oversleep until every
                # request meets an idle engine. Here the second request
                # is sent on an event, the first token of a stream that
                # has a hundred steps to go.
                first = eng.stream([5, 6], 100, eos_id=-1)
                next(first)
                eng.submit([1, 2], 4)
                assert sum(1 for _ in first) == 99
                assert eng.stats()["drain_stalls"] > st["drain_stalls"]
        finally:
            eng.close()
    assert outs[2] == outs[1]
    assert outs[3] == outs[1]


def test_pipeline_stop_sequence_parity(tiny):
    """A stop sequence completing mid-block trims identically at every
    depth (the retire point is a host decision replayed on the same
    token stream)."""
    cfg, model, params = tiny
    base = _reference(model, params, [1, 2, 3], 12)
    j = next(i for i in range(1, 7) if base[i] not in base[:i])
    outs = {}
    for depth in (1, 2):
        eng = ContinuousBatcher(
            model, params, slots=2, prompt_widths=(8,),
            decode_block=4, pipeline_depth=depth,
        )
        try:
            outs[depth] = [
                eng.submit([1, 2, 3], 12, stop=[[base[j]]]),
                # multi-token stop, concurrent greedy rider
                eng.submit([1, 2, 3], 12, stop=[base[j - 1 : j + 1]]),
            ]
        finally:
            eng.close()
    assert outs[2] == outs[1]
    assert outs[1][0] == base[:j]


def test_pipeline_chunked_prefill_parity(tiny):
    """Chunked prefill (+ prefix cache) under the overlapped pipeline:
    the final-chunk admit drains the window and the first token defers
    into the fetch path — outputs must still match depth 1 exactly."""
    cfg, model, params = tiny
    reqs = [
        dict(tokens=list(range(1, 11)), n=6, temperature=0.9, seed=2),
        dict(tokens=list(range(1, 8)), n=5),
        # shares a prefix with the first — exercises the bucketed store
        dict(tokens=list(range(1, 11)) + [3, 4], n=6),
    ]
    outs = {}
    for depth in (1, 2):
        eng = ContinuousBatcher(
            model, params, slots=2, prompt_widths=(16,),
            decode_block=4, pipeline_depth=depth,
            prefill_chunk=4, prefix_cache=4,
        )
        try:
            outs[depth] = _run_traffic(eng, reqs)
            assert eng._prefix_store.hits >= 1
        finally:
            eng.close()
    assert outs[2] == outs[1]


def test_pipeline_cancel_mid_block_isolated(tiny):
    """Closing a stream mid-decode at depth 2 cancels within the
    bounded k*depth window, never corrupts a concurrent request, and
    the consumed prefix matches the serial engine's stream."""
    cfg, model, params = tiny
    want = _reference(model, params, [9, 4], 10)
    prefixes = {}
    for depth in (1, 2):
        eng = ContinuousBatcher(
            model, params, slots=2, prompt_widths=(8,),
            decode_block=4, pipeline_depth=depth,
        )
        try:
            stream = eng.stream([1, 2, 3], 64)
            got = [next(stream) for _ in range(3)]
            stream.close()  # cancel with ~61 tokens of budget left
            # the concurrent request is unaffected by the cancel
            assert eng.submit([9, 4], 10) == want
            prefixes[depth] = got
            deadline = time.time() + 120
            while (
                eng.stats()["cancelled"] < 1 and time.time() < deadline
            ):
                time.sleep(0.05)
            st = eng.stats()
            assert st["cancelled"] == 1
            # the cancelled row retired long before its budget: the
            # bounded discard means total decoded tokens stay far
            # under the 64-token budget it abandoned
            assert st["tokens_emitted"] < 40
        finally:
            eng.close()
    assert prefixes[2] == prefixes[1]


def test_pipeline_stats_and_metrics_surfaces(tiny):
    """The overlap pipeline's observability: /stats fields and the
    Prometheus registry series exist and move."""
    cfg, model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,),
        decode_block=4, pipeline_depth=2,
    )
    try:
        holder = threading.Thread(target=lambda: eng.submit([1, 2], 40))
        holder.start()
        deadline = time.time() + 60
        while eng.stats()["slots_busy"] < 1 and time.time() < deadline:
            time.sleep(0.02)
        time.sleep(0.2)  # let the window fill mid-decode
        eng.submit([3], 2)  # admission under a live window -> drain
        holder.join(timeout=120)
        assert not holder.is_alive()
        st = eng.stats()
        assert st["pipeline_depth"] == 2
        assert st["drain_stalls"] >= 1
        assert st["inflight_depth"] >= 0
        assert st["overlap_hidden_ms"] >= 0.0
        assert "sweep" in st["phase_ms"]
        text = eng.metrics.render()
        for series in (
            "engine_inflight_depth",
            "engine_drain_stalls_total",
            "engine_overlap_hidden_seconds",
        ):
            assert series in text, series
    finally:
        eng.close()


def test_prefix_store_bucketed_lookup():
    """The adapter-bucketed, length-indexed prefix store: longest match
    wins via per-length hashing, adapters are isolated, eviction and
    clear keep the index consistent."""
    s = _PrefixStore(capacity=3)
    s.insert([1, 2], "c12")
    s.insert([1, 2, 3, 4], "c1234")
    s.insert([1, 2], "ad1", adapter=1)
    # longest stored prefix wins (not the shorter [1,2])
    cache, resume = s.lookup([1, 2, 3, 4, 5])
    assert (cache, resume) == ("c1234", 4)
    # exact-length match is capped at len-1 so the last token recomputes
    cache, resume = s.lookup([1, 2, 3, 4])
    assert (cache, resume) == ("c1234", 3)
    # adapter isolation: adapter 1 only sees its own entry
    cache, resume = s.lookup([1, 2, 3, 4, 5], adapter=1)
    assert (cache, resume) == ("ad1", 2)
    assert s.lookup([9, 9, 9]) == (None, 0)
    assert s.hits == 3 and s.misses == 1
    # eviction (capacity 3): inserting a 4th evicts the LRU ([1,2] was
    # never looked up as best — it was refreshed least recently)
    s.insert([7, 8, 9], "c789")
    assert len(s) == 3
    assert s.lookup([1, 2, 9]) == (None, 0)  # [1,2] evicted + unindexed
    cache, resume = s.lookup([7, 8, 9, 1])
    assert (cache, resume) == ("c789", 3)
    s.clear()
    assert len(s) == 0 and not s._by_adapter
    assert s.lookup([1, 2, 3]) == (None, 0)


@pytest.mark.slow
def test_pipeline_stress_submit_cancel_close(tiny):
    """Threaded storm: concurrent submits, streams with early close,
    and a drain shutdown. Fails on deadlock (join timeouts) or dropped
    waiters (every accepted request must resolve)."""
    cfg, model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=3, prompt_widths=(8,),
        decode_block=4, pipeline_depth=2,
    )
    n_threads, per_thread = 6, 4
    resolved = []
    errors = []
    lock = threading.Lock()

    def worker(w):
        for r in range(per_thread):
            try:
                if (w + r) % 3 == 2:
                    stream = eng.stream([w + 1, r + 1], 12)
                    # consume a couple of tokens, then abandon
                    for _, _tok in zip(range(2), stream):
                        pass
                    stream.close()
                    with lock:
                        resolved.append(("cancel", w, r))
                else:
                    out = eng.submit(
                        [w + 1, r + 1], 4 + (w + r) % 5,
                        temperature=0.5 * ((w + r) % 2), seed=w * 10 + r,
                    )
                    assert out, "empty completion"
                    with lock:
                        resolved.append(("done", w, r))
            except BaseException as e:  # noqa: BLE001
                with lock:
                    errors.append((w, r, e))

    threads = [
        threading.Thread(target=worker, args=(w,))
        for w in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "worker deadlocked"
    assert not errors, errors
    assert len(resolved) == n_threads * per_thread
    eng.close(drain=True, drain_timeout=120)
    st = eng.stats()
    # drain accounting closed: everything accepted either completed or
    # failed; nothing is left parked in a slot or the queue
    assert st["slots_busy"] == 0
    assert st["queue_depth"] == 0
    assert eng._accepted_total == eng.completed + eng._failed_total
