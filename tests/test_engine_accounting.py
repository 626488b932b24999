"""The engine's account of its own admission cycle: what prefill had to
process against what it computed, how many of the dispatched slot-steps
were live, what a forced drain cost, what warm-up took. Counts are held
to what the requests themselves say, exactly; spans to each other, on
the tracer's one clock.
"""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import trace_reduce
from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig
from tensorflowonspark_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def tiny_hybrid():
    model = FalconH1(FalconH1Config.tiny(dtype=jnp.float32))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _counter(eng, name):
    return eng.metrics.counter(name).value()


def _phase_series(eng, phase):
    series = eng.metrics.window()["engine_request_phase_seconds"]["series"]
    return series.get('{phase="%s"}' % phase)


_A = [5, 6, 7, 8, 9, 10, 11, 12, 13]

# kind -> (engine options, prompts, prompt tokens processed, positions computed)
_PREFILL_CASES = {
    "plain": (
        dict(prompt_widths=(4, 8, 16)),
        [[1], [1, 2, 3, 4], [3] * 5, [4] * 8, [5] * 9, [6] * 16],
        1 + 4 + 5 + 8 + 9 + 16,
        4 + 4 + 8 + 8 + 16 + 16,
    ),
    "chunked": (
        dict(prompt_widths=(16,), prefill_chunk=4),
        [[1], [1, 2, 3, 4], [3] * 5, [4] * 8, [5] * 9, [6] * 16],
        1 + 4 + 5 + 8 + 9 + 16,
        4 * (1 + 1 + 2 + 2 + 3 + 4),
    ),
    # the second request finds all of _A stored and resumes at its last
    # token; the third resumes after _A: the suffix only is counted, in
    # one chunk each where the first took three
    "prefix_hit": (
        dict(prompt_widths=(16,), prefill_chunk=4, prefix_cache=8),
        [_A, _A, _A + [40, 41]],
        9 + 1 + 2,
        4 * (3 + 1 + 1),
    ),
    # a final chunk that would run past max_seq_len is shifted back and
    # recomputes positions it had already: they count as positions, not
    # as tokens
    "shifted_back": (
        dict(prompt_widths=(128,), prefill_chunk=48),
        [[7] * 100],
        100,
        48 * 3,
    ),
}


@pytest.mark.parametrize(
    "kind,family",
    [(k, "llama") for k in sorted(_PREFILL_CASES)]
    # the same counts whatever the model; a model that carries recurrent
    # state refuses the prefix store
    + [(k, "hybrid") for k in sorted(_PREFILL_CASES) if k != "prefix_hit"],
)
def test_prefill_tokens_and_positions_are_exact(
    tiny, tiny_hybrid, kind, family
):
    model, params = tiny if family == "llama" else tiny_hybrid
    options, prompts, tokens, positions = _PREFILL_CASES[kind]
    eng = ContinuousBatcher(model, params, slots=2, **options)
    try:
        for p in prompts:  # one at a time: the store is read in this order
            eng.submit(p, 2, eos_id=-1)
        assert _counter(eng, "engine_prefill_tokens_total") == tokens
        assert _counter(eng, "engine_prefill_positions_total") == positions
        saved = eng.stats().get("prefix_tokens_saved", 0)
        assert tokens == sum(map(len, prompts)) - saved
        # the engine.prefill spans say the same: the width computed and
        # how much of it was the prompt's own tokens
        spans = [s for s in eng._tracer.spans() if s.name == "engine.prefill"]
        assert sum(s.args["valid"] for s in spans) == tokens
        assert sum(s.args["width"] for s in spans) == positions
    finally:
        eng.close()


def test_new_counters_read_zero_before_any_work(tiny):
    """A window with no fallback step reads 0 %, a true count: the
    unlabelled series exist from construction."""
    model, params = tiny
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
    try:
        snap = eng.metrics.window()
        for name in (
            "engine_prefill_tokens_total",
            "engine_prefill_positions_total",
            "engine_slot_steps_live_total",
            "engine_decode_fallback_steps_total",
        ):
            assert snap[name]["series"][""] == {"value": 0.0, "delta": 0.0}
        # unlabelled as before: slot_occupancy_pct.serve reads this series
        assert set(snap["engine_decode_steps_total"]["series"]) <= {""}
    finally:
        eng.close()


def _churn(eng, n_clients=3, rounds=4):
    """More clients than slots, each sending again when its last request
    ends: admissions land under live windows and queue behind full
    slots."""
    errors = []

    def client(i):
        try:
            for r in range(rounds):
                eng.submit([1 + i, 2 + r, 3], 5 + 3 * ((i + r) % 3), eos_id=-1)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "client wedged"
    if errors:
        raise errors[0]


def test_live_slot_steps_lie_between_emitted_and_dispatched(tiny):
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=2,
    )
    try:
        _churn(eng)
        emitted = _counter(eng, "engine_tokens_emitted_total")
        completed = _counter(eng, "engine_requests_completed_total")
        live = _counter(eng, "engine_slot_steps_live_total")
        steps = _counter(eng, "engine_decode_steps_total")
        fallback = _counter(eng, "engine_decode_fallback_steps_total")
        assert completed == 12 and steps == eng.steps
        # each request's first token comes from its prefill; every other
        # emitted token was a live slot-step, and some live ones were
        # computed past a row's end and thrown away
        assert emitted - completed <= live <= steps * 2
        assert 0 <= fallback <= steps
    finally:
        eng.close()


def test_fallback_steps_count_blocks_shorter_than_decode_block(tiny):
    """warmup() compiles the single-step program through a request
    pinned to k=1: every step of that request is a fallback step, and a
    lone request on an idle engine runs in whole blocks."""
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4
    )
    try:
        eng.submit([1, 2, 3], 9, eos_id=-1)
        assert _counter(eng, "engine_decode_fallback_steps_total") == 0
        assert _counter(eng, "engine_decode_steps_total") % 4 == 0
        p = eng._enqueue([0], 3, eos_id=-1, decode_block_pin=1)
        assert p.event.wait(120) and p.error is None
        fallback = _counter(eng, "engine_decode_fallback_steps_total")
        assert 2 <= fallback <= 2 * eng.stats()["pipeline_depth"] + 2
        assert _counter(eng, "engine_decode_steps_total") % 4 == fallback % 4
    finally:
        eng.close()


def test_drain_phase_counts_the_stalls_and_holds_its_fetches(tiny):
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=2,
    )
    # stamp every fetch a drain makes, on the tracer's clock
    in_drain = []
    drain_fetches = []
    drain_window, fetch_packed = eng._drain_window, eng._fetch_packed

    def stamped_drain(reason):
        in_drain.append(reason)
        try:
            drain_window(reason)
        finally:
            in_drain.pop()

    def stamped_fetch(packed):
        t0 = time.perf_counter()
        host = fetch_packed(packed)
        if in_drain:
            drain_fetches.append((t0, time.perf_counter()))
        return host

    eng._drain_window, eng._fetch_packed = stamped_drain, stamped_fetch
    try:
        holder = threading.Thread(
            target=lambda: eng.submit([1, 2], 100, eos_id=-1)
        )
        holder.start()
        deadline = time.time() + 60
        while eng.stats()["slots_busy"] < 1 and time.time() < deadline:
            time.sleep(0.02)
        time.sleep(0.2)  # let the window fill mid-decode
        eng.submit([3], 2)  # admission under a live window -> drain
        holder.join(timeout=120)
        assert not holder.is_alive()
        stalls = eng.stats()["drain_stalls"]
        assert stalls >= 1
        drains = [s for s in eng._tracer.spans() if s.name == "engine.drain"]
        series = _phase_series(eng, "drain")
        assert series["count"] == stalls == len(drains)
        assert {s.args["reason"] for s in drains} == {"admit"}
        # ring and histogram describe the same intervals on one clock
        assert series["sum"] == pytest.approx(sum(s.dur for s in drains), rel=1e-9)
        assert len(drain_fetches) >= stalls
        for t0, t1 in drain_fetches:
            assert any(s.ts <= t0 and t1 <= s.ts + s.dur for s in drains)
        # the steady-state phases nest inside and keep their own names
        steady = [
            x for x in eng._tracer.spans()
            if x.name in ("engine.fetch", "engine.sweep")
        ]
        for s in drains:
            inner = [
                x for x in steady
                if s.ts <= x.ts and x.ts + x.dur <= s.ts + s.dur
            ]
            assert {x.name for x in inner} == {"engine.fetch", "engine.sweep"}
            assert sum(x.dur for x in inner) <= s.dur
        assert eng.stats()["phase_ms"]["drain"]["count"] == stalls
    finally:
        eng.close()


def test_nested_phase_gives_the_watchdog_the_outer_name_back(tiny, caplog):
    model, params = tiny
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
    try:
        # an idle scheduler blocks on its queue and enters no phase
        assert eng._current_phase is None
        with eng._phase("drain", reason="admit"):
            with eng._phase("fetch"):
                assert eng._current_phase == "fetch"
            assert eng._current_phase == "drain"
            with caplog.at_level(logging.ERROR):
                eng._watchdog_fire(1.0)
        assert eng._current_phase is None
        assert "stuck in drain" in caplog.text
        assert _phase_series(eng, "drain")["count"] == 1
        assert _phase_series(eng, "fetch")["count"] == 1
        # nothing was in flight: the loop clears the flag and serves on
        eng._queue.put(eng._WAKE)
        deadline = time.time() + 30
        while eng._watchdog_abort.is_set() and time.time() < deadline:
            time.sleep(0.01)
        assert len(eng.submit([1, 2, 3], 3, eos_id=-1)) == 3
    finally:
        eng.close()


def test_warmup_is_one_span_and_one_observation(tiny):
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(4, 8), decode_block=4
    )
    try:
        assert "" not in eng.metrics.window()["engine_warmup_seconds"]["series"]
        t0 = time.perf_counter()
        eng.warmup()
        wall = time.perf_counter() - t0
        s = eng.metrics.window()["engine_warmup_seconds"]["series"][""]
        spans = [x for x in eng._tracer.spans() if x.name == "engine.warmup"]
        assert s["count"] == 1 == len(spans)
        assert s["sum"] == spans[0].dur and 0 < s["sum"] <= wall
        # warm-up's own requests are accounted like any others
        assert _counter(eng, "engine_prefill_positions_total") == 4 + 8 + 4
    finally:
        eng.close()


# -- the completion clock and the discarded slot-steps ----------------------

_SECONDS = (
    "engine_device_decode_seconds_total",
    "engine_device_prefill_seconds_total",
    "engine_device_starved_seconds_total",
)


def _identity(eng):
    """Both sides of the slot-step identity, read once the scheduler has
    stopped (close() joins it: nothing is mid-sweep)."""
    eng.close()
    live = _counter(eng, "engine_slot_steps_live_total")
    emitted = _counter(eng, "engine_tokens_emitted_total")
    completed = _counter(eng, "engine_requests_completed_total")
    discarded = _counter(eng, "engine_slot_steps_discarded_total")
    return live, (emitted - completed) + discarded, discarded


@pytest.mark.parametrize("depth", [1, 2])
def test_slot_step_identity_is_exact_under_churn(tiny, depth):
    """Every live slot-step either gave a request a token or is counted
    as discarded: rows retire in the middle of blocks of four, with one
    and with two blocks in flight."""
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=depth,
    )
    try:
        _churn(eng)  # budgets 5, 8, 11: none ends on a block's edge
        live, accounted, discarded = _identity(eng)
        assert _counter(eng, "engine_requests_completed_total") == 12
        assert live == accounted
        # a budget of 5 is a first token and one block: nothing of that
        # block is thrown away, but the blocks behind it are
        assert 0 < discarded < live
    finally:
        eng.close()


def test_slot_step_identity_holds_through_a_dropped_window(tiny):
    """A lone request retires with a block still in flight behind it:
    the loop drops that block unfetched, and its steps are discards."""
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=2,
    )
    dropped = []
    drop_window = eng._drop_window

    def counting_drop():
        dropped.append(sum(k * rows for k, _, rows in eng._window))
        drop_window()

    eng._drop_window = counting_drop
    try:
        for budget in (6, 3, 9):
            assert len(eng.submit([1, 2, 3], budget, eos_id=-1)) == budget
        live, accounted, discarded = _identity(eng)
        assert sum(dropped) > 0, "no window was dropped: the case is not covered"
        assert live == accounted
        assert discarded >= sum(dropped)
    finally:
        eng.close()


def test_clock_and_discard_series_read_zero_at_construction(tiny):
    model, params = tiny
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
    try:
        snap = eng.metrics.window()
        for name in _SECONDS + ("engine_slot_steps_discarded_total",):
            assert snap[name]["series"][""] == {"value": 0.0, "delta": 0.0}
        # a histogram's series begins with its first observation, as
        # engine_warmup_seconds': the intervals reach 30 s
        gap = snap["engine_completion_gap_seconds"]
        assert gap["series"] == {}
        assert gap["kind"] == "histogram" and eng._m_gap.buckets[-1] == 30.0
    finally:
        eng.close()


def _seconds(eng):
    return sum(_counter(eng, name) for name in _SECONDS)


@pytest.mark.parametrize("depth", [1, 2])
def test_clock_seconds_sum_to_the_gaps_and_to_the_busy_wall_time(tiny, depth):
    """Decode, prefill and starved seconds are one partition of the
    intervals between awaited completions: they sum to the histogram's
    sum, and over each stretch in which the engine held work to the wall
    time from its first launch to its last completion."""
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=depth,
    )
    stretches = []  # [first launch, last completion] while the clock ran
    launched, completed = eng._clock_launched, eng._clock_completed

    def stamped_launch():
        starts = eng._clock_at is None
        launched()
        if starts:
            stretches.append([eng._clock_at, eng._clock_at])

    def stamped_completion(ended_by, in_flight):
        completed(ended_by, in_flight)
        stretches[-1][1] = eng._clock_at

    eng._clock_launched, eng._clock_completed = stamped_launch, stamped_completion
    try:
        _churn(eng)
        eng.close()
        gap = eng.metrics.window()["engine_completion_gap_seconds"]["series"][""]
        total = _seconds(eng)
        assert total == pytest.approx(gap["sum"], rel=1e-9)
        assert all(_counter(eng, name) >= 0 for name in _SECONDS)
        assert _counter(eng, "engine_device_decode_seconds_total") > 0
        assert _counter(eng, "engine_device_prefill_seconds_total") > 0
        if depth == 1:
            # one block at a time: after every fetch the chip has
            # nothing until the next dispatch returns
            assert _counter(eng, "engine_device_starved_seconds_total") > 0
        busy = sum(b - a for a, b in stretches)
        assert total == pytest.approx(busy, rel=0.02)
        # a completion a block's fetch and a first token each
        fetches = _phase_series(eng, "fetch")["count"]
        assert gap["count"] == fetches + eng.admitted
    finally:
        eng.close()


def test_nothing_accrues_while_the_engine_stands_empty(tiny):
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4
    )
    try:
        eng.submit([1, 2, 3], 6, eos_id=-1)  # compiles
        before = _seconds(eng)
        walls = []
        for pause in (1.0, 0.0):
            t0 = time.perf_counter()
            eng.submit([1, 2, 3], 6, eos_id=-1)
            walls.append(time.perf_counter() - t0)
            time.sleep(pause)
        # every interval lies inside the call that held the engine busy
        assert 0 < _seconds(eng) - before <= sum(walls)
        gap = eng.metrics.window()["engine_completion_gap_seconds"]["series"][""]
        assert gap["sum"] == pytest.approx(_seconds(eng), rel=1e-9)
    finally:
        eng.close()


@pytest.mark.parametrize("depth", [1, 2])
def test_first_token_spans_are_the_resolution_passes(tiny, depth):
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4,
        pipeline_depth=depth,
    )
    passes = []
    resolve = eng._resolve_first_tokens

    def counted_resolve():
        if eng._pending_first:
            passes.append(len(eng._pending_first))
        resolve()

    eng._resolve_first_tokens = counted_resolve
    try:
        _churn(eng)
        eng.close()
        spans = [s for s in eng._tracer.spans() if s.name == "engine.first_token"]
        assert [s.args["rows"] for s in spans] == passes
        assert sum(passes) == eng.admitted == 12
        series = _phase_series(eng, "first_token")
        assert series["count"] == len(passes)
        assert series["sum"] == pytest.approx(sum(s.dur for s in spans), rel=1e-9)
    finally:
        eng.close()


@pytest.mark.parametrize(
    "options", [dict(prompt_widths=(8, 16)), dict(prompt_widths=(16,), prefill_chunk=4)],
    ids=["plain", "chunked"],
)
def test_prefill_stage_and_launch_lie_inside_a_prefill(tiny, options):
    model, params = tiny
    eng = ContinuousBatcher(model, params, slots=2, decode_block=4, **options)
    try:
        for prompt in ([1, 2, 3], [4] * 9, [5] * 16):
            eng.submit(prompt, 4, eos_id=-1)
        eng.close()
        spans = eng._tracer.spans()
        prefills = [s for s in spans if s.name == "engine.prefill"]
        assert prefills
        inner = {name: [s for s in spans if s.name == "engine." + name]
                 for name in ("prefill_stage", "prefill_launch")}
        for name, children in inner.items():
            assert _phase_series(eng, name)["count"] == len(children) > 0
            for c in children:
                assert any(
                    s.ts <= c.ts and c.ts + c.dur <= s.ts + s.dur for s in prefills
                ), name
        for s in prefills:
            held = {
                name: [c for c in children
                       if s.ts <= c.ts and c.ts + c.dur <= s.ts + s.dur]
                for name, children in inner.items()
            }
            # one staging a program; a final chunk launches its sample
            # and admit in a second launch
            assert len(held["prefill_stage"]) == 1
            assert 1 <= len(held["prefill_launch"]) <= 2
            assert sum(c.dur for cs in held.values() for c in cs) <= s.dur
        if "prefill_chunk" not in options:
            assert len(inner["prefill_launch"]) == len(prefills) == 3
    finally:
        eng.close()


@pytest.mark.parametrize("depth", [1, 2])
def test_phases_cover_the_scheduler_while_it_holds_work(tiny, depth):
    """dispatch, fetch, sweep, drain, prefill (with its two children)
    and first_token leave none of the scheduler's waits unnamed: their
    self times sum to the loop's wall time while it holds work, less
    the bookkeeping between them."""
    model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=16,
        pipeline_depth=depth,
    )
    try:
        eng.submit([1, 2, 3], 6, eos_id=-1)  # compiles, outside the reading
        eng._tracer.clear()
        # one burst, never empty until its last request ends; blocks of
        # sixteen steps, so that the loop mostly waits for the device as
        # it does on a chip (97 % is covered here; the rest is the
        # bookkeeping between phases, microseconds a turn of the loop)
        threads = [
            threading.Thread(target=eng.submit, args=([1 + i, 2, 3], 40 + 23 * i),
                             kwargs=dict(eos_id=-1))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        eng.close()
        spans = [s for s in eng._tracer.spans() if s.tid == eng._thread.ident]
        # with one block at a time an admission never finds a window
        named = {"dispatch", "fetch", "sweep", "prefill", "prefill_stage",
                 "prefill_launch", "first_token"} | ({"drain"} if depth > 1 else set())
        assert {s.name for s in spans} == {"engine." + p for p in named}
        # nesting-aware, as the benchmark's reducer reads a timeline
        own = trace_reduce.self_times([(s.name, s.ts, s.dur) for s in spans])
        wall = max(s.ts + s.dur for s in spans) - min(s.ts for s in spans)
        covered = sum(own.values())
        assert covered <= wall * (1 + 1e-9)
        assert covered >= 0.9 * wall, (covered, wall)
    finally:
        eng.close()
