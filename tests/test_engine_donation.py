"""The engine's batch state exists once: the decode block and the admit
program take it by donation, so the compiled programs alias every batch
cache leaf from input to output and the buffers handed in are gone
after the call. What is shared stays whole: params (hot swap, replicas)
and the single-row cache (a prefix entry others resume from). A fault
between donated calls still fails requests with its own error.
"""

import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig, generate
from tensorflowonspark_tpu.serving import ContinuousBatcher
from tensorflowonspark_tpu.serving.engine import _BIAS_SLOTS
from tensorflowonspark_tpu.utils import failpoints as fp
from tensorflowonspark_tpu.utils.failpoints import FailpointError

# kind -> (LlamaConfig.tiny overrides, whether the engine gets a TP mesh)
_KINDS = {
    "dense": ({}, False),
    # the scale planes are leaves of the same tree
    "int8_kv": (dict(kv_cache_dtype="int8"), False),
    # segment and position planes likewise
    "rolling": (dict(sliding_window=8, kv_cache_len=16), False),
    # constrain returns the sharding it was given: input and output alias
    "tp_mesh": ({}, True),
    # recurrent state and the convolution's window beside K/V: leaves of
    # the same tree, row first like the planes (cache/ssm, cache/conv)
    "hybrid": (None, False),
}


@pytest.fixture(scope="module", params=list(_KINDS))
def built(request):
    """An engine for one kind of cache. The tests drive its compiled
    programs directly; its loop stays idle."""
    overrides, tp = _KINDS[request.param]
    if overrides is None:
        model = FalconH1(FalconH1Config.tiny(dtype=jnp.float32))
    else:
        model = Llama(
            LlamaConfig.tiny(dtype=jnp.float32, remat=False, **overrides)
        )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    mesh = None
    if tp:
        from tensorflowonspark_tpu.compute.mesh import make_mesh

        mesh = make_mesh({"data": 4, "model": 2})
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=8,
        mesh=mesh,
    )
    yield eng
    eng.close()


def _prefill_row(eng, tokens):
    """One prompt through the engine's own prefill program: the *_1 side
    of an admission, as ``_admit_one`` builds it."""
    prompt = np.zeros((1, 8), np.int32)
    prompt[0, : len(tokens)] = tokens
    temp_1 = jnp.zeros((1,), jnp.float32)
    ad_1 = jnp.zeros((1,), jnp.int32)
    kp_1 = jnp.asarray(
        [[float(eng._model.cfg.vocab_size), 1.0, 0.0]], jnp.float32
    )
    seed_1 = jnp.zeros((1,), jnp.uint32)
    bid_1 = jnp.full((1, _BIAS_SLOTS), -1, jnp.int32)
    bval_1 = jnp.zeros((1, _BIAS_SLOTS), jnp.float32)
    cache_1, tok_1, pos_1, _lp = eng._prefill_fn(8)(
        eng._params, jnp.asarray(prompt),
        jnp.asarray([len(tokens)], jnp.int32), temp_1, ad_1, kp_1,
        seed_1, bid_1, bval_1,
    )
    pen_1 = jnp.zeros((1, 2), jnp.float32)
    return dict(
        cache_1=cache_1, tok_1=tok_1, pos_1=pos_1, temp_1=temp_1,
        ad_1=ad_1, kp_1=kp_1, seed_1=seed_1, pen_1=pen_1, bid_1=bid_1,
        bval_1=bval_1,
    )


def _admit_args(state, one, row=0):
    (
        cache, tok, pos, temps, ads, kps, seeds, pens, counts, bids,
        bvals,
    ) = state
    return (
        cache, one["cache_1"], jnp.int32(row), tok, one["tok_1"], pos,
        one["pos_1"], temps, one["temp_1"], ads, one["ad_1"], kps,
        one["kp_1"], seeds, one["seed_1"], pens, one["pen_1"], counts,
        bids, one["bid_1"], bvals, one["bval_1"],
    )


def _block_args(eng, state):
    (
        cache, tok, pos, temps, ads, kps, seeds, pens, counts, bids,
        bvals,
    ) = state
    return (
        eng._params, cache, tok, pos, temps, ads, kps, seeds, pens,
        counts, bids, bvals, eng._step_gates(),
    )


def _running_state(eng, one, row=0):
    """The batch state as a running loop holds it: through one admission
    and one block, so that every leaf carries the placement the programs
    themselves give it (under a mesh the compiler shards the block's
    ``counts`` result, which an ``_empty_state`` leaf is not)."""
    state = eng._admit_fn(*_admit_args(eng._empty_state(), one, row))
    cache, tok, pos, _packed, counts = eng._block_fn(8)(
        *_block_args(eng, state)
    )
    return (cache, tok, pos, *state[3:8], counts, *state[9:])


_BLOCK_ARGS = (
    "params", "cache", "tok", "pos", "temps", "ads", "kps", "seeds",
    "pens", "counts", "bias_ids", "bias_vals", "gates",
)
_ADMIT_ARGS = (
    "cache_b", "cache_1", "row", "tok_b", "tok_1", "pos_b", "pos_1",
    "temps_b", "temp_1", "ads_b", "ad_1", "kps_b", "kp_1", "seeds_b",
    "seed_1", "pens_b", "pen_1", "counts_b", "bids_b", "bid_1",
    "bvals_b", "bval_1",
)


def _aliased_inputs(compiled, args):
    """For each argument name, (leaves the executable may write an
    output into, leaves it takes): the first from the compiled module's
    own header (``input_output_alias={ {out}: (parameter, {}, may-alias),
    ...}``), the parameters matched to the flattened arguments in order,
    less those jit pruned as unused."""
    txt = compiled.as_text()
    aliased = {
        int(n)
        for n in re.findall(
            r"\{[\d, ]*\}: \((\d+), \{[\d, ]*\}, (?:may|must)-alias\)",
            txt.split("\n", 1)[0],
        )
    }
    entry = txt[txt.index("\nENTRY "):]
    n_params = len(set(re.findall(r" parameter\((\d+)\)", entry)))
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    kept = sorted(
        getattr(compiled._executable, "_kept_var_idx", range(len(flat)))
    )
    assert len(kept) == n_params
    out = {}
    for number, i in enumerate(kept):
        took = out.setdefault(flat[i][0][0].idx, [0, 0])
        took[0] += number in aliased
        took[1] += 1
    return out


@pytest.mark.parametrize("program", ["block8", "block1", "admit"])
def test_compiled_program_aliases_the_batch_state(built, program):
    """From the executable: every leaf of the batch cache (and the other
    carried state the program returns) is an input-output alias; params
    and the single-row cache are not."""
    eng = built
    one = _prefill_row(eng, [1, 2, 3])
    state = _running_state(eng, one)
    n_cache = len(jax.tree_util.tree_leaves(state[0]))
    if program == "admit":
        args, names = _admit_args(state, one), _ADMIT_ARGS
        fn = eng._admit_fn
    else:
        args, names = _block_args(eng, state), _BLOCK_ARGS
        fn = eng._block_fn(int(program[len("block"):]))
    by_arg = _aliased_inputs(fn.lower(*args).compile(), args)
    got = {names[i]: tuple(v) for i, v in by_arg.items()}
    cache = "cache_b" if program == "admit" else "cache"
    assert got[cache] == (n_cache, n_cache)
    if program == "admit":
        for name, (aliased, took) in got.items():
            # every *_b is taken whole; no *_1, and not the row index
            assert aliased == (took if name.endswith("_b") else 0), name
        assert got["cache_1"][1] > 0
        return
    assert got["params"] == (0, len(jax.tree_util.tree_leaves(eng._params)))
    for name, (aliased, took) in got.items():
        # the per-row knobs are read again by the next block
        donated = name in ("cache", "tok", "pos", "counts")
        assert aliased == (took if donated else 0), name
    assert {"tok", "pos", "counts"} <= set(got)


def test_calls_consume_the_batch_state_and_nothing_shared(built):
    """One admit, then one block: the batch state handed in is deleted
    leaf for leaf, the results are alive, and params, the single-row
    cache and the first token (which waits in ``_pending_first``) are
    untouched."""
    eng = built
    one = _prefill_row(eng, [4, 5])
    empty = eng._empty_state()
    first = eng._admit_fn(*_admit_args(empty, one, row=1))
    for leaf in jax.tree_util.tree_leaves(empty):
        assert leaf.is_deleted()
    for leaf in jax.tree_util.tree_leaves((first, one, eng._params)):
        assert not leaf.is_deleted()
    held = _running_state(eng, one, row=1)
    state = eng._admit_fn(*_admit_args(held, one, row=1))
    for leaf in jax.tree_util.tree_leaves(held):
        assert leaf.is_deleted()
    for leaf in jax.tree_util.tree_leaves((state, one, eng._params)):
        assert not leaf.is_deleted()

    cache, tok, pos, packed, counts = eng._block_fn(8)(
        *_block_args(eng, state)
    )
    taken = (state[0], state[1], state[2], state[8])
    for leaf in jax.tree_util.tree_leaves(taken):
        assert leaf.is_deleted()
    kept = (state[3:8], state[9:], one, eng._params)
    for leaf in jax.tree_util.tree_leaves(kept):
        assert not leaf.is_deleted()
    for leaf in jax.tree_util.tree_leaves((cache, tok, pos, counts)):
        assert not leaf.is_deleted()
    assert np.asarray(packed).shape == (2, 8, 2)
    # a second admission of the same single-row cache (a prefix entry
    # serves many requests) into the state the block returned
    again = eng._admit_fn(
        *_admit_args(
            (cache, tok, pos, *state[3:8], counts, *state[9:]), one,
            row=0,
        )
    )
    planes = {
        jax.tree_util.keystr(path): np.asarray(x)
        for path, x in jax.tree_util.tree_leaves_with_path(again[0])
        if x.ndim >= 3  # K/V and scale planes, recurrent state, window
    }
    for name, plane in planes.items():
        assert plane[0].any() and plane[1].any(), name
    if isinstance(eng._model, FalconH1):
        assert any(n.endswith("['ssm']") for n in planes)
        assert any(n.endswith("['conv']") for n in planes)


def _reference(model, params, tokens, n):
    out = generate(model, params, jnp.asarray([tokens], jnp.int32), n)
    return np.asarray(out)[0].tolist()


@pytest.mark.parametrize("depth", [1, 2])
def test_dispatch_fault_after_donated_calls_keeps_its_own_error(depth):
    """The loop's failure arm with the state donated: a fault at the
    dispatch site, after admits and blocks have each consumed their
    inputs, fails the live request with the fault's own error (never
    "buffer has been deleted") and the queued one as at any shutdown,
    closes the engine, and a fresh engine on the same params serves
    the same tokens again."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    want = _reference(model, params, [1, 2, 3], 6)
    opts = dict(
        slots=1, prompt_widths=(8,), decode_block=2, pipeline_depth=depth
    )
    eng = ContinuousBatcher(model, params, **opts)
    errors = {}

    def queued():
        try:
            eng.submit([7, 5], 4)
        except BaseException as e:  # noqa: BLE001 - the assertion reads it
            errors["queued"] = e

    try:
        assert eng.submit([1, 2, 3], 6) == want  # donated calls ran
        # pace the loop, so that the stream outlives the arming below
        fp.arm("engine.fetch", "delay", delay_s=0.05)
        stream = eng.stream([1, 2, 3], 100)
        got = [next(stream) for _ in range(5)]  # blocks are in flight
        assert got == _reference(model, params, [1, 2, 3], 5)
        waiter = threading.Thread(target=queued)
        waiter.start()
        deadline = time.monotonic() + 10
        while eng.stats()["queue_depth"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        fp.arm("engine.dispatch", "raise", count=1)
        with pytest.raises(FailpointError) as live:
            for _ in stream:
                pass
        assert "deleted" not in str(live.value)
        waiter.join(timeout=30)
        assert not waiter.is_alive()
        # what waited in the queue is failed as at any shutdown
        assert "shutting down" in str(errors["queued"])
        with pytest.raises(RuntimeError, match="shutting down"):
            eng.submit([3], 2)
        assert eng.stats()["closed"] is True
    finally:
        fp.disarm_all()
        eng.close()
    # the params were never donated: a fresh engine serves from them
    fresh = ContinuousBatcher(model, params, **opts)
    try:
        assert fresh.submit([1, 2, 3], 6) == want
    finally:
        fresh.close()
