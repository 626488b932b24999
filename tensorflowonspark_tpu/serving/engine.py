"""Continuous batching: slot-based admission into a persistent decode loop.

The fixed-batch server path (`tools/serve_model.py --gen-batch-window`)
coalesces requests into one decode call — late arrivals wait for the
whole batch to finish. Continuous batching removes that convoy: the
engine keeps a B-slot KV cache resident and decodes ONE token for all
active slots per step; a new request is prefilled into any free slot
*between steps*, and a finished row frees its slot immediately. Decode
is weight-read-bound, so stepping a partially full batch costs the same
HBM traffic as a full one — utilization comes from keeping slots busy,
which is exactly what per-step admission does.

TPU-first mechanics: all shapes are static, so the engine runs a small
FIXED set of compiled programs and admission never recompiles:

- **step** (compiled once per engine): (B, 1) tokens through the model
  with ``decode=True, padded=True`` — each row writes K/V at its OWN
  position (the per-row scatter path of `models/llama.py`
  `Attention._decode_attention`), so rows at different depths coexist
  in one batch. Per-request temperature and LoRA-adapter ids ride it
  as traced per-row inputs.
- **prefill** (compiled once per prompt-width bucket): a (1, W) padded
  prefill builds a fresh single-row cache and samples the row's first
  token from its true last position. The program hands the model no
  cache, so the model knows the call starts its sequence: it writes
  the cache and attends among the prompt's own W positions
  (`ops.attention.dot_product_attention`: on one TPU the flash kernel
  from W = 128 up), never against the cache's empty slots. In chunked
  mode the bucket prefills are replaced by ONE (1, C) **chunk** program
  plus a tiny **sample** program, reused for every prompt length; a
  chunk is handed the job's cache and scores its queries against every
  slot of it (the einsum). `engine_prefill_kv_positions_scored_total`
  over `..._span_total` says which ran, by the models' own rule
  (`decode_cache.keys_scored`).
- **admit** (compiled once): scatters the single-row cache into slot
  ``r`` of the engine cache with `lax.dynamic_update_slice` — no
  host-side cache reads, no recompilation.

``warmup()`` pre-compiles all of them before real traffic. The host
loop owns scheduling only: admit-then-step, retire rows on EOS, budget,
stop-sequence match, or cancellation, hand tokens to waiters. The
device work per step is what the plain `generate` loop runs.

**Overlapped pipeline** (``pipeline_depth``, default 2): the scheduler
keeps up to that many k-step decode blocks IN FLIGHT at once. Block
N+1 dispatches straight from the device-resident functional state
(cache/tok/pos are jax arrays — it never needs host data), THEN block
N is fetched and swept, so the host sweep (emit, stop-match, retire,
stream hand-off) hides behind device compute instead of serializing
with it — the tf.data overlap discipline applied to decode. The window
drains (fetch + sweep every in-flight block, oldest first) only when
host state must change under it: a request admission or a chunked
prefill's final-chunk admit, both of which scatter into the shared
batch state and must see the true free-slot set. Rows that finish
mid-window follow the same bounded discard semantics mid-block retire
already has — surplus tokens (at most ``decode_block × pipeline_depth``
per retire) are decoded and thrown away host-side, never emitted.
``pipeline_depth=1`` reproduces the strictly serial
dispatch→fetch→sweep loop exactly. Prefill/admission is asynchronous
too: the prefill and admit programs are dispatched without a device
sync and the first token's fetch is deferred into the normal fetch
path, so back-to-back admissions batch into one drain instead of
paying two scalar round-trips each. Stream deliveries (``sink.put``)
run on a dedicated emitter thread, off the scheduler's critical path.

Reference parity note: nothing in the reference corresponds to this
(its serving was batch scoring over Spark partitions); this is the
rebuild's answer to modern LLM-serving schedulers (vLLM-style), built
on the same static-shape KV cache the rest of the stack uses.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import math
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.models.decode_cache import (
    init_cache,
    keys_scored,
    kv_layers,
    leaf_kind,
)
from tensorflowonspark_tpu.obs import registry as obs_registry
from tensorflowonspark_tpu.obs import reqtrace
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.ops import decode_attention
from tensorflowonspark_tpu.parallel.context import use_mesh
from tensorflowonspark_tpu.utils.failpoints import failpoint

logger = logging.getLogger(__name__)

# Per-request logit_bias entries are capped so the (B, K) traced bias
# arrays stay a fixed compiled shape; 16 matches the typical ban/force
# use cases (OpenAI allows 300, but those maps thrash any static shape).
_BIAS_SLOTS = 16


class EngineOverloaded(RuntimeError):
    """Raised by submit()/stream() when the bounded request queue is
    full — callers should shed load (HTTP 503), not block."""


class DeadlineExceeded(TimeoutError):
    """Terminal per-request error: the request's ``deadline_s`` budget
    expired before it finished decoding. The scheduler retires the row
    at the next block boundary — an expired request never decodes past
    its deadline by more than one in-flight block window — and the
    caller should map this to a timeout status (HTTP 504), not retry
    blindly."""


class EngineWedged(RuntimeError):
    """Terminal per-request error from the scheduler watchdog: the
    dispatch/fetch loop made no observable progress for the configured
    window while work was in flight (a wedged device transfer, a hung
    runtime callback). In-flight requests are aborted with this error so
    their callers unblock; the scheduler itself is left to recover and
    keep serving — see ``ContinuousBatcher(watchdog_s=...)``."""


class WeightsIncompatible(ValueError):
    """``swap_weights`` payload does not fit the running engine: tree
    structure, leaf shape/dtype, or LoRA factor layout differs from the
    weights currently serving. The swap is REJECTED before anything is
    placed on device — the engine keeps serving its current version —
    and a rollout controller treats this as a per-replica failure that
    triggers automatic rollback (docs/ROBUSTNESS.md "Rolling weight
    updates")."""


def _row_truncate(scaled, ks, ps):
    """Per-row top-k/top-p mask over (B, vocab) temperature-scaled
    logits: top-k first, then top-p renormalized over the k survivors
    (the standard stacks' composition). ``ks``/``ps`` (B,) are traced —
    the shapes don't depend on the values (top-k compares sorted rank
    against k; top-p thresholds a cumsum). Disabled rows pass
    ``k = vocab`` / ``p = 1.0``."""
    vocab = scaled.shape[-1]
    sorted_desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
    rank = jnp.arange(vocab, dtype=jnp.float32)[None, :]
    kept = jnp.where(rank < ks[:, None], sorted_desc, -jnp.inf)
    probs = jax.nn.softmax(kept, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Last kept rank, via the EXCLUSIVE prefix (cum - probs): rank i
    # survives iff the mass strictly before it is < p. The inclusive
    # compare (cum < p) would let fp32 cumsum error bite disabled rows
    # (k=vocab, p=1.0) routed through the sort because a co-batched row
    # truncates: the cumsum can saturate at exactly 1.0 several ranks
    # early (~1e-5 of accumulated error), silently masking tail tokens
    # and making a seeded plain-temperature row's distribution depend on
    # its batchmates. With the exclusive form, any rank whose own prob
    # is representable keeps (1.0 - prob < 1.0); only prob==0 underflow
    # ranks — unsampleable anyway — fall off. Clamps: >= 0 (the most
    # likely token survives even when it alone exceeds p) and < k (a p
    # of ~1.0 must not walk into the -inf tail, whose exclusive prefix
    # plateaus just under 1.0 in floating point, and then keep MORE
    # than k tokens).
    cutoff_index = (
        jnp.sum(cum - probs < ps[:, None], axis=-1, keepdims=True) - 1
    )
    cutoff_index = jnp.clip(
        cutoff_index, 0, (ks[:, None] - 1).astype(jnp.int32)
    )
    cutoff = jnp.take_along_axis(kept, cutoff_index, axis=-1)
    return jnp.where(scaled < cutoff, -jnp.inf, scaled)


def _sample_rows(
    logits,
    temps,
    kps,
    seeds,
    counters,
    pens=None,
    counts=None,
    bias_ids=None,
    bias_vals=None,
    gates=None,
):
    """Per-row sampling over (B, vocab) logits.

    Every sampling input is a TRACED per-row value — no recompilation
    for any mix: ``temps`` (B,) temperature (0 = greedy), ``kps``
    (B, 3) resolved [top_k, top_p, min_p] (see :func:`_row_truncate`;
    min_p keeps tokens whose probability is at least min_p times the
    most likely token's — an elementwise log-space compare, no sort),
    ``seeds`` (B,) uint32 and ``counters`` (B,) int32. Each row's draw
    uses its OWN key, ``fold_in(fold_in(base, seed), counter)`` with
    the counter = the sampled token's sequence position — so a seeded
    request's completion is a pure function of (params, prompt, seed),
    REPRODUCIBLE regardless of how its row interleaves with other
    traffic in the continuous batch (the global-key design it replaces
    made every sample depend on the engine-lifetime step count).

    The truncation mask runs under ``lax.cond`` on "any row truncates":
    greedy and plain-temperature batches — the benchmarked configs —
    skip the full-vocab sort entirely.

    ``pens`` (B, 2) [frequency_penalty, presence_penalty] with
    ``counts`` (B, vocab) per-row generated-token counts applies the
    OpenAI-convention repetition penalties BEFORE temperature scaling
    (and before the greedy argmax — penalties shape greedy rows too):
    ``logit - freq*count - pres*(count > 0)``. Cond-gated: batches with
    all-zero penalties never touch the count plane.

    Returns ``(tokens (B,) int32, logprobs (B,) fp32)`` — the logprob
    of each chosen token under the RAW (unscaled, unpenalized) model
    distribution, the same convention the /score surface reports, so
    sampled and scored numbers compare directly.
    """
    vocab = logits.shape[-1]
    raw = logits
    if bias_ids is not None:
        # per-request logit_bias (OpenAI convention: applied straight to
        # the logits, so it shapes greedy rows and bans/forces tokens).
        # ids are (B, K) with -1 = inactive slot; duplicate ids in one
        # request accumulate. Cond-gated like the other knobs.
        def _bias(lg):
            safe = jnp.maximum(bias_ids, 0)
            vals = jnp.where(bias_ids >= 0, bias_vals, 0.0)
            add = jax.vmap(
                lambda ids, v: jnp.zeros((vocab,), jnp.float32)
                .at[ids]
                .add(v)
            )(safe, vals)
            return (lg.astype(jnp.float32) + add).astype(lg.dtype)

        logits = jax.lax.cond(
            gates[3] if gates is not None else jnp.any(bias_ids >= 0),
            _bias,
            lambda lg: lg,
            logits,
        )
    if pens is not None:
        def _penalize(lg):
            return (
                lg.astype(jnp.float32)
                - pens[:, :1] * counts
                - pens[:, 1:] * (counts > 0)
            ).astype(lg.dtype)

        logits = jax.lax.cond(
            gates[2] if gates is not None else jnp.any(pens != 0.0),
            _penalize,
            lambda lg: lg,
            logits,
        )
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    ks, ps, ms = kps[:, 0], kps[:, 1], kps[:, 2]

    # two independent conds: k/p need the full-vocab sort, min_p is a
    # row-max compare — each batch pays only for what its rows use.
    # ``gates`` ((4,) bool [sort, min_p, penalties, bias], traced) lets
    # the SCHEDULER decide from its live-row bookkeeping: device-side
    # any() over the state arrays would keep firing on a retired row's
    # stale values until the slot is reused, taxing every remaining
    # greedy row with the full-vocab sort. Single-row prefill callers
    # omit gates — the device derivation is exact there.
    need_sort = (
        gates[0]
        if gates is not None
        else jnp.any((ks < vocab) | (ps < 1.0))
    )
    trunc = jax.lax.cond(
        need_sort,
        lambda lg: _row_truncate(lg, ks, ps),
        lambda lg: lg,
        scaled,
    )

    def _min_p(lg):
        # keep where prob >= min_p * prob_max, i.e. (in log space)
        # scaled >= row_max + log(min_p); computed on the UNtruncated
        # scaled logits so min_p composes with k/p by mask intersection
        floor = jnp.max(scaled, axis=-1, keepdims=True) + jnp.log(
            jnp.maximum(ms, 1e-38)
        )[:, None]
        return jnp.where(scaled < floor, -jnp.inf, lg)

    trunc = jax.lax.cond(
        gates[1] if gates is not None else jnp.any(ms > 0.0),
        _min_p,
        lambda lg: lg,
        trunc,
    )
    base = jax.random.PRNGKey(0)
    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.fold_in(base, s), c)
    )(seeds, counters)
    sampled = jax.vmap(jax.random.categorical)(keys, trunc).astype(
        jnp.int32
    )
    tok = jnp.where(temps > 0, sampled, greedy)
    logp = jax.nn.log_softmax(raw.astype(jnp.float32), axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok, lp


@dataclasses.dataclass
class _Pending:
    tokens: list[int]
    max_new_tokens: int
    event: threading.Event
    temperature: float | None = None  # None = the engine-wide default
    top_k: int | None = None  # None = the engine-wide default
    top_p: float | None = None  # None = the engine-wide default
    min_p: float | None = None  # None = the engine-wide default
    frequency_penalty: float | None = None  # None/0 = disabled
    presence_penalty: float | None = None  # None/0 = disabled
    # {token_id: bias}; at most _BIAS_SLOTS entries, biases clamp the
    # OpenAI [-100, 100] convention
    logit_bias: "dict[int, float] | None" = None
    # None = engine-drawn (independent, nondeterministic across
    # submissions); set = reproducible completion for this request
    seed: int | None = None
    eos_id: int | None = None  # None = the engine-wide default
    adapter: int = 0  # MultiLoraTensor bank slot (0 = base model)
    # multi-token stop sequences (host-side tail match; the matched
    # suffix is trimmed from the RESULT — streams necessarily saw its
    # tokens already, since the match completes only on the last one)
    stop: tuple = ()
    # set by the consumer side (stream close); the scheduler treats it
    # as finished at the next step/admission — a plain bool is enough
    # (single writer, benign race: at worst one extra token decodes)
    cancelled: bool = False
    # While this request is LIVE, the scheduler caps its decode-block
    # size at this value (warmup rides it to compile the k=1 program
    # without mutating the shared engine knob under live traffic).
    decode_block_pin: int | None = None
    # wall-clock budget from enqueue; None = unbounded. Expiry is a
    # TERMINAL DeadlineExceeded, checked at queue pop and every
    # scheduler iteration (see _expire_deadlines).
    deadline_s: float | None = None
    submitted_at: float = 0.0  # time.monotonic() at enqueue
    first_token_at: float | None = None  # set when token 0 emits
    # the engine weights version this request RESOLVED under, stamped on
    # the scheduler thread at retirement — the same thread that applies
    # weight swaps, so the stamp is coherent by construction (a rollout
    # bench asserts every completion carries one; see swap_weights)
    weights_version: str | None = None
    # resolve-once latch (guarded by the engine's _resolve_lock): a
    # request resolves as EXACTLY one of completed/failed even when the
    # watchdog thread races the scheduler — whoever flips this delivers
    # the terminal; the loser only frees bookkeeping.
    resolved: bool = False
    result: list[int] | None = None
    logprobs: list[float] | None = None  # filled at retirement
    error: BaseException | None = None
    # streaming: every emitted token is ALSO pushed here as it decodes,
    # then True (done) or the error object as the terminal item.
    # Deliveries go through the engine's _EmitWorker thread (see
    # ContinuousBatcher._emit) so consumer-side work never runs on the
    # scheduler's critical path.
    sink: "queue.Queue | None" = None
    # distributed request tracing (obs.reqtrace): the trace id this
    # request rides, or None (near-zero cost — every stamp below is
    # gated on `trace is not None`). `trace_mark` is the scheduler's
    # per-request segment cursor: monotonic time of the last stamped
    # segment boundary, advanced queue -> prefill -> decode blocks ->
    # finish so the segment union covers the request's wall time.
    trace: str | None = None
    trace_mark: float | None = None


class _Stream:
    """Iterator over a streaming request's tokens; ``close()`` (or GC)
    before exhaustion CANCELS the request — the scheduler frees its
    slot at the next step instead of running out the budget."""

    def __init__(self, p: "_Pending", yield_logprobs: bool):
        self._p = p
        self._yield_logprobs = yield_logprobs
        self._done = False

    def __iter__(self):
        return self

    def __next__(self):
        item = self._p.sink.get()
        if item is True:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        token, lp = item
        return (token, lp) if self._yield_logprobs else token

    @property
    def result(self):
        """The request's FINAL (stop-trimmed) completion, available
        once the stream is exhausted — the streamed tokens necessarily
        include any matched stop suffix (the match completes on its
        last token), so trailer-building consumers should prefer this
        over re-assembling the yielded tokens."""
        return self._p.result

    @property
    def logprobs(self):
        return self._p.logprobs

    @property
    def weights_version(self):
        """The weights version this request resolved under (set with
        ``result``, i.e. once the stream is exhausted)."""
        return self._p.weights_version

    def close(self) -> None:
        if not self._done:
            self._p.cancelled = True

    __del__ = close


class _EmitWorker:
    """Dedicated delivery thread for stream sinks.

    The scheduler loop hands every sink item — per-token ``(token,
    logprob)`` tuples and the terminal ``True``/exception markers — to
    this thread instead of pushing them inline, so per-token consumer
    hand-off cost never sits on the decode critical path (and a sink
    subclass with a slow/blocking ``put`` cannot stall every other
    request's decode). One FIFO queue preserves per-request item order;
    the single producer is the scheduler thread, so cross-request order
    matches the scheduler's emit order too. ``stop()`` is a sentinel:
    everything enqueued before it is delivered first, then the thread
    exits — the engine calls it as the scheduler loop winds down."""

    _STOP = object()

    def __init__(self) -> None:
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="engine-emitter"
        )
        self._thread.start()

    def deliver(self, sink: "queue.Queue", item) -> None:
        self._q.put((sink, item))

    def stop(self, timeout: float = 30.0) -> bool:
        """Flush + stop; False when the thread outlived the join (a
        sink ``put`` blocking forever — callers log it loudly)."""
        self._q.put(self._STOP)
        self._thread.join(timeout=timeout)
        return not self._thread.is_alive()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            sink, payload = item
            try:
                sink.put(payload)
            except Exception:  # noqa: BLE001 - one bad sink must not
                # take down delivery for every other stream
                logger.exception("stream sink delivery failed")


@dataclasses.dataclass
class _PrefillJob:
    """A chunked prefill in flight: one slot reserved, the single-row
    cache accumulating chunk by chunk between decode steps."""

    p: _Pending
    row: int
    cache_1: object
    next_pos: int  # next chunk's start offset into the prompt
    length: int
    temp_1: object  # (1,) fp32
    kp_1: object  # (1, 3) fp32 resolved [top_k, top_p, min_p]
    seed_1: object  # (1,) uint32 resolved sampling seed
    pen_1: object  # (1, 2) fp32 [frequency_penalty, presence_penalty]
    bias_1: object  # ((1, K) int32 ids, (1, K) fp32 values)
    ad_1: object  # (1,) int32 adapter id
    # next prompt depth at which to store a chunk-boundary prefix entry
    # (doubles after each insert — see _advance_job)
    next_insert_depth: int = 0
    boundary_inserts: int = 0  # made so far, capped per request


@dataclasses.dataclass
class _SwapRequest:
    """A validated, device-placed weight tree waiting for the scheduler
    to install it between decode blocks (see ``swap_weights``). All
    expensive work (validation, host→device transfer) already happened
    on the caller thread — installation is a pointer flip."""

    placed: object
    version: str
    event: threading.Event
    error: BaseException | None = None  # set if the swap was aborted


@dataclasses.dataclass
class _KnobRequest:
    """A validated scheduler-knob change (``decode_block`` /
    ``pipeline_depth``) waiting for the scheduler to install between
    decode blocks — the same discipline as a weight swap (see
    ``set_knobs``): the loop owns both knobs, so a caller-thread
    mutation would race the dispatch/fetch bookkeeping."""

    decode_block: int | None
    pipeline_depth: int | None
    event: threading.Event
    error: BaseException | None = None  # set if the change was aborted


class _PrefixStore:
    """LRU of prompt→single-row-KV-cache entries for prefix reuse.

    A request whose prompt extends a stored prompt resumes prefill from
    the stored cache instead of position 0 — the serving win for shared
    system prompts. Entries are jax arrays (immutable), so "reuse" is a
    reference: the continuation's functional cache updates never touch
    the stored buffer, and no device copies happen at lookup or insert.

    Cost model: each entry holds ONE full-length single-row KV cache
    (layers × 2 × max_seq_len × kv_heads × head_dim in the cache dtype
    — e.g. ~130 MB for the llama1b config at seq 4096 bf16), so
    ``capacity`` is a real HBM budget knob, not just an entry count.
    Accessed only from the scheduler loop thread — no locking.
    """

    def __init__(self, capacity: int):
        from collections import OrderedDict

        self.capacity = capacity
        self._d: "OrderedDict[tuple, object]" = OrderedDict()
        # adapter -> {key_length -> set of stored key tuples}: lookup
        # hashes the PROMPT's prefix at each stored length (longest
        # first, early exit) instead of comparing every stored key —
        # the old scan was O(entries × prompt_len) per admission, so a
        # large warm cache taxed every cold-store admission too.
        self._by_adapter: "dict[int, dict[int, set]]" = {}
        self.hits = 0
        self.misses = 0
        self.tokens_saved = 0

    def lookup(self, tokens: list[int], adapter: int = 0):
        """Longest stored prefix of ``tokens`` under the same adapter →
        (cache, resume_pos), or (None, 0). A prefix computed under one
        LoRA adapter is NOT valid under another (its K/V went through
        that adapter's projections), so entries are bucketed per
        adapter and other adapters' caches cost nothing here. Within
        the bucket, stored key lengths are probed longest-first — one
        prefix-tuple hash per distinct length, stopping at the first
        hit (two distinct same-length keys cannot both prefix one
        prompt, so the first hit IS the longest match). resume_pos is
        capped at len(tokens)-1 so the chunk path always re-processes
        at least the last prompt token — its logits are where the first
        completion token samples from (the overlap recompute writes
        back identical K/V rows)."""
        n = len(tokens)
        best_key = None
        best_len = 0
        by_len = self._by_adapter.get(adapter)
        if by_len:
            for lk in sorted(by_len, reverse=True):
                if lk > n:
                    continue
                cand = tuple(tokens[:lk])
                if cand in by_len[lk]:
                    best_key, best_len = (adapter, cand), lk
                    break
        resume = min(best_len, n - 1)
        if best_key is None or resume < 1:
            self.misses += 1
            return None, 0
        self._d.move_to_end(best_key)
        self.hits += 1
        self.tokens_saved += resume
        return self._d[best_key], resume

    def insert(self, tokens: list[int], cache_1, adapter: int = 0) -> None:
        key = tuple(tokens)
        k = (adapter, key)
        if k not in self._d:
            self._by_adapter.setdefault(adapter, {}).setdefault(
                len(key), set()
            ).add(key)
        self._d[k] = cache_1
        self._d.move_to_end(k)
        while len(self._d) > self.capacity:
            (ad, old), _ = self._d.popitem(last=False)
            self._unindex(ad, old)

    def _unindex(self, adapter: int, key: tuple) -> None:
        by_len = self._by_adapter[adapter]
        bucket = by_len[len(key)]
        bucket.discard(key)
        if not bucket:
            del by_len[len(key)]
            if not by_len:
                del self._by_adapter[adapter]

    def clear(self) -> None:
        self._d.clear()
        self._by_adapter.clear()

    def __len__(self) -> int:
        return len(self._d)


class ContinuousBatcher:
    """Persistent B-slot decode engine over one decoder checkpoint
    (``models.llama.Llama``, ``models.falcon_h1.FalconH1``,
    ``models.pangu_moe.PanguMoE``: any module with their call signature,
    a ``head`` method and a ``cache`` collection as
    ``models/decode_cache.py`` describes it). The engine behaves by what
    the model's cache tree holds: a model that carries recurrent state
    beside K/V is served through the same programs, and refuses
    ``prefix_cache``, ``prefix_l2`` and a ``mesh`` whose ``model`` extent
    is over 1; one whose cache entry is a latent is served like K/V (one
    entry a position) and refuses that mesh too; one that counts what it
    routes (``moe_counts``) has the counts ride the block's packed fetch
    and names the registry counters they feed (``counter_entries()``;
    see ``docs/SERVING.md``).

    ``submit(tokens, max_new_tokens)`` blocks the calling thread until
    that request's completion is ready (server handler threads call it
    concurrently). Greedy by default. ``temperature``, ``top_k`` and
    ``top_p`` are PER-REQUEST (the constructor values are just the
    defaults): they ride the compiled step as traced per-row inputs, so
    mixing greedy, sampled, and differently-truncated rows in one batch
    costs no recompilation (see ``_sample_rows`` — batches with no
    truncation active skip the sort entirely).

    ``prompt_widths``: prompts are right-padded to the smallest listed
    width (one prefill compilation each). A prompt longer than the
    largest width is rejected, as is prompt+budget beyond the model's
    ``max_seq_len`` (the KV cache cannot hold it).

    ``decode_block``: steady-state decode runs as one ``lax.scan`` of
    this many steps per host iteration (one dispatch + one fetch per
    block instead of per token), dropping to single steps only while a
    queued request could actually be admitted into a free slot (or a
    chunked prefill is in flight). Rows finishing mid-block — budget,
    stop, or eos — retire at their finish point; surplus block tokens
    are discarded, never emitted. Kept tokens are bit-identical to
    single stepping; set ``decode_block=1`` to disable (e.g. to
    minimize admission latency jitter under bursty traffic).

    ``pipeline_depth``: how many decode blocks the scheduler keeps in
    flight at once (dispatch-ahead; see the module docstring's
    overlapped-pipeline section). Depth 2 hides the host sweep behind
    device compute; depth 1 is the strictly serial loop. Output tokens
    and logprobs are identical at every depth — the device computation
    chain does not depend on when the host fetches it — only latency
    bounds change: a cancel or mid-window retire can decode (and
    discard) up to ``decode_block × pipeline_depth`` surplus tokens.
    """

    _STOP = object()
    # queue sentinel that only WAKES an idle scheduler (so a pending
    # weight swap is noticed without a request arriving); carries no
    # state change itself
    _WAKE = object()

    def __init__(
        self,
        model,
        params,
        *,
        slots: int = 8,
        prompt_widths: tuple[int, ...] = (128,),
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        min_p: float | None = None,
        eos_id: int | None = None,
        seed: int = 0,
        mesh=None,
        max_queue: int | None = None,
        prefill_chunk: int | None = None,
        prefix_cache: int | None = None,
        prefix_l2=None,
        decode_block: int = 8,
        pipeline_depth: int = 2,
        watchdog_s: float | None = None,
        weights_version: str = "v0",
    ):
        cfg = model.cfg
        self._model = model
        self._mesh = mesh
        self._slots = int(slots)
        if self._slots < 1:
            # slots=0 would construct fine, then the scheduler thread
            # busy-spins and every submit() waits forever on a free slot.
            raise ValueError(f"slots must be >= 1, got {slots}")
        # The batch cache's tree, by one eval_shape (no compile, no
        # device work): what kinds of per-request state the model keeps
        # decides what the engine may do with a row.
        self._params = params
        self._batch_cache_shapes = self._cache_shapes(self._slots)
        cache_bytes = dict.fromkeys(("kv", "latent", "recurrent", "other"), 0)
        # entries of one layer's ``moe_counts`` leaf (0: the model
        # counts nothing); the layers' leaves are summed
        self._n_routed = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            self._batch_cache_shapes
        ):
            kind = leaf_kind(path)
            if kind == "counter":
                self._n_routed = leaf.shape[0]
            cache_bytes[kind if kind in cache_bytes else "other"] += (
                math.prod(leaf.shape) * leaf.dtype.itemsize
            )
        if cache_bytes["latent"] and (
            mesh is not None and mesh.shape.get("model", 1) > 1
        ):
            raise ValueError(
                "a mesh 'model' extent over 1 is unsupported with a latent "
                "cache: one entry a position has no heads to shard, and "
                "there is no parameter table for the latent projections "
                "and the expert banks"
            )
        if cache_bytes["recurrent"]:
            # Recurrent state is valid at one position only: the one
            # after the last token the row consumed.
            if prefix_cache is not None or prefix_l2 is not None:
                raise ValueError(
                    "prefix_cache / prefix_l2 are unsupported with a "
                    "model that carries recurrent state: a stored row "
                    "resumes at any depth inside it, which needs a "
                    "state snapshot at the resume position, and none "
                    "is kept"
                )
            if mesh is not None and mesh.shape.get("model", 1) > 1:
                raise ValueError(
                    "a mesh 'model' extent over 1 is unsupported with a "
                    "model that carries recurrent state: there is no "
                    "cache sharding for its recurrent leaves (nor a "
                    "parameter table for its mixer)"
                )
        if mesh is not None:
            from tensorflowonspark_tpu.compute import layout

            tp = mesh.shape.get("model", 1)
            if cfg.num_heads % tp or cfg.num_kv_heads % tp:
                raise ValueError(
                    f"heads ({cfg.num_heads}/{cfg.num_kv_heads} kv) not "
                    f"divisible by the mesh 'model' extent {tp}"
                )
            other = {
                ax: n
                for ax, n in mesh.shape.items()
                if ax != "model" and n > 1
            }
            if other:
                # Row-wise admission keeps the batch axis UNSHARDED, so
                # non-'model' extents only replicate the computation —
                # correct but wasted chips for a serving engine.
                logger.warning(
                    "continuous engine shards TP on 'model' only; mesh "
                    "axes %s replicate work rather than adding "
                    "throughput",
                    other,
                )

            # Keep ONLY the 'model' (TP) placement; the training
            # rules also shard on 'fsdp', which with a replicated
            # batch would force a weight all-gather on every
            # per-token decode step. One source of truth: the llama
            # layout table projected through layout.tp_only.
            params = jax.device_put(
                params,
                jax.tree.map(
                    lambda sh: layout.tp_only(mesh, sh),
                    layout.param_shardings(params, mesh, "llama"),
                ),
            )
        self._params = params
        from tensorflowonspark_tpu.ops.lora import bank_size

        # MultiLoraTensor banks in the params enable per-request adapter
        # routing; 0 slots means "no bank" (adapter must be 0/None).
        self._n_adapters = bank_size(params)
        self._widths = tuple(sorted(int(w) for w in prompt_widths))
        if not self._widths or self._widths[-1] > cfg.max_seq_len:
            raise ValueError(
                f"prompt_widths {prompt_widths} must be non-empty and "
                f"<= max_seq_len ({cfg.max_seq_len})"
            )
        if self._widths[0] < 1:
            raise ValueError(
                f"prompt_widths must all be >= 1, got {prompt_widths}"
            )
        self._temperature = float(temperature)
        self._top_k = None if top_k is None else int(top_k)
        self._top_p = None if top_p is None else float(top_p)
        self._min_p = None if min_p is None else float(min_p)
        # The engine-wide defaults feed _resolve_kp exactly like request
        # values do, so they get the same validity check — a top_k=0
        # default would otherwise silently DISABLE truncation (rank < 0
        # keeps nothing; the cutoff clamp then keeps everything).
        if self._top_k is not None and self._top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if self._top_p is not None and not (
            math.isfinite(self._top_p) and 0 < self._top_p <= 1
        ):
            raise ValueError(
                f"top_p must be finite and in (0, 1], got {top_p}"
            )
        if self._min_p is not None and not (
            math.isfinite(self._min_p) and 0 <= self._min_p <= 1
        ):
            raise ValueError(
                f"min_p must be finite and in [0, 1], got {min_p}"
            )
        self._eos_id = None if eos_id is None else int(eos_id)
        # Per-request sampling seeds: explicit request seeds pass
        # through; unseeded requests draw one here at enqueue — making
        # each independent, and the whole engine reproducible given its
        # constructor seed and request order.
        # (mod 2**64: PCG64 rejects negative seeds, which PRNGKey-era
        # configs may legitimately pass)
        self._seed_rng = np.random.Generator(
            np.random.PCG64(int(seed) % 2**64)
        )

        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._max_queue = max_queue
        if prefill_chunk is not None and not (
            1 <= prefill_chunk <= cfg.max_seq_len
        ):
            # The upper bound keeps _advance_job's window shift
            # (start_w = min(start, max_seq_len - chunk)) non-negative.
            raise ValueError(
                f"prefill_chunk must be in [1, max_seq_len="
                f"{cfg.max_seq_len}], got {prefill_chunk}"
            )
        self._prefill_chunk = prefill_chunk
        if prefix_cache is not None:
            if prefix_cache < 1:
                raise ValueError(
                    f"prefix_cache must be >= 1 entries, got {prefix_cache}"
                )
            if prefill_chunk is None:
                # Prefix reuse resumes prefill mid-prompt, which is what
                # the chunk path does; the width-bucket prefill always
                # starts from position 0.
                raise ValueError(
                    "prefix_cache requires prefill_chunk (prefix reuse "
                    "resumes prefill through the chunked path)"
                )
            self._prefix_store = _PrefixStore(prefix_cache)
        else:
            self._prefix_store = None
        if prefix_l2 is not None and self._prefix_store is None:
            # The L2 feeds and is fed through the L1 insert/lookup
            # sites; without an L1 neither exists.
            raise ValueError("prefix_l2 requires prefix_cache")
        # Fleet-global prefix L2 (cachetier.PrefixL2 or None). Rebound
        # atomically by attach_prefix_l2; the scheduler thread reads it
        # racily — a one-iteration-stale None/instance is benign (one
        # extra miss or one extra offer to a live client).
        self._prefix_l2 = prefix_l2
        self._queue: "queue.Queue" = queue.Queue()
        self._closed = False  # guarded-by: self._submit_lock
        # Hot weight swap (zero-downtime rollout): the label of the
        # weights currently serving, and the validated/placed update
        # waiting for the scheduler to install between decode blocks.
        # _weights_version is written ONLY on the scheduler thread (at
        # apply) and read racily by stats/health — a str rebind is
        # atomic and a one-iteration-stale read is benign.
        self._weights_version = str(weights_version)
        self._weights_swaps = 0  # applied swaps (scheduler-thread-owned)
        self._pending_swap: _SwapRequest | None = None  # guarded-by: self._submit_lock
        # Live scheduler-knob change (autotune actuation path), applied
        # between decode blocks exactly like a pending weight swap.
        self._pending_knobs: _KnobRequest | None = None  # guarded-by: self._submit_lock
        # True only while warmup() runs its throwaway requests: a fresh
        # replica compiling is ALIVE but not READY — health probers
        # must see the difference (a warmup stall otherwise looks
        # wedged). Single writer (the warmup caller); racy bool reads
        # from health() are benign.
        self._warming = False
        self._stop_now = threading.Event()
        self._submit_lock = threading.Lock()
        self._prefill_cache: dict = {}
        # Block decode: in steady state the loop runs ONE lax.scan of
        # decode_block steps per host iteration instead of decode_block
        # jit calls — collapsing the per-token host round-trips (gates
        # upload, dispatch, token fetch, waiter hand-off; their share
        # of an engine step is not measured on this installation).
        # Kept tokens are bit-identical to single
        # stepping (sampling is (seed, position)-keyed); a row that
        # finishes mid-block — budget, stop, or eos — wastes its
        # remaining block steps: the surplus tokens are discarded
        # host-side, never emitted.
        self._decode_block = max(1, int(decode_block))
        self._block_cache: dict = {}
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        # Overlapped pipeline: up to pipeline_depth dispatched-but-not-
        # fetched decode blocks. Each window entry is (k, packed, rows)
        # — the block length, its device-resident (2, k, slots) result
        # and how many rows were live when it was dispatched (what the
        # sweep does not append of k x rows was computed and discarded).
        # Scheduler-thread-only, like _live.
        self._pipeline_depth = int(pipeline_depth)
        self._window: "collections.deque[tuple[int, object, int]]" = (
            collections.deque()
        )
        # Async admissions whose first token is still device-resident:
        # (row, tok_1, lp_1). Resolved before any sweep can touch the
        # row (see _resolve_first_tokens).
        self._pending_first: list[tuple[int, object, object]] = []
        self._drain_stalls = 0  # forced drains of a non-empty window
        self._overlap_hidden_s = 0.0  # host sweep time hidden by flight
        # Device-resident (4,) gates array, rebuilt only when the live
        # set changes (admit/retire), not per step: the per-step
        # jnp.asarray was a host->device upload on the decode hot path.
        self._gates_arr = None
        # The request popped from the queue but not yet parked in a slot
        # — must be failed explicitly if the loop dies mid-admission.
        self._inflight: _Pending | None = None
        # Chunked-prefill job in flight (loop thread only); its request
        # is in neither _live nor the queue, so shutdown/death paths
        # must fail it explicitly.
        self._job: _PrefillJob | None = None

        # Device-resident engine state (built lazily on first request so
        # constructing an engine is cheap in tests/CLIs that never run).
        self._state = None
        # Host-side per-slot bookkeeping: None = free, else
        # (_Pending, output tokens, output logprobs).
        self._live: list[
            tuple[_Pending, list[int], list[float]] | None
        ] = [None] * self._slots
        self.steps = 0  # observability: engine decode steps taken
        self.admitted = 0
        self.completed = 0
        # Accepted-but-not-yet-resolved accounting for the drain
        # quiescence check: _accepted_total bumps under the submit lock
        # at enqueue, and every request resolves as exactly one of
        # completed or _failed_total. Sampling queue/_inflight/_live
        # individually instead would race the scheduler's pop→park
        # handoffs and let a drain declare "idle" around a request it
        # promised to finish.
        self._accepted_total = 0  # guarded-by: self._submit_lock
        # _failed_total is scheduler-thread-owned (bumped only in
        # _fail_one on the loop thread); the drain loop in close() reads
        # it racily by design, like `completed` — deliberately NOT
        # lock-annotated.
        self._failed_total = 0
        self.tokens_emitted = 0
        self.cancelled = 0  # consumer-abandoned requests (stream close)
        # Degradation surface: deadline expiries and watchdog fires are
        # failures (every one resolves its request via _fail_one).
        self.deadline_expired = 0  # scheduler-thread-owned, like steps
        self.watchdog_fires = 0  # watchdog-thread-owned
        # None until close() runs, then whether the scheduler (and the
        # emitter) actually wound down inside the join timeout.
        self._stopped_cleanly: bool | None = None
        # _fail_one may now run on the watchdog thread concurrently with
        # the scheduler's retire path; this lock backs the resolve-once
        # latch on _Pending and the _failed_total count.
        self._resolve_lock = threading.Lock()
        # Watchdog plumbing: the scheduler stamps _progress_ts at every
        # observable step; _current_phase names where it currently is
        # (racy single-writer reads — diagnostics, not control flow).
        self._progress_ts = time.monotonic()
        self._current_phase: str | None = None
        self._watchdog_abort = threading.Event()
        self._watchdog_suspended = False  # warmup compiles under it
        if watchdog_s is not None and watchdog_s <= 0:
            raise ValueError(f"watchdog_s must be > 0, got {watchdog_s}")
        self._watchdog_s = watchdog_s
        self._ttft_sum = 0.0  # seconds, summed over completed requests
        self._duration_sum = 0.0
        # Latency denominators track only requests that actually ran:
        # unadmitted cancels complete (for drain accounting) with no
        # tokens and ~zero duration, and would drag the averages down.
        self._latency_n = 0

        # Observability (obs/): a PER-ENGINE span tracer (so /stats
        # percentiles describe this engine, not every engine in the
        # process) and a per-engine metrics registry rendered at the
        # server's /metrics. Phase spans cover the scheduler's hot
        # path: queue wait, prefill/batch formation, device dispatch,
        # block fetch.
        self._tracer = obs_spans.SpanTracer(capacity=4096)
        self.metrics = obs_registry.Registry()
        self._m_accepted = self.metrics.counter(
            "engine_requests_total", "requests accepted into the queue"
        )
        self._m_completed = self.metrics.counter(
            "engine_requests_completed_total", "requests resolved"
        )
        self._m_failed = self.metrics.counter(
            "engine_requests_failed_total", "requests failed"
        )
        self._m_tokens = self.metrics.counter(
            "engine_tokens_emitted_total", "completion tokens decoded"
        )
        self._m_steps = self.metrics.counter(
            "engine_decode_steps_total", "device decode steps taken"
        )
        self._m_live_steps = self.metrics.counter(
            "engine_slot_steps_live_total",
            "decode steps x rows live at dispatch (of "
            "engine_decode_steps_total x slots; the rest ran on slots "
            "that stood empty)",
        )
        self._m_fallback_steps = self.metrics.counter(
            "engine_decode_fallback_steps_total",
            "decode steps dispatched in a block shorter than "
            "decode_block (admission pending, chunked job, pin)",
        )
        self._m_prefill_tokens = self.metrics.counter(
            "engine_prefill_tokens_total",
            "prompt tokens the dispatched prefill programs had to "
            "process (after a prefix hit: the suffix only)",
        )
        self._m_prefill_positions = self.metrics.counter(
            "engine_prefill_positions_total",
            "positions the dispatched prefill programs computed "
            "(bucket or chunk width; the excess over "
            "engine_prefill_tokens_total is padding)",
        )
        self._m_prefill_kv_scored = self.metrics.counter(
            "engine_prefill_kv_positions_scored_total",
            "(query, key) pairs the dispatched prefill programs score, "
            "a head and K/V layer (their mean, each at its own length "
            "and window): per call its width x the keys a query "
            "of it is scored against (decode_cache.keys_scored: the "
            "width, at most the window, where the call creates its "
            "cache and attends among its own positions, the cache "
            "length where a chunk runs the einsum over the cache it "
            "was handed)",
        )
        self._m_prefill_kv_span = self.metrics.counter(
            "engine_prefill_kv_positions_span_total",
            "(query, key) pairs the dispatched prefill programs span: "
            "width x cache length a call and K/V layer (their mean)",
        )
        self._m_kv_read = self.metrics.counter(
            "engine_decode_kv_positions_read_total",
            "cache positions the dispatched decode steps fetch, a "
            "plane and K/V layer (their mean, each at its own length "
            "and window): per step and slot the blocks the "
            "decode-attention kernel reads at the slot's position "
            "(ceil(length / block_k) x block_k, clipped to the "
            "window), or the whole cache row where the einsum runs",
        )
        self._m_kv_span = self.metrics.counter(
            "engine_decode_kv_positions_span_total",
            "cache positions the dispatched decode steps span: "
            "steps x slots x cache length (the K/V layers' mean)",
        )
        # recurrent leaves have the row first: a slot's share of them
        self._recurrent_row_bytes = cache_bytes["recurrent"] // self._slots
        self._m_recurrent = self.metrics.counter(
            "engine_recurrent_state_bytes_total",
            "bytes of the recurrent leaves (engine_cache_bytes's "
            "recurrent kind) of the live slots, summed over the "
            "dispatched decode steps: what the steps' state updates "
            "read, and write again",
        )
        self._m_discarded = self.metrics.counter(
            "engine_slot_steps_discarded_total",
            "of engine_slot_steps_live_total, the slot-steps whose "
            "token no request got: computed past a row's end inside a "
            "block in flight, or in a block dropped unfetched. Once "
            "idle, without failures or cancels: live = (tokens emitted "
            "- requests completed) + discarded, exactly",
        )
        # The completion clock: the scheduler awaits every block's
        # result and every admission's first token, programs run on the
        # chip in the order they were launched, so the moments those
        # waits return are a clock of the device's completions on the
        # host's perf_counter (the spans' clock). Each interval between
        # two of them goes to the program whose completion ends it.
        self._m_device_decode_s = self.metrics.counter(
            "engine_device_decode_seconds_total",
            "completion-clock seconds that ended with a decode block's "
            "fetch (the block, and whatever ran before it since the "
            "last awaited completion: the admit program's scatter, the "
            "intermediate chunks of a chunked prefill, which nobody "
            "awaits, blocks dropped unfetched)",
        )
        self._m_device_prefill_s = self.metrics.counter(
            "engine_device_prefill_seconds_total",
            "completion-clock seconds that ended with an admission's "
            "first token on the host (its prefill program, and what "
            "ran before it since the last awaited completion)",
        )
        self._m_device_starved_s = self.metrics.counter(
            "engine_device_starved_seconds_total",
            "completion-clock seconds carved off the front of an "
            "interval: at the completion before it nothing was in "
            "flight (no block in the window, no first token pending), "
            "until the next launch call had returned. The chip had "
            "nothing to run. An intermediate chunk of a chunked "
            "prefill is awaited by nobody and is not seen in flight",
        )
        self._m_gap = self.metrics.histogram(
            "engine_completion_gap_seconds",
            "the completion clock's intervals, whole (decode or "
            "prefill and the starved part together): the time between "
            "two awaited device results while the engine holds work",
            buckets=obs_registry.DEFAULT_BUCKETS + (30.0,),
        )
        # the previous completion (None: the engine held nothing since;
        # the clock starts again at the next launch), and from when in
        # the current interval the chip has had something to run (None:
        # nothing yet)
        self._clock_at: float | None = None
        self._clock_fed_at: float | None = None
        # a window in which nothing was counted reads 0, not absent
        for c in (
            self._m_live_steps, self._m_fallback_steps,
            self._m_prefill_tokens, self._m_prefill_positions,
            self._m_prefill_kv_scored, self._m_prefill_kv_span,
            self._m_kv_read, self._m_kv_span, self._m_recurrent,
            self._m_discarded, self._m_device_decode_s,
            self._m_device_prefill_s, self._m_device_starved_s,
        ):
            c.inc(0)
        if self._n_routed:
            # A model that counts what its expert layers route
            # (decode_cache's ``moe_counts``): the sums since the state
            # was made come back with every block's packed fetch, and
            # the registry takes the difference to the last one seen.
            # The registry's counters are the engine's; which of them an
            # entry of the leaf feeds, and under what labels, is the
            # model's to say (``decode_cache.moe_count_entries``).
            counters = {c.name: c for c in (
                self.metrics.counter(
                    "engine_moe_assignments_total",
                    "(token, chosen expert) pairs the dispatched decode "
                    "steps routed: slots x top-k x expert layers a step",
                ),
                self.metrics.counter(
                    "engine_moe_local_assignments_total",
                    "those of engine_moe_assignments_total whose expert "
                    "this model holds",
                ),
                self.metrics.counter(
                    "engine_moe_expert_tokens_total",
                    "pairs routed to each held expert, summed over layers",
                ),
                self.metrics.counter(
                    "engine_moe_experts_reached_total",
                    "held experts that got at least one pair, summed over "
                    "expert layers and decode steps (each reads its banks)",
                ),
            )}
            self._routed_entries = [
                [(counters[name], labels) for name, labels in feeds]
                for feeds in model.counter_entries()
            ]
            assert len(self._routed_entries) == self._n_routed
            for feeds in self._routed_entries:
                for counter, labels in feeds:
                    counter.inc(0, **labels)
            self._routed_seen = np.zeros((self._n_routed,), np.uint32)
        # The K/V layers by what a step reads of each: its plane's
        # length, its window (a layer that records positions reads under
        # the model's) and the granule in which a decode step reads a
        # row: the kernel's block on a dense plane, asked as the step's
        # trace will ask (under this engine's mesh), or the whole row
        # where the einsum runs. And the host's copy of every
        # slot's device position: set at admission, advanced with each
        # dispatched step, so counting fetches no device value.
        groups: collections.Counter = collections.Counter()
        for length, windowed in kv_layers(self._batch_cache_shapes):
            with use_mesh(mesh):
                block = decode_attention.cache_block_k(
                    dataclasses.replace(cfg, kv_cache_len=length)
                )
            groups[(length, cfg.sliding_window if windowed else None, block or length)] += 1
        self._kv_groups = sorted(groups.items(), key=repr)
        self._row_pos = np.zeros((self._slots,), np.int64)
        self._m_phase = self.metrics.histogram(
            "engine_request_phase_seconds",
            "scheduler phase latency (queue/prefill per request, "
            "prefill_stage/prefill_launch nested inside a prefill: "
            "the host arrays made device arrays, then the program "
            "calls until they return; first_token per pass that "
            "awaits admissions' first tokens; dispatch/fetch/sweep "
            "per k-step decode block shared by all live slots; drain "
            "per forced drain, its fetches and sweeps nested inside)",
        )
        self._m_warmup = self.metrics.histogram(
            "engine_warmup_seconds",
            "wall time of one warmup() call",
            buckets=(1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0),
        )
        self._m_ttft = self.metrics.histogram(
            "engine_ttft_seconds", "time to first token"
        )
        self._m_drains = self.metrics.counter(
            "engine_drain_stalls_total",
            "forced drains of a non-empty in-flight block window "
            "(admission or prefill-admit state changes)",
        )
        self._m_deadline = self.metrics.counter(
            "engine_deadline_expired_total",
            "requests retired with a terminal DeadlineExceeded",
        )
        self._m_watchdog = self.metrics.counter(
            "engine_watchdog_fires_total",
            "scheduler watchdog fires (no loop progress with work in "
            "flight; in-flight requests aborted)",
        )
        self._m_overlap = self.metrics.histogram(
            "engine_overlap_hidden_seconds",
            "host sweep time that ran while >=1 decode block was "
            "still in flight (hidden behind device compute)",
        )
        g_busy = self.metrics.gauge(
            "engine_slots_busy", "KV-cache slots currently occupied"
        )
        g_depth = self.metrics.gauge(
            "engine_queue_depth", "requests waiting for a slot"
        )
        g_slots = self.metrics.gauge(
            "engine_slots", "configured KV-cache slots"
        )
        g_inflight = self.metrics.gauge(
            "engine_inflight_depth",
            "decode blocks dispatched but not yet fetched",
        )
        # set once: the batch cache is allocated whole at the first
        # admission and never resized
        self._cache_bytes = cache_bytes
        g_cache = self.metrics.gauge(
            "engine_cache_bytes",
            "bytes of the batch cache by kind of leaf: kv (a plane of "
            "positions a row), latent (one headless entry a position), "
            "recurrent (one state a row), other (segment ids, "
            "positions, write indices, counters)",
        )
        for kind, n in cache_bytes.items():
            g_cache.set(n, kind=kind)

        def _collect(
            busy=g_busy, depth=g_depth, slots=g_slots,
            inflight=g_inflight,
        ):
            # render-time refresh: these values' truth lives in the
            # scheduler's bookkeeping, not in a mutation stream
            busy.set(
                sum(e is not None for e in self._live)
                + (self._job is not None)
            )
            depth.set(self._queue.qsize())
            slots.set(self._slots)
            inflight.set(len(self._window))

        self.metrics.add_collector(_collect)

        self._emitter = _EmitWorker()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="continuous-batcher"
        )
        self._thread.start()
        if self._watchdog_s is not None:
            threading.Thread(
                target=self._watchdog_loop,
                daemon=True,
                name="engine-watchdog",
            ).start()

    # -- public API ----------------------------------------------------

    def _validate(
        self,
        tokens: list[int],
        max_new_tokens: int,
        temperature: float | None,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int | None = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        deadline_s: float | None = None,
    ) -> None:
        if deadline_s is not None and not (
            isinstance(deadline_s, (int, float))
            and math.isfinite(deadline_s)
            and deadline_s > 0
        ):
            raise ValueError(
                f"deadline_s must be finite and > 0, got {deadline_s!r}"
            )
        if logit_bias is not None:
            if not isinstance(logit_bias, dict) or len(logit_bias) > _BIAS_SLOTS:
                raise ValueError(
                    f"logit_bias must be a dict of at most {_BIAS_SLOTS} "
                    f"token->bias entries, got {logit_bias!r}"
                )
            for t, v in logit_bias.items():
                if not (
                    isinstance(t, int)
                    and 0 <= t < self._model.cfg.vocab_size
                ):
                    raise ValueError(
                        f"logit_bias token id {t!r} outside "
                        f"[0, {self._model.cfg.vocab_size})"
                    )
                if not (
                    isinstance(v, (int, float))
                    and math.isfinite(v)
                    and -100.0 <= v <= 100.0
                ):
                    raise ValueError(
                        f"logit_bias value for {t} must be finite and "
                        f"in [-100, 100], got {v!r}"
                    )
        if seed is not None and not isinstance(seed, int):
            raise ValueError(f"seed must be an int, got {seed!r}")
        for nm, v in (
            ("frequency_penalty", frequency_penalty),
            ("presence_penalty", presence_penalty),
        ):
            # OpenAI's documented range; NaN fails the bounds check
            if v is not None and not (
                isinstance(v, (int, float))
                and math.isfinite(v)
                and -2.0 <= v <= 2.0
            ):
                raise ValueError(
                    f"{nm} must be finite and in [-2, 2], got {v!r}"
                )
        if min_p is not None and not (
            isinstance(min_p, (int, float))
            and math.isfinite(min_p)
            and 0 <= min_p <= 1
        ):
            raise ValueError(
                f"min_p must be finite and in [0, 1], got {min_p!r}"
            )
        if top_k is not None and (not isinstance(top_k, int) or top_k < 1):
            raise ValueError(f"top_k must be an int >= 1, got {top_k!r}")
        if top_p is not None and not (
            isinstance(top_p, (int, float))
            and math.isfinite(top_p)
            and 0 < top_p <= 1
        ):
            # NaN fails every comparison; an explicit finite-and-in-range
            # check rejects it instead of silently disabling truncation
            raise ValueError(
                f"top_p must be finite and in (0, 1], got {top_p!r}"
            )
        if stop:
            if len(stop) > 16:
                # the tail match runs per decoded token inside the
                # SHARED scheduler loop — an unbounded stop list from
                # one tenant would tax every concurrent request
                raise ValueError(
                    f"at most 16 stop sequences, got {len(stop)}"
                )
            for seq in stop:
                if not seq or not all(
                    isinstance(t, int) and 0 <= t for t in seq
                ):
                    raise ValueError(
                        "stop sequences must be non-empty lists of "
                        f"non-negative token ids, got {seq!r}"
                    )
                if len(seq) > 64:
                    raise ValueError(
                        f"stop sequences are capped at 64 tokens, got "
                        f"{len(seq)}"
                    )
        cfg = self._model.cfg
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if temperature is not None and not (
            math.isfinite(temperature) and temperature >= 0
        ):
            # NaN fails every comparison, so a plain `< 0` guard would
            # accept it and then silently decode greedy (NaN > 0 is
            # False in the sampling select)
            raise ValueError(
                f"temperature must be finite and >= 0, got {temperature}"
            )
        if adapter is not None and adapter != 0:
            if self._n_adapters == 0:
                raise ValueError(
                    "this engine's params hold no MultiLoraTensor bank; "
                    "only adapter 0/None (base model) is valid"
                )
            if not 0 <= adapter < self._n_adapters:
                # jnp.take clamps out-of-range gathers silently — a bad
                # id would serve the WRONG tenant's adapter, not error
                raise ValueError(
                    f"adapter {adapter} out of range [0, "
                    f"{self._n_adapters})"
                )
        if self._prefill_chunk is None and len(tokens) > self._widths[-1]:
            # chunked prefill never touches the width buckets — its only
            # cap is the KV capacity checked below
            raise ValueError(
                f"prompt length {len(tokens)} exceeds the largest "
                f"prompt width {self._widths[-1]}"
            )
        if len(tokens) + max_new_tokens > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({len(tokens)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({cfg.max_seq_len})"
            )

    def _enqueue_all(
        self,
        requests: list[tuple[list[int], "queue.Queue | None"]],
        max_new_tokens: int,
        temperature: float | None = None,
        eos_id: int | None = None,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: "int | list[int] | None" = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        decode_block_pin: int | None = None,
        deadline_s: float | None = None,
        trace: str | None = None,
    ) -> list[_Pending]:
        """Validate then enqueue a group ATOMICALLY: either every row is
        accepted or none is — a partially admitted multi-row request
        would burn slots on work the client then discards on its 503.

        ``seed``: None = each row draws an engine seed (independent);
        an int seeds row i as ``seed + i`` (rows stay distinct — n
        identical fanned prompts with one seed must not return n
        identical completions — while the whole call stays
        reproducible); a list gives each row its exact seed."""
        failpoint("engine.submit")
        if isinstance(seed, list):
            if len(seed) != len(requests):
                raise ValueError(
                    f"seed list has {len(seed)} entries for "
                    f"{len(requests)} rows"
                )
            row_seeds = seed
        elif seed is None:
            row_seeds = [None] * len(requests)
        elif not isinstance(seed, int):
            # type-check BEFORE the seed+i derivation below: a str seed
            # must be the documented ValueError (the client-fault class
            # serve_model maps to HTTP 400), not a TypeError from `+`
            raise ValueError(f"seed must be an int, got {seed!r}")
        else:
            row_seeds = [seed + i for i in range(len(requests))]
        for (tokens, _), rs in zip(requests, row_seeds):
            self._validate(
                tokens, max_new_tokens, temperature, adapter, stop,
                top_k, top_p, rs, min_p, frequency_penalty,
                presence_penalty, logit_bias, deadline_s,
            )
        ps = [
            _Pending(
                list(tokens),
                int(max_new_tokens),
                threading.Event(),
                temperature=temperature,
                top_k=top_k,
                top_p=top_p,
                min_p=min_p,
                frequency_penalty=frequency_penalty,
                presence_penalty=presence_penalty,
                logit_bias=dict(logit_bias) if logit_bias else None,
                seed=rs,
                eos_id=eos_id,
                adapter=int(adapter or 0),
                stop=tuple(tuple(q) for q in (stop or ())),
                decode_block_pin=decode_block_pin,
                deadline_s=(
                    None if deadline_s is None else float(deadline_s)
                ),
                submitted_at=time.monotonic(),
                sink=sink,
                trace=trace,
            )
            for (tokens, sink), rs in zip(requests, row_seeds)
        ]
        if self._max_queue is not None and len(ps) > self._max_queue:
            # Permanently unsatisfiable, not transient overload: a 503 +
            # Retry-After would send the client into an infinite retry
            # loop for a request that can NEVER fit the bound.
            raise ValueError(
                f"request has {len(ps)} rows but the queue bound is "
                f"{self._max_queue}; split the request"
            )
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("engine shutting down")
            if (
                self._max_queue is not None
                and self._queue.qsize() + len(ps) > self._max_queue
            ):
                # Shed load instead of queueing unboundedly: a waiting
                # client's budgeted latency is better spent retrying
                # another replica than sitting behind a deep queue.
                raise EngineOverloaded(
                    f"request queue full ({self._max_queue} waiting)"
                )
            self._accepted_total += len(ps)
            self._m_accepted.inc(len(ps))
            for p in ps:
                self._queue.put(p)
        return ps

    def _enqueue(
        self,
        tokens: list[int],
        max_new_tokens: int,
        sink=None,
        temperature: float | None = None,
        eos_id: int | None = None,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int | None = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        decode_block_pin: int | None = None,
        deadline_s: float | None = None,
        trace: str | None = None,
    ) -> _Pending:
        return self._enqueue_all(
            [(tokens, sink)], max_new_tokens, temperature, eos_id,
            adapter, stop, top_k, top_p, seed, min_p,
            frequency_penalty, presence_penalty, logit_bias,
            decode_block_pin, deadline_s, trace=trace,
        )[0]

    def submit(
        self,
        tokens: list[int],
        max_new_tokens: int,
        temperature: float | None = None,
        eos_id: int | None = None,
        return_logprobs: bool = False,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int | None = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        deadline_s: float | None = None,
        trace: str | None = None,
    ) -> "list[int] | tuple[list[int], list[float]]":
        """Blocking decode. ``temperature``, ``top_k``, ``top_p`` and
        ``eos_id`` override the engine-wide defaults FOR THIS REQUEST
        (the sampling knobs are traced per-row inputs — no
        recompilation; temperature 0 = greedy; eos is host-side
        retirement bookkeeping, a NEGATIVE value disables EOS stopping
        entirely for this request).
        ``return_logprobs``: also return each emitted token's logprob
        under the raw model distribution (the /score convention).
        ``adapter`` selects the row's MultiLoraTensor bank slot when the
        params carry one (multi-tenant serving; 0/None = base model),
        traced per-row — mixed-adapter batches cost no recompilation.
        ``deadline_s``: wall-clock budget from submission; on expiry the
        request fails with a terminal :class:`DeadlineExceeded` instead
        of decoding on for a caller that stopped waiting."""
        p = self._enqueue(
            tokens, max_new_tokens, temperature=temperature,
            eos_id=eos_id, adapter=adapter, stop=stop,
            top_k=top_k, top_p=top_p, seed=seed, min_p=min_p,
            frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty,
            logit_bias=logit_bias,
            deadline_s=deadline_s,
            trace=trace,
        )
        p.event.wait()
        if p.error is not None:
            raise p.error
        if return_logprobs:
            return p.result, p.logprobs
        return p.result

    def submit_many(
        self,
        prompts: list[list[int]],
        max_new_tokens: int,
        temperature: float | None = None,
        eos_id: int | None = None,
        return_logprobs: bool = False,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: "int | list[int] | None" = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        deadline_s: float | None = None,
        return_versions: bool = False,
        trace: str | None = None,
    ) -> "list[list[int]] | tuple[list[list[int]], list[list[float]]]":
        """Blocking decode of several prompts admitted ATOMICALLY (all
        rows accepted or an EngineOverloaded/ValueError before any row
        enters the queue) — the multi-row /generate path. Rows decode
        concurrently, interleaved with other requests' rows.
        ``return_versions``: also return each row's per-request
        ``weights_version`` stamp (appended as the trailing element of
        the return tuple) — the rollout coherence surface."""
        ps = self._enqueue_all(
            [(p, None) for p in prompts],
            max_new_tokens,
            temperature,
            eos_id,
            adapter,
            stop,
            top_k,
            top_p,
            seed,
            min_p,
            frequency_penalty,
            presence_penalty,
            logit_bias,
            None,
            deadline_s,
            trace=trace,
        )
        for p in ps:
            p.event.wait()
        for p in ps:
            if p.error is not None:
                raise p.error
        out: tuple = ([p.result for p in ps],)
        if return_logprobs:
            out += ([p.logprobs for p in ps],)
        if return_versions:
            out += ([p.weights_version for p in ps],)
        return out if len(out) > 1 else out[0]

    def stream(
        self,
        tokens: list[int],
        max_new_tokens: int,
        temperature: float | None = None,
        eos_id: int | None = None,
        yield_logprobs: bool = False,
        adapter: int | None = None,
        stop: "list[list[int]] | None" = None,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int | None = None,
        min_p: float | None = None,
        frequency_penalty: float | None = None,
        presence_penalty: float | None = None,
        logit_bias: "dict[int, float] | None" = None,
        deadline_s: float | None = None,
        trace: str | None = None,
    ):
        """Yield completion tokens AS THEY DECODE (one engine step of
        latency each) instead of blocking for the full result.

        Validation and enqueue happen EAGERLY, at the call — callers
        like the HTTP streaming path must see bad-prompt ValueErrors
        before they commit a 200 status to the wire. The iterator
        raises if the request fails mid-decode; closing it early (or
        dropping it) CANCELS the request: a decoding row frees its slot
        at the scheduler's next step and retires with its partial
        output, a queued or mid-prefill request resolves empty without
        ever taking a slot — an abandoned consumer never burns its
        remaining budget. ``yield_logprobs``: yield ``(token,
        logprob)`` pairs instead of bare tokens."""
        p = self._enqueue(
            tokens,
            max_new_tokens,
            sink=queue.Queue(),
            temperature=temperature,
            eos_id=eos_id,
            adapter=adapter,
            stop=stop,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
            min_p=min_p,
            frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty,
            logit_bias=logit_bias,
            deadline_s=deadline_s,
            trace=trace,
        )

        # An explicit iterator, NOT a generator: close() on a
        # never-started generator skips its finally block entirely, so
        # a consumer that abandons the stream before the first next()
        # would never cancel. This handle cancels from close()/GC
        # regardless of iteration state.
        return _Stream(p, yield_logprobs)

    def warmup(self) -> None:
        """Pre-compile every program a request could hit (the decode
        step, the admit scatter, each prompt-width prefill or the
        chunk/sample pair) by running one thrown-away token through
        each width bucket. Without this the FIRST real request pays
        every XLA compile in its TTFT — seconds to minutes on TPU —
        which is exactly when a load balancer health-checks a fresh
        replica. Call after construction, before serving traffic
        (``--gen-warmup``). Thread-safe via the ordinary submit path;
        the throwaway requests are excluded from the latency averages
        only insofar as they are real requests — warm up BEFORE
        exposing /stats to dashboards if that matters."""
        # Budget 2 with eos DISABLED on (at least) one request: a
        # 1-token budget retires at admission and the decode step —
        # the program every subsequent token runs — would never
        # compile; and without eos_id=-1 a sampled first token equal to
        # the engine's default eos could nondeterministically retire
        # the row before a step runs.
        # Watchdog suspended for the duration: first-compile stalls are
        # indistinguishable from the wedges it hunts, and warmup exists
        # precisely to take them before traffic.
        self._watchdog_suspended = True
        self._warming = True
        span = self._tracer.span("engine.warmup")
        try:
            with span:
                self._warmup_requests()
        finally:
            self._warming = False
            self._watchdog_suspended = False
        self._m_warmup.observe(span.dur)

    def _warmup_requests(self) -> None:
        max_seq = self._model.cfg.max_seq_len
        if self._prefill_chunk is not None:
            # chunk + sample1 + admit + step compile on any prompt;
            # cover a multi-chunk prompt so the window-shift math runs
            n = max(1, min(self._prefill_chunk + 1, max_seq - 2))
            self.submit([0] * n, 2, eos_id=-1)
        else:
            step_warmed = False
            prev = 0
            for w in self._widths:
                # the longest VALID prompt that still maps to this
                # bucket compiles its prefill (a width at max_seq_len
                # can only be reached by shorter prompts — budget >= 1)
                n = min(w, max_seq - 1)
                if n <= prev:
                    continue  # no valid request can reach this bucket
                if not step_warmed and n + 2 <= max_seq:
                    self.submit([0] * n, 2, eos_id=-1)
                    step_warmed = True
                else:
                    self.submit([0] * n, 1)
                prev = w
            if not step_warmed:
                self.submit([0], 2, eos_id=-1)
        if self._decode_block > 1:
            # The k=1 program still runs whenever an admission or chunk
            # job is pending, but every warmup submit above was a lone
            # request (empty queue) and so compiled only the k-block
            # scan. Pin the block to 1 THROUGH the warmup request
            # itself (decode_block_pin rides the _Pending; the
            # scheduler caps k at any live row's pin) so one throwaway
            # request compiles the single-step program WITHOUT mutating
            # the shared self._decode_block from the caller thread —
            # concurrent live traffic keeps its full block, and /stats
            # never transiently reports decode_block=1.
            p = self._enqueue([0], 2, eos_id=-1, decode_block_pin=1)
            p.event.wait()
            if p.error is not None:
                raise p.error
        if self._prefix_store is not None:
            # drop the throwaway prompts' entries — each would pin a
            # full single-row KV cache of HBM until evicted. Safe here:
            # submit() returned, so the scheduler is blocked on the
            # queue and not touching the store.
            self._prefix_store.clear()

    # -- hot weight swap (zero-downtime rollout) ----------------------

    @property
    def weights_version(self) -> str:
        """Label of the weights currently serving (written only by the
        scheduler thread at swap time; observability readers tolerate
        one-swap staleness — per-request coherence comes from the
        ``_Pending.weights_version`` stamp, not this property)."""
        return self._weights_version

    def current_weights(self) -> "tuple[str, object]":
        """``(version, params)`` of the tree currently serving — the
        rollback retention surface: a rollout controller snapshots this
        (a reference, not a copy — jax arrays are immutable) before
        swapping, and re-installs it on rollback. Read it only while
        the seat is quiesced/held if the pair must be mutually
        consistent."""
        return self._weights_version, self._params

    def swap_weights(
        self,
        new_params,
        *,
        version: str,
        kind: str = "full",
        timeout: float = 120.0,
    ) -> str:
        """Replace the serving weights WITHOUT restarting the engine.

        All expensive work happens on the CALLER thread: the update is
        validated against the running tree (structure, per-leaf
        shape/dtype — any mismatch is a synchronous
        :class:`WeightsIncompatible`, and the engine keeps serving its
        current version) and placed on device mirroring each running
        leaf's sharding. The scheduler then installs the prepared tree
        between decode blocks — a pointer flip, so the serving stall is
        one in-flight-window drain, not a restart. The prefix cache is
        cleared at install (stored K/V was computed under the old
        weights; resuming prefill from it post-swap would serve stale
        state), and compiled programs are reused (same shapes/dtypes/
        shardings ⇒ no recompile).

        ``kind='full'``: ``new_params`` carries the exact pytree of the
        running weights — host numpy or jax arrays; a
        ``compute.elastic.host_snapshot`` of a co-trained state's
        params is exactly this shape. ``kind='lora'``:
        ``new_params`` is a nested mapping mirroring the params dict
        down to LoRA kernels, each as ``{"a": ..., "b": ...}`` — only
        the factors transfer, the resident base weights are reused by
        reference (the cheap adapter-only swap; see
        ``serving.rollout.lora_state``).

        Requests decoding ACROSS the install finish under the new
        weights and are stamped with the new version at retirement —
        drain first (the fleet rollout controller does) when a request
        must never span versions. Returns the installed version label.
        """
        if kind not in ("full", "lora"):
            raise ValueError(f"kind must be 'full' or 'lora', got {kind!r}")
        version = str(version)
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("engine shutting down")
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
        placed = self._place_update(new_params, kind)
        req = _SwapRequest(
            placed=placed, version=version, event=threading.Event()
        )
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("engine shutting down")
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
            self._pending_swap = req
        self._queue.put(self._WAKE)  # an idle scheduler must notice
        if not req.event.wait(timeout):
            with self._submit_lock:
                if self._pending_swap is req:
                    self._pending_swap = None
                    raise TimeoutError(
                        f"weight swap to {version!r} not applied within "
                        f"{timeout}s (scheduler busy or wedged)"
                    )
            # the scheduler claimed it just as we timed out: the
            # install is in flight — wait it out briefly
            req.event.wait(10.0)
        if not req.event.is_set():
            raise TimeoutError(
                f"weight swap to {version!r} not applied within {timeout}s"
            )
        if req.error is not None:
            raise req.error
        return version

    def _place_update(self, new_params, kind: str):
        """Validate + device-place an update against the running tree
        (caller thread). Raises :class:`WeightsIncompatible` on any
        structure/shape/dtype mismatch BEFORE anything is installed."""
        if kind == "lora":
            return self._graft_lora(self._params, new_params, "params")
        old_paths, old_def = jax.tree_util.tree_flatten_with_path(
            self._params
        )
        new_leaves, new_def = jax.tree.flatten(new_params)
        if old_def != new_def:
            raise WeightsIncompatible(
                "full-swap tree structure differs from the running "
                f"weights ({new_def.num_leaves} leaves vs "
                f"{old_def.num_leaves} running; static fields — e.g. a "
                "LoRA scale — count too)"
            )
        placed = [
            self._place_leaf(old, new, jax.tree_util.keystr(path))
            for (path, old), new in zip(old_paths, new_leaves)
        ]
        return jax.tree.unflatten(old_def, placed)

    @staticmethod
    def _place_leaf(old, new, where: str):
        if new is old:
            return old  # re-install of a retained tree: nothing to move
        shape = tuple(getattr(new, "shape", ()))
        dtype = getattr(new, "dtype", None)
        if shape != tuple(old.shape) or (
            dtype is not None and np.dtype(dtype) != np.dtype(old.dtype)
        ):
            raise WeightsIncompatible(
                f"leaf {where}: update has shape {shape} dtype {dtype}, "
                f"running weights have {tuple(old.shape)} "
                f"{np.dtype(old.dtype)}"
            )
        sharding = getattr(old, "sharding", None)
        if sharding is not None:
            return jax.device_put(new, sharding)
        return jax.device_put(new)

    def _graft_lora(self, old_node, upd, where: str):
        """Adapter-only update: descend the running tree along the
        update's keys and replace exactly the LoRA ``a``/``b`` factors,
        keeping every base weight by reference (zero transfer cost for
        the frozen bulk)."""
        from tensorflowonspark_tpu.ops.lora import (
            LoraTensor,
            MultiLoraTensor,
        )

        if isinstance(old_node, (LoraTensor, MultiLoraTensor)):
            if (
                not isinstance(upd, dict)
                or set(upd) != {"a", "b"}
            ):
                raise WeightsIncompatible(
                    f"{where}: adapter update must be an {{'a','b'}} "
                    f"mapping, got {type(upd).__name__} "
                    f"{sorted(upd) if isinstance(upd, dict) else ''}"
                )
            return old_node.replace(
                a=self._place_leaf(old_node.a, upd["a"], where + ".a"),
                b=self._place_leaf(old_node.b, upd["b"], where + ".b"),
            )
        if isinstance(old_node, dict):
            if not isinstance(upd, dict):
                raise WeightsIncompatible(
                    f"{where}: expected a mapping along the params "
                    f"tree, got {type(upd).__name__}"
                )
            unknown = set(upd) - set(old_node)
            if unknown:
                raise WeightsIncompatible(
                    f"{where}: update names keys absent from the "
                    f"running weights: {sorted(unknown)}"
                )
            return {
                k: (
                    self._graft_lora(v, upd[k], f"{where}/{k}")
                    if k in upd
                    else v
                )
                for k, v in old_node.items()
            }
        raise WeightsIncompatible(
            f"{where}: adapter update path does not terminate at a "
            f"LoRA kernel (found {type(old_node).__name__}); use "
            "kind='full' for non-LoRA weights"
        )

    def _apply_pending_swap(self) -> None:
        """Scheduler thread: install a prepared swap between decode
        blocks. In-flight blocks were dispatched against the old tree
        and stay functionally valid — sweep them out, then flip."""
        with self._submit_lock:
            req, self._pending_swap = self._pending_swap, None
        if req is None:
            return
        self._drain_window("swap")
        self._params = req.placed
        self._weights_version = req.version
        self._weights_swaps += 1
        # the swap joins every in-flight request's timeline: a traced
        # completion whose tokens span the install shows exactly where
        # its weights changed (rollout coherence evidence)
        reqtrace.mark("engine.weights_swap", version=req.version)
        if self._prefix_store is not None:
            # stored prefixes' K/V was computed under the OLD weights —
            # a post-swap hit would resume prefill from stale state
            # (the router drops its affinity entries via replica_reset)
            self._prefix_store.clear()
        req.event.set()
        logger.info(
            "engine weights swapped to %r (swap #%d)",
            req.version,
            self._weights_swaps,
        )

    def _abort_pending_swap(self, err: BaseException) -> None:
        """Fail a waiting swap when the scheduler exits before applying
        it (shutdown or loop death) — its caller must not hang."""
        with self._submit_lock:
            req, self._pending_swap = self._pending_swap, None
        if req is not None:
            req.error = RuntimeError(f"weight swap aborted: {err}")
            req.event.set()

    # -- live scheduler knobs (autotune actuation) --------------------

    def set_knobs(
        self,
        *,
        decode_block: int | None = None,
        pipeline_depth: int | None = None,
        timeout: float = 30.0,
    ) -> dict:
        """Change ``decode_block`` and/or ``pipeline_depth`` on a RUNNING
        engine — the autotune actuation path for the engine knobs.

        Both knobs are owned by the scheduler thread (``decode_block``
        picks the compiled block program each iteration;
        ``pipeline_depth`` bounds the dispatch-ahead window), so the
        change is staged here and installed by the scheduler between
        decode blocks, exactly like :meth:`swap_weights`: the install
        drains the in-flight window first (a depth shrink under
        dispatched-but-unfetched blocks would corrupt the window
        accounting), then rebinds — a new ``decode_block`` compiles its
        block program lazily at first use (``_block_cache``). Returns
        the knob values actually in effect after the install.
        """
        if decode_block is None and pipeline_depth is None:
            return {
                "decode_block": self._decode_block,
                "pipeline_depth": self._pipeline_depth,
            }
        if decode_block is not None and int(decode_block) < 1:
            raise ValueError(
                f"decode_block must be >= 1, got {decode_block}"
            )
        if pipeline_depth is not None and int(pipeline_depth) < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        req = _KnobRequest(
            decode_block=(
                None if decode_block is None else int(decode_block)
            ),
            pipeline_depth=(
                None if pipeline_depth is None else int(pipeline_depth)
            ),
            event=threading.Event(),
        )
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("engine shutting down")
            if self._pending_knobs is not None:
                raise RuntimeError("a knob change is already pending")
            self._pending_knobs = req
        self._queue.put(self._WAKE)  # an idle scheduler must notice
        if not req.event.wait(timeout):
            with self._submit_lock:
                if self._pending_knobs is req:
                    self._pending_knobs = None
                    raise TimeoutError(
                        f"knob change not applied within {timeout}s "
                        "(scheduler busy or wedged)"
                    )
            # the scheduler claimed it just as we timed out: the
            # install is in flight — wait it out briefly
            req.event.wait(10.0)
        if not req.event.is_set():
            raise TimeoutError(
                f"knob change not applied within {timeout}s"
            )
        if req.error is not None:
            raise req.error
        return {
            "decode_block": self._decode_block,
            "pipeline_depth": self._pipeline_depth,
        }

    def _apply_pending_knobs(self) -> None:
        """Scheduler thread: install a staged knob change between decode
        blocks. The caller (``_loop``) rebinds its local ``depth``
        immediately after — it snapshots ``_pipeline_depth`` once at
        loop entry."""
        with self._submit_lock:
            req, self._pending_knobs = self._pending_knobs, None
        if req is None:
            return
        # in-flight blocks were dispatched under the old knobs — sweep
        # them out so the window restarts under the new depth/block
        self._drain_window("knobs")
        if req.decode_block is not None:
            self._decode_block = max(1, int(req.decode_block))
        if req.pipeline_depth is not None:
            self._pipeline_depth = max(1, int(req.pipeline_depth))
        reqtrace.mark(
            "engine.knobs",
            decode_block=self._decode_block,
            pipeline_depth=self._pipeline_depth,
        )
        req.event.set()
        logger.info(
            "engine knobs applied: decode_block=%d pipeline_depth=%d",
            self._decode_block,
            self._pipeline_depth,
        )

    def _abort_pending_knobs(self, err: BaseException) -> None:
        """Fail a waiting knob change when the scheduler exits before
        applying it — its caller must not hang."""
        with self._submit_lock:
            req, self._pending_knobs = self._pending_knobs, None
        if req is not None:
            req.error = RuntimeError(f"knob change aborted: {err}")
            req.event.set()

    @contextlib.contextmanager
    def _phase(self, phase: str, **args):
        """Measure one scheduler phase into both surfaces: the span
        ring (``/stats`` percentiles, Chrome-trace export, XLA-timeline
        bridge) and the Prometheus phase histogram, with the span's
        own duration so that all of them describe one interval on one
        clock. Also names the phase for the watchdog/close diagnostics
        ("stuck in fetch"); phases nest (a drain holds its fetches and
        sweeps), and the outer name comes back on exit."""
        outer = self._current_phase
        self._current_phase = phase
        span = self._tracer.span("engine." + phase, **args)
        try:
            with span:
                yield
        finally:
            self._current_phase = outer
        self._m_phase.observe(span.dur, phase=phase)

    def _clock_launched(self) -> None:
        """A launch call (a decode block, a prefill and its admit, a
        chunk) has returned: from here the chip has something to run.
        Starts the completion clock where the engine had held nothing,
        and ends the starved front of an interval that began with
        nothing in flight."""
        if self._clock_fed_at is None:
            self._clock_fed_at = time.perf_counter()
            if self._clock_at is None:
                self._clock_at = self._clock_fed_at

    def _clock_completed(self, ended_by, in_flight: bool) -> None:
        """An awaited result is on the host: the interval since the
        last such moment goes to ``ended_by`` (the seconds counter of
        the program that completed), less its starved front.
        ``in_flight``: whether the chip still has launched work, a
        block in the window or a first token pending."""
        now = time.perf_counter()
        if self._clock_at is not None:
            gap = now - self._clock_at
            starved = (self._clock_fed_at or self._clock_at) - self._clock_at
            self._m_device_starved_s.inc(starved)
            ended_by.inc(gap - starved)
            self._m_gap.observe(gap)
        self._clock_at = now
        self._clock_fed_at = now if in_flight else None

    def _drop_window(self) -> None:
        """Drop the in-flight blocks unfetched (every row they decode
        for has gone): all their slot-steps are discards. They end no
        interval of the completion clock; their time on the chip falls
        to the next completion."""
        self._m_discarded.inc(sum(k * rows for k, _, rows in self._window))
        self._window.clear()

    def _observe_queue_wait(self, p: _Pending) -> None:
        now = time.monotonic()
        dur = now - p.submitted_at
        self._tracer.record("engine.queue", dur)
        self._m_phase.observe(dur, phase="queue")
        if p.trace is not None:
            reqtrace.segment(p.trace, "engine.queue", dur)
            p.trace_mark = now

    def health(self) -> dict:
        """Liveness vs readiness, split (the ``/healthz`` contract —
        docs/ROBUSTNESS.md "Serving fleet"): ``live`` = the scheduler
        thread exists and runs; ``ready`` = live AND warmup is not in
        progress AND the engine is not closed/draining. A warming or
        draining engine is alive (do not restart it) but must not
        receive new traffic (do not route to it)."""
        live = self._thread.is_alive()
        return {
            "live": live,
            "ready": bool(live and not self._warming and not self._closed),  # lint: lockfree-read: advisory health probe; a torn one-bool read is benign and the submit lock must not be taken per probe
            "warming": self._warming,
            "closed": self._closed,  # lint: lockfree-read: same advisory snapshot as above
            "weights_version": self._weights_version,
        }

    def unresolved(self) -> int:
        """Accepted-but-not-yet-resolved request count — the drain
        quiescence metric ``close(drain=True)`` polls, exposed for
        fleet supervisors that must know when a DRAINING replica has
        run out its in-flight work."""
        return self._accepted_total - (  # lint: lockfree-read: monotonic counters; a stale read only delays one supervisor poll
            self.completed + self._failed_total
        )

    def stats(self) -> dict:
        """Scheduler observability (served at the HTTP ``/stats``
        endpoint): slot occupancy, queue depth, lifetime counters."""
        # a chunked prefill holds a reserved slot that is not yet in
        # _live — it IS load, so capacity math must see it
        busy = sum(e is not None for e in self._live) + (
            self._job is not None
        )
        done = self._latency_n
        return {
            "slots": self._slots,
            "slots_busy": busy,
            "queue_depth": self._queue.qsize(),
            "steps": self.steps,
            "decode_block": self._decode_block,
            "pipeline_depth": self._pipeline_depth,
            # dispatched-but-unfetched decode blocks right now (the
            # overlap window); sampled without a lock — a point-in-time
            # observability read, like slots_busy
            "inflight_depth": len(self._window),
            # forced window drains (admission / final-chunk prefill
            # admit under a non-empty window)
            "drain_stalls": self._drain_stalls,
            # host sweep time that ran while >=1 block was in flight —
            # scheduler cost the pipeline hid behind device compute
            "overlap_hidden_ms": round(self._overlap_hidden_s * 1e3, 3),
            "admitted": self.admitted,
            "completed": self.completed,
            "cancelled": self.cancelled,
            # accepted-but-unresolved (the drain quiescence metric;
            # counts queued requests `admitted` cannot see and uses
            # the same accounting close(drain=True) polls) — remote
            # fleet supervisors read it off /stats
            "unresolved": self.unresolved(),
            "tokens_emitted": self.tokens_emitted,
            # the batch cache by kind of leaf (engine_cache_bytes)
            "cache_bytes": dict(self._cache_bytes),
            # degradation surface: terminal deadline expiries, watchdog
            # fires, and (after close()) whether the scheduler actually
            # wound down inside its join timeout — None while running
            "deadline_expired": self.deadline_expired,
            "watchdog_fires": self.watchdog_fires,
            "stopped_cleanly": self._stopped_cleanly,
            # hot-swap surface: the serving weights label + how many
            # swaps this engine has applied (scheduler-thread writes;
            # point-in-time reads like the rest of /stats)
            "weights_version": self._weights_version,
            "weights_swaps": self._weights_swaps,
            "prefill_in_progress": self._job is not None,
            # queue wait + prefill, averaged over completed requests
            "ttft_avg_ms": round(self._ttft_sum / done * 1e3, 3)
            if done
            else None,
            "request_avg_ms": round(self._duration_sum / done * 1e3, 3)
            if done
            else None,
            # Per-phase latency percentiles over the span ring's
            # sliding window. UNITS DIFFER BY PHASE: queue and prefill
            # are per REQUEST (one observation each); dispatch, fetch
            # and sweep are per k-step decode BLOCK shared by every
            # live slot — so comparing them to the per-request phases
            # requires dividing by k×occupancy. With pipeline_depth>1,
            # fetch measures the wait for the OLDEST in-flight block
            # while younger blocks keep the device busy — it shrinks
            # as overlap hides host work, which is the point.
            "phase_ms": {
                name.split(".", 1)[1]: v
                for name, v in self._tracer.summary(
                    prefix="engine."
                ).items()
            },
            "closed": self._closed,  # lint: lockfree-read: advisory /stats snapshot; a torn one-bool read is benign and the submit lock must not be taken per scrape
            **(
                {"adapters": self._n_adapters}
                if self._n_adapters
                else {}
            ),
            **(
                {
                    "prefix_cache_entries": len(self._prefix_store),
                    "prefix_hits": self._prefix_store.hits,
                    "prefix_misses": self._prefix_store.misses,
                    "prefix_tokens_saved": self._prefix_store.tokens_saved,
                }
                if self._prefix_store is not None
                else {}
            ),
            **(
                {
                    f"prefix_{k}": v
                    for k, v in self._prefix_l2.stats().items()
                }
                if self._prefix_l2 is not None
                else {}
            ),
        }

    def close(self, drain: bool = False, drain_timeout: float = 300.0) -> None:
        """Stop the loop. Default: queued requests fail and live rows
        are failed once the STOP marker is reached (abrupt shutdown).
        ``drain=True``: refuse new submits immediately but let every
        already-accepted request (queued, prefilling, decoding) run to
        completion first — the production drain — up to
        ``drain_timeout`` seconds before falling back to the abrupt
        path."""
        with self._submit_lock:
            if self._closed:
                return
            self._closed = True
            if not drain:
                self._queue.put(self._STOP)
        if drain:
            deadline = time.monotonic() + drain_timeout
            while time.monotonic() < deadline:
                # Quiescence by ACCOUNTING, not structure-sampling:
                # every accepted request resolves as exactly one of
                # completed/failed, so this cannot race the scheduler's
                # queue-pop → _inflight → slot handoffs (a structural
                # check could observe the instant a request is in none
                # of those places and wrongly declare idle).
                unresolved = self._accepted_total - (  # lint: lockfree-read: drain quiescence poll; monotonic counter, a stale read only delays one 50ms iteration and taking the submit lock would contend with live submits
                    self.completed + self._failed_total
                )
                if unresolved == 0:
                    break
                time.sleep(0.05)
            self._queue.put(self._STOP)
        # The queued STOP only wakes a loop BLOCKED on the queue; a loop
        # busy decoding full slots never pops it (the admit loop breaks
        # first). The event makes the abrupt path reach that case too —
        # checked at the top of every scheduler iteration.
        self._stop_now.set()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            # Don't proceed silently past a wedged scheduler: name where
            # it is stuck (span-phase tracking) and surface the fact in
            # /stats via stopped_cleanly.
            logger.warning(
                "engine scheduler did not stop within 60s "
                "(stuck in %s); resources may leak until process exit",
                self._current_phase or "between phases",
            )
            self._stopped_cleanly = False
        else:
            self._stopped_cleanly = True
        if self._prefix_store is not None and not self._thread.is_alive():
            # Drop the stored KV buffers (up to capacity × a full
            # single-row cache of HBM) — a closed-but-still-referenced
            # engine must not pin them against a replacement engine's
            # budget. Only once the loop thread is truly gone: it reads
            # the store without a lock.
            self._prefix_store.clear()
        if self._prefix_l2 is not None and not self._thread.is_alive():
            # Stop the L2 filler thread (pending offers drain or drop);
            # the underlying client/tier belongs to the fleet, not this
            # engine, so only the facade winds down here.
            self._prefix_l2.close()

    # -- compiled pieces ----------------------------------------------

    def _constrain_cache(self, cache):
        """Pin KV-cache leaves to the engine's TP sharding (heads on
        'model', batch replicated) at every compiled-program boundary,
        so sharding propagation can't drift to a layout whose per-step
        all-gathers would swamp the HBM-bound decode. No-op without a
        mesh."""
        if self._mesh is None:
            return cache
        from tensorflowonspark_tpu.compute import layout

        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, layout.serve_cache_sharding(self._mesh, x)
            ),
            cache,
        )

    def _decode_body(self):
        """One decode step — the body shared by every k in
        :meth:`_block_fn` (k=1 is the old per-token program; k>1 wraps
        it in a ``lax.scan``)."""
        model = self._model
        constrain = self._constrain_cache

        def body(
            params, cache, tok, pos, temps, ads, kps, seeds, pens,
            counts, bias_ids, bias_vals, gates,
        ):
            # the mesh is ambient while the step is traced: under one
            # the cached attention keeps the einsum GSPMD can partition
            with use_mesh(self._mesh):
                logits, updated = model.apply(
                    {"params": params, "cache": cache},
                    tok[:, None],
                    positions=pos[:, None],
                    decode=True,
                    padded=True,
                    adapter_ids=ads,
                    mutable=["cache"],
                )
            # The per-step logprob costs one (slots, vocab) fp32
            # log_softmax (~1 MB at 8x32k ≈ a few µs of HBM time vs the
            # ~GB of weight reads bounding the step) and a (slots,)
            # host fetch that rides the existing token fetch — cheap
            # enough to keep unconditional rather than doubling the
            # compiled-variant count.
            # the sampled token will occupy position pos+1 (unclamped:
            # the cache-write clamp below must not alias two counters)
            nxt, lp = _sample_rows(
                logits[:, -1], temps, kps, seeds, pos + 1, pens, counts,
                bias_ids, bias_vals, gates,
            )
            # the emitted token enters its row's generated-token counts
            # (cond: all-unpenalized batches never write the plane).
            # Inside a block this runs per scan iteration, so penalties
            # see every token of the block as it lands — identical to
            # single stepping.
            counts = jax.lax.cond(
                gates[2],
                lambda c: c + jax.nn.one_hot(
                    nxt, c.shape[-1], dtype=c.dtype
                ),
                lambda c: c,
                counts,
            )
            # Clamp so a retired-but-not-yet-reused row parked at the
            # cache edge never scatters out of bounds (its writes are
            # garbage either way; admission overwrites the whole row).
            nxt_pos = jnp.minimum(pos + 1, model.cfg.max_seq_len - 1)
            return constrain(updated["cache"]), nxt, nxt_pos, lp, counts

        return body

    def _per_kv_layer(self, count) -> float:
        """``count(length, window, block)`` over the K/V layers, each at
        its own, as the mean a layer: where every layer has the same
        plane, exactly that layer's count."""
        layers = sum(n for _, n in self._kv_groups)
        total = sum(n * count(*g) for g, n in self._kv_groups)
        if not layers:
            return 0
        return total // layers if len(self._kv_groups) == 1 else total / layers

    def _prefill_pairs(self, width: int, handed_cache: bool) -> dict:
        """What one dispatched prefill program of ``width`` positions
        scores and spans, in (query, key) pairs a layer and head: the
        ``engine.prefill`` span's arguments, and what
        :meth:`_count_prefill` adds to the registry."""
        return {
            "kv_scored": self._per_kv_layer(
                lambda length, window, _: width * keys_scored(
                    width, length, handed_cache, window
                )
            ),
            "kv_span": self._per_kv_layer(lambda length, *_: width * length),
        }

    def _count_prefill(
        self, tokens: int, width: int, handed_cache: bool
    ) -> None:
        pairs = self._prefill_pairs(width, handed_cache)
        self._m_prefill_tokens.inc(tokens)
        self._m_prefill_positions.inc(width)
        self._m_prefill_kv_scored.inc(pairs["kv_scored"])
        self._m_prefill_kv_span.inc(pairs["kv_span"])

    def _count_kv_positions(self, k: int) -> None:
        """Count what the k steps just dispatched read of the cache
        and what they span, and advance the host's copy of the slots'
        positions as :meth:`_decode_body` advances the device's (every
        slot steps, live or not, and stops at the cache's edge)."""
        cfg = self._model.cfg
        at = np.minimum(
            self._row_pos[:, None] + np.arange(k), cfg.max_seq_len - 1
        )
        self._row_pos = np.minimum(
            self._row_pos + k, cfg.max_seq_len - 1
        )
        self._m_kv_span.inc(
            self._per_kv_layer(lambda length, *_: at.size * length)
        )
        self._m_kv_read.inc(
            self._per_kv_layer(
                lambda length, window, block: int(
                    decode_attention.positions_read(
                        at + 1, length, window, block
                    ).sum()
                )
            )
        )

    def _block_compiler_options(self):
        """What the decode block asks of the TPU compiler, None
        elsewhere (the option is the TPU backend's; the platform is that
        of the devices the weights are on, so a compile for a described
        chip takes it too).

        With the cache aliased from input to output, the compiler's
        memory-space assignment stages about half of the K/V planes
        through on-chip memory in every step: it prefetches a whole
        plane, scatters the step's 16 rows into the copy and writes the
        whole plane back, 84 MB a plane at the benchmark's size, on a
        step that is bound by HBM as it is. The ratio tells it to leave
        a buffer in HBM unless its uses there read nearly as many bytes
        as the copies in and out move; the planes are then updated in
        place, as they were when the loop's carry was a private copy."""
        leaf = jax.tree_util.tree_leaves(self._params)[0]
        if next(iter(leaf.sharding.device_set)).platform != "tpu":
            return None
        return {"xla_tpu_msa_inefficient_use_to_copy_ratio": 0.9}

    def _block_fn(self, k: int):
        """Jitted k-step decode block. Per-instance memo like
        :meth:`_prefill_fn` (a class-level cache would pin closed
        engines). Returns ``(cache, tok, pos, packed, counts)`` where
        ``packed`` is ONE (2, k, slots) int32 array — row 0 the sampled
        tokens, row 1 their fp32 logprobs bitcast to int32 (and, for a
        model that counts what it routes, further rows holding the
        summed ``moe_counts``: :meth:`_count_routed`) — so the
        host retires a whole block with a single device fetch instead
        of 2·k transfers. Packing INTO int32 (not tokens into f32) is
        deliberate: token ids bitcast to f32 land in the denormal
        range, where a flushing/canonicalizing copy path would silently
        zero them; integer copies are never flushed."""
        cached = self._block_cache.get(k)
        if cached is not None:
            return cached
        body = self._decode_body()

        # The carried batch state is donated: the scan updates the one
        # batch cache in place (no copy into the loop's carry, no
        # second copy alive per block in flight), and the caller's
        # passed-in cache/tok/pos/counts are DELETED by the call — the
        # scheduler rebinds them from the results and never reads the
        # old values. The per-row knobs are read by the next block
        # again and not returned; params is shared (hot swap, replicas).
        @functools.partial(
            jax.jit,
            donate_argnames=("cache", "tok", "pos", "counts"),
            compiler_options=self._block_compiler_options(),
        )
        def block(
            params, cache, tok, pos, temps, ads, kps, seeds, pens,
            counts, bias_ids, bias_vals, gates,
        ):
            def scan_body(carry, _):
                cache, tok, pos, counts = carry
                cache, nxt, nxt_pos, lp, counts = body(
                    params, cache, tok, pos, temps, ads, kps, seeds,
                    pens, counts, bias_ids, bias_vals, gates,
                )
                return (cache, nxt, nxt_pos, counts), (nxt, lp)

            (cache, tok, pos, counts), (toks, lps) = jax.lax.scan(
                scan_body, (cache, tok, pos, counts), None, length=k
            )
            packed = jnp.stack(
                [toks, jax.lax.bitcast_convert_type(lps, jnp.int32)]
            )
            routed = [
                leaf for path, leaf
                in jax.tree_util.tree_leaves_with_path(cache)
                if leaf_kind(path) == "counter"
            ]
            if routed:
                # the layers' routed counts ride the same fetch, as
                # whole (k, slots) rows after the tokens and logprobs
                routed = sum(routed)
                rows = -(-routed.size // toks.size)
                routed = jnp.pad(routed, (0, rows * toks.size - routed.size))
                packed = jnp.concatenate(
                    [packed, routed.reshape(rows, *toks.shape)]
                )
            return cache, tok, pos, packed, counts

        self._block_cache[k] = block
        return block

    def _prefill_fn(self, width: int):
        # Per-instance memo (NOT functools.lru_cache on the method: a
        # class-level cache would pin closed engines — params, compiled
        # programs and all — for the process lifetime).
        cached = self._prefill_cache.get(width)
        if cached is not None:
            return cached
        model = self._model
        constrain = self._constrain_cache

        @jax.jit  # lint: layout-ok: params arrive pre-committed to the engine TP layout at construction (layout.tp_only) and are shared (hot swap, replicas); the program takes no cache and builds its single-row one, so there is nothing to donate
        def prefill(
            params, prompt, length, temps, ads, kps, seed_1, bid_1,
            bval_1,
        ):
            positions = jnp.arange(width, dtype=jnp.int32)[None, :]
            # The padding after the prompt is marked: K/V written for
            # it are masked when read, a recurrence must not run over
            # it at all.
            (hidden, _), state = model.apply(
                {"params": params},
                prompt,
                positions=positions,
                decode=True,
                padded=True,
                adapter_ids=ads,
                valid=positions < length[:, None],
                return_hidden=True,
                mutable=["cache"],
            )
            # the head on the prompt's last position only: no
            # (width, vocab) array exists
            last = model.apply(
                {"params": params},
                jnp.take_along_axis(
                    hidden, (length - 1)[:, None, None], axis=1
                )[:, 0],
                method="head",
            )
            # the first sampled token occupies position `length`;
            # logit_bias shapes it too (penalties don't - zero counts)
            tok, lp = _sample_rows(
                last, temps, kps, seed_1, length,
                bias_ids=bid_1, bias_vals=bval_1,
            )
            return constrain(state["cache"]), tok, length, lp

        self._prefill_cache[width] = prefill
        return prefill

    @functools.cached_property
    def _admit_fn(self):
        constrain = self._constrain_cache

        # Every batch-state argument (*_b) is donated and returned
        # updated: the scatter writes one row into the batch cache in
        # place instead of copying all of it. Never the single-row
        # side: cache_1 may be held by _PrefixStore and the L2 filler
        # thread, tok_1 waits in _pending_first.
        @functools.partial(
            jax.jit,
            donate_argnames=(
                "cache_b", "tok_b", "pos_b", "temps_b", "ads_b",
                "kps_b", "seeds_b", "pens_b", "counts_b", "bids_b",
                "bvals_b",
            ),
        )
        def admit(
            cache_b, cache_1, row, tok_b, tok_1, pos_b, pos_1,
            temps_b, temp_1, ads_b, ad_1, kps_b, kp_1, seeds_b, seed_1,
            pens_b, pen_1, counts_b, bids_b, bid_1, bvals_b, bval_1,
        ):
            def scatter(path, leaf_b, leaf_1):
                if leaf_b.ndim == 0:  # per-layer scalar write index:
                    return leaf_b  # unused on the padded decode path
                if leaf_kind(path) == "counter":
                    return leaf_b  # the batch's own: a row brings none
                start = (row,) + (0,) * (leaf_b.ndim - 1)
                return jax.lax.dynamic_update_slice(
                    leaf_b, leaf_1.astype(leaf_b.dtype), start
                )

            cache = constrain(
                jax.tree_util.tree_map_with_path(scatter, cache_b, cache_1)
            )
            tok = jax.lax.dynamic_update_slice(tok_b, tok_1, (row,))
            pos = jax.lax.dynamic_update_slice(pos_b, pos_1, (row,))
            temps = jax.lax.dynamic_update_slice(temps_b, temp_1, (row,))
            ads = jax.lax.dynamic_update_slice(ads_b, ad_1, (row,))
            kps = jax.lax.dynamic_update_slice(kps_b, kp_1, (row, 0))
            seeds = jax.lax.dynamic_update_slice(seeds_b, seed_1, (row,))
            pens = jax.lax.dynamic_update_slice(pens_b, pen_1, (row, 0))
            # the row's generated-token counts restart at ONE for the
            # prefill-sampled first token (penalties count generated
            # tokens; the prompt is not penalized - documented)
            counts_1 = jax.nn.one_hot(
                tok_1[:1], counts_b.shape[-1], dtype=counts_b.dtype
            )
            counts = jax.lax.dynamic_update_slice(
                counts_b, counts_1, (row, 0)
            )
            bids = jax.lax.dynamic_update_slice(bids_b, bid_1, (row, 0))
            bvals = jax.lax.dynamic_update_slice(
                bvals_b, bval_1, (row, 0)
            )
            return (
                cache, tok, pos, temps, ads, kps, seeds, pens, counts,
                bids, bvals,
            )

        return admit

    @functools.cached_property
    def _chunk_fn(self):
        """One prompt chunk through the model against the single-row
        cache — the unit a chunked prefill interleaves with decode
        steps. One compile for (1, prefill_chunk)."""
        model = self._model
        constrain = self._constrain_cache

        @jax.jit  # lint: layout-ok: params/cache arrive pre-committed to the engine TP layout at construction (layout.tp_only + serve_cache_sharding); the single-row cache is NOT donated: it can be a _PrefixStore entry (or an L2 offer in flight) that other requests resume from
        def chunk(params, cache, tokens, positions, ads, lo, hi):
            # Real tokens of this call: inside the prompt (< hi) and
            # not yet consumed (>= lo: a window shifted back recomputes
            # its overlap, which is harmless for K/V and would be
            # applied twice by a recurrence).
            (hidden, _), updated = model.apply(
                {"params": params, "cache": cache},
                tokens,
                positions=positions,
                decode=True,
                padded=True,
                adapter_ids=ads,
                valid=(positions >= lo) & (positions < hi),
                return_hidden=True,
                mutable=["cache"],
            )
            return constrain(updated["cache"]), hidden

        return chunk

    @functools.cached_property
    def _sample1_fn(self):
        model = self._model

        @jax.jit  # lint: layout-ok: params arrive pre-committed to the engine TP layout at construction (layout.tp_only) and are shared (hot swap, replicas); the chunk's hidden states are read once and nothing here is carried, so there is nothing to donate
        def sample1(
            params, hidden_chunk, idx, temps, kps, seed_1, length_1,
            bid_1, bval_1,
        ):
            # the head on the prompt's true last position only
            last = model.apply(
                {"params": params},
                jax.lax.dynamic_index_in_dim(
                    hidden_chunk, idx, axis=1, keepdims=False
                ),
                method="head",
            )  # (1, vocab)
            # the first sampled token occupies position `length`;
            # logit_bias shapes it too (penalties don't - zero counts)
            return _sample_rows(
                last, temps, kps, seed_1, length_1,
                bias_ids=bid_1, bias_vals=bval_1,
            )

        return sample1

    def _cache_shapes(self, batch: int):
        """Cache-tree ShapeDtypeStructs for a ``batch``-row decode —
        one eval_shape (traces the whole model, no compile/device
        work), made once at construction for the engine's batch; the
        per-row tree is cut from it by shape, so the two can never
        drift structurally."""
        _, shapes = jax.eval_shape(
            lambda p, t, pos: self._model.apply(
                {"params": p},
                t,
                positions=pos,
                decode=True,
                padded=True,
                mutable=["cache"],
            ),
            self._params,
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        )
        return shapes["cache"]

    @functools.cached_property
    def _single_row_cache_shapes(self):
        # A constant, NOT per-admission work on the scheduler thread (a
        # per-request trace would stall live rows' step dispatch,
        # exactly the latency chunked prefill exists to remove). By
        # shape from the batch's tree, whatever the model: every leaf
        # but the scalar write index and the counters have the row first.
        return jax.tree_util.tree_map_with_path(
            lambda path, s: s
            if not s.shape or leaf_kind(path) == "counter"
            else jax.ShapeDtypeStruct((1, *s.shape[1:]), s.dtype),
            self._batch_cache_shapes,
        )

    def _single_row_cache(self):
        # decode_cache.init_cache owns the leaves' init values (zeros;
        # the position plane -1, not 0)
        return init_cache(self._single_row_cache_shapes)

    def _l2_offer(self, tokens: list[int], cache_1, adapter) -> None:
        """Publish one L1-inserted prefix to the fleet L2, fire-and-
        forget: the scheduler thread hands the (immutable) device
        leaves to the L2's filler thread and returns — the device→host
        transfer and transport never run here."""
        l2 = self._prefix_l2
        if l2 is None or self._warming:
            # warmup's throwaway prompts are cleared from L1 afterwards;
            # publishing them fleet-wide would be respawn-time junk
            return
        try:
            l2.offer(
                tokens,
                jax.tree_util.tree_leaves(cache_1),
                adapter,
                self._weights_version,
            )
        except Exception:  # noqa: BLE001 - a lost offer is a later miss
            logger.warning("prefix L2 offer failed", exc_info=True)

    def _l2_reconstruct(self, leaves):
        """Rebuild a single-row cache pytree from L2 host leaves, or
        None when the payload does not match this engine's cache
        structure (a foreign config's entry — treat as a miss; the
        shape/dtype check is the exactness guard)."""
        import numpy as np

        flat, treedef = jax.tree_util.tree_flatten(
            self._single_row_cache_shapes
        )
        if not isinstance(leaves, list) or len(leaves) != len(flat):
            return None
        placed = []
        for arr, want in zip(leaves, flat):
            got = tuple(getattr(arr, "shape", ()))
            if getattr(arr, "dtype", None) != want.dtype:
                return None
            if got != tuple(want.shape):
                # a stepped cache's scalar planes (positions) come back
                # as the batch-1 row, shape (1, *template); fold that
                # row axis away — anything else is a foreign config
                if got != (1, *want.shape):
                    return None
                arr = np.asarray(arr).reshape(want.shape)
            placed.append(jax.device_put(arr))
        return jax.tree_util.tree_unflatten(treedef, placed)

    def attach_prefix_l2(self, l2) -> None:
        """Attach (or detach with None) the fleet-global prefix L2 on a
        RUNNING engine — the ServingFleet injection path for factory-
        built replicas. The rebind is a single atomic reference swap;
        the scheduler reads ``_prefix_l2`` racily and a one-iteration-
        stale view is benign (one extra miss or offer)."""
        if l2 is not None and self._prefix_store is None:
            raise ValueError("prefix_l2 requires prefix_cache")
        self._prefix_l2 = l2

    def _start_job(self, p: _Pending, row: int) -> _PrefillJob:
        temp = (
            self._temperature
            if p.temperature is None
            else float(p.temperature)
        )
        cache_1, resume = None, 0
        if self._prefix_store is not None:
            # Longest stored prompt that prefixes this one: resume the
            # chunked prefill from its end instead of position 0. The
            # stored buffer's padding rows beyond its own prompt are
            # overwritten by the first continuation chunk before any
            # query position can attend them (keys > query pos are
            # masked), so reuse needs no cleanup pass.
            cache_1, resume = self._prefix_store.lookup(
                p.tokens, p.adapter
            )
            if cache_1 is None and self._prefix_l2 is not None:
                # L1 miss → bounded-latency fleet-global probe. A hit
                # is a prefix some OTHER replica prefilled under the
                # SAME weights version (the version is baked into the
                # key, so a stale-version cache can never extend this
                # decode). The reconstructed cache is inserted into L1
                # so repeats on this replica stay device-local.
                hit = self._prefix_l2.lookup(
                    p.tokens, p.adapter, self._weights_version
                )
                if hit is not None:
                    rebuilt = self._l2_reconstruct(hit[0])
                    if rebuilt is not None:
                        depth = hit[1]
                        cache_1 = rebuilt
                        resume = min(depth, len(p.tokens) - 1)
                        self._prefix_store.insert(
                            p.tokens[:depth], cache_1, p.adapter
                        )
        if cache_1 is None:
            cache_1 = self._single_row_cache()
        return _PrefillJob(
            p=p,
            row=row,
            cache_1=cache_1,
            next_pos=resume,
            length=len(p.tokens),
            temp_1=jnp.asarray([temp], jnp.float32),
            kp_1=self._resolve_kp(p),
            seed_1=self._resolve_seed(p),
            pen_1=self._resolve_pen(p),
            bias_1=self._resolve_bias(p),
            ad_1=jnp.asarray([p.adapter], jnp.int32),
            # first boundary entry lands at the first chunk boundary
            # past the resume point, then depths double
            next_insert_depth=self._prefill_chunk or 0,
        )

    def _chunk_window(self, job: _PrefillJob) -> tuple[int, int]:
        """Where the job's next chunk starts, and how many of its
        positions are new prompt tokens.

        The window is shifted back rather than letting positions run
        past max_seq_len: a final chunk starting at `next_pos` would
        scatter rows at next_pos+c-1 >= max_seq_len, which only works
        by JAX's silent out-of-bounds-scatter drop. The overlap
        start..next_pos is already in the cache: its K/V rows are
        recomputed identically (chunked prefill is causal-consistent),
        and the chunk program marks it invalid, so that a recurrent
        state does not consume those tokens twice. Every position stays
        in [0, max_seq_len) and distinct. __init__ guarantees
        c <= max_seq_len, so start >= 0."""
        c = self._prefill_chunk
        start = min(job.next_pos, self._model.cfg.max_seq_len - c)
        return start, max(0, min(job.length, start + c) - job.next_pos)

    def _advance_job(
        self, cache, tok, pos, temps, ads, kps, seeds, pens, counts,
        bids, bvals,
    ):
        """Run ONE chunk of the in-flight prefill; on the final chunk,
        sample the first token and scatter the row into the batch.
        Chunks cover only the true prompt length — the padding region a
        full-width prefill would burn compute on is never touched."""
        job = self._job
        if job.p.cancelled:
            self._resolve_unadmitted_cancel(job.p)
            self._job = None
            return (
                cache, tok, pos, temps, ads, kps, seeds, pens, counts,
                bids, bvals,
            )
        c = self._prefill_chunk
        start_w, n_new = self._chunk_window(job)
        with self._phase("prefill_stage"):
            toks = np.zeros((1, c), np.int32)
            piece = job.p.tokens[start_w : start_w + c]
            toks[0, : len(piece)] = piece
            positions = np.arange(
                start_w, start_w + c, dtype=np.int32
            )[None, :]
            toks_1, positions_1 = jnp.asarray(toks), jnp.asarray(positions)
            lo_1, hi_1 = jnp.int32(job.next_pos), jnp.int32(job.length)
        # new prompt tokens only: a window shifted back recomputes
        # start_w..next_pos, which is padding like the tail's. The
        # chunk program is handed the job's cache: every query of it
        # is scored against every slot.
        self._count_prefill(n_new, c, handed_cache=True)
        with self._phase("prefill_launch"):
            job.cache_1, hidden = self._chunk_fn(
                self._params, job.cache_1, toks_1, positions_1,
                job.ad_1, lo_1, hi_1,
            )
            self._clock_launched()
        job.next_pos = start_w + c
        if job.next_pos < job.length:
            if (
                self._prefix_store is not None
                and job.next_pos >= job.next_insert_depth
                and job.boundary_inserts
                < self._prefix_store.capacity // 2
            ):
                # Chunk-boundary prefix: the cache now covers exactly
                # tokens[:next_pos] with no padding junk (only final
                # chunks pad), so a later prompt sharing just the system
                # prefix — not this whole prompt — can resume here.
                # Storing the reference costs no device work or copies
                # (jax arrays are immutable). Flood control, two layers:
                # depths are exponentially spaced (the threshold doubles
                # per insert — O(log L) coverage of the sharing scales),
                # AND boundary inserts are capped at capacity//2 per
                # request, shallowest first (shallow prefixes are the
                # shareable ones), because log2(L/chunk) alone can still
                # exceed a small LRU. Hot shared entries are refreshed
                # on every hit, so one long prompt cannot flush them.
                self._prefix_store.insert(
                    job.p.tokens[: job.next_pos], job.cache_1,
                    job.p.adapter,
                )
                self._l2_offer(job.p.tokens[: job.next_pos], job.cache_1,
                               job.p.adapter)
                job.next_insert_depth = 2 * job.next_pos
                job.boundary_inserts += 1
            return (
                cache, tok, pos, temps, ads, kps, seeds, pens, counts,
                bids, bvals,
            )
        if self._prefix_store is not None:
            # The completed single-row cache covers the whole prompt.
            self._prefix_store.insert(
                job.p.tokens, job.cache_1, job.p.adapter
            )
            self._l2_offer(job.p.tokens, job.cache_1, job.p.adapter)
        # final chunk: it contains the prompt's last true position
        with self._phase("prefill_launch"):
            tok_1, lp_1 = self._sample1_fn(
                self._params,
                hidden,
                jnp.int32(job.length - 1 - start_w),
                job.temp_1,
                job.kp_1,
                job.seed_1,
                jnp.asarray([job.length], jnp.int32),
                *job.bias_1,
            )
            (
                cache, tok, pos, temps, ads, kps, seeds, pens, counts,
                bids, bvals,
            ) = self._admit_fn(
                cache,
                job.cache_1,
                jnp.int32(job.row),
                tok,
                tok_1,
                pos,
                jnp.asarray([job.length], jnp.int32),
                temps,
                job.temp_1,
                ads,
                job.ad_1,
                kps,
                job.kp_1,
                seeds,
                job.seed_1,
                pens,
                job.pen_1,
                counts,
                bids,
                job.bias_1[0],
                bvals,
                job.bias_1[1],
            )
        # Deferred first-token fetch, same as _admit_one: the sample and
        # admit are dispatched; the host value resolves on the fetch path.
        self._live[job.row] = (job.p, [], [])
        self._row_pos[job.row] = job.length
        self._gates_arr = None
        self.admitted += 1
        self._pending_first.append((job.row, tok_1, lp_1))
        if self._pipeline_depth == 1:
            self._resolve_first_tokens()
        self._job = None
        return (
            cache, tok, pos, temps, ads, kps, seeds, pens, counts,
            bids, bvals,
        )

    # -- engine loop ---------------------------------------------------

    def _empty_state(self):
        b = self._slots
        cache = init_cache(self._batch_cache_shapes)
        if self._n_routed:
            self._routed_seen[:] = 0  # the device's sums restart with it
        tok = jnp.zeros((b,), jnp.int32)
        # Parked rows decode at position 0 against their own slot only;
        # their K/V writes stay inside their row and are overwritten on
        # admission.
        pos = jnp.zeros((b,), jnp.int32)
        temps = jnp.zeros((b,), jnp.float32)
        ads = jnp.zeros((b,), jnp.int32)  # adapter slot 0 = base
        # per-row [top_k, top_p, min_p], truncation disabled (k=vocab,
        # p=1, m=0): parked rows must not flip the truncation conds
        kps = jnp.tile(
            jnp.asarray(
                [[float(self._model.cfg.vocab_size), 1.0, 0.0]],
                jnp.float32,
            ),
            (b, 1),
        )
        seeds = jnp.zeros((b,), jnp.uint32)
        pens = jnp.zeros((b, 2), jnp.float32)
        counts = jnp.zeros((b, self._model.cfg.vocab_size), jnp.float32)
        bids = jnp.full((b, _BIAS_SLOTS), -1, jnp.int32)
        bvals = jnp.zeros((b, _BIAS_SLOTS), jnp.float32)
        state = (
            cache, tok, pos, temps, ads, kps, seeds, pens, counts,
            bids, bvals,
        )
        if self._mesh is None:
            return state
        # Under a mesh, commit the state to the layout the programs keep
        # it in (cache heads on 'model', the rest replicated), so that
        # the first admit writes in place like every later one.
        from tensorflowonspark_tpu.compute import layout

        cache_sh = jax.tree.map(
            lambda x: layout.serve_cache_sharding(self._mesh, x), cache
        )
        rest = (layout.replicated(self._mesh),) * (len(state) - 1)
        return jax.device_put(state, (cache_sh,) + rest)

    def _effective_knobs(self, p: _Pending):
        """Resolved (top_k, top_p, min_p) for one request — the request
        value, else the engine-wide default, else disabled (k = vocab /
        p = 1.0 / m = 0.0, the identity values in _sample_rows).

        A row whose EFFECTIVE temperature is 0 decodes greedily —
        _sample_rows discards its sampled token — so k/p/min_p resolve
        to disabled outright: otherwise an all-greedy batch on an
        engine with default truncation would flip the truncation conds
        and pay the full-vocab sort for nothing. THE single source for
        both the device kps rows (_resolve_kp) and the host cond gates
        (_step_gates): sharing it is what guarantees a gate can never
        read False while a live row's kps are active."""
        vocab = self._model.cfg.vocab_size
        temp = (
            self._temperature if p.temperature is None else p.temperature
        )
        if temp <= 0:
            return float(vocab), 1.0, 0.0
        k = p.top_k if p.top_k is not None else self._top_k
        k = vocab if k is None else min(int(k), vocab)
        q = p.top_p if p.top_p is not None else self._top_p
        q = 1.0 if q is None else float(q)
        m = p.min_p if p.min_p is not None else self._min_p
        m = 0.0 if m is None else float(m)
        return float(k), q, m

    def _resolve_kp(self, p: _Pending):
        """(1, 3) fp32 [top_k, top_p, min_p] via _effective_knobs."""
        return jnp.asarray([list(self._effective_knobs(p))], jnp.float32)

    def _resolve_pen(self, p: _Pending):
        """(1, 2) fp32 [frequency_penalty, presence_penalty]; 0 =
        disabled (no engine-wide default - penalties are a per-request
        behavior, not a serving policy)."""
        return jnp.asarray(
            [[
                float(p.frequency_penalty or 0.0),
                float(p.presence_penalty or 0.0),
            ]],
            jnp.float32,
        )

    def _resolve_bias(self, p: _Pending):
        """((1, K) int32 ids, (1, K) fp32 values); unused slots id=-1."""
        ids = np.full((1, _BIAS_SLOTS), -1, np.int32)
        vals = np.zeros((1, _BIAS_SLOTS), np.float32)
        for i, (t, v) in enumerate((p.logit_bias or {}).items()):
            ids[0, i] = t
            vals[0, i] = v
        return jnp.asarray(ids), jnp.asarray(vals)

    def _resolve_seed(self, p: _Pending):
        """(1,) uint32 sampling seed: the request's, else one drawn from
        the engine's stream at admission (rows stay independent; the
        engine stays reproducible given its constructor seed)."""
        if p.seed is not None:
            val = int(p.seed) % (2**32)
        else:
            val = int(self._seed_rng.integers(2**32, dtype=np.uint32))
        return jnp.asarray([val], jnp.uint32)

    def _gates_dev(self):
        """The (4,) gates array for the decode step, cached across
        steps: the live set (and with it every resolved knob) only
        changes at admission/retire, so rebuilding per token — a
        host→device upload on the hot path — was pure overhead. Every
        ``_live`` mutation site clears ``_gates_arr``."""
        if self._gates_arr is None:
            self._gates_arr = self._step_gates()
        return self._gates_arr

    def _step_gates(self):
        """(4,) bool [sort, min_p, penalties, bias] from the LIVE rows'
        resolved knobs — the host's bookkeeping, not the device arrays,
        so a retired row's stale state can't keep a cond (and its
        full-vocab sort / count-plane update) firing for the rest of
        the batch."""
        vocab = self._model.cfg.vocab_size
        sort = minp = pen = bias = False
        for e in self._live:
            if e is None:
                continue
            p = e[0]
            if p.logit_bias:
                bias = True
            if p.frequency_penalty or p.presence_penalty:
                pen = True  # penalties shape greedy rows too
            k, q, m = self._effective_knobs(p)  # same resolver as kps
            if k < vocab or q < 1.0:
                sort = True
            if m > 0.0:
                minp = True
        return jnp.asarray([sort, minp, pen, bias])

    def _bucket(self, n: int) -> int:
        for w in self._widths:
            if n <= w:
                return w
        raise AssertionError  # submit() validated against widths[-1]

    def _admit_one(
        self, p: _Pending, row: int, cache, tok, pos, temps, ads, kps,
        seeds, pens, counts, bids, bvals,
    ):
        w = self._bucket(len(p.tokens))
        # Two child phases say what the chip waits for inside an
        # admission: the host arrays made device arrays, then the two
        # program calls until they return (the admit call's own two
        # small arrays are made between them, as before).
        with self._phase("prefill_stage"):
            prompt = np.zeros((1, w), np.int32)
            prompt[0, : len(p.tokens)] = p.tokens
            temp = (
                self._temperature
                if p.temperature is None
                else float(p.temperature)
            )
            temp_1 = jnp.asarray([temp], jnp.float32)
            kp_1 = self._resolve_kp(p)
            seed_1 = self._resolve_seed(p)
            bid_1, bval_1 = self._resolve_bias(p)
            ad_1 = jnp.asarray([p.adapter], jnp.int32)
            prompt_1 = jnp.asarray(prompt)
            len_1 = jnp.asarray([len(p.tokens)], jnp.int32)
        # the prefill program creates its single-row cache: it attends
        # among its own w positions
        self._count_prefill(len(p.tokens), w, handed_cache=False)
        with self._phase("prefill_launch"):
            cache_1, tok_1, pos_1, lp_1 = self._prefill_fn(w)(
                self._params,
                prompt_1,
                len_1,
                temp_1,
                ad_1,
                kp_1,
                seed_1,
                bid_1,
                bval_1,
            )
            (
                cache, tok, pos, temps, ads, kps, seeds, pens, counts,
                bids, bvals,
            ) = self._admit_fn(
                cache, cache_1, jnp.int32(row), tok, tok_1, pos, pos_1,
                temps, temp_1, ads, ad_1, kps, kp_1, seeds, seed_1,
                pens, self._resolve_pen(p), counts, bids, bid_1, bvals,
                bval_1,
            )
            self._clock_launched()
        # Async admission: prefill + admit are DISPATCHED (jax enqueues
        # without a device sync); the first token's fetch is deferred to
        # _resolve_first_tokens on the normal fetch path, so a burst of
        # admissions batches into back-to-back dispatches instead of
        # paying two scalar round-trips each.
        self._live[row] = (p, [], [])
        self._row_pos[row] = len(p.tokens)
        self._gates_arr = None
        self.admitted += 1
        self._pending_first.append((row, tok_1, lp_1))
        if self._pipeline_depth == 1:
            # serial mode: resolve immediately — today's exact behavior
            self._resolve_first_tokens()
        return (
            cache, tok, pos, temps, ads, kps, seeds, pens, counts,
            bids, bvals,
        )

    def _emit(self, p: _Pending, token: int, logprob: float) -> None:
        """Emit one decoded token: bookkeeping (TTFT stamp) stays on the
        scheduler thread; the sink delivery itself runs on the emitter
        thread so stream consumers are off the decode critical path."""
        if p.first_token_at is None:
            p.first_token_at = time.monotonic()
            if p.trace is not None and p.trace_mark is not None:
                # dequeue -> first token: the request's prefill share
                # (includes its chunked-prefill dispatch waits)
                reqtrace.segment(
                    p.trace, "engine.prefill",
                    p.first_token_at - p.trace_mark,
                )
                p.trace_mark = p.first_token_at
        if p.sink is not None:
            self._emitter.deliver(p.sink, (token, logprob))

    def _resolve_first_tokens(self) -> None:
        """Fetch the deferred first tokens of async admissions, emit
        them, and retire rows that are already finished (budget 1, eos,
        stop, or cancel at token 0). MUST run before any sweep that
        could touch these rows — the scheduler guarantees it by
        resolving right after each dispatch phase and at every drain,
        and by only dispatching blocks AFTER the admissions they
        cover."""
        if not self._pending_first:
            return
        n = len(self._pending_first)
        # the scheduler's wait for the prefill programs: each row's
        # fetch returns when its prefill has run, a completion
        with self._phase("first_token", rows=n):
            for i, (row, tok_1, lp_1) in enumerate(self._pending_first):
                p, out, lps = self._live[row]
                if p.resolved:  # failed (watchdog/deadline) before token 0
                    self._live[row] = None
                    self._gates_arr = None
                    continue
                first = int(np.asarray(tok_1)[0])
                lp = float(np.asarray(lp_1)[0])
                self._clock_completed(
                    self._m_device_prefill_s,
                    in_flight=bool(self._window) or i + 1 < n,
                )
                out.append(first)
                lps.append(lp)
                self._emit(p, first, lp)
                if self._finished(p, out, first):
                    self._retire(row)
            self._pending_first.clear()

    @staticmethod
    def _block_ready(packed) -> bool:
        """True when a dispatched block's result is already on host-
        fetchable memory — the non-blocking readiness probe behind the
        opportunistic early fetch. Arrays without ``is_ready`` (older
        jax) report ready, degrading to the blocking fetch."""
        try:
            return bool(packed.is_ready())
        except AttributeError:
            return True

    def _fetch_packed(self, packed) -> np.ndarray:
        """Materialize one block's packed (2, k, slots) result on host.
        ``jax.device_get`` blocks only until THIS block is done — with
        dispatch-ahead the next block keeps the device busy while the
        host sweeps this one."""
        # chaos: a delay armed here models a wedged device transfer —
        # the exact stall the scheduler watchdog exists to detect
        failpoint("engine.fetch")
        host = np.asarray(jax.device_get(packed))
        self._clock_completed(
            self._m_device_decode_s,
            in_flight=bool(self._window or self._pending_first),
        )
        self._progress_ts = time.monotonic()
        if self._n_routed:
            self._count_routed(host[2:].reshape(-1)[: self._n_routed])
        return host

    def _count_routed(self, sums: np.ndarray) -> None:
        """``sums``: the expert layers' ``moe_counts`` added up, as the
        block just fetched left them. They count since the state was
        made and wrap as int32 does; blocks are fetched in dispatch
        order, so the difference to the last fetch is this block's, and
        each entry's goes to the counters the model names for it."""
        sums = sums.astype(np.uint32)
        delta = (sums - self._routed_seen).astype(np.int64)  # mod 2**32
        self._routed_seen = sums
        for n, feeds in zip(delta.tolist(), self._routed_entries):
            for counter, labels in feeds:
                counter.inc(n, **labels)

    def _sweep_block(self, k: int, host: np.ndarray, rows: int) -> None:
        """Host sweep of one fetched block: append tokens/logprobs,
        emit to streams, retire finished rows. Time spent here while
        another block is still in flight is overlap the pipeline hid —
        tracked in overlap_hidden (the serial loop paid it on the
        critical path). ``rows`` were live when the block was
        dispatched: what of its ``k * rows`` slot-steps appends no
        token here was computed for a row that had ended, a discard."""
        host_tok = host[0]
        host_lp = host[1].view(np.float32)
        t0 = time.monotonic()
        appended = 0
        with self._phase("sweep"):
            for j in range(k):
                for row, entry in enumerate(self._live):
                    if entry is None:
                        continue  # free, or finished earlier in block
                    p, out, lps = entry
                    if p.resolved:
                        # failed off-thread (watchdog) mid-flight: the
                        # terminal already went out — free the slot and
                        # discard the block's tokens for this row
                        self._live[row] = None
                        self._gates_arr = None
                        continue
                    t = int(host_tok[j, row])
                    out.append(t)
                    appended += 1
                    lps.append(float(host_lp[j, row]))
                    self._emit(p, t, lps[-1])
                    if self._finished(p, out, t):
                        self._retire(row)
            now = time.monotonic()
            for entry in self._live:
                if entry is None:
                    continue
                p = entry[0]
                if p.trace is not None and p.trace_mark is not None:
                    # this block's wall share for the request: dispatch
                    # + fetch wait + sweep since the last stamp
                    reqtrace.segment(
                        p.trace, "engine.decode", now - p.trace_mark,
                        tokens=k,
                    )
                    p.trace_mark = now
        self._m_discarded.inc(k * rows - appended)
        if self._window:
            dur = time.monotonic() - t0
            self._overlap_hidden_s += dur
            self._m_overlap.observe(dur)

    def _drain_window(self, reason: str) -> None:
        """Fetch + sweep every in-flight block, oldest first — the
        pipeline's synchronization point, required before any mutation
        of the shared batch state (admission, final-chunk prefill
        admit): an unswept block's retires haven't freed slots yet, and
        admitting into a slot whose garbage tokens are still in flight
        would credit them to the new request. Counted as a drain stall
        only when the window actually held work.

        An empty window needs NO first-token resolution here (there is
        nothing to sweep), and skipping it is what lets back-to-back
        admissions inside one admit loop stay sync-free."""
        if not self._window:
            return
        # Invariant guard: first tokens resolve before any sweep. In
        # practice pending_first is always empty when blocks are in
        # flight (blocks dispatch after admissions and resolution
        # follows the dispatch phase), so this is a no-op.
        self._resolve_first_tokens()
        if all(e is None for e in self._live):
            # every row already retired: the in-flight blocks hold only
            # discards — drop the references without fetching
            self._drop_window()
            return
        self._drain_stalls += 1
        self._m_drains.inc(reason=reason)
        with self._phase("drain", reason=reason):
            while self._window:
                k0, packed, rows = self._window.popleft()
                with self._phase("fetch"):
                    host = self._fetch_packed(packed)
                self._sweep_block(k0, host, rows)

    def _finished(self, p: _Pending, out: list[int], last: int) -> bool:
        if p.cancelled:
            return True  # consumer went away; free the slot now
        for seq in p.stop:
            # the match can only complete on the token just emitted
            if last == seq[-1] and tuple(out[-len(seq):]) == seq:
                return True
        # Per-request eos: None = engine default; negative = DISABLED
        # (run the full budget even when the engine has a default eos —
        # None can't express that, it IS the use-the-default sentinel).
        if p.eos_id is None:
            eos = self._eos_id
        else:
            eos = None if p.eos_id < 0 else p.eos_id
        return len(out) >= p.max_new_tokens or (
            eos is not None and last == eos
        )

    def _retire(self, row: int) -> None:
        p, out, lps = self._live[row]
        self._live[row] = None
        self._gates_arr = None
        if not self._try_resolve(p):
            # the watchdog (or a deadline expiry) already failed this
            # request and delivered its terminal — only free the slot
            return
        now = time.monotonic()
        self.tokens_emitted += len(out)  # decoded count, pre-trim
        # same pre-trim count: /stats and /metrics must agree on what
        # "tokens emitted" means (decoded device work, stop tail incl.)
        self._m_tokens.inc(len(out))
        matched = max(
            (
                seq
                for seq in p.stop
                if len(out) >= len(seq)
                and tuple(out[-len(seq):]) == seq
            ),
            key=len,
            default=None,
        )
        if matched is not None:
            # standard stop-sequence semantics: the completion ends
            # BEFORE the stop text (streams already saw the tokens; the
            # blocking result is the trimmed one). LONGEST tail match,
            # so [[b],[a,b]] and [[a,b],[b]] trim identically.
            out = out[: len(out) - len(matched)]
            lps = lps[: len(out)]
        if p.cancelled:
            self.cancelled += 1
        if p.first_token_at is not None:
            self._ttft_sum += p.first_token_at - p.submitted_at
            self._m_ttft.observe(p.first_token_at - p.submitted_at)
        self._m_completed.inc()
        self._duration_sum += now - p.submitted_at
        self._latency_n += 1
        # Incremented LAST: stats() divides the sums by this count from
        # another thread, and a count that runs ahead of its sums would
        # fabricate zero/low latency averages.
        self.completed += 1
        p.result = out
        p.logprobs = lps
        # stamped on the scheduler thread — the thread that applies
        # weight swaps — so a completion's version is exactly the tree
        # it finished decoding under (rollout coherence contract)
        p.weights_version = self._weights_version
        if p.trace is not None:
            if p.trace_mark is not None:
                # tail of the final decode block up to retirement
                reqtrace.segment(
                    p.trace, "engine.decode", now - p.trace_mark
                )
                p.trace_mark = now
            reqtrace.event(
                p.trace, "engine.retire",
                tokens=len(out),
                weights_version=p.weights_version,
                cancelled=p.cancelled,
            )
        # result/logprobs are set BEFORE the terminal marker is queued:
        # a stream consumer that sees the emitter-delivered True and
        # reads .result gets the final value.
        if p.sink is not None:
            self._emitter.deliver(p.sink, True)
        p.event.set()

    def _resolve_unadmitted_cancel(self, p: _Pending) -> None:
        """A request cancelled while still queued (or mid-prefill): no
        slot to retire, no tokens; resolve as completed-empty so drain
        accounting closes and nothing prefills for a dead consumer.
        Excluded from the latency averages — it never ran."""
        if not self._try_resolve(p):
            return
        p.result = []
        p.logprobs = []
        p.weights_version = self._weights_version
        self.cancelled += 1
        self.completed += 1
        self._m_completed.inc()
        if p.sink is not None:
            self._emitter.deliver(p.sink, True)
        p.event.set()

    def _try_resolve(self, p: _Pending) -> bool:
        """Flip the request's resolve-once latch; True means the caller
        owns delivering the terminal (result or error). Exists because
        the watchdog thread can fail a request the scheduler is about
        to retire — exactly one side may win."""
        with self._resolve_lock:
            if p.resolved:
                return False
            p.resolved = True
            return True

    def _fail_one(self, p: _Pending, err: BaseException) -> bool:
        """Fail a request; False when something else (watchdog vs
        scheduler race) already resolved it — callers must not count a
        terminal they didn't deliver."""
        if not self._try_resolve(p):
            return False
        with self._resolve_lock:
            # under the same lock as the latch: close()'s drain
            # accounting reads completed+_failed_total against
            # _accepted_total and must never see a resolved request
            # counted zero times
            self._failed_total += 1
        self._m_failed.inc()
        p.error = err
        if p.trace is not None:
            reqtrace.event(
                p.trace, "engine.fail", error=type(err).__name__
            )
            reqtrace.flag(p.trace, error=type(err).__name__)
        if p.sink is not None:
            self._emitter.deliver(p.sink, err)
        p.event.set()
        return True

    def _fail_all(self, err: BaseException) -> None:
        for row, entry in enumerate(self._live):
            if entry is not None:
                self._fail_one(entry[0], err)
                self._live[row] = None
        self._gates_arr = None
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            if item is self._STOP or item is self._WAKE:
                continue
            self._fail_one(item, RuntimeError("engine shutting down"))

    # -- degradation: watchdog + deadlines ----------------------------

    def _watchdog_loop(self) -> None:
        """Sidecar thread: fire when the scheduler has made no progress
        for ``watchdog_s`` seconds WHILE work was in flight. Idle
        blocking on the request queue is progress-free by design and
        never fires; warmup suspends the check (first compiles look
        exactly like stalls)."""
        poll = max(0.05, min(1.0, self._watchdog_s / 4.0))
        while self._thread.is_alive():
            time.sleep(poll)
            if self._watchdog_suspended or self._watchdog_abort.is_set():
                continue
            busy = (
                bool(self._window)
                or self._job is not None
                or self._inflight is not None
                or any(e is not None for e in self._live)
            )
            if not busy:
                continue
            stuck = time.monotonic() - self._progress_ts
            if stuck > self._watchdog_s:
                self._watchdog_fire(stuck)

    def _watchdog_fire(self, stuck_for: float) -> None:
        """Abort every in-flight request with a terminal EngineWedged so
        their callers unblock NOW, then flag the scheduler to reset its
        window/slots when (if) it unwedges — the loop itself stays
        alive and keeps serving whatever arrives next. Queued requests
        are left queued: they admit normally after recovery."""
        phase = self._current_phase or "between phases"
        self.watchdog_fires += 1
        self._m_watchdog.inc()
        err = EngineWedged(
            f"engine scheduler made no progress for {stuck_for:.1f}s "
            f"(stuck in {phase}); request aborted by watchdog"
        )
        logger.error(
            "engine watchdog fired: no scheduler progress for %.1fs "
            "(stuck in %s); aborting in-flight requests",
            stuck_for,
            phase,
        )
        # Postmortem: persist the flight record (recent spans, metrics,
        # events) NOW — a wedge that escalates to a kill leaves no
        # later chance (no-op when the process installed no recorder).
        from tensorflowonspark_tpu.obs import flightrec

        flightrec.note(
            "engine_watchdog", stuck_for=round(stuck_for, 3), phase=phase
        )
        flightrec.dump_now("engine_watchdog")
        # Racy snapshot reads are fine: entries are immutable tuples and
        # _fail_one's resolve-once latch makes double-resolution
        # impossible whichever thread wins.
        for entry in list(self._live):
            if entry is not None:
                self._fail_one(entry[0], err)
        job = self._job
        if job is not None:
            self._fail_one(job.p, err)
        inflight = self._inflight
        if inflight is not None:
            self._fail_one(inflight, err)
        self._watchdog_abort.set()

    def _recover_from_watchdog(self) -> None:
        """Scheduler-side cleanup after a watchdog fire: drop in-flight
        device blocks unfetched (their rows' requests already failed),
        free every slot whose request the watchdog resolved, and keep
        going."""
        self._drop_window()
        self._pending_first.clear()
        for row, entry in enumerate(self._live):
            if entry is not None and entry[0].resolved:
                self._live[row] = None
        if self._job is not None and self._job.p.resolved:
            self._job = None
        if self._inflight is not None and self._inflight.resolved:
            self._inflight = None
        self._gates_arr = None
        self._watchdog_abort.clear()
        logger.warning(
            "engine scheduler recovered after watchdog fire; resuming"
        )

    def _expired(self, p: _Pending, now: float) -> bool:
        return (
            p.deadline_s is not None
            and now - p.submitted_at > p.deadline_s
        )

    def _expire_one(self, p: _Pending, detail: str) -> None:
        delivered = self._fail_one(
            p,
            DeadlineExceeded(
                f"request exceeded deadline_s={p.deadline_s} {detail}"
            ),
        )
        if delivered:
            # count only terminals actually delivered: the watchdog may
            # have resolved this request a beat earlier, and a
            # DeadlineExceeded that never reached the caller must not
            # appear in /stats
            self.deadline_expired += 1
            self._m_deadline.inc()

    def _expire_deadlines(self) -> None:
        """Retire every live/prefilling request whose wall-clock budget
        expired — terminal DeadlineExceeded, never a silent truncation.
        Runs once per scheduler iteration, so an expired request decodes
        at most one in-flight block window past its deadline."""
        now = time.monotonic()
        for row, entry in enumerate(self._live):
            if entry is None:
                continue
            p = entry[0]
            if self._expired(p, now):
                self._expire_one(
                    p, f"({len(entry[1])} token(s) decoded)"
                )
                self._live[row] = None
                self._gates_arr = None
        if self._job is not None and self._expired(self._job.p, now):
            self._expire_one(self._job.p, "(mid-prefill)")
            self._job = None

    # -- engine loop (continued) --------------------------------------

    def _loop(self) -> None:
        cache = tok = pos = temps = ads = kps = seeds = None
        pens = counts = bids = bvals = None
        depth = self._pipeline_depth
        try:
            while True:
                self._progress_ts = time.monotonic()
                if self._watchdog_abort.is_set():
                    self._recover_from_watchdog()
                self._expire_deadlines()
                if self._stop_now.is_set():
                    err = RuntimeError("engine shutting down")
                    # abrupt shutdown: in-flight device work and
                    # unresolved first tokens are dropped unfetched —
                    # every owning request fails below anyway
                    self._drop_window()
                    self._pending_first.clear()
                    if self._job is not None:
                        self._fail_one(self._job.p, err)
                        self._job = None
                    self._abort_pending_swap(err)
                    self._abort_pending_knobs(err)
                    self._fail_all(err)
                    return
                if (
                    self._pending_swap is not None  # lint: lockfree-read: claim is re-checked under _submit_lock in _apply_pending_swap; a stale None only delays the install one iteration
                    and self._job is None
                ):
                    # between decode blocks, never mid-chunked-prefill
                    # (a prompt half-prefilled under two weight versions
                    # would hold internally inconsistent K/V)
                    self._apply_pending_swap()
                if (
                    self._pending_knobs is not None  # lint: lockfree-read: claim is re-checked under _submit_lock in _apply_pending_knobs; a stale None only delays the install one iteration
                    and self._job is None
                ):
                    # knob installs follow the weight-swap discipline:
                    # between decode blocks, never mid-chunked-prefill
                    self._apply_pending_knobs()
                    depth = self._pipeline_depth  # rebind loop snapshot
                if self._window and all(e is None for e in self._live):
                    # every row retired mid-window: the remaining
                    # in-flight blocks hold only discards — drop them
                    # without fetching (nothing to sweep)
                    self._drop_window()
                idle = (
                    all(e is None for e in self._live)
                    and self._job is None
                    and not self._window
                )
                # Admit queued requests into free slots (chunked mode:
                # start at most one prefill job, advanced one chunk per
                # iteration below); block only when fully idle. The
                # FIRST admissible pop drains the in-flight window (a
                # state change under unswept blocks would corrupt slot
                # accounting); subsequent pops in the same sweep see an
                # empty window and batch their admissions sync-free.
                while True:
                    free = [
                        i
                        for i, e in enumerate(self._live)
                        if e is None
                        and (self._job is None or self._job.row != i)
                    ]
                    if not free:
                        break
                    if (
                        self._prefill_chunk is not None
                        and self._job is not None
                    ):
                        break  # one chunked prefill at a time
                    try:
                        if idle:
                            # the engine holds nothing: the completion
                            # clock stands until the next launch
                            self._clock_at = self._clock_fed_at = None
                            item = self._queue.get()
                        else:
                            item = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if item is self._STOP:
                        # no live job possible here: the admit loop
                        # breaks before queue.get while a job runs, so
                        # a queued STOP is only reached after it ends
                        self._drain_window("shutdown")
                        self._pending_first.clear()
                        err = RuntimeError("engine shutting down")
                        self._abort_pending_swap(err)
                        self._abort_pending_knobs(err)
                        self._fail_all(err)
                        return
                    if item is self._WAKE:
                        # woke only so the top-of-loop swap check runs
                        break
                    if item.cancelled:
                        self._resolve_unadmitted_cancel(item)
                        continue
                    if self._expired(item, time.monotonic()):
                        # expired while queued: fail WITHOUT burning a
                        # prefill on a request whose caller's budget is
                        # already gone
                        self._expire_one(item, "(while queued)")
                        continue
                    self._observe_queue_wait(item)
                    self._inflight = item
                    self._drain_window("admit")
                    # the drain may have retired rows — recompute the
                    # target slot from the freshest free set
                    free = [
                        i
                        for i, e in enumerate(self._live)
                        if e is None
                        and (self._job is None or self._job.row != i)
                    ]
                    if cache is None:
                        (
                            cache, tok, pos, temps, ads, kps, seeds,
                            pens, counts, bids, bvals,
                        ) = self._empty_state()
                    if self._prefill_chunk is None:
                        w = self._bucket(len(item.tokens))
                        with self._phase(
                            "prefill", width=w, valid=len(item.tokens),
                            **self._prefill_pairs(w, handed_cache=False),
                        ):
                            (
                                cache, tok, pos, temps, ads, kps, seeds,
                                pens, counts, bids, bvals,
                            ) = self._admit_one(
                                item, free[0], cache, tok, pos, temps,
                                ads, kps, seeds, pens, counts, bids,
                                bvals,
                            )
                    else:
                        self._job = self._start_job(item, free[0])
                    self._inflight = None
                    idle = False

                if self._job is not None:
                    c = self._prefill_chunk
                    if (
                        not self._job.p.cancelled
                        and self._job.next_pos + c >= self._job.length
                    ):
                        # this chunk is the FINAL one: it samples the
                        # first token and scatters the row into the
                        # shared batch state — same drain rule as
                        # admission. Intermediate chunks touch only the
                        # job's private single-row cache and overlap
                        # freely with in-flight decode blocks.
                        self._drain_window("prefill_admit")
                    with self._phase(
                        "prefill", width=c,
                        valid=self._chunk_window(self._job)[1],
                        **self._prefill_pairs(c, handed_cache=True),
                    ):
                        (
                            cache, tok, pos, temps, ads, kps, seeds,
                            pens, counts, bids, bvals,
                        ) = self._advance_job(
                            cache, tok, pos, temps, ads, kps, seeds,
                            pens, counts, bids, bvals,
                        )

                if all(e is None for e in self._live):
                    continue  # nothing decoding; admit/chunk again

                # Block size for this iteration: the full decode_block
                # unless an admission could actually proceed right now —
                # a queued request with a FREE slot (all-slots-busy
                # backlog keeps blocking: dropping to k=1 then would
                # reinstate the per-token host round-trips for the whole
                # saturated period while admitting nothing), or a
                # chunked-prefill job in flight (it advances one chunk
                # per loop iteration, so a block would starve it).
                # Rows that finish mid-block — budget, stop, or eos —
                # retire at their finish point in the host sweep;
                # their surplus block tokens are discarded, never
                # emitted (the device-side waste is bounded by
                # k·pipeline_depth ~ms-scale steps per retire, vs the
                # ~100 ms-scale per-token host round-trips a
                # whole-batch k=1 fallback would reinstate), their
                # garbage cache writes are position-clamped and
                # overwritten by the next admission.
                k = self._decode_block
                if k > 1 and (
                    self._job is not None
                    or (
                        not self._queue.empty()
                        and any(e is None for e in self._live)
                    )
                ):
                    k = 1
                # A live row's decode_block_pin caps the block while it
                # is in flight (warmup's k=1 compile rides this instead
                # of mutating the shared knob under live traffic).
                for e in self._live:
                    if e is not None and e[0].decode_block_pin:
                        k = min(k, max(1, int(e[0].decode_block_pin)))
                # Dispatch-ahead: refill the in-flight window from the
                # device-resident functional state — block N+1 needs no
                # host data, so it enqueues before block N is fetched
                # and the device never waits on the host sweep.
                with self._phase("dispatch"):
                    while len(self._window) < depth:
                        failpoint("engine.dispatch")
                        (
                            cache, tok, pos, packed, counts,
                        ) = self._block_fn(k)(
                            self._params, cache, tok, pos, temps, ads,
                            kps, seeds, pens, counts, bids, bvals,
                            self._gates_dev(),
                        )
                        self._clock_launched()
                        self.steps += k
                        self._m_steps.inc(k)
                        rows = sum(e is not None for e in self._live)
                        self._m_live_steps.inc(k * rows)
                        self._m_recurrent.inc(
                            k * rows * self._recurrent_row_bytes
                        )
                        if k < self._decode_block:
                            self._m_fallback_steps.inc(k)
                        self._count_kv_positions(k)
                        self._window.append((k, packed, rows))
                        self._progress_ts = time.monotonic()
                # Deferred admission first tokens resolve AFTER the
                # dispatch above, so their device_get overlaps the
                # freshly enqueued block — and BEFORE any sweep below
                # can touch their rows (stream order: first token, then
                # block tokens).
                self._resolve_first_tokens()
                # Fetch the oldest block: blocking once the window is
                # full (steady state — its compute is hidden by the
                # younger in-flight blocks), opportunistically early
                # when the device has already finished it.
                if self._window and (
                    len(self._window) >= depth
                    or self._block_ready(self._window[0][1])
                ):
                    k0, packed, rows = self._window.popleft()
                    with self._phase("fetch"):
                        # ONE fetch: (2, k, slots) int32; row 1 carries
                        # the fp32 logprob bits (see _block_fn)
                        host = self._fetch_packed(packed)
                    self._sweep_block(k0, host, rows)
        except BaseException as e:  # noqa: BLE001 - ferry to waiters
            logger.exception("continuous-batcher loop died")
            # Refuse new submits FIRST (a dead loop never answers), then
            # fail the request caught mid-admission (in neither _live
            # nor the queue) and everything parked or queued.
            with self._submit_lock:
                self._closed = True
            self._drop_window()
            self._pending_first.clear()
            if self._inflight is not None:
                self._fail_one(self._inflight, e)
                self._inflight = None
            if self._job is not None:
                self._fail_one(self._job.p, e)
                self._job = None
            self._abort_pending_swap(e)
            self._abort_pending_knobs(e)
            self._fail_all(e)
        finally:
            # Wind down the delivery thread once the scheduler is done:
            # everything enqueued above (tokens, terminals, errors)
            # flushes before the sentinel, so close() callers see fully
            # delivered sinks once the loop thread joins.
            if not self._emitter.stop():
                logger.warning(
                    "engine emitter did not flush within its stop "
                    "timeout (a stream sink put() is blocking); "
                    "undelivered stream items dropped"
                )
