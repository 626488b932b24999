"""Driverless pull ingestion — executor-local sharded columnar readers.

The push plane's ceiling shows why this module exists: every byte of
``InputMode.SPARK`` crosses the single driver process, so the aggregate
is bounded by one host however many nodes consume. The reference never
had the problem because its feed
tasks ran on the executors with HDFS locality — the driver shipped
closures, not bytes (SURVEY.md §3.2); tf.data (arXiv:2101.12127) makes
the same move with source sharding + per-host pipelines, and the
TensorFlow system paper (arXiv:1605.08695) argues for keeping the
coordinator off the data path entirely.

This module is that shape for ``InputMode.TENSORFLOW``: the driver
ships only partition *manifests* (``TFCluster.assign_shards`` →
``feed.manifest.plan_manifests`` → one tiny plan per node over the
manager KV), and each node opens, reads, and columnizes its own shard
locally:

- :class:`ShardReader` iterates a shard's pieces. ``'columnar'``
  manifests (the CRC-framed files from ``feed/columnar.py`` — the
  ready-made on-disk wire format) decode to **zero-copy column views
  over one shared mmap**; other formats stream rows through
  ``data.readers.columnar_pieces`` (block columnization where the data
  lives, with the same row-list fallback matrix as the push wire).
- :class:`IngestFeed` is the ``DataFeed``-shaped consumer: the same
  slice-not-stack batch assembly (``ColumnAssembler``), the same
  ``batch_stream`` contract, and therefore the same
  ``DevicePrefetcher.from_feed`` staging — a training loop moves from
  push to pull by swapping ``ctx.get_data_feed()`` for
  ``ctx.get_ingest_feed()``.

**Exactly-once + ordering.** Every piece of one shard stream carries a
deterministic ``(stream, seq)`` — the stream id is a pure function of
what is read (:func:`stream_id`: path + record range), the seq is the
block ordinal — checked by the same :class:`~tensorflowonspark_tpu.
feed.datafeed.ReplayCursor` protocol as the push wire: duplicates
(a retried shard read, a restarted node re-reading its shard, an
elastic re-plan) drop silently, forward gaps (a lost block — see the
``ingest.read_block`` failpoint) raise. ``IngestFeed.cursor()``
returns only FULLY-consumed blocks (pieces still buffered in the
assembler are excluded), so a consumer that checkpoints the cursor
beside its train state and later seeds a fresh feed
(:meth:`IngestFeed.seed_cursor`) replays with zero duplicates and zero
holes, mid-shard.

Transient read failures retry in place (``RetryPolicy`` backoff; the
replay cursor makes the re-read idempotent); non-retryable failures
propagate and the node relaunch path (``run_with_restarts`` / elastic
supervise) takes over — the successor seeds its cursor and resumes.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Iterator, Sequence

from tensorflowonspark_tpu.cluster import wire
from tensorflowonspark_tpu.feed.columnar import ColumnAssembler, ColumnChunk
from tensorflowonspark_tpu.feed.datafeed import (
    ReplayCursor,
    columnize_rows,
    normalize_cursor_entry,
)
from tensorflowonspark_tpu.feed.manifest import (
    FileManifest,
    read_manifest,
    read_manifest_chunks,
    stream_id,
)
from tensorflowonspark_tpu.obs import flightrec
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.utils.failpoints import FailpointError, failpoint
from tensorflowonspark_tpu.utils.retry import DEFAULT_RETRYABLE, RetryPolicy

logger = logging.getLogger(__name__)

__all__ = ["IngestFeed", "RowPiece", "ShardReader", "metrics", "stream_id"]

# Read faults a shard read retries in place. FailpointError is included
# deliberately: the ``ingest.open_shard`` / ``ingest.read_block`` chaos
# sites exercise exactly this loop (docs/ROBUSTNESS.md failpoint
# conventions — a site opts into retrying injected faults).
_RETRYABLE = DEFAULT_RETRYABLE + (FailpointError,)


# -- obs ---------------------------------------------------------------------

_metrics_lock = threading.Lock()
_metrics: dict[str, Any] | None = None


def metrics() -> dict[str, Any]:
    """Pull-plane ingest counters in the process-global obs registry:
    shard files opened, column-payload bytes and records delivered by
    THIS node's executor-local readers. The driver-side
    ``MetricsAggregator`` differentiates ``feed_ingest_bytes_total``
    between scrapes into the per-node ``cluster_node_ingest_bytes_per_s``
    gauge — the scaling bench's "per-node throughput flat" criterion,
    readable straight off the registry."""
    global _metrics
    if _metrics is None:
        with _metrics_lock:
            if _metrics is None:
                from tensorflowonspark_tpu.obs.registry import default_registry

                r = default_registry()
                _metrics = {
                    "files": r.counter(
                        "feed_ingest_files_total",
                        "shard files opened by executor-local readers, "
                        "by format",
                    ),
                    "bytes": r.counter(
                        "feed_ingest_bytes_total",
                        "column-payload bytes ingested by executor-local "
                        "readers",
                    ),
                    "records": r.counter(
                        "feed_ingest_records_total",
                        "records ingested by executor-local readers",
                    ),
                    # live shard redistribution (handover protocol)
                    "plan_epoch": r.gauge(
                        "ingest_plan_epoch",
                        "membership epoch of the ingest plan currently "
                        "consumed (node) / published (driver)",
                    ),
                    "handover_s": r.histogram(
                        "ingest_handover_seconds",
                        "wall seconds from handover drain to re-split "
                        "adoption",
                    ),
                    "cursor_publishes": r.counter(
                        "ingest_cursor_publishes_total",
                        "replay-cursor publications to the driver KV, "
                        "by kind",
                    ),
                    "cursor_publish_s": r.histogram(
                        "ingest_cursor_publish_seconds",
                        "wall seconds per replay-cursor publication "
                        "(the autotune publish_blocks overhead signal)",
                    ),
                    # growing-dataset wire (TFCluster.extend_shards)
                    "growth_adoptions": r.counter(
                        "ingest_growth_adoptions_total",
                        "same-epoch plan-generation bumps adopted by a "
                        "lingering consumer (appended shards absorbed "
                        "without a membership bump)",
                    ),
                }
    return _metrics


# -- stream identity ---------------------------------------------------------
# stream_id now lives in feed/manifest.py (the driver's shard
# re-planner needs it without importing this module); re-exported here
# unchanged — a pure function of WHAT is read, which is what lets a
# seeded ReplayCursor recognize the already-consumed prefix.


class RowPiece(list):
    """A row-list piece (the non-columnizable fallback) stamped with
    its ``(stream, seq)`` so the consumed-cursor bookkeeping survives
    the fallback path; slicing preserves the stamp (the assembler
    splits head pieces across batches)."""

    __slots__ = ("stream", "seq")

    def __init__(self, rows: Sequence[Any], stream: str | None = None, seq: int = 0):
        super().__init__(rows)
        self.stream = stream
        self.seq = seq

    def __getitem__(self, i):
        out = super().__getitem__(i)
        if isinstance(i, slice):
            return RowPiece(out, self.stream, self.seq)
        return out


# -- executor-local reading --------------------------------------------------


class ShardReader:
    """Reads one node's shard — a list of manifests — locally, yielding
    stamped pieces (``ColumnChunk`` views / :class:`RowPiece` lists).

    Manifests are read sequentially (ordering is part of the replay
    contract); each manifest is one replay stream whose blocks carry
    ordinal ``seq``. A transient failure (``_RETRYABLE``) mid-manifest
    restarts that manifest's read under the jittered ``retry`` policy —
    the caller's :class:`ReplayCursor` drops the re-read prefix, so a
    retry can neither duplicate nor skip records (the ``ingest.
    open_shard`` / ``ingest.read_block`` failpoints exercise this).
    """

    def __init__(
        self,
        manifests: Sequence[Any],
        reader: Callable[[Any], Iterator[Any]] | None = None,
        records_per_chunk: int = 1024,
        retry: RetryPolicy | None = None,
        frame_cache: Any | None = None,
    ):
        self.manifests = list(manifests)
        self.reader = reader
        self.records_per_chunk = int(records_per_chunk)
        # Optional cachetier.FrameCache: 'columnar' manifests fetch
        # frame payloads through the shared read-through tier (one
        # backing read per frame across N co-located readers); cache
        # failure falls back to the local mmap — never an error.
        self.frame_cache = frame_cache
        self.retry = (
            retry
            if retry is not None
            else RetryPolicy(max_attempts=3, deadline_s=120.0)
        )

    def pieces(self, cursor: ReplayCursor) -> Iterator[Any]:
        """All pieces of this shard, in manifest order, deduped/ordered
        through ``cursor``."""
        for m in self.manifests:
            yield from self._manifest_pieces(m, cursor)

    def _manifest_pieces(self, m: Any, cursor: ReplayCursor) -> Iterator[Any]:
        # Hand-rolled rather than RetryPolicy.call: the body is a
        # GENERATOR (pieces stream out between faults), which a
        # callable-wrapping retry cannot express. The policy's
        # invariants are preserved: its jittered schedule, its counter,
        # and its deadline — a sleep never starts at or past the
        # deadline, and never overshoots it.
        from tensorflowonspark_tpu.utils.retry import _retry_counter

        delays = self.retry.delays()
        deadline = (
            None
            if self.retry.deadline_s is None
            else time.monotonic() + self.retry.deadline_s
        )
        attempt = 0
        while True:
            attempt += 1
            try:
                yield from self._read_once(m, cursor)
                return
            except _RETRYABLE as e:
                delay = next(delays, None)
                if delay is None:
                    raise
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise
                    delay = min(delay, remaining)
                _retry_counter().inc(site="ingest.shard")
                logger.warning(
                    "ingest: shard %s read failed (%s: %s); retrying "
                    "(attempt %d/%d) — the replay cursor drops re-read "
                    "blocks",
                    getattr(m, "path", m),
                    type(e).__name__,
                    e,
                    attempt,
                    self.retry.max_attempts,
                )
                time.sleep(delay)

    def _raw_pieces(self, m: Any) -> Iterator[Any]:
        if (
            self.reader is None
            and isinstance(m, FileManifest)
            and m.format == "columnar"
        ):
            # the on-disk wire format: zero-copy views over one mmap,
            # payload-CRC-verified per frame
            yield from read_manifest_chunks(
                m, frame_cache=self.frame_cache
            )
            return
        from tensorflowonspark_tpu.data.readers import columnar_pieces

        yield from columnar_pieces(
            read_manifest(m, self.reader), self.records_per_chunk
        )

    def _read_once(self, m: Any, cursor: ReplayCursor) -> Iterator[Any]:
        met = metrics()
        sid = stream_id(m)
        fmt = m.format if isinstance(m, FileManifest) else "custom"
        failpoint("ingest.open_shard")
        met["files"].inc(format=fmt)
        # ingest.read is an externally-measured interval (spans.record's
        # synthetic lane), accumulated around the read steps only: a
        # call-stack span held open across yields would swallow the
        # consumer's compute between pulls into "read" time.
        read_s = 0.0
        n_records = 0
        raw = self._raw_pieces(m)
        seq = -1
        try:
            while True:
                t0 = time.perf_counter()
                piece = next(raw, None)
                read_s += time.perf_counter() - t0
                if piece is None:
                    return
                seq += 1
                if failpoint("ingest.read_block") == "drop":
                    # chaos: block lost mid-shard — the cursor's gap
                    # check on the NEXT block surfaces it loudly
                    continue
                if not cursor.check(sid, seq):
                    continue  # replayed duplicate (retry/restart/re-plan)
                if isinstance(piece, ColumnChunk):
                    piece = ColumnChunk(
                        piece.kind,
                        piece.keys,
                        piece.arrays,
                        qname=piece.qname,
                        stream=sid,
                        seq=seq,
                    )
                    met["bytes"].inc(piece.nbytes)
                else:
                    piece = RowPiece(piece, sid, seq)
                met["records"].inc(len(piece))
                n_records += len(piece)
                yield piece
                # no piece reference held across the next read — the
                # same liveness rule as the wire pull loops (mmap
                # pinning is milder than ring slots, but uniform rules
                # are checkable rules)
                piece = None
        finally:
            try:
                obs_spans.record(
                    "ingest.read",
                    read_s,
                    path=str(getattr(m, "path", m)),
                    format=fmt,
                    records=n_records,
                )
            except Exception:  # pragma: no cover - interpreter teardown
                pass  # an abandoned reader GC'd at exit must stay quiet


# -- the DataFeed-shaped consumer --------------------------------------------


class IngestFeed:
    """The pull plane's in-node consumer: ``DataFeed``'s surface
    (``next_batch`` / ``should_stop`` / ``batch_stream`` / ``cursor`` /
    ``seed_cursor`` / ``terminate``) over an executor-local
    :class:`ShardReader` — no queue, no driver, no bytes over the
    control plane.

    Construct directly from manifests, or via ``ctx.get_ingest_feed()``
    which fetches this node's shard from the driver-published plan
    (``TFCluster.assign_shards``). With an ``input_mapping`` batches
    are ``{tensor: ndarray}`` columns SLICED from the shard's chunks
    (zero-copy within one chunk); without one, plain record lists.
    Like ``ManifestFeed``, batches fill across file boundaries — steady
    jit shapes are the point of the plane.
    """

    def __init__(
        self,
        manifests: Sequence[Any],
        input_mapping: dict[str, str] | None = None,
        reader: Callable[[Any], Iterator[Any]] | None = None,
        records_per_chunk: int = 1024,
        retry: RetryPolicy | None = None,
        plan_epoch: int = 0,
        plan_seq: int = 0,
        worker_index: int | None = None,
        plan_fetch: Callable[[int, float], dict | None] | None = None,
        cursor_publish: Callable[[dict], None] | None = None,
        epoch_watch: Callable[[], int] | None = None,
        publish_blocks: int = 32,
        adopt_timeout: float = 120.0,
        knob_fetch: Callable[[], dict | None] | None = None,
        frame_cache: Any | None = None,
    ):
        """``plan_fetch`` / ``cursor_publish`` / ``epoch_watch`` arm the
        live-shard-redistribution protocol (all three together — wired
        by ``ctx.get_ingest_feed`` when the driver published the plan
        with ``handover`` set): the feed watches the membership epoch
        (``epoch_watch``, one int read per block), publishes its
        record-exact replay cursor every ``publish_blocks`` fully
        consumed blocks — the crash-handover duplicate bound — and on
        an epoch bump drains to a block boundary, publishes, and adopts
        the driver's re-split (``plan_fetch(min_epoch, timeout)``,
        bounded by ``adopt_timeout``). Unarmed (the default), behavior
        is exactly the PR-8 static-shard feed."""
        self.input_mapping = input_mapping
        self.plan_epoch = int(plan_epoch)
        # plan GENERATION within the membership epoch (the growing-
        # dataset wire): TFCluster.extend_shards bumps it; the
        # exhaustion-linger adopts a same-epoch plan with a higher seq
        # as appended work instead of completing
        self.plan_seq = int(plan_seq)
        self.worker_index = worker_index
        self._user_reader = reader
        self._records_per_chunk = int(records_per_chunk)
        self._retry = retry
        self._frame_cache = frame_cache
        self._reader = ShardReader(
            manifests,
            reader=reader,
            records_per_chunk=records_per_chunk,
            retry=retry,
            frame_cache=frame_cache,
        )
        from tensorflowonspark_tpu.feed.datafeed import _replay_counter

        self._seq = ReplayCursor(
            name=f"ingest shard (worker "
            f"{worker_index if worker_index is not None else '?'})",
            on_drop=lambda _s: _replay_counter().inc(queue="ingest"),
        )
        self._assembler = (
            ColumnAssembler(input_mapping) if input_mapping else None
        )
        self._buffer: list[Any] = []  # rows of a partially-consumed piece
        self._iter: Iterator[Any] | None = None
        self._exhausted = False
        # Exactly-once bookkeeping. Pieces enter assembly in FIFO order
        # and records leave it in the same order, so one cumulative
        # consumption count maps back to (fully-consumed blocks, record
        # offset into the in-progress block) — the record-exact cursor.
        # cursor() runs on the training/checkpoint thread while the
        # DevicePrefetcher producer thread advances consumption, so the
        # bookkeeping is lock-guarded (tfsan dogfood; a torn deque/dict
        # read here would checkpoint a cursor with holes).
        self._cursor_lock = threading.Lock()
        self._delivered: deque = deque()  # (stream, seq, length, base)  # guarded-by: self._cursor_lock
        self._head_consumed = 0  # records consumed from _delivered[0]  # guarded-by: self._cursor_lock
        # stream -> consumed state: int (last fully consumed seq) or
        # [seq, skip] (seeded mid-block state not yet superseded by
        # this feed's own progress)
        self._done: dict[str, Any] = {}  # guarded-by: self._cursor_lock
        self._pending_skip: dict[str, tuple[int, int]] = {}  # seeded offsets  # guarded-by: self._cursor_lock
        # -- live shard redistribution (handover protocol) -----------------
        self._plan_fetch = plan_fetch
        self._cursor_publish = cursor_publish
        self._epoch_watch = epoch_watch
        self._handover = (
            plan_fetch is not None
            and epoch_watch is not None
        )
        self._publish_blocks = max(1, int(publish_blocks))  # guarded-by: self._cursor_lock
        self._adopt_timeout = float(adopt_timeout)
        self._blocks_since_publish = 0  # guarded-by: self._cursor_lock
        # Driver-pushed feed knobs (autotune): a driver-side controller
        # re-publishes {seq, knobs} to the KV; this feed polls at block
        # boundaries (time-gated) and adopts monotonically by seq.
        self._knob_fetch = knob_fetch
        self._knob_seq = -1  # last adopted knob publication seq
        self._knob_poll_ts = 0.0  # consumer-thread-only time gate
        self._terminated = False
        self._complete = False
        if self._handover:
            metrics()["plan_epoch"].set(self.plan_epoch)
            # announce the subscription: an epoch bump landing before
            # the first periodic publication must still find this
            # consumer in the driver's cursor table, so the drain wait
            # covers it (zero-dup needs the driver to wait for us)
            self._publish_cursor(final=False, kind="announce")

    # -- replay cursor -------------------------------------------------
    def cursor(self) -> dict[str, Any]:
        """Record-exact consumption snapshot, per stream: ``seq`` when
        block ``seq`` is the last FULLY consumed one, or ``[seq, skip]``
        when additionally the first ``skip`` records of block
        ``seq + 1`` have left in batches. Records still buffered inside
        the feed (read but never batched out) are NOT counted — a
        successor seeded with this snapshot (:meth:`seed_cursor`)
        re-reads them: zero duplicates, zero holes, mid-shard and even
        mid-block. Checkpoint it beside the train state. Safe to call
        from any thread while the feed is being consumed."""
        with self._cursor_lock:
            return self._cursor_locked()

    def _cursor_locked(self) -> dict[str, Any]:  # lint: holds-lock
        out: dict[str, Any] = dict(self._done)
        if self._delivered and self._head_consumed:
            s, q, _ln, base = self._delivered[0]
            if s is not None:
                out[s] = wire.encode_cursor_entry(
                    q - 1, base + self._head_consumed
                )
        return out

    def seed_cursor(self, cursor: dict[str, Any]) -> None:
        """Adopt a :meth:`cursor` snapshot BEFORE consuming. Whole
        blocks at or below each stream's seeded seq drop as replayed
        duplicates on the re-read; a ``[seq, skip]`` entry additionally
        trims the first ``skip`` records off block ``seq + 1``. Plain
        ``{stream: seq}`` cursors (the push plane's ``DataFeed``
        format) are accepted unchanged.

        Seeded state is itself part of :meth:`cursor`'s output until
        this feed makes further progress on the stream: a successor
        that crashes before touching an already-consumed stream must
        still hand ITS successor the full consumed prefix — otherwise
        the third incarnation would replay whole streams (duplicates).
        """
        seed: dict[str, int] = {}
        with self._cursor_lock:
            for s, v in cursor.items():
                s = str(s)
                seq0, skip = normalize_cursor_entry(v)
                if seq0 >= 0:
                    seed[s] = seq0
                if skip > 0:
                    self._pending_skip[s] = (seq0 + 1, skip)
                    self._done[s] = wire.encode_cursor_entry(seq0, skip)
                elif seq0 >= 0:
                    self._done[s] = wire.encode_cursor_entry(seq0)
        self._seq.seed(seed)

    # -- live shard redistribution (the handover protocol) --------------
    def _handover_due(self) -> bool:
        """One int compare per block: has the membership epoch moved
        past the plan this feed is consuming?"""
        return self._handover and self._epoch_watch() > self.plan_epoch

    def publish_cursor(self, final: bool = False) -> None:
        """Publish this feed's record-exact replay cursor to the driver
        KV now (best-effort, like the periodic beat). A planned leaver
        calls this right before exiting so the re-split starts from an
        exact cursor — zero duplicates — instead of the last periodic
        one."""
        self._publish_cursor(final=final, kind="explicit")

    def _publish_cursor(
        self,
        epoch: int | None = None,
        final: bool = False,
        kind: str = "periodic",
        done: bool | None = None,
    ) -> None:
        """Best-effort by contract: a lost publication can only widen
        the crash-handover duplicate window (the driver falls back to
        an older cursor), never lose records — so a failure here warns
        and moves on rather than killing training.

        Default stamp is ``plan_epoch`` — the plan this cursor was
        consumed UNDER — never the watched epoch: a periodic beat that
        landed after a bump but before this feed drained must not
        satisfy the driver's drain wait (it would release the re-split
        while this consumer is still emitting old-plan records). Only
        the drain/final paths, which have actually stopped consuming,
        pass the observed epoch explicitly."""
        if self._cursor_publish is None:
            return
        if epoch is None:
            epoch = self.plan_epoch
        payload = wire.encode(
            "ingest.cursor_payload",
            epoch=int(epoch),
            final=bool(final),
            # done = this consumer will NEVER consume again (final OR
            # terminated): the driver stops waiting on it, stops
            # assigning it work, and completion need not require a
            # fresh stamp from it
            done=bool(final if done is None else done),
            cursor=self.cursor(),
            records_per_chunk=self._records_per_chunk,
            # block→record math hint for the driver's re-planner: a
            # custom reader streams records_per_chunk blocks even over
            # 'columnar'-format manifests
            frame_blocks=False if self._user_reader is not None else None,
            # plan generation this cursor was consumed under: the
            # driver's completion check must not accept a final
            # published BEFORE the dataset grew (growing-dataset wire)
            plan_seq=self.plan_seq,
        )
        try:
            t0 = time.perf_counter()
            self._cursor_publish(payload)
            met = metrics()
            met["cursor_publishes"].inc(kind=kind)
            # measured per-publication cost: the autotune
            # publish_blocks policy trades this overhead against the
            # crash-replay duplicate bound
            met["cursor_publish_s"].observe(time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 - best-effort by contract
            logger.warning(
                "ingest: cursor publication failed (%s) — the driver "
                "will fall back to the last one it has (duplicates "
                "bounded by the staleness, zero-gap unaffected)",
                e,
            )

    def _run_handover(self) -> None:
        """Cooperative adoption, the consumer side of the protocol:
        (1) drain to a block boundary on the old plan — every record
        that left in a batch is consumed; read-but-unconsumed records
        buffered in the feed are DISCARDED for replay (the re-split
        covers them, so discarding is what makes the handover
        zero-dup/zero-gap); (2) publish the record-exact ``[seq,
        skip]`` cursor; (3) adopt the driver's re-split for the new
        epoch, reseeding the sequence cursor from consumed state."""
        t0 = time.monotonic()
        observed = max(self._epoch_watch(), self.plan_epoch)
        skip_publish = failpoint("ingest.handover_drain") == "drop"
        with self._cursor_lock:
            # fold the consumed snapshot (incl. the partial head's
            # [seq, skip]) into _done, then drop everything unconsumed
            self._done = self._cursor_locked()
            self._delivered.clear()
            self._head_consumed = 0
            self._pending_skip.clear()
        self._buffer = []
        if self._assembler is not None and len(self._assembler):
            self._assembler.take(len(self._assembler))  # discard: replays
        it, self._iter = self._iter, None
        if it is not None and hasattr(it, "close"):
            it.close()
        if not skip_publish:
            self._publish_cursor(epoch=observed, final=False, kind="drain")
        failpoint("ingest.plan_adopt")
        plan = self._plan_fetch(observed, self._adopt_timeout)
        if plan is None:
            raise TimeoutError(
                f"ingest handover: no plan for membership epoch >= "
                f"{observed} within {self._adopt_timeout}s — the driver "
                "stopped republishing (worker "
                f"{self.worker_index if self.worker_index is not None else '?'})"
            )
        self._adopt(plan)
        dt = time.monotonic() - t0
        metrics()["handover_s"].observe(dt)
        flightrec.note(
            "ingest_handover",
            worker=self.worker_index,
            from_epoch=observed,
            epoch=self.plan_epoch,
            manifests=len(self._reader.manifests),
            seconds=round(dt, 3),
        )
        logger.info(
            "ingest: handover to plan epoch %d (%d manifest(s), %.3fs)",
            self.plan_epoch,
            len(self._reader.manifests),
            dt,
        )

    def _adopt(self, plan: dict) -> None:
        """Swap in a re-split plan: fresh reader, fresh block-sequence
        cursor reseeded from consumed state (a zero-consumption stream
        keeps its id across a re-split, and the old cursor's accepted
        blocks would wrongly dedupe its legitimate re-read)."""
        manifests = list(plan.get("manifests") or [])
        from tensorflowonspark_tpu.feed.datafeed import _replay_counter

        with self._cursor_lock:
            self.plan_epoch = int(plan.get("epoch", self.plan_epoch))
            self.plan_seq = int(plan.get("seq") or 0)
            self._complete = bool(plan.get("complete"))
            self._pending_skip = {}
            done = dict(self._done)
        self._seq = ReplayCursor(
            name=f"ingest shard (worker "
            f"{self.worker_index if self.worker_index is not None else '?'})",
            on_drop=lambda _s: _replay_counter().inc(queue="ingest"),
        )
        # re-seed from consumed state through the ONE entry-splitting
        # implementation (seed_cursor re-derives _done from its own
        # snapshot — idempotent)
        self.seed_cursor(done)
        self._reader = ShardReader(
            manifests,
            reader=self._user_reader,
            records_per_chunk=self._records_per_chunk,
            retry=self._retry,
            frame_cache=self._frame_cache,
        )
        self._iter = None
        self._exhausted = False
        metrics()["plan_epoch"].set(self.plan_epoch)

    def _adopt_growth(self, plan: dict) -> None:
        """Adopt a same-epoch plan-generation bump from the linger: the
        plan's manifest list is CUMULATIVE (old shard + appended), but
        at linger time every current stream is fully consumed — so the
        reader is rebuilt over only the streams ``_done`` has no state
        for (the appended ones), avoiding an O(history) re-scan per
        growth cycle. ``_done`` keeps the full consumed prefix, so
        ``cursor()`` still reports exactly-once state over the whole
        grown dataset."""
        with self._cursor_lock:
            consumed = set(self._done)
        manifests = [
            m
            for m in (plan.get("manifests") or [])
            if stream_id(m) not in consumed
        ]
        n_appended = len(manifests)
        self._adopt(dict(plan, manifests=manifests))
        metrics()["growth_adoptions"].inc()
        flightrec.note(
            "ingest_handover",
            worker=self.worker_index,
            cause="growth",
            epoch=self.plan_epoch,
            plan_seq=self.plan_seq,
            manifests=n_appended,
        )
        logger.info(
            "ingest: adopted grown plan seq %d (%d appended "
            "manifest(s) at epoch %d)",
            self.plan_seq,
            n_appended,
            self.plan_epoch,
        )

    def _await_redistribution(self) -> bool:
        """Shard exhausted under an armed handover: publish the FINAL
        cursor (full consumption, the driver's completion signal) and
        linger for either a plan-epoch bump — adopt the re-split and
        return True (more work may exist) — or the driver's completion
        marker / :meth:`terminate` — return False, the feed is done.
        The linger is what lets a survivor that finished its own shard
        early absorb a dead peer's remainder instead of exiting."""
        if not self._handover or self._terminated or self._complete:
            return False
        published_final = False
        while True:
            if self._terminated:
                return False
            if self._handover_due():
                self._run_handover()
                return not self._complete
            if not published_final:
                # Published only while NO bump is pending, stamped with
                # the PLAN epoch: finality at epoch E means "I adopted
                # plan E and consumed all of it". Stamping the watched
                # epoch here would let a final slip out between a bump
                # and this consumer's adoption — the driver's
                # completion check would then release everyone while
                # the re-split's manifests are still unread (a
                # zero-gap race).
                self._publish_cursor(
                    epoch=self.plan_epoch, final=True, kind="final"
                )
                published_final = True
            plan = self._plan_fetch(self.plan_epoch, 0.0)
            if (
                plan is not None
                and plan.get("complete")
                and int(plan.get("epoch", 0)) >= self.plan_epoch
            ):
                self._complete = True
                return False
            if (
                plan is not None
                and not plan.get("complete")
                and int(plan.get("epoch", 0)) == self.plan_epoch
                and int(plan.get("seq") or 0) > self.plan_seq
            ):
                # the growing-dataset wire: a SAME-epoch plan with a
                # higher generation is appended work (TFCluster.
                # extend_shards) — adopt it and resume consuming. The
                # final published above is stamped with the OLD seq, so
                # the driver's completion check cannot mistake it for
                # exhaustion of the grown dataset.
                self._adopt_growth(plan)
                return True
            time.sleep(0.25)

    # -- iteration core ------------------------------------------------
    def _pieces_iter(self) -> Iterator[Any]:
        if self._iter is None:
            self._iter = self._reader.pieces(self._seq)
        return self._iter

    def _pull_piece(self, inline_handover: bool = True) -> Any | None:
        """Next piece off the reader, seeded-skip applied and delivery
        recorded for the consumed-cursor bookkeeping.

        With the handover armed, an epoch bump observed here either
        runs the handover INLINE (default — safe whenever every
        read-but-unconsumed record lives in feed-owned buffers, which
        the drain discards for replay) or, with
        ``inline_handover=False``, returns ``None`` as a PAUSE so the
        caller can release externally buffered rows first (the
        mapping-less ``batch_stream``, whose pending rows sit inside
        ``fixed_size_batches``)."""
        while not self._exhausted:
            if self._handover_due():
                if not inline_handover:
                    return None  # pause: caller drains, then hands over
                self._run_handover()
                continue
            piece = next(self._pieces_iter(), None)
            if piece is None:
                self._exhausted = True
                return None
            stream = getattr(piece, "stream", None)
            seq = int(getattr(piece, "seq", 0))
            base = 0
            if stream is not None:
                with self._cursor_lock:
                    sk = self._pending_skip.get(stream)
                    matched = sk is not None and sk[0] == seq
                    if matched:
                        del self._pending_skip[stream]
                if matched:
                    base = min(int(sk[1]), len(piece))
                    if base:
                        piece = (
                            piece.view(base, len(piece))
                            if isinstance(piece, ColumnChunk)
                            else RowPiece(list(piece)[base:], stream, seq)
                        )
            if len(piece):
                with self._cursor_lock:
                    self._delivered.append((stream, seq, len(piece), base))
                return piece
        return None

    def _advance_consumed(self, n: int) -> None:
        """Records left the feed in a batch (or were dropped at the
        tail): pop fully-consumed pieces off the delivery FIFO and
        advance the per-stream done cursor. Every ``publish_blocks``
        fully consumed blocks, the handover-armed feed publishes its
        cursor to the driver KV — the periodic beat whose interval
        bounds crash-handover duplicates."""
        publish = False
        with self._cursor_lock:
            self._head_consumed += int(n)
            while self._delivered:
                s, q, ln, _base = self._delivered[0]
                if self._head_consumed < ln:
                    break
                self._delivered.popleft()
                self._head_consumed -= ln
                if s is not None:
                    self._done[s] = q
                    self._blocks_since_publish += 1
            if (
                self._handover
                and self._blocks_since_publish >= self._publish_blocks
            ):
                self._blocks_since_publish = 0
                publish = True
        if publish:
            self._publish_cursor(final=False, kind="periodic")
        self._maybe_adopt_knobs()

    def set_publish_blocks(self, blocks: int) -> int:
        """Live-set the cursor-publication interval (the autotune
        actuation path for the ``ingest.publish_blocks`` knob): how
        many fully consumed blocks between periodic replay-cursor
        publications — the knob trading publication RPC overhead
        against the crash-handover duplicate bound. Returns the value
        in effect."""
        blocks = max(1, int(blocks))
        with self._cursor_lock:
            self._publish_blocks = blocks
        return blocks

    def publish_blocks(self) -> int:
        """The cursor-publication interval in effect (knob readback)."""
        with self._cursor_lock:
            return self._publish_blocks

    def _maybe_adopt_knobs(self, now: float | None = None) -> None:
        """Consumer thread, outside the cursor lock: poll the driver's
        feed-knob publication (time-gated — at most one KV read every
        few seconds regardless of batch rate) and adopt it
        monotonically by seq. Best-effort like the cursor beat: a
        failed fetch warns once per poll and keeps the current knobs."""
        if self._knob_fetch is None:
            return
        if now is None:
            now = time.monotonic()
        if now - self._knob_poll_ts < 5.0:
            return
        self._knob_poll_ts = now
        try:
            pub = self._knob_fetch()
        except Exception as e:  # noqa: BLE001 - best-effort by contract
            logger.warning(
                "ingest: feed-knob fetch failed (%s) — keeping the "
                "current knobs",
                e,
            )
            return
        if not pub:
            return
        seq = int(pub.get("seq", 0))
        if seq <= self._knob_seq:
            return  # already adopted (or a stale republish)
        self._knob_seq = seq
        knobs = pub.get("knobs") or {}
        if "publish_blocks" in knobs:
            self.set_publish_blocks(int(knobs["publish_blocks"]))
            logger.info(
                "ingest: adopted driver feed knobs seq=%d "
                "(publish_blocks=%d)",
                seq,
                self.publish_blocks(),
            )

    def should_stop(self) -> bool:
        """True once the shard is exhausted AND every buffered record
        has left in a batch (``DataFeed.should_stop`` contract).

        Handover-armed feeds add one clause: an exhausted-and-drained
        feed is not DONE until the driver says the whole dataset is
        (completion marker) or an epoch bump hands it more work — so
        this call may BLOCK while it lingers (bounded by driver
        progress; ``terminate()`` from another thread unblocks it)."""
        drained = (
            self._exhausted
            and not self._buffer
            and (self._assembler is None or len(self._assembler) == 0)
        )
        if not drained:
            return False
        if not self._handover or self._terminated or self._complete:
            return True
        return not self._await_redistribution()

    def next_batch(self, batch_size: int) -> list | dict[str, Any]:
        """Up to ``batch_size`` records; partial only at shard end.
        Mapped feeds return sliced ``{tensor: column}`` dicts, mapping-
        less feeds record lists (``ColumnChunk.rows`` semantics, as on
        the push wire)."""
        if self._assembler is None:
            if self.input_mapping is not None:
                # degenerate empty mapping: legacy stacking contract
                return columnize_rows(
                    self._next_raw(batch_size), self.input_mapping
                )
            return self._next_raw(batch_size)
        asm = self._assembler
        while len(asm) < batch_size:
            piece = self._pull_piece()
            if piece is None:
                break
            asm.push(piece)
        n = min(batch_size, len(asm))
        out = asm.take(batch_size)
        self._advance_consumed(n)
        return out

    def _next_raw(
        self,
        batch_size: int,
        account: bool = True,
        inline_handover: bool = True,
    ) -> list:
        """Up to ``batch_size`` raw records. ``account=False`` defers
        the consumed-cursor advance to the caller — rows handed to an
        intermediate buffer (``fixed_size_batches``) have NOT left the
        feed yet, and counting them consumed would punch resume holes.

        An inline handover is only legal while every pulled row is in
        FEED-OWNED buffers (the drain discards those for replay); rows
        already moved into the local ``batch`` are neither claimed by
        the drain cursor nor discarded, so once ``batch`` is non-empty
        an epoch bump PAUSES the loop instead (partial batch out,
        consumption accounted against the old plan; the handover runs
        on the next call, when the slate is clean)."""
        batch: list[Any] = []
        while len(batch) < batch_size:
            take = batch_size - len(batch)
            if self._buffer:
                batch.extend(self._buffer[:take])
                del self._buffer[:take]
                continue
            piece = self._pull_piece(
                inline_handover=inline_handover and not batch
            )
            if piece is None:
                break
            if isinstance(piece, ColumnChunk):
                self._buffer.extend(piece.rows())
            else:
                self._buffer.extend(piece)
            piece = None
        if account:
            self._advance_consumed(len(batch))
        return batch

    def batch_stream(
        self,
        batch_size: int,
        multiple_of: int = 1,
        input_mapping: dict[str, str] | None = None,
    ):
        """Fixed-size batches with the ``DataFeed.batch_stream``
        contract: every yield has exactly ``batch_size`` records
        (rounded down to ``multiple_of``) until the shard tail, which
        trims to the largest multiple (sub-multiple remainder dropped
        with a log line). The mapping may come from the constructor
        (``DataFeed`` style) or here (``ManifestFeed`` style) — either
        way ``DevicePrefetcher.from_feed`` drives it unchanged."""
        mapping = (
            input_mapping if input_mapping is not None else self.input_mapping
        )
        if not mapping:
            from tensorflowonspark_tpu.utils.batching import fixed_size_batches

            # consumption is advanced per EMITTED batch, never when rows
            # merely enter fixed_size_batches' pending buffer — those
            # rows have not left the feed, and counting them consumed
            # would make a checkpointed cursor skip them on resume.
            # Handover pauses must happen OUTSIDE _pull_piece here
            # (inline_handover=False): rows pending inside
            # fixed_size_batches are out of the feed's reach, so the
            # drain first lets the batcher flush its trimmed tail, then
            # hands over — the un-emitted sub-multiple remainder stays
            # unconsumed and replays under the re-split.
            while True:
                pulled = 0
                paused = [False]

                def records():
                    nonlocal pulled
                    while True:
                        if self._handover_due():
                            paused[0] = True
                            return
                        rows = self._next_raw(
                            batch_size, account=False, inline_handover=False
                        )
                        if not rows:
                            paused[0] = self._handover_due()
                            return
                        pulled += len(rows)
                        yield from rows

                emitted = 0
                for batch in fixed_size_batches(
                    records(),
                    batch_size,
                    multiple_of,
                    assemble=lambda rows: list(rows),
                ):
                    emitted += len(batch)
                    self._advance_consumed(len(batch))
                    yield batch
                if paused[0]:
                    # the pulled-but-unemitted remainder was NOT
                    # advanced: the handover discards it for replay
                    self._run_handover()
                    continue
                # normal exhaustion: the sub-multiple remainder was
                # DROPPED (drop-remainder semantics) — dropped counts
                # as consumed. Unreached on an early generator close,
                # where the pending rows were never delivered and must
                # replay.
                self._advance_consumed(pulled - emitted)
                if (
                    self._exhausted
                    and self._handover
                    and not self._terminated
                    and not self._complete
                    and self._await_redistribution()
                ):
                    continue
                return
        if self._assembler is None or self._assembler.mapping != mapping:
            old = self._assembler
            self._assembler = ColumnAssembler(dict(mapping))
            # FIFO order is the cursor's correctness invariant: oldest
            # unconsumed records (a prior mapping-less next_batch's row
            # buffer) re-enter assembly first.
            if self._buffer:
                self._assembler.push(list(self._buffer))
                self._buffer = []
            if old is not None:
                for piece in old.drain_pieces():
                    self._assembler.push(piece)
        bs = batch_size - batch_size % multiple_of
        if bs == 0:
            raise ValueError(
                f"batch_size < multiple_of ({multiple_of}); nothing to yield"
            )
        asm = self._assembler
        while True:
            while len(asm) < bs:
                piece = self._pull_piece()
                if piece is None:
                    break
                asm.push(piece)
            if len(asm) >= bs:
                batch = asm.take(bs)
                self._advance_consumed(bs)
                yield batch
                continue
            # reader exhausted (handover pauses run inline on this
            # path — every buffered record is feed-owned)
            if (
                self._handover
                and not self._terminated
                and not self._complete
            ):
                # plan boundary: flush the buffered tail exactly like
                # the feed end (one short batch + drop-remainder), so
                # the FINAL cursor the await publishes is exact, then
                # linger for a re-split or the completion marker
                yield from self._flush_tail(asm, multiple_of)
                if self._await_redistribution():
                    continue
            break
        yield from self._flush_tail(asm, multiple_of)

    def _flush_tail(self, asm: ColumnAssembler, multiple_of: int):
        """Feed-end tail contract, shared by final exhaustion and every
        handover plan boundary: emit the largest ``multiple_of``
        multiple as one (short) batch, drop the sub-multiple remainder
        loudly — dropped counts as consumed (a resume or re-split must
        not replay it; same semantics as the push wire)."""
        tail = len(asm) - len(asm) % multiple_of
        rem = len(asm) % multiple_of
        if rem:
            logger.warning(
                "dropping %d tail records (not a multiple of %d)",
                rem,
                multiple_of,
            )
        if tail:
            batch = asm.take(tail)
            self._advance_consumed(tail)
            yield batch
        if len(asm):
            asm.take(len(asm))
            self._advance_consumed(rem)

    def terminate(self) -> None:
        """Stop reading (early stop). Purely local — there is no
        producer to signal on the pull plane — except that a
        handover-armed feed publishes its cursor once more (best
        effort) so the driver's view of this consumer is as fresh as
        possible, and any blocked :meth:`should_stop` linger unblocks."""
        self._terminated = True
        self._exhausted = True
        it, self._iter = self._iter, None
        if it is not None and hasattr(it, "close"):
            it.close()
        if self._handover:
            # a terminated feed consumes nothing more, so its cursor is
            # drain-exact: stamp the observed epoch, sparing the driver
            # a full drain-timeout wait on a consumer that cannot answer
            self._publish_cursor(
                epoch=max(self.plan_epoch, self._epoch_watch()),
                final=False,
                kind="terminate",
                done=True,
            )
