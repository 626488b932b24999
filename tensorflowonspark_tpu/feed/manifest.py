"""Manifest feeding — node-side feeders over the push control plane.

The push plane has a ceiling per driver host: every byte of
``InputMode.SPARK`` crosses the driver. The
reference never had this problem because its feed tasks ran *on the
executors* with HDFS data locality — the driver shipped closures, not
bytes (SURVEY.md §3.2).

This module restores that property inside SPARK mode: the driver feeds
:class:`FileManifest` records (tiny — a path and a format), and the
node-side :class:`ManifestFeed` expands each manifest into its records
by reading the file locally. Driver traffic drops from O(dataset bytes)
to O(number of files); assignment, ordering, epochs, and shutdown keep
the exact ``cluster.train`` semantics (manifests are ordinary records
on the existing queue plane).

Usage::

    # driver: ship paths, not bytes
    cluster.train([[FileManifest(p) for p in shard] for shard in shards])

    # node (map_fun): expand locally
    feed = ManifestFeed(ctx.get_data_feed())
    while not feed.should_stop():
        rows = feed.next_batch(batch_size)

When the files live on shared storage (NFS/GCS/HDFS-FUSE) every node
can read any manifest; with node-local storage, partition the manifests
to match file placement — the driver controls assignment either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Sequence

__all__ = [
    "FileManifest",
    "ManifestFeed",
    "consumed_records",
    "manifest_records",
    "merge_cursor_payloads",
    "plan_manifests",
    "read_manifest",
    "read_manifest_chunks",
    "remaining_manifest",
    "replan_manifests",
    "split_manifest",
    "stream_id",
]


@dataclasses.dataclass(frozen=True)
class FileManifest:
    """One node-readable unit of input: a file (or a record range of one).

    ``format``: ``'tfrecord'`` (rows decoded via the native codec +
    ``dfutil.fromTFExample``), ``'lines'`` (text lines, stripped), or
    ``'columnar'`` (a file of 64-aligned columnar frames written by
    ``feed.columnar.write_frames`` — read back as zero-copy column
    views over one shared mmap; ``ManifestFeed.batch_stream`` slices
    batches straight out of the chunks). Custom formats: pass a
    ``reader`` callable to :class:`ManifestFeed` instead.
    ``start``/``stop`` bound the record index range (Python slice
    semantics), so one large file can be split across nodes.
    """

    path: str
    format: str = "tfrecord"
    start: int = 0
    stop: int | None = None
    binary_features: tuple[str, ...] = ()
    # Training epoch this manifest instance belongs to (pull-mode
    # per-epoch shuffle): folded into :func:`stream_id`, so epoch 1's
    # re-read of the same records is a FRESH replay stream — consumed-
    # cursor state from epoch 0 can never suppress (or be suppressed
    # by) another epoch's pass. 0 keeps the legacy stream id exactly.
    epoch: int = 0


def read_manifest(
    m: FileManifest, reader: Callable[[FileManifest], Iterator[Any]] | None = None
) -> Iterator[Any]:
    """Yield the records a manifest names, reading the file locally."""
    if reader is not None:
        yield from _sliced(reader(m), m)
        return
    if m.format == "tfrecord":
        from tensorflowonspark_tpu.data import dfutil
        from tensorflowonspark_tpu.native.tfrecord import read_records

        # slice the SERIALIZED stream, decode only kept records: a node
        # taking the tail of a shared file must not pay proto decoding
        # for every record it skips
        for s in _sliced(read_records(m.path), m):
            yield dfutil.fromTFExample(s, list(m.binary_features))
    elif m.format == "lines":
        with open(m.path) as f:
            yield from _sliced((line.rstrip("\n") for line in f), m)
    elif m.format == "columnar":
        for chunk in read_manifest_chunks(m):
            yield from chunk.rows()
    else:
        raise ValueError(
            f"unknown manifest format {m.format!r}; use 'tfrecord', "
            "'lines', 'columnar', or pass reader= to ManifestFeed"
        )


def read_manifest_chunks(m: FileManifest, *, frame_cache=None):
    """ColumnChunks of a ``'columnar'`` manifest, honoring its
    ``start``/``stop`` record range by chunk-slicing (views — the mmap
    stays shared). ``frame_cache`` routes frame payload reads through
    the shared cache tier (see ``columnar.read_frames``)."""
    from tensorflowonspark_tpu.feed.columnar import read_frames

    pos = 0
    for chunk in read_frames(m.path, frame_cache=frame_cache):
        lo = max(m.start - pos, 0)
        hi = len(chunk) if m.stop is None else min(m.stop - pos, len(chunk))
        pos += len(chunk)
        if hi <= lo:
            if m.stop is not None and pos >= m.stop:
                return
            continue
        yield chunk if (lo, hi) == (0, len(chunk)) else chunk.view(lo, hi)


def plan_manifests(
    manifests: Sequence[FileManifest],
    num_shards: int,
    *,
    seed: int | None = None,
    epoch: int = 0,
    split: int = 1,
    reader: Callable[[FileManifest], Iterator[Any]] | None = None,
) -> list[list[FileManifest]]:
    """Deterministic round-robin shard assignment — the driver side of
    the pull plane's manifest planning (``TFCluster.assign_shards``).

    Round-robin (like ``TFCluster.train``'s partition assignment) keeps
    per-shard record statistics close to the input distribution when
    file sizes vary. Determinism is a replay requirement, not a
    nicety: an elastic reconfigure re-plans over the surviving roster,
    and a restarted driver must hand every node the same shard it held
    before, or the seeded replay cursors point at the wrong streams.
    Shards may be empty when ``len(manifests) < num_shards`` — a node
    with an empty shard sees an immediately-exhausted feed, not an
    error (skewed file counts are normal at small scale).

    **Per-epoch seeded shuffle** (ROADMAP 4a, the pull-mode
    ``reshuffle_each_iteration``): ``seed`` permutes the manifests with
    a PRNG keyed on ``(seed, epoch)`` — the SAME (seed, epoch) pair
    always reproduces the same plan byte-for-byte (what lets a
    restarted driver, an elastic re-plan, or a resumed run re-derive
    it), while each epoch draws a fresh permutation. ``split > 1``
    first splits every manifest into up to that many contiguous
    record-range pieces (:func:`split_manifest` — header-only for
    ``'columnar'``), making the shuffle block-granular rather than
    file-granular. The ``epoch`` is stamped onto every planned manifest
    and folded into its :func:`stream_id`, so record-exact replay
    cursors stay exact across epochs (resume mid-epoch is zero-dup/
    zero-gap — a cursor from epoch *e* speaks only for epoch *e*'s
    streams). ``seed=None`` with ``epoch > 0`` stamps the epoch without
    permuting.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if split < 1:
        raise ValueError(f"split must be >= 1, got {split}")
    if epoch < 0:
        raise ValueError(f"epoch must be >= 0, got {epoch}")
    ms = list(manifests)
    if split > 1:
        ms = [
            piece
            for m in ms
            for piece in split_manifest(m, split, reader)
        ]
    if epoch and any(
        isinstance(m, FileManifest) and m.epoch != epoch for m in ms
    ):
        ms = [
            dataclasses.replace(m, epoch=int(epoch))
            if isinstance(m, FileManifest)
            else m
            for m in ms
        ]
    if seed is not None:
        import random

        # keyed on (seed, epoch): same pair -> same permutation on any
        # host/run (random.Random is version-stable for shuffle);
        # different epochs draw independent permutations
        rng = random.Random(1_000_003 * int(seed) + int(epoch))
        rng.shuffle(ms)
    return [ms[i::num_shards] for i in range(num_shards)]


def manifest_records(
    m: FileManifest,
    reader: Callable[[FileManifest], Iterator[Any]] | None = None,
) -> int:
    """Record count a manifest names. For ``'columnar'`` manifests this
    is a header-only frame scan (payload bytes untouched — splitting a
    multi-GB file costs one metadata pass); other formats pay a full
    read."""
    if reader is None and m.format == "columnar":
        from tensorflowonspark_tpu.feed.columnar import scan_frames

        total = sum(n for _, _, n in scan_frames(m.path))
        stop = total if m.stop is None else min(m.stop, total)
        return max(0, stop - min(m.start, stop))
    return sum(1 for _ in read_manifest(m, reader))


def split_manifest(
    m: FileManifest,
    n: int,
    reader: Callable[[FileManifest], Iterator[Any]] | None = None,
) -> list[FileManifest]:
    """Split one manifest into at most ``n`` contiguous record-range
    manifests (sizes differ by at most one; empties dropped) so a
    single large file can feed many nodes. Contiguous ranges keep each
    shard a sequential read of its region."""
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    total = manifest_records(m, reader)
    k, rem = divmod(total, n)
    out: list[FileManifest] = []
    lo = 0
    for i in range(n):
        hi = lo + k + (1 if i < rem else 0)
        if hi > lo:
            out.append(
                dataclasses.replace(m, start=m.start + lo, stop=m.start + hi)
            )
        lo = hi
    return out


def stream_id(m: Any) -> str:
    """Deterministic replay-stream id for one manifest: a pure function
    of WHAT is read (path + record range), never of when or by whom —
    a restarted reader, a relaunched node, an elastic re-plan, or the
    driver's shard re-planner all re-derive the same id, which is what
    lets consumed-cursor state and manifests be matched up across
    processes. A re-split's remaining manifest (advanced ``start``) is
    by construction a FRESH stream, and a manifest planned for a later
    ``epoch`` folds the epoch in (``#e<n>``) — each shuffled epoch's
    pass over the same records is its own stream, so cursor
    determinism composes with per-epoch reshuffling. Epoch 0 keeps the
    pre-shuffle id byte-identical (persisted cursors stay valid)."""
    if isinstance(m, FileManifest):
        stop = "" if m.stop is None else int(m.stop)
        sid = f"{m.path}@{int(m.start)}:{stop}"
        if m.epoch:
            sid += f"#e{int(m.epoch)}"
        return sid
    return f"manifest:{m!r}"


# ---------------------------------------------------------------------------
# live shard redistribution: re-planning over per-stream replay cursors
# (docs/ROBUSTNESS.md "Live shard redistribution"). The driver side of
# the handover protocol: given the manifests of the CURRENT plan and the
# union of published consumed-cursors, compute the manifests of the
# REMAINING records and deal them over the surviving workers.
# ---------------------------------------------------------------------------


def _columnar_block_lengths(m: FileManifest) -> list[int]:
    """Record count of each block a ``'columnar'`` manifest's reader
    yields, via header-only frame scans — the exact ``lo``/``hi``
    slicing of :func:`read_manifest_chunks` replayed over
    ``scan_frames`` counts, so block ordinal ``seq`` maps back to a
    record offset without touching payload bytes."""
    from tensorflowonspark_tpu.feed.columnar import scan_frames

    out: list[int] = []
    pos = 0
    for _off, _span, n in scan_frames(m.path):
        lo = max(m.start - pos, 0)
        hi = n if m.stop is None else min(m.stop - pos, n)
        pos += n
        if hi <= lo:
            if m.stop is not None and pos >= m.stop:
                break
            continue
        out.append(hi - lo)
    return out


def consumed_records(
    m: FileManifest,
    entry: Any,
    records_per_chunk: int = 1024,
    frame_blocks: bool | None = None,
) -> int:
    """Records of manifest ``m`` a replay-cursor entry proves consumed,
    counted from ``m.start``. ``entry`` is a
    :func:`~tensorflowonspark_tpu.feed.datafeed.normalize_cursor_entry`
    form (``seq`` or ``[seq, skip]``); ``None`` means nothing consumed.

    Block→record math depends on how the consumer read the manifest:
    ``'columnar'`` manifests (read without a custom reader) have
    frame-sliced blocks — resolved exactly via a header-only scan —
    while every other format streams ``records_per_chunk``-sized blocks
    (``data.readers.columnar_pieces``; the publisher's payload carries
    its value so both sides agree). Pass ``frame_blocks`` to override
    the format-based default (a custom ``reader=`` over a
    ``'columnar'``-format manifest uses chunk math).
    """
    if entry is None:
        return 0
    from tensorflowonspark_tpu.feed.datafeed import normalize_cursor_entry

    seq, skip = normalize_cursor_entry(entry)
    if seq < 0:
        return max(0, skip)
    if frame_blocks is None:
        frame_blocks = m.format == "columnar"
    if frame_blocks:
        lengths = _columnar_block_lengths(m)
        whole = sum(lengths[: seq + 1])
        partial = (
            min(skip, lengths[seq + 1]) if seq + 1 < len(lengths) else 0
        )
        return whole + partial
    # Fixed-size blocks: exact for every mid-stream block (only the tail
    # can be short, and a consumed tail means the stream is finished —
    # the overshoot then lands past the range and reads nothing).
    return (seq + 1) * int(records_per_chunk) + skip


def remaining_manifest(
    m: FileManifest,
    entry: Any,
    records_per_chunk: int = 1024,
    frame_blocks: bool | None = None,
    final: bool = False,
) -> FileManifest | None:
    """The manifest of ``m``'s UNCONSUMED records (``start`` advanced
    past the cursor's consumed prefix — a fresh replay stream), or
    ``None`` when nothing remains. ``final`` asserts full consumption
    regardless of the entry (an exhausted consumer's flag beats block
    math — for non-columnar formats the total is not knowable without
    a full read)."""
    if final:
        return None
    consumed = consumed_records(
        m, entry, records_per_chunk=records_per_chunk, frame_blocks=frame_blocks
    )
    if consumed <= 0:
        return m
    if m.format == "columnar" and (frame_blocks is None or frame_blocks):
        if consumed >= manifest_records(m):
            return None
    elif m.stop is not None and m.start + consumed >= m.stop:
        return None
    return dataclasses.replace(m, start=m.start + consumed)


def merge_cursor_payloads(
    payloads: Iterator[dict[str, Any]] | Sequence[dict[str, Any]],
) -> dict[str, dict[str, Any]]:
    """Union the per-node cursor publications into one per-stream view:
    ``{stream: {"entry", "records_per_chunk", "frame_blocks"}}``.

    Under any single plan each stream has one owner, but across plan
    generations (and across a crash, where the dead node's LAST
    publication and a survivor's re-read both speak for overlapping
    ranges) two payloads can claim the same stream — consumption claims
    are append-only truths, so the one covering more records wins
    (:func:`~tensorflowonspark_tpu.feed.datafeed.cursor_covers`)."""
    from tensorflowonspark_tpu.feed.datafeed import cursor_covers

    merged: dict[str, dict[str, Any]] = {}
    for p in payloads:
        rpc = int(p.get("records_per_chunk", 1024) or 1024)
        fb = p.get("frame_blocks")
        for s, entry in (p.get("cursor") or {}).items():
            s = str(s)
            prev = merged.get(s)
            if prev is None or cursor_covers(entry, prev["entry"]):
                merged[s] = {
                    "entry": entry,
                    "records_per_chunk": rpc,
                    "frame_blocks": fb,
                }
    return merged


def replan_manifests(
    shards: dict[int, Sequence[FileManifest]],
    merged_cursors: dict[str, dict[str, Any]],
    active_ids: Sequence[int],
    final_streams: Sequence[str] = (),
) -> dict[int, list[FileManifest]]:
    """THE re-split: deal the remaining records of a plan over the
    surviving workers.

    ``shards`` is the current plan (executor id → manifests; departed
    ids' shards included — their remainders are exactly what must be
    redistributed), ``merged_cursors`` the
    :func:`merge_cursor_payloads` union, ``final_streams`` the stream
    ids whose owners declared exhaustion (full consumption without
    block math). Returns a plan covering **every** active id (possibly
    with an empty shard) whose manifests partition the unconsumed
    records exactly — zero-gap and zero-dup by construction, because
    consumed prefixes are excluded and each remainder is assigned to
    exactly one worker. Deterministic: original (executor id, position)
    order in, round-robin over sorted active ids out."""
    if not active_ids:
        raise ValueError("cannot replan over an empty active worker set")
    finals = set(final_streams)
    remaining: list[FileManifest] = []
    for eid in sorted(shards):
        for m in shards[eid]:
            sid = stream_id(m)
            info = merged_cursors.get(sid)
            rm = remaining_manifest(
                m,
                None if info is None else info["entry"],
                records_per_chunk=(
                    1024 if info is None else info["records_per_chunk"]
                ),
                frame_blocks=None if info is None else info["frame_blocks"],
                final=sid in finals,
            )
            if rm is not None:
                remaining.append(rm)
    ids = sorted(int(i) for i in active_ids)
    dealt = plan_manifests(remaining, len(ids))
    return {eid: shard for eid, shard in zip(ids, dealt)}


def _sliced(rows: Iterator[Any], m: FileManifest) -> Iterator[Any]:
    import itertools

    if m.start or m.stop is not None:
        return itertools.islice(rows, m.start, m.stop)
    return rows


class ManifestFeed:
    """Expand driver-fed :class:`FileManifest` records into data records.

    Wraps a :class:`~tensorflowonspark_tpu.feed.datafeed.DataFeed`: each
    record pulled from the underlying feed must be a FileManifest (or
    whatever ``reader`` understands); its records stream out of
    :meth:`next_batch` without ever crossing the driver.
    ``should_stop`` matches DataFeed (false until the feed ends AND the
    last manifest is drained), so existing training loops work
    unchanged. One deliberate contract difference: batches fill across
    file AND partition/epoch boundaries (manifests are pulled one at a
    time, so DataFeed's partial-batch-at-EndPartition signal never
    fires here) — steady batch shapes are what jitted training wants.
    Callers needing strict epoch separation should make one ``train``
    + drain cycle per epoch instead of ``num_epochs > 1``.
    """

    def __init__(
        self,
        feed,
        reader: Callable[[FileManifest], Iterator[Any]] | None = None,
    ):
        self.feed = feed
        self.reader = reader
        self._iter: Iterator[Any] | None = None

    def should_stop(self) -> bool:
        return self._iter is None and self.feed.should_stop()

    def next_batch(self, batch_size: int) -> list[Any]:
        """Up to ``batch_size`` records; empty once the feed has ended
        and the last manifest is drained."""
        out: list[Any] = []
        while len(out) < batch_size:
            if self._iter is not None:
                try:
                    out.append(next(self._iter))
                    continue
                except StopIteration:
                    self._iter = None
            got = self.feed.next_batch(1)
            if not got:
                break  # EndOfFeed (DataFeed returns [] only then)
            self._iter = read_manifest(got[0], self.reader)
        return out

    def batch_stream(
        self,
        batch_size: int,
        multiple_of: int = 1,
        input_mapping: dict[str, str] | None = None,
    ):
        """Fixed-size batches, exactly like ``DataFeed.batch_stream``
        (steady jit shapes; the feed tail trims to ``multiple_of``).
        Manifest records are rows, so an ``input_mapping`` for column
        assembly is taken here rather than from the underlying feed
        (whose records are manifests, not rows)."""
        from tensorflowonspark_tpu.utils.batching import fixed_size_batches

        if input_mapping is not None:
            from tensorflowonspark_tpu.feed.columnar import column_batches

            # Columnar manifests contribute whole chunks (batches are
            # then SLICED column views); other formats contribute row
            # lists that pay columnize_rows per batch, as before.
            yield from column_batches(
                self._pieces(batch_size),
                batch_size,
                multiple_of,
                input_mapping,
            )
            return

        def records():
            while not self.should_stop():
                yield from self.next_batch(batch_size)

        yield from fixed_size_batches(
            records(), batch_size, multiple_of, assemble=lambda rows: list(rows)
        )

    def _pieces(self, batch_hint: int):
        """Pieces (ColumnChunk / row lists) across the fed manifests —
        starting with the remainder of a manifest a prior ``next_batch``
        call partially consumed (``self._iter``)."""
        import itertools

        def row_pieces(it):
            while True:
                rows = list(itertools.islice(it, max(batch_hint, 1)))
                if not rows:
                    return
                yield rows

        if self._iter is not None:
            leftover, self._iter = self._iter, None
            yield from row_pieces(leftover)
        while True:
            got = self.feed.next_batch(1)
            if not got:
                return
            m = got[0]
            if (
                self.reader is None
                and isinstance(m, FileManifest)
                and m.format == "columnar"
            ):
                yield from read_manifest_chunks(m)
                continue
            yield from row_pieces(read_manifest(m, self.reader))

    def terminate(self) -> None:
        self.feed.terminate()
