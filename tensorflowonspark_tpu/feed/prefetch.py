"""Host->device prefetch: overlap transfer with the training step.

The reference's feed path stopped at the host (Spark task -> manager queue
-> ``DataFeed`` -> ``tf.data``); TF's runtime hid the host->device copy.
In JAX that copy is explicit (``device_put`` / ``shard_batch``), and on
TPU hosts it is worth a dedicated thread: while step N executes, batch
N+1 is already in flight over PCIe/DCN, so the transfer hides behind
compute once depth >= 2 (the gain per iteration is not measured on this
installation).

Usage::

    feed = ctx.get_data_feed()
    pf = DevicePrefetcher(
        (feed.next_batch(bs) for _ in iter(int, 1)), mesh, depth=2
    )
    for batch in pf:          # device-resident, mesh-sharded batches
        state, loss = step(state, batch)
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np

from tensorflowonspark_tpu.compute.mesh import shard_batch
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.utils.failpoints import failpoint

logger = logging.getLogger(__name__)

_DONE = object()


# -- obs ---------------------------------------------------------------------

_metrics_lock = threading.Lock()
_metrics: dict[str, Any] | None = None


def metrics() -> dict[str, Any]:
    """Consumer-side feed counters in the process-global obs registry.
    The ``feed.data_wait`` span already narrates per-wait timing into
    the trace plane, but spans do not land in the metrics registry —
    and the autotune prefetch-depth policy needs a *windowed* wait
    share (``History.delta_sum`` over ``feed_data_wait_seconds``) plus
    a delivered-batches throughput objective (``feed_batches_total``)
    to decide grow-vs-shrink. Registered lazily so merely importing the
    feed package never touches the registry."""
    global _metrics
    if _metrics is None:
        with _metrics_lock:
            if _metrics is None:
                from tensorflowonspark_tpu.obs.registry import default_registry

                r = default_registry()
                _metrics = {
                    "data_wait_s": r.histogram(
                        "feed_data_wait_seconds",
                        "seconds the training loop blocked waiting for "
                        "the next device batch",
                    ),
                    "batches": r.counter(
                        "feed_batches_total",
                        "device batches delivered to the training loop "
                        "by DevicePrefetcher",
                    ),
                }
    return _metrics


class _StagingPool:
    """Rotating host staging buffers for the producer thread.

    Columnar batches arrive as views over wire memory (ring slots, TCP
    bytes, mmaps); copying them into a small pool of REUSED contiguous
    host buffers right before ``device_put`` (a) releases the underlying
    ring frame the moment the batch is staged — the "consumed or
    transferred" end of the zero-copy lifetime — and (b) stops the
    steady-state loop from allocating fresh host arrays per batch. The
    pool holds ``depth + 2`` slots so a buffer is never rewritten while
    its batch can still be in flight (queue depth + the consumer's
    current batch + the one being staged) — and, because the Python-side
    window cannot bound XLA's async H2D copy, ``stage`` additionally
    blocks on the slot's PREVIOUS device transfer before rewriting it
    (``commit`` records each transfer result against its slot). Without
    that, an input-bound loop on TPU/GPU could overwrite host memory a
    still-running DMA is reading from."""

    def __init__(self, slots: int):
        self._slots: list[dict | None] = [None] * max(1, slots)
        self._inflight: list[Any] = [None] * max(1, slots)
        self._i = 0
        self._staged_i: int | None = None

    def ensure(self, slots: int) -> None:
        """Grow the pool (never shrink: a retired slot's buffer may
        still back an enqueued batch). Called from the producer thread
        between batches when a live ``set_depth`` widened the window
        past the pool built at construction — without this, a deeper
        queue would let ``stage`` rewrite a host buffer whose batch is
        still waiting to be consumed."""
        extra = int(slots) - len(self._slots)
        if extra > 0:
            self._slots.extend([None] * extra)
            self._inflight.extend([None] * extra)

    def stage(self, batch):
        if not isinstance(batch, dict):
            self._staged_i = None
            return batch  # row-list batches pass through untouched
        i = self._i
        prev = self._inflight[i]
        if prev is not None:
            jax.block_until_ready(prev)
            self._inflight[i] = None
        slot = self._slots[i]
        if (
            slot is None
            or len(slot) != len(batch)
            or any(
                k not in slot
                or slot[k].shape != v.shape
                or slot[k].dtype != v.dtype
                for k, v in batch.items()
            )
        ):
            slot = {
                k: np.empty(v.shape, v.dtype) for k, v in batch.items()
            }
            self._slots[i] = slot
        for k, v in batch.items():
            np.copyto(slot[k], v)
        self._staged_i = i
        self._i = (i + 1) % len(self._slots)
        return slot

    def commit(self, transferred) -> None:
        """Tie the device-side result of the just-staged batch to its
        slot, so the next ``stage`` of that slot can wait out the
        transfer before rewriting the host buffer."""
        if self._staged_i is not None:
            self._inflight[self._staged_i] = transferred
            self._staged_i = None


class DevicePrefetcher:
    """Iterate device-resident batches, transferring ``depth`` ahead.

    ``host_batches`` yields host batches (dict/list/array pytrees);
    ``transform`` (default :func:`shard_batch` over ``mesh``) moves one
    batch to device. The background (daemon) thread stops at iterator
    exhaustion or on ``close()`` — call ``close()`` (or use the context
    manager) when abandoning the iterator early, otherwise the producer
    keeps ``depth`` transferred batches alive until process exit. A raise
    in the producer (e.g. a feed timeout) is re-raised at the consumer's
    next ``__next__`` so errors keep flowing to the training loop.
    """

    def __init__(
        self,
        host_batches: Iterable[Any],
        mesh=None,
        depth: int = 2,
        transform: Callable[[Any], Any] | None = None,
    ):
        if transform is None:
            if mesh is None:
                raise ValueError("need a mesh or an explicit transform")
            transform = lambda b: shard_batch(mesh, b)  # noqa: E731
        self._transform = transform
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        # Cross-thread stats: the producer thread writes, consumers read
        # via stats() — the "is the input plane keeping up" numbers next
        # to the feed.transfer/feed.data_wait spans.
        self._lock = threading.Lock()
        self._prefetch_depth = max(1, int(depth))  # guarded-by: self._lock
        self._transferred = 0  # guarded-by: self._lock
        self._transfer_s = 0.0  # guarded-by: self._lock
        self._thread = threading.Thread(
            target=self._run, args=(iter(host_batches),), daemon=True
        )
        self._thread.start()

    @classmethod
    def from_feed(
        cls,
        feed,
        batch_size: int,
        mesh=None,
        depth: int = 2,
        multiple_of: int = 1,
        prepare: Callable[[Any], Any] | None = None,
        transform: Callable[[Any], Any] | None = None,
        input_mapping: dict[str, str] | None = None,
    ) -> "DevicePrefetcher":
        """THE default training-loop input: device batches straight off a
        :class:`~tensorflowonspark_tpu.feed.datafeed.DataFeed` — or any
        feed with its ``batch_stream`` contract: ``ManifestFeed``
        (manifest records expanded node-locally inside SPARK mode) and
        ``IngestFeed`` (the pull plane's executor-local shard readers)
        plug in unchanged, so both planes end at the same staging +
        H2D/compute overlap.

        The producer thread pulls ``feed.batch_stream(batch_size,
        multiple_of)`` — columnar wire chunks are batch-sliced as
        zero-copy views there — runs ``prepare`` (optional host-side
        transform: dtype casts, normalization), stages the batch into a
        reused host buffer (releasing the underlying ring frame), and
        issues ``shard_batch``/``device_put`` — so columnize + H2D fully
        hide behind step compute::

            feed = ctx.get_data_feed(input_mapping={...})
            with DevicePrefetcher.from_feed(
                feed, bs, mesh, multiple_of=jax.device_count()
            ) as pf:
                for batch in pf:
                    state, loss = step(state, batch)
        """
        staging = _StagingPool(depth + 2)
        if transform is None:
            if mesh is None:
                raise ValueError("need a mesh or an explicit transform")
            transform = lambda b: shard_batch(mesh, b)  # noqa: E731

        # ManifestFeed takes the column mapping at batch_stream (its feed
        # records are manifests, not rows); DataFeed holds it from the ctor.
        kwargs = {} if input_mapping is None else {"input_mapping": input_mapping}

        def host_batches():
            for cols in feed.batch_stream(batch_size, multiple_of, **kwargs):
                yield cols

        holder: dict = {}  # filled after cls() below; producer-thread read

        def stage_and_transfer(cols):
            pf = holder.get("pf")
            if pf is not None:
                # a live set_depth may have widened the window; the
                # pool must cover queue depth + consumer + staging
                staging.ensure(pf.stats()["depth"] + 2)
            if prepare is not None:
                cols = prepare(cols)
            out = transform(staging.stage(cols))
            staging.commit(out)
            return out

        pf = cls(host_batches(), depth=depth, transform=stage_and_transfer)
        holder["pf"] = pf
        return pf

    def stats(self) -> dict:
        """Producer-side counters: batches transferred to device and
        total transfer seconds (divide for the mean transfer cost this
        prefetcher is hiding), plus the current prefetch depth. Safe
        from any thread."""
        with self._lock:
            return {
                "transferred": self._transferred,
                "transfer_s": self._transfer_s,
                "depth": self._prefetch_depth,
            }

    def set_depth(self, depth: int) -> int:
        """Live-resize the prefetch window (the autotune actuation path
        for the ``feed.prefetch_depth`` knob). ``queue.Queue`` freezes
        ``maxsize`` at construction but only consults it under its own
        mutex, so a guarded rewrite plus ``not_full.notify_all()`` is a
        safe live resize: growing immediately unblocks a producer
        waiting in ``put``; shrinking takes effect as the consumer
        drains the (briefly oversized) queue down to the new bound.
        Returns the depth actually in effect."""
        depth = max(1, int(depth))
        q = self._queue
        with q.mutex:
            q.maxsize = depth
            q.not_full.notify_all()
        with self._lock:
            self._prefetch_depth = depth
        return depth

    def _run(self, it: Iterator[Any]) -> None:
        try:
            for batch in it:
                if self._stop.is_set():
                    return
                # chaos: a producer raise here must ferry to the
                # consumer's next __next__, like any real transfer error
                failpoint("prefetch.producer")
                # host->device transfer time, on the producer thread —
                # beside feed.data_wait it answers "is the input plane
                # keeping up or is the consumer starving"
                t0 = time.perf_counter()
                with obs_spans.span("feed.transfer"):
                    item = (self._transform(batch), None)
                with self._lock:
                    self._transferred += 1
                    self._transfer_s += time.perf_counter() - t0
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
            self._put_final((_DONE, None))
        except BaseException as e:  # ferry the error to the consumer
            self._put_final((_DONE, e))

    def _put_final(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.2)
                return
            except queue.Full:
                continue

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self) -> Any:
        if self._stop.is_set():  # exhausted or closed: stay stopped
            raise StopIteration
        # data-wait: how long the training loop sat here is THE
        # input-bound-vs-compute-bound discriminator (tf.data's
        # bottleneck analysis asks exactly this question)
        t0 = time.perf_counter()
        with obs_spans.span("feed.data_wait"):
            batch, err = self._queue.get()
        m = metrics()
        m["data_wait_s"].observe(time.perf_counter() - t0)
        if batch is _DONE:
            self._stop.set()
            if err is not None:
                raise err
            raise StopIteration
        m["batches"].inc()
        return batch

    def close(self) -> bool:
        """Stop the producer and drain the queue; returns whether the
        producer thread actually joined (mirrors ``EmitWorker.stop``:
        ``False`` means it is wedged mid-transfer and was abandoned)."""
        self._stop.set()

        # drain so the producer's blocked put can observe the stop flag;
        # a ferried terminal error found here would otherwise vanish
        # silently with the queue
        def _drain() -> BaseException | None:
            found: BaseException | None = None
            try:
                while True:
                    batch, err = self._queue.get_nowait()
                    if batch is _DONE and err is not None:
                        found = err
            except queue.Empty:
                return found

        swallowed = _drain()
        self._thread.join(timeout=5)
        joined = not self._thread.is_alive()
        # re-drain after the join: _put_final checks the stop flag only
        # BETWEEN put attempts, so an in-flight put can land the ferried
        # (_DONE, err) just after the first drain emptied the queue
        swallowed = _drain() or swallowed
        if swallowed is not None:
            logger.warning(
                "DevicePrefetcher.close: discarding ferried producer "
                "error (never observed by the consumer): %r",
                swallowed,
            )
        if not joined:
            logger.warning(
                "DevicePrefetcher.close: producer thread did not join "
                "within 5s (stuck in transform/transfer); abandoning it"
            )
        return joined

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
