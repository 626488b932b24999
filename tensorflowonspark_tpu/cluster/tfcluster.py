"""Driver-side cluster orchestrator.

Reference parity: ``tensorflowonspark/TFCluster.py`` — ``InputMode``,
``run()`` (role template → reservation server → launch nodes → roster
barrier → handle), ``TFCluster.train/inference/shutdown/tensorboard_url``.

TPU-native differences:

- ``num_ps`` is rejected: parameter servers dissolve into sharded optimizer
  state (FSDP) on the mesh — see SURVEY.md §2.3 and
  :mod:`tensorflowonspark_tpu.compute`.
- The roster carries a ``jax.distributed`` coordinator address instead of a
  TF_CONFIG role map.
- Data feeding runs from driver-side threads over TCP to each node's
  manager (Spark's feed *tasks* collapse into these threads).
"""

from __future__ import annotations

import logging
import os
import queue as _stdqueue
import secrets
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Sequence

from tensorflowonspark_tpu.cluster import node as tfnode_runtime
from tensorflowonspark_tpu.cluster import reservation
from tensorflowonspark_tpu.cluster import wire
from tensorflowonspark_tpu.cluster.launchers import LocalLauncher
from tensorflowonspark_tpu.obs import cluster as obs_cluster
from tensorflowonspark_tpu.obs import flightrec
from tensorflowonspark_tpu.obs.registry import default_registry

logger = logging.getLogger(__name__)


class InputMode:
    """Reference: ``TFCluster.py:InputMode``."""

    TENSORFLOW = 0  # nodes read data themselves (files / grain / tf.data)
    SPARK = 1  # driver pushes partitions into node queues (the push plane)


class TFCluster:
    """Handle to a running cluster; returned by :func:`run`."""

    def __init__(
        self,
        launcher,
        server: reservation.Server,
        server_addr: tuple[str, int],
        cluster_info: list[dict[str, Any]],
        cluster_meta: dict[str, Any],
        input_mode: int,
        queues: Sequence[str],
    ):
        self.launcher = launcher
        self.server = server
        self.server_addr = server_addr
        self.cluster_info = cluster_info
        self.cluster_meta = cluster_meta
        self.input_mode = input_mode
        self.queues = queues
        self.heartbeat_interval = float(
            cluster_meta.get("heartbeat_interval", 0) or 0
        )
        self.heartbeat_grace = float(cluster_meta.get("heartbeat_grace", 0) or 0)
        # Chunk-columnar wire format on the feed plane (feed/columnar.py);
        # False pins the legacy row-pickle wire end-to-end.
        self.columnar = bool(cluster_meta.get("columnar", True))
        self._shutdown_done = False
        self._dstream_bridge: tuple | None = None
        # -- elastic plane (compute/elastic.py; docs/ROBUSTNESS.md) --------
        # With elastic=True, supervise() answers a membership change with
        # a reconfigure (remove/admit + epoch bump) instead of raising.
        self.elastic = bool(cluster_meta.get("elastic", False))
        self.elastic_min_nodes = int(cluster_meta.get("elastic_min_nodes", 1))
        # Live shard redistribution (docs/ROBUSTNESS.md): with
        # ingest_handover (the default), an elastic reconfigure
        # RE-SPLITS the remaining records over the survivors instead of
        # re-publishing stable shards — the PR-8 stable assignment
        # stays as the ingest_handover=False fallback.
        self.ingest_handover = bool(cluster_meta.get("ingest_handover", True))
        self.handover_timeout = float(
            cluster_meta.get("handover_timeout", 30.0)
        )
        # The startup barrier roster is epoch-0 membership.
        server.reservations.seal()
        # Executors that elastically LEFT (death or voluntary): their
        # nonzero exits are expected, not failures, and no manager RPC
        # may ever target them again.
        self._departed: set[int] = set()  # guarded-by: self._dead_lock
        # Launchers spawned for replacement nodes (launch_replacement);
        # shutdown waits on / terminates these alongside the primary.
        self._replacement_launchers: list[Any] = []
        # The env run() launched nodes with — replacements must boot
        # with the same one (run() fills this in).
        self._node_env: dict[str, str] = {}
        # Pull-plane shard map (assign_shards): executor id -> manifest
        # list. With the handover protocol armed (elastic +
        # ingest_handover) this is the CURRENT plan — each reconfigure
        # replaces it with the re-split of the remaining records; with
        # handover off it is stable per executor id forever (PR-8).
        # Guarded: the supervise thread re-splits while the user thread
        # may still be assigning/tearing down.
        self._ingest_lock = threading.Lock()
        self._ingest_shards: dict[int, list[Any]] | None = None  # guarded-by: self._ingest_lock
        self._ingest_complete = False  # guarded-by: self._ingest_lock
        self._ingest_republished = False  # guarded-by: self._ingest_lock
        # Plan GENERATION within a membership epoch (the growing-
        # dataset wire): bumped by assign_shards and extend_shards so a
        # lingering consumer can tell appended work from a stale
        # republish, and so completion requires finals at the CURRENT
        # generation (a final published before an append must not
        # complete the grown dataset).
        self._ingest_seq = 0  # guarded-by: self._ingest_lock
        # Online mode (run_online): suppress the supervise loop's
        # auto-completion while the dataset is still growing; shutdown
        # clears it so teardown always releases lingering consumers.
        self._ingest_hold_completion = False  # guarded-by: self._ingest_lock
        # Serializes whole plan-mutation episodes (an epoch re-split vs
        # a growth append) INCLUDING their out-of-lock IO, so neither
        # can clobber the other's published plan. Ordering:
        # _ingest_replan_lock > _ingest_lock, never the reverse.
        self._ingest_replan_lock = threading.Lock()
        # Driver-pushed feed knobs (autotune): monotonically increasing
        # publication seq — consumers adopt each publication once.
        self._feed_knob_seq = 0  # guarded-by: self._ingest_lock
        # -- cluster observability plane (obs.cluster; docs/OBSERVABILITY.md)
        # Liveness surfaced in the registry: per-executor heartbeat age
        # as a render-time collector (PR 4's plane was invisible to
        # /metrics), and a counter that ticks once per node DEATH
        # transition (dead_nodes()).
        reg = default_registry()
        self._m_dead = reg.counter(
            "cluster_dead_nodes_total",
            "nodes declared dead by the liveness plane (transitions)",
        )
        self._counted_dead: set[int] = set()  # guarded-by: self._dead_lock
        self._dead_lock = threading.Lock()
        hb_gauge = reg.gauge(
            "node_heartbeat_age_seconds",
            "seconds since each executor's last heartbeat, by node",
        )

        def _liveness_collector(
            _g=hb_gauge, _res=server.reservations
        ) -> None:
            for eid, age in _res.last_seen().items():
                _g.set(age, node=str(eid))

        self._liveness_collector = _liveness_collector
        reg.add_collector(_liveness_collector)
        # Driver-side aggregation: scrape every node's /metrics on the
        # liveness cadence, merge, and re-serve at a driver /metrics
        # endpoint (every sample labelled node="<eid>"; the driver's
        # own registry under node="driver").
        self.aggregator: obs_cluster.MetricsAggregator | None = None
        self._driver_metrics_server = None
        self._driver_metrics_port: int | None = None
        if cluster_meta.get("metrics", True) and self.metrics_urls():
            self.aggregator = obs_cluster.MetricsAggregator(
                self.metrics_urls,
                interval=max(self.heartbeat_interval, 1.0)
                if self.heartbeat_interval > 0
                else 2.0,
            )
            self.aggregator.start()
            (
                self._driver_metrics_server,
                self._driver_metrics_port,
            ) = obs_cluster.serve_text(self.aggregator.render)

    # ------------------------------------------------------------------
    # liveness plane
    def dead_nodes(self, grace: float | None = None) -> list[int]:
        """Executor ids whose heartbeats have been silent longer than
        the grace window ([] when heartbeats are disabled). This is the
        fast failure detector: a SIGKILLed or wedged node shows up here
        within ``heartbeat_grace`` seconds instead of only at a feed or
        shutdown timeout."""
        if self.heartbeat_interval <= 0 or self._shutdown_done:
            return []
        grace = self.heartbeat_grace if grace is None else grace
        if grace <= 0:
            return []
        silent = self.server.reservations.dead_nodes(grace)
        if not silent:
            return []
        # A node that FINISHED and exited 0 stops heartbeating too —
        # silence plus a clean exit is completion, not death (supervise
        # and shutdown would otherwise tear down healthy runs with
        # skewed finish times).
        exit_codes = self.launcher.exitcodes()
        dead = [
            eid
            for eid in silent
            if not (eid < len(exit_codes) and exit_codes[eid] == 0)
        ]
        self._note_dead(dead)
        return dead

    def _note_dead(self, dead: list[int]) -> None:
        """Once per death TRANSITION (not per poll): tick the
        cluster_dead_nodes_total counter and drop a driver-side flight
        record — the postmortem's first artifact, written the moment
        the liveness plane passes judgment."""
        if not dead:
            return
        with self._dead_lock:
            new = [eid for eid in dead if eid not in self._counted_dead]
            self._counted_dead.update(new)
        if new:
            self._m_dead.inc(len(new))
            for eid in new:
                flightrec.note("dead_node", executor_id=eid)
            flightrec.dump_now("dead_node")

    def _dead_error(self, dead: list[int], detail: str = "") -> RuntimeError:
        """THE presumed-dead diagnostic — one builder so every surface
        (liveness check, stream polls) reports identically."""
        return RuntimeError(
            f"node(s) {dead} missed heartbeats for more than "
            f"{self.heartbeat_grace}s — presumed dead{detail}"
        )

    def _check_liveness(self) -> None:
        """Raise if any node is presumed dead; prefer its ferried
        traceback (or process exit) over the bare liveness message when
        one exists."""
        dead = self.dead_nodes()
        if not dead:
            return
        self._check_errors()  # a real traceback beats "missed heartbeats"
        failed = self.launcher.poll_failed()
        detail = f" (process(es) {failed} exited nonzero)" if failed else ""
        raise self._dead_error(dead, detail)

    # ------------------------------------------------------------------
    @property
    def workers(self) -> list[dict[str, Any]]:
        """Data-plane nodes (everything except evaluators), roster order."""
        return sorted(
            (n for n in self.cluster_info if n["job_name"] != "evaluator"),
            key=lambda n: n["executor_id"],
        )

    def tensorboard_url(self) -> str | None:
        """Reference: ``TFCluster.tensorboard_url``."""
        for n in self.cluster_info:
            if n.get("tb_port"):
                return f"http://{n['host']}:{n['tb_port']}"
        return None

    def profiler_urls(self) -> dict[int, str]:
        """Per-node ``jax.profiler`` trace-server addresses, by executor id.

        Populated when the cluster was started with ``profiler=True``
        (SURVEY.md §5.1: the coordinator knows every host's profiler URL —
        point TensorBoard's profile capture, or ``jax.profiler.trace``, at
        any of these).
        """
        return {
            n["executor_id"]: f"{n['host']}:{n['prof_port']}"
            for n in self.cluster_info
            if n.get("prof_port")
        }

    def metrics_urls(self) -> dict[int, str]:
        """Per-node Prometheus ``/metrics`` endpoints, by executor id —
        each node runtime serves its process-global obs registry
        (``tensorflowonspark_tpu.obs``); point a scraper at all of
        them, or curl one mid-run."""
        return {
            n["executor_id"]: (
                f"http://{n['host']}:{n['metrics_port']}/metrics"
            )
            for n in self.cluster_info
            if n.get("metrics_port")
        }

    def cluster_stats(self, fresh: bool = True) -> dict[str, Any]:
        """Typed cluster-level series scraped from every node's
        ``/metrics`` plus the driver's own registry: ``{"nodes":
        {key: health}, "series": {name: {"type", "per_node", "sum",
        "max"}}}`` (obs.cluster.MetricsAggregator.cluster_stats).
        ``fresh=False`` reuses the background loop's last round
        instead of scraping now. ``{}`` when metrics are disabled."""
        if self.aggregator is None:
            return {}
        return self.aggregator.cluster_stats(fresh=fresh)

    def driver_metrics_url(self) -> str | None:
        """The driver's aggregated ``/metrics`` endpoint (every node's
        samples re-labelled ``node="<eid>"``), or None when metrics
        are disabled — point ONE scraper here instead of N."""
        if self._driver_metrics_port is None:
            return None
        return f"http://127.0.0.1:{self._driver_metrics_port}/metrics"

    # ------------------------------------------------------------------
    def train(
        self,
        data: Iterable,
        num_epochs: int = 1,
        feed_timeout: float = 600.0,
        qname: str = "input",
        close_feed: bool = False,
    ) -> None:
        """Feed data partitions to the workers (InputMode.SPARK only).

        ``data`` is either an iterable of partitions (each an iterable of
        records) or a flat iterable of records (auto-partitioned). Partitions
        go round-robin to workers; each worker's partitions are fed
        sequentially by a dedicated thread (the moral equivalent of Spark's
        waves of ``foreachPartition`` feed tasks, reference ``TFCluster.train``
        → ``TFSparkNode._train``).

        A :class:`~tensorflowonspark_tpu.streaming.DStream` is also
        accepted (reference: ``TFCluster.train`` with a DStream →
        ``foreachRDD`` feeding): the call registers the feed bridge and
        returns immediately; micro-batches flow once the stream's
        ``StreamingContext.start()`` runs. End with
        ``shutdown(ssc=ssc)``.

        ``close_feed=True`` pushes EndOfFeed after the last partition, so
        worker loops see a clean end-of-stream without waiting for
        ``shutdown()``. Required for multi-controller workers consuming
        via ``DataFeed.synchronized_batch_stream`` (feeds must end for
        the cross-process exhaustion agreement to fire); no further
        ``train()`` calls are allowed on ``qname`` afterwards.
        """
        from tensorflowonspark_tpu.streaming import DStream

        if isinstance(data, DStream):
            if num_epochs != 1:
                raise ValueError(
                    "num_epochs does not apply to a DStream (each "
                    "micro-batch is fed once, on arrival)"
                )
            self._train_dstream(data, feed_timeout, qname)
            return
        self._require_spark_mode("train")
        workers = self.workers
        partitions = _as_partitions(data, len(workers))
        assignments: list[list[Any]] = [[] for _ in workers]
        n_parts = 0
        for epoch in range(num_epochs):
            for i, part in enumerate(partitions):
                assignments[(n_parts) % len(workers)].append(part)
                n_parts += 1
        self._check_errors()
        errors: list[BaseException] = []

        def feed_worker(widx: int) -> None:
            try:
                mgr = tfnode_runtime.connect_manager(workers[widx])
                # publish the feed policy to the node: DataFeed pull
                # loops bound their queue waits by the same timeout the
                # driver feeds under (see DataFeed._next_raw/FeedTimeout)
                mgr.set(
                    wire.FEED_TIMEOUT_KEY,
                    wire.encode("kv.feed_timeout", value=float(feed_timeout)),
                )
                for part in assignments[widx]:
                    tfnode_runtime.feed_partition(
                        mgr,
                        part,
                        feed_timeout=feed_timeout,
                        qname=qname,
                        node=workers[widx],
                        columnar=self.columnar,
                    )
                if close_feed:
                    tfnode_runtime.close_feed(
                        workers[widx], qname=qname, timeout=feed_timeout
                    )
            except BaseException as e:  # noqa: BLE001 - ferried to caller
                errors.append(e)

        threads = [
            threading.Thread(target=feed_worker, args=(i,), daemon=True)
            for i in range(len(workers))
        ]
        for t in threads:
            t.start()
        self._join_feeders(threads)
        if errors:
            self._check_errors()
            raise errors[0]
        self._check_errors()

    def _join_feeders(
        self, threads: list[threading.Thread], poll: float = 2.0
    ) -> None:
        """Join feeder threads while watching node liveness: a feeder
        blocked pushing to a SIGKILLed node would otherwise sit out the
        whole ``feed_timeout`` before anyone noticed the death. On a
        liveness failure the (daemon) feeders are abandoned and the
        error raises within the heartbeat grace."""
        last_check = time.monotonic()
        while True:
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                return
            alive[0].join(min(1.0, poll))
            if time.monotonic() - last_check >= poll:
                self._check_liveness()
                last_check = time.monotonic()

    def _train_dstream(self, dstream, feed_timeout: float, qname: str) -> None:
        """Bridge a DStream into :meth:`train_stream`: ``foreachRDD``
        pushes micro-batches into a bounded queue; a background thread
        drains it through the normal streaming feed path. Non-blocking —
        mirrors the reference, where ``train(DStream)`` just registered
        the ``foreachRDD`` and Spark Streaming drove the feeding."""
        self._require_spark_mode("train")
        if getattr(self, "_dstream_bridge", None) is not None:
            raise RuntimeError("a DStream is already being trained on")
        bridge: _stdqueue.Queue = _stdqueue.Queue(maxsize=2)
        end = object()
        errors: list[BaseException] = []
        stop_evt = threading.Event()

        def micro_batches():
            while True:
                item = bridge.get()
                if item is end:
                    return
                yield item

        def run() -> None:
            try:
                self.train_stream(
                    micro_batches(), feed_timeout=feed_timeout, qname=qname
                )
            except BaseException as e:  # noqa: BLE001 - ferried to shutdown
                errors.append(e)

        thread = threading.Thread(
            target=run, name="dstream-feed", daemon=True
        )

        def bridge_put(rdd) -> None:
            # Never block the scheduler forever: if the feed thread died
            # (worker early-stop, feeder error) or shutdown started, drop
            # the micro-batch instead of wedging the tick loop — the
            # reference's foreachRDD feed task failed/no-opped the same
            # way once the TF side stopped consuming.
            while not stop_evt.is_set() and thread.is_alive():
                try:
                    bridge.put(rdd, timeout=0.2)
                    return
                except _stdqueue.Full:
                    continue

        dstream.foreachRDD(bridge_put)
        thread.start()
        self._dstream_bridge = (bridge, end, thread, errors, stop_evt)

    def _drain_dstream(self) -> None:
        bridge, end, thread, errors, stop_evt = self._dstream_bridge
        self._dstream_bridge = None
        stop_evt.set()  # scheduler callbacks stop feeding / unblock
        while thread.is_alive():
            try:
                bridge.put(end, timeout=0.2)
                break
            except _stdqueue.Full:
                # Feed thread stopped consuming (early stop) — make room
                # by dropping pending micro-batches; shutdown means stop.
                try:
                    bridge.get_nowait()
                except _stdqueue.Empty:
                    pass
        thread.join()
        if errors:
            raise errors[0]

    def train_stream(
        self,
        stream: Iterable[Iterable],
        feed_timeout: float = 600.0,
        qname: str = "input",
    ) -> None:
        """Feed an unbounded stream of micro-batches (Spark Streaming parity).

        Reference: ``TFCluster.train`` with a DStream — each RDD of the
        stream is fed on arrival via ``foreachRDD`` (``TFCluster.py:train``).
        Here ``stream`` yields micro-batches; each micro-batch is
        partitioned like :meth:`train` and its partitions are handed
        round-robin to persistent per-worker feeder threads, so feeding
        micro-batch *k+1* overlaps with workers still consuming *k*.

        Returns when the stream is exhausted or every worker has entered
        the ``terminating`` state (early stop). The stream may be infinite;
        call :meth:`shutdown` from another thread (or let the workers call
        ``DataFeed.terminate``) to end training. The stream generator runs
        in a pump thread, so worker termination and feeder errors are
        noticed within ~5 s even while the source is quiet between
        micro-batches (a slow generator itself cannot be interrupted
        mid-``next()``, only abandoned).
        """
        self._require_spark_mode("train_stream")
        workers = self.workers
        errors: list[BaseException] = []
        work_qs: list[Any] = []
        feeders: list[threading.Thread] = []
        terminated = [False] * len(workers)
        pump_done = threading.Event()
        pump_stop = threading.Event()
        # Bounded so an unbounded stream can't buffer itself into the
        # driver's memory.
        micro_q: _stdqueue.Queue = _stdqueue.Queue(maxsize=2)

        def pump() -> None:
            try:
                for micro_batch in stream:
                    while not pump_stop.is_set():
                        try:
                            micro_q.put(micro_batch, timeout=1.0)
                            break
                        except _stdqueue.Full:
                            continue
                    if pump_stop.is_set():
                        return
            except BaseException as e:  # noqa: BLE001 - ferried to caller
                errors.append(e)
            finally:
                pump_done.set()

        def feed_worker(widx: int) -> None:
            # NOTE: deliberately no feed_timeout KV publish here (unlike
            # train): a stream is allowed to be quiet for arbitrary
            # stretches, so the consumer pull must stay unbounded.
            try:
                mgr = tfnode_runtime.connect_manager(workers[widx])
                while True:
                    part = work_qs[widx].get()
                    if part is None:
                        return
                    fed = tfnode_runtime.feed_partition(
                        mgr,
                        part,
                        feed_timeout=feed_timeout,
                        qname=qname,
                        node=workers[widx],
                        columnar=self.columnar,
                    )
                    if fed is None:  # node terminating; partition skipped
                        terminated[widx] = True
            except BaseException as e:  # noqa: BLE001 - ferried to caller
                errors.append(e)
                terminated[widx] = True

        for i in range(len(workers)):
            # 4 pending partitions per worker keeps the pipeline full
            # across micro-batch boundaries.
            work_qs.append(_stdqueue.Queue(maxsize=4))
            t = threading.Thread(target=feed_worker, args=(i,), daemon=True)
            feeders.append(t)
            t.start()
        threading.Thread(target=pump, daemon=True, name="stream-pump").start()

        def poll_node_states() -> None:
            # Worker-initiated termination (DataFeed.terminate) only flips
            # terminated[i] when a feed attempt observes it; on a quiet
            # stream no feed happens, so poll manager state directly.
            # Liveness first: a SIGKILLed node's manager port may refuse
            # (indistinguishable from clean termination below), but its
            # missed heartbeats are an unambiguous death signal that must
            # RAISE, not silently early-stop the stream.
            dead = set(self.dead_nodes())
            for i, w in enumerate(workers):
                if not terminated[i] and w["executor_id"] in dead:
                    errors.append(self._dead_error([w["executor_id"]]))
                    terminated[i] = True
            for i, w in enumerate(workers):
                if not terminated[i]:
                    try:
                        mgr = tfnode_runtime.connect_manager(w)
                        # 'finished' too: a map_fun that terminate()s and
                        # returns flips terminating -> finished immediately.
                        state = tfnode_runtime.fetch_node_state(mgr)
                        if state in ("terminating", "finished", "error"):
                            terminated[i] = True
                    except (ConnectionError, OSError, EOFError):
                        terminated[i] = True

        n_parts = 0
        last_err_check = time.monotonic()
        try:
            while not (all(terminated) or errors):
                # Node-side failures and worker-initiated termination
                # surface through the managers, not the feeder threads —
                # poll them, but at most every 5 s (each poll opens a
                # connection to every node).
                if time.monotonic() - last_err_check > 5.0:
                    self._check_errors()
                    poll_node_states()
                    last_err_check = time.monotonic()
                try:
                    micro_batch = micro_q.get(timeout=1.0)
                except _stdqueue.Empty:
                    if pump_done.is_set() and micro_q.empty():
                        break
                    continue
                for part in _as_partitions(micro_batch, len(workers)):
                    if not part:
                        continue  # empty partition: nothing to feed
                    widx = n_parts % len(workers)
                    n_parts += 1
                    while not terminated[widx] and not errors:
                        try:
                            work_qs[widx].put(part, timeout=1.0)
                            break
                        except _stdqueue.Full:
                            continue
        finally:
            pump_stop.set()
            for q, t in zip(work_qs, feeders):
                # A dead feeder no longer drains its (bounded) queue, so an
                # unconditional put could block forever — poll instead.
                # After an error (including a liveness failure) the
                # poison-put and join are BOUNDED: a feeder blocked
                # mid-push to a wedged node would otherwise hang this
                # cleanup forever, exactly the wait the liveness plane
                # exists to cut short (the feeders are daemons).
                give_up = (
                    time.monotonic() + 2.0 if errors else float("inf")
                )
                while t.is_alive() and time.monotonic() < give_up:
                    try:
                        q.put(None, timeout=1.0)
                        break
                    except _stdqueue.Full:
                        continue
            for t in feeders:
                t.join(2.0 if errors else None)
        if errors:
            self._check_errors()
            raise errors[0]
        self._check_errors()

    def inference(
        self,
        data: Iterable,
        feed_timeout: float = 600.0,
        qname: str = "input",
    ) -> list[Any]:
        """Feed partitions and gather results, preserving input order.

        Reference: ``TFCluster.inference`` → ``TFSparkNode._inference``.
        Equal-count contract: the user fn must emit exactly one result per
        input record via ``DataFeed.batch_results``.
        """
        # mode check BEFORE draining data: misuse on a TENSORFLOW-mode
        # cluster must raise promptly, not block on an unbounded iterable
        self._require_spark_mode("inference")
        # contiguous: partition-order reassembly then preserves flat
        # input order end-to-end
        partitions = _as_partitions(data, len(self.workers), contiguous=True)
        return list(
            self.inference_stream(
                partitions, feed_timeout=feed_timeout, qname=qname
            )
        )

    def inference_stream(
        self,
        partitions: Iterable,
        feed_timeout: float = 600.0,
        qname: str = "input",
    ):
        """Streaming :meth:`inference`: pull record-list partitions lazily
        from an iterable and yield results in partition order as they
        complete.

        Memory contract (the scale fix the reference got from
        ``mapPartitions``, SURVEY §3.4): the input is never materialized
        — workers stay at most ``2 × num_workers`` partitions ahead of
        the consumer (in-flight work plus reorder slack), so a slow
        consumer throttles the pulls instead of the whole source
        buffering in the reorder dict. Closing the generator early
        (``break`` / ``.close()``) stops further pulls; it waits only
        for each worker's current in-flight partition, not the rest of
        the source. Unlike :meth:`inference`, ``partitions`` is taken
        as-is (every element IS one record-list partition); no
        flat-input convention detection, which would need the whole
        input up front.
        """
        self._require_spark_mode("inference")
        workers = self.workers
        source = enumerate(iter(partitions))
        results: dict[int, list[Any]] = {}
        errors: list[BaseException] = []
        finished = [0]
        # head = next partition index to deliver; taken = indices handed
        # to workers; stop = consumer gone, pull no more
        state = {"head": 0, "taken": 0, "stop": False}
        max_ahead = 2 * len(workers)
        cond = threading.Condition()

        def next_partition():
            with cond:  # cond's lock doubles as the source lock
                while (
                    not state["stop"]
                    and not errors
                    and state["taken"] - state["head"] >= max_ahead
                ):
                    cond.wait(1.0)  # backpressure: consumer is behind
                if state["stop"] or errors:
                    return None
                item = next(source, None)
                if item is not None:
                    state["taken"] = item[0] + 1
                return item

        def run_worker(widx: int) -> None:
            # no feed_timeout KV publish: inference_stream throttles
            # workers when the RESULT consumer lags, so the node's input
            # queue legitimately goes quiet for as long as the consumer
            # pleases — a consumer-side pull bound would misread that
            # backpressure as producer death.
            try:
                mgr = tfnode_runtime.connect_manager(workers[widx])
                while True:
                    item = next_partition()
                    if item is None:
                        return
                    pidx, part = item
                    part = list(part)
                    fed = tfnode_runtime.feed_partition(
                        mgr,
                        part,
                        feed_timeout=feed_timeout,
                        qname=qname,
                        node=workers[widx],
                        columnar=self.columnar,
                    )
                    if fed is None:  # node terminating; partition skipped
                        with cond:
                            results[pidx] = []
                            cond.notify_all()
                        continue
                    out = tfnode_runtime.collect_results(
                        mgr, fed, timeout=feed_timeout
                    )
                    with cond:
                        results[pidx] = out
                        cond.notify_all()
            except BaseException as e:  # noqa: BLE001
                with cond:
                    errors.append(e)
                    cond.notify_all()
            finally:
                with cond:
                    finished[0] += 1
                    cond.notify_all()

        threads = [
            threading.Thread(target=run_worker, args=(i,), daemon=True)
            for i in range(len(workers))
        ]
        for t in threads:
            t.start()
        try:
            while True:
                with cond:
                    head = state["head"]
                    while (
                        head not in results
                        and not errors
                        and finished[0] < len(threads)
                    ):
                        cond.wait(1.0)
                        dead = self.dead_nodes()
                        if dead:
                            errors.append(self._dead_error(dead))
                    if errors:
                        break
                    if head in results:
                        out = results.pop(head)
                        state["head"] = head + 1
                        cond.notify_all()  # frees throttled workers
                    else:  # finished[0] >= len(threads): source drained
                        break
                # yield OUTSIDE the lock: a slow consumer must not stall
                # workers posting results
                yield from out
        finally:
            # normal exhaustion, an error, or the consumer closing the
            # generator early: stop further pulls, then wait out only
            # the in-flight partitions
            with cond:
                state["stop"] = True
                cond.notify_all()
            for t in threads:
                # After an error (including a liveness failure) the
                # (daemon) workers may be mid-push to a dead node —
                # abandon them instead of riding out feed_timeout.
                t.join(2.0 if errors else None)
        if errors:
            self._check_errors()
            raise errors[0]
        self._check_errors()

    # ------------------------------------------------------------------
    # pull plane (driverless sharded ingestion — feed/ingest.py)
    def assign_shards(
        self,
        manifests: Iterable[Any],
        *,
        seed: int | None = None,
        epoch: int = 0,
        split: int = 1,
    ) -> None:
        """Plan and publish the pull plane's shard assignment
        (``InputMode.TENSORFLOW`` only): ``manifests`` (typically
        :class:`~tensorflowonspark_tpu.feed.manifest.FileManifest`
        records — a path and a format, O(files) driver bytes) are
        round-robin split across the workers
        (``feed.manifest.plan_manifests``) and each worker's shard is
        published to its manager KV. Nodes consume via
        ``ctx.get_ingest_feed()`` — the driver never touches the data
        again. Use ``feed.manifest.split_manifest`` first when one
        large file must feed many nodes.

        With the handover protocol armed (``elastic=True`` +
        ``ingest_handover``, the default), the plan FOLLOWS membership:
        every reconfigure re-splits the *remaining* records over the
        survivors from the consumers' published replay cursors
        (:meth:`_redistribute_ingest_plan`) — no shard is ever left
        unread by a permanent shrink, and a joiner picks up real work.

        With handover off (``ingest_handover=False``, or a non-elastic
        cluster), assignment is computed ONCE and is then **stable per
        executor id**: an elastic reconfigure re-publishes each active
        executor's ORIGINAL shard — a replacement for executor *k*
        (``launch_replacement`` reuses the id) fetches *k*'s shard and
        seeds its predecessor's persisted replay cursor
        (``IngestFeed.seed_cursor``). A shard whose executor id has no
        active owner is then logged loudly as UNREAD (and counted in
        the ``ingest_unread_shards`` gauge) — the recorded limitation
        the handover protocol exists to remove.

        ``seed``/``epoch``/``split`` thread the per-epoch seeded
        shuffle (``feed.manifest.plan_manifests``): the SAME
        (seed, epoch) pair always re-derives the same plan — cursor-
        exact resume composes with ``reshuffle_each_iteration`` — and
        each epoch's manifests carry epoch-folded stream ids, so one
        ``assign_shards(..., seed=s, epoch=e)`` + drain cycle per
        epoch gives pull-mode training a fresh deterministic
        permutation per pass.
        """
        if self.input_mode != InputMode.TENSORFLOW:
            raise RuntimeError(
                "assign_shards() requires InputMode.TENSORFLOW — in "
                "InputMode.SPARK the driver pushes records itself "
                "(use train(), or ManifestFeed for node-local reads)"
            )
        from tensorflowonspark_tpu.feed.manifest import plan_manifests

        workers = self.workers
        shards = plan_manifests(
            list(manifests), len(workers), seed=seed, epoch=epoch,
            split=split,
        )
        with self._ingest_lock:
            self._ingest_shards = {
                w["executor_id"]: shard for w, shard in zip(workers, shards)
            }
            # a fresh assignment is a fresh dataset: a completion
            # latched by the PREVIOUS dataset must neither suppress
            # this one's completion nor prematurely release its
            # consumers at the next reconfigure
            self._ingest_complete = False
            self._ingest_republished = False
            # a fresh dataset is also a fresh plan generation (never a
            # reset: the seq must stay monotonic per membership epoch
            # so consumers can order publications)
            self._ingest_seq += 1
        failed = self._publish_ingest_plan()
        if failed:
            # At ASSIGN time a publish failure is the caller's problem
            # (the pre-handover behavior): without a plan, consumers
            # block the full fetch timeout blaming a missing
            # assign_shards call. Reconfigure-time republishes stay
            # best-effort (the next bump retries).
            raise RuntimeError(
                f"ingest: plan publish failed for node(s) {failed} — "
                "no consumer on those nodes will receive a shard"
            )

    def extend_shards(self, manifests: Iterable[Any]) -> None:
        """APPEND manifests to the RUNNING plan (the growing-dataset
        wire — docs/ROBUSTNESS.md "Online continual loop"): the new
        manifests are dealt round-robin across the current workers,
        each worker's cumulative shard is republished under the SAME
        membership epoch with a bumped plan generation (``seq``), and
        a lingering consumer (exhaustion-linger) adopts exactly the
        appended streams instead of completing. Active consumers are
        never interrupted — they discover the growth at their own
        exhaustion. Requires the handover protocol (``elastic=True`` +
        ``ingest_handover``): without the linger there is no consumer-
        side hook to hand appended work to."""
        if self.input_mode != InputMode.TENSORFLOW:
            raise RuntimeError(
                "extend_shards() requires InputMode.TENSORFLOW"
            )
        if not self._handover_armed:
            raise RuntimeError(
                "extend_shards() requires the handover protocol "
                "(elastic=True + ingest_handover) — a static plan has "
                "no lingering consumers to adopt appended shards"
            )
        new = list(manifests)
        if not new:
            return
        # Serialize the whole append against a concurrent epoch
        # re-split: interleaving their read-modify-write cycles could
        # publish a plan missing either the appended shards or the
        # re-split (both are zero-gap violations).
        with self._ingest_replan_lock:
            workers = self.workers
            if not workers:
                logger.warning(
                    "ingest: no live workers to extend the plan to — "
                    "appended manifests deferred to the next call"
                )
                return
            from tensorflowonspark_tpu.feed.manifest import plan_manifests

            shards = plan_manifests(new, len(workers))
            with self._ingest_lock:
                if self._ingest_shards is None:
                    self._ingest_shards = {}
                base = self._ingest_shards
                for w, shard in zip(workers, shards):
                    eid = w["executor_id"]
                    base[eid] = list(base.get(eid, ())) + list(shard)
                self._ingest_seq += 1
                seq = self._ingest_seq
                # appended work un-latches a completed dataset: the
                # grown plan must complete on ITS OWN finals
                self._ingest_complete = False
            logger.info(
                "ingest: extended plan with %d manifest(s) over %d "
                "worker(s) (seq %d)",
                len(new),
                len(workers),
                seq,
            )
            self._publish_ingest_plan()

    def hold_ingest_completion(self, hold: bool = True) -> None:
        """Suppress (or release) the supervise loop's auto-completion
        of the ingest plan: an online loop's dataset is never "as
        consumed as it will ever be" while traffic still flows, so
        all-finals must not release the lingering consumers between
        growth cycles. :meth:`shutdown` force-releases regardless."""
        with self._ingest_lock:
            self._ingest_hold_completion = bool(hold)

    def run_online(self, log_root: str, **kw: Any) -> Any:
        """Start the continual-training loop over a live traffic log
        (``tfos.online``): holds ingest completion open, then polls
        ``log_root`` for sealed traffic-log manifests and appends them
        to the running plan via :meth:`extend_shards` on a daemon
        thread. Keyword arguments pass through to
        :class:`tensorflowonspark_tpu.online.OnlineLoop` (notably
        ``channel_dir=`` — the rollout channel whose published
        ``weights_version`` is the trainer-progress signal for stall
        detection). Returns the started loop; call ``.stop()`` to end
        it (releasing the hold so the run can drain), or let
        :meth:`shutdown` force-release. Run :meth:`supervise` alongside
        — growth publication rides the same plan machinery elastic
        reshards use."""
        if not self._handover_armed:
            raise RuntimeError(
                "run_online() requires the handover protocol "
                "(elastic=True + ingest_handover)"
            )
        from tensorflowonspark_tpu.online import OnlineLoop

        return OnlineLoop(self, log_root, **kw).start()

    @property
    def _handover_armed(self) -> bool:
        return self.elastic and self.ingest_handover

    def _publish_ingest_plan(self, complete: bool = False) -> list[int]:
        """Publish the current plan to every live worker's manager KV;
        returns the executor ids whose publish failed after retries
        (callers decide whether that is fatal — assign time — or
        best-effort — reconfigure time)."""
        workers = self.workers
        epoch = self.membership_epoch()
        with self._ingest_lock:
            shards = {
                k: list(v) for k, v in (self._ingest_shards or {}).items()
            }
            republish = self._ingest_republished
            self._ingest_republished = True
            seq = self._ingest_seq
        # Never RPC a node the liveness plane declared dead: a wedged
        # process's kernel still accepts the connect and hangs the
        # handshake (same rule as shutdown/_check_errors).
        dead = set(self.dead_nodes())
        failed: list[int] = []
        from tensorflowonspark_tpu.utils.retry import RetryPolicy

        # A re-split plan is load-bearing: the consumer is blocked in
        # plan_fetch(min_epoch) and a lost publish escalates to a node
        # TimeoutError after adopt_timeout — so transient RPC blips are
        # retried here (short, bounded) rather than merely logged.
        policy = RetryPolicy(
            max_attempts=3, base_delay=0.1, max_delay=0.5, deadline_s=5.0
        )
        for w in workers:
            eid = w["executor_id"]
            if eid in dead:
                continue
            try:
                policy.call(
                    lambda w=w, eid=eid: tfnode_runtime.publish_ingest_plan(
                        tfnode_runtime.connect_manager(w),
                        shards.get(eid, []),
                        epoch=epoch,
                        shard_index=eid,
                        num_shards=len(shards),
                        plan_id=self.cluster_meta.get("id"),
                        handover=self._handover_armed,
                        complete=complete,
                        seq=seq,
                    ),
                    retry_on=(ConnectionError, OSError, EOFError),
                    site="ingest.plan_publish",
                )
            except (ConnectionError, OSError, EOFError) as e:
                failed.append(eid)
                logger.warning(
                    "ingest: plan publish to node %s failed (%s)", eid, e
                )
        unowned = sorted(
            set(shards) - {w["executor_id"] for w in workers}
        )
        if unowned:
            logger.warning(
                "ingest: shard(s) of departed executor(s) %s have no "
                "active owner — their manifests are UNREAD until a "
                "replacement with the same id rejoins",
                unowned,
            )
        reg = default_registry()
        # the log-only UNREAD warning, as a scrapeable signal (0 when
        # every shard has an owner — the gauge must CLEAR on recovery)
        reg.gauge(
            "ingest_unread_shards",
            "published shards with no active owner (manifests unread "
            "until a replacement rejoins); nonzero is data loss in "
            "progress",
        ).set(len(unowned))
        from tensorflowonspark_tpu.feed.ingest import metrics as _ing_metrics

        _ing_metrics()["plan_epoch"].set(epoch)
        flightrec.note(
            "ingest_plan_republish" if republish else "ingest_plan",
            epoch=epoch,
            seq=seq,
            shards={k: len(v) for k, v in shards.items()},
            unowned=unowned,
            complete=complete,
            publish_failed=failed,
        )
        if republish:
            # a republish is always part of an incident (membership
            # change / completion) — leave the postmortem artifact now
            flightrec.dump_now("ingest_plan_republish")
        logger.info(
            "ingest plan published: %d shard(s) over %d worker(s) "
            "(epoch %d%s)",
            len(shards),
            len(workers),
            epoch,
            ", complete" if complete else "",
        )
        return failed

    def publish_feed_knobs(self, **knobs: Any) -> list[int]:
        """Driver-side autotune actuation for NODE-side feed knobs
        (currently ``publish_blocks``): re-publish the tuned values to
        every live worker's manager KV under a fresh monotonically
        increasing seq. Each node's ``IngestFeed`` polls the key at
        block boundaries and adopts a publication exactly once — a
        controller revert is simply the next publication. Best-effort
        like the plan republish: returns the executor ids whose
        publish failed (the next publication covers them)."""
        if not knobs:
            raise ValueError("publish_feed_knobs: no knobs given")
        with self._ingest_lock:
            self._feed_knob_seq += 1
            seq = self._feed_knob_seq
        dead = set(self.dead_nodes())
        failed: list[int] = []
        for w in self.workers:
            eid = w["executor_id"]
            if eid in dead:
                continue
            try:
                tfnode_runtime.publish_feed_knobs(
                    tfnode_runtime.connect_manager(w), knobs, seq=seq
                )
            except (ConnectionError, OSError, EOFError) as e:
                failed.append(eid)
                logger.warning(
                    "feed knobs publish to node %s failed (%s) — the "
                    "next publication covers it",
                    eid,
                    e,
                )
        logger.info(
            "feed knobs published (seq %d): %s%s",
            seq,
            knobs,
            f"; failed for {failed}" if failed else "",
        )
        return failed

    def _await_handover_cursors(
        self, epoch: int, fresh_ids: "set[int] | frozenset" = frozenset()
    ) -> dict[int, dict]:
        """Bounded wait for every live, actively-consuming worker to
        drain and publish a cursor stamped >= ``epoch``. Dead nodes
        cannot publish (their last periodic cursor is the seed — the
        crash-handover duplicate bound), ``done`` consumers (final or
        terminated) will never publish again and their content is
        already exact, and a straggler past ``handover_timeout``
        degrades to its last cursor with a loud warning — duplicates
        bounded by the staleness, zero-gap untouched either way.
        ``fresh_ids`` are executor ids admitted by THIS reconfigure: a
        cursor retained under such an id belongs to a dead predecessor
        (the replacement is still blocked waiting for the very plan
        this wait precedes) — waiting on it would stall every
        crash→rejoin handover for the full timeout."""
        res = self.server.reservations
        active = {w["executor_id"] for w in self.workers}
        deadline = time.monotonic() + self.handover_timeout
        while True:
            cursors = res.cursors()
            waiting = sorted(
                eid
                for eid, p in cursors.items()
                if eid in active
                and eid not in fresh_ids
                and not p.get("final")
                and not p.get("done")
                and int(p.get("epoch", 0)) < epoch
            )
            if not waiting:
                return cursors
            if time.monotonic() >= deadline:
                logger.warning(
                    "ingest: handover drain timed out after %.1fs "
                    "waiting for node(s) %s — proceeding with their "
                    "last published cursors (duplicates bounded by the "
                    "staleness; zero-gap unaffected)",
                    self.handover_timeout,
                    waiting,
                )
                return cursors
            time.sleep(0.1)

    def _redistribute_ingest_plan(
        self, epoch: int, fresh_ids: "set[int] | frozenset" = frozenset()
    ) -> None:
        """The tentpole: make the ingest plan follow membership. Wait
        for the cooperative drain, merge every published cursor
        (departed nodes' last publications included), re-split the
        REMAINING records over the surviving workers, and publish the
        new plan keyed by the membership epoch. The whole episode runs
        under the replan lock so a concurrent growth append
        (:meth:`extend_shards`) cannot interleave with the re-split's
        read-modify-write."""
        with self._ingest_replan_lock:
            self._redistribute_ingest_plan_locked(epoch, fresh_ids)

    def _redistribute_ingest_plan_locked(
        self, epoch: int, fresh_ids: "set[int] | frozenset" = frozenset()
    ) -> None:  # lint: holds-lock
        from tensorflowonspark_tpu.feed.manifest import (
            merge_cursor_payloads,
            replan_manifests,
            stream_id,
        )

        cursors = self._await_handover_cursors(epoch, fresh_ids=fresh_ids)
        merged = merge_cursor_payloads(cursors.values())
        active = sorted(w["executor_id"] for w in self.workers)
        if not active:
            logger.warning(
                "ingest: no surviving workers to redistribute to"
            )
            return
        # A TERMINATED consumer (done, not final — deliberate early
        # stop) will never read again: assigning it work would leave
        # that work unread forever. Deal only to workers that still
        # consume; if none remain, fall back to all (the completion
        # check accepts terminated consumers, so nothing hangs).
        consuming = [
            eid
            for eid in active
            if not (
                (p := cursors.get(eid)) is not None
                and p.get("done")
                and not p.get("final")
            )
        ]
        if consuming:
            active = consuming
        with self._ingest_lock:
            old = self._ingest_shards or {}
        # A FINAL publication proves exactly one thing: the shard its
        # publisher CURRENTLY owns is exhausted. Consumers keep
        # consumed-state for streams from earlier plan generations
        # forever (the restart-seeding contract), so a final's cursor
        # may name streams now owned — and still mid-read — by someone
        # else; marking those final would drop their unconsumed
        # remainder (a zero-gap violation). Scope each node's finals
        # to the streams of ITS current shard.
        finals = {
            sid
            for eid, p in cursors.items()
            if p.get("final")
            for sid in (
                {stream_id(m) for m in old.get(eid, ())}
                & set(p.get("cursor") or {})
            )
        }
        # The re-split's header scans (scan_frames — the only point the
        # driver touches data files) run OUTSIDE _ingest_lock: slow or
        # flaky storage must never wedge shutdown()'s force-complete or
        # a concurrent assign behind this lock.
        try:
            new = replan_manifests(old, merged, active, final_streams=finals)
        except (OSError, ValueError) as e:
            # A transient storage blip here — plausibly correlated with
            # the very failure being handled — must degrade, not crash
            # supervise(): republish the CURRENT plan at the new epoch.
            # Consumers drain and re-adopt identical shards; their
            # reseeded cursors dedupe the re-read, so correctness holds
            # and only the redistribution is deferred.
            logger.warning(
                "ingest: re-split failed (%s); republishing the "
                "current plan unchanged at epoch %d",
                e,
                epoch,
            )
            new = old
        with self._ingest_lock:
            if (self._ingest_shards or {}) is not old:
                # a concurrent assign_shards superseded this plan while
                # we were re-planning; its fresh publish wins
                logger.warning(
                    "ingest: plan reassigned mid-redistribution; "
                    "dropping the stale re-split"
                )
                return
            moved = sum(
                1 for eid in new if new[eid] != old.get(eid, [])
            )
            self._ingest_shards = new
        default_registry().counter(
            "ingest_redistributed_shards_total",
            "node shards whose manifest set changed in a live "
            "redistribution",
        ).inc(moved)
        logger.warning(
            "ingest: redistributed remaining records over %d worker(s) "
            "at epoch %d (%d shard(s) changed)",
            len(active),
            epoch,
            moved,
        )
        self._publish_ingest_plan()

    def _maybe_complete_ingest(self) -> None:
        """Supervise-loop completion check: once every active worker's
        latest cursor is FINAL at the current epoch — or the worker
        TERMINATED (deliberate early stop; it will never consume again
        and must not gate the others) — the current plan is as consumed
        as it will ever be: publish the completion marker so lingering
        consumers (waiting to absorb more work) stop. Flag-based, not
        block-math-based: a final publication is the consumer's own
        exhaustion proof."""
        with self._ingest_lock:
            if (
                self._ingest_shards is None
                or self._ingest_complete
                # online mode: the dataset is still growing — never
                # auto-release the lingering consumers (shutdown
                # force-completes regardless)
                or self._ingest_hold_completion
            ):
                return
            seq = self._ingest_seq
        if not self._handover_armed:
            return
        epoch = self.membership_epoch()
        cursors = self.server.reservations.cursors()
        active = [w["executor_id"] for w in self.workers]
        if not active:
            return
        for eid in active:
            p = cursors.get(eid)
            if p is None:
                return
            if p.get("done") and not p.get("final"):
                continue  # terminated: never publishes again
            if not p.get("final") or int(p.get("epoch", 0)) < epoch:
                return
            if int(p.get("plan_seq") or 0) < seq:
                # a final published BEFORE the last append proves only
                # the pre-growth dataset was consumed — the grown plan
                # must earn its own finals
                return
        self._finish_ingest_plan()

    def _finish_ingest_plan(self) -> None:
        """Publish the completion marker (idempotent): lingering
        consumers see ``complete`` on their next plan poll and stop.
        Also forced by :meth:`shutdown` so a teardown without
        supervision can never leave consumers lingering."""
        with self._ingest_lock:
            if self._ingest_shards is None or self._ingest_complete:
                return
            self._ingest_complete = True
        armed = self._handover_armed
        if not armed:
            return
        logger.info("ingest: plan complete — releasing consumers")
        self._publish_ingest_plan(complete=True)

    # ------------------------------------------------------------------
    def membership_epoch(self) -> int:
        """The current membership epoch (0 = the startup roster; bumped
        once per reconfigure — see :meth:`supervise` elastic mode)."""
        return self.server.reservations.epoch()

    def launch_replacement(self, executor_id: int, map_fun, tf_args) -> None:
        """Spawn a replacement node process for a departed executor id
        (local-launcher path). The process registers with the running
        reservation server like any node; elastic :meth:`supervise`
        notices the pending registration and admits it with an epoch
        bump. The replacement's ``map_fun`` typically hydrates via
        ``ElasticTrainer.hydrate()`` before training."""
        if executor_id not in self._snapshot_departed():
            raise ValueError(
                f"executor {executor_id} has not departed; replacements "
                "are for elastically-removed members only"
            )
        launcher = LocalLauncher(env=self._node_env)
        launcher._replaces = executor_id
        launcher.launch(
            1,
            tfnode_runtime.run_node,
            lambda _i: (executor_id, map_fun, tf_args, self.cluster_meta),
        )
        self._replacement_launchers.append(launcher)

    def _snapshot_departed(self) -> set[int]:
        with self._dead_lock:
            return set(self._departed)

    def _reconfigure(
        self,
        departed: list[int],
        joined: list[dict[str, Any]],
    ) -> int:
        """Drive one membership change: remove the departed, admit the
        joiners, bump the epoch (published to every survivor via the
        next heartbeat reply), and leave the audit trail — flight
        record + ``cluster_membership_epoch`` gauge."""
        from tensorflowonspark_tpu.utils.failpoints import failpoint

        failpoint("elastic.epoch_bump")
        res = self.server.reservations
        for eid in departed:
            res.remove(eid)
        with self._dead_lock:
            self._departed.update(departed)
            for m in joined:
                # A readmitted executor id is a full member again: its
                # exit codes count, and a second death must re-count.
                self._departed.discard(m["executor_id"])
                self._counted_dead.discard(m["executor_id"])
        epoch = res.bump_epoch()
        self.cluster_info = res.active()
        reg = default_registry()
        reg.gauge(
            "cluster_membership_epoch",
            "current membership epoch (bumped on every reconfigure)",
        ).set(epoch)
        flightrec.note(
            "elastic_epoch_bump",
            epoch=epoch,
            departed=sorted(departed),
            joined=sorted(m["executor_id"] for m in joined),
            nodes=sorted(n["executor_id"] for n in self.cluster_info),
        )
        flightrec.dump_now("elastic_epoch_bump")
        logger.warning(
            "elastic: membership epoch %d — departed %s, joined %s, "
            "%d node(s) remain",
            epoch,
            sorted(departed),
            sorted(m["executor_id"] for m in joined),
            len(self.cluster_info),
        )
        # Make the ingest plan follow membership. Handover armed (the
        # default): REDISTRIBUTE — wait for the cooperative drain, then
        # re-split the remaining records over the survivors (zero
        # shards left unread by a permanent shrink). Handover off: the
        # PR-8 fallback — re-publish each active id's stable shard
        # (content never changes, so a mid-loop failure is harmless; a
        # replacement fetches its predecessor's shard + disk cursor).
        with self._ingest_lock:
            has_plan = self._ingest_shards is not None
            plan_done = self._ingest_complete
        if has_plan and plan_done:
            # A joiner admitted AFTER dataset completion must still
            # learn the dataset is done — its fresh manager KV has no
            # plan, and it would otherwise block in fetch_ingest_plan.
            self._publish_ingest_plan(complete=True)
        elif has_plan:
            if self._handover_armed:
                self._redistribute_ingest_plan(
                    epoch,
                    fresh_ids={m["executor_id"] for m in joined},
                )
            else:
                try:
                    self._publish_ingest_plan()
                except (ConnectionError, OSError, EOFError) as e:
                    logger.warning(
                        "elastic: ingest plan re-publish failed (%s); "
                        "a rejoining node must wait for the next "
                        "reconfigure to fetch its shard",
                        e,
                    )
        return epoch

    def _elastic_scan(self) -> bool:
        """One elastic supervision round: detect departures (process
        exits + liveness) and pending joins; reconfigure when membership
        moved. Returns True if a reconfigure happened. Raises when the
        surviving membership would fall below ``elastic_min_nodes`` —
        at that point restart (the PR-4 path) is the only recovery."""
        active_ids = {n["executor_id"] for n in self.cluster_info}
        exit_codes = self.launcher.exitcodes()
        departed = set()
        for eid in active_ids:
            if (
                eid < len(exit_codes)
                and exit_codes[eid] is not None
                and exit_codes[eid] != 0
                and not self._is_replacement(eid)
            ):
                departed.add(eid)
        departed.update(
            eid for eid in self.dead_nodes() if eid in active_ids
        )
        joined = self.server.reservations.pending_joins()
        if not departed and not joined:
            return False
        survivors = len(active_ids) - len(departed) + len(joined)
        if survivors < self.elastic_min_nodes:
            raise RuntimeError(
                f"elastic supervision: {sorted(departed)} departed, "
                f"leaving {survivors} node(s) — below elastic_min_nodes="
                f"{self.elastic_min_nodes}; restart is the only recovery"
            )
        self._note_dead(sorted(departed))
        self._reconfigure(sorted(departed), joined)
        return True

    def _is_replacement(self, executor_id: int) -> bool:
        """True when a replacement process owns this executor id (alive,
        or exited cleanly) — the primary launcher's dead exit code for
        that slot is then history, not a departure/pending signal. Only
        the LATEST replacement for the id counts: its predecessors'
        fates are already-handled membership history."""
        for launcher in reversed(self._replacement_launchers):
            if getattr(launcher, "_replaces", None) != executor_id:
                continue
            # launch_replacement launches exactly one process per
            # launcher; alive or exited-0 means the id is owned.
            codes = launcher.exitcodes()
            return bool(codes) and (codes[0] is None or codes[0] == 0)
        return False

    def supervise(self, poll: float = 2.0) -> None:
        """Block until every node reaches a terminal state, failing FAST
        on a dead node — or, in **elastic** mode (``run(elastic=True)``),
        answering membership changes with a reconfigure instead of a
        failure.

        Non-elastic (the default): the watch loop ``run_with_restarts``
        runs between startup and teardown — it raises RuntimeError
        within ~``poll`` seconds of a node process exiting nonzero, and
        within ``heartbeat_grace`` of a node going silent (SIGKILL,
        kernel OOM, network partition — cases where the process table
        can't tell the driver anything). Without it, a dead node
        surfaced only when ``shutdown``'s watchdog expired.

        Elastic: a departed node (process exit or missed heartbeats) is
        REMOVED from membership and the epoch bumps; a pending mid-run
        registration (a replacement or voluntary joiner) is ADMITTED,
        bumping the epoch again. Survivors learn each bump within one
        heartbeat and reshard in place (``compute/elastic.py``).
        Raises only when membership would fall below
        ``elastic_min_nodes``. Returns once every ACTIVE node is
        ``finished``/``error`` (or exited cleanly), at which point
        :meth:`shutdown` completes promptly.
        """
        # Terminal states are cached: a node observed finished/error
        # never needs another manager RPC. Non-terminal nodes are
        # probed IN PARALLEL on a slower cadence than the (cheap)
        # process/liveness checks — one shared probe window per round,
        # so a single wedged node cannot serialize the loop, and far
        # fewer probe threads over a long run.
        terminal: dict[int, str] = {}
        state_poll = max(poll, 5.0)
        next_state_probe = 0.0
        while True:
            if self.elastic:
                if self._elastic_scan():
                    # Membership moved: stale terminal cache entries for
                    # readmitted ids must not mask a fresh process.
                    active = {n["executor_id"] for n in self.cluster_info}
                    terminal = {
                        k: v for k, v in terminal.items() if k in active
                    }
                # Handover consumers LINGER after exhausting their
                # shard (they may yet absorb a dead peer's remainder);
                # once every active consumer is final at the current
                # epoch, release them.
                self._maybe_complete_ingest()
            else:
                failed = self.launcher.poll_failed()
                if failed:
                    raise RuntimeError(
                        f"node process(es) {failed} died mid-run "
                        "(exited nonzero)"
                    )
                self._check_liveness()
            exit_codes = self.launcher.exitcodes()
            pending = [
                n
                for n in self.cluster_info
                if n["executor_id"] not in terminal
                and not (
                    n["executor_id"] < len(exit_codes)
                    and exit_codes[n["executor_id"]] == 0
                    and not self._is_replacement(n["executor_id"])
                )
            ]
            if not pending:
                return
            if time.monotonic() >= next_state_probe:
                next_state_probe = time.monotonic() + state_poll
                for n, state in zip(
                    pending, _probe_node_states(pending, timeout=10.0)
                ):
                    # "hung" (no answer in the window: a wedging node —
                    # liveness passes judgment next poll) and
                    # "unreachable" (manager gone but process not
                    # failed: about to exit cleanly or to miss
                    # heartbeats) both stay pending.
                    if state in ("finished", "error"):
                        terminal[n["executor_id"]] = state
            time.sleep(poll)

    # ------------------------------------------------------------------
    def shutdown(
        self,
        grace_secs: float = 0.0,
        timeout: float = 259200.0,
        ssc=None,
    ) -> None:
        """Graceful teardown with a force-kill watchdog.

        Reference: ``TFCluster.shutdown`` (await streaming termination if
        an ``ssc`` is given → grace sleep → terminal markers on every
        queue → join nodes → watchdog force-terminate → reservation
        STOP). Raises if any node ferried an exception or exited nonzero.
        """
        if self._shutdown_done:
            return
        stream_error: BaseException | None = None
        if ssc is not None:
            ssc.stop()
            try:
                ssc.awaitTermination(timeout=timeout)
            except BaseException as e:  # noqa: BLE001 - raised after teardown
                stream_error = e
        if self._dstream_bridge is not None:
            try:
                self._drain_dstream()
            except BaseException as e:  # noqa: BLE001 - raised after teardown
                stream_error = stream_error or e
        if grace_secs:
            time.sleep(grace_secs)

        # Dead (wedged) nodes are excluded from every manager RPC below:
        # their kernels may still accept the connect and then hang the
        # handshake; the launcher watchdog force-terminates them instead.
        dead = set(self.dead_nodes())
        if dead:
            logger.warning(
                "shutdown: skipping manager RPCs to dead node(s) %s",
                sorted(dead),
            )
        # A teardown must never leave handover consumers lingering for
        # more work: force the completion marker (idempotent; no-op
        # when supervise already published it or no plan exists). The
        # online hold is released first — run_online's growing dataset
        # ends HERE, by definition.
        with self._ingest_lock:
            self._ingest_hold_completion = False
        self._finish_ingest_plan()
        node_errors = self._collect_errors(skip=dead)
        feed_queues = (
            [q for q in self.queues if q not in ("output", "error", "control")]
            if self.input_mode == InputMode.SPARK
            else []
        )
        for node_meta in self.cluster_info:
            # Every node gets the control STOP; feed-queue end markers only
            # go where feeders did (evaluator sidecars have no feed).
            if node_meta["executor_id"] in dead:
                continue
            is_worker = node_meta["job_name"] != "evaluator"
            try:
                tfnode_runtime.shutdown_node(
                    node_meta, queues=feed_queues if is_worker else ()
                )
            except (ConnectionError, OSError, EOFError) as e:
                logger.warning(
                    "could not signal node %s: %s", node_meta["executor_id"], e
                )

        if not self.launcher.wait(timeout=timeout):
            logger.error("shutdown watchdog fired after %ss; terminating", timeout)
            self.launcher.terminate()
        # Replacement nodes got the same STOP as everyone else; a short
        # bounded wait here — the primary wait above already burned the
        # caller's budget.
        for launcher in self._replacement_launchers:
            if not launcher.wait(timeout=min(timeout, 60.0)):
                launcher.terminate()
        self.server.stop()
        self._shutdown_done = True
        # Detach the observability plane: the scrape loop and the
        # registry collector both reference this (now torn down)
        # cluster and would keep refreshing stale series forever.
        if self.aggregator is not None:
            self.aggregator.stop()
        if self._driver_metrics_server is not None:
            self._driver_metrics_server.shutdown()
            self._driver_metrics_server = None
        default_registry().remove_collector(self._liveness_collector)

        # Elastically-departed executors died by design (their nonzero
        # exits ARE the membership change); a replaced slot's primary
        # exit code is history too — judge the replacement's instead.
        departed = self._snapshot_departed()
        exitcodes = self.launcher.exitcodes()
        bad = [
            (i, c)
            for i, c in enumerate(exitcodes)
            if c is not None
            and c != 0
            and i not in departed
            and not self._is_replacement(i)
        ]
        # Only the LAST replacement per executor id is judged: an
        # earlier replacement that crashed triggered its own departure
        # + readmission cycle — that exit IS membership history, and
        # counting it would fail a fully recovered run.
        last_replacement: dict[Any, Any] = {}
        for launcher in self._replacement_launchers:
            last_replacement[getattr(launcher, "_replaces", None)] = launcher
        for eid, launcher in last_replacement.items():
            if eid in departed:
                continue  # the replacement itself departed later
            bad.extend(
                (eid, c)
                for c in launcher.exitcodes()
                if c is not None and c != 0
            )
        if node_errors:
            tracebacks = "\n".join(e["traceback"] for e in node_errors)
            raise RuntimeError(f"cluster node(s) failed:\n{tracebacks}")
        if bad:
            raise RuntimeError(f"node process(es) exited nonzero: {bad}")
        if stream_error is not None:
            raise stream_error

    # ------------------------------------------------------------------
    def _require_spark_mode(self, op: str) -> None:
        if self.input_mode != InputMode.SPARK:
            raise RuntimeError(
                f"cluster.{op}() requires InputMode.SPARK; in "
                "InputMode.TENSORFLOW nodes read data themselves"
            )

    def _collect_errors(
        self, skip: "set[int] | frozenset" = frozenset()
    ) -> list[dict[str, Any]]:
        errors: list[dict[str, Any]] = []
        for node_meta in self.cluster_info:
            if node_meta["executor_id"] in skip:
                continue
            try:
                errors.extend(tfnode_runtime.drain_errors(node_meta))
            except (ConnectionError, OSError, EOFError):
                pass  # node already gone; exitcode check will catch it
        return errors

    def _check_errors(self) -> None:
        # Never open a manager connection to a node the liveness plane
        # already declared dead: a WEDGED (e.g. SIGSTOPped) process's
        # kernel still accepts the TCP connect, and the authkey
        # handshake then blocks forever — the exact hang heartbeats
        # exist to cut short.
        errs = self._collect_errors(skip=set(self.dead_nodes()))
        if errs:
            tracebacks = "\n".join(e["traceback"] for e in errs)
            try:
                self.shutdown(timeout=60)
            except RuntimeError:
                pass
            raise RuntimeError(f"cluster node(s) failed:\n{tracebacks}")


def run(
    map_fun: Callable,
    tf_args: Any,
    num_executors: int,
    num_ps: int = 0,
    tensorboard: bool = False,
    profiler: bool = False,
    metrics: bool = True,
    input_mode: int = InputMode.SPARK,
    log_dir: str | None = None,
    master_node: str | None = None,
    reservation_timeout: float = 600.0,
    queues: Sequence[str] | None = None,
    eval_node: bool = False,
    launcher=None,
    default_fs: str = "",
    working_dir: str | None = None,
    distributed: bool = False,
    queue_maxsize: int = 1024,
    env: dict[str, str] | None = None,
    use_shm_ring: bool = True,
    shm_ring_mb: int = 64,
    heartbeat_interval: float = 2.0,
    heartbeat_grace: float = 60.0,
    columnar: bool = True,
    flightrec_dir: str | None = "logs",
    elastic: bool = False,
    elastic_min_nodes: int = 1,
    ingest_handover: bool = True,
    handover_timeout: float = 30.0,
) -> TFCluster:
    """Start a cluster and return its handle.

    Reference signature parity: ``TFCluster.run(sc, map_fun, tf_args,
    num_executors, num_ps, tensorboard, input_mode, log_dir, driver_ps_nodes,
    master_node, reservation_timeout, queues, eval_node, release_port)`` —
    minus ``sc`` (the launcher replaces Spark) and minus PS knobs.
    """
    if num_ps:
        raise ValueError(
            "num_ps > 0 is not supported on TPU: parameter servers are an "
            "asymmetric-role design that SPMD cannot express. Shard optimizer "
            "state over the mesh instead (FSDP): see "
            "tensorflowonspark_tpu.compute.train and SURVEY.md §2.3."
        )
    if num_executors < 1:
        raise ValueError("num_executors must be >= 1")
    if elastic:
        # Elastic reconfigure replays data from (epoch, step) — nodes
        # must own their readers. A push feed's consumed partitions
        # cannot be reassigned by the driver (same constraint as
        # run_with_restarts).
        if input_mode != InputMode.TENSORFLOW:
            raise ValueError(
                "elastic=True requires input_mode=InputMode.TENSORFLOW "
                "(push-fed partitions cannot be replayed on reconfigure)"
            )
        if heartbeat_interval <= 0:
            raise ValueError(
                "elastic=True requires heartbeats (heartbeat_interval "
                "> 0): membership changes are detected and published "
                "through the liveness plane"
            )

    # Role template (reference: TFCluster.py:run role map). All roles are
    # mesh-symmetric workers on TPU; 'chief' marks process 0 (checkpoint
    # writer, coordinator host), 'evaluator' an optional sidecar.
    n_train = num_executors - (1 if eval_node else 0)
    if n_train < 1:
        raise ValueError("need at least one non-evaluator node")
    cluster_template: dict[str, list[int]] = {"chief": [0]}
    if n_train > 1:
        cluster_template["worker"] = list(range(1, n_train))
    if eval_node:
        cluster_template["evaluator"] = [num_executors - 1]

    server = reservation.Server(num_executors)
    server_addr = server.start()

    # The node runtime itself requires 'error' (exception ferry) and
    # 'control' (STOP); 'output' is needed by inference. Union them in so a
    # reference-style custom queue list can't break the runtime.
    queues = tuple(queues) if queues else ("input",)
    for required in ("output", "error", "control"):
        if required not in queues:
            queues = queues + (required,)
    cluster_meta: dict[str, Any] = {
        "id": secrets.token_hex(4),
        "cluster_template": cluster_template,
        "num_executors": num_executors,
        "server_addr": list(server_addr),
        "authkey": secrets.token_hex(16),
        "queues": list(queues),
        "input_mode": input_mode,
        "default_fs": default_fs,
        "working_dir": working_dir or "",
        "tensorboard": tensorboard,
        "profiler": profiler,
        # per-node Prometheus /metrics endpoint (an unauthenticated
        # read-only listener on the node host; metrics=False for
        # deployments with strict port policies — see metrics_urls())
        "metrics": metrics,
        "log_dir": log_dir,
        "reservation_timeout": reservation_timeout,
        # Liveness plane: every node heartbeats the reservation server
        # at this interval (<= 0 disables); the driver treats a node
        # silent for heartbeat_grace seconds as dead (TFCluster.
        # dead_nodes / supervise and the feed-plane checks).
        "heartbeat_interval": heartbeat_interval,
        "heartbeat_grace": heartbeat_grace,
        # Elastic plane: supervise() reconfigures (epoch bump + reshard)
        # on membership change instead of failing; below
        # elastic_min_nodes survivors it gives up and raises (restart —
        # run_with_restarts — is then the only recovery).
        "elastic": elastic,
        "elastic_min_nodes": elastic_min_nodes,
        # Live shard redistribution (docs/ROBUSTNESS.md): elastic
        # reconfigures RE-SPLIT the remaining ingest records over the
        # survivors (cooperative drain bounded by handover_timeout);
        # False falls back to PR-8 stable per-executor-id shards.
        "ingest_handover": ingest_handover,
        "handover_timeout": handover_timeout,
        "distributed": distributed,
        "queue_maxsize": queue_maxsize,
        "manager_mode": "remote",
        # Ring only pays off when a feeder will attach, i.e. SPARK mode.
        "use_shm_ring": use_shm_ring and input_mode == InputMode.SPARK,
        "shm_ring_mb": shm_ring_mb,
        # Chunk-columnar wire format (feed/columnar.py): driver feeders
        # columnize each chunk once and nodes slice zero-copy column
        # views; False = legacy row-pickle wire. TFOS_COLUMNAR=0 in the
        # driver environment forces it off too (operator escape hatch).
        "columnar": columnar and os.environ.get("TFOS_COLUMNAR", "1") != "0",
        # Run-scoped trace id: every process stamps it into its span
        # exports so driver + node timelines stitch (obs.cluster /
        # tools/trace_merge.py). The cluster id IS the trace id.
        "trace_id": None,  # filled below from "id"
        # Flight-recorder directory (None disables): each node keeps a
        # rolling logs/flightrec-node<id>.json snapshot so a SIGKILL
        # still leaves a postmortem (obs.flightrec).
        "flightrec_dir": flightrec_dir,
    }
    cluster_meta["trace_id"] = cluster_meta["id"]
    logger.info(
        "starting cluster %s: %d nodes, template %s",
        cluster_meta["id"],
        num_executors,
        cluster_template,
    )

    # Driver-side trace context + flight recorder (event-triggered: the
    # driver dumps on dead-node detection and supervised relaunches —
    # it is alive to do so; nodes roll periodic snapshots instead).
    obs_cluster.set_trace_context(cluster_meta["trace_id"], node="driver")
    if flightrec_dir:
        fr_dir = flightrec_dir
        if not os.path.isabs(fr_dir):
            fr_dir = os.path.join(working_dir or os.getcwd(), fr_dir)
        flightrec.install(
            os.path.join(fr_dir, "flightrec-driver.json"), process="driver"
        )

    if launcher is None:
        launcher = LocalLauncher()
    try:
        # env rides the launch call (never mutate a caller's launcher):
        # per-node interpreters must see it at boot, when jax reads
        # JAX_PLATFORMS. Custom launchers advertise support by accepting
        # an `env` kwarg; silently dropping it could let processes the
        # caller wanted CPU-only take the chip, so an env-less launcher
        # + env is a loud error.
        import inspect

        sig = inspect.signature(launcher.launch).parameters
        accepts_env = "env" in sig or any(
            p.kind == p.VAR_KEYWORD for p in sig.values()
        )
        if env and not accepts_env:
            raise ValueError(
                f"launcher {type(launcher).__name__}.launch() does not "
                "accept env=; it cannot carry env vars to node processes"
            )
        launch_kwargs = {"env": env} if accepts_env else {}
        launcher.launch(
            num_executors,
            tfnode_runtime.run_node,
            lambda i: (i, map_fun, tf_args, cluster_meta),
            **launch_kwargs,
        )
    except Exception:
        launcher.terminate()
        server.stop()
        raise

    try:
        cluster_info = server.await_reservations(
            timeout=reservation_timeout,
            status_fn=lambda rem: _abort_if_node_died(launcher, rem),
        )
    except Exception:
        launcher.terminate()
        server.stop()
        raise
    logger.info("cluster %s up: %s", cluster_meta["id"], cluster_info)
    cluster = TFCluster(
        launcher, server, server_addr, cluster_info, cluster_meta, input_mode, queues
    )
    cluster._node_env = dict(env or {})
    return cluster


# Reference-compat: the reference exposes `TFCluster.run(...)` as a module
# function; callers importing our class get the same spelling.
TFCluster.run = staticmethod(run)


def run_with_restarts(
    map_fun: Callable,
    tf_args: Any,
    num_executors: int,
    max_restarts: int = 2,
    launcher_factory: Callable[[], Any] | None = None,
    shutdown_timeout: float = 259200.0,
    **run_kwargs,
) -> int:
    """Supervised whole-cluster auto-restart for ``InputMode.TENSORFLOW``
    jobs; returns the number of restarts that were needed.

    The reference had no elasticity — its recovery story was "Spark
    retries the job; TF restores from checkpoint" (SURVEY.md §5.3). This
    is that story made first-class on the TPU side: run the cluster, and
    if any node dies or ferries an exception, tear the whole cluster
    down, relaunch it (fresh reservation round), and let the user's
    ``map_fun`` resume from its latest orbax checkpoint — the resume
    convention the examples already follow (``CheckpointManager.
    latest_step()`` + restore at startup, e.g. ``examples/llama/
    llama_fsdp.py``). After ``max_restarts`` failed attempts the last
    error propagates.

    Only ``InputMode.TENSORFLOW`` is supervisable: a push feed's consumed
    partitions cannot be replayed by the driver (``InputMode.SPARK`` is
    rejected). Pass ``launcher_factory`` (not a launcher instance) so
    each attempt gets a fresh launcher.
    """
    if run_kwargs.get("input_mode", InputMode.SPARK) != InputMode.TENSORFLOW:
        raise ValueError(
            "run_with_restarts requires input_mode=InputMode.TENSORFLOW "
            "(a push feed's consumed partitions cannot be replayed)"
        )
    if "launcher" in run_kwargs:
        raise ValueError(
            "pass launcher_factory=callable, not launcher=: each restart "
            "attempt needs a fresh launcher"
        )
    restarts = 0
    while True:
        try:
            # run() failures (e.g. a node dying before its reservation)
            # count against the restart budget too: startup flakiness is
            # exactly what the supervisor exists for. run() cleans up its
            # own launcher/server on the way out.
            cluster = run(
                map_fun,
                tf_args,
                num_executors,
                launcher=launcher_factory() if launcher_factory else None,
                **run_kwargs,
            )
            # Supervised wait: liveness + process exits, so a node that
            # is SIGKILLed (or wedges past the heartbeat grace) mid-run
            # triggers the relaunch within seconds instead of after
            # shutdown_timeout. On failure, kill the survivors so the
            # shutdown below reaps the whole attempt promptly.
            supervise_error: RuntimeError | None = None
            try:
                cluster.supervise()
            except RuntimeError as e:
                supervise_error = e
                logger.warning("supervision detected failure: %s", e)
                # postmortem artifact before the relaunch erases state
                flightrec.note("supervise_restart", error=str(e))
                flightrec.dump_now("supervise_restart")
                cluster.launcher.terminate()
            cluster.shutdown(timeout=shutdown_timeout)
            if supervise_error is not None:
                # shutdown absorbed the damage (e.g. every process was
                # terminated back to exit 0 somehow): the supervision
                # verdict still stands — this attempt failed.
                raise supervise_error
            return restarts
        except RuntimeError as e:
            restarts += 1
            if restarts > max_restarts:
                raise
            logger.warning(
                "cluster attempt failed (%s); restarting (%d/%d) — nodes "
                "resume from their latest checkpoint",
                e,
                restarts,
                max_restarts,
            )


def _probe_node_states(
    nodes: list[dict[str, Any]], timeout: float
) -> list[str]:
    """Each node's manager KV ``state``, probed in parallel bounded
    daemon threads sharing ONE ``timeout`` window.

    Manager RPCs have no client-side timeout, and a WEDGED node's kernel
    happily accepts the TCP connect and then hangs the handshake —
    exactly what supervision must not do. Per node, returns the state
    string, ``"unreachable"`` (connect refused/reset: the process is
    gone or going), or ``"hung"`` (no answer inside the window; that
    probe thread is daemon and abandoned)."""
    results: list[list[str]] = [[] for _ in nodes]

    def probe(i: int, node_meta: dict[str, Any]) -> None:
        try:
            mgr = tfnode_runtime.connect_manager(node_meta)
            results[i].append(tfnode_runtime.fetch_node_state(mgr))
        except (ConnectionError, OSError, EOFError):
            results[i].append("unreachable")

    threads = [
        threading.Thread(target=probe, args=(i, n), daemon=True)
        for i, n in enumerate(nodes)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    return [r[0] if r else "hung" for r in results]


def _abort_if_node_died(launcher, remaining: int) -> None:
    failed = launcher.poll_failed()
    if failed:
        raise RuntimeError(
            f"node process(es) {failed} died during startup "
            f"({remaining} reservations still pending)"
        )


def _as_partitions(
    data: Iterable, num_workers: int, contiguous: bool = False
) -> list[list[Any]]:
    """Normalize user data into a list of record-list partitions.

    Convention (documented in ``TFCluster.train``): if every element is a
    ``list`` or an iterator/generator, the elements ARE the partitions
    (generators are drained); otherwise the whole iterable is a flat
    sequence of records, split into ``num_workers`` partitions so every
    worker receives data — round-robin by default (train: strided
    samples keep per-worker batch statistics close to the input
    distribution), CONTIGUOUS near-equal when ``contiguous=True``
    (inference: results are reassembled in partition order, so
    contiguous splits are what make the order-preserving contract hold
    for flat inputs). Records may be tuples, arrays, dicts, or scalars
    — use tuples (not lists) for row records, exactly as a DataFrame
    ``Row`` would arrive in the reference.
    """
    data = list(data)
    if data and all(
        isinstance(p, list) or isinstance(p, Iterator) for p in data
    ):
        return [list(p) for p in data]
    if len(data) <= num_workers:
        # Per-record partitions: one big partition here would feed ONLY
        # worker 0 and leave every other worker blocking until shutdown
        # (harmless at scale, baffling in smoke tests).
        return [[r] for r in data]
    if not contiguous:
        return [data[i::num_workers] for i in range(num_workers)]
    return contiguous_split(data, num_workers)


def contiguous_split(records: list, n: int) -> list[list[Any]]:
    """Split ``records`` into at most ``n`` contiguous near-equal
    partitions (sizes differ by at most one, empties dropped).
    Contiguity is what makes partition-order reassembly — the
    ``inference``/distributed-``transform`` result path — preserve the
    original record order."""
    k, m = divmod(len(records), n)
    bounds = [i * k + min(i, m) for i in range(n + 1)]
    return [
        records[bounds[i] : bounds[i + 1]]
        for i in range(n)
        if bounds[i] < bounds[i + 1]
    ]
