"""Measure the SPARK-mode push-feed plane's throughput ceiling (CPU).

ALL partition data in InputMode.SPARK flows from the single driver
process to the node managers (shm ring when co-located,
TCP otherwise) — the reference's feed tasks ran *on the executors* with
HDFS locality, so its driver shipped closures, not bytes. This bench
quantifies that design's ceiling so DESIGN.md can state when to switch to
pull mode (InputMode.TENSORFLOW + grain/tf.data sharding).

What it measures, per (node count, path, wire): wall time from the
start of ``cluster.train(close_feed=True)`` until ``shutdown()``
returns — i.e. until every node has DRAINED its feed into
``{tensor: ndarray}`` batches through an ``input_mapping`` (the shape a
train step consumes), not merely until the driver buffered it into
rings — for a fixed payload of DISTINCT uint8-array records. (Distinct
matters: identical record objects would let pickle's memoizer collapse
a whole chunk to a few bytes and the row leg would measure nothing.)

Paths:
- ``shm``: the co-located fast path (``native/shmring.cc``).
- ``tcp``: the manager-proxy path every remote node uses (forced by
  disabling the driver-side ring lookup; the node-side ring still
  exists but no producer attaches).
- ``manifest``: node-side feeders (``feed/manifest.py``) — the driver
  ships one FileManifest per node and each node streams its file
  locally; driver traffic is O(files), so this path's number is the
  node-local read rate, not a driver ceiling.
- ``pull``: the driverless pull plane (ISSUE 8; ``feed/ingest.py``) —
  ``InputMode.TENSORFLOW``, the driver publishes only the shard plan
  (``assign_shards``) and every node's executor-local reader drains
  its columnar shard with NO driver process in the data loop. Each
  node self-times its drain (first batch → last batch) and reports
  per-node MB/s beside the wall-clock aggregate.

Wires (ISSUE 5): ``columnar`` ships each chunk as one CRC-framed
column frame (``feed/columnar.py``; scatter-pushed zero-copy on shm,
one bytes payload on tcp, 64-aligned frame files on manifest);
``row`` pins the legacy row-pickle wire (``columnar=False`` /
lines-format manifests) — the before/after pair the results artifact
records. The pull leg is columnar-only (the frame files ARE its wire).

Scaling sweep (ISSUE 8): ``--nodes 1,2,4,8 --paths shm,pull`` produces
the push-columnar vs pull-sharded legs per node count. Because every
bench node is co-located on ONE host, wall-clock aggregate is bounded
by host cores for BOTH legs once nodes exceed them; ``--pull-mode
staggered`` additionally serializes the pull drains (a driver-side
turn token: one node's shard plan is published only after the previous
node reported its stats), measuring each node's UNCONTENDED rate at
every cluster size — the number that transfers to one-node-per-host
deployments, since pull nodes share no driver-side component (the push
legs have no analogous projection: their shared component IS the
driver). Both modes land in the artifact.

Usage::

    python benchmarks/feed_plane.py [--nodes 1,2,4,8] [--mb-per-node 64]
        [--record-kb 64] [--paths shm,tcp,pull] [--wire columnar,row]
        [--pull-mode coscheduled,staggered] [--json out.jsonl]

Prints one JSON line per configuration.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def drain_fn(args, ctx):
    """Consume the feed into mapped column batches as fast as possible;
    count records. The mapping is the point: the row wire pays
    ``columnize_rows`` (np.stack) per batch here, the columnar wire
    slices zero-copy views. (The lines-format manifest leg drains raw
    rows — text lines have no column mapping.)"""
    batch = int(args["batch"])
    n = 0
    if args.get("manifest"):
        from tensorflowonspark_tpu.feed.manifest import ManifestFeed

        feed = ManifestFeed(ctx.get_data_feed())
        if args.get("columnar"):
            for cols in feed.batch_stream(batch, 1, input_mapping={"x": "x"}):
                n += len(cols["x"])
        else:
            while not feed.should_stop():
                n += len(feed.next_batch(batch))
    else:
        feed = ctx.get_data_feed(input_mapping={"x": "x"})
        while not feed.should_stop():
            cols = feed.next_batch(batch)
            if cols:
                n += len(cols["x"])
    print(f"node {ctx.worker_num}: drained {n} records", flush=True)


def pull_drain_fn(args, ctx):
    """Pull-plane map_fun: drain this node's shard through
    ``ctx.get_ingest_feed`` (executor-local columnar reader, mapped
    column batches — the same consuming shape as ``drain_fn``),
    self-timing first→last batch, and report stats via the manager KV
    so the driver can collect per-node rates."""
    import time as _time

    feed = ctx.get_ingest_feed(
        input_mapping={"x": "x"}, timeout=float(args.get("timeout", 600))
    )
    batch = int(args["batch"])
    n = 0
    nbytes = 0
    t0 = None
    for cols in feed.batch_stream(batch):
        if t0 is None:
            t0 = _time.perf_counter()
        n += len(cols["x"])
        nbytes += cols["x"].nbytes
    secs = 0.0 if t0 is None else _time.perf_counter() - t0
    ctx.mgr.set(
        "ingest_stats", {"records": n, "bytes": nbytes, "secs": secs}
    )
    print(f"node {ctx.worker_num}: drained {n} records", flush=True)


def _collect_ingest_stats(worker, timeout: float = 600.0) -> dict:
    from tensorflowonspark_tpu.cluster import node as tfnode_runtime

    deadline = time.perf_counter() + timeout
    mgr = tfnode_runtime.connect_manager(worker)
    while time.perf_counter() < deadline:
        stats = mgr.get("ingest_stats")
        if stats is not None:
            return stats
        time.sleep(0.1)
    raise TimeoutError(
        f"node {worker['executor_id']} never reported ingest stats"
    )


def _run_pull_config(
    n_nodes: int,
    mb_per_node: int,
    record_kb: int,
    batch: int,
    staggered: bool = False,
) -> dict:
    from tensorflowonspark_tpu.cluster import node as tfnode_runtime
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tensorflowonspark_tpu.feed.columnar import write_frames
    from tensorflowonspark_tpu.feed.manifest import FileManifest
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    import tempfile

    import numpy as np

    record_len = record_kb * 1024
    per_node = (mb_per_node * 1024 * 1024) // record_len
    # records per frame sized so one frame is ~4 MB: big enough to
    # amortize header decode, small enough that batch slicing stays
    # fine-grained
    rpf = max(1, (4 << 20) // record_len)
    tmpdir = tempfile.TemporaryDirectory(prefix="feed_plane_pull_")
    manifests = []
    for i in range(n_nodes):
        fp = f"{tmpdir.name}/node{i}.colf"
        arr = np.full((per_node, record_len), 120, np.uint8)
        write_frames(fp, ((row,) for row in arr), records_per_frame=rpf)
        manifests.append(FileManifest(fp, format="columnar"))
    total_mb = n_nodes * per_node * record_len / 1e6
    cluster = None
    try:
        cluster = tfcluster.run(
            pull_drain_fn,
            # staggered mode publishes node i's plan only after i-1
            # finished draining, so a later node's plan-fetch wait must
            # outlast ALL earlier drains — scale the timeout with the
            # cluster size instead of trusting the 600s default
            {"batch": batch, "timeout": 600.0 * max(1, n_nodes)},
            num_executors=n_nodes,
            input_mode=InputMode.TENSORFLOW,
            reservation_timeout=120,
            env=cpu_only_env(),
        )
        workers = cluster.workers
        t0 = time.perf_counter()
        per_node_stats = []
        if staggered:
            # turn token: node i's plan is published only after node
            # i-1 reported — each drain runs uncontended on this host
            for i, w in enumerate(workers):
                tfnode_runtime.publish_ingest_plan(
                    tfnode_runtime.connect_manager(w),
                    [manifests[i]],
                    shard_index=i,
                    num_shards=n_nodes,
                )
                per_node_stats.append(_collect_ingest_stats(w))
        else:
            cluster.assign_shards(manifests)
            per_node_stats = [_collect_ingest_stats(w) for w in workers]
        secs = time.perf_counter() - t0
        cluster.shutdown(timeout=600)
    finally:
        # teardown BEFORE deleting the frame files: live readers still
        # mmap them, and yanking the files would bury the real error
        # under FileNotFoundError noise from every surviving node
        if cluster is not None and not cluster._shutdown_done:
            try:
                cluster.launcher.terminate()
                cluster.server.stop()
            except Exception:
                pass
        tmpdir.cleanup()
    rates = [
        s["bytes"] / s["secs"] / 1e6 for s in per_node_stats if s["secs"] > 0
    ]
    # staggered aggregate = sum of uncontended per-node rates (pull
    # nodes share nothing driver-side); co-scheduled aggregate = real
    # wall clock on this host
    aggregate = sum(rates) if staggered else total_mb / secs
    return {
        "bench": "feed_plane",
        "leg": "pull-sharded",
        "nodes": n_nodes,
        "path": "pull",
        "wire": "columnar",
        "mode": "staggered" if staggered else "coscheduled",
        "record_kb": record_kb,
        "mb_total": round(total_mb, 1),
        "secs": round(secs, 3),
        "mb_per_s": round(aggregate, 1),
        "mb_per_s_per_node": round(
            (sum(rates) / len(rates)) if rates else 0.0, 1
        ),
        "per_node_mb_per_s": [round(r, 1) for r in rates],
    }


def _run_config(n_nodes: int, path: str, mb_per_node: int, record_kb: int,
                batch: int, wire: str = "columnar") -> dict:
    from tensorflowonspark_tpu.cluster import node as tfnode_runtime
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    import tempfile

    import numpy as np

    columnar = wire == "columnar"
    record_len = record_kb * 1024
    per_node = (mb_per_node * 1024 * 1024) // record_len
    tmpdir = None
    if path == "manifest":
        # Node-side feeders: the driver ships ONE FileManifest per node;
        # each node streams its file locally (feed/manifest.py). File
        # creation is setup, not part of the timed window. The columnar
        # wire reads 64-aligned frame files zero-copy over one mmap; the
        # row wire streams text lines.
        from tensorflowonspark_tpu.feed.manifest import FileManifest

        tmpdir = tempfile.TemporaryDirectory(prefix="feed_plane_")
        partitions = []
        for i in range(n_nodes):
            if columnar:
                from tensorflowonspark_tpu.feed.columnar import write_frames

                fp = f"{tmpdir.name}/node{i}.colf"
                arr = np.full((per_node, record_len), 120, np.uint8)
                write_frames(
                    fp,
                    ((row,) for row in arr),
                    records_per_frame=512,
                )
                partitions.append([FileManifest(fp, format="columnar")])
            else:
                fp = f"{tmpdir.name}/node{i}.txt"
                line = "x" * (record_len - 1)
                with open(fp, "w") as f:
                    for _ in range(per_node):
                        f.write(line + "\n")
                partitions.append([FileManifest(fp, format="lines")])
    else:
        # DISTINCT per-record arrays (views over one allocation): pickle
        # must move every byte, as it would for real data
        partitions = [
            [
                (row,)
                for row in np.full((per_node, record_len), 120, np.uint8)
            ]
            for _ in range(n_nodes)
        ]
    total_mb = n_nodes * per_node * record_len / 1e6

    real_node_ring = tfnode_runtime._node_ring
    if path == "tcp":
        # Driver-side only: pretend no ring is advertised, forcing every
        # chunk through the TCP manager proxy (what any remote node gets).
        tfnode_runtime._node_ring = lambda node: None
    try:
        cluster = tfcluster.run(
            drain_fn,
            {
                "batch": batch,
                "manifest": path == "manifest",
                "columnar": columnar,
            },
            num_executors=n_nodes,
            input_mode=InputMode.SPARK,
            reservation_timeout=120,
            env=cpu_only_env(),
            columnar=columnar,
        )
        t0 = time.perf_counter()
        cluster.train(partitions, close_feed=True)
        cluster.shutdown(timeout=600)
        secs = time.perf_counter() - t0
    finally:
        tfnode_runtime._node_ring = real_node_ring
        if tmpdir is not None:
            tmpdir.cleanup()
    return {
        "bench": "feed_plane",
        "leg": f"push-{wire}",
        "nodes": n_nodes,
        "path": path,
        "wire": wire,
        "record_kb": record_kb,
        "mb_total": round(total_mb, 1),
        "secs": round(secs, 3),
        "mb_per_s": round(total_mb / secs, 1),
        "mb_per_s_per_node": round(total_mb / secs / n_nodes, 1),
    }


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--nodes", default="1,2,4,8")
    p.add_argument("--mb-per-node", type=int, default=64)
    p.add_argument("--record-kb", type=int, default=64)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--paths", default="shm,tcp")
    p.add_argument("--wire", default="columnar,row",
                   help="comma list of wire formats: columnar,row")
    p.add_argument(
        "--pull-mode",
        default="coscheduled,staggered",
        help="comma list for the pull path: coscheduled (wall-clock "
        "aggregate; core-bounded on one host), staggered (serialized "
        "drains; uncontended per-node rates)",
    )
    p.add_argument("--json", default=None, help="also append JSONL here")
    args = p.parse_args(argv)

    out = open(args.json, "a") if args.json else None
    try:
        for n in [int(x) for x in args.nodes.split(",") if x.strip()]:
            for path in [x.strip() for x in args.paths.split(",") if x.strip()]:
                if path == "pull":
                    rows = [
                        _run_pull_config(
                            n, args.mb_per_node, args.record_kb,
                            args.batch, staggered=mode == "staggered",
                        )
                        for mode in [
                            m.strip()
                            for m in args.pull_mode.split(",")
                            if m.strip()
                        ]
                    ]
                else:
                    rows = [
                        _run_config(
                            n, path, args.mb_per_node, args.record_kb,
                            args.batch, wire,
                        )
                        for wire in [
                            w.strip()
                            for w in args.wire.split(",")
                            if w.strip()
                        ]
                    ]
                for row in rows:
                    line = json.dumps(row)
                    print(line, flush=True)
                    if out:
                        out.write(line + "\n")
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
