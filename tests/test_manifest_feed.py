"""Manifest feeding: driver ships paths, nodes read files locally
(feed/manifest.py — the node-side feeder closing the push-plane
ceiling gap)."""

import os

import numpy as np
import pytest

from tensorflowonspark_tpu.feed.manifest import (
    FileManifest,
    ManifestFeed,
    read_manifest,
)


class _FakeFeed:
    """DataFeed stand-in: yields queued records one call at a time."""

    def __init__(self, records):
        self._records = list(records)

    def should_stop(self):
        return not self._records

    def next_batch(self, n):
        out, self._records = self._records[:n], self._records[n:]
        return out


def test_read_manifest_lines_and_slicing(tmp_path):
    p = tmp_path / "a.txt"
    p.write_text("".join(f"v{i}\n" for i in range(10)))
    assert list(read_manifest(FileManifest(str(p), format="lines"))) == [
        f"v{i}" for i in range(10)
    ]
    sliced = FileManifest(str(p), format="lines", start=3, stop=7)
    assert list(read_manifest(sliced)) == ["v3", "v4", "v5", "v6"]
    with pytest.raises(ValueError, match="format"):
        list(read_manifest(FileManifest(str(p), format="bogus")))


def test_read_manifest_tfrecord(tmp_path):
    from tensorflowonspark_tpu.data import dfutil

    rows = [{"x": float(i), "i": i} for i in range(6)]
    dfutil.saveAsTFRecords(rows, str(tmp_path / "rec"))
    (path,) = dfutil.tfrecord_files(str(tmp_path / "rec"))
    back = list(read_manifest(FileManifest(path)))
    assert [int(r["i"]) for r in back] == list(range(6))
    np.testing.assert_allclose([float(np.ravel(r["x"])[0]) for r in back],
                               range(6))


def test_manifest_feed_batches_across_files(tmp_path):
    """next_batch spans file boundaries and drains the last manifest
    after the underlying feed ends; custom reader callables work."""
    paths = []
    for fi in range(3):
        p = tmp_path / f"f{fi}.txt"
        p.write_text("".join(f"{fi}:{i}\n" for i in range(5)))
        paths.append(str(p))
    feed = ManifestFeed(
        _FakeFeed([FileManifest(p, format="lines") for p in paths])
    )
    seen = []
    while not feed.should_stop():
        batch = feed.next_batch(4)
        assert len(batch) <= 4
        seen.extend(batch)
    assert seen == [f"{fi}:{i}" for fi in range(3) for i in range(5)]

    # custom reader: manifests can be anything the callable understands
    feed = ManifestFeed(
        _FakeFeed([FileManifest("three", format="custom")]),
        reader=lambda m: iter([m.path] * 3),
    )
    assert feed.next_batch(8) == ["three"] * 3


def test_manifest_feed_batch_stream(tmp_path):
    """batch_stream parity with DataFeed: fixed shapes, multiple_of
    trimming, and column assembly from an input_mapping (rows are the
    manifest-expanded records, not the manifests)."""
    from tensorflowonspark_tpu.data import dfutil

    rows = [{"x": float(i), "label": i % 3} for i in range(22)]
    dfutil.saveAsTFRecords(rows, str(tmp_path / "rec"))
    (path,) = dfutil.tfrecord_files(str(tmp_path / "rec"))

    feed = ManifestFeed(_FakeFeed([FileManifest(path)]))
    batches = list(
        feed.batch_stream(
            8, multiple_of=4, input_mapping={"x": "x", "label": "y"}
        )
    )
    # 22 records -> 8, 8, then tail 6 trimmed to 4 (multiple_of)
    assert [len(b["y"]) for b in batches] == [8, 8, 4]
    got = np.concatenate([np.ravel(b["y"]) for b in batches])
    np.testing.assert_array_equal(got, [i % 3 for i in range(20)])


@pytest.mark.e2e
def test_manifest_feeding_through_cluster(tmp_path):
    """End-to-end: driver feeds ONLY FileManifest records (O(files)
    driver traffic); every node expands its manifests locally; together
    they cover the dataset exactly once."""
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tensorflowonspark_tpu.utils.util import cpu_only_env

    from tests import cluster_fns

    paths = []
    for fi in range(6):
        p = tmp_path / f"data{fi}.txt"
        p.write_text("".join(f"{fi * 100 + i}\n" for i in range(20)))
        paths.append(str(p))

    out_dir = str(tmp_path)
    cluster = tfcluster.run(
        cluster_fns.manifest_drain_fn,
        {"out_dir": out_dir},
        num_executors=2,
        input_mode=InputMode.SPARK,
        reservation_timeout=120,
        env=cpu_only_env(),
    )
    manifests = [FileManifest(p, format="lines") for p in paths]
    cluster.train([manifests[0::2], manifests[1::2]], close_feed=True)
    cluster.shutdown(timeout=120)

    got = []
    for i in range(2):
        with open(os.path.join(out_dir, f"node{i}.txt")) as f:
            got.extend(int(line) for line in f)
    expected = sorted(fi * 100 + i for fi in range(6) for i in range(20))
    assert sorted(got) == expected
