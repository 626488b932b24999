"""Failure flight recorder: the last N seconds, on disk, at death.

PR 4 made node death *detectable* in seconds (liveness plane), but the
postmortem still had nothing to read: a SIGKILLed node's span ring,
counters, and recent events died with the process, and the driver-side
diagnostic was one line ("node(s) [1] missed heartbeats"). This module
keeps a bounded in-memory record per process — recent spans (the
tracer's ring IS the bound), a metrics snapshot, and a small event log
— and persists it to ``logs/flightrec-<node>.json``:

- **Periodically** (node processes, on the heartbeat cadence): an
  atomic rolling snapshot, so even a SIGKILL — where the process gets
  no chance to say goodbye — leaves its last interval on disk.
- **On events**: the driver dumps when the liveness plane declares a
  node dead or ``supervise()`` triggers a relaunch; the serving
  engine dumps when its wedge watchdog fires; a node dumps when its
  ``map_fun`` ferries an exception.

Dumps embed the tracer's Chrome-trace export (with its
``trace_context`` metadata), so ``tools/trace_merge.py`` reads them
directly — a postmortem is one
``trace_merge logs/flightrec-*.json`` away from a cluster timeline.

Module-level :func:`install` / :func:`note` / :func:`dump_now` keep
call sites one line: hot paths ``note()`` unconditionally (a no-op
before install), and crash handlers ``dump_now(reason)``.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any

from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.obs.registry import Registry, default_registry

logger = logging.getLogger(__name__)

__all__ = [
    "EVENTS",
    "FlightRecorder",
    "dump_now",
    "get",
    "install",
    "note",
]

FORMAT_VERSION = 1

#: The registered event-name catalog. Postmortem tooling greps dumps by
#: these exact strings, so ``note()`` call sites must use literals from
#: this set — lint rule OB002 (``analysis/flightrecnames.py``) parses
#: this assignment from disk (the FP001 pattern) and flags dynamic or
#: unregistered names at build time. Adding an event = add the literal
#: here, ``note()`` it at the site, document it in
#: docs/OBSERVABILITY.md. (``dump_now`` *reasons* are free-form — they
#: name why a dump was cut, not a queryable event stream.)
EVENTS = frozenset(
    {
        # cluster liveness / supervision (cluster/*)
        "node_start",
        "dead_node",
        "supervise_restart",
        "map_fun_error",
        "membership_epoch",
        # elastic reconfiguration (compute/elastic.py, cluster/tfcluster.py)
        "elastic_epoch_bump",
        "elastic_reconfigure",
        "elastic_reconfigure_failed",
        "elastic_hydrate",
        # ingest plane (feed/ingest.py, cluster/tfcluster.py)
        "ingest_plan",
        "ingest_plan_republish",
        "ingest_handover",
        # serving fleet (serving/*)
        "engine_watchdog",
        "fleet_shed",
        "fleet_drain",
        "replica_drain",
        "replica_respawn",
        "replica_dead",
        "replica_swap",
        "rollout_begin",
        "rollout_complete",
        "rollout_rollback",
        # disaggregated cache tier (cachetier/ + serving/fleet.py —
        # docs/SERVING.md "Cache tier"): daemon lifecycle and rollout
        # reclamation are the post-mortem surface for "why did the
        # fleet hit-rate fall off a cliff at 14:03"
        "cachetier_spawn",
        "cachetier_respawn",
        "cachetier_invalidate",
        # observability plane (obs/slo.py, utils/lockwitness.py)
        "slo_breach",
        "tfsan",
        # online knob tuning (autotune/ — docs/AUTOTUNE.md): every
        # controller move, every regression revert, and every freeze
        # (operator or SLO-breach back-off) is auditable after the fact
        "autotune_decision",
        "autotune_revert",
        "autotune_frozen",
        # online continual loop (feed/livelog.py + online.py — see
        # docs/ROBUSTNESS.md "Online continual loop"): every loop cycle
        # (manifests discovered, data age, lag), every sealed-segment
        # manifest publication, and every stall onset is auditable
        "online_cycle",
        "online_stall",
        "online_manifest_publish",
    }
)


class FlightRecorder:
    """Bounded per-process black box; :meth:`dump` writes it atomically.

    ``tracer``/``registry`` default to the process-global ones — the
    recorder does not re-instrument anything, it snapshots what the
    existing obs surfaces already hold. ``interval > 0`` enables the
    rolling-snapshot daemon (:meth:`start`).
    """

    def __init__(
        self,
        path: str,
        process: str = "proc",
        tracer: obs_spans.SpanTracer | None = None,
        registry: Registry | None = None,
        events_capacity: int = 512,
        interval: float = 0.0,
    ):
        self.path = path
        self.process = process
        self.tracer = tracer if tracer is not None else obs_spans.get_tracer()
        self.registry = registry if registry is not None else default_registry()
        self.interval = float(interval)
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(1, int(events_capacity)))  # guarded-by: self._lock
        self.dumps = 0  # lifetime dump count  # guarded-by: self._lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def note(self, kind: str, **details: Any) -> None:
        """Append one event (wall-clock stamped) to the bounded log —
        cheap enough for supervision/degradation paths (one deque
        append; no IO)."""
        with self._lock:
            self._events.append(
                {"t_unix": time.time(), "kind": kind, **details}
            )

    def snapshot(self, reason: str) -> dict[str, Any]:
        from tensorflowonspark_tpu.obs import cluster as obs_cluster

        with self._lock:
            events = list(self._events)
        try:
            metrics_text = self.registry.render()
        except Exception as e:  # noqa: BLE001 - a snapshot must not raise
            metrics_text = f"# render failed: {type(e).__name__}: {e}\n"
        return {
            "flightrec_version": FORMAT_VERSION,
            "process": self.process,
            "reason": reason,
            "written_unix": time.time(),
            "trace_context": obs_cluster.trace_context(),
            "clock_sync": obs_cluster.clock_sync(),
            "events": events,
            "metrics": metrics_text,
            # full Chrome-trace export (with trace_context metadata):
            # trace_merge reads dumps as trace files
            "spans": self.tracer.export(process_name=self.process),
        }

    def dump(self, reason: str) -> str:
        """Write the snapshot atomically (tmp + rename, so a reader —
        or a SIGKILL mid-write — never sees a torn file); returns the
        path."""
        snap = self.snapshot(reason)
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            # default=str: span args are user-extensible (numpy scalars
            # and the like must degrade to text, not kill the dump)
            json.dump(snap, f, default=str)
            f.write("\n")
        os.replace(tmp, self.path)
        with self._lock:
            self.dumps += 1
        return self.path

    # -- rolling snapshots --------------------------------------------

    def start(self) -> None:
        """Daemon thread re-dumping every ``interval`` seconds — the
        SIGKILL story: the process never gets to dump at death, so the
        last rolling snapshot IS the postmortem."""
        if self.interval <= 0 or self._thread is not None:
            return
        self._stop.clear()

        def loop() -> None:
            while not self._stop.wait(self.interval):
                try:
                    self.dump("periodic")
                except Exception as e:  # noqa: BLE001 - keep rolling
                    logger.warning("flight recorder snapshot failed: %s", e)

        self._thread = threading.Thread(
            target=loop, daemon=True, name="flightrec"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)


# -- process-global recorder -------------------------------------------------

_install_lock = threading.Lock()
_recorder: FlightRecorder | None = None  # guarded-by: _install_lock


def install(path: str, **kwargs: Any) -> FlightRecorder:
    """Install (or replace) the process-global recorder; a replaced
    recorder's snapshot thread is stopped. Returns the new recorder —
    call :meth:`FlightRecorder.start` for rolling snapshots."""
    global _recorder
    rec = FlightRecorder(path, **kwargs)
    with _install_lock:
        old, _recorder = _recorder, rec
    if old is not None:
        old.stop()
    return rec


def get() -> FlightRecorder | None:
    with _install_lock:
        return _recorder


def note(kind: str, **details: Any) -> None:
    """Event-log append on the installed recorder; no-op without one
    — callers (engine watchdog, supervision) never need to know
    whether this process opted into flight recording."""
    rec = get()
    if rec is not None:
        try:
            rec.note(kind, **details)
        except Exception:  # pragma: no cover - note must never raise
            pass


def dump_now(reason: str) -> str | None:
    """Dump the installed recorder (None without one / on IO failure)
    — the one-liner for crash paths, which must never crash harder
    because the black box had a bad day."""
    rec = get()
    if rec is None:
        return None
    try:
        return rec.dump(reason)
    except Exception as e:  # noqa: BLE001 - crash paths call this
        logger.warning("flight recorder dump failed: %s", e)
        return None
