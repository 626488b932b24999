"""Checkpoint / restore via orbax.

Reference parity (SURVEY.md §5.4): the reference delegated checkpointing to
TF (``ModelCheckpoint``/``BackupAndRestore``) and contributed pathing plus a
chief-only export convention. Here orbax gives async + sharded checkpoints;
the chief-writes convention is enforced by the caller
(``TFNodeContext.export_saved_model``).

Sharded-state contract: save/restore is placement-agnostic — a
ZeRO-partitioned optimizer tree (Adam moments / mixed-precision masters
data-axis sharded per ``LAYOUT_TABLES['optimizer']``) round-trips
byte-identically, with restore committing each array to the TARGET's
sharding (so restoring into a ``shard_state(..., zero_sharding=...)``
target reproduces either knob setting's placement regardless of which
one wrote the checkpoint). Pinned by tests/test_elastic.py's orbax
round-trip of a ZeRO-sharded TrainState.
"""

from __future__ import annotations

import os
from typing import Any

import orbax.checkpoint as ocp

from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.utils.failpoints import FailpointError, failpoint
from tensorflowonspark_tpu.utils.retry import RetryPolicy

# Orbax IO rides shared filesystems (GCS/NFS) whose transient errors are
# routine at pod scale; retry them with backoff rather than failing a
# multi-hour training step. Injected FailpointErrors are retryable here
# so chaos runs can exercise exactly this path.
_IO_RETRY = RetryPolicy(max_attempts=3, base_delay=0.2, max_delay=5.0)
_IO_RETRYABLE = (OSError, ConnectionError, TimeoutError, FailpointError)


def _abs(path: str) -> str:
    if "://" in path:
        return path
    return os.path.abspath(path)


def _canonicalize_leaves(state: Any) -> Any:
    """Version shim (the ``utils/compat.py`` pattern): current orbax's
    StandardSave validator rejects numpy *scalar* leaves (``np.float32``,
    ``np.int64``, ``np.bool_`` — the types a host-side metrics dict or a
    ``jax.device_get`` of a 0-d array naturally produces) while accepting
    0-d ``np.ndarray``s of the same dtype. Canonicalize scalars to 0-d
    arrays at every save boundary; dtype and value round-trip, and orbax
    versions that accepted scalars store the identical array."""
    import jax
    import numpy as np

    return jax.tree.map(
        lambda x: np.asarray(x) if isinstance(x, np.generic) else x, state
    )


def checkpoint_complete(path: str) -> bool:
    """True iff ``path`` holds a COMMITTED orbax checkpoint.

    The serving rollout channel (``serving/rollout.py``) publishes
    checkpoint directories to live engines; a torn or in-progress write
    must never be hot-swapped into a serving fleet. Two signals, both
    required: the directory exists under its FINAL name (orbax writes
    into a ``*.orbax-checkpoint-tmp-*`` directory and renames at
    commit — on posix the final name existing IS the commit), and the
    ``_CHECKPOINT_METADATA`` finalization marker is present (guards
    partially-copied directories, e.g. an interrupted rsync between
    filesystems, where the rename atomicity did not travel).

    Remote URIs (``gs://...`` and friends) cannot be probed with local
    filesystem calls: the tmp-name rejection still applies (orbax's
    rename-at-commit naming travels with the store), but a final-named
    remote path is TRUSTED — the publisher's contract is to publish
    only after the save fully landed (``CheckpointManager.wait()``)."""
    path = _abs(path)
    if "orbax-checkpoint-tmp" in os.path.basename(path.rstrip("/")):
        return False
    if "://" in path:
        return True
    if not os.path.isdir(path):
        return False
    return os.path.exists(os.path.join(path, "_CHECKPOINT_METADATA"))


def save_checkpoint(path: str, state: Any, force: bool = True) -> str:
    """Synchronously write ``state`` (any pytree) to ``path``."""
    path = _abs(path)
    state = _canonicalize_leaves(state)
    with obs_spans.span("train.checkpoint"):
        with ocp.StandardCheckpointer() as ckptr:

            def do_save():
                failpoint("checkpoint.save")
                ckptr.save(path, state, force=force)

            _IO_RETRY.call(
                do_save, retry_on=_IO_RETRYABLE, site="checkpoint.save"
            )
    return path


def restore_checkpoint(path: str, target: Any | None = None) -> Any:
    """Restore a pytree; ``target`` (abstract or concrete) pins structure,
    dtypes, and — when built from abstract arrays with shardings — the
    placement of restored arrays on the mesh."""
    path = _abs(path)
    with ocp.StandardCheckpointer() as ckptr:
        if target is None:
            def do_restore():
                failpoint("checkpoint.restore")
                return ckptr.restore(path)

        else:
            import jax

            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target)

            def do_restore():
                failpoint("checkpoint.restore")
                return ckptr.restore(path, abstract)

        return _IO_RETRY.call(
            do_restore, retry_on=_IO_RETRYABLE, site="checkpoint.restore"
        )


class CheckpointManager:
    """Step-numbered checkpoints with retention + async write.

    The async writer overlaps checkpoint I/O with the next training steps —
    part of the MFU recipe (SURVEY.md §7 "hard parts").
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        async_save: bool = True,
        save_interval_steps: int = 1,
        keep_best_metric: str | None = None,
        keep_best_mode: str = "min",
    ):
        """``save_interval_steps``: calls to :meth:`save` off the interval
        are no-ops returning False (callers can save unconditionally every
        step and let the policy decide). ``keep_best_metric``: retain the
        ``max_to_keep`` checkpoints with the best value of that key in the
        metrics dict passed to :meth:`save` (``keep_best_mode`` 'min' for
        losses, 'max' for accuracies) instead of the most recent ones.
        """
        self.directory = _abs(directory)
        if keep_best_mode not in ("min", "max"):
            raise ValueError("keep_best_mode must be 'min' or 'max'")
        best: dict[str, Any] = {}
        if keep_best_metric is not None:
            best = dict(
                best_fn=lambda metrics: metrics[keep_best_metric],
                best_mode=keep_best_mode,
            )
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
            save_interval_steps=save_interval_steps,
            **best,
        )
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    def save(
        self,
        step: int,
        state: Any,
        metrics: dict[str, Any] | None = None,
        force: bool = False,
    ) -> bool:
        """``force=True`` bypasses the save-interval policy (use for the
        end-of-training save, which must land regardless of interval)."""
        # The span measures the BLOCKING portion only: with async_save
        # the actual I/O overlaps subsequent steps, and the interesting
        # host cost is exactly how long the training loop stalled here.
        state = _canonicalize_leaves(state)
        with obs_spans.span("train.checkpoint", step=step):

            def do_save():
                failpoint("checkpoint.save")
                return self._mgr.save(
                    step,
                    args=ocp.args.StandardSave(state),
                    metrics=metrics,
                    force=force,
                )

            return _IO_RETRY.call(
                do_save, retry_on=_IO_RETRYABLE, site="checkpoint.save"
            )

    def restore(self, step: int | None = None, target: Any | None = None) -> Any:
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        if target is not None:
            import jax

            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target)

            def do_restore():
                failpoint("checkpoint.restore")
                return self._mgr.restore(
                    step, args=ocp.args.StandardRestore(abstract)
                )

        else:

            def do_restore():
                failpoint("checkpoint.restore")
                # A CheckpointManager-written step stores its tree under
                # the composite item name "default"; naming the handler
                # restores it on a manager that has not saved in this
                # process without orbax having to guess one.
                return self._mgr.restore(
                    step, args=ocp.args.StandardRestore()
                )

        return _IO_RETRY.call(
            do_restore, retry_on=_IO_RETRYABLE, site="checkpoint.restore"
        )

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def step_path(self, step: int) -> str:
        """Directory of one saved step (the unit the rollout channel
        publishes: ``serving.rollout.publish_checkpoint(path=
        mgr.step_path(step), ...)`` after :meth:`wait`)."""
        return os.path.join(self.directory, str(int(step)))

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.wait()
        self.close()


def restore_latest(ckpt: CheckpointManager, target: Any):
    """Resume convention: restore the newest checkpoint into ``target``'s
    structure. Returns ``(step, restored)``, or ``(None, target)`` when
    the directory has no checkpoints. A structure mismatch (e.g. a
    directory written by different code) fails with a clear error
    instead of an orbax tree-diff traceback."""
    step = ckpt.latest_step()
    if step is None:
        return None, target
    try:
        return step, ckpt.restore(step, target=target)
    except Exception as e:
        # Only claim "wrong trainer" when the stored tree's top-level
        # keys genuinely differ from the target's; any other failure
        # (IO, partial step dir, truncated arrays) propagates unchanged
        # so operators retry instead of deleting good checkpoints.
        stored_keys = _stored_top_level_keys(ckpt, step)
        if (
            isinstance(target, dict)
            and stored_keys is not None
            and stored_keys != set(target)
        ):
            raise ValueError(
                f"checkpoint step {step} in {ckpt.directory} has keys "
                f"{sorted(stored_keys)} but this trainer expects "
                f"{sorted(target)}; it was written by a different trainer "
                "— delete the directory or point the model dir elsewhere"
            ) from e
        raise


def _stored_top_level_keys(ckpt: CheckpointManager, step: int):
    """Top-level keys of a stored checkpoint's tree, or None if the
    metadata cannot be read (caller treats that as 'unknown')."""
    try:
        meta = ckpt._mgr.item_metadata(step)
        tree = getattr(meta, "tree", meta)
        return set(tree) if isinstance(tree, dict) else None
    except Exception:
        return None


def hydration_restore(directory: str, target: Any):
    """Elastic-rejoin fallback: restore the newest checkpoint under
    ``directory`` into ``target``'s structure. Returns ``(step,
    state)`` or ``(None, None)`` when the directory holds no
    checkpoints (including a directory that does not exist yet — a
    joiner probing an optional fallback must not crash on it).

    This is the "checkpoint restore is the fallback, not the recovery
    path" half of the elastic contract (compute/elastic.py): peers'
    in-memory state is tried first; only when that is impossible does
    the joiner pay a full checkpoint read.
    """
    with CheckpointManager(directory) as ckpt:
        step, state = restore_latest(ckpt, target)
        if step is None:
            return None, None
        return step, state


def saves_on_this_process(is_chief: bool) -> bool:
    """Which processes must call ``save`` (and ``wait``):

    - **Single-controller** (``jax.process_count() == 1`` — e.g. the local
      launcher, where every node is an independent JAX runtime holding a
      full replica): chief only. Concurrent saves of the same fully-
      addressable state to one orbax directory would race.
    - **Multi-controller** (``jax.distributed`` initialized,
      ``process_count > 1``): EVERY process. State is jax.Arrays sharded
      across processes; orbax save/restore of non-fully-addressable
      arrays is a collective — each process writes its addressable
      shards and process 0 coordinates the commit. A chief-only save
      there raises or hangs.

    Gate *logging* on ``is_chief``; gate *saving* on this.
    """
    import jax

    return is_chief or jax.process_count() > 1


def _final_save_needed(ckpt: CheckpointManager, step: int) -> bool:
    """Collectively consistent "does the final save still need to run".

    Under multi-controller, the save of cross-process-sharded arrays is a
    collective — every process must enter it or none. A per-process
    ``latest_step() != step`` check can disagree across processes on
    eventually-consistent shared filesystems (GCS/NFS): some would enter
    the collective save and others skip, deadlocking the job. Process 0's
    view is authoritative (orbax's commit is coordinated by process 0, so
    if process 0 sees the step landed, every process participated in that
    save) and is broadcast to all."""
    import jax

    needed = ckpt.latest_step() != step
    if jax.process_count() > 1:
        import numpy as np
        from jax.experimental import multihost_utils

        needed = bool(
            multihost_utils.broadcast_one_to_all(
                np.asarray(needed, dtype=np.int32)
            )
        )
    return needed


def chief_final_save(
    ckpt: CheckpointManager, state: Any, step: int, is_chief: bool
) -> None:
    """End-of-training save convention: forced past any save-interval
    policy, and skipped when a previous attempt (e.g. a
    ``run_with_restarts`` relaunch or an in-loop interval save) already
    landed this step (``force=True`` also makes a redundant save on a
    stale-FS miss an overwrite, not an error).

    "chief" in the name is the single-controller convention; under
    multi-controller (``jax.process_count() > 1``) the save runs on
    every process because sharded-state checkpointing is a collective
    (see :func:`saves_on_this_process`), and the skip decision is made
    collectively (see :func:`_final_save_needed`) so no process enters
    the collective alone. Every process closes the manager."""
    if saves_on_this_process(is_chief):
        ckpt.wait()  # async in-loop saves may still be landing
        if _final_save_needed(ckpt, step):
            ckpt.save(step, state, force=True)
    ckpt.close()
