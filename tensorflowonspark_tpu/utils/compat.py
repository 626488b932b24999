"""Version shims (reference parity: ``tensorflowonspark/compat.py``).

The reference papered over TF 2.0/2.1 API drift (``export_saved_model``,
``disable_auto_shard``, ``is_gpu_available``). The rebuild's equivalents:

- ``export_saved_model`` → orbax checkpoint export (the SavedModel analog)
- ``disable_auto_shard`` → a no-op by construction: the queue feed already
  delivers distinct per-host data, and jit+NamedSharding splits the global
  batch by sharding, so there is no competing auto-shard machinery to turn
  off. Kept callable so reference-shaped user code ports unchanged.
- ``is_gpu_available`` → accelerator probe.

This module is also the ONE sanctioned import site for jax symbols that
have moved between releases (``tools/tfoslint.py`` rule JX002,
``pyproject.toml`` ``moved_jax_symbols``): call sites import
``shard_map`` and ``axis_size`` from here, never from jax directly, so
the next move is a one-file change. The code targets the one jax that
is installed; there are no branches for others.
"""

from __future__ import annotations

from tensorflowonspark_tpu.utils.device_info import (  # noqa: F401
    is_gpu_available,
    is_tpu_available,
)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` (keyword signature, replication checking under
    ``check_vma``)."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis, inside a ``shard_map``/vmapped
    body."""
    import jax

    return jax.lax.axis_size(axis_name)


def export_saved_model(state, export_dir: str, **kwargs) -> str:
    from tensorflowonspark_tpu.compute.checkpoint import save_checkpoint

    return save_checkpoint(export_dir, state, **kwargs)


def disable_auto_shard(options=None) -> None:
    """No-op (see module docstring); accepts and ignores tf.data options."""
    return None
