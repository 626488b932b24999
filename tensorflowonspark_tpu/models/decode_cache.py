"""The decode cache tree, as the models and the serving engine agree on
it: a flax ``cache`` collection whose leaves are told apart by name.

- K/V leaves (``k``, ``v`` and, under ``kv_cache_dtype="int8"``,
  ``k_scale``, ``v_scale``): one entry a position, (rows, positions, ...).
- Recurrent leaves (``ssm``, ``conv``): one entry a request, (rows, ...),
  valid at exactly one position: the state after the last token the row
  has consumed. They cannot be resumed from an earlier position, nor
  masked after the fact: a token that must not count must not be applied.
- Everything else (``seg``, ``pos``, the scalar ``idx``): bookkeeping.

Every leaf but the scalar write index has the row first, which is all the
engine's admission scatter and donation need to know of a leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KV_LEAVES = ("k", "v", "k_scale", "v_scale")
RECURRENT_LEAVES = ("ssm", "conv")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def leaf_kind(path) -> str:
    """``"kv"``, ``"recurrent"`` or ``"other"`` for a cache leaf's tree
    path (as ``jax.tree_util.tree_map_with_path`` hands it over)."""
    name = _leaf_name(path)
    if name in KV_LEAVES:
        return "kv"
    return "recurrent" if name in RECURRENT_LEAVES else "other"


def init_cache(shapes):
    """Fresh cache values for a tree of ShapeDtypeStructs (the serving
    engine builds per-row caches from ``jax.eval_shape`` rather than a
    real ``model.init`` — an init-valued apply would also WRITE its
    dummy token into the cache). This is the single source of truth for
    cache-leaf init values outside flax: everything zero-fills (the
    recurrent state and the convolution window too) EXCEPT the position
    plane, which is -1 ("never written") so a rolling cache cannot
    mistake a stale slot for a valid position 0. Keep in lockstep with
    the ``self.variable`` inits in ``llama.Attention._cached_attention``
    and ``falcon_h1.Mixer``.
    """

    def init(path, s):
        if _leaf_name(path) == "pos":
            return jnp.full(s.shape, -1, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(init, shapes)
