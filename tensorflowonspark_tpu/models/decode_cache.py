"""The decode cache tree, as the models and the serving engine agree on
it: a flax ``cache`` collection whose leaves are told apart by name.

- K/V leaves (``k``, ``v`` and, under ``kv_cache_dtype="int8"``,
  ``k_scale``, ``v_scale``): one entry a position, (rows, positions, ...),
  or a position's heads as rows, (rows, positions x heads, d), under
  ``llama.Attention``'s ``kv_heads_in_rows``.
- Latent leaves (``latent``): one entry a position like K/V, (rows,
  positions, width), but no heads: the compressed key-value of latent
  attention and the rotary key every head shares, which a decode step
  reads as both K and V (``models/pangu_moe.py``; the width is
  ``ops.decode_attention.latent_entry_width``). Masked by position when
  read, as K/V are, so padding and an overlap may be written.
- Recurrent leaves (``ssm``, ``kda``, ``conv``): one entry a request,
  (rows, ...), valid at exactly one position: the state after the last
  token the row has consumed. They cannot be resumed from an earlier
  position, nor masked after the fact: a token that must not count must
  not be applied. ``ssm`` is the Mamba-2 state (rows, h, p, N) of
  ``models/falcon_h1.py`` or the Mamba-1 state (rows, N, c) of
  ``models/phi4_flash.py``, ``kda`` the delta-rule state (rows, h, d_k,
  d_v) of ``models/solar_open2.py``, ``conv`` any of these models' last
  ``k - 1`` inputs of a depthwise convolution, (rows, k - 1, c).
- Counters (``moe_counts``): what the decode steps have routed, summed
  on the device since the cache was made and wrapping as int32 does. No
  row: the batch's leaf is the engine's, an admitted row brings none.
  ``moe_counts`` makes a step's entries and ``moe_count_entries`` says
  which registry counters each feeds; the engine adds the layers' leaves
  up and knows neither.
- Everything else (``seg``, ``pos``, the scalar ``idx``): bookkeeping.

Every leaf but the scalar write index and the counters has the row first,
which is all the engine's admission scatter and donation need to know of
a leaf. A layer need not hold every kind: a model whose layers differ
(``models/solar_open2.py``) has K/V in some and recurrent leaves in the
others, and the engine asks of the tree, never of a layer. Nor need the
K/V layers agree on a length: a windowed layer may keep a ring of its
window beside a dense plane of ``max_seq_len`` (``models/phi4_flash.py``),
and the engine counts each at its own (:func:`kv_layers`).
"""

from __future__ import annotations

from collections.abc import Mapping

import jax
import jax.numpy as jnp

KV_LEAVES = ("k", "v", "k_scale", "v_scale")
LATENT_LEAVES = ("latent",)
RECURRENT_LEAVES = ("ssm", "kda", "conv")
COUNTER_LEAVES = ("moe_counts",)


def moe_counts(group_sizes, pairs: int):
    """One step's addition to a layer's ``moe_counts`` leaf: the pairs
    routed to each held expert (``group_sizes``, int32), the ``pairs``
    routed in all, and how many held experts got one."""
    return jnp.concatenate([
        group_sizes,
        jnp.asarray([pairs], jnp.int32),
        jnp.sum(group_sizes > 0, dtype=jnp.int32)[None],
    ])


def moe_count_entries(first_held: int, held: int) -> tuple:
    """Entry by entry of ``moe_counts``'s result, the ``(counter, labels)``
    pairs it adds to, by the names the engine's registry gives them; a
    held expert is labelled with its index in the router's range."""
    local = ("engine_moe_local_assignments_total", {})
    return (
        *(
            (("engine_moe_expert_tokens_total", {"expert": str(first_held + e)}), local)
            for e in range(held)
        ),
        (("engine_moe_assignments_total", {}),),
        (("engine_moe_experts_reached_total", {}),),
    )


def starts_sequence(module, leaf: str) -> bool:
    """Whether a ``decode=True`` call of an attention ``module`` was
    handed no cache, asked of its per-position ``leaf`` BEFORE the call
    makes its variables. Such a call starts its sequence: it writes the
    cache for the calls that follow and attends among its own positions
    (``dot_product_attention`` over the K/V in hand); a call that was
    handed one reads the cache. The one rule of ``llama.Attention``
    (Falcon-H1's too) and ``pangu_moe.LatentAttention``."""
    return not module.has_variable("cache", leaf)


def keys_scored(
    width: int, cache_len: int, handed_cache: bool, window: int | None = None
) -> int:
    """The keys each query of a ``width``-position prefill call is
    scored against, as :func:`starts_sequence` decides it in the models:
    the call's own ``width`` where it creates its cache (at most the
    layer's ``window``: the flash kernel skips the tiles outside it),
    every one of the ``cache_len`` slots where it was handed one (a
    chunk, a prefix resume: the einsum over the cache). What the engine
    counts per dispatched prefill program and K/V layer, so that the
    counter follows the models' rule and not a copy of it."""
    if handed_cache:
        return cache_len
    return width if window is None else min(width, window)


def kv_layers(tree) -> list[tuple[int, bool]]:
    """For each layer of a cache tree that holds one entry a position
    (a ``k`` or ``latent`` leaf): its positions, and whether it records
    them (a ``pos`` leaf beside it: the layer reads under a window). What
    the engine counts each layer's reads by, whatever lengths the layers'
    planes have (``models/phi4_flash.py``: rings beside a dense plane)."""
    out = []
    for key, node in tree.items():
        if not isinstance(node, Mapping):
            continue
        plane = node.get("k", node.get("latent"))
        if plane is not None and not isinstance(plane, Mapping):
            # the id plane has one entry a position however K/V lie
            out.append((node.get("seg", plane).shape[1], "pos" in node))
        else:
            out += kv_layers(node)
    return out


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def leaf_kind(path) -> str:
    """``"kv"``, ``"latent"``, ``"recurrent"``, ``"counter"`` or
    ``"other"`` for a cache leaf's tree path (as
    ``jax.tree_util.tree_map_with_path`` hands it over)."""
    name = _leaf_name(path)
    for kind, names in (
        ("kv", KV_LEAVES), ("latent", LATENT_LEAVES),
        ("recurrent", RECURRENT_LEAVES), ("counter", COUNTER_LEAVES),
    ):
        if name in names:
            return kind
    return "other"


def init_cache(shapes):
    """Fresh cache values for a tree of ShapeDtypeStructs (the serving
    engine builds per-row caches from ``jax.eval_shape`` rather than a
    real ``model.init`` — an init-valued apply would also WRITE its
    dummy token into the cache). This is the single source of truth for
    cache-leaf init values outside flax: everything zero-fills (the
    latent plane, the recurrent state, the convolution window and the
    counters too) EXCEPT the position
    plane, which is -1 ("never written") so a rolling cache cannot
    mistake a stale slot for a valid position 0. Keep in lockstep with
    the ``self.variable`` inits in ``llama.Attention._cached_attention``,
    ``falcon_h1.Mixer``, ``pangu_moe``'s ``LatentAttention`` / ``Block``,
    ``solar_open2``'s ``DeltaMixer`` / ``Block`` and ``phi4_flash.Mamba``.
    """

    def init(path, s):
        if _leaf_name(path) == "pos":
            return jnp.full(s.shape, -1, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(init, shapes)
