"""The one general traffic generator; a mix is a data file it reads.

``perfbench/traffic/<name>.json`` holds parameters only: kind, length
distributions, pool size, clients. Two kinds:

- ``documents`` (training): a pool of documents whose lengths are drawn
  ONCE from the file's own ``pool_seed`` and grouped into rows that fit
  ``seq_len + 1`` tokens. ``--seed`` decides the order of the rows, the
  order of the documents in a row and every token, never the lengths: all
  seeds do the same work in another order.
- ``requests`` (serving): a pool of (prompt length, output length) pairs,
  drawn once likewise; ``--seed`` decides their order (a shuffle, or where
  the pool's own order starts) and their tokens. The pool is cycled, so a
  window that needs more than the pool sees the same sizes again.
"""

from __future__ import annotations

import numpy as np


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"], size=n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec.get("min", 1), spec.get("max", None)).astype(np.int64)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


# -- documents ----------------------------------------------------------------


def document_rows(spec: dict) -> list[list[int]]:
    """``pool_rows`` rows of document lengths, each filling between
    ``min_fill`` and all of ``seq_len + 1`` tokens: first fit in arrival
    order, a row closed when the next document does not fit."""
    rng = rng_for(spec["pool_seed"], 0)
    cap = spec["seq_len"] + 1
    rows: list[list[int]] = []
    cur: list[int] = []
    while len(rows) < spec["pool_rows"]:
        n = int(min(draw_lengths(spec["length"], 1, rng)[0], cap))
        if sum(cur) + n > cap:
            if sum(cur) >= spec.get("min_fill", 0.6) * cap:
                rows.append(cur)
            cur = []
        cur.append(n)
    return rows


def document_batches(spec: dict, seed: int, rows_per_step: int, vocab: int):
    """Endless: per step, the documents (lists of token ids) that pack into
    ``rows_per_step`` rows. Yields ``(docs, lengths)``."""
    rows = document_rows(spec)
    cycle = 0
    while True:
        rng = rng_for(seed, 1, cycle)
        order = rng.permutation(len(rows))
        for i in range(0, len(order) - rows_per_step + 1, rows_per_step):
            lengths: list[int] = []
            for r in order[i:i + rows_per_step]:
                lengths += [rows[r][j] for j in rng.permutation(len(rows[r]))]
            tokens = rng.integers(0, vocab, size=sum(lengths), dtype=np.int32)
            cuts = np.cumsum(lengths)[:-1]
            yield [d.tolist() for d in np.split(tokens, cuts)], lengths
        cycle += 1


# -- requests -----------------------------------------------------------------


def request_sizes(spec: dict) -> list[tuple[int, int]]:
    rng = rng_for(spec["pool_seed"], 0)
    p = draw_lengths(spec["prompt"], spec["pool_size"], rng)
    o = draw_lengths(spec["output"], spec["pool_size"], rng)
    return list(zip(p.tolist(), o.tolist()))


def requests(spec: dict, seed: int, vocab: int):
    """Endless: ``(prompt token ids, output length)``, the pool again and
    again. ``order`` ``permute`` shuffles every cycle by the seed;
    ``rotate`` keeps the pool's own order and lets the seed choose where it
    starts, so that every seed sees the same neighbours."""
    sizes = request_sizes(spec)
    rng = rng_for(seed, 2)
    start = int(rng.integers(len(sizes)))
    while True:
        if spec.get("order", "permute") == "rotate":
            order = (start + np.arange(len(sizes))) % len(sizes)
        else:
            order = rng.permutation(len(sizes))
        for i in order:
            p, o = sizes[i]
            yield rng.integers(0, vocab, size=p, dtype=np.int32).tolist(), o
