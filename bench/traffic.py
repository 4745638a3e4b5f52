"""The one traffic generator: a mix file's parameters -> a request schedule.

A mix (``bench/traffic/<name>.json``) gives arrivals and lengths as
numbers; :func:`schedule` turns them into :class:`Item` s, each with the
time it is due (seconds after the schedule's origin), its source
length, its output budget, its target language and its token ids, from
the mix and the run's ``--seed`` alone.

Every seed serves the same work in another order. The mix's
``pool_seed`` draws one pool of requests (source lengths, output
budgets and, for Poisson arrivals, the gaps between them); the run
seed draws the order, epoch after epoch, each epoch a permutation of
the pool, and the token ids. No two neighbours in that order have the
same source length, so each request prefills as its own group (the
program groups only neighbours of one exact source length) and the
prefill shapes a run can reach are the pool's lengths, one group each:
:func:`src_lengths`.

Arrival kinds:

* ``poisson``: open loop at ``rate_per_s``. The pool holds the
  ``rate_per_s`` x ``--seconds`` requests due in one window, their
  exponential gaps scaled to fill it exactly. The warm-up (``warm_s``)
  is the tail of one epoch, the window one whole epoch, and further
  epochs keep the load on while the window's requests finish (up to
  ``drain_s``).
* ``backlog``: a corpus of ``pool_size`` sentences, all due at once and
  sent in epochs without end; the load generator keeps the program's
  queue at least ``queued_min`` deep (``harness.open_loop``), so the
  backlog never runs dry whatever the throughput.

Source lengths are log-normal (``median``, ``sigma``), rounded and
clipped to ``[min, max]``, and sent as drawn. The output budget is that
length times U(``ratio_low``, ``ratio_high``), rounded and clipped to
``[min, max]``.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
from typing import Iterator, List

import numpy as np

__all__ = ["Item", "load_mix", "schedule", "src_lengths", "pool_size"]

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Item:
    due_s: float
    src_len: int          # source tokens sent
    new_tokens: int       # output budget (max_new_tokens)
    lang: int             # target-language code token
    src: np.ndarray       # (src_len,) int32 token ids
    in_window: bool = False   # due inside the measured window


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def pool_size(mix: dict, seconds: float) -> int:
    arr = mix["arrival"]
    if arr["kind"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    if arr["kind"] == "backlog":
        return int(arr["pool_size"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _pool(mix: dict, n: int, seconds: float):
    """The seed-independent pool of n requests: source lengths, output
    budgets and gaps (summing to ``seconds``)."""
    rng = np.random.default_rng(int(mix["pool_seed"]))
    ln, out = mix["src_len"], mix["out_len"]
    drawn = np.exp(rng.normal(math.log(ln["median"]), ln["sigma"], n))
    src = np.clip(np.round(drawn), ln["min"], ln["max"]).astype(np.int64)
    ratio = rng.uniform(out["ratio_low"], out["ratio_high"], n)
    new = np.clip(np.round(src * ratio), out["min"], out["max"])
    gaps = rng.exponential(1.0, n)
    return src, new.astype(np.int64), gaps * (seconds / gaps.sum())


def src_lengths(mix: dict, seconds: float) -> List[int]:
    """Every source length a run of ``seconds`` can send, ascending."""
    src, _, _ = _pool(mix, pool_size(mix, seconds), seconds)
    return sorted(set(int(s) for s in src))


def _epoch(rng, lens, prev: int) -> np.ndarray:
    """A permutation of the pool from ``rng`` in which no two
    neighbours, nor the first and ``prev`` (the length sent before),
    share a length: each clash is swapped with the next position that
    removes it, and what the tail cannot place goes where it fits."""
    order = list(rng.permutation(len(lens)))
    i = 0
    while i < len(order):
        left = lens[order[i - 1]] if i else prev
        if lens[order[i]] == left:
            j = next((j for j in range(i + 1, len(order))
                      if lens[order[j]] != left), None)
            if j is None:
                break
            order[i], order[j] = order[j], order[i]
        i += 1
    stuck = order[i:]
    order = order[:i]
    for k in stuck:
        for at in range(1, len(order) + 1):
            right = lens[order[at]] if at < len(order) else None
            if lens[order[at - 1]] != lens[k] and right != lens[k]:
                order.insert(at, k)
                break
        else:
            raise ValueError("the pool has too many requests of one "
                             "source length to keep them apart")
    return np.asarray(order)


def _epochs(rng, lens) -> Iterator[np.ndarray]:
    prev = -1
    while True:
        e = _epoch(rng, lens, prev)
        prev = lens[e[-1]]
        yield e


def _item(mix, rng, src, new, i, due, win) -> Item:
    vocab, langs = mix["vocab"], mix["langs"]
    s = int(src[i])
    return Item(due_s=float(due), src_len=s, new_tokens=int(new[i]),
                lang=int(rng.integers(langs["low"], langs["high"])),
                src=rng.integers(vocab["low"], vocab["high"], s,
                                 dtype=np.int32),
                in_window=win)


def schedule(mix: dict, seed: int, seconds: float):
    """The run's requests in due order (see the module docstring): a
    list for Poisson arrivals, an endless iterator for a backlog."""
    rng = np.random.default_rng(seed)
    n = pool_size(mix, seconds)
    src, new, gaps = _pool(mix, n, seconds)
    epochs = _epochs(rng, src)
    if mix["arrival"]["kind"] == "backlog":
        return (_item(mix, rng, src, new, i, 0.0, False)
                for e in epochs for i in e)
    warm = float(mix["warm_s"])
    count = 2 + int(math.ceil(mix["drain_s"] / seconds))
    perms = list(itertools.islice(epochs, count))
    # the last part of epoch 0 that fits in the warm-up, then whole
    # epochs: the window is epoch 1, starting at time `warm`
    idx, due = [], []
    t = warm
    for i in perms[0][::-1]:
        t -= gaps[i]
        if t < 0:
            break
        idx.append(i)
        due.append(t)
    idx.reverse()
    due.reverse()
    win = [False] * len(idx)
    t = warm
    for e, p in enumerate(perms[1:]):
        for i in p:
            idx.append(i)
            due.append(t)
            win.append(e == 0)
            t += gaps[i]
    return [_item(mix, rng, src, new, i, d, w)
            for i, d, w in zip(idx, due, win)]
