"""Non-IID partitioners — Appendix C / Eqs. 8-10.

Each partitioner maximizes ONE statistic's cross-client standard deviation
while pinning the others (the paper's "maximise a single metric discrepancy
... keeping other metrics almost the same"):

  * ``iid``        — shuffled equal split (all sigmas ~ 0).
  * ``quantity``   — Eq. 8: client i gets i / sum(j) of the documents;
                     assignment is random, so length/vocab stay flat.
  * ``length``     — Eq. 9: equal counts; documents sorted by mean sentence
                     length and split contiguously -> max sigma(L).
  * ``vocab``      — Eq. 10: equal counts; documents sorted by their lexicon
                     offset (vocabulary-pool position) and split contiguously
                     -> client union-vocabulary sizes diverge while lengths
                     stay flat (pool windows are length-independent).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from repro.data.corpus import Document, corpus_stats

SKEWS = ("iid", "quantity", "length", "vocab")


def quantity_split_sizes(n_docs: int, k: int) -> List[int]:
    """Eq. 8: Q_i = i / sum_j(j) * Q — client i+1's DOCUMENT count out of
    ``n_docs`` (largest-remainder rounding; conserves the total).  The
    resulting per-client step counts are what the async simulator replays
    as the quantity-skew schedule.

    >>> quantity_split_sizes(100, 4)
    [10, 20, 30, 40]
    >>> sum(quantity_split_sizes(101, 4))
    101
    """
    denom = k * (k + 1) // 2
    raw = [(i + 1) / denom * n_docs for i in range(k)]
    sizes = [int(x) for x in raw]
    rem = n_docs - sum(sizes)
    fracs = sorted(range(k), key=lambda i: raw[i] - sizes[i], reverse=True)
    for i in fracs[:rem]:
        sizes[i] += 1
    return sizes


def _doc_vocab_key(d: Document) -> float:
    """Surrogate for the doc's lexicon-window position: lexicographically
    smallest word — contiguous-sorted split clusters shared pools."""
    return min(min(s) for s in d.sentences)


def partition(docs: Sequence[Document], k: int, skew: str = "iid",
              *, seed: int = 0) -> List[List[Document]]:
    """Partition docs into k client shards per the requested skew."""
    rng = np.random.default_rng(seed)
    docs = list(docs)
    n = len(docs)
    order = rng.permutation(n)

    if skew == "iid":
        shards = [[] for _ in range(k)]
        for pos, di in enumerate(order):
            shards[pos % k].append(docs[di])
        return shards

    if skew == "quantity":
        sizes = quantity_split_sizes(n, k)
        shards, at = [], 0
        for s in sizes:
            shards.append([docs[i] for i in order[at:at + s]])
            at += s
        return shards

    if skew == "length":
        idx = sorted(range(n), key=lambda i: docs[i].mean_sentence_length)
        per = n // k
        shards = [[docs[i] for i in idx[c * per:(c + 1) * per]] for c in range(k)]
        for j, i in enumerate(idx[k * per:]):    # spread the remainder
            shards[j % k].append(docs[i])
        return shards

    if skew == "vocab":
        # maximize sigma of per-client vocabulary-union size at equal counts:
        # "narrow" clients take contiguous runs of vocab-sorted docs (shared
        # pools -> small union); "wide" clients stride across the remainder
        # (disjoint pools -> large union).  Length stays pinned because the
        # vocab key is independent of sentence length.
        idx = sorted(range(n), key=lambda i: _doc_vocab_key(docs[i]))
        per = n // k
        n_narrow = (k + 1) // 2
        shards: List[List[Document]] = []
        at = 0
        for _ in range(n_narrow):
            shards.append([docs[i] for i in idx[at:at + per]])
            at += per
        rest = idx[at:]
        n_wide = k - n_narrow
        for c in range(n_wide):
            shards.append([docs[rest[j]] for j in range(c, n_wide * per, n_wide)])
        for j, i in enumerate(rest[n_wide * per:]):
            shards[j % k].append(docs[i])
        return shards

    raise ValueError(f"unknown skew {skew!r}; have {SKEWS}")


class ClientPool:
    """Virtual population of ``n_clients`` federated clients backed by a
    small pool of real data shards — the lazy client-data provider the
    round engines consume (``batches_for`` / ``sizes`` / ``max_steps`` /
    ``n_built`` / ``__len__``).

    Cross-device populations are sampled, not enumerated: a 100k–1M-client
    round touches only its cohort, so materializing every client's batches
    up front is both impossible (memory) and pointless.  Virtual client
    ``k`` serves pool shard ``k % P``; a pool shard's batches build on
    FIRST access (``builders[i]`` is a zero-arg callable) and are cached,
    so a run materializes at most ``P`` datasets no matter how many
    clients exist or participate.

    >>> pool = ClientPool(6, [lambda: ["a", "b"], lambda: ["c"]], sizes=[2, 1])
    >>> len(pool), pool.batches_for(3)
    (6, ['c'])
    >>> pool.sizes
    [2, 1, 2, 1, 2, 1]
    >>> pool.materialized        # only shard 1 was ever built
    [1]
    """

    def __init__(self, n_clients: int, builders: Sequence, sizes: Sequence[int],
                 *, limit: int = 0):
        if len(builders) != len(sizes):
            raise ValueError(f"{len(builders)} builders vs {len(sizes)} sizes")
        if n_clients < 1 or not builders:
            raise ValueError("need n_clients >= 1 and a non-empty pool")
        self._n = int(n_clients)
        self._builders = list(builders)
        self._pool_sizes = [int(s) for s in sizes]
        self._limit = int(limit)              # >0: cap local steps per epoch
        self._cache: dict = {}

    def __len__(self) -> int:
        return self._n

    @property
    def sizes(self) -> List[int]:
        """Virtual n_k aggregation weights: the pool sizes, cycled."""
        p = len(self._builders)
        return [self._pool_sizes[k % p] for k in range(self._n)]

    @property
    def max_steps(self) -> int:
        """Longest local epoch across the pool (materializes the pool — at
        most P builds, cached; never per virtual client)."""
        return max(len(self._shard(i)) for i in range(len(self._builders)))

    @property
    def materialized(self) -> List[int]:
        """Pool shard indices built so far (laziness observability)."""
        return sorted(self._cache)

    @property
    def n_built(self) -> int:
        """How many pool shards have been built so far."""
        return len(self._cache)

    def _shard(self, i: int):
        if i not in self._cache:
            built = self._builders[i]()
            self._cache[i] = built[:self._limit] if self._limit else built
        return self._cache[i]

    def batches_for(self, k: int):
        return self._shard(k % len(self._builders))


def client_stats_table(shards: Sequence[Sequence[Document]]) -> dict:
    """Table-3 analogue: mean and sigma of (quantity, sentence length,
    union vocabulary, per-doc vocabulary) across clients.  The per-doc
    metric is quantity-invariant (the paper's near-zero vocab sigma under
    quantity skew); the union metric is what Eq. 10 maximizes."""
    per = [corpus_stats(s) for s in shards]
    for p, s in zip(per, shards):
        p["doc_vocab"] = float(np.mean([len(d.unique_words) for d in s])) \
            if s else 0.0
    out = {}
    for key in ("quantity", "mean_sentence_length", "unique_words", "doc_vocab"):
        vals = np.asarray([p[key] for p in per], np.float64)
        out[key] = {"mean": float(vals.mean()), "sigma": float(vals.std()),
                    "per_client": vals.tolist()}
    return out
