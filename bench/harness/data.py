"""Client batches for a federated pre-training cell, made from the seed.

One general generator for every training traffic mix.  A mix names the job
(clients, local steps, batch, sequence) and the document statistics; the
generator packs synthetic documents into each client's stream and cuts it
into the model's batches.  It follows the semantics of the program's own
synthetic corpus and batching (``repro.data.corpus`` and
``repro.data.batching``), vectorized and kept here so that no change to the
program can change the benchmark's inputs:

* a document is BOS, a body and EOS; body lengths are log-normal (heavy
  tailed), and each document draws ids from its own window of the
  vocabulary: a Zipf-weighted start and a random walk of steps in [-2, 2],
  so neighbouring tokens are correlated;
* documents are packed back to back into one stream per client, so a
  sequence crosses document boundaries (the program has no segment ids);
* ``mlm``: BERT masking, 15% of positions chosen, of which 80% become MASK,
  10% a random id and 10% stay; the loss reads the chosen positions only;
* ``clm``: the targets are the next token and the loss reads every position.

Every seed gives the same shapes and the same number of loss positions in
expectation; only the ids differ.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

PAD, UNK, MASK, BOS, EOS = 0, 1, 2, 3, 4
N_SPECIALS = 5


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _document(rng: np.random.Generator, docs: Dict, vocab: int) -> np.ndarray:
    n = int(np.clip(rng.lognormal(np.log(docs["length_median"]),
                                  docs["length_sigma"]),
                    docs["length_min"], docs["length_max"]))
    pool = int(rng.integers(docs["pool_min"], docs["pool_max"]))
    span = vocab - N_SPECIALS
    off = int(rng.integers(0, max(1, span - pool)))
    ranks = np.arange(1, pool + 1)
    pz = (1.0 / ranks) / np.sum(1.0 / ranks)
    start = int(rng.choice(pool, p=pz))
    walk = (start + np.cumsum(rng.integers(-2, 3, size=n))) % pool
    body = N_SPECIALS + (off + walk) % span
    return np.concatenate([[BOS], body, [EOS]]).astype(np.int32)


def _stream(rng: np.random.Generator, docs: Dict, vocab: int,
            n_tokens: int) -> np.ndarray:
    parts, have = [], 0
    while have < n_tokens:
        d = _document(rng, docs, vocab)
        parts.append(d)
        have += len(d)
    return np.concatenate(parts)[:n_tokens]


def _mlm(rng: np.random.Generator, ids: np.ndarray, vocab: int,
         rate: float) -> Dict[str, np.ndarray]:
    sel = rng.random(ids.shape) < rate
    r = rng.random(ids.shape)
    inputs = ids.copy()
    inputs[sel & (r < 0.8)] = MASK
    swap = sel & (r >= 0.8) & (r < 0.9)
    inputs[swap] = rng.integers(N_SPECIALS, vocab, size=ids.shape)[swap]
    return {"tokens": inputs, "targets": ids,
            "loss_mask": sel.astype(np.float32)}


def client_batches(traffic: Dict, model: Dict, seed: int
                   ) -> List[List[Dict[str, np.ndarray]]]:
    """``[client][step] -> {"tokens", "targets", "loss_mask"}``, each
    ``(batch, seq)``: the layout ``repro.core.noniid`` gives the engines."""
    if traffic.get("skew", "iid") != "iid":
        raise ValueError(f"unsupported skew {traffic['skew']!r}")
    k, steps = traffic["clients"], traffic["local_steps"]
    b, s = traffic["batch"], traffic["seq"]
    vocab, objective = model["vocab_size"], model["objective"]
    width = s + 1 if objective == "clm" else s
    out = []
    for c in range(k):
        rng = _rng(seed, 1, c)
        ids = _stream(rng, traffic["documents"], vocab,
                      steps * b * width).reshape(steps, b, width)
        mrng = _rng(seed, 2, c)
        batches = []
        for step in ids:
            if objective == "mlm":
                batches.append(_mlm(mrng, step, vocab,
                                    model["mlm_mask_rate"]))
            elif objective == "clm":
                batches.append({"tokens": step[:, :-1].copy(),
                                "targets": step[:, 1:].copy(),
                                "loss_mask": np.ones((b, s), np.float32)})
            else:
                raise ValueError(f"unsupported objective {objective!r}")
        out.append(batches)
    return out
