"""The data kind ``packed_token_documents``: rows of packed token documents,
from the seed (what a data kind provides: ``data/class_mean_images.py``).

A sample is one row of ``S = cfg["input_shape"][0]`` tokens.  Documents have
a heavy-tailed length (lognormal, median ``doc_median`` tokens, ``doc_sigma``,
clipped to ``S - 1``) and their ids follow Zipf(``zipf``) over their topic's
permutation of the vocabulary slice ``[1, cfg["vocab_size"])``; a client
draws each document's topic from its own mixture, Dirichlet(``alpha``) over
``topics``, so clients are skewed by topic.  A document is written as the
token 0 (the boundary mark the program reads: a document starts there,
positions restart and attention does not cross it) and then its ids; rows
are filled in order and a document that does not fit the rest of its row is
cut there, so every row starts a document.  ``y`` holds each position's next
token, ``-1`` where that is another document's (or past the row).

Every seed gives arrays of the same shapes: ``train_rows`` + ``test_rows``
rows a client, all real (``lengths`` is constant); only the documents, their
topics and their lengths change.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BOS = 0


def pack(next_doc, seq_len: int, rows: int):
    """``(x, y)`` ``(rows, seq_len)`` int32 from the documents ``next_doc()``
    draws (1-D id arrays without their start token), as the module
    docstring says, until ``rows`` rows are full."""
    x = np.full((rows, seq_len), BOS, np.int32)
    y = np.full((rows, seq_len), -1, np.int32)
    r = at = 0
    while r < rows:
        doc = np.concatenate([[BOS], next_doc()])[: seq_len - at]
        x[r, at:at + len(doc)] = doc
        y[r, at:at + len(doc) - 1] = doc[1:]
        at += len(doc)
        if at == seq_len:
            r, at = r + 1, 0
    return x, y


def make(spec: dict, num_clients: int, cfg: dict, seed: int) -> dict:
    """``tokens`` and ``targets`` ``(N, S)`` are the pool of rows (on the
    host: 0.6 MB a client); ``train`` and ``test`` are ``(ids, lengths)``
    into it, one row of ids a client."""
    seq_len, vocab = cfg["input_shape"][0], cfg["vocab_size"]
    n_train, n_test = spec["train_rows"], spec["test_rows"]
    rows, topics = n_train + n_test, spec["topics"]
    rng = np.random.default_rng([seed, 0x70C5])
    cdf = np.cumsum(np.arange(1, vocab, dtype=np.float64) ** -spec["zipf"])
    cdf /= cdf[-1]
    perms = np.stack([1 + rng.permutation(vocab - 1)
                      for _ in range(topics)]).astype(np.int32)
    xs, ys = [], []
    for _ in range(num_clients):
        mix = rng.dirichlet(np.full(topics, spec["alpha"]))

        def next_doc():
            n = int(np.clip(rng.lognormal(np.log(spec["doc_median"]),
                                          spec["doc_sigma"]), 1, seq_len - 1))
            topic = rng.choice(topics, p=mix)
            return perms[topic][np.searchsorted(cdf, rng.random(n))]

        x, y = pack(next_doc, seq_len, rows)
        xs.append(x)
        ys.append(y)
    ids = np.arange(num_clients * rows, dtype=np.int32).reshape(
        num_clients, rows)
    return {"tokens": np.concatenate(xs), "targets": np.concatenate(ys),
            "train": (ids[:, :n_train],
                      np.full(num_clients, n_train, np.int32)),
            "test": (ids[:, n_train:],
                     np.full(num_clients, n_test, np.int32)),
            "dataset": {"name": spec["stands_in_for"],
                        "input_shape": (seq_len,), "num_classes": vocab}}


def gather(data: dict, part: str):
    """``(x (n, cap, S) int32, y (n, cap, S) int32, lengths)``."""
    ids, lengths = data[part]
    return data["tokens"][ids], data["targets"][ids], lengths


def batches(data: dict, ids):
    """``(tokens (G, steps, batch, S), targets (G, steps, batch, S))`` on
    the device, as the family's ``loss_fn`` reads a batch."""
    return jnp.asarray(data["tokens"][ids]), jnp.asarray(data["targets"][ids])
