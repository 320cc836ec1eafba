"""The data kind ``class_mean_images``: labelled images, from the seed.

A data kind is the traffic's, not the model's: the ``data`` block of a
traffic file (``perfbench/traffic/<name>.json``) names it (``kind``) and
holds its parameters, and ``pb/manifest.py::data_kind`` finds this file by
that name.  What every data kind provides:

- ``make(spec, num_clients, cfg, seed)``: the federation's data.  ``train``
  and ``test`` are ``(ids (n, cap), lengths (n,))``, one row a client, into a
  pool of samples that only the kind reads; ``dataset`` holds, as plain
  values, what the program's dataset object is told of the data beside the
  arrays (``pb/sut.py::build`` passes them on by keyword).
- ``gather(data, part)``: a part laid out as the ``(n, cap, ...)`` arrays a
  vmapped federation reads: what the program is handed.
- ``batches(data, ids)``: the samples ``ids`` ``(G, steps, batch)`` as the
  reference's ``loss_fn`` reads them, ``(x (G, steps, batch, ...), y)``.

Every seed gives arrays of the same shapes (``shard_cap`` rows a client, true
sizes in ``lengths``), so that a new seed is new data for the same compiled
programs and the same work: only which samples a client holds, and how
skewed its labels are, changes.

This kind: one image a label, a fixed random mean per class plus Gaussian
noise, of the shape and class count the configuration's file states
(``input_shape``, ``num_classes``); label-skewed (Dirichlet) or iid shards.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("shape", "classes", "noise", "dtype"))
def class_mean_images(key, labels, shape, classes, noise, dtype):
    """One image a label, made on the device in one call: a fixed random
    mean per class plus Gaussian noise, rounded to ``dtype`` (the values the
    program and the reference both read)."""
    k_mu, k_eps = jax.random.split(key)
    mus = jax.random.normal(k_mu, (classes,) + shape, jnp.float32)
    eps = jax.random.normal(k_eps, labels.shape + shape, jnp.float32)
    return (mus[labels] + np.float32(noise) * eps).astype(dtype)


def dirichlet_shards(rng, labels, num_clients: int, alpha: float,
                     min_size: int, cap: int):
    """Label-skewed shards: per class, client shares drawn from
    Dirichlet(alpha), clients already at their fair share skipped (as the
    FL literature's partitioner does); then shards are repaired into
    ``[min_size, cap]`` by moving rows from the largest to the smallest."""
    n = labels.shape[0]
    fair = n / num_clients
    parts = [[] for _ in range(num_clients)]
    sizes = np.zeros(num_clients, np.int64)
    for c in np.unique(labels):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(num_clients, alpha))
        props = np.where(sizes >= fair, 0.0, props)
        props = (np.full(num_clients, 1.0 / num_clients)
                 if props.sum() <= 0 else props / props.sum())
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            if len(part):
                parts[i].append(part)
                sizes[i] += len(part)
    shards = [np.concatenate(p) if p else np.zeros(0, np.int64)
              for p in parts]
    # Repair into [min_size, cap]: rows over the cap go to a pool, the
    # largest shards top the pool up if the starved need more than it holds,
    # and the pool is dealt out smallest shard first.
    pool = [s[cap:] for s in shards if len(s) > cap]
    shards = [s[:cap] for s in shards]
    have = sum(map(len, pool))
    need = sum(max(min_size - len(s), 0) for s in shards)
    for i in np.argsort([-len(s) for s in shards]):
        if have >= need:
            break
        give = min(len(shards[i]) - min_size, need - have)
        if give > 0:
            pool.append(shards[i][-give:])
            shards[i] = shards[i][:-give]
            have += give
    pool = np.concatenate(pool) if pool else np.zeros(0, np.int64)
    for i in np.argsort([len(s) for s in shards]):
        if not len(pool):
            break
        room = (min_size if len(shards[i]) < min_size else int(fair)) - len(shards[i])
        take = min(max(room, 0), len(pool))
        shards[i] = np.concatenate([shards[i], pool[:take]])
        pool = pool[take:]
    if (len(pool) or min(map(len, shards)) < min_size
            or max(map(len, shards)) > cap):
        raise ValueError(f"{n} samples cannot give {num_clients} clients "
                         f"shards of {min_size} to {cap} rows")
    return [np.sort(s) for s in shards]


def pad_shards(shards, cap: int):
    """``(ids (n, cap), lengths (n,))``: each client's sample ids, padded
    with its own rows cyclically."""
    ids = np.stack([np.resize(s, cap) for s in shards]).astype(np.int32)
    return ids, np.array([len(s) for s in shards], np.int32)


def make(spec: dict, num_clients: int, cfg: dict, seed: int) -> dict:
    """The federation's data from the traffic file's ``data`` block.

    ``pool_x`` ``(N, H, W, C)`` on the device and ``pool_y`` ``(N,)`` on the
    host are the samples; ``train`` and ``test`` are ``(ids, lengths)`` into
    them, one row a client."""
    input_shape, num_classes = tuple(cfg["input_shape"]), cfg["num_classes"]
    rng = np.random.default_rng([seed, 0xDA7A])
    n_train = num_clients * spec["train_per_client"]
    n_test = num_clients * spec["test_per_client"]
    pool_y = rng.integers(0, num_classes, size=n_train + n_test).astype(
        np.int32)
    pool_x = class_mean_images(
        jax.random.PRNGKey(seed), jnp.asarray(pool_y), input_shape,
        num_classes, float(spec["noise"]), spec["image_dtype"])
    part = spec["partition"]
    if part["kind"] == "dirichlet":
        shards = dirichlet_shards(rng, pool_y[:n_train], num_clients,
                                  part["alpha"], part["min_shard"],
                                  spec["shard_cap"])
    elif part["kind"] == "iid":
        shards = np.array_split(rng.permutation(n_train), num_clients)
    else:
        raise ValueError(f"unknown partition {part['kind']!r}")
    test_shards = np.array_split(n_train + rng.permutation(n_test),
                                 num_clients)
    return {"pool_x": pool_x, "pool_y": pool_y,
            "train": pad_shards(shards, spec["shard_cap"]),
            "test": pad_shards(test_shards, spec["test_per_client"]),
            "dataset": {"name": spec["stands_in_for"],
                        "input_shape": input_shape,
                        "num_classes": num_classes}}


def gather(data: dict, part: str):
    """``(x (n, cap, H, W, C) on the device, y (n, cap), lengths)``."""
    ids, lengths = data[part]
    return data["pool_x"][jnp.asarray(ids)], data["pool_y"][ids], lengths


def batches(data: dict, ids):
    """``(x (G, steps, batch, H, W, C) float32 on the device, y (G, steps,
    batch))`` of the samples ``ids``."""
    return (data["pool_x"][jnp.asarray(ids)].astype(jnp.float32),
            jnp.asarray(data["pool_y"][ids]))
