"""The plain reference of a robust federated round, for any model family.

Imports ``jax`` and ``numpy`` only: nothing of the program under test, and
nothing the program has made.  It is the yardstick ``correct`` is decided
against (see ``compare.py``), so it follows the published description and
nothing cleverer.  The model is the family's (``families/<family>.py``:
``init_params``, ``num_params``, ``loss_fn``) and the samples are the data
kind's (``data/<kind>.py``: ``batches``); the round is here, once for all
of them:

- A client's local round: ``local_steps`` plain SGD steps of the family's
  loss on batches it draws with replacement from its own shard; its update
  is ``params_end - params_start``.
- Update rows are stored in the configuration's ``update_dtype``.
- ALIE (Baruch et al. 2019): every malicious row is ``mean + z_max * std``
  of the benign rows (unbiased std), ``z_max`` the inverse normal CDF at
  ``(n - f - s) / (n - f)``, ``s = n // 2 + 1 - f``.
- Median: the symmetrised coordinate-wise median of all n rows.
  GeoMed: smoothed Weiszfeld (RFA, Pillutla et al. 2022) from the mean, at
  most ``maxiter`` steps, stopped when the objective moves by less than
  ``ftol`` of itself.
- Server: ``params += server_lr * aggregate``.

The random draws follow the program's documented stream (the program pins it
bit-exactly across its own execution paths): ``PRNGKey(seed)`` split once for
initialisation, then one split per round; a round key splits five ways
(sample, train, adversary, aggregator, dp), the sample key splits per client,
a client's key splits per local batch, and a batch is
``randint(key, (batch,), 0, shard_length)``.

``quant`` selects the arithmetic (``arith.py``).  ``None`` is the reference.
``"fp8"`` is the control: the same round with every contraction's operands
and cotangents rounded to fp8 inside the family's ``loss_fn``, and the
stored rows rounded to float8_e4m3 with a per-row scale.
"""

from __future__ import annotations

from functools import partial
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .arith import HIGHEST, round_fp8


def store_rows(rows, cfg: dict, quant):
    """Rows as the update matrix keeps them."""
    dtype = jnp.dtype(cfg["update_dtype"])
    if quant == "fp8":
        rows = round_fp8(rows, jnp.float8_e4m3fn, axis=1)
    return rows.astype(dtype)


def local_round(loss_fn, cfg: dict, fed: dict, params, xs, ys, quant=None):
    """One client: ``xs`` ``(steps, batch, ...)`` as the family's ``loss_fn``
    reads a batch.  Returns its update tree and its mean loss."""
    p = params
    losses = []
    for s in range(xs.shape[0]):
        loss, g = jax.value_and_grad(partial(loss_fn, cfg))(
            p, xs[s], ys[s], quant=quant)
        p = jax.tree.map(lambda w, gw: w - fed["client_lr"] * gw, p, g)
        losses.append(loss)
    update = jax.tree.map(lambda a, b: a - b, p, params)
    return update, jnp.stack(losses).mean()


def flatten_rows(tree):
    """``(G, ...)`` leaves -> ``(G, d)`` in sorted-leaf order."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(l.shape[0], -1) for l in leaves],
                           axis=1)


def unflatten_vec(vec, like):
    leaves, treedef = jax.tree.flatten(like)
    out, at = [], 0
    for l in leaves:
        out.append(vec[at:at + l.size].reshape(l.shape))
        at += l.size
    return jax.tree.unflatten(treedef, out)


# -- the random stream ---------------------------------------------------------


def round_keys(seed: int, rounds: int):
    key = jax.random.PRNGKey(seed)
    _, key = jax.random.split(key)
    out = []
    for _ in range(rounds):
        rk, key = jax.random.split(key)
        out.append(rk)
    return out


@partial(jax.jit, static_argnames=("n", "steps", "batch"))
def batch_indices(round_key, lengths, n, steps, batch):
    """``(n, steps, batch)`` row indices into every client's shard."""
    k_sample = jax.random.split(round_key, 5)[0]
    client_keys = jax.random.split(k_sample, n)

    def per_client(k, ln):
        ks = jax.random.split(k, steps)
        return jax.vmap(lambda kb: jax.random.randint(
            kb, (batch,), 0, jnp.maximum(ln, 1)))(ks)

    return jax.vmap(per_client)(client_keys, lengths)


# -- forge and aggregate ---------------------------------------------------------


def alie_z(n: int, f: int) -> float:
    s = n // 2 + 1 - f
    cdf = (n - f - s) / max(n - f, 1)
    return NormalDist().inv_cdf(min(max(cdf, 1e-9), 1.0 - 1e-9))


def _forge(x, z):
    """ALIE's row from the benign block ``x`` ``(nb, c)`` float32."""
    nb = x.shape[0]
    mean = x.sum(axis=0) / nb
    var = jnp.square(x - mean).sum(axis=0) / max(nb - 1, 1)
    return mean + jnp.sqrt(var) * np.float32(z)


@partial(jax.jit, static_argnames=("c", "f", "z"))
def _median_chunk(mat, start, c, f, z):
    x = lax.dynamic_slice(mat, (0, start), (mat.shape[0], c)).astype(
        jnp.float32)
    rows = x
    if f:
        forged = _forge(x, z)
        rows = jnp.concatenate(
            [jnp.broadcast_to(forged, (f, c)), x], axis=0)
    rows = jnp.sort(rows, axis=0)
    m = rows.shape[0]
    return (rows[(m - 1) // 2] + rows[m // 2]) / 2.0


@partial(jax.jit, static_argnames=("c", "z"))
def _forge_chunk(mat, start, c, z):
    x = lax.dynamic_slice(mat, (0, start), (mat.shape[0], c)).astype(
        jnp.float32)
    return _forge(x, z)


def _chunks(d: int, c: int):
    c = min(c, d)
    return c, [min(i * c, d - c) for i in range(-(-d // c))]


def _by_chunks(fn, d, c):
    """Assemble a ``(d,)`` vector from ``fn(start, width) -> (width,)``; the
    tail chunk overlaps its predecessor and rewrites equal values."""
    c, starts = _chunks(d, c)
    out = jnp.zeros((d,), jnp.float32)
    for s in starts:
        out = lax.dynamic_update_slice(out, fn(s, c), (s,))
    return out


def aggregate(mat, fed: dict, chunk: int = 1 << 16, info=None):
    """The aggregate of the benign rows ``mat`` ``(nb, d)`` plus ``f`` forged
    rows, by the federation's defense.  ``info``, a dict, takes what the
    defense can say of its own work (GeoMed's Weiszfeld steps)."""
    d = mat.shape[1]
    f = fed["num_malicious_clients"]
    z = alie_z(fed["num_clients"], f)
    kind = fed["aggregator"]["type"]
    if kind == "Median":
        return _by_chunks(lambda s, c: _median_chunk(
            mat, jnp.int32(s), c, f, z), d, chunk)
    if kind == "GeoMed":
        forged = (_by_chunks(lambda s, c: _forge_chunk(
            mat, jnp.int32(s), c, z), d, chunk) if f else None)
        return _geomed(mat, forged, f, fed["aggregator"], chunk, info)
    raise ValueError(f"no reference for aggregator {kind!r}")


@partial(jax.jit, static_argnames=("c",))
def _sqdist_chunk(mat, med, start, c):
    x = lax.dynamic_slice(mat, (0, start), (mat.shape[0], c)).astype(
        jnp.float32)
    m = lax.dynamic_slice(med, (start,), (c,))
    return jnp.square(x - m).sum(axis=1)


@partial(jax.jit, static_argnames=("c",))
def _wsum_chunk(mat, w, start, c):
    x = lax.dynamic_slice(mat, (0, start), (mat.shape[0], c)).astype(
        jnp.float32)
    return jnp.dot(w, x, precision=HIGHEST)


def _geomed(mat, forged, f, spec, chunk, info=None):
    nb, d = mat.shape
    n = nb + f
    maxiter = int(spec.get("maxiter", 100))
    eps = float(spec.get("eps", 1e-6))
    ftol = float(spec.get("ftol", 1e-10))
    c, starts = _chunks(d, chunk)
    # Chunks but the last are disjoint; the last overlaps, so its squared
    # distances count only the columns not seen yet.
    fresh = [s + c - (starts[i - 1] + c) if i else c
             for i, s in enumerate(starts)]

    def dists(med):
        sq = jnp.zeros((nb,), jnp.float32)
        for s, new in zip(starts, fresh):
            if new == c:
                sq = sq + _sqdist_chunk(mat, med, jnp.int32(s), c)
            else:
                sq = sq + _sqdist_chunk(mat, med, jnp.int32(s + c - new), new)
        db = jnp.sqrt(sq)
        df = (jnp.linalg.norm(forged - med) if f else jnp.float32(0.0))
        return db, df

    def wavg(wb, wf):
        tot = wb.sum() + f * wf
        out = _by_chunks(lambda s, cc: _wsum_chunk(mat, wb, jnp.int32(s), cc),
                         d, chunk)
        if f:
            out = out + f * wf * forged
        return out / tot

    w0 = np.float32(1.0 / n)

    def objective(db, df):
        return (db.sum() + f * df) * w0

    med = wavg(jnp.full((nb,), w0), jnp.float32(w0))
    db, df = dists(med)
    prev, cur = np.inf, float(objective(db, df))
    it = 0
    while it < maxiter and abs(prev - cur) > ftol * cur:
        wb = w0 / jnp.maximum(db, eps)
        wf = w0 / jnp.maximum(df, eps)
        med = wavg(wb, wf)
        db, df = dists(med)
        prev, cur = cur, float(objective(db, df))
        it += 1
    if info is not None:
        info.setdefault("geomed_steps", []).append(it)
    return med


# -- rounds ------------------------------------------------------------------------


def make_block_fn(loss_fn, cfg: dict, fed: dict, quant=None):
    """Jitted ``(params, xs, ys) -> (rows (G, d) as stored, losses (G,))``
    for a block of clients, ``xs`` ``(G, steps, batch, ...)``."""

    @jax.jit
    def block(params, xs, ys):
        upd, losses = jax.vmap(lambda x, y: local_round(
            loss_fn, cfg, fed, params, x, y, quant))(xs, ys)
        return store_rows(flatten_rows(upd), cfg, quant), losses

    return block


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(mat, rows, row0):
    return lax.dynamic_update_slice(mat, rows, (row0, 0))


@partial(jax.jit, static_argnames=("lr",))
def _server_step(params, agg, lr):
    return jax.tree.map(lambda p, a: p + np.float32(lr) * a, params,
                        unflatten_vec(agg, params))


def run_rounds(family, kind, cfg: dict, fed: dict, data, seed: int,
               rounds: int, client_block: int, quant=None, fault=None):
    """Follow the federation for ``rounds`` rounds from the seed's weights.

    ``family`` is the configuration's model family and ``kind`` the
    traffic's data kind, both as ``manifest`` finds them.  ``data`` is
    ``kind.make``'s: ``train = (ids (n, cap), lengths (n,))`` on the host,
    sample ids that ``kind.batches`` turns into batches.  Returns
    ``{"losses": [...], "params": [params after round 1, ..., after round
    R] as host trees, "params0": host tree}``.

    ``fault`` plants one of the harness's known faults in the reference,
    for reading how far each moves the compared numbers: ``"half_batch"``
    (each client trains on the first half of its batch) or
    ``"half_clients"`` (the second half of the benign clients never train;
    their rows repeat the first half's).
    """
    if (fed["client_momentum"] or fed["server_momentum"]
            or (fed["num_malicious_clients"]
                and fed["adversary"].get("type") != "ALIE")):
        raise ValueError("the reference follows plain SGD on both sides and "
                         f"the ALIE forge only, not {fed}")
    ids_of, lengths = data["train"]
    n, f = fed["num_clients"], fed["num_malicious_clients"]
    nb = n - f
    steps, batch = fed["local_steps"], fed["batch_size"]
    params = family.init_params(cfg, seed)
    params0 = jax.device_get(params)
    d = family.num_params(cfg)
    block = make_block_fn(family.loss_fn, cfg, fed, quant)
    dtype = jnp.dtype(cfg["update_dtype"])
    out = {"losses": [], "params": [], "params0": params0, "info": {}}
    g = client_block
    lengths_dev = jnp.asarray(lengths)
    for rk in round_keys(seed, rounds):
        idx = np.asarray(batch_indices(rk, lengths_dev, n, steps, batch))
        mat = jnp.zeros((nb, d), dtype)
        losses = []
        trained = nb // 2 if fault == "half_clients" else nb
        for b0 in range(0, trained, g):
            ids = np.arange(f + b0, min(f + b0 + g, f + trained))
            if len(ids) < g:  # last block: pad with the block's first client
                ids = np.concatenate([ids, np.full(g - len(ids), ids[0])])
            sid = ids_of[ids[:, None, None], idx[ids]]      # (G, steps, B)
            if fault == "half_batch":
                sid = sid[:, :, : batch // 2]
            rows, ls = block(params, *kind.batches(data, sid))
            keep = min(g, trained - b0)
            mat = _write_rows(mat, rows[:keep], jnp.int32(b0))
            if fault == "half_clients":   # the untrained half repeats it
                again = min(keep, nb - trained - b0)
                if again > 0:
                    mat = _write_rows(mat, rows[:again],
                                      jnp.int32(trained + b0))
            losses.append(np.asarray(ls)[:keep])
        agg = aggregate(mat, fed, info=out["info"])
        del mat
        params = _server_step(params, agg, float(fed["server_lr"]))
        out["losses"].append(float(np.concatenate(losses).mean()))
        out["params"].append(jax.device_get(params))
    return out
