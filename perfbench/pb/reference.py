"""The plain reference of a robust federated round, and its weights.

Imports ``jax`` and ``numpy`` only: nothing of the program under test, and
nothing the program has made.  It is the yardstick ``correct`` is decided
against (see ``compare.py``), so it follows the published description and
nothing cleverer:

- CIFAR ResNet with BasicBlocks (He et al. 2016; 3x3 stem, no max-pool),
  batch normalisation by the current batch's statistics (no running
  averages), global average pool, one dense head.  NHWC, float32,
  ``precision=HIGHEST`` on every contraction.
- A client's local round: ``local_steps`` plain SGD steps of the mean
  softmax cross-entropy on batches it draws with replacement from its own
  shard; its update is ``params_end - params_start``.
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

``quant`` selects the arithmetic.  ``None`` is the reference.  ``"fp8"`` is
the control: the same round with every conv/dense operand rounded to
float8_e4m3 and every cotangent to float8_e5m2, each with a per-tensor
scale to its own largest magnitude (the usual fp8 training recipe), and the
stored rows rounded to float8_e4m3 with a per-row scale.
"""

from __future__ import annotations

from functools import partial
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


# -- the layer list, from the configuration's file --------------------------


def layer_shapes(cfg: dict) -> dict:
    """``{module: {leaf: shape}}`` of the model the configuration's file
    describes, under flax-linen's automatic module names (the one
    convention the reference shares with the program, stated under
    ``assumed`` in the file)."""
    if cfg["block"] != "basic":
        raise ValueError(f"block {cfg['block']!r}: only 'basic' is described")
    cin = cfg["input_shape"][-1]
    stem = cfg["stem_width"]
    tree = {"Conv_0": {"kernel": (3, 3, cin, stem)},
            "BatchStatsNorm_0": {"scale": (stem,), "bias": (stem,)}}
    prev, idx = stem, 0
    for width, blocks, stride in zip(cfg["stage_widths"], cfg["stage_blocks"],
                                     cfg["stage_strides"]):
        for j in range(blocks):
            s = stride if j == 0 else 1
            blk = {"Conv_0": {"kernel": (3, 3, prev, width)},
                   "BatchStatsNorm_0": {"scale": (width,), "bias": (width,)},
                   "Conv_1": {"kernel": (3, 3, width, width)},
                   "BatchStatsNorm_1": {"scale": (width,), "bias": (width,)}}
            if s != 1 or prev != width:
                blk["Conv_2"] = {"kernel": (1, 1, prev, width)}
                blk["BatchStatsNorm_2"] = {"scale": (width,),
                                           "bias": (width,)}
            tree[f"BasicBlock_{idx}"] = blk
            prev, idx = width, idx + 1
    tree["Dense_0"] = {"kernel": (prev, cfg["num_classes"]),
                       "bias": (cfg["num_classes"],)}
    return tree


def block_strides(cfg: dict) -> list:
    return [stride if j == 0 else 1
            for blocks, stride in zip(cfg["stage_blocks"],
                                      cfg["stage_strides"])
            for j in range(blocks)]


def num_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        layer_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_params(cfg: dict, seed: int):
    """The weights every side starts from, made on the device in one jitted
    call from the seed: He-normal kernels (std sqrt(2 / fan_in)) for the
    convs, std sqrt(1 / fan_in) for the head, unit scales, zero biases."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        layer_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            leaf = path[-1].key
            if leaf == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif leaf == "bias":
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                fan_in = int(np.prod(shape[:-1]))
                gain = 2.0 if len(shape) == 4 else 1.0
                out.append(jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
                           * np.float32(np.sqrt(gain / fan_in)))
        return out

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed)))


# -- arithmetic: exact, or rounded to fp8 for the control --------------------


def _round_fp8(x, dtype, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round_fp8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_round_fp8(x, jnp.float8_e4m3fn), None),
                    lambda _, g: (_round_fp8(g, jnp.float8_e5m2),))


def _operand(quant):
    if quant is None:
        return lambda x: x
    if quant == "fp8":
        return _fp8_operand
    raise ValueError(f"unknown arithmetic {quant!r}")


def store_rows(rows, cfg: dict, quant):
    """Rows as the update matrix keeps them."""
    dtype = jnp.dtype(cfg["update_dtype"])
    if quant == "fp8":
        rows = _round_fp8(rows, jnp.float8_e4m3fn, axis=1)
    return rows.astype(dtype)


# -- the model ----------------------------------------------------------------


def _norm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(cfg: dict, params, x, quant=None):
    q = _operand(quant)
    eps = cfg["norm_eps"]

    def conv(x, w, stride, pad):
        return lax.conv_general_dilated(
            q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=_DN, precision=HIGHEST)

    x = conv(x, params["Conv_0"]["kernel"], 1, 1)
    x = jax.nn.relu(_norm(x, params["BatchStatsNorm_0"], eps))
    for i, stride in enumerate(block_strides(cfg)):
        p = params[f"BasicBlock_{i}"]
        y = conv(x, p["Conv_0"]["kernel"], stride, 1)
        y = jax.nn.relu(_norm(y, p["BatchStatsNorm_0"], eps))
        y = conv(y, p["Conv_1"]["kernel"], 1, 1)
        y = _norm(y, p["BatchStatsNorm_1"], eps)
        if "Conv_2" in p:
            x = _norm(conv(x, p["Conv_2"]["kernel"], stride, 0),
                      p["BatchStatsNorm_2"], eps)
        x = jax.nn.relu(y + x)
    x = jnp.mean(x, axis=(1, 2))
    head = params["Dense_0"]
    return jnp.dot(q(x), q(head["kernel"]), precision=HIGHEST) + head["bias"]


def loss_fn(cfg: dict, params, x, y, quant=None):
    logits = forward(cfg, params, x, quant)
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0].mean()
    return jnp.clip(ce, 0.0, 1e6)


def local_round(cfg: dict, fed: dict, params, xs, ys, quant=None):
    """One client: ``xs`` ``(steps, batch, H, W, C)``.  Returns its update
    tree and its mean loss."""
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


def make_block_fn(cfg: dict, fed: dict, quant=None):
    """Jitted ``(params, xs, ys) -> (rows (G, d) as stored, losses (G,))``
    for a block of clients, ``xs`` ``(G, steps, batch, H, W, C)``."""

    @jax.jit
    def block(params, xs, ys):
        upd, losses = jax.vmap(
            lambda x, y: local_round(cfg, fed, params, x, y, quant))(xs, ys)
        return store_rows(flatten_rows(upd), cfg, quant), losses

    return block


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(mat, rows, row0):
    return lax.dynamic_update_slice(mat, rows, (row0, 0))


@partial(jax.jit, static_argnames=("lr",))
def _server_step(params, agg, lr):
    return jax.tree.map(lambda p, a: p + np.float32(lr) * a, params,
                        unflatten_vec(agg, params))


def run_rounds(cfg: dict, fed: dict, data, seed: int, rounds: int,
               client_block: int, quant=None, fault=None):
    """Follow the federation for ``rounds`` rounds from the seed's weights.

    ``data`` is the traffic generator's: ``pool_x`` ``(N, H, W, C)`` on the
    device, ``pool_y`` ``(N,)`` and ``train = (ids (n, cap), lengths (n,))``
    on the host.  Returns ``{"losses": [...], "params": [params after round
    1, ..., after round R] as host trees, "params0": host tree}``.

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
    pool_x, pool_y = data["pool_x"], data["pool_y"]
    ids_of, lengths = data["train"]
    n, f = fed["num_clients"], fed["num_malicious_clients"]
    nb = n - f
    steps, batch = fed["local_steps"], fed["batch_size"]
    params = init_params(cfg, seed)
    params0 = jax.device_get(params)
    d = num_params(cfg)
    block = make_block_fn(cfg, fed, quant)
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
            rows, ls = block(params,
                             pool_x[jnp.asarray(sid)].astype(jnp.float32),
                             jnp.asarray(pool_y[sid]))
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
