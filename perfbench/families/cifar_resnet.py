"""The CIFAR ResNet family: everything the benchmark knows of the model.

A family is the whole model-specific surface of the yardstick, found by the
``family`` a configuration's file names (``pb/manifest.py::family``).  Plain
``jax`` and ``numpy``: nothing of the program under test, and nothing the
program has made.  What every family provides:

- ``layer_shapes(cfg)``, ``num_params(cfg)``, ``init_params(cfg, seed)``:
  the parameter tree the configuration's file describes, and the weights
  every side starts from;
- ``loss_fn(cfg, params, x, y, quant=None)``: the plain reference's loss on
  one batch, float32 with ``precision=HIGHEST`` on every contraction, each
  contraction's operands wrapped in ``pb.arith.operand(quant)`` (the fp8
  control's hook);
- the required work, as functions of ``(cfg, fed)``:
  ``train_flops_per_sample`` and ``train_activation_bytes_per_sample``
  (``pb/costs.py`` multiplies them out over batch, steps and trained lanes),
  and ``WORKS``, a table ``{name: (cfg, fed) -> (operations, bytes) a
  round}`` of works of its own that a metric's file may name (``"work"``)
  beside the shared ``"train"`` and ``"finish"``.

This one follows the published description and nothing cleverer: CIFAR
ResNet with BasicBlocks (He et al. 2016; 3x3 stem, no max-pool), batch
normalisation by the current batch's statistics (no running averages),
global average pool, one dense head, mean softmax cross-entropy.  NHWC.

Cost conventions: a multiply-add is 2 operations; a kernel tap that falls on
the zero padding is no required work and is not counted (so a 3x3
convolution over a 4x4 map counts 100 of its 144 taps); the backward pass
costs twice the forward (one contraction for the input's gradient, one for
the weight's), with no recomputation; normalisation, activations, the loss
and the optimizer count nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pb.arith import HIGHEST, operand
from pb.costs import ITEMSIZE

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


# -- the model ----------------------------------------------------------------


def _norm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def forward(cfg: dict, params, x, quant=None):
    q = operand(quant)
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


# -- the required work, from the configuration's shapes alone -----------------


def contractions(cfg: dict) -> list:
    """``(name, multiply-adds per image, output elements per image)`` of
    every conv and dense layer."""
    h, w, cin = cfg["input_shape"]
    out = []

    def taps(n, k, stride):
        """Kernel taps that land inside a side of ``n``, summed over the
        output positions (padding ``k // 2`` each side)."""
        pad = k // 2
        return sum(1 for o in range(-(-n // stride)) for t in range(k)
                   if 0 <= o * stride - pad + t < n)

    def conv(name, k, ci, co, stride):
        nonlocal h, w
        ho, wo = -(-h // stride), -(-w // stride)
        out.append((name, taps(h, k, stride) * taps(w, k, stride) * ci * co,
                    ho * wo * co))
        return ho, wo

    h, w = conv("stem", 3, cin, cfg["stem_width"], 1)
    prev, idx = cfg["stem_width"], 0
    for width, blocks, stride in zip(cfg["stage_widths"], cfg["stage_blocks"],
                                     cfg["stage_strides"]):
        for j in range(blocks):
            s = stride if j == 0 else 1
            hin, win = h, w
            h, w = conv(f"block{idx}.conv0", 3, prev, width, s)
            conv(f"block{idx}.conv1", 3, width, width, 1)
            if s != 1 or prev != width:
                keep = h, w
                h, w = hin, win
                conv(f"block{idx}.shortcut", 1, prev, width, s)
                h, w = keep
            prev, idx = width, idx + 1
    out.append(("head", prev * cfg["num_classes"], cfg["num_classes"]))
    return out


def forward_macs_per_sample(cfg: dict) -> int:
    return sum(m for _, m, _ in contractions(cfg))


def train_flops_per_sample(cfg: dict, fed: dict) -> int:
    """Forward 2 per multiply-add, backward twice that."""
    return 3 * 2 * forward_macs_per_sample(cfg)


def train_activation_bytes_per_sample(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of one sample's local step: the image read once
    and every contraction's output written once (forward) and read once
    (backward), in the compute type."""
    act = ITEMSIZE[cfg["compute_dtype"]]
    h, w, c = cfg["input_shape"]
    return h * w * c * act + 2 * act * sum(o for _, _, o in contractions(cfg))


# No work of its own: its rooflines are the shared "train" and "finish".
WORKS = {}
