"""Required operations and bytes, from the configuration's shapes alone.

Nothing here calls a compiler: the yardstick has to read the same work
whatever implements it.  A CPU test holds ``train_flops_per_sample`` to
XLA's ``cost_analysis`` of the plain client step so that a typo shows.

Conventions: a multiply-add is 2 operations; a kernel tap that falls on the
zero padding is no required work and is not counted (so a 3x3 convolution
over a 4x4 map counts 100 of its 144 taps); the backward pass costs twice
the forward (one contraction for the input's gradient, one for the
weight's), with no recomputation; normalisation, activations, the loss and
the optimizer count nothing.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
             "float8_e4m3fn": 1}


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


def train_flops_per_sample(cfg: dict) -> int:
    """Forward 2 per multiply-add, backward twice that."""
    return 3 * 2 * forward_macs_per_sample(cfg)


def trained_lanes(fed: dict) -> int:
    """Clients whose local training the round needs: malicious lanes whose
    rows a forging attack overwrites do no required work."""
    return fed["num_clients"] - fed["elided_lanes"]


def round_flops(cfg: dict, fed: dict) -> int:
    return (train_flops_per_sample(cfg) * fed["batch_size"]
            * fed["local_steps"] * trained_lanes(fed))


def train_bytes(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of a round's local training: per trained lane and
    local step, the batch read once and every contraction's output written
    once (forward) and read once (backward) in the compute type; per lane,
    its update row written once in the matrix's type; the parameters read
    once per round."""
    act = _ITEMSIZE[cfg["compute_dtype"]]
    h, w, c = cfg["input_shape"]
    per_sample = h * w * c * act + 2 * act * sum(
        o for _, _, o in contractions(cfg))
    lanes = trained_lanes(fed)
    return (per_sample * fed["batch_size"] * fed["local_steps"] * lanes
            + lanes * cfg["num_params"] * _ITEMSIZE[cfg["update_dtype"]]
            + cfg["num_params"] * _ITEMSIZE[cfg["param_dtype"]])


def finish_bytes(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of forge + aggregate: one read of the stored
    ``(rows, d)`` matrix at its type, one float32 write of ``d``.  The same
    for an iterative defense: the floor is one pass, whatever the count."""
    rows = fed["stored_rows"]
    return (rows * cfg["num_params"] * _ITEMSIZE[cfg["update_dtype"]]
            + 4 * cfg["num_params"])
