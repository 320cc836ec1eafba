"""Required operations and bytes of a round, from shapes alone.

Nothing here calls a compiler: the yardstick has to read the same work
whatever implements it.  What depends on the model is its family's
(``families/<family>.py``: ``train_flops_per_sample``,
``train_activation_bytes_per_sample``, with the conventions stated there);
what a robust round adds to it is here, for every family: the trained lanes,
the stored rows, the parameters, the finish.  A CPU test holds each family's
``train_flops_per_sample`` to XLA's ``cost_analysis`` of the plain client
step so that a typo shows.

``work(family, name)`` finds a named work for a roofline or an ``mfu``: the
family's own table first, this module's second.
"""

from __future__ import annotations

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
            "float8_e4m3fn": 1}


def trained_lanes(fed: dict) -> int:
    """Clients whose local training the round needs: malicious lanes whose
    rows a forging attack overwrites do no required work."""
    return fed["num_clients"] - fed["elided_lanes"]


def round_flops(family, cfg: dict, fed: dict) -> int:
    return (family.train_flops_per_sample(cfg, fed) * fed["batch_size"]
            * fed["local_steps"] * trained_lanes(fed))


def train_bytes(family, cfg: dict, fed: dict) -> int:
    """Least HBM traffic of a round's local training: per trained lane and
    local step, the family's activation traffic of every sample of the
    batch; per lane, its update row written once in the matrix's type; the
    parameters read once per round."""
    lanes = trained_lanes(fed)
    return (family.train_activation_bytes_per_sample(cfg, fed)
            * fed["batch_size"] * fed["local_steps"] * lanes
            + lanes * cfg["num_params"] * ITEMSIZE[cfg["update_dtype"]]
            + cfg["num_params"] * ITEMSIZE[cfg["param_dtype"]])


def finish_bytes(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of forge + aggregate: one read of the stored
    ``(rows, d)`` matrix at its type, one float32 write of ``d``.  The same
    for an iterative defense: the floor is one pass, whatever the count."""
    rows = fed["stored_rows"]
    return (rows * cfg["num_params"] * ITEMSIZE[cfg["update_dtype"]]
            + 4 * cfg["num_params"])


def work(family, name: str):
    """``(cfg, fed) -> (operations, bytes)`` a round of the work ``name``."""
    if name in family.WORKS:
        return family.WORKS[name]
    if name == "train":
        return lambda cfg, fed: (round_flops(family, cfg, fed),
                                 train_bytes(family, cfg, fed))
    if name == "finish":
        return lambda cfg, fed: (0, finish_bytes(cfg, fed))
    raise KeyError(f"no work {name!r}: the family has "
                   f"{sorted(family.WORKS)}, every family has 'train' and "
                   "'finish'")
