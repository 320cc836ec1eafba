"""The shape arithmetic against XLA's own count of the plain client step,
so that a typo in either shows.  After this the yardstick no longer follows
what the program compiles to."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from pb import costs
from pb.manifest import CHECKOUT, family

# XLA counts the convolutions as the arithmetic does (taps on the zero
# padding left out) and adds the
# normalisations, activations, loss and SGD update on top: a few percent.
TOLERANCE = 0.04


def _cfg(name):
    return json.load(open(os.path.join(CHECKOUT, "perfbench", "configs",
                                       name + ".json")))


def _family(cfg):
    return family(cfg["family"])


@pytest.mark.parametrize("name,macs", [("resnet10_cifar32", 224_270_080),
                                       ("resnet18_cifar32", 481_859_328)])
def test_forward_macs(name, macs):
    cfg = _cfg(name)
    assert _family(cfg).forward_macs_per_sample(cfg) == macs
    assert _family(cfg).train_flops_per_sample(cfg, {}) == 6 * macs


@pytest.mark.parametrize("name", ["resnet10_cifar32", "resnet18_cifar32"])
def test_train_flops_against_cost_analysis(name):
    cfg = _cfg(name)
    fam = _family(cfg)
    batch = 32
    params = jax.eval_shape(lambda: fam.init_params(cfg, 0))
    x = jax.ShapeDtypeStruct((batch,) + tuple(cfg["input_shape"]),
                             jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    def step(p, x, y):
        loss, g = jax.value_and_grad(
            lambda p: fam.loss_fn(cfg, p, x, y))(p)
        return loss, jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g)

    cost = jax.jit(step).lower(params, x, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    xla = float(cost["flops"])
    mine = fam.train_flops_per_sample(cfg, {}) * batch
    assert abs(xla - mine) / mine < TOLERANCE, (xla, mine)


def test_round_flops_count_trained_lanes_only():
    cfg = _cfg("resnet10_cifar32")
    fed = {"num_clients": 1000, "elided_lanes": 250, "batch_size": 32,
           "local_steps": 1}
    full = dict(fed, elided_lanes=0)
    fam = _family(cfg)
    assert costs.round_flops(fam, cfg, fed) == 750 * 32 * 6 * 224_270_080
    assert costs.round_flops(fam, cfg, full) * 3 == \
        costs.round_flops(fam, cfg, fed) * 4


@pytest.mark.parametrize("name,rows,nbytes", [
    ("resnet10_cifar32", 750, 42_912_331_968),
    ("resnet18_cifar32", 576, 58_329_566_760)])
def test_named_works_resolve_through_the_family_then_the_shared_ones(
        name, rows, nbytes):
    # nbytes: what pb/costs.py::train_bytes read at PR 27, before the model's
    # terms moved into the family.
    cfg = _cfg(name)
    fam = _family(cfg)
    fed = {"num_clients": rows, "elided_lanes": 0, "stored_rows": rows,
           "batch_size": 32, "local_steps": 1}
    assert costs.work(fam, "train")(cfg, fed) == (
        costs.round_flops(fam, cfg, fed), nbytes)
    assert costs.work(fam, "finish")(cfg, fed) == (
        0, costs.finish_bytes(cfg, fed))
    with pytest.raises(KeyError):
        costs.work(fam, "expert_matmul")

    class Other:
        WORKS = {"train": lambda cfg, fed: (1, 2)}

    assert costs.work(Other, "train")(cfg, fed) == (1, 2)


@pytest.mark.parametrize("name,rows", [("resnet10_cifar32", 750),
                                       ("resnet18_cifar32", 576),
                                       ("resnet10_cifar32", 1000)])
def test_finish_byte_floor(name, rows):
    cfg = _cfg(name)
    d = cfg["num_params"]
    assert costs.finish_bytes(cfg, {"stored_rows": rows}) == \
        rows * d * 2 + 4 * d
