"""The seam is enough: a second model family comes with new files only.

Into a temporary copy of ``BENCHMARK.json`` + ``perfbench/`` this test writes
what a ``model_config`` PR would bring for a model the program can already
run (the package's MLP under ``tuned_examples/toy_fedsgd.yaml``'s Median
arm, ALIE in place of IPM): a family file, a data kind, a configuration, a
traffic mix, a cell file with limits, a roofline metric on a *work* the
family defines, and the appended entries of ``BENCHMARK.json``.  Then the
harness's own ``run_cell`` on that checkout comes out ``correct`` with the
contract's keys, and no file copied from the repo differs from its original.

The one thing the test adds on the program's side: ``mlp_plain``, the
package's MLP with its dropout rate at 0, registered through the package's
own ``register_model``.  The package's ``mlp`` draws dropout masks from a
per-step key that ``loss_fn(cfg, params, x, y, quant)`` is not handed
(``PERF.md`` Open questions): a family with dropout needs that key threaded
through the shared round first.
"""

import hashlib
import json
import os
import shutil
import time

import pytest

from pb import cell as C
from pb import costs
from pb.manifest import CHECKOUT, Manifest, family, reader

SEED = 2_800_000_011

FAMILY = '''"""A three-layer perceptron, as a family (see families/cifar_resnet.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from pb.arith import HIGHEST, operand
from pb.costs import ITEMSIZE, trained_lanes


def _sizes(cfg):
    return [int(np.prod(cfg["input_shape"]))] + list(cfg["hidden"]) + [
        cfg["num_classes"]]


def layer_shapes(cfg):
    s = _sizes(cfg)
    return {f"Dense_{i}": {"kernel": (s[i], s[i + 1]), "bias": (s[i + 1],)}
            for i in range(len(s) - 1)}


def num_params(cfg):
    s = _sizes(cfg)
    return sum(a * b + b for a, b in zip(s, s[1:]))


def init_params(cfg, seed):
    shapes = layer_shapes(cfg)

    @jax.jit
    def make(key):
        return {m: {"kernel": jax.random.normal(
            jax.random.fold_in(key, i), v["kernel"], jnp.float32)
            * np.float32(np.sqrt(1.0 / v["kernel"][0])),
            "bias": jnp.zeros(v["bias"], jnp.float32)}
            for i, (m, v) in enumerate(sorted(shapes.items()))}

    return make(jax.random.PRNGKey(seed))


def loss_fn(cfg, params, x, y, quant=None):
    q = operand(quant)
    x = x.reshape((x.shape[0], -1))
    last = len(params) - 1
    for i in range(last + 1):
        p = params[f"Dense_{i}"]
        x = jnp.dot(q(x), q(p["kernel"]), precision=HIGHEST) + p["bias"]
        if i < last:
            x = jax.nn.relu(x)
    logp = jax.nn.log_softmax(x)
    ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0].mean()
    return jnp.clip(ce, 0.0, 1e6)


def _macs(cfg):
    s = _sizes(cfg)
    return sum(a * b for a, b in zip(s, s[1:]))


def train_flops_per_sample(cfg, fed):
    return 3 * 2 * _macs(cfg)


def train_activation_bytes_per_sample(cfg, fed):
    return ITEMSIZE[cfg["compute_dtype"]] * (
        _sizes(cfg)[0] + 2 * sum(_sizes(cfg)[1:]))


def _widest_matmul(cfg, fed):
    """The widest layer's three contractions over a round's trained lanes,
    its kernel read once a lane and its output written and read."""
    s = _sizes(cfg)
    a, b = max(zip(s, s[1:]), key=lambda ab: ab[0] * ab[1])
    n = fed["batch_size"] * fed["local_steps"] * trained_lanes(fed)
    act = ITEMSIZE[cfg["compute_dtype"]]
    return 6 * a * b * n, trained_lanes(fed) * a * b * 4 + 2 * act * b * n


WORKS = {"widest_matmul": _widest_matmul}
'''

DATA_KIND = '''"""Labelled images whose label is the brightest of ``num_classes`` stripes
(see data/class_mean_images.py for what a data kind provides)."""
import jax
import jax.numpy as jnp
import numpy as np


def make(spec, num_clients, cfg, seed):
    shape, classes = tuple(cfg["input_shape"]), cfg["num_classes"]
    cap, test = spec["train_per_client"], spec["test_per_client"]
    rng = np.random.default_rng([seed, 0x57A1])
    pool_y = rng.integers(0, classes, num_clients * (cap + test)).astype(
        np.int32)
    stripe = (jnp.arange(shape[0]) * classes // shape[0])[:, None, None]
    x = jnp.float32(spec["noise"]) * jax.random.normal(
        jax.random.PRNGKey(seed), pool_y.shape + shape, jnp.float32)
    x = x + (stripe == jnp.asarray(pool_y)[:, None, None, None])
    ids = np.arange(len(pool_y), dtype=np.int32).reshape(num_clients, -1)
    return {"x": x, "y": pool_y,
            "train": (ids[:, :cap], np.full(num_clients, cap, np.int32)),
            "test": (ids[:, cap:], np.full(num_clients, test, np.int32)),
            "dataset": {"name": spec["stands_in_for"], "input_shape": shape,
                        "num_classes": classes}}


def gather(data, part):
    ids, lengths = data[part]
    return data["x"][jnp.asarray(ids)], data["y"][ids], lengths


def batches(data, ids):
    return data["x"][jnp.asarray(ids)], jnp.asarray(data["y"][ids])
'''

CONFIG = {
    "model": "mlp_plain", "family": "mlp", "input_shape": [28, 28, 1],
    "hidden": [128, 256], "num_classes": 10, "param_dtype": "float32",
    "compute_dtype": "float32", "update_dtype": "bfloat16",
    "num_params": 784 * 128 + 128 + 128 * 256 + 256 + 256 * 10 + 10,
    "num_clients": 8, "num_malicious_clients": 2,
    "reference": {"client_block": 2, "rounds": 3, "chunk": 65536},
}
TRAFFIC = {
    "experiment_file": "blades_tpu/tuned_examples/toy_fedsgd.yaml",
    "arm": {"aggregator": "Median"},
    "overrides": {"evaluation_interval": 0, "global_model": "mlp_plain",
                  "adversary_config": {"type": "ALIE"},
                  "execution": "streamed", "client_block": 2,
                  "update_dtype": "bfloat16"},
    "data": {"kind": "stripe_images", "stands_in_for": "mnist",
             "train_per_client": 40, "test_per_client": 8, "noise": 0.5},
    "warmup_rounds": 3,
    "rehearsal": {"overrides": {}, "reference_client_block": 3},
}
# The program and the reference both compute in float32 here and store bf16
# rows; what is left is the order of float32 sums (read here on the CPU:
# losses 0, leaf gaps 2e-7, diffs 2e-5).
LIMITS = {"limits": {"loss_r1": 1e-5, "loss_r2": 1e-5, "loss_r3": 1e-5,
                     "agg1_worst_leaf": 1e-4, "change_worst_leaf": 1e-4,
                     "agg1_diff": 1e-3, "change_diff": 1e-3,
                     "change_energy": 1e-6}}
METRIC = {"reader": "roofline", "patterns": ["_train_block"],
          "work": "widest_matmul", "name": "widest_matmul_roofline",
          "unit": "%", "layer": "local training: core/task.py, models/mlp.py",
          "moves": "rounds_per_s"}


def _hashes(root):
    out = {}
    for base, _, names in os.walk(root):
        if "__pycache__" in base:
            continue
        for n in names:
            p = os.path.join(base, n)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    from blades_tpu.models import register_model
    from blades_tpu.models.mlp import MLP

    register_model("mlp_plain", lambda **kw: MLP(dropout_rate=0.0, **kw))
    tmp = tmp_path_factory.mktemp("seam")
    shutil.copytree(os.path.join(CHECKOUT, "perfbench"), tmp / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "blades_tpu"), tmp / "blades_tpu")
    before = _hashes(tmp / "perfbench")

    def write(rel, content):
        path = tmp / "perfbench" / rel
        assert not path.exists(), rel        # new files only
        path.write_text(content if isinstance(content, str)
                        else json.dumps(content, indent=1))

    write("families/mlp.py", FAMILY)
    write("data/stripe_images.py", DATA_KIND)
    write("configs/mlp_mnist28.json", CONFIG)
    write("traffic/n8_alie_median_mlp.json", TRAFFIC)
    write("cells/mlp_median.json", LIMITS)
    write("metrics/widest_matmul_roofline.json", METRIC)
    doc = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    kept = json.dumps(doc, sort_keys=True)
    doc["configs"].append({
        "name": "mlp_mnist28", "source": "blades_tpu/models/mlp.py",
        "file": "perfbench/configs/mlp_mnist28.json", "reduced": [],
        "why": "a second family"})
    doc["workloads"].append({
        "name": "mlp_median", "config": "mlp_mnist28",
        "traffic": "n8_alie_median_mlp", "chips": 1, "why": "the seam"})
    doc["per_layer"].append({
        "name": "widest_matmul_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": METRIC["layer"],
        "moves": "rounds_per_s", "workloads": ["mlp_median"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    os.environ["PERFBENCH_OUT"] = str(tmp / "out")
    os.environ["BLADES_TPU_DATA_ROOT"] = str(tmp / ".no_data")
    yield {"root": str(tmp), "before": before, "kept": kept,
           "reference_cache": {}}
    os.environ.pop("PERFBENCH_OUT", None)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_new_family_runs_correct_from_new_files_alone(checkout, trace):
    code, result = C.run_cell(
        checkout["root"], "mlp_median", SEED, 0.1, bool(trace),
        time.perf_counter(), rehearse=True,
        reference_cache=checkout["reference_cache"])
    assert code == 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["compared"]) == set(LIMITS["limits"])
    if not trace:
        assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    else:   # no chip: what needs a device's trace or its peaks is left out
        assert "round_ms_p50" in result["metrics"]
        assert "widest_matmul_roofline" not in result["metrics"]
    # Nothing that was there was edited: the repo's files in the copy are
    # the repo's files still, and the old entries of BENCHMARK.json too.
    after = _hashes(os.path.join(checkout["root"], "perfbench"))
    assert {k: after[k] for k in checkout["before"]} == checkout["before"]
    assert checkout["before"] == _hashes(os.path.join(CHECKOUT, "perfbench"))
    assert sorted(set(after) - set(checkout["before"])) == [
        "cells/mlp_median.json", "configs/mlp_mnist28.json",
        "data/stripe_images.py", "families/mlp.py",
        "metrics/widest_matmul_roofline.json",
        "traffic/n8_alie_median_mlp.json"]
    doc = json.load(open(os.path.join(checkout["root"], "BENCHMARK.json")))
    for kind, n in (("configs", 1), ("workloads", 1), ("per_layer", 1)):
        doc[kind] = doc[kind][:-n]
    assert json.dumps(doc, sort_keys=True) == checkout["kept"]


def test_the_control_fails_the_new_family_too(checkout):
    from pb import compare

    cell = C.Cell(Manifest(checkout["root"]), "mlp_median", SEED,
                  rehearse=True)
    assert cell.reference_block == 3 and cell.describe["model"] == "mlp_plain"
    ref = checkout["reference_cache"].get(("mlp_median", SEED, True)) \
        or cell.follow()
    control = cell.follow(quant="fp8")
    cell.free()
    ok, report = compare.decide(compare.numbers(control, ref), cell.limits)
    assert not ok, report


def test_the_new_metric_reads_the_familys_own_work(checkout):
    m = Manifest(checkout["root"])
    files = m.cell("mlp_median")
    fam = family(files["config"]["family"], m.root)
    fed = {"num_clients": 8, "elided_lanes": 2, "stored_rows": 6,
           "batch_size": 16, "local_steps": 1}
    flops, nbytes = costs.work(fam, "widest_matmul")(files["config"], fed)
    assert flops == 6 * 784 * 128 * 16 * 6
    assert nbytes == 6 * 784 * 128 * 4 + 2 * 4 * 128 * 16 * 6
    assert costs.work(fam, "train")(files["config"], fed)[0] == \
        6 * (784 * 128 + 128 * 256 + 256 * 10) * 16 * 6
    with pytest.raises(KeyError):       # the other family has no such work
        costs.work(family("cifar_resnet"), "widest_matmul")
    # Through the metric's own file and the shared reader, on a device trace
    # of two launches of 1 ms each in one round.
    spec = m.metric_file("widest_matmul_roofline")
    ctx = {"trace": {"host": [["bench/round", 0.0, 6e6]],
                     "devices": {"/device:TPU:0": {"modules": [
                         ["jit__train_block", 0.0, 1e6],
                         ["jit__train_block", 2e6, 1e6],
                         ["jit__finish", 4e6, 1e6]]}}},
           "family": fam, "config": files["config"], "federation": fed,
        "peaks": m.peaks("TPU v5 lite"), "traced_rounds": 1, "notes": {}}
    share = reader(spec["reader"], m.root)(ctx, spec)
    least = max(flops / 197e12, nbytes / 819e9)
    assert share == pytest.approx(100.0 * least / 2e-3)
    assert ctx["notes"]["widest_matmul_roofline.bound_by"] == "hbm_bytes"
