"""Real-data loader tests (VERDICT r1 #6: the IDX/pickle readers were dead
code in practice — every accuracy number came from the synthetic fallback).

Fixtures write tiny files in the STANDARD raw formats (IDX for MNIST-like,
CIFAR python pickles) into a temp data root; the loaders must parse them,
normalise, and mark the dataset non-synthetic.
"""

import gzip
import pickle

import numpy as np
import pytest

from blades_tpu.data import DatasetCatalog

N_TRAIN, N_TEST = 48, 16


def _write_idx(path, arr, compress=False):
    header = bytes([0, 0, 0x08, arr.ndim]) + b"".join(
        int(d).to_bytes(4, "big") for d in arr.shape
    )
    payload = header + arr.astype(np.uint8).tobytes()
    if compress:
        path = path.with_suffix(path.suffix + ".gz")
        path.write_bytes(gzip.compress(payload))
    else:
        path.write_bytes(payload)


@pytest.fixture()
def data_root(tmp_path, monkeypatch):
    monkeypatch.setenv("BLADES_TPU_DATA_ROOT", str(tmp_path))
    rng = np.random.default_rng(0)

    # MNIST-like IDX (train gzipped to cover both openers).
    for sub in ("mnist", "fashionmnist"):
        d = tmp_path / sub
        d.mkdir()
        _write_idx(d / "train-images-idx3-ubyte",
                   rng.integers(0, 255, (N_TRAIN, 28, 28)), compress=True)
        _write_idx(d / "train-labels-idx1-ubyte",
                   rng.integers(0, 10, (N_TRAIN,)), compress=True)
        _write_idx(d / "t10k-images-idx3-ubyte",
                   rng.integers(0, 255, (N_TEST, 28, 28)))
        _write_idx(d / "t10k-labels-idx1-ubyte",
                   rng.integers(0, 10, (N_TEST,)))

    # CIFAR-10 python pickles.
    c10 = tmp_path / "cifar10" / "cifar-10-batches-py"
    c10.mkdir(parents=True)
    per = N_TRAIN // 5 + 1
    for i in range(1, 6):
        with open(c10 / f"data_batch_{i}", "wb") as f:
            pickle.dump({b"data": rng.integers(0, 255, (per, 3072), dtype=np.uint8),
                         b"labels": list(rng.integers(0, 10, (per,)))}, f)
    with open(c10 / "test_batch", "wb") as f:
        pickle.dump({b"data": rng.integers(0, 255, (N_TEST, 3072), dtype=np.uint8),
                     b"labels": list(rng.integers(0, 10, (N_TEST,)))}, f)

    # CIFAR-100 python pickles (fine_labels).
    c100 = tmp_path / "cifar100" / "cifar-100-python"
    c100.mkdir(parents=True)
    for split, n in (("train", N_TRAIN), ("test", N_TEST)):
        with open(c100 / split, "wb") as f:
            pickle.dump({b"data": rng.integers(0, 255, (n, 3072), dtype=np.uint8),
                         b"fine_labels": list(rng.integers(0, 100, (n,)))}, f)
    return tmp_path


@pytest.mark.parametrize("name,shape,ncls,n_train", [
    ("mnist", (28, 28, 1), 10, N_TRAIN),
    ("fashionmnist", (28, 28, 1), 10, N_TRAIN),
    ("cifar10", (32, 32, 3), 10, (N_TRAIN // 5 + 1) * 5),
    ("cifar100", (32, 32, 3), 100, N_TRAIN),
])
def test_real_loader(data_root, name, shape, ncls, n_train):
    ds = DatasetCatalog.get_dataset(name, num_clients=4, seed=0)
    assert not ds.synthetic
    assert ds.input_shape == shape
    assert ds.num_classes == ncls
    assert ds.test_x.shape == (N_TEST,) + shape
    assert ds.test_x.dtype == np.float32
    assert int(ds.train.lengths.sum()) == n_train
    assert 0 <= ds.test_y.min() and ds.test_y.max() < ncls
    # Normalisation happened: raw u8 range is gone.
    assert ds.test_x.max() < 20.0 and ds.test_x.min() < 0.0


def test_real_data_trains_end_to_end(data_root):
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=4)
        .training(global_model="mlp", server_lr=1.0, train_batch_size=8)
        .evaluation(evaluation_interval=2)
    )
    algo = cfg.build()
    assert not algo.dataset.synthetic
    r = [algo.train() for _ in range(2)][-1]
    assert np.isfinite(r["train_loss"])
    assert "test_acc" in r


@pytest.mark.slow  # the shrunk ResNet-34 point is still minutes of CPU compile
def test_cifar100_yaml_runs_two_rounds(tmp_path):
    """BASELINE config 5's YAML parses (DnC + FLTrust grid); a shrunk
    DnC instance runs 2 rounds with ResNet-34.  The FLTrust point is
    pinned out of the run — each grid point is its own ~5-minute
    ResNet-34 CPU compile, and FLTrust is exercised end-to-end by
    test_aggregators/test_dsharded."""
    from pathlib import Path

    from blades_tpu.tune import (
        expand_grid,
        load_experiments_from_file,
        run_experiments,
    )

    yml = (Path(__file__).parent.parent / "blades_tpu" / "tuned_examples"
           / "fedavg_cifar100_resnet34.yaml")
    experiments = load_experiments_from_file(str(yml))
    [spec] = experiments.values()
    assert len(expand_grid(spec["config"])) == 2  # DnC, FLTrust
    # Shrink to CI scale: same model family/dataset/adversary, tiny counts.
    # evaluation_interval > max rounds: the eval program is a second
    # ResNet-34 CPU compile (~8 min of pure compile time in CI) and the
    # eval path is covered by every other integration test.
    spec["config"]["dataset_config"].update(num_clients=6, train_bs=4)
    spec["config"]["num_malicious_clients"] = 1
    spec["config"]["evaluation_interval"] = 50
    spec["config"]["server_config"]["aggregator"] = {"type": "DnC"}
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        max_rounds_override=2,
    )
    assert len(summaries) == 1
    for s in summaries:
        assert s["rounds"] == 2
