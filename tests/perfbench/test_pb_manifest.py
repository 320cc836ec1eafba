"""BENCHMARK.json keeps to the contract, and every name finds its file."""

import json
import os

import pytest

from pb import costs
from pb.manifest import (CHECKOUT, NAME, UNIT, Manifest, data_kind, family,
                         reader)

M = Manifest()
DOC = M.doc
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert 1 <= len(DOC["paths"]) <= 16 and len(DOC["command"]) <= 32
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536


def test_check_fits_the_budget_with_24_cells():
    s = DOC["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_of_the_allowed_characters_and_unique(kind):
    names = [e["name"] for e in DOC[kind]]
    assert len(names) == len(set(names))
    for e in DOC[kind]:
        assert NAME.match(e["name"]), e["name"]
        for k in ("config", "traffic"):
            if k in e:
                assert NAME.match(e[k]), e[k]
        for k in e.get("reduced", []):
            assert NAME.match(k), k
        for k in ("why", "layer", "source"):
            if k in e and kind != "end_to_end" and kind != "per_layer":
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_just_the_contract_keys(kind, keys):
    for e in DOC[kind]:
        assert set(e) - {"workloads"} == keys, e["name"]


@pytest.mark.parametrize("m", DOC["end_to_end"] + DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(m):
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert m["moves"] in M.end_to_end
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in m.get("workloads", []):
        assert w in M.workloads


def test_setup_s_is_an_end_to_end_metric_and_mfu_is_not():
    assert "setup_s" in M.end_to_end
    assert not any("mfu" in n for n in M.end_to_end)
    assert any("mfu" in n for n in M.per_layer)


def test_four_chip_share():
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)


@pytest.mark.parametrize("w", DOC["workloads"], ids=lambda w: w["name"])
def test_every_cells_files_are_found_by_name(w):
    files = M.cell(w["name"])
    cfg, traffic = files["config"], files["traffic"]
    assert os.path.isfile(os.path.join(CHECKOUT,
                                       traffic["experiment_file"]))
    assert set(files["limits"]["limits"]) >= {"loss_r1", "agg1_worst_leaf"}
    assert {"family", "num_params", "reference"} <= set(cfg)
    assert {"overrides", "reference_client_block"} <= set(
        traffic["rehearsal"])
    fam = family(cfg["family"])
    for need in ("layer_shapes", "num_params", "init_params", "loss_fn",
                 "train_flops_per_sample",
                 "train_activation_bytes_per_sample"):
        assert callable(getattr(fam, need)), need
    assert isinstance(fam.WORKS, dict)
    kind = data_kind(traffic["data"]["kind"])
    for need in ("make", "gather", "batches"):
        assert callable(getattr(kind, need)), need
    for kind in ("end_to_end", "per_layer"):
        assert M.metrics_of(w["name"], kind)
    for m in M.metrics_of(w["name"], "per_layer"):
        spec = M.metric_file(m["name"])
        assert spec["unit"] == m["unit"] and spec["moves"] == m["moves"]
        assert spec["layer"] == m["layer"]
        assert callable(reader(spec["reader"]))
        if "work" in spec:
            flops, nbytes = costs.work(fam, spec["work"])(
                cfg, {"num_clients": cfg["num_clients"], "elided_lanes": 0,
                      "stored_rows": cfg["num_clients"], "batch_size": 32,
                      "local_steps": 1})
            assert flops >= 0 and nbytes > 0


@pytest.mark.parametrize("find", [reader, family, data_kind],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ["../pb/cell", "a/b", "", "x" * 65, "a b"])
def test_a_name_that_is_no_name_finds_no_file(find, name):
    assert not NAME.match(name)
    with pytest.raises(ValueError):
        find(name)


@pytest.mark.parametrize("find", [reader, family, data_kind],
                         ids=lambda f: f.__name__)
def test_a_name_with_no_file_is_an_error_and_a_file_loads_once(find):
    with pytest.raises(KeyError):
        find("there_is_no_such_file")
    name = {"reader": "roofline", "family": "cifar_resnet",
            "data_kind": "class_mean_images"}[find.__name__]
    assert find(name) is find(name)


@pytest.mark.parametrize("c", DOC["configs"], ids=lambda c: c["name"])
def test_config_files_lie_under_paths_and_cut_no_width(c):
    assert any(c["file"].startswith(p + "/") for p in DOC["paths"])
    used = {w["config"] for w in DOC["workloads"]}
    assert c["name"] in used
    for k in c["reduced"]:
        assert not (k.endswith("_dim") or k.endswith("_rank")
                    or "width" in k or "hidden" in k), k
    cfg = json.load(open(os.path.join(CHECKOUT, c["file"])))
    assert family(cfg["family"]).num_params(cfg) == cfg["num_params"]


def test_peaks_table_is_keyed_by_device_kind_and_has_no_default():
    assert M.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert M.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        M.peaks("cpu")


def test_alone_in_a_directory_the_harness_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no program to measure: another exit code than 0, and
    nothing on standard output."""
    import shutil
    import subprocess
    import sys

    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    for p in DOC["paths"]:
        shutil.copytree(os.path.join(CHECKOUT, p), tmp_path / p)
    run = subprocess.run(
        [sys.executable] + DOC["command"][1:] + [
            "--workload", DOC["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "nothing to measure" in run.stderr
