"""``BENCHMARK.json`` and the files it names.

Whatever belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own, found here by its name:

- ``configs/<config>.json``  (the path is the entry's ``file``); its
  ``family`` names the model family
- ``families/<family>.py``    the model: weights, reference loss, required
  work (what a family provides: ``families/cifar_resnet.py``)
- ``traffic/<traffic>.json``  the program's experiment arm and overrides,
  the ``data`` block (its ``kind`` names the data kind), the rehearsal's sizes
- ``data/<kind>.py``          the clients' data from the seed (what a data
  kind provides: ``data/class_mean_images.py``)
- ``cells/<workload>.json``   the cell's limits for ``correct``
- ``metrics/<metric>.json``   the metric's reader and its parameters
- ``readers/<reader>.py``     a reader, shared by the metrics that name it
- ``peaks.json``              the chips' peaks, keyed by ``device_kind``

A PR that brings a new model family, data kind, configuration, traffic mix,
cell or metric adds files and entries, and edits none
(``tests/perfbench/test_pb_family_seam.py`` holds the harness to that).
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, checkout: str = CHECKOUT):
        self.checkout = checkout
        self.doc = _load(os.path.join(checkout, "BENCHMARK.json"))
        self.root = os.path.join(checkout, self.doc["paths"][0])
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def cell(self, workload: str) -> dict:
        """Everything one run of ``workload`` reads."""
        if workload not in self.workloads:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(self.workloads)}")
        w = self.workloads[workload]
        c = self.configs[w["config"]]
        return {
            "workload": w,
            "config": _load(os.path.join(self.checkout, c["file"])),
            "traffic": _load(os.path.join(
                self.root, "traffic", w["traffic"] + ".json")),
            "limits": _load(os.path.join(
                self.root, "cells", workload + ".json")),
        }

    def metrics_of(self, workload: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def metric_file(self, name: str) -> dict:
        return _load(os.path.join(self.root, "metrics", name + ".json"))

    def peaks(self, device_kind: str) -> dict:
        table = _load(os.path.join(self.root, "peaks.json"))["peaks"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"peaks.json ({sorted(table)}): add it with its "
                           "source, do not default it")
        return table[device_kind]


_MODULES = {}


def _find(root: str, package: str, name: str):
    """The module ``<root>/<package>/<name>.py``, loaded from that file (not
    from ``sys.path``: a manifest of another checkout finds its own) and
    once a process, so that what it jits compiles once."""
    if not NAME.match(name):
        raise ValueError(f"bad {package} name {name!r}")
    path = os.path.realpath(os.path.join(root, package, name + ".py"))
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise KeyError(f"no {package}/{name}.py under {root}")
        spec = importlib.util.spec_from_file_location(
            f"{package}.{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def reader(name: str, root: str = HERE):
    """``readers/<name>.py``'s ``read(ctx, spec)``."""
    return _find(root, "readers", name).read


def family(name: str, root: str = HERE):
    """``families/<name>.py``: the model family a configuration names."""
    return _find(root, "families", name)


def data_kind(name: str, root: str = HERE):
    """``data/<name>.py``: the data kind a traffic mix names."""
    return _find(root, "data", name)
