"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program.  It builds the
cell's trial the way a user's sweep does (experiment file -> grid arm ->
``config.build()``), hands it the harness's data and weights, and exposes
what the harness reads back: the round call, the server's parameters, the
pass counts and the compile log.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
)


class CompileLog:
    """What JAX compiled in this process, from ``jax.monitoring``: seconds
    spent tracing + lowering + compiling, the programs the backend compiled
    (by name), and persistent-cache requests and hits.  ``mark()`` returns a
    snapshot; the difference of two snapshots is what happened between."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.backend_compiles = 0
        self.programs = {}
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _BACKEND_COMPILE:
            name = kw.get("fun_name", "?")
            self.programs[name] = self.programs.get(name, 0) + 1
            self.backend_compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def mark(self) -> dict:
        return {"seconds": self.seconds,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.cache_hits,
                "cache_requests": self.cache_requests}

    @staticmethod
    def between(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def place_compile_cache(checkout: str) -> str:
    """JAX's persistent cache at a fixed directory inside the checkout (the
    path is part of the cache's key), unless ``JAX_COMPILATION_CACHE_DIR``
    places it from outside.  Every program is kept, however quick."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def trial_dict(checkout: str, traffic: dict) -> dict:
    """The experiment file's grid arm the traffic names, with the traffic's
    overrides laid over it, as one flat-or-nested config dict."""
    from blades_tpu.tune import expand_grid, load_experiments_from_file

    (spec,) = load_experiments_from_file(
        os.path.join(checkout, traffic["experiment_file"])).values()
    want = traffic["arm"]["aggregator"]
    (trial,) = [t for t in expand_grid(spec["config"])
                if t["server_config"]["aggregator"]["type"] == want]
    return {"run": spec["run"], "trial": trial,
            "overrides": traffic.get("overrides", {})}


def build_config(found: dict, seed: int):
    from blades_tpu.algorithms import get_algorithm_class

    _, config = get_algorithm_class(found["run"], return_config=True)
    config.update_from_dict(found["trial"])
    config.update_from_dict(found["overrides"])
    config.update_from_dict({"seed": int(seed)})
    return config


def federation(config) -> dict:
    """What the reference and the cost arithmetic need to know of the
    federation, read off the built config (not off the cell's name)."""
    agg = config.aggregator
    agg = dict(agg) if isinstance(agg, dict) else {"type": str(agg)}
    f = int(config.num_malicious_clients)
    block = int(config.client_block)
    elided = (f // block) * block if config.execution == "streamed" else 0
    compact = (elided == f and f > 0
               and agg["type"] in ("Mean", "Median", "Trimmedmean"))
    return {
        "num_clients": int(config.num_clients),
        "num_malicious_clients": f,
        "batch_size": int(config.train_batch_size),
        "local_steps": int(config.num_batch_per_round),
        "client_lr": float(config.client_lr),
        "client_momentum": float(config.client_momentum or 0.0),
        "server_lr": float(config.server_lr),
        "server_momentum": float(config.server_momentum or 0.0),
        "aggregator": agg,
        "adversary": dict(config.adversary_config or {}),
        "elided_lanes": elided,
        "stored_rows": int(config.num_clients) - (f if compact else 0),
        "client_block": block,
        "execution": str(config.execution),
    }


def build(config, kind, data: dict):
    """The algorithm object on the harness's data: each part as the data
    kind lays it out, and what the kind says of the data beside the arrays
    (``data["dataset"]``) passed on by keyword."""
    from blades_tpu.data.datasets import FLDataset
    from blades_tpu.data.partition import Partition

    parts = {}
    for name in ("train", "test"):
        x, y, lengths = kind.gather(data, name)
        parts[name] = Partition(x=x, y=y, lengths=lengths)
    config.data(dataset=FLDataset(
        train=parts["train"], test_x=None, test_y=None, test=parts["test"],
        synthetic=True, **data["dataset"]))
    return config.build()


def place_weights(algo, params) -> None:
    """Start the program from the harness's weights: same tree, same
    shapes, or an error."""
    server = algo.state.server
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), server.params)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError("the configuration's file and the program "
                         f"disagree on the model: {want} != {got}")
    algo.state = dataclasses.replace(
        algo.state, server=dataclasses.replace(server, params=params))


def server_params(algo):
    """The server's parameters, fetched to the host."""
    return jax.device_get(algo.state.server.params)


def describe(algo) -> dict:
    cfg = algo.config
    if not algo.dataset.synthetic:
        raise AssertionError("real data was loaded")
    if algo.plan is not None:
        raise AssertionError("an autotune plan was resolved")
    return {"model": cfg.global_model, "params": int(algo._num_params),
            "clients": int(cfg.num_clients),
            "malicious": int(cfg.num_malicious_clients),
            "execution": cfg.execution,
            "client_block": int(cfg.client_block),
            "update_dtype": str(cfg.update_dtype),
            "evaluation_interval": int(cfg.evaluation_interval or 0)}


def round_failed(row: dict) -> bool:
    loss = float(row.get("train_loss", np.nan))
    return not (np.isfinite(loss) and bool(row.get("round_ok", True)))
