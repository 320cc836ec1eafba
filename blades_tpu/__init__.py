"""blades_tpu — a TPU-native Byzantine-robust federated-learning framework.

A from-scratch JAX/XLA re-design of the capabilities of the reference
``blades``/``fllib`` stack (dddkyi/blades): instead of Ray actors hosting
per-client PyTorch optimizers and shipping pseudo-gradients through an object
store, clients are a leading array axis.  Local SGD rounds are jit-compiled
trainsteps ``vmap``-ed over clients-per-chip and sharded over the ICI mesh
with ``shard_map``; robust aggregators and model-poisoning attacks are pure
``jnp`` ops on stacked ``(num_clients, num_params)`` update matrices; the
client→server gradient push is an on-device collective.

Package layout (mirrors the reference's layer map, SURVEY.md §1):

- :mod:`blades_tpu.ops`          robust aggregators (ref: fllib/aggregators/)
- :mod:`blades_tpu.adversaries`  attacks (ref: blades/adversaries/)
- :mod:`blades_tpu.models`       model zoo (ref: fllib/models/)
- :mod:`blades_tpu.data`         dataset + partitioner (ref: fllib/datasets/)
- :mod:`blades_tpu.core`         client/task/server train-step layer
                                 (ref: fllib/clients, fllib/tasks,
                                 fllib/algorithms/server.py)
- :mod:`blades_tpu.parallel`     mesh/sharding — replaces the reference's
                                 Ray execution layer (fllib/core/execution/)
                                 and NCCL communicator (fllib/communication/)
- :mod:`blades_tpu.algorithms`   FedAvg / FedAvg-DP drivers + config system
                                 (ref: fllib/algorithms, blades/algorithms)
- :mod:`blades_tpu.tune`         YAML experiment sweeps (ref: blades/train.py)
- :mod:`blades_tpu.utils`        pytree utilities
"""

__version__ = "0.1.0"

from blades_tpu import ops as ops  # noqa: F401


def __getattr__(name):
    # Lazy subpackage access (keeps `import blades_tpu` light; models/data
    # pull in flax/numpy loaders only when used).
    import importlib

    if name in ("adversaries", "algorithms", "core", "data", "models",
                "parallel", "tune", "utils"):
        return importlib.import_module(f"blades_tpu.{name}")
    raise AttributeError(f"module 'blades_tpu' has no attribute {name!r}")
