"""Task = model + loss + metrics + the local-SGD round (ref: fllib/tasks/task.py).

A ``Task`` binds a flax module to a loss and exposes pure functions:

- ``init`` — parameters + per-client optimizer state.
- ``train_one_batch`` — one SGD step (ref: task.py:170-186's
  zero_grad/forward/backward/step, as one ``value_and_grad`` step).
- ``local_round`` — ``num_batches`` steps via ``lax.scan``; returns the
  flat pseudo-gradient ``ravel(params_end) - ravel(params_start)`` (the
  sign convention is "update direction": the server *adds* the aggregate,
  ref: fllib/algorithms/server.py:109-130 writes ``-agg`` into ``.grad``
  and lets SGD subtract it — same fixed point).
- ``evaluate`` — summed cross-entropy + top-k accuracies over a client's
  test shard (ref: task.py:104-121, 188-202), masked for padding.

A model that declares ``sequence_model = True`` (models/mla_moe.py) makes
the task a SEQUENCE task: ``x`` is ``(batch, S)`` int32 tokens of packed
documents and ``y`` ``(batch, S)`` next-token targets, ``-1`` where the
next token lies in another document (or past the row): the loss is the
mean next-token cross-entropy over the positions that have a target, one
term per logits plane the model returns (MTP), and ``evaluate`` counts
tokens, so that ``exp(test_loss)`` is the perplexity.  Everything else (the
hooks, the scan, the update vector, ``vmap`` over clients) is shared.

Adversary interposition happens through two per-lane hooks threaded into
the scan — ``data_hook(x, y, malicious)`` (label-flip style, ref:
blades/adversaries/labelflip_adversary.py:10-16) and
``grad_hook(grads, malicious)`` (sign-flip style, ref:
signflip_adversary.py:9-15).  Both are branchless: they apply
``jnp.where(malicious, attacked, benign)`` so the whole federation stays
one jit program (SURVEY.md §7.3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from blades_tpu.models.catalog import ModelCatalog
from blades_tpu.utils.tree import ravel_fn

# Per-lane hooks: (x, y, malicious_flag) -> (x, y)  /  (grads_pytree, flag) -> grads
DataHook = Callable[[jax.Array, jax.Array, jax.Array], Tuple[jax.Array, jax.Array]]
GradHook = Callable[[Any, jax.Array], Any]


def identity_data_hook(x, y, malicious):
    del malicious
    return x, y


def identity_grad_hook(grads, malicious):
    del malicious
    return grads


def identity_round_begin_hook(params, opt_state, malicious):
    del malicious
    return params, opt_state


def identity_round_end_hook(update, malicious):
    del malicious
    return update


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """Declarative task config (ref: fllib/tasks/task.py:32-71)."""

    model: Any = "mlp"
    num_classes: int = 10
    input_shape: Tuple[int, ...] = (28, 28, 1)
    lr: float = 0.1
    momentum: float = 0.0
    loss_clamp: float = 1e6  # ref: fllib/tasks/mnist.py:12-14 clamps CE to [0, 1e6]
    # Keyed train-time augmentation ("cifar" = random crop + flip, the
    # reference's loader transforms, ref: fllib/datasets/cifar10.py:56-64).
    augment: Any = None
    # Mixed precision: forward/backward in this dtype (params, optimizer
    # state and the update vector stay f32 — standard bf16-compute/f32-master
    # recipe; bfloat16 feeds the MXU at full rate).
    compute_dtype: Any = None  # e.g. "bfloat16"

    def build(self) -> "Task":
        model = ModelCatalog.get_model(self.model, num_classes=self.num_classes)
        return Task(spec=self, model=model)


@dataclasses.dataclass(frozen=True)
class Task:
    spec: TaskSpec
    model: nn.Module

    # -- construction -------------------------------------------------------

    def client_optimizer(self) -> optax.GradientTransformation:
        """Per-client SGD (ref: fllib/clients/client_config.py lr/momentum)."""
        if self.spec.momentum:
            return optax.sgd(self.spec.lr, momentum=self.spec.momentum)
        return optax.sgd(self.spec.lr)

    @property
    def sequence(self) -> bool:
        """Next-token loss over ``(batch, S)`` int32 tokens (module
        docstring), declared by the model."""
        return bool(getattr(self.model, "sequence_model", False))

    def init_params(self, key: jax.Array):
        if self.sequence:
            # One compiled program: an eager init of a model this deep is
            # hundreds of one-operation programs.
            x = jnp.zeros((1,) + self.spec.input_shape, jnp.int32)
            return jax.jit(lambda k: self.model.init(
                {"params": k}, x)["params"])(key)
        x = jnp.zeros((1,) + self.spec.input_shape, jnp.float32)
        return self.model.init({"params": key, "dropout": key}, x)["params"]

    def init_client_opt_state(self, params):
        return self.client_optimizer().init(params)

    # -- pure compute -------------------------------------------------------

    def apply(self, params, x, *, train: bool = False, dropout_key=None):
        if getattr(self.model, "explicit_dropout", False):
            # Keyed-dropout models (models/layers.py): masks derive from
            # fold_in(dropout_key, layer_index) — pack-agnostic, which is
            # what lets the lane-packing path reproduce them exactly.
            return self.model.apply(
                {"params": params}, x, train=train, dropout_key=dropout_key
            )
        rngs = {"dropout": dropout_key} if dropout_key is not None else None
        return self.model.apply({"params": params}, x, train=train, rngs=rngs)

    def cast_to_compute(self, tree):
        """Cast floating leaves to ``spec.compute_dtype`` (identity when no
        mixed precision is configured) — the single source of the
        casting rule for the training paths."""
        if self.spec.compute_dtype is None:
            return tree
        dt = jnp.dtype(self.spec.compute_dtype)
        # Leaves the model names stay float32 whatever the compute type
        # (a router: its top-k flips on near-tied bf16 scores).
        keep = getattr(self.model, "float32_params", ())
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p.astype(dt)
            if jnp.issubdtype(p.dtype, jnp.floating)
            and not any(getattr(k, "key", None) in keep for k in path)
            else p, tree)

    def sequence_planes(self, params, tokens, *, stats: bool = False):
        """The sequence model's float32 logits planes ``(B, S, V)`` (and,
        with ``stats``, what its layers sowed, one stacked leaf a name)."""
        out = self.model.apply({"params": params}, tokens,
                               mutable=["stats"] if stats else False)
        if not stats:
            return out
        planes, state = out
        sown = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                state.get("stats", {})):
            name = [k.key for k in path if hasattr(k, "key")][-1]
            sown.setdefault(name, []).append(leaf)
        return planes, {k: jnp.stack(v) for k, v in sorted(sown.items())}

    @staticmethod
    def plane_targets(y, depth: int):
        """Targets of logits plane ``depth`` (it predicts token ``i + 1 +
        depth`` at position ``i``) from next-token targets ``y`` ``(..., S)``
        with ``-1`` where a position has none: valid where every step of
        the way stays inside the document."""
        t = y
        for _ in range(depth):
            ahead = jnp.concatenate(
                [t[..., 1:], jnp.full_like(t[..., :1], -1)], axis=-1)
            t = jnp.where(y >= 0, ahead, -1)
        return t

    def sequence_loss(self, params, x, y):
        """``(loss, stats)``: the mean next-token cross-entropy over the
        positions with a target, logits and loss in float32, one weighted
        term per plane; and what the model's layers sowed."""
        params = self.cast_to_compute(params)
        planes, sown = self.sequence_planes(params, x, stats=True)
        weights = getattr(self.model, "loss_weights", (1.0,) * len(planes))
        loss = 0.0
        for depth, (logits, w) in enumerate(zip(planes, weights)):
            t = self.plane_targets(y, depth)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), jnp.maximum(t, 0))
            valid = (t >= 0).astype(jnp.float32)
            loss = loss + w * (ce * valid).sum() / jnp.maximum(
                valid.sum(), 1.0)
        return jnp.clip(loss, 0.0, self.spec.loss_clamp), sown

    def loss_and_stats(self, params, x, y, dropout_key=None):
        """``(loss, stats)``: ``stats`` is what the model's layers sowed
        on this batch (a sequence model's expert counts), ``{}`` for every
        other model."""
        if self.sequence:
            return self.sequence_loss(params, x, y)
        return self.loss_fn(params, x, y, dropout_key), {}

    def round_counters(self, stats) -> dict:
        """A round's row counters from the trained lanes' stacked stats
        (:meth:`local_round_batched`), reduced by the model that sowed
        them (its ``round_counters``); ``{}`` where there are none."""
        reduce = getattr(self.model, "round_counters", None)
        return reduce(stats) if reduce is not None and stats else {}

    def loss_fn(self, params, x, y, dropout_key=None):
        if self.sequence:
            return self.sequence_loss(params, x, y)[0]
        if self.spec.compute_dtype is not None:
            dt = jnp.dtype(self.spec.compute_dtype)
            params = self.cast_to_compute(params)
            x = x.astype(dt)
        logits = self.apply(params, x, train=True, dropout_key=dropout_key)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()
        return jnp.clip(ce, 0.0, self.spec.loss_clamp)

    def train_one_batch(
        self,
        params,
        opt_state,
        x,
        y,
        key,
        malicious,
        data_hook: DataHook = identity_data_hook,
        grad_hook: GradHook = identity_grad_hook,
    ):
        """One local SGD step with adversary hooks (ref: task.py:170-186):
        ``(params, opt_state, loss, stats)``, ``stats`` as
        :meth:`loss_and_stats` gives them.

        Order matches the reference loader->callback pipeline: augmentation
        first (DataLoader transform), then the adversary's data hook
        (``on_train_batch_begin``).
        """
        from blades_tpu.data.augment import get_augmentation

        aug = get_augmentation(self.spec.augment)
        if aug is not None:
            k_aug, key = jax.random.split(key)
            x = aug(k_aug, x)
        x, y = data_hook(x, y, malicious)
        (loss, stats), grads = jax.value_and_grad(
            self.loss_and_stats, has_aux=True)(params, x, y, key)
        grads = grad_hook(grads, malicious)
        updates, opt_state = self.client_optimizer().update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, stats

    def local_round(
        self,
        global_params,
        opt_state,
        batches_x,
        batches_y,
        key,
        malicious,
        data_hook: DataHook = identity_data_hook,
        grad_hook: GradHook = identity_grad_hook,
        round_begin_hook=identity_round_begin_hook,
        round_end_hook=identity_round_end_hook,
        out_dtype=None,
        ravel_update: bool = True,
    ):
        """One client's full local round: scan SGD over ``num_batches``.

        Args:
            global_params: the round's incoming global params pytree.
            opt_state: this client's optimizer state (stacked outside).
            batches_x/batches_y: ``(num_batches, batch, ...)`` presampled.
            key: per-client PRNG key (dropout etc.).
            malicious: scalar bool — this lane's malicious flag.
            data_hook/grad_hook: per-batch hooks (callback chain +
                adversary, ref: fllib/clients/callbacks.py:33-48).
            round_begin_hook/round_end_hook: round-boundary hooks (ref:
                callbacks.py:25-31, :50-56); ``round_end`` edits the flat
                pseudo-gradient the way the reference's
                ``on_train_round_end`` edits ``pseudo_grad_vec``.
            out_dtype: storage dtype of the returned update vector (the
                streamed round's bf16 matrix).  With the identity
                round_end_hook the cast happens per LEAF before the
                concat — bit-identical values (cast commutes with
                concatenation), but the flat-vector assembly passes run
                at storage width instead of f32.
            ravel_update: ``False`` returns the update as the params'
                pytree of per-leaf differences, cast and NOT
                concatenated, for a caller that lays the row out itself
                (the streamed round's one-lane block, which must not
                let a ``(1, d)`` row be assembled: parallel/streamed.py
                ``_train_block``).  Only with ``out_dtype`` and the
                identity ``round_end_hook``, the per-leaf cast's case.

        Returns:
            ``(update_vec, new_opt_state, mean_loss, stats)`` where
            ``update_vec`` is the flat pseudo-gradient (ref: task.py:162-168,
            functionally) and ``stats`` what the model's layers sowed
            (:meth:`loss_and_stats`), summed over the local steps: ``{}``
            for a model that sows nothing.
        """
        ravel, _, _ = ravel_fn(global_params)
        num_batches = batches_x.shape[0]
        keys = jax.random.split(key, num_batches)
        params0, opt_state = round_begin_hook(global_params, opt_state, malicious)

        def step(carry, inp):
            params, opt_state = carry
            x, y, k = inp
            params, opt_state, loss, stats = self.train_one_batch(
                params, opt_state, x, y, k, malicious, data_hook, grad_hook
            )
            return (params, opt_state), (loss, stats)

        (params, opt_state), (losses, stats) = jax.lax.scan(
            step, (params0, opt_state), (batches_x, batches_y, keys)
        )
        # Pseudo-grad is always vs the INCOMING global params (the
        # reference snapshots the global weights, ref: task.py:159-168).
        if out_dtype is not None and round_end_hook is identity_round_end_hook:
            update = jax.tree.map(
                lambda p1, p0: (p1 - p0).astype(out_dtype),
                params, global_params,
            )
            if ravel_update:
                update = ravel(update)
        elif not ravel_update:
            raise ValueError("ravel_update=False needs out_dtype and the "
                             "identity round_end_hook")
        else:
            update = ravel(params) - ravel(global_params)
            update = round_end_hook(update, malicious)
            if out_dtype is not None:
                update = update.astype(out_dtype)
        return (update, opt_state, losses.mean(),
                jax.tree.map(lambda a: a.sum(0), stats))

    def local_round_batched(
        self,
        global_params,
        opt_states,
        batches_x,
        batches_y,
        client_keys,
        malicious,
        data_hook: DataHook = identity_data_hook,
        grad_hook: GradHook = identity_grad_hook,
        round_begin_hook=identity_round_begin_hook,
        round_end_hook=identity_round_end_hook,
        out_dtype=None,
        ravel_update: bool = True,
    ):
        """A whole client block's local rounds: ``(G, nb, B, ...)`` batches
        -> ``(updates (G, d), new_opt_states, losses (G,), stats)``, each
        lane's stats stacked (``{}`` for a model that sows nothing).
        With ``ravel_update=False`` the updates are the params' pytree
        with a leading ``G`` on every leaf (:meth:`local_round`).

        Semantically ``vmap(local_round)`` over the client axis.  (A
        merged-batch "FedSGD" formulation — one shared forward over
        ``(G*B, ...)`` with per-client weight grads via phantom
        parameters — was built and equivalence-tested in round 3 but
        measured ~1.5x SLOWER than this vmap on a v5e, XLA inserting
        transposes around every batch-grouped dW conv; removed in round
        4 per the review verdict rather than carried as permanently
        gated code.  It lives in git history should a pallas batched-dW
        kernel ever revive it.)
        """

        def one_client(opt_state, cbx, cby, ck, mal):
            return self.local_round(
                global_params, opt_state, cbx, cby, ck, mal,
                data_hook, grad_hook, round_begin_hook, round_end_hook,
                out_dtype=out_dtype, ravel_update=ravel_update,
            )

        return jax.vmap(one_client)(
            opt_states, batches_x, batches_y, client_keys, malicious
        )

    def evaluate(self, params, x, y, mask):
        """Masked eval over one client's padded test shard.

        Returns summed-CE loss, top-1/top-3 correct counts, and the sample
        count — so the driver can do the reference's weighted average
        (ref: blades/algorithms/fedavg/fedavg.py:268-277).  A sequence
        task counts tokens: ``count`` is the test rows' positions with a
        target, so the driver's ``ce_sum / count`` is the mean token loss
        and its exponential the perplexity.
        """
        if self.sequence:
            return self._evaluate_sequence(params, x, y, mask)
        logits = self.apply(params, x, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        m = mask.astype(jnp.float32)
        top1 = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        k = min(3, logits.shape[-1])
        topk_idx = jax.lax.top_k(logits, k)[1]
        topk = jnp.any(topk_idx == y[:, None], axis=-1).astype(jnp.float32)
        return {
            "ce_sum": (ce * m).sum(),
            "top1_sum": (top1 * m).sum(),
            "top3_sum": (topk * m).sum(),
            "count": m.sum(),
        }

    def _evaluate_sequence(self, params, x, y, mask):
        """One row at a time (``lax.map``): a row's ``(S, vocab)`` float32
        logits are the largest array alive."""

        def one_row(row):
            tokens, t, keep = row
            logits = self.sequence_planes(params, tokens[None])[0][0]
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.maximum(t, 0))
            m = (t >= 0).astype(jnp.float32) * keep.astype(jnp.float32)
            top3 = jax.lax.top_k(logits, min(3, logits.shape[-1]))[1]
            return jnp.stack([
                (ce * m).sum(),
                ((top3[:, 0] == t) * m).sum(),
                (jnp.any(top3 == t[:, None], axis=-1) * m).sum(),
                m.sum()])

        sums = jax.lax.map(one_row, (x, y, mask)).sum(0)
        return {"ce_sum": sums[0], "top1_sum": sums[1], "top3_sum": sums[2],
                "count": sums[3]}
