"""Core train-step layer tests: local rounds, server step, full FL round.

Model: the reference's tiny-fixture integration tests
(ref: blades/algorithms/fedavg/tests/test_fedavg.py) — a small synthetic
dataset + small model driven end-to-end, asserting learning happens and
state flows correctly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.data import DatasetCatalog
from blades_tpu.data.sampler import sample_batch, sample_client_batches
from blades_tpu.utils.tree import ravel_fn


@pytest.fixture(scope="module")
def tiny():
    ds = DatasetCatalog.get_dataset("mnist", num_clients=6)
    task = TaskSpec(model="mlp", lr=0.1, input_shape=(28, 28, 1)).build()
    server = Server.from_config(aggregator="Mean", lr=1.0)
    fr = FedRound(task=task, server=server, batch_size=16, num_batches_per_round=2)
    state = fr.init(jax.random.PRNGKey(0), 6)
    arrays = (
        jnp.array(ds.train.x), jnp.array(ds.train.y), jnp.array(ds.train.lengths),
    )
    return ds, fr, state, arrays


def test_sampler_never_selects_padding():
    x = jnp.arange(20.0).reshape(10, 2)
    y = jnp.arange(10)
    # true length 4: indices must stay < 4
    for s in range(5):
        bx, by = sample_batch(jax.random.PRNGKey(s), x, y, jnp.array(4), 8)
        assert (by < 4).all()


def test_sampler_shapes_and_decorrelation():
    x = jnp.zeros((3, 50, 2))
    y = jnp.broadcast_to(jnp.arange(50), (3, 50))
    ln = jnp.array([50, 50, 50])
    bx, by = sample_client_batches(jax.random.PRNGKey(0), x, y, ln, 8, 4)
    assert bx.shape == (3, 4, 8, 2) and by.shape == (3, 4, 8)
    assert not jnp.array_equal(by[0], by[1])  # lanes decorrelated


def test_local_round_update_is_param_delta(tiny):
    ds, fr, state, (x, y, ln) = tiny
    task = fr.task
    ravel, _, d = ravel_fn(state.server.params)
    bx, by = sample_client_batches(jax.random.PRNGKey(3), x, y, ln, 16, 2)
    upd, opt, loss, _ = task.local_round(
        state.server.params, jax.tree.map(lambda a: a[0], state.client_opt),
        bx[0], by[0], jax.random.PRNGKey(4), jnp.array(False),
    )
    assert upd.shape == (d,)
    assert jnp.isfinite(upd).all() and float(jnp.linalg.norm(upd)) > 0
    assert float(loss) > 0


@pytest.mark.parametrize("lanes", [1, 3])
def test_unraveled_update_is_the_raveled_one_leaf_by_leaf(tiny, lanes):
    """``ravel_update=False`` (ISSUE 32): the block's updates as the
    params' pytree, cast per leaf and not concatenated, for a caller
    that lays a row out itself.  Concatenated leaf by leaf they are the
    raveled updates to the bit, and ``row_planes`` lays one lane's leaves
    out as it lays out that lane's raveled row."""
    from blades_tpu.ops.pallas_store import row_planes

    ds, fr, state, (x, y, ln) = tiny
    bx, by = sample_client_batches(jax.random.PRNGKey(3), x, y, ln, 16, 2)
    keys = jax.random.split(jax.random.PRNGKey(4), lanes)
    args = (state.server.params,
            jax.tree.map(lambda a: a[:lanes], state.client_opt),
            bx[:lanes], by[:lanes], keys, jnp.zeros((lanes,), bool))
    flat = fr.task.local_round_batched(*args, out_dtype=jnp.bfloat16)[0]
    tree = fr.task.local_round_batched(*args, out_dtype=jnp.bfloat16,
                                       ravel_update=False)[0]
    assert (jax.tree.structure(tree)
            == jax.tree.structure(state.server.params))
    leaves = jax.tree.leaves(tree)
    assert all(leaf.dtype == jnp.bfloat16 and leaf.shape[0] == lanes
               for leaf in leaves)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(
            [leaf.reshape(lanes, -1) for leaf in leaves], axis=1),
            np.float32),
        np.asarray(flat, np.float32))
    if lanes == 1:
        tail = (-(-flat.shape[1] // 2048) * 16, 128)
        np.testing.assert_array_equal(
            np.asarray(row_planes(tree, tail), np.float32),
            np.asarray(row_planes(flat, tail), np.float32))


def test_unraveled_update_needs_the_per_leaf_cast_s_case(tiny):
    ds, fr, state, (x, y, ln) = tiny
    bx, by = sample_client_batches(jax.random.PRNGKey(3), x, y, ln, 16, 2)
    args = (state.server.params,
            jax.tree.map(lambda a: a[0], state.client_opt),
            bx[0], by[0], jax.random.PRNGKey(4), jnp.array(False))
    with pytest.raises(ValueError, match="ravel_update"):
        fr.task.local_round(*args, ravel_update=False)   # no out_dtype
    with pytest.raises(ValueError, match="ravel_update"):
        fr.task.local_round(*args, out_dtype=jnp.bfloat16,
                            round_end_hook=lambda u, m: u,
                            ravel_update=False)


def test_server_step_applies_update_direction(tiny):
    ds, fr, state, _ = tiny
    ravel, _, d = ravel_fn(state.server.params)
    # A constant update vector must move params by lr * update under plain SGD.
    upd = jnp.ones((3, d)) * 0.5
    new_state, agg = fr.server.step(state.server, upd)
    assert jnp.allclose(agg, 0.5)
    delta = ravel(new_state.params) - ravel(state.server.params)
    assert jnp.allclose(delta, 1.0 * 0.5, atol=1e-6)  # server lr = 1.0
    assert int(new_state.round) == 1


def test_full_round_learns(tiny):
    ds, fr, state, (x, y, ln) = tiny
    mal = jnp.zeros(6, bool)
    step = jax.jit(fr.step)
    losses = []
    for r in range(25):
        state, m = step(state, x, y, ln, mal, jax.random.fold_in(jax.random.PRNGKey(7), r))
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0] * 0.5
    ev = jax.jit(fr.evaluate)(
        state, jnp.array(ds.test.x), jnp.array(ds.test.y), jnp.array(ds.test.lengths)
    )
    assert float(ev["test_acc"]) > 0.8
    assert float(ev["num_samples"]) == float(jnp.array(ds.test.lengths).sum())


def test_round_determinism_same_seed(tiny):
    ds, fr, _, (x, y, ln) = tiny
    mal = jnp.zeros(6, bool)
    ravel, _, _ = ravel_fn(fr.init(jax.random.PRNGKey(0), 6).server.params)

    def run():
        st = fr.init(jax.random.PRNGKey(0), 6)
        step = jax.jit(fr.step)
        for r in range(3):
            st, _ = step(st, x, y, ln, mal, jax.random.fold_in(jax.random.PRNGKey(9), r))
        return ravel(st.server.params)

    a, b = run(), run()
    assert jnp.array_equal(a, b)


def test_lr_schedule_piecewise():
    from blades_tpu.core.server import lr_schedule

    sched = lr_schedule(0.1, [(0, 0.1), (100, 0.01)])
    assert np.isclose(float(sched(0)), 0.1)
    assert np.isclose(float(sched(100)), 0.01, atol=1e-4)
    # Linear interpolation midway.
    assert 0.01 < float(sched(50)) < 0.1


def test_bf16_compute_learns(tiny):
    ds, _, _, (x, y, ln) = tiny
    from blades_tpu.core import FedRound, Server, TaskSpec

    task = TaskSpec(model="mlp", lr=0.1, input_shape=(28, 28, 1),
                    compute_dtype="bfloat16").build()
    fr = FedRound(task=task, server=Server.from_config(aggregator="Mean", lr=1.0),
                  batch_size=16)
    st = fr.init(jax.random.PRNGKey(0), 6)
    # Params stay f32 masters.
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(st.server.params))
    step = jax.jit(fr.step)
    losses = []
    mal = jnp.zeros(6, bool)
    for r in range(20):
        st, m = step(st, x, y, ln, mal, jax.random.fold_in(jax.random.PRNGKey(3), r))
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0] * 0.6
