"""Streamed row-geometry aggregation vs the dense round.

The streamed path re-expresses every row-geometry aggregator as chunked
full-matrix passes (:mod:`blades_tpu.parallel.streamed_geometry`).  With
f32 storage the only divergence from the dense ``FedRound.step`` is
chunk-level reduction reassociation, so whole-round equivalence holds to
tight tolerances.  d and d_chunk are chosen so the matrix spans several
chunks including a ragged overlapping tail.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.adversaries import get_adversary, make_malicious_mask
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.parallel.streamed import streamed_step

N, F = 12, 3
D_CHUNK = 1024  # model d ~ 44k -> dozens of chunks + ragged tail


def _setup(aggregator, adversary=None, trusted=False, **fr_kw):
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator=aggregator, num_byzantine=F, lr=0.5)
    adv = (get_adversary(adversary, num_clients=N, num_byzantine=F)
           if adversary else None)
    rng = np.random.default_rng(0)
    extra = {}
    if trusted:
        extra["trusted_data"] = (
            jnp.asarray(rng.normal(size=(16, 8, 8, 1)), jnp.float32),
            jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32),
        )
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1, **extra, **fr_kw)
    x = jnp.asarray(rng.normal(size=(N, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(N, 8)), jnp.int32)
    lengths = jnp.full((N,), 8, jnp.int32)
    mal = make_malicious_mask(N, F)
    return fr, x, y, lengths, mal


def _run_both(fr, x, y, lengths, mal, rounds=2):
    dense = jax.jit(fr.step)
    streamed = streamed_step(fr, client_block=4, d_chunk=D_CHUNK,
                             update_dtype=jnp.float32, donate=False)
    sd = fr.init(jax.random.PRNGKey(0), N)
    ss = fr.init(jax.random.PRNGKey(0), N)
    for r in range(rounds):
        k = jax.random.fold_in(jax.random.PRNGKey(7), r)
        sd, md = dense(sd, x, y, lengths, mal, k)
        ss, ms = streamed(ss, x, y, lengths, mal, k)
    return sd, md, ss, ms


AGGS = ["GeoMed", "Multikrum", "DnC", "Centeredclipping", "Signguard",
        "Clippedclustering"]


@pytest.mark.parametrize("aggregator", AGGS)
def test_rowgeom_matches_dense(aggregator):
    fr, x, y, lengths, mal = _setup(aggregator, adversary="ALIE")
    sd, md, ss, ms = _run_both(fr, x, y, lengths, mal)
    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(ms[k]), float(md[k]), rtol=2e-4,
                                   atol=1e-5)
    for a, b in zip(jax.tree.leaves(ss.server.params),
                    jax.tree.leaves(sd.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_rowgeom_fltrust_matches_dense():
    fr, x, y, lengths, mal = _setup("FLTrust", adversary="IPM", trusted=True)
    sd, md, ss, ms = _run_both(fr, x, y, lengths, mal)
    for a, b in zip(jax.tree.leaves(ss.server.params),
                    jax.tree.leaves(sd.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_rowgeom_stateful_state_advances():
    """Centeredclipping's momentum and Clippedclustering's norm history
    thread through the streamed round like the dense one."""
    fr, x, y, lengths, mal = _setup("Centeredclipping")
    sd, _, ss, _ = _run_both(fr, x, y, lengths, mal)
    np.testing.assert_allclose(np.asarray(ss.server.agg_state),
                               np.asarray(sd.server.agg_state),
                               rtol=2e-4, atol=2e-5)
    fr, x, y, lengths, mal = _setup("Clippedclustering")
    sd, _, ss, _ = _run_both(fr, x, y, lengths, mal)
    assert int(ss.server.agg_state["count"]) == int(sd.server.agg_state["count"])
    np.testing.assert_allclose(
        np.sort(np.asarray(ss.server.agg_state["norm_history"])),
        np.sort(np.asarray(sd.server.agg_state["norm_history"])),
        rtol=2e-4, atol=2e-5,
    )


def test_rowgeom_alie_signguard_negates_global_half():
    """The round-1 landmine: ALIE's SignGuard evasion must negate the
    GLOBAL first half of the std under the chunked layout."""
    fr, x, y, lengths, mal = _setup("Signguard", adversary="ALIE")
    sd, _, ss, _ = _run_both(fr, x, y, lengths, mal, rounds=1)
    for a, b in zip(jax.tree.leaves(ss.server.params),
                    jax.tree.leaves(sd.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_rowgeom_dp_overlap_columns_not_reprocessed():
    """The tail chunk overlaps its predecessor; DP clip (non-idempotent)
    must not be applied twice to the overlap columns.  d_model (~44k) is
    not a multiple of D_CHUNK, so the tail overlap exists here."""
    fr, x, y, lengths, mal = _setup(
        "GeoMed", dp_clip_threshold=0.05, dp_noise_factor=0.0
    )
    sd, md, ss, ms = _run_both(fr, x, y, lengths, mal, rounds=1)
    for a, b in zip(jax.tree.leaves(ss.server.params),
                    jax.tree.leaves(sd.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_rowgeom_health_check_survives_nan_lane():
    fr, x, y, lengths, mal = _setup("Multikrum", health_check=True)
    streamed = streamed_step(fr, client_block=4, d_chunk=D_CHUNK,
                             update_dtype=jnp.float32, donate=False)
    st = fr.init(jax.random.PRNGKey(0), N)
    x_bad = x.at[2].set(jnp.nan)
    st, m = streamed(st, x_bad, y, lengths, mal, jax.random.PRNGKey(1))
    assert int(m["num_unhealthy"]) >= 1
    assert bool(m["round_ok"])
    assert all(bool(jnp.isfinite(p).all()) for p in
               jax.tree.leaves(st.server.params))


@pytest.mark.parametrize("adversary,aggregator", [
    ("MinMax", "Median"),
    ("MinMax", "Signguard"),          # SignGuard-evasion negate-half path
    ("SignGuard", "Mean"),
    ("Attackclippedclustering", "Clippedclustering"),
    ("MinMax", "Multikrum"),          # rowgeom forger + rowgeom aggregator
])
def test_rowgeom_forgers_match_dense(adversary, aggregator):
    """MinMax / SignGuard-attack / Attackclippedclustering forge via
    stats passes + a scatter; whole rounds match the dense path."""
    fr, x, y, lengths, mal = _setup(aggregator, adversary=adversary)
    rtol = 5e-3 if adversary == "MinMax" else 2e-4
    sd, md, ss, ms = _run_both(fr, x, y, lengths, mal)
    for a, b in zip(jax.tree.leaves(ss.server.params),
                    jax.tree.leaves(sd.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=5e-5)


def test_config_streamed_execution_accepts_rowgeom_aggregator():
    """execution='streamed' at the algorithm layer drives a row-geometry
    aggregator end-to-end."""
    from blades_tpu.algorithms import FedavgConfig

    algo = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=8)
        .training(global_model="mlp", server_lr=0.5,
                  aggregator={"type": "Multikrum"}, train_batch_size=4)
        .adversary(num_malicious_clients=2,
                   adversary_config={"type": "IPM"})
        .resources(execution="streamed", client_block=4)
        .build()
    )
    r = algo.train()
    assert np.isfinite(r["train_loss"])


def test_rowgeom_rejects_ghost_lanes():
    fr, x, y, lengths, mal = _setup("GeoMed")
    fr = FedRound(task=fr.task, server=fr.server, adversary=fr.adversary,
                  batch_size=4, num_batches_per_round=1, num_clients=N - 2)
    streamed = streamed_step(fr, client_block=4, d_chunk=D_CHUNK,
                             update_dtype=jnp.float32, donate=False)
    st = fr.init(jax.random.PRNGKey(0), N)
    with pytest.raises(ValueError, match="ghost"):
        streamed(st, x, y, lengths, mal, jax.random.PRNGKey(1))
