"""Forged-row separability vs synthetic heterogeneity (VERDICT r4 #3).

Question: at what per-client feature-drift dial ``h``
(``datasets._heterogenize_partition``) do ALIE's forged rows stop being
separable by the filtering defenses' own statistics — the precondition
for reproducing the published CIFAR-10 collapse of SignGuard /
ClippedClustering / CenteredClipping / DnC at 25-30% malicious
(``/root/reference/doc/source/images/cifar10.png``, ALIE row)?

Instead of burning a 36-cell accuracy grid per candidate ``h``, this
measures the defenses' DECISIONS directly on the forged update matrix,
per round, at small scale:

- ``sg_forged_kept``: fraction of forged rows surviving SignGuard's
  norm band + sign-census majority (the defense fails when ~1).
- ``ccl_forged_kept``: fraction of forged rows inside ClippedClustering's
  majority cosine cluster.
- ``dnc_forged_kept``: fraction kept by DnC's spectral outlier score.
- ``benign_cos``: mean pairwise cosine among benign rows (the spread the
  forged cluster must hide in; ~1 = the homogeneity problem).
- ``forged_z``: ||forged - benign_mean|| / mean ||benign_i - benign_mean||
  (how far outside the benign cloud the forged row sits).

Run (CPU is fine at this scale):
    python artifacts/alie_separability/measure.py [--out results.json]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

# Runnable from anywhere: the repo root is two levels up.
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


_FR_CACHE = {}


def _build_round(n, f, model, input_shape, num_classes):
    """fr + a jitted round compiled ONCE and reused for every h (the data
    is an argument, not a closure — a per-h closure would recompile the
    resnet10 round per grid point, ~25 min each on CPU)."""
    import jax

    from blades_tpu.adversaries import get_adversary
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.data.sampler import sample_client_batches

    key = (n, f, model, input_shape, num_classes)
    if key in _FR_CACHE:
        return _FR_CACHE[key]
    task = TaskSpec(model=model, input_shape=input_shape,
                    num_classes=num_classes, lr=0.1).build()
    server = Server.from_config(aggregator="Mean", lr=1.0)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=32)

    @jax.jit
    def round_updates(state, x, y, ln, mal, key):
        """Mirror of FedRound.step up to the forged matrix (round.py:148-176),
        returning the matrix for measurement plus the advanced state."""
        k_sample, k_train, k_adv, k_agg, _ = jax.random.split(key, 5)
        bx, by = sample_client_batches(k_sample, x, y, ln, fr.batch_size,
                                       fr.num_batches_per_round)
        hooks = fr._hooks()
        updates, client_opt, *_ = fr.task.local_round_batched(
            state.server.params, state.client_opt, bx, by,
            jax.random.split(k_train, n), mal, *hooks)
        forged = fr.adversary.on_updates_ready(
            updates, mal, k_adv, aggregator=fr.server.aggregator,
            global_params=state.server.params)
        server, _ = fr.server.step(state.server, forged, key=k_agg)
        return forged, type(state)(server=server, client_opt=client_opt)

    _FR_CACHE[key] = (fr, round_updates)
    return fr, round_updates


def measure_h(h: float, *, n=30, f=9, rounds=6, noise=3.0, alpha=0.1,
              model="resnet10", dataset="cifar10", seed=5):
    import jax
    import jax.numpy as jnp

    from blades_tpu.adversaries import make_malicious_mask
    from blades_tpu.data import DatasetCatalog
    from blades_tpu.ops import clustering

    ds = DatasetCatalog.get_dataset(
        {"type": dataset, "synthetic_noise": noise,
         "synthetic_heterogeneity": h},
        num_clients=n, iid=False, alpha=alpha, seed=seed)
    assert ds.synthetic
    x = jnp.array(ds.train.x)
    y = jnp.array(ds.train.y)
    ln = jnp.array(ds.train.lengths)
    mal = make_malicious_mask(n, f)
    mal_np = np.asarray(mal)

    fr, round_updates = _build_round(n, f, model, ds.input_shape,
                                     ds.num_classes)
    state = fr.init(jax.random.PRNGKey(0), n)

    rows = []
    for r in range(rounds):
        forged, state = round_updates(state, x, y, ln, mal,
                                      jax.random.PRNGKey(100 + r))
        U = np.asarray(forged, np.float64)
        ben = U[~mal_np]
        frg = U[mal_np]

        # Benign geometry.
        bn = ben / np.maximum(np.linalg.norm(ben, axis=1, keepdims=True),
                              1e-12)
        cos = bn @ bn.T
        iu = np.triu_indices(len(ben), 1)
        bmean = ben.mean(axis=0)
        bdev = np.linalg.norm(ben - bmean, axis=1).mean()
        forged_z = float(np.linalg.norm(frg[0] - bmean) / max(bdev, 1e-12))

        # SignGuard's decision (aggregators.py Signguard.aggregate).
        norms = np.linalg.norm(U, axis=1)
        M = np.median(norms)
        clipped = U * np.minimum(1.0, M / np.maximum(norms, 1e-12))[:, None]
        cn = np.minimum(norms, M)
        s1 = (cn >= 0.1 * M) & (cn <= 3.0 * M)
        s2 = np.asarray(clustering.kmeans_majority(
            clustering.sign_features(jnp.asarray(clipped, jnp.float32))))
        sg_mask = s1 & s2

        # ClippedClustering's majority cosine cluster (fresh threshold =
        # median norm, the steady-state value).
        cl = U * np.minimum(1.0, M / np.maximum(norms, 1e-12))[:, None]
        nn = cl / np.maximum(np.linalg.norm(cl, axis=1, keepdims=True), 1e-12)
        dist = 1.0 - np.clip(nn @ nn.T, -1.0, 1.0)
        ccl_mask = np.asarray(clustering.agglomerative_majority(
            jnp.asarray(dist, jnp.float32), linkage="average"))

        # DnC's decision, recomputed transparently with the SAME
        # coordinate subsample the aggregator would draw
        # (aggregators.py DnC: idx = permutation(k_iter, d)[:sub_dim]
        # for k_iter in split(key, num_iters); num_iters=1 here).
        k_iter = jax.random.split(jax.random.PRNGKey(r), 1)[0]
        idx = np.asarray(jax.random.permutation(k_iter, U.shape[1])[:10000])
        sub = U[:, idx]
        cen = sub - sub.mean(axis=0)
        v = np.linalg.svd(cen, full_matrices=False)[2][0]
        score = (cen @ v) ** 2
        keep = U.shape[0] - int(1.0 * f)
        dnc_mask = np.argsort(np.argsort(score)) < keep

        rows.append({
            "round": r,
            "benign_cos_mean": float(cos[iu].mean()),
            "benign_cos_std": float(cos[iu].std()),
            "forged_z": forged_z,
            "sg_forged_kept": float(sg_mask[mal_np].mean()),
            "sg_benign_kept": float(sg_mask[~mal_np].mean()),
            "ccl_forged_kept": float(ccl_mask[mal_np].mean()),
            "ccl_benign_kept": float(ccl_mask[~mal_np].mean()),
            "dnc_forged_kept": float(dnc_mask[mal_np].mean()),
            "dnc_benign_kept": float(dnc_mask[~mal_np].mean()),
        })
        print(json.dumps({"h": h, **rows[-1]}), flush=True)

    def avg(k):
        return round(float(np.mean([r[k] for r in rows[1:]])), 3)

    return {"h": h, "n": n, "f": f, "rounds": rounds, "noise": noise,
            "alpha": alpha, "model": model,
            **{k: avg(k) for k in rows[0] if k != "round"}}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=str(Path(__file__).parent / "results.json"))
    p.add_argument("--h-grid", nargs="+", type=float,
                   default=[0.0, 0.5, 1.0, 2.0, 4.0])
    p.add_argument("--model", default="resnet10")
    p.add_argument("--rounds", type=int, default=6)
    args = p.parse_args(argv)

    results = []
    for h in args.h_grid:
        results.append(measure_h(h, model=args.model, rounds=args.rounds))
        Path(args.out).write_text(json.dumps(results, indent=2))
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
