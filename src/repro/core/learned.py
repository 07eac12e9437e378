"""Learned construction distances (ISSUE 9): the paper's closing line.

The paper ends at "designing index-specific graph-construction distance
functions".  ``DistancePolicy`` made construction distances composable
(Blend / RankBlend), ``repro.core.autotune`` searches those fixed
parametric families — this module takes the next step and LEARNS one on a
calibration sample:

  1. fit a low-rank Mahalanobis map ``L`` by margin-ranking against
     ``knn_scan`` ground truth under the ORIGINAL non-metric distance
     (``metric_learning.fit_mahalanobis_map``);
  2. assemble a small candidate family over
     ``alpha * d(u,v) + (1-alpha) * proxy(d(v,u)) + beta * ||L^T(u-v)||^2``
     — blend alphas x Mahalanobis betas (scale-normalized so beta=1 means
     "as large as the typical base distance") x an optional rankblend
     proxy at the data-calibrated tau;
  3. measure every candidate AS a construction distance: build the index
     with it (same build key for all), search under the original
     distance, score recall against brute-force ground truth;
  4. select the best candidate whose distance-eval cost does not exceed
     the hand anchor's, and seal the winning weights into a
     fingerprint-checked artifact (``spec.learned_artifact``) that
     ``load_spec`` / ``serve.py --spec`` consume directly.

The candidate family ALWAYS contains the degenerate clone of the hand
anchor (``alpha = hand_alpha, beta = 0, tau = None``), which
``symmetrize.LearnedDistance`` evaluates with arithmetic bit-identical to
the distance ``Blend(hand_alpha)`` binds to — so with the shared build key the clone
reproduces the anchor's graph, evals and recall exactly, and the selected
candidate can never be worse than the anchor.  That by-construction
guarantee is what the CI gate (``benchmarks/bench_learned.py``) leans on.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .brute_force import knn_scan
from .index import ANNIndex
from .metric_learning import fit_mahalanobis_map
from .metrics import recall_at_k
from .spec import Learned, RetrievalSpec, learned_artifact
from .symmetrize import calibrate_tau, learned_weights_fingerprint


def mahalanobis_weights(L, alpha: float, beta: float,
                        tau: Optional[float] = None) -> dict:
    """Plain-JSON learned-weights dict (the registry / artifact currency).

    ``L`` may be None (no Mahalanobis term; required when ``beta == 0``)
    or an (m, rank) array, stored as nested float32 lists so the content
    fingerprint is platform-stable.
    """
    if (beta != 0.0) and L is None:
        raise ValueError("beta != 0 requires a Mahalanobis map L")
    return {
        "alpha": float(alpha),
        "beta": float(beta),
        "tau": None if tau is None else float(tau),
        "L": None if L is None or beta == 0.0
        else np.asarray(L, np.float32).tolist(),
    }


def _median_scales(dist, L, X, *, max_rows: int = 256):
    """(median |base distance|, median mapped-L2 distance) over a strided
    sample — the scale normalizer that makes candidate betas unit-free."""
    X = jnp.asarray(X)
    n = int(X.shape[0])
    stride = max(1, n // max_rows)
    S = X[::stride][:max_rows]
    m = int(S.shape[0])
    off = ~jnp.eye(m, dtype=bool)
    med_base = float(jnp.median(jnp.abs(dist.matrix(S, S)[off])))
    Z = S @ jnp.asarray(L, jnp.float32)
    n2 = jnp.sum(Z * Z, axis=1)
    D = jnp.maximum(n2[:, None] - 2.0 * (Z @ Z.T) + n2[None, :], 0.0)
    med_maha = float(jnp.median(D[off]))
    return med_base, med_maha


@dataclasses.dataclass(frozen=True)
class LearnedResult:
    """Outcome of ``fit_construction_distance``.

    ``spec`` is the winning learned spec (build_policy = ``learned(<fp>)``
    with the weights registered); ``candidates`` records every measured
    row — weights fingerprint, policy string, recall, evals — so the
    selection is auditable; ``anchor`` is the hand combinator's row.
    """

    spec: RetrievalSpec
    weights: dict
    fingerprint: str  # weights content fingerprint (== spec build_policy ref)
    objectives: dict
    anchor: dict
    candidates: tuple
    calibration: dict

    def artifact(self) -> dict:
        return learned_artifact(
            self.spec, self.weights, self.objectives, anchor=self.anchor,
            candidates=self.candidates, calibration=self.calibration,
            provenance={"selection": "max recall s.t. evals <= anchor evals"},
        )

    def save(self, path: str) -> dict:
        art = self.artifact()
        with open(path, "w") as f:
            json.dump(art, f, indent=1)
        return art


def fit_construction_distance(
    X,
    Q_cal,
    *,
    base: RetrievalSpec,
    dist=None,
    natural=None,
    hand_policy=None,
    rank: int = 16,
    steps: int = 150,
    n_anchors: int = 256,
    k_pos: int = 10,
    alphas=(0.5, 0.75, 1.0),
    betas=(0.25, 1.0),
    with_rank_proxy: bool = True,
    seed: int = 0,
    verbose: bool = True,
) -> LearnedResult:
    """Learn an index-specific construction distance on a calibration sample.

    Args:
        X: (n, m) database rows (the corpus being indexed).
        Q_cal: (B, m) calibration queries (measure recall on these; keep a
            holdout for honesty checks).
        base: the ``RetrievalSpec`` scenario everything else is pinned to
            (builder / engine / k / ef_search); its ``build_policy`` is
            ignored — the candidates supply it.
        dist: optional explicit base distance (e.g. a ``ViewedDistance``
            the registry cannot name); defaults to
            ``base.base_distance()``.
        natural: forwarded to ``ANNIndex.build`` for ``natural``-mode
            search policies.
        hand_policy: the hand combinator to anchor against; defaults to
            ``Blend(0.75)`` — the BENCH_spec winner.  NOTE: alpha must not
            be one of Blend's lowered special cases {0, 0.5, 1} for the
            degenerate-clone bit-parity guarantee to hold exactly.
        rank / steps / n_anchors / k_pos: ``fit_mahalanobis_map`` knobs.
        alphas / betas: candidate grid; betas are unit-free (scaled by the
            measured base/Mahalanobis median-distance ratio).
        with_rank_proxy: also try rankblend-compressed variants at the
            data-calibrated tau.
        seed: master PRNG seed (training batches AND the shared build key).

    Returns:
        A ``LearnedResult`` whose spec's recall is >= the anchor's at
        equal-or-fewer distance evals per query (by construction: the
        degenerate clone of the anchor is always in the family).
    """
    from .spec import Blend

    X = jnp.asarray(X)
    Q_cal = jnp.asarray(Q_cal)
    if dist is None:
        dist = base.base_distance()
    hand_policy = hand_policy if hand_policy is not None else Blend(0.75)
    hand_alpha = float(hand_policy.alpha if hand_policy.alpha is not None else 1.0)

    key = jax.random.PRNGKey(seed)
    k_fit, k_build = jax.random.split(key)

    # -- 1. fit the low-rank Mahalanobis map on true neighborhoods ----------
    L = fit_mahalanobis_map(X, dist, k_fit, rank=rank, steps=steps,
                            n_anchors=n_anchors, k_pos=k_pos)
    med_base, med_maha = _median_scales(dist, L, X)
    beta_unit = med_base / med_maha if med_maha > 0.0 and med_base > 0.0 else 0.0
    tau_cal = calibrate_tau(dist, X)

    # -- 2. candidate family (degenerate anchor clone ALWAYS included) ------
    cand_weights = [mahalanobis_weights(None, hand_alpha, 0.0)]
    if beta_unit > 0.0:
        for a in alphas:
            for b in betas:
                cand_weights.append(mahalanobis_weights(L, a, b * beta_unit))
        if with_rank_proxy:
            for a in alphas:
                if a < 1.0:  # tau only touches the reverse branch
                    cand_weights.append(
                        mahalanobis_weights(L, a, betas[0] * beta_unit, tau=tau_cal)
                    )
    seen: dict = {}
    for w in cand_weights:
        seen.setdefault(learned_weights_fingerprint(w), w)

    # -- 3. measure anchor + every candidate with ONE shared build key ------
    _, true_ids = knn_scan(dist, Q_cal, X, base.k)
    true_np = np.asarray(true_ids)
    bkey = jax.random.fold_in(k_build, 0xB)

    def measure(spec):
        idx = ANNIndex.build(X, dist, spec=spec, key=bkey, natural=natural)
        _, ids, n_evals, _ = idx.searcher(spec=spec)(Q_cal)
        jax.block_until_ready(ids)
        return {
            "recall": round(recall_at_k(np.asarray(ids), true_np), 4),
            "evals_per_query": round(float(np.mean(np.asarray(n_evals))), 1),
            "spec_fingerprint": spec.fingerprint(),
        }

    anchor_spec = base.replace(build_policy=hand_policy)
    anchor = {"policy": str(hand_policy), **measure(anchor_spec)}
    if verbose:
        print(f"[learned] anchor {hand_policy}: recall={anchor['recall']:.4f} "
              f"evals={anchor['evals_per_query']:.0f}")

    rows = []
    for fp, w in sorted(seen.items()):
        spec = base.replace(build_policy=Learned(w))
        row = {"policy": str(spec.build_policy), "weights_fingerprint": fp,
               "weights": w, **measure(spec)}
        rows.append(row)
        if verbose:
            tag = ("clone" if w["beta"] == 0.0 else
                   f"a={w['alpha']:g} b={w['beta']:.3g}"
                   + (f" tau={w['tau']:.3g}" if w["tau"] is not None else ""))
            print(f"[learned] cand {fp} ({tag}): recall={row['recall']:.4f} "
                  f"evals={row['evals_per_query']:.0f}")

    # -- 4. select: max recall subject to evals <= anchor evals -------------
    eligible = [r for r in rows
                if r["evals_per_query"] <= anchor["evals_per_query"]]
    if not eligible:
        raise AssertionError(
            "no learned candidate within the anchor's eval budget — the "
            "degenerate clone should always qualify (bit-parity broken?)"
        )
    best = min(eligible,
               key=lambda r: (-r["recall"], r["evals_per_query"], r["policy"]))
    if best["recall"] < anchor["recall"]:
        raise AssertionError(
            f"learned selection lost to the anchor ({best['recall']} < "
            f"{anchor['recall']}) — the clone guarantee is broken"
        )

    weights = best["weights"]
    spec = base.replace(build_policy=Learned(weights))
    candidates = tuple(
        {k: v for k, v in r.items() if k != "weights"} for r in rows
    )
    objectives = {k: best[k] for k in ("recall", "evals_per_query")}
    calibration = {
        "n_db": int(X.shape[0]), "n_cal_queries": int(Q_cal.shape[0]),
        "dim": int(X.shape[1]), "k": base.k, "rank": int(min(rank, X.shape[1])),
        "steps": steps, "n_anchors": n_anchors, "k_pos": k_pos,
        "beta_unit": round(beta_unit, 6), "tau_cal": round(tau_cal, 6),
        "seed": seed,
    }
    return LearnedResult(
        spec=spec, weights=weights,
        fingerprint=best["weights_fingerprint"], objectives=objectives,
        anchor=anchor, candidates=candidates, calibration=calibration,
    )
