"""FedAvg aggregation across tiers (Algorithm 1 lines 11-13, Appendix A.7 (5)).

Each client's (client-side, server-side) halves are merged back into a full
parameter tree (lossless — tiering.merge_params), then averaged with weights
``N_k / N`` (Eq. 1; Algorithm 1 line 13 uses 1/K — we expose both).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Params = dict


def weighted_average(trees: list[Params], weights: list[float]) -> Params:
    w = jnp.asarray(weights, jnp.float32)
    w = w / w.sum()

    def avg(*leaves):
        stacked = jnp.stack([l.astype(jnp.float32) for l in leaves])
        out = jnp.tensordot(w, stacked, axes=1)
        return out.astype(leaves[0].dtype)

    return jax.tree.map(avg, *trees)


def uniform_average(trees: list[Params]) -> Params:
    return weighted_average(trees, [1.0] * len(trees))


@jax.jit
@jax.named_scope("aggregate")
def _wavg_cohorts(stacked_trees: list, ws: list):
    total = sum(w.sum() for w in ws)

    def partial(w):
        return lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=1)

    acc = jax.tree.map(partial(ws[0]), stacked_trees[0])
    for tree, w in zip(stacked_trees[1:], ws[1:]):
        acc = jax.tree.map(lambda a, x, p=partial(w): a + p(x), acc, tree)
    return jax.tree.map(
        lambda a, x: (a / total).astype(x.dtype), acc, stacked_trees[0]
    )


def weighted_average_cohorts(stacked_trees: list[Params], weights: list) -> Params:
    """Weighted average across several stacked pytrees (one per cohort).

    Every tree carries a leading client axis; weights are per-client within
    each cohort and normalized over the union of all cohorts. Runs as one
    jitted program (cached per pytree structure/shapes)."""
    ws = [jnp.asarray(w, jnp.float32) for w in weights]
    return _wavg_cohorts(stacked_trees, ws)


@jax.jit
@jax.named_scope("aggregate")
def _combine_sums(sums: list, totals: list, like: Params):
    total = totals[0]
    for t in totals[1:]:
        total = total + t
    acc = sums[0]
    for s in sums[1:]:
        acc = jax.tree.map(lambda a, x: a + x, acc, s)
    return jax.tree.map(lambda a, p: (a / total).astype(p.dtype), acc, like)


def combine_weighted_sums(sums: list[Params], totals: list, like: Params) -> Params:
    """Finalize per-cohort weighted SUMS into the global weighted average.

    The sharded plane's cohort programs reduce their client axis on-device
    (``psum`` of ``tensordot(w, x)`` partials + ``psum`` of ``w.sum()``); the
    host only ever sees one (sum_tree, weight_total) pair per cohort. This
    mirrors ``_wavg_cohorts`` exactly — same per-cohort partials, same
    cohort-order accumulation, same single division — so a 1-shard mesh
    reproduces the cohort plane bit-for-bit. ``like`` supplies output dtypes.
    """
    totals = [jnp.asarray(t, jnp.float32) for t in totals]
    return _combine_sums(sums, totals, like)


def aggregate_dtfl_round(cfg, tier_states: list[tuple[int, Params, Params]],
                         weights: list[float]) -> Params:
    """tier_states: [(tier, client_params, server_params)] per client."""
    from repro.core import tiering

    fulls = [tiering.merge_params(c, s) for _, c, s in tier_states]
    return weighted_average(fulls, weights)
