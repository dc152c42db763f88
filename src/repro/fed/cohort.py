"""Tier-cohort vectorized round engine.

The sequential round loop dispatches O(n_clients x n_batches) jitted steps
per round; this engine collapses each round to O(n_tiers) device programs:

  1. participants are grouped into *cohorts* by (tier, per-batch sample
     shape) — every client in a cohort trains the same client/server split
     on identically-shaped batches;
  2. each client's local steps (``local_epochs`` epochs of its minibatches)
     are materialized and stacked into leading-axis arrays of shape
     ``(n_steps, n_clients, batch, ...)``;
  3. ragged cohorts (clients with unequal batch counts) are padded with
     zero batches up to the cohort max and masked out: a ``(n_steps,
     n_clients)`` boolean mask gates the state update, so padded steps are
     identity for that client;
  4. one jitted program per cohort runs ``jax.lax.scan`` over steps with a
     ``jax.vmap``-ed per-client step inside, so XLA sees a single static
     (n_steps, n_clients)-shaped computation per (tier, shape-bucket).

The engine is trainer-agnostic: any per-client step function
``step(state, batch) -> (state, out)`` over arbitrary pytrees can be lifted
with :func:`run_cohort`. ``DTFLTrainer`` uses it for per-tier split
training; ``BaseTrainer`` routes the full-model baselines (FedAvg, TiFL,
SplitFed, FedYogi, DropStrag) through the same path.

Recompilation note: a cohort program specializes on (n_steps, n_clients,
batch shapes). Rounds with stable tier assignments and participation reuse
the cached executable; a changed cohort size retraces.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import materialize_round


@dataclass
class Cohort:
    """One (tier, batch-shape) group of a round's participants."""

    tier: int
    cids: list[int]                # participant ids, stacking order
    batches: dict                  # name -> (n_steps, n_clients + n_pad, batch, ...)
    mask: np.ndarray               # (n_steps, n_clients + n_pad) bool; False = padded
    n_pad: int = 0                 # trailing pad clients (sharded divisibility)

    @property
    def size(self) -> int:
        return len(self.cids)

    @property
    def nbytes(self) -> int:
        """Bytes of the host arrays the cohort's program takes in (stacked
        batches and step mask): what the round copies to the device."""
        return sum(a.nbytes for a in self.batches.values()) + self.mask.nbytes

    def client_weights(self, clients) -> np.ndarray:
        """(size + n_pad,) f32 aggregation weights: N_k for real members, 0
        for pad clients — so on-device weighted sums ignore padding exactly."""
        w = [float(len(clients[k].dataset)) for k in self.cids] + [0.0] * self.n_pad
        return np.asarray(w, np.float32)


@functools.partial(jax.profiler.annotate_function, name="fed.assemble")
def build_cohorts(
    clients, cids: list[int], tier_of: dict[int, int], r: int, local_epochs: int,
    *, pad_multiple: int = 1,
) -> list[Cohort]:
    """Group ``cids`` into cohorts and stack their round-``r`` batches.

    ``tier_of`` maps cid -> tier (use a constant for untired full-model
    training). Batches come from ``materialize_round`` so they are
    bit-identical to what the sequential loop would consume.

    ``pad_multiple > 1`` (the sharded plane's mesh axis size) pads each
    cohort's client axis with zero-batch / all-False-mask / weight-0 pad
    clients up to the next multiple, so ``shard_map`` can split the axis
    evenly; pad clients never touch state (mask) or aggregation (weight).
    """
    per_client = {k: materialize_round(clients[k].dataset, r, local_epochs) for k in cids}
    groups: dict[tuple, list[int]] = {}
    for k in cids:
        arrs = per_client[k]
        shape_key = tuple(sorted((name, a.shape[1:]) for name, a in arrs.items()))
        groups.setdefault((tier_of[k], shape_key), []).append(k)

    cohorts = []
    for (tier, _), members in groups.items():
        steps = np.array([len(next(iter(per_client[k].values()))) for k in members])
        s_max = int(steps.max())
        n_pad = (-len(members)) % max(1, int(pad_multiple))
        names = per_client[members[0]].keys()
        batches = {}
        for name in names:
            stacked = np.stack(
                [_pad_steps(per_client[k][name], s_max) for k in members], axis=1
            )  # (S, C, batch, ...)
            if n_pad:
                zeros = np.zeros(
                    (s_max, n_pad) + stacked.shape[2:], stacked.dtype
                )
                stacked = np.concatenate([stacked, zeros], axis=1)
            batches[name] = stacked
        steps_padded = np.concatenate([steps, np.zeros(n_pad, steps.dtype)])
        mask = np.arange(s_max)[:, None] < steps_padded[None, :]  # (S, C + pad)
        cohorts.append(Cohort(tier, members, batches, mask, n_pad))
    return cohorts


def chunk_slices(n_cols: int, chunk_size: int) -> list[slice]:
    """Client-axis slices cutting a padded cohort into fixed-size chunks.

    The chunked ExecPlan builds cohorts with ``pad_multiple=chunk_size``, so
    ``n_cols`` (real + pad clients) is always divisible and every chunk has
    the same static shape — one compiled per-chunk program serves them all.
    """
    if n_cols % chunk_size:
        raise ValueError(
            f"cohort client axis {n_cols} is not a multiple of chunk_size "
            f"{chunk_size}; build cohorts with pad_multiple=chunk_size")
    return [slice(i, i + chunk_size) for i in range(0, n_cols, chunk_size)]


def slice_clients(batches: dict, mask: np.ndarray, sl: slice) -> tuple[dict, np.ndarray]:
    """One client-chunk's view of a cohort's stacked batches + step mask."""
    return {k: v[:, sl] for k, v in batches.items()}, mask[:, sl]


def _pad_steps(a: np.ndarray, s_max: int) -> np.ndarray:
    if len(a) == s_max:
        return a
    pad = np.zeros((s_max - len(a),) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad])


# ---------------------------------------------------------------------------
# the vectorized program
# ---------------------------------------------------------------------------

def named_jit(fn, name: str):
    """``jax.jit(fn)`` under a stable program name: compile logs and the
    profiler's ``XLA Modules`` line show ``jit_<name>``, not the name of
    whatever closure built the program."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def broadcast_state(state, n: int):
    """Replicate a single-client state pytree along a new leading axis."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + jnp.shape(x)), state)


def tree_select(mask: jax.Array, new, old):
    """Per-client select: leaves have leading client axis; mask is (C,)."""
    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (jnp.ndim(n) - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


def run_cohort(step_fn, state, batches, mask, *, axis: str | None = None):
    """Traceable core: broadcast a SINGLE client's initial ``state`` across
    the cohort and scan ``step(state, batch) -> (state, out)`` over the
    stacked steps with a vmapped per-client step inside. Masked (padded)
    steps leave that client's state untouched.

    Call inside a jitted per-trainer program so that state construction
    (split, optimizer init) and post-processing (merge, weighted sums) fuse
    into the same device program — eager dispatch is the cost the engine
    exists to remove.

    ``axis`` names the mesh axis the client axis is split over when this
    runs inside ``shard_map`` (the sharded plane). The broadcast state is
    replicated there while each shard's trained state varies over ``axis``,
    so the scan carry is cast to vary over it up front.
    """
    with jax.named_scope("dtfl.state"):
        stacked = broadcast_state(state, mask.shape[1])
        if axis is not None:
            stacked = jax.lax.pcast(stacked, axis, to="varying")

    def body(s, xs):
        batch, m = xs
        new_s, out = jax.vmap(step_fn)(s, batch)
        with jax.named_scope("dtfl.state"):
            return tree_select(m, new_s, s), out

    return jax.lax.scan(body, stacked, (batches, mask))
