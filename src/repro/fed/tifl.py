"""TiFL-style tier-based client SELECTION (Chai et al. 2020) — the tier-based
line of work the paper builds on: clients are profiled into speed tiers and
each round trains clients FROM ONE TIER (rotating by an accuracy credit),
but every client still trains the FULL model. Included as the reference
point between FedAvg and DTFL: selection removes intra-round stragglers but
pays full-model time on slow tiers and skips data every round.

Speed profiling consumes the event-derived completion timestamps
(``observe_round``): under churn, only clients that actually reported
refresh their profile, exactly like a real TiFL server.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.fed.base import BaseTrainer, RoundPlan

N_TIERS = 3


class TiFLTrainer(BaseTrainer):
    name = "tifl"
    supports_async = False  # algorithm lives outside train_group

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._speed_obs = {}          # cid -> last observed full-model time
        self._round_robin = 0

    def _tiers(self, participants):
        # profile clients by observed (or estimated) full-model time
        times = {k: self._speed_obs.get(k, self.client_time(k)) for k in participants}
        order = sorted(participants, key=lambda k: times[k])
        cut = max(1, len(order) // N_TIERS)
        return [order[i * cut : (i + 1) * cut] or order[-1:] for i in range(N_TIERS)]

    def select_clients(self, r: int, participants: list[int]) -> list[int]:
        tiers = self._tiers(participants)
        chosen = tiers[self._round_robin % len(tiers)]
        self._round_robin += 1
        return chosen

    @functools.partial(jax.profiler.annotate_function, name="fed.observe")
    def observe_round(self, plan: RoundPlan, idx: list[int], obs_times, totals) -> None:
        for j, i in enumerate(idx):
            self._speed_obs[plan.trained[i]] = float(totals[j])

    # speed profile + tier rotation ride the resume envelope, otherwise a
    # resumed run re-profiles from scratch and selects different tiers
    def save_state(self) -> dict:
        state = super().save_state()
        cids = np.array(sorted(self._speed_obs), dtype=np.int64)
        state["tifl"] = {
            "obs_cids": cids,
            "obs_times": np.array([self._speed_obs[int(c)] for c in cids]),
            "round_robin": np.int64(self._round_robin),
        }
        return state

    def load_state(self, state: dict) -> None:
        super().load_state(state)
        if "tifl" in state:
            t = state["tifl"]
            self._speed_obs = {
                int(c): float(v)
                for c, v in zip(np.asarray(t["obs_cids"]).reshape(-1),
                                np.asarray(t["obs_times"]).reshape(-1))
            }
            self._round_robin = int(t["round_robin"])
