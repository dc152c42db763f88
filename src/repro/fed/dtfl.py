"""The DTFL training loop (Algorithm 1 MainServer, end to end).

Per round:
  1. TierScheduler assigns every participant a tier (dynamic, from observed
     times) — or a StaticScheduler for the Table-1 ablations.
  2. Each tier's participants train as ONE vectorized cohort (fed.cohort):
     client-side + aux training and the server-side training on the uploaded
     z run inside a single jitted vmap+scan program per tier — O(n_tiers)
     dispatches per round. The trainer's :class:`~repro.fed.execplan.ExecPlan`
     picks the execution plane: ``cohort`` (single device), ``sharded``
     (client axis split over a device mesh, psum aggregation), or ``loop``
     (per-client sequential debug path).
  3. Simulated wall-times per client come from the analytic time model and
     the client's ground-truth resource profile (vectorized over the round);
     the scheduler only observes the resulting times (+ the client-reported
     nu), as in the paper.
  4. Halves are merged and FedAvg'd with weights N_k/N; per-tier aux heads
     start each round from the tier's shared head and are weight-averaged
     within their tier cohort afterwards (both execution paths).
  5. A wire :class:`~repro.core.codec.Codec` (``codec=`` / ``--codec``)
     compresses the three wires inside the jitted programs — activation
     uplink z, client-model download, client-update upload (delta-coded,
     with client-held error feedback for top-k) — and its TRUE byte counts
     drive both the simulated times and the scheduler's profile, so
     re-tiering reacts to the compressed compute/comm balance
     (docs/architecture.md §7).
  6. A profiler trace shows each round as host spans (``fed.round`` over
     ``fed.plan``, ``fed.assemble``, ``fed.dispatch``, ``fed.aggregate``,
     ``fed.observe`` and ``fed.eval``) and the per-tier programs
     (``dtfl_cohort_t<tier>``, ``dtfl_sharded_t<tier>``) split by named
     scope into ``dtfl.client``, ``dtfl.server``, ``dtfl.optimizer``,
     ``dtfl.codec`` and ``dtfl.state``; ``RoundLog.h2d_bytes`` counts the
     input bytes each round copies to the device. With no profiler running
     a span costs about a microsecond and a scope nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, timemodel
from repro.core import codec as codec_lib
from repro.core import topology as topology_lib
from repro.core.scheduler import DynamicTierScheduler, StaticScheduler, TierProfile
from repro.data import pipeline
from repro.fed import cohort as cohort_engine
from repro.fed import engine as event_engine
from repro.fed.adapter import DTFLStepState
from repro.fed.client import HeteroEnv, SimClient
from repro.fed.engine import RoundLog, RoundPlan  # noqa: F401 (re-export)
from repro.fed.execplan import ExecPlan


class DTFLTrainer:
    name = "dtfl"

    def __init__(
        self,
        adapter,
        clients: list[SimClient],
        env: HeteroEnv,
        optimizer,
        *,
        scheduler: str | int = "dynamic",
        topology: str = "server",
        seed: int = 0,
        local_epochs: int = 1,
        server_flops: float = timemodel.SERVER_FLOPS,
        exec_plan: ExecPlan | str | None = None,
        codec: codec_lib.Codec | str | None = None,
    ):
        self.adapter = adapter
        self.clients = clients
        self.env = env
        self.opt = optimizer
        self.local_epochs = local_epochs
        self.server_flops = server_flops
        self.key = jax.random.PRNGKey(seed)
        self.params = adapter.init_global(self._next_key())
        self.costs = adapter.tier_costs(clients[0].dataset.batch_size)
        # communication plane: the codec compresses the three wires inside
        # the jitted programs AND prices them for the time model + scheduler
        self.codec = codec_lib.make_codec(codec)
        self.wires = codec_lib.wire_sizes(self.costs, self.codec)
        self._ef: dict[int, dict] = {}     # cid -> error-feedback residuals
        self.last_uplink_bytes = 0.0
        self.last_h2d_bytes = 0
        profile = TierProfile.from_cost_table(
            self.costs,
            ref_flops=timemodel.UNIT_FLOPS,
            server_flops=server_flops,
            wires=self.wires,
        )
        # scheduler specs resolve through the component registry, so
        # register_scheduler'd strategies work here with no trainer change
        from repro import registry

        if topology not in registry.topologies:
            registry.topologies.validate(topology)   # raises with choices
        if topology == "pairing" and scheduler == "dynamic":
            scheduler = "pairing"
        self.sched = registry.schedulers.build(
            scheduler, profile=profile, n_clients=len(clients),
            n_tiers=adapter.n_tiers)
        # the effective topology follows the scheduler: a host-providing
        # scheduler (pairing) activates peer offload, anything else is the
        # classic all-server topology
        provides_hosts = getattr(self.sched, "provides_hosts", False)
        if topology == "pairing" and not provides_hosts:
            raise ValueError(
                "topology='pairing' requires a host-providing scheduler "
                "(scheduler='pairing' or 'pairing:greedy'), got "
                f"{scheduler!r}")
        self.topology = "pairing" if provides_hosts else "server"
        self.last_hosts: dict[int, int] | None = None
        # per-tier aux heads, persistent and aggregated within tier cohorts
        self.aux = {
            m: adapter.aux_init(self._next_key(), m) for m in range(adapter.n_tiers)
        }
        # "loop" | "cohort" | "sharded[mesh]" — replaces the old cohort bool
        self.exec_plan = ExecPlan.resolve(exec_plan)
        self._step_cache: dict[int, callable] = {}
        self._cohort_cache: dict[int, callable] = {}
        self._sharded_cache: dict[int, callable] = {}

    # ------------------------------------------------------------------
    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _raw_step(self, tier: int):
        """Single-client DTFL step for ``tier`` (unjitted; shared by the
        sequential path and the vmapped cohort program). The activation
        uplink ``z`` is what actually crosses the network, so the codec
        round-trips it before the server loss (the client's own aux loss
        sees the uncompressed local activations)."""
        ad, opt, codec = self.adapter, self.opt, self.codec

        def step(state: DTFLStepState, batch: dict):
            with jax.named_scope("dtfl.client"):
                (closs, z), (cg, ag) = jax.value_and_grad(
                    lambda cp, ap: ad.client_loss(cp, ap, batch), argnums=(0, 1),
                    has_aux=True,
                )(state.client, state.aux)
            with jax.named_scope("dtfl.codec"):
                z = codec.tree_rt(jax.lax.stop_gradient(z))
            with jax.named_scope("dtfl.server"):
                sloss, sg = jax.value_and_grad(
                    lambda sp: ad.server_loss(sp, z, batch, tier)
                )(state.server)
            with jax.named_scope("dtfl.optimizer"):
                c, co = opt.update(state.client, cg, state.c_opt)
                a, ao = opt.update(state.aux, ag, state.a_opt)
                s, so = opt.update(state.server, sg, state.s_opt)
            return DTFLStepState(c, a, s, co, ao, so), (closs, sloss)

        return step

    def _tier_step(self, tier: int):
        if tier not in self._step_cache:
            self._step_cache[tier] = cohort_engine.named_jit(
                self._raw_step(tier), f"dtfl_step_t{tier}")
        return self._step_cache[tier]

    def _cohort_body(self, tier: int, axis: str | None = None):
        """The per-client part of the cohort and sharded programs: split +
        optimizer init + vmapped scan over the cohort's steps + merge.

        The codec's download wire round-trips (client half, tier aux head)
        before training and the upload wire round-trips each member's delta
        before the merge; stateful codecs additionally thread the per-client
        error-feedback residuals ``efc``/``efa`` through (``None`` otherwise).
        Returns (merged trees, uploaded aux heads, efc, efa), client-stacked."""
        ad, opt, codec = self.adapter, self.opt, self.codec
        step = self._raw_step(tier)

        def body(params, aux, batches, mask, efc=None, efa=None):
            with jax.named_scope("dtfl.state"):
                cp, sp = ad.split(params, tier)
            with jax.named_scope("dtfl.codec"):
                cp, auxd = codec.tree_down_rt(cp), codec.tree_down_rt(aux)
            with jax.named_scope("dtfl.state"):
                state = DTFLStepState(
                    cp, auxd, sp, opt.init(cp), opt.init(auxd), opt.init(sp)
                )
            final, _ = cohort_engine.run_cohort(step, state, batches, mask,
                                                axis=axis)
            with jax.named_scope("dtfl.codec"):
                if codec.stateful:
                    upc, efc = codec_lib.uplink_rt_ef(codec, final.client, cp, efc)
                    upa, efa = codec_lib.uplink_rt_ef(codec, final.aux, auxd, efa)
                else:
                    upc = codec_lib.uplink_rt(codec, final.client, cp)
                    upa = codec_lib.uplink_rt(codec, final.aux, auxd)
            with jax.named_scope("dtfl.state"):
                merged = jax.vmap(ad.merge)(upc, final.server)
            return merged, upa, efc, efa

        return body

    def _cohort_program(self, tier: int):
        """One jitted program per tier, ``dtfl_cohort_t<tier>``: the cohort
        body fused on device (eager per-leaf dispatch is exactly the overhead
        the engine removes)."""
        if tier not in self._cohort_cache:
            body = self._cohort_body(tier)
            if self.codec.stateful:
                def run(params, aux, batches, mask, efc, efa):
                    return body(params, aux, batches, mask, efc, efa)
            else:
                def run(params, aux, batches, mask):
                    return body(params, aux, batches, mask)[:2]

            self._cohort_cache[tier] = cohort_engine.named_jit(
                run, f"dtfl_cohort_t{tier}")
        return self._cohort_cache[tier]

    def _sharded_program(self, tier: int):
        """The per-tier cohort program with its client axis split across the
        ExecPlan's mesh via shard_map, ``dtfl_sharded_t<tier>``. Each shard
        trains its client slice (the cohort body), then the cross-client
        FedAvg weighted sums — merged global trees AND tier aux heads —
        reduce on-device as psum collectives; only (sum_tree, aux_sum_tree,
        weight_total) leave the mesh, with the stateful codec's residuals
        after them, client-sharded."""
        if tier not in self._sharded_cache:
            plan, stateful = self.exec_plan, self.codec.stateful
            body = self._cohort_body(tier, axis=plan.axis)

            def local(params, aux, batches, mask, weights, *ef):
                merged, upa, efc, efa = body(params, aux, batches, mask, *ef)
                with jax.named_scope("aggregate"):
                    sums = (plan.psum_tree(merged, scaled_by=weights),
                            plan.psum_tree(upa, scaled_by=weights),
                            plan.psum_scalar(weights.sum()))
                return (sums + (efc, efa)) if stateful else sums

            local.__name__ = f"dtfl_sharded_t{tier}"
            if stateful:
                call = plan.shard_cohort_call(
                    local, n_replicated=2, n_client_extra=2,
                    n_outs=5, client_outs=2)
            else:
                call = plan.shard_cohort_call(local, n_replicated=2)
            self._sharded_cache[tier] = cohort_engine.named_jit(
                call, local.__name__)
        return self._sharded_cache[tier]

    # ------------------------------------------------------------------
    # engine hooks (fed/engine.py contract): plan -> execute -> observe
    # ------------------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="fed.plan")
    def plan_round(self, r: int, participants: list[int]) -> RoundPlan:
        """Profile switching + Algorithm-1 scheduling + analytic Eq.-5 times.

        Pure planning: no parameter updates, no scheduler observations — the
        engine decides which planned clients actually report (churn)."""
        self.env.maybe_switch(r)
        # engine-side widening adapter: narrow cid->tier schedules (static /
        # dynamic) and generalized cid->(tier, host) schedules (pairing) both
        # become an OffloadTopology; plan.assign stays the narrow tier view
        # every downstream consumer (cohorts, EF, logs) uses
        topo = topology_lib.OffloadTopology.from_schedule(
            self.sched.schedule(participants))
        assign = topo.tiers()
        tiers = np.array([assign[k] for k in participants])
        profs = [self.env.profile(k) for k in participants]
        bps = np.array([p.bytes_per_s for p in profs])
        nb = np.array([self.clients[k].n_batches for k in participants])
        if topo.is_server_only:
            t = timemodel.simulate_client_times_batch(
                self.costs, tiers, np.array([p.flops for p in profs]), bps, nb,
                server_flops=self.server_flops, n_sharing=len(participants),
                wires=self.wires,
            )
            obs_nu = bps
        else:
            t = topology_lib.simulate_times(
                self.costs, topo, participants, profs, nb,
                server_flops=self.server_flops, wires=self.wires)
            obs_nu = t["link"]   # guests report the pair link, not their uplink
        # codec-true client->host bytes of this round (z uplink + update
        # upload), surfaced per round through RoundLog.uplink_bytes
        self.last_uplink_bytes = float(self.wires.uplink_bytes(tiers, nb).sum())
        self.last_h2d_bytes = 0     # set by the round's training
        self.last_hosts = (None if topo.is_server_only else
                           {k: h for k, h in topo.hosts().items()
                            if h != topology_lib.SERVER})
        return RoundPlan(
            participants=list(participants), trained=list(participants),
            assign=assign, times=t["total"],
            obs={"t": t["client"] + t["comm"], "nu": obs_nu, "nb": nb},
            topology=topo,
        )

    def execute_round(self, r: int, plan: RoundPlan, trained: list[int]) -> float:
        if not trained:
            return 0.0
        self.params = self._train_participants(r, trained, plan.assign)
        return 0.0

    @functools.partial(jax.profiler.annotate_function, name="fed.observe")
    def observe_round(self, plan: RoundPlan, idx: list[int], obs_times, totals) -> None:
        # contract (see fed/engine.py): obs_times is pre-sliced to idx;
        # plan.obs arrays are full-length and sliced here
        if not len(idx):
            return
        sel = np.asarray(idx, int)
        ks = [plan.trained[i] for i in idx]
        tiers = [plan.assign[k] for k in ks]
        self.sched.observe_cohort(
            ks, tiers, obs_times, plan.obs["nu"][sel], plan.obs["nb"][sel]
        )

    def train_group(self, r: int, plan: RoundPlan, trained: list[int]):
        """Async-tier hook: group-local training that returns the aggregated
        tree (per-tier aggregation) instead of committing it, so the async
        merger can staleness-weight it across tiers."""
        tree = self._train_participants(r, trained, plan.assign)
        return tree, float(sum(len(self.clients[k].dataset) for k in trained))

    def _train_participants(self, r, participants, assign):
        """ExecPlan dispatch: loop | cohort | sharded | chunked."""
        mode = self.exec_plan.mode
        if mode == "loop":
            return self._train_sequential(r, participants, assign)
        if mode == "sharded":
            return self._train_sharded(r, participants, assign)
        if mode == "chunked":
            return self._train_chunked(r, participants, assign)
        return self._train_cohorts(r, participants, assign)

    def async_groups(self, cids: list[int], n_groups: int) -> list[list[int]]:
        """Speed groups from the SCHEDULER's estimates (never ground truth):
        min-over-allowed-tiers T_hat, ascending — fast group first. A static
        scheduler has no estimates; its groups are contiguous slices."""
        if isinstance(self.sched, StaticScheduler):
            order = list(cids)
        else:
            sel = np.array(self.sched.allowed)
            est = self.sched.estimate_matrix(list(cids))[:, sel].min(axis=1)
            order = [cids[i] for i in np.argsort(est, kind="stable")]
        return event_engine.split_speed_groups(order, n_groups)

    # ------------------------------------------------------------------
    def train_round(self, r: int, participants: list[int]) -> tuple[float, dict[int, int]]:
        """Legacy scalar-clock round: plan -> execute(all) -> observe(all)."""
        plan = self.plan_round(r, participants)
        self.execute_round(r, plan, plan.trained)
        self.observe_round(plan, list(range(len(plan.trained))), plan.obs["t"], plan.times)
        return float(plan.times.max()), plan.assign

    def _train_cohorts(self, r, participants, assign):
        """O(n_tiers) device programs: one vmap+scan per (tier, shape) cohort.
        Returns the N_k/N aggregated global tree; updates per-tier aux heads."""
        merged_trees, merged_ws = [], []
        aux_by_tier: dict[int, list] = {}
        cohorts = cohort_engine.build_cohorts(
            self.clients, participants, assign, r, self.local_epochs
        )
        self.last_h2d_bytes = sum(co.nbytes for co in cohorts)
        for co in cohorts:
            with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                if self.codec.stateful:
                    efc, efa = self._gather_ef(co)
                    merged, aux, efc2, efa2 = self._cohort_program(co.tier)(
                        self.params, self.aux[co.tier], co.batches, co.mask,
                        efc, efa,
                    )
                    self._scatter_ef(co, efc2, efa2)
                else:
                    merged, aux = self._cohort_program(co.tier)(
                        self.params, self.aux[co.tier], co.batches, co.mask
                    )
            w = [len(self.clients[k].dataset) for k in co.cids]
            merged_trees.append(merged)
            merged_ws.append(w)
            aux_by_tier.setdefault(co.tier, []).append((aux, w))
        with jax.profiler.TraceAnnotation("fed.aggregate"):
            for tier, parts in aux_by_tier.items():
                self.aux[tier] = aggregation.weighted_average_cohorts(
                    [a for a, _ in parts], [w for _, w in parts]
                )
            return aggregation.weighted_average_cohorts(merged_trees, merged_ws)

    def _train_sharded(self, r, participants, assign):
        """The cohort round with every cohort's client axis sharded over the
        ExecPlan mesh. Cohorts pad to a multiple of the mesh axis (zero
        batches, all-False mask, weight 0 — exact no-ops); each per-tier
        program returns psum-reduced weighted sums, and the host only
        combines one (sum, total) pair per cohort — identical math to
        ``_train_cohorts``'s stacked aggregation, so a 1-device mesh is
        bit-equal and an N-device mesh differs only by collective order."""
        sums, totals = [], []
        aux_by_tier: dict[int, list] = {}
        cohorts = cohort_engine.build_cohorts(
            self.clients, participants, assign, r, self.local_epochs,
            pad_multiple=self.exec_plan.pad_multiple,
        )
        self.last_h2d_bytes = sum(co.nbytes for co in cohorts)
        for co in cohorts:
            w = co.client_weights(self.clients)
            with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                if self.codec.stateful:
                    efc, efa = self._gather_ef(co)
                    msum, asum, wtot, efc2, efa2 = self._sharded_program(co.tier)(
                        self.params, self.aux[co.tier], co.batches, co.mask, w,
                        efc, efa,
                    )
                    self._scatter_ef(co, efc2, efa2)
                else:
                    msum, asum, wtot = self._sharded_program(co.tier)(
                        self.params, self.aux[co.tier], co.batches, co.mask, w
                    )
            sums.append(msum)
            totals.append(wtot)
            aux_by_tier.setdefault(co.tier, []).append((asum, wtot))
        with jax.profiler.TraceAnnotation("fed.aggregate"):
            for tier, parts in aux_by_tier.items():
                self.aux[tier] = aggregation.combine_weighted_sums(
                    [a for a, _ in parts], [t for _, t in parts],
                    like=self.aux[tier])
            return aggregation.combine_weighted_sums(sums, totals, like=self.params)

    def _train_chunked(self, r, participants, assign):
        """The cohort round with each cohort's client axis cut into
        ``exec_plan.chunk_size``-client chunks, each run through the SAME
        compiled per-tier cohort program at chunk width — so the device
        training working set (stacked batches, per-client optimizer states,
        activations) is O(chunk_size), not O(cohort), which is what lets a
        512-participant sample train on a small host. Per-chunk outputs are
        concatenated on the host, pad columns dropped, and the identical
        ``weighted_average_cohorts`` aggregation runs on the reassembled
        stack — equivalence with ``_train_cohorts`` is by construction
        (eager per-chunk invocations of the same program are bitwise equal
        to slices of the full-cohort vmap; a ``lax.scan`` over chunks is
        not — see ``ExecPlan``)."""
        cs = self.exec_plan.chunk_size
        merged_trees, merged_ws = [], []
        aux_by_tier: dict[int, list] = {}
        cohorts = cohort_engine.build_cohorts(
            self.clients, participants, assign, r, self.local_epochs,
            pad_multiple=cs,
        )
        self.last_h2d_bytes = sum(co.nbytes for co in cohorts)
        for co in cohorts:
            prog = self._cohort_program(co.tier)
            mchunks, achunks = [], []
            for sl in cohort_engine.chunk_slices(co.mask.shape[1], cs):
                b, m = cohort_engine.slice_clients(co.batches, co.mask, sl)
                with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                    if self.codec.stateful:
                        cids_c = co.cids[sl.start:min(sl.stop, co.size)]
                        efc, efa = self._gather_ef_cids(cids_c, co.tier, pad_to=cs)
                        merged, upa, efc2, efa2 = prog(
                            self.params, self.aux[co.tier], b, m, efc, efa)
                        self._scatter_ef_cids(cids_c, co.tier, efc2, efa2)
                    else:
                        merged, upa = prog(self.params, self.aux[co.tier], b, m)
                    mchunks.append(jax.tree.map(np.asarray, merged))
                    achunks.append(jax.tree.map(np.asarray, upa))
            n = co.size    # reassemble the cohort stack, drop pad columns
            cat = lambda *xs: np.concatenate(xs)[:n]
            merged_trees.append(jax.tree.map(cat, *mchunks))
            w = [len(self.clients[k].dataset) for k in co.cids]
            merged_ws.append(w)
            aux_by_tier.setdefault(co.tier, []).append(
                (jax.tree.map(cat, *achunks), w))
        with jax.profiler.TraceAnnotation("fed.aggregate"):
            for tier, parts in aux_by_tier.items():
                self.aux[tier] = aggregation.weighted_average_cohorts(
                    [a for a, _ in parts], [w for _, w in parts]
                )
            return aggregation.weighted_average_cohorts(merged_trees, merged_ws)

    def _train_sequential(self, r, participants, assign):
        """Per-client loop (debug escape hatch; O(clients x batches) dispatches)."""
        round_aux = dict(self.aux)  # cohort members share the round-start head
        merged, weights = [], []
        aux_by_tier: dict[int, list] = {}
        self.last_h2d_bytes = 0
        for k in participants:
            tier = assign[k]
            cl = self.clients[k]
            cp, sp = self.adapter.split(self.params, tier)
            cp = self.codec.tree_down_rt(cp)                  # download wire
            auxd = self.codec.tree_down_rt(round_aux[tier])
            state = DTFLStepState(
                cp, auxd, sp,
                self.opt.init(cp), self.opt.init(auxd), self.opt.init(sp),
            )
            step = self._tier_step(tier)
            with jax.profiler.TraceAnnotation("fed.dispatch", tier=tier):
                for e in range(self.local_epochs):
                    for batch in cl.dataset.epoch(r * pipeline.ROUND_SEED_STRIDE + e):
                        self.last_h2d_bytes += sum(v.nbytes for v in batch.values())
                        batch = {k2: jnp.asarray(v) for k2, v in batch.items()}
                        state, _ = step(state, batch)
            # upload wire (with error feedback for stateful codecs)
            efc = efa = None
            if self.codec.stateful:
                efc, efa = self._client_ef(k, tier)
            upc, efc2 = codec_lib.uplink_rt_one(self.codec, state.client, cp, efc)
            upa, efa2 = codec_lib.uplink_rt_one(self.codec, state.aux, auxd, efa)
            if self.codec.stateful:
                self._ef[k] = {
                    "tier": tier,
                    "c": jax.tree.map(np.asarray, efc2),
                    "a": jax.tree.map(np.asarray, efa2),
                }
            aux_by_tier.setdefault(tier, []).append((upa, len(cl.dataset)))
            merged.append(self.adapter.merge(upc, state.server))
            weights.append(len(cl.dataset))
        with jax.profiler.TraceAnnotation("fed.aggregate"):
            for tier, parts in aux_by_tier.items():
                self.aux[tier] = aggregation.weighted_average(
                    [a for a, _ in parts], [w for _, w in parts]
                )
            return aggregation.weighted_average(merged, weights)

    # ------------------------------------------------------------------
    # error-feedback state (stateful codecs): residuals live host-side per
    # client, shaped like the client's CURRENT tier halves — a re-tiered
    # client's residual no longer matches its upload shapes and is reset
    # (the standard EF answer to a topology change)
    # ------------------------------------------------------------------
    def _client_ef(self, cid: int, tier: int):
        """This client's (client-half, aux) residuals for ``tier`` — zeros
        if it has none yet or was re-tiered since."""
        st = self._ef.get(cid)
        if st is not None and st["tier"] == tier:
            return st["c"], st["a"]
        cp, _ = self.adapter.split(self.params, tier)
        zero = lambda t: jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), t)
        return zero(cp), zero(self.aux[tier])

    def _gather_ef_cids(self, cids, tier: int, *, pad_to: int | None = None):
        """Stack ``cids``'s residuals along the client axis, zero-padded up
        to ``pad_to`` clients (chunk tails / sharded pad clients — zero
        residuals are exact EF no-ops for weight-0 members)."""
        pairs = [self._client_ef(k, tier) for k in cids]
        n_pad = 0 if pad_to is None else pad_to - len(pairs)
        if n_pad:
            if pairs:
                zc = jax.tree.map(np.zeros_like, pairs[0][0])
                za = jax.tree.map(np.zeros_like, pairs[0][1])
            else:
                cp, _ = self.adapter.split(self.params, tier)
                zero = lambda t: jax.tree.map(
                    lambda x: np.zeros(x.shape, x.dtype), t)
                zc, za = zero(cp), zero(self.aux[tier])
            pairs += [(zc, za)] * n_pad
        stack = lambda trees: jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees)
        return stack([c for c, _ in pairs]), stack([a for _, a in pairs])

    def _scatter_ef_cids(self, cids, tier: int, efc, efa) -> None:
        for i, cid in enumerate(cids):
            self._ef[cid] = {
                "tier": tier,
                "c": jax.tree.map(lambda x: np.asarray(x[i]), efc),
                "a": jax.tree.map(lambda x: np.asarray(x[i]), efa),
            }

    def _gather_ef(self, co):
        """Stack the cohort's residuals along the client axis (zeros for the
        sharded plane's pad clients)."""
        return self._gather_ef_cids(co.cids, co.tier, pad_to=co.size + co.n_pad)

    def _scatter_ef(self, co, efc, efa) -> None:
        self._scatter_ef_cids(co.cids, co.tier, efc, efa)

    # ------------------------------------------------------------------
    def compact(self, keep) -> None:
        """Drop per-client state — cached data clients, scheduler history,
        EF residuals — of clients outside ``keep`` (PERMANENT departures).
        The engines never call this: a transiently-offline churn client
        keeps its EMA/EF history so rejoining is bit-identical with or
        without the absence. A compacted client that returns restarts from
        the never-sampled state (data rebuilds bit-identically from the
        lazy factory; scheduler/EF state restarts from defaults)."""
        keep = set(int(k) for k in keep)
        if hasattr(self.clients, "compact"):
            self.clients.compact(keep)
        if hasattr(self.sched, "compact"):
            self.sched.compact(keep)
        self._ef = {c: st for c, st in self._ef.items() if c in keep}

    # ------------------------------------------------------------------
    # checkpointing (server state: global params + per-tier aux heads +
    # scheduler EMA history + jax RNG key + env profile state)
    # ------------------------------------------------------------------
    def save_state(self) -> dict:
        from repro.core.scheduler import DynamicTierScheduler

        state = {"params": self.params,
                 "aux": {str(k): v for k, v in self.aux.items()},
                 "key": np.asarray(self.key),
                 "env": self.env.save_state()}
        if isinstance(self.sched, DynamicTierScheduler):
            # sparse: only TOUCHED clients ride the envelope (untouched ones
            # are pure defaults and rebuild lazily), so checkpoint size is
            # O(sampled participants) even for a 10^6-client registry
            items = self.sched.clients.touched_items()
            ema_t, ema_v = [], []
            for cid, cl in items:
                for tier, ema in cl.ema.items():
                    ema_t.append([cid, tier])
                    ema_v.append(ema.value)
            state["sched"] = {
                "cids": np.array([c for c, _ in items], dtype=np.int64),
                "tiers": np.array([cl.tier for _, cl in items], dtype=np.int64),
                "nu": np.array([cl.nu for _, cl in items], dtype=np.float64),
                "nb": np.array([cl.n_batches for _, cl in items], dtype=np.int64),
                "obs": np.array(
                    [-1 if cl.last_obs_tier is None else cl.last_obs_tier
                     for _, cl in items], dtype=np.int64),
                "ema_keys": np.array(ema_t or [[0, 0]][:0]).reshape(-1, 2),
                "ema_vals": np.array(ema_v),
            }
            if getattr(self.sched, "provides_hosts", False):
                # pairing topology: the latest guest->host map rides the
                # envelope so --resume re-enters the same offload topology
                hosts = self.sched.last_hosts
                state["sched"]["host_cids"] = np.array(
                    sorted(hosts), dtype=np.int64)
                state["sched"]["host_of"] = np.array(
                    [hosts[c] for c in sorted(hosts)], dtype=np.int64)
        if self.codec.stateful:
            # error-feedback residuals ride the envelope so --resume
            # continues the compressed-upload stream bit-deterministically
            state["ef"] = {
                str(cid): {"tier": np.int64(st["tier"]),
                           "c": st["c"], "a": st["a"]}
                for cid, st in self._ef.items()
            }
        return state

    def load_state(self, state: dict) -> None:
        from repro.core.scheduler import EMA, DynamicTierScheduler

        self.params = state["params"]
        self.aux = {int(k): v for k, v in state["aux"].items()}
        if "key" in state:
            self.key = jnp.asarray(state["key"])
        if "env" in state:
            self.env.load_state(state["env"])
        if "sched" in state and isinstance(self.sched, DynamicTierScheduler):
            sc = state["sched"]
            if "cids" in sc:
                # sparse envelope: reset to all-default, then replay the
                # touched clients — untouched ids stay lazy defaults
                self.sched.clients.compact([])
                cids = [int(c) for c in np.asarray(sc["cids"]).reshape(-1)]
            else:
                # legacy dense envelope (one entry per registered client)
                cids = list(range(len(np.asarray(sc["tiers"]).reshape(-1))))
            self.sched._rows.clear()
            for i, cid in enumerate(cids):
                cl = self.sched.clients[cid]
                cl.tier = int(sc["tiers"][i])
                cl.nu = float(sc["nu"][i])
                cl.n_batches = int(sc["nb"][i])
                obs = int(sc["obs"][i])
                cl.last_obs_tier = None if obs < 0 else obs
            for (cid, tier), v in zip(sc["ema_keys"], sc["ema_vals"]):
                e = EMA()
                e.value = float(v)
                self.sched.clients[int(cid)].ema[int(tier)] = e
            if "host_cids" in sc and getattr(self.sched, "provides_hosts",
                                            False):
                self.sched.last_hosts = {
                    int(c): int(h)
                    for c, h in zip(np.asarray(sc["host_cids"]).reshape(-1),
                                    np.asarray(sc["host_of"]).reshape(-1))}
        if "ef" in state:
            self._ef = {
                int(cid): {"tier": int(st["tier"]), "c": st["c"], "a": st["a"]}
                for cid, st in state["ef"].items()
            }

    def save(self, path: str) -> None:
        from repro import checkpoint as ckpt

        ckpt.save(path, self.save_state())

    def restore(self, path: str) -> None:
        """Load trainer state from ``path`` — either a bare ``save()`` state
        or a ``fed.engine.save_train_state`` resume envelope (unwrapped)."""
        event_engine.restore_trainer(self, path)

    # ------------------------------------------------------------------
    def run(
        self,
        n_rounds: int,
        eval_batch: dict,
        *,
        target_acc: float | None = None,
        participation: float = 1.0,
        sample_size: int | None = None,
        eval_every: int = 1,
        verbose: bool = False,
        checkpoint_path: str | None = None,
        checkpoint_every: int = 10,
        engine: str = "rounds",
        churn=None,
        n_groups: int = 3,
        resume: dict | None = None,
        on_round=None,
    ) -> list[RoundLog]:
        common = dict(
            target_acc=target_acc, participation=participation,
            eval_every=eval_every, verbose=verbose,
            checkpoint_path=checkpoint_path, checkpoint_every=checkpoint_every,
            resume=resume, on_round=on_round,
        )
        if engine == "events":
            return event_engine.run_events(
                self, n_rounds, eval_batch, churn=churn,
                sample_size=sample_size, **common)
        if engine == "async":
            if sample_size is not None:
                raise ValueError("sample_size is a rounds/events knob; the "
                                 "async engine groups the full population")
            return event_engine.run_async(
                self, n_rounds, eval_batch, churn=churn, n_groups=n_groups,
                **common)
        if engine != "rounds":
            raise ValueError(f"unknown engine {engine!r}")
        return event_engine.run_rounds(
            self, n_rounds, eval_batch, sample_size=sample_size, **common)
