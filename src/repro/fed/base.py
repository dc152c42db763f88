"""Shared machinery for the baseline federated trainers (paper Sec. 4.1).

All baselines consume the same adapter / clients / env / synthetic clock as
DTFLTrainer so Table-3 style comparisons are apples-to-apples: identical
model, partitions, eval batch; only the algorithm and its time profile vary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, timemodel
from repro.core import codec as codec_lib
from repro.data import pipeline
from repro.fed import cohort as cohort_engine
from repro.fed import engine as event_engine
from repro.fed.client import HeteroEnv, SimClient
from repro.fed.engine import RoundLog, RoundPlan
from repro.fed.execplan import ExecPlan


def kd_loss(student_logits: jax.Array, teacher_logits: jax.Array, temp: float = 1.0,
            weight: jax.Array | None = None) -> jax.Array:
    """KL(teacher || student) with temperature. ``weight`` (per-sample, e.g.
    the fixed-shape pad mask from data/pipeline.py) turns the mean over rows
    into a weighted mean so padded samples contribute nothing."""
    t = jax.nn.softmax(teacher_logits.astype(jnp.float32) / temp, -1)
    ls = jax.nn.log_softmax(student_logits.astype(jnp.float32) / temp, -1)
    lt = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / temp, -1)
    per = jnp.sum(t * (lt - ls), -1)
    if weight is None:
        return jnp.mean(per) * temp * temp
    w = weight.astype(jnp.float32)
    w = jnp.broadcast_to(w.reshape(w.shape + (1,) * (per.ndim - w.ndim)), per.shape)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0) * temp * temp


class BaseTrainer:
    """Round loop scaffolding; subclasses implement train_round()."""

    name = "base"
    # whether the async engine's default train_group (plain FedAvg-style
    # group aggregation) faithfully represents this algorithm. Trainers
    # whose algorithm lives in execute_round / select_clients (fedyogi's
    # server optimizer, fedgkt's KD phases, tifl/drop30's selection) must
    # NOT silently degrade to FedAvg under engine="async".
    supports_async = True
    # whether the codec plane's wires map onto this algorithm's round
    # structure. SplitFed's per-batch gradient round-trip and FedGKT's
    # bespoke two-phase KD protocol are NOT the download/upload wires the
    # codec contract compresses, so they reject non-identity codecs rather
    # than silently mis-pricing them.
    supports_codec = True

    def __init__(self, adapter, clients: list[SimClient], env: HeteroEnv, optimizer,
                 *, seed: int = 0, local_epochs: int = 1,
                 server_flops: float = timemodel.SERVER_FLOPS,
                 exec_plan: ExecPlan | str | None = None,
                 codec: codec_lib.Codec | str | None = None):
        self.adapter = adapter
        self.clients = clients
        self.env = env
        self.opt = optimizer
        self.local_epochs = local_epochs
        self.server_flops = server_flops
        # "loop" | "cohort" | "sharded[mesh]" — replaces the old cohort bool
        self.exec_plan = ExecPlan.resolve(exec_plan)
        self.key = jax.random.PRNGKey(seed)
        self.params = adapter.init_global(self._next_key())
        self.costs = adapter.tier_costs(clients[0].dataset.batch_size)
        # communication plane (core/codec.py): download the codec'd global,
        # upload codec'd deltas, price both wires with codec-true bytes
        self.codec = codec_lib.make_codec(codec)
        if not self.supports_codec and not self.codec.is_identity:
            raise ValueError(
                f"{self.name} does not support wire compression (codec="
                f"{self.codec.name!r}); its round structure is not the "
                "download/update-upload contract the codec plane compresses")
        self.wires = codec_lib.wire_sizes(self.costs, self.codec)
        self._ef: dict[int, dict] = {}     # cid -> error-feedback residual
        self.last_uplink_bytes = 0.0
        self.last_h2d_bytes = 0

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    # ------------------------------------------------------------------
    # engine hooks (fed/engine.py contract). Full-model baselines override
    # select_clients / client_time / execute_round / observe_round; the
    # defaults implement FedAvg semantics.
    # ------------------------------------------------------------------
    def select_clients(self, r: int, participants: list[int]) -> list[int]:
        """Which participants actually train (TiFL picks a tier, drop30 the
        fastest subset)."""
        return list(participants)

    def client_time(self, k: int) -> float:
        """Planned Eq.-5 completion offset for client ``k`` under this
        algorithm's time profile."""
        return self._full_model_time(k, self.clients[k].n_batches)

    @functools.partial(jax.profiler.annotate_function, name="fed.plan")
    def plan_round(self, r: int, participants: list[int]) -> RoundPlan:
        self.env.maybe_switch(r)
        trained = list(self.select_clients(r, participants))
        times = np.array([self.client_time(k) for k in trained], float)
        # full-model uplink = one codec'd update upload per trained client
        self.last_uplink_bytes = float(self.wires.full_up * len(trained))
        self.last_h2d_bytes = 0     # set by the round's training
        return RoundPlan(
            participants=list(participants), trained=trained,
            assign={k: 0 for k in trained}, times=times,
        )

    def execute_round(self, r: int, plan: RoundPlan, trained: list[int]) -> float:
        """Train the survivors; returns extra serial time (FedGKT's server
        phase) appended after the last completion."""
        if trained:
            self.params = self._train_round_full(r, trained)
        return 0.0

    def observe_round(self, plan: RoundPlan, idx: list[int], obs_times, totals) -> None:
        """Feed event-derived timestamps back (TiFL's speed profiling)."""

    def train_group(self, r: int, plan: RoundPlan, trained: list[int]):
        """Async-tier hook: group aggregate without committing to params."""
        tree = self._train_round_full(r, trained)
        return tree, float(sum(len(self.clients[k].dataset) for k in trained))

    def async_groups(self, cids: list[int], n_groups: int) -> list[list[int]]:
        """Speed groups (fast -> slow) by this algorithm's own time profile —
        the FedAT/TiFL tier-profiling step."""
        return event_engine.split_speed_groups(
            sorted(cids, key=self.client_time), n_groups
        )

    # ------------------------------------------------------------------
    def train_round(self, r: int, participants: list[int]) -> float:
        """Legacy scalar-clock round: plan -> execute(all) -> observe(all)."""
        plan = self.plan_round(r, participants)
        extra = self.execute_round(r, plan, plan.trained)
        self.observe_round(
            plan, list(range(len(plan.trained))), plan.times, plan.times
        )
        return float(plan.times.max()) + extra

    # ------------------------------------------------------------------
    # error-feedback state (stateful codecs): one full-model-shaped
    # residual per client, host-side
    # ------------------------------------------------------------------
    def _client_ef(self, cid: int):
        st = self._ef.get(cid)
        if st is not None:
            return st
        return jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), self.params)

    def _gather_ef_cids(self, cids, *, pad_to: int | None = None):
        trees = [self._client_ef(k) for k in cids]
        n_pad = 0 if pad_to is None else pad_to - len(trees)
        if n_pad:
            z = (jax.tree.map(np.zeros_like, trees[0]) if trees
                 else jax.tree.map(
                     lambda x: np.zeros(x.shape, x.dtype), self.params))
            trees += [z] * n_pad
        return jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *trees)

    def _scatter_ef_cids(self, cids, ef) -> None:
        for i, cid in enumerate(cids):
            self._ef[cid] = jax.tree.map(lambda x: np.asarray(x[i]), ef)

    def _gather_ef(self, co):
        return self._gather_ef_cids(co.cids, pad_to=co.size + co.n_pad)

    def _scatter_ef(self, co, ef) -> None:
        self._scatter_ef_cids(co.cids, ef)

    # ------------------------------------------------------------------
    def compact(self, keep) -> None:
        """Drop per-client state (cached data clients, EF residuals) of
        clients outside ``keep`` — PERMANENT departures only; the engines
        never call this (transiently-offline churn clients keep state)."""
        keep = set(int(k) for k in keep)
        if hasattr(self.clients, "compact"):
            self.clients.compact(keep)
        self._ef = {c: st for c, st in self._ef.items() if c in keep}

    # ------------------------------------------------------------------
    # resumable training state (engine.save_train_state envelope body)
    # ------------------------------------------------------------------
    def save_state(self) -> dict:
        """Everything a deterministic resume needs: params, the trainer's jax
        RNG key, and the env's profile state. Subclasses with extra server
        state (FedYogi's optimizer, DTFL's aux heads / scheduler) extend."""
        state = {"params": self.params, "key": np.asarray(self.key),
                 "env": self.env.save_state()}
        if self.codec.stateful:
            state["ef"] = {str(cid): t for cid, t in self._ef.items()}
        return state

    def load_state(self, state: dict) -> None:
        self.params = state["params"]
        if "key" in state:
            self.key = jnp.asarray(state["key"])
        if "env" in state:
            self.env.load_state(state["env"])
        if "ef" in state:
            self._ef = {int(cid): t for cid, t in state["ef"].items()}

    def save(self, path: str) -> None:
        from repro import checkpoint as ckpt

        ckpt.save(path, self.save_state())

    def restore(self, path: str) -> None:
        """Load trainer state from ``path`` — either a bare ``save()`` state
        or a ``fed.engine.save_train_state`` resume envelope (unwrapped)."""
        event_engine.restore_trainer(self, path)

    def run(self, n_rounds: int, eval_batch: dict, *, target_acc: float | None = None,
            participation: float = 1.0, sample_size: int | None = None,
            eval_every: int = 1, verbose: bool = False,
            engine: str = "rounds", churn=None, n_groups: int = 3,
            checkpoint_path: str | None = None, checkpoint_every: int = 10,
            resume: dict | None = None, on_round=None,
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
            if not self.supports_async:
                raise ValueError(
                    f"{self.name} has no faithful async formulation (its "
                    "algorithm lives outside train_group); run it with "
                    "engine='rounds' or 'events', or use method 'fedat'"
                )
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

    # ------------------------------------------------------------------
    # time helpers (analytic, from the shared cost table)
    # ------------------------------------------------------------------
    def _full_model_time(self, cid: int, n_batches: int) -> float:
        """FedAvg-style: the client trains the ENTIRE model locally. The
        comm term prices the codec-true download + update upload (identity:
        the legacy ``2 * full_param_bytes``)."""
        prof = self.env.profile(cid)
        compute = self.costs.full_flops * n_batches * self.local_epochs / prof.flops
        comm = (self.wires.full_down + self.wires.full_up) / prof.bytes_per_s
        return compute + comm

    def _local_full_steps(self, r: int, cid: int, params):
        """Run local_epochs of full-model SGD for one client; returns the
        client's (codec'd) upload. The codec's download wire round-trips the
        global before training; the upload wire round-trips the delta."""
        if not hasattr(self, "_full_step"):
            full_step = self._full_step_fn()

            def step(p, o, batch):
                state, loss = full_step({"p": p, "o": o}, batch)
                return state["p"], state["o"], loss

            self._full_step = cohort_engine.named_jit(step, f"{self.name}_step")
        ref = self.codec.tree_down_rt(params)             # download wire
        params = ref
        o = self.opt.init(params)
        with jax.profiler.TraceAnnotation("fed.dispatch", tier=0):
            for e in range(self.local_epochs):
                for batch in self.clients[cid].dataset.epoch(
                    r * pipeline.ROUND_SEED_STRIDE + e
                ):
                    self.last_h2d_bytes += sum(v.nbytes for v in batch.values())
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                    params, o, _ = self._full_step(params, o, batch)
        ef = self._client_ef(cid) if self.codec.stateful else None
        up, ef2 = codec_lib.uplink_rt_one(self.codec, params, ref, ef)
        if self.codec.stateful:
            self._ef[cid] = jax.tree.map(np.asarray, ef2)
        return up

    # ------------------------------------------------------------------
    # cohort / sharded engine paths (same math as _local_full_steps)
    # ------------------------------------------------------------------
    def _full_step_fn(self):
        """Single-client full-model step (unjitted; lifted by run_cohort)."""
        ad, opt = self.adapter, self.opt

        def step(state, batch):
            with jax.named_scope("dtfl.client"):
                loss, g = jax.value_and_grad(
                    lambda q: ad.full_loss(q, batch)
                )(state["p"])
            with jax.named_scope("dtfl.optimizer"):
                p, o = opt.update(state["p"], g, state["o"])
            return {"p": p, "o": o}, loss

        return step

    def _train_round_full(self, r: int, cids: list[int]):
        """Full-model local training for every client in ``cids`` followed by
        the N_k/N weighted average; returns the aggregated params.

        ExecPlan dispatch: ``cohort`` runs vectorized shape-bucketed cohorts
        — one jitted program each (optimizer init + vmap+scan fused on
        device) and a stacked aggregation; ``sharded`` splits each cohort's
        client axis over the plan's mesh and reduces the weighted sums
        on-device (psum); ``loop`` is the per-client debug path.
        """
        weigh = lambda k: len(self.clients[k].dataset)
        if self.exec_plan.mode == "loop":
            self.last_h2d_bytes = 0
            locals_ = [self._local_full_steps(r, k, self.params) for k in cids]
            with jax.profiler.TraceAnnotation("fed.aggregate"):
                return aggregation.weighted_average(
                    locals_, [weigh(k) for k in cids])
        tier_of = {k: 0 for k in cids}  # untired: bucket by batch shape only
        cohorts = cohort_engine.build_cohorts(
            self.clients, cids, tier_of, r, self.local_epochs,
            pad_multiple=self.exec_plan.pad_multiple,
        )
        self.last_h2d_bytes = sum(co.nbytes for co in cohorts)
        if self.exec_plan.mode == "sharded":
            sums, totals = [], []
            for co in cohorts:
                with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                    if self.codec.stateful:
                        ef = self._gather_ef(co)
                        s, t, ef2 = self._full_sharded_program()(
                            self.params, co.batches, co.mask,
                            co.client_weights(self.clients), ef,
                        )
                        self._scatter_ef(co, ef2)
                    else:
                        s, t = self._full_sharded_program()(
                            self.params, co.batches, co.mask,
                            co.client_weights(self.clients),
                        )
                sums.append(s)
                totals.append(t)
            with jax.profiler.TraceAnnotation("fed.aggregate"):
                return aggregation.combine_weighted_sums(
                    sums, totals, like=self.params)
        if not hasattr(self, "_full_cohort_program"):
            body = self._full_body()
            if self.codec.stateful:
                def run(params, batches, mask, ef):
                    return body(params, batches, mask, ef)
            else:
                def run(params, batches, mask):
                    return body(params, batches, mask)[0]

            self._full_cohort_program = cohort_engine.named_jit(
                run, f"{self.name}_cohort")
        if self.exec_plan.mode == "chunked":
            # the SAME compiled cohort program, invoked at chunk width with
            # per-chunk outputs reassembled on host: the device training
            # working set is O(chunk_size), the aggregation below is the
            # identical ``weighted_average_cohorts`` call — bit-equal to the
            # cohort plane by construction (see ExecPlan)
            cs = self.exec_plan.chunk_size
            trees, ws = [], []
            for co in cohorts:
                chunks = []
                for sl in cohort_engine.chunk_slices(co.mask.shape[1], cs):
                    b, m = cohort_engine.slice_clients(co.batches, co.mask, sl)
                    with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                        if self.codec.stateful:
                            cids_c = co.cids[sl.start:min(sl.stop, co.size)]
                            ef = self._gather_ef_cids(cids_c, pad_to=cs)
                            up, ef2 = self._full_cohort_program(
                                self.params, b, m, ef)
                            self._scatter_ef_cids(cids_c, ef2)
                        else:
                            up = self._full_cohort_program(self.params, b, m)
                        chunks.append(jax.tree.map(np.asarray, up))
                trees.append(jax.tree.map(
                    lambda *xs: np.concatenate(xs)[:co.size], *chunks))
                ws.append([weigh(k) for k in co.cids])
            with jax.profiler.TraceAnnotation("fed.aggregate"):
                return aggregation.weighted_average_cohorts(trees, ws)
        trees, ws = [], []
        for co in cohorts:
            with jax.profiler.TraceAnnotation("fed.dispatch", tier=co.tier):
                if self.codec.stateful:
                    ef = self._gather_ef(co)
                    up, ef2 = self._full_cohort_program(
                        self.params, co.batches, co.mask, ef)
                    self._scatter_ef(co, ef2)
                    trees.append(up)
                else:
                    trees.append(self._full_cohort_program(
                        self.params, co.batches, co.mask))
            ws.append([weigh(k) for k in co.cids])
        with jax.profiler.TraceAnnotation("fed.aggregate"):
            return aggregation.weighted_average_cohorts(trees, ws)

    def _full_body(self, axis: str | None = None):
        """The per-client part of the full-model cohort and sharded
        programs: download wire, optimizer init, the vmapped scan, upload
        wire (threading the stateful codec's residuals ``ef``, ``None``
        otherwise). Returns (uploaded trees, ef), client-stacked."""
        step, opt, codec = self._full_step_fn(), self.opt, self.codec

        def body(params, batches, mask, ef=None):
            with jax.named_scope("dtfl.codec"):
                ref = codec.tree_down_rt(params)          # download wire
            with jax.named_scope("dtfl.state"):
                state = {"p": ref, "o": opt.init(ref)}
            final, _ = cohort_engine.run_cohort(
                step, state, batches, mask, axis=axis)
            with jax.named_scope("dtfl.codec"):
                if codec.stateful:
                    return codec_lib.uplink_rt_ef(codec, final["p"], ref, ef)
                return codec_lib.uplink_rt(codec, final["p"], ref), None

        return body

    def _full_sharded_program(self):
        """One jitted shard_map program, ``<name>_sharded``: the full-model
        cohort body with its client axis split over the plan's mesh; the
        N_k-weighted parameter sum and the weight total leave the device
        pre-reduced (psum), so per-client trees never materialize on host.
        Error-feedback residuals travel client-sharded."""
        if not hasattr(self, "_full_sharded"):
            plan, stateful = self.exec_plan, self.codec.stateful
            body = self._full_body(axis=plan.axis)

            def local(params, batches, mask, weights, *ef):
                up, ef2 = body(params, batches, mask, *ef)
                with jax.named_scope("aggregate"):
                    sums = (plan.psum_tree(up, scaled_by=weights),
                            plan.psum_scalar(weights.sum()))
                return (sums + (ef2,)) if stateful else sums

            if stateful:
                call = plan.shard_cohort_call(
                    local, n_replicated=1, n_client_extra=1,
                    n_outs=3, client_outs=1)
            else:
                call = plan.shard_cohort_call(local, n_replicated=1)
            self._full_sharded = cohort_engine.named_jit(
                call, f"{self.name}_sharded")
        return self._full_sharded
