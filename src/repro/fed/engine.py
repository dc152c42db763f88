"""Event-driven federation engine over the discrete-event core.

``core/events.py`` supplies the virtual clock and deterministic event queue;
this layer turns a trainer's *round plan* into completion events, drains due
events back into (tier, shape) cohorts, and executes them through the
existing vectorized cohort programs (``fed/cohort.py``) — async semantics
cost no per-client dispatch.

Trainer contract (implemented by ``BaseTrainer`` and ``DTFLTrainer``):

  plan_round(r, participants) -> RoundPlan
      Profile switching + scheduling/selection + the analytic Eq.-5
      completion offset of every client that will train. Pure planning: no
      parameter updates, no scheduler observations.
  execute_round(r, plan, trained) -> float
      Train ``trained`` (the survivors) through the cohort programs and fold
      the result into the trainer's state. Returns extra *serial* simulated
      time appended after the last completion (e.g. FedGKT's server phase).
  observe_round(plan, idx, obs_times, totals)
      Feed the event-derived timestamps of the clients that actually
      reported back to the scheduler / speed profiler. Contract: ``idx``
      indexes into ``plan.trained``; ``obs_times`` and ``totals`` are
      ALREADY SLICED to ``idx`` (obs_times[j] belongs to
      plan.trained[idx[j]]) — per-participant plan arrays such as
      ``plan.obs['nu']`` are full-length and must be indexed with ``idx``.
  train_group(r, plan, trained) -> (tree, weight)     [async mode]
      Like execute_round but returns the group-aggregated parameter tree
      instead of committing it, so the async merger can staleness-weight it.
  async_groups(cids, n_groups) -> list[list[int]]     [async mode]
      Speed grouping (fast -> slow) for FedAT-style per-tier pacing.

Three run modes:

  * :func:`run_events` — **sync**: every round's completions drain before
    aggregation. Without churn this reproduces the legacy scalar-clock loop
    exactly (same participant sampling, same clock, same scheduler
    observations — equivalence-tested in ``tests/test_events.py``); with a
    :class:`~repro.fed.client.ChurnModel` it adds dropout / arrival /
    mid-round profile switches that the scalar loop cannot express.
  * :func:`run_async` — **async tiers**: clients are grouped by speed; each
    group paces itself, and every group completion triggers a per-tier
    aggregation plus a staleness-weighted cross-tier merge (FedAT,
    arXiv:2010.05958). Fast groups stop waiting for stragglers entirely.
  * the legacy ``rounds`` loop stays in the trainers as the scalar-clock
    reference path.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, timemodel
from repro.core.events import EventQueue


@dataclass
class RoundLog:
    round: int
    clock: float
    acc: float
    assignment: dict[int, int]
    straggler: float
    # codec-true client->server bytes planned for the round (z uplink +
    # update upload; fed/dtfl.py / fed/base.py set it in plan_round)
    uplink_bytes: float = 0.0
    # guest cid -> host cid under a peer-offload topology (core/topology.py);
    # None for the classic all-server topology, so server-mode logs are
    # unchanged field-for-field
    hosts: dict[int, int] | None = None
    # bytes of the host arrays the round's device programs took in (stacked
    # batches and step masks, ``Cohort.nbytes``; the loop plane's batches);
    # the trainers set it in training, as last_h2d_bytes
    h2d_bytes: int = 0


@dataclass
class RoundPlan:
    """A trainer's declarative plan for one round (or one async wave)."""

    participants: list[int]        # sampled participants
    trained: list[int]             # subset that actually computes (TiFL/drop30)
    assign: dict[int, int]         # cid -> tier (constant for full-model)
    times: np.ndarray              # (len(trained),) Eq.-5 completion offsets
    obs: dict | None = None        # scheduler observation arrays:
                                   #   t (client+comm), nu, nb — or None
    topology: object | None = None  # core.topology.OffloadTopology, or None
                                    # (classic all-server far-half placement)


def _plan_hosts(plan: RoundPlan) -> dict[int, int] | None:
    """Guest->host map for the round log; None when every far half runs on
    the server (keeps server-mode logs identical to the pre-topology path)."""
    topo = plan.topology
    if topo is None or topo.is_server_only:
        return None
    return {k: h for k, h in topo.hosts().items() if h != -1}


def split_speed_groups(order: list[int], n_groups: int) -> list[list[int]]:
    """Slice a fast->slow ordering into ``n_groups`` contiguous speed groups
    (the remainder joins the slowest group; fewer clients than groups yields
    fewer groups). Shared by every ``async_groups`` implementation so DTFL
    and the full-model baselines group identically."""
    cut = max(1, len(order) // n_groups)
    groups = [order[i * cut: (i + 1) * cut] for i in range(n_groups - 1)]
    groups.append(order[(n_groups - 1) * cut:])
    return [g for g in groups if g]


def _participants_rng() -> np.random.Generator:
    # the legacy loops draw participants from default_rng(0); the engine must
    # consume the identical stream for sync-mode equivalence
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# resumable training state (train.py --save-every / --out-ckpt / --resume)
# ---------------------------------------------------------------------------

# the rounds and events engines share round/clock/rng semantics, so their
# envelopes resume interchangeably; async envelopes count merges, not rounds
_SYNC_ENGINES = frozenset({"rounds", "events"})


def save_train_state(path: str, trainer, *, round_: int, clock: float,
                     rng: np.random.Generator | None = None,
                     acc: float = 0.0, engine: str = "rounds") -> None:
    """Checkpoint the FULL run state as one envelope: the trainer's state
    (params, per-tier aux heads, optimizer states, scheduler history, jax
    RNG key, env profile state) plus the loop cursor (next round, virtual
    clock, last evaluated accuracy — non-eval rounds carry it forward, so
    target_acc early-stops stay resume-invariant), the participant-sampling
    numpy rng stream, and the originating engine (async envelopes count
    merges, not rounds, and must not resume a sync loop) — everything a
    resumed run needs to continue bit-for-bit where it left off."""
    from repro import checkpoint as ckpt

    state = {"round": np.int64(round_), "clock": np.float64(clock),
             "acc": np.float64(acc), "engine": engine,
             "trainer": trainer.save_state()}
    if rng is not None:
        state["rng"] = ckpt.pack_rng(rng)
    # spec-built trainers (repro.api.Federation) stamp the envelope with the
    # experiment's identity hash + canonical JSON so resume can verify it is
    # continuing the SAME experiment
    stamp = getattr(trainer, "_spec_stamp", None)
    if stamp is not None:
        state["spec"] = dict(stamp)
    ckpt.save(path, state)


def apply_resume(trainer, resume: dict, rng: np.random.Generator,
                 *, engine: str) -> tuple[int, float, float]:
    """Restore a :func:`save_train_state` envelope into ``trainer`` and the
    caller's participant rng (mutated in place so the stream continues);
    returns (start_round, start_clock, last_acc). Rejects envelopes whose
    originating engine is incompatible with ``engine``."""
    from repro import checkpoint as ckpt

    src = str(resume["engine"]) if "engine" in resume else None
    if src is not None and not (src in _SYNC_ENGINES and engine in _SYNC_ENGINES):
        raise ValueError(
            f"checkpoint was written by engine={src!r}; it cannot resume a "
            f"run under engine={engine!r} (round counters and rng streams "
            "are engine-specific)")
    trainer.load_state(resume["trainer"])
    if "rng" in resume:
        rng.bit_generator.state = ckpt.unpack_rng(resume["rng"]).bit_generator.state
    return (int(resume["round"]), float(resume["clock"]),
            float(resume.get("acc", 0.0)))


def restore_trainer(trainer, path: str) -> None:
    """Load trainer state from ``path`` — a bare ``save_state()`` dump or a
    :func:`save_train_state` envelope (unwrapped). Shared by every
    trainer's ``restore``."""
    from repro import checkpoint as ckpt

    state = ckpt.load(path)
    trainer.load_state(state["trainer"] if "trainer" in state else state)


def _round_sample_size(n_clients: int, participation: float,
                       sample_size: int | None) -> int:
    """Participants per round. ``sample_size`` is the population plane's
    absolute count (a 512-sample round over a 10^6 registry); ``None``
    keeps the legacy fractional ``participation`` sizing bit-for-bit.
    ``Generator.choice(n, k, replace=False)`` is O(k) time and memory
    (Floyd's algorithm), so sampling never scales with the registry."""
    if sample_size is None:
        return max(1, int(participation * n_clients))
    if sample_size < 1:
        raise ValueError(f"sample_size must be >= 1, got {sample_size}")
    return min(int(sample_size), n_clients)


def run_rounds(
    trainer,
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
    resume: dict | None = None,
    on_round=None,
) -> list[RoundLog]:
    """The legacy scalar-clock synchronous loop, shared by every trainer
    (``run(engine="rounds")``): sample participants, ``train_round``,
    accumulate the straggler clock, eval/log, checkpoint. DTFL's
    ``train_round`` returns ``(straggler, assign)``; full-model baselines
    return the bare straggler.

    ``on_round(log)`` (every engine takes it) is called with each
    ``RoundLog`` as soon as it is appended: the round's device work has
    finished by then, since its accuracy was read back from the new
    parameters (``on_round`` should block on ``trainer.params`` itself on a
    non-eval round)."""
    rng = _participants_rng()
    eval_fn, eval_batch = _eval_setup(trainer, eval_batch)
    clock, logs = 0.0, []
    start_round, last_acc = 0, 0.0
    if resume is not None:
        start_round, clock, last_acc = apply_resume(
            trainer, resume, rng, engine="rounds")
    next_round = start_round
    n_part = _round_sample_size(len(trainer.clients), participation, sample_size)
    for r in range(start_round, n_rounds):
        with jax.profiler.TraceAnnotation("fed.round", round=r):
            participants = sorted(
                rng.choice(len(trainer.clients), n_part, replace=False).tolist()
            )
            res = trainer.train_round(r, participants)
            straggler, assign = res if isinstance(res, tuple) else (res, {})
            clock += straggler
            acc = _evaluate(eval_fn, trainer, eval_batch) if r % eval_every == 0 else (
                logs[-1].acc if logs else last_acc)
            logs.append(RoundLog(r, clock, acc, assign, straggler,
                                 uplink_bytes=getattr(trainer, "last_uplink_bytes", 0.0),
                                 hosts=getattr(trainer, "last_hosts", None),
                                 h2d_bytes=getattr(trainer, "last_h2d_bytes", 0)))
        if on_round is not None:
            on_round(logs[-1])
        next_round = r + 1
        if verbose:
            tiers = f" tiers={sorted(set(assign.values()))}" if assign else ""
            hosts = logs[-1].hosts
            pairs = f" pairs={sorted(hosts.items())}" if hosts else ""
            print(f"[{trainer.name}] r={r} clock={clock:.0f}s acc={acc:.3f}"
                  f"{tiers}{pairs}")
        if checkpoint_path and (r + 1) % checkpoint_every == 0:
            save_train_state(checkpoint_path, trainer, round_=r + 1,
                             clock=clock, rng=rng, acc=acc)
        if target_acc is not None and acc >= target_acc:
            break
    if checkpoint_path:
        save_train_state(checkpoint_path, trainer, round_=next_round,
                         clock=clock, rng=rng,
                         acc=logs[-1].acc if logs else last_acc)
    return logs


def _eval_setup(trainer, eval_batch):
    eval_batch = {k: jnp.asarray(v) for k, v in eval_batch.items()}
    # cache the jitted eval on the trainer: repeated run() calls (and sweep
    # grid points that adopt this trainer's compiled programs) must not
    # retrace a fresh jit wrapper per run
    fn = getattr(trainer, "_eval_jit", None)
    if fn is None:
        adapter = trainer.adapter

        def eval_acc(params, batch):
            with jax.named_scope("eval"):
                return adapter.eval_acc(params, batch)

        fn = jax.jit(eval_acc)
        trainer._eval_jit = fn
    return fn, eval_batch


def _evaluate(eval_fn, trainer, eval_batch) -> float:
    """The round's accuracy: the eval dispatch and its read-back, which
    waits for the round's device work."""
    with jax.profiler.TraceAnnotation("fed.eval"):
        return float(eval_fn(trainer.params, eval_batch))


# ===========================================================================
# sync mode: the legacy round loop as a degenerate event schedule
# ===========================================================================

def run_events(
    trainer,
    n_rounds: int,
    eval_batch: dict,
    *,
    target_acc: float | None = None,
    participation: float = 1.0,
    sample_size: int | None = None,
    eval_every: int = 1,
    verbose: bool = False,
    churn=None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 10,
    resume: dict | None = None,
    on_round=None,
) -> list[RoundLog]:
    rng = _participants_rng()
    eval_fn, eval_batch = _eval_setup(trainer, eval_batch)
    q = EventQueue()
    logs: list[RoundLog] = []
    n_clients = len(trainer.clients)

    start_round, last_acc = 0, 0.0
    if resume is not None:
        if churn is not None:
            raise ValueError("resume with churn is unsupported (the churn "
                             "model's offline/arrival state is not "
                             "checkpointed); restart without --churn")
        start_round, clock0, last_acc = apply_resume(
            trainer, resume, rng, engine="events")
        q.advance_to(clock0)
    next_round = start_round

    for r in range(start_round, n_rounds):
        with jax.profiler.TraceAnnotation("fed.round", round=r):
            # no churn: pass the population SIZE, not an arange — choice(int)
            # consumes the identical rng stream as choice(arange(int)) but stays
            # O(sample) instead of materializing an O(population) id array
            pool = churn.begin_round(r) if churn is not None else n_clients
            pool_n = len(pool) if churn is not None else n_clients
            cap = (int(participation * n_clients) if sample_size is None
                   else _round_sample_size(n_clients, participation, sample_size))
            n_part = max(1, min(pool_n, cap))
            participants = sorted(rng.choice(pool, n_part, replace=False).tolist())

            plan = trainer.plan_round(r, participants)
            start = q.now
            # one completion event per trained client; payload carries the
            # planned offset so float identity survives absolute-time round trips
            pending: dict[int, object] = {}
            for i, k in enumerate(plan.trained):
                pending[i] = q.push(
                    start + plan.times[i], "complete",
                    cid=k, idx=i, offset=float(plan.times[i]),
                )
            if churn is not None:
                for kind, i, frac in churn.sample_mid_round(plan.trained, plan.times):
                    q.push(start + frac * plan.times[i], kind,
                           cid=plan.trained[i], idx=i)

            # drain the round: completions, dropouts, mid-round switches
            survivors: list[int] = []
            offsets: dict[int, float] = {}
            while not q.empty():
                ev = q.pop()
                i = ev.payload["idx"]
                if ev.kind == "complete":
                    survivors.append(i)
                    offsets[i] = ev.payload["offset"]
                elif ev.kind == "dropout":
                    if i in survivors:
                        continue  # completed before the dropout fired
                    pending[i].cancel()
                    churn.mark_offline(ev.payload["cid"])
                elif ev.kind == "switch":
                    if i in survivors:
                        continue
                    cid = ev.payload["cid"]
                    old = trainer.env.profile(cid)
                    churn.resample_profile(trainer.env, cid)
                    new = trainer.env.profile(cid)
                    new_off = timemodel.rescale_remaining(
                        pending[i].payload["offset"], ev.time - start, old, new
                    )
                    pending[i].cancel()
                    pending[i] = q.push(
                        start + new_off, "complete",
                        cid=cid, idx=i, offset=float(new_off),
                    )

            survivors.sort()
            trained = [plan.trained[i] for i in survivors]
            extra = trainer.execute_round(r, plan, trained) or 0.0

            if trained:
                ratios = np.array(
                    [offsets[i] / plan.times[i] for i in survivors]
                )
                totals = np.array([offsets[i] for i in survivors])
                if plan.obs is not None:
                    obs_t = plan.obs["t"][np.asarray(survivors, int)] * ratios
                else:
                    obs_t = totals
                trainer.observe_round(plan, survivors, obs_t, totals)
                base = float(max(offsets[i] for i in survivors)) + extra
            else:
                base = extra  # everyone dropped
            # the server learns of a dropout at the dropout timestamp, so a round
            # never ends before the last drained event (q.now)
            round_end = max(q.now, start + base)
            straggler = round_end - start
            q.advance_to(round_end)

            acc = _evaluate(eval_fn, trainer, eval_batch) if r % eval_every == 0 else (
                logs[-1].acc if logs else last_acc
            )
            logs.append(RoundLog(r, q.now, acc,
                                 plan.assign if hasattr(trainer, "sched") else {},
                                 straggler,
                                 uplink_bytes=getattr(trainer, "last_uplink_bytes", 0.0),
                                 hosts=_plan_hosts(plan),
                                 h2d_bytes=getattr(trainer, "last_h2d_bytes", 0)))
        if on_round is not None:
            on_round(logs[-1])
        next_round = r + 1
        if verbose:
            dropped = len(plan.trained) - len(trained)
            hosts = logs[-1].hosts
            print(f"[events:{trainer.name}] r={r} clock={q.now:.0f}s acc={acc:.3f}"
                  + (f" dropped={dropped}" if dropped else "")
                  + (f" pairs={sorted(hosts.items())}" if hosts else ""))
        if checkpoint_path and (r + 1) % checkpoint_every == 0:
            save_train_state(checkpoint_path, trainer, round_=r + 1,
                             clock=q.now, rng=rng, acc=acc, engine="events")
        if target_acc is not None and acc >= target_acc:
            break
    if checkpoint_path:
        save_train_state(checkpoint_path, trainer, round_=next_round,
                         clock=q.now, rng=rng,
                         acc=logs[-1].acc if logs else last_acc,
                         engine="events")
    return logs


# ===========================================================================
# async mode: FedAT-style per-tier pacing + staleness-weighted merge
# ===========================================================================

def run_async(
    trainer,
    n_rounds: int,
    eval_batch: dict,
    *,
    target_acc: float | None = None,
    participation: float = 1.0,
    eval_every: int = 1,
    verbose: bool = False,
    churn=None,
    n_groups: int = 3,
    staleness_lambda: float = 1.0,
    max_merges: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 10,
    resume: dict | None = None,
    on_round=None,
) -> list[RoundLog]:
    """Async tier federation: ``n_rounds`` is a per-group wave budget, so the
    total merge budget is ``n_rounds * n_groups`` (comparable local work to
    ``n_rounds`` synchronous rounds when groups are balanced).

    Wave 0 is a synchronous profiling round over all participants — it seeds
    the speed estimates that ``async_groups`` needs, exactly like FedAT's
    tier-profiling phase. After it, each group schedules its own completion
    events and the clock advances per *group* straggler, never per global
    straggler. A wave trains from the global params as they were at wave
    LAUNCH (the model the tier downloaded), not from merges that landed
    while the wave was in flight — that staleness is the phenomenon the
    staleness-weighted merge compensates for.

    ``checkpoint_every`` counts merges (the async analogue of rounds).
    """
    if resume is not None:
        raise ValueError("resume is supported for engine='rounds'/'events' "
                         "only (the async engine's in-flight wave queue is "
                         "not checkpointed)")
    rng = _participants_rng()
    eval_fn, eval_batch = _eval_setup(trainer, eval_batch)
    q = EventQueue()
    logs: list[RoundLog] = []
    n_clients = len(trainer.clients)
    budget = max_merges if max_merges is not None else max(1, n_rounds) * n_groups

    # ---- wave 0: synchronous profiling round (seeds speed estimates) ----
    with jax.profiler.TraceAnnotation("fed.round", round=0):
        pool = churn.begin_round(0) if churn is not None else np.arange(n_clients)
        n_part = max(1, min(len(pool), int(participation * n_clients)))
        participants = sorted(rng.choice(pool, n_part, replace=False).tolist())
        plan0 = trainer.plan_round(0, participants)
        trainer.execute_round(0, plan0, plan0.trained)
        idx0 = list(range(len(plan0.trained)))
        trainer.observe_round(
            plan0, idx0,
            plan0.obs["t"] if plan0.obs is not None else plan0.times, plan0.times,
        )
        q.advance_to(float(plan0.times.max()))
        acc = _evaluate(eval_fn, trainer, eval_batch)
        logs.append(RoundLog(0, q.now, acc, plan0.assign, float(plan0.times.max()),
                             uplink_bytes=getattr(trainer, "last_uplink_bytes", 0.0),
                             hosts=_plan_hosts(plan0),
                             h2d_bytes=getattr(trainer, "last_h2d_bytes", 0)))
    if on_round is not None:
        on_round(logs[-1])
    if target_acc is not None and acc >= target_acc:
        return logs

    # ---- async phase ----
    groups = trainer.async_groups(list(range(n_clients)), n_groups)
    tier_model: dict[int, object] = {}
    tier_weight: dict[int, float] = {}
    last_merge: dict[int, int] = {}
    wave_idx = {g: 1 for g in range(len(groups))}
    last_wave_time = {g: float(plan0.times.max()) for g in range(len(groups))}
    version = 0
    merges = 0

    def launch(g: int) -> None:
        members = groups[g]
        if churn is not None:
            act = set(churn.active())
            members = [k for k in members if k in act]
        if participation < 1.0 and members:
            m = max(1, int(participation * len(members)))
            members = sorted(rng.choice(members, m, replace=False).tolist())
        if not members:
            # whole group offline: re-poll after the group's last wave
            # duration (its natural pace), so rejoin latency stays bounded
            q.push_in(max(last_wave_time[g], 1.0), "wave", g=g, plan=None)
            return
        plan = trainer.plan_round(wave_idx[g], members)
        last_wave_time[g] = float(plan.times.max())
        # snapshot the global params the tier downloads at wave start; the
        # wave trains from this even if other groups merge meanwhile
        q.push_in(last_wave_time[g], "wave", g=g, plan=plan,
                  start_params=trainer.params)

    for g in range(len(groups)):
        launch(g)

    while merges < budget:
        ev = q.pop()
        if ev is None:
            break
        g, plan = ev.payload["g"], ev.payload["plan"]
        if churn is not None:
            churn.begin_round(wave_idx[g])
        if plan is None:
            launch(g)
            continue
        # churn inside the wave: dropouts leave the wave, switches re-roll
        # the ground-truth profile for FUTURE waves (the coarse per-group
        # event already fired, so no mid-wave reschedule is needed)
        with jax.profiler.TraceAnnotation("fed.round", round=merges + 1):
            idx = list(range(len(plan.trained)))
            if churn is not None:
                for kind, i, _ in churn.sample_mid_round(plan.trained, plan.times):
                    if kind == "dropout":
                        churn.mark_offline(plan.trained[i])
                        idx.remove(i)
                    else:
                        churn.resample_profile(trainer.env, plan.trained[i])
            trained = [plan.trained[i] for i in idx]
            wave_time = float(plan.times.max())
            if trained:
                # train from the wave-launch snapshot (the model the tier
                # actually downloaded), then restore the merged global
                current = trainer.params
                trainer.params = ev.payload["start_params"]
                try:
                    tree, w = trainer.train_group(wave_idx[g], plan, trained)
                finally:
                    trainer.params = current
                tier_model[g], tier_weight[g] = tree, w
                last_merge[g] = version
                version += 1
                # staleness-weighted cross-tier merge over groups that reported
                gs = sorted(tier_model)
                betas = [
                    tier_weight[x]
                    / (1.0 + staleness_lambda * (version - 1 - last_merge[x]))
                    for x in gs
                ]
                with jax.profiler.TraceAnnotation("fed.aggregate"):
                    trainer.params = aggregation.weighted_average(
                        [tier_model[x] for x in gs], betas
                    )
                obs_t = (plan.obs["t"][np.asarray(idx, int)]
                         if plan.obs is not None else plan.times[np.asarray(idx, int)])
                trainer.observe_round(plan, idx, obs_t, plan.times)
                merges += 1
                acc = _evaluate(eval_fn, trainer, eval_batch) if (
                    merges % eval_every == 0) else logs[-1].acc
                logs.append(RoundLog(
                    merges, q.now, acc, dict(plan.assign), wave_time,
                    uplink_bytes=getattr(trainer, "last_uplink_bytes", 0.0),
                    hosts=_plan_hosts(plan),
                    h2d_bytes=getattr(trainer, "last_h2d_bytes", 0)))
        if trained:
            if on_round is not None:
                on_round(logs[-1])
            if verbose:
                print(f"[async:{trainer.name}] merge={merges} group={g} "
                      f"clock={q.now:.0f}s acc={acc:.3f}")
            if checkpoint_path and merges % checkpoint_every == 0:
                save_train_state(checkpoint_path, trainer, round_=merges,
                                 clock=q.now, acc=acc, engine="async")
            if target_acc is not None and acc >= target_acc:
                break
        wave_idx[g] += 1
        launch(g)
    if checkpoint_path:
        save_train_state(checkpoint_path, trainer, round_=merges, clock=q.now,
                         acc=logs[-1].acc, engine="async")
    return logs
