"""Event-driven request-level serving engine.

The unit of work is a *request*, not a round.  The engine advances in
decision ticks of ``tick_ms`` wall clock; per tick, inside one jitted
``lax.scan`` body, it

    1. admits newly-arrived requests into fixed-capacity per-cell device
       queues (overflow = counted drop, never a silent clip),
    2. forms a round at every idle cell with backlog — the round size is
       ``min(queue_len, n_max)``, so a burst of 3·n_max requests drains
       as three consecutive rounds and an empty cell simply idles,
    3. micro-batches ALL pending decisions *across cells* through one
       ``Policy.act`` call (``act_batch`` rebinds each cell's current
       round size for round-size-conditioned policies), steps the fleet
       env once, and
    4. on round completion scatters per-request records — queueing wait,
       service latency, the round's ART and accuracy-violation flag —
       into preallocated device arrays indexed by request id.

Cells are therefore mid-round *asynchronously*: one cell can be on
decision 3 of a 7-request round while its neighbor starts a fresh
2-request round and a third sits idle, yet every tick issues exactly one
fleet-wide ``Policy.act`` — the accelerator sees the same batched
decision shape as the round-synchronous evaluator.

The host driver ``serve_stream`` chunks the tick scan at the stream's
epoch boundaries and refreshes scenario-borne policy params between
chunks (``on_epoch`` is the bundle hot-swap point), then reduces the
per-request records with ``repro.serve.metrics``.

With ``ServeConfig.telemetry`` on, a ``repro.telemetry.MetricBuffer``
rides in the scan carry: per-``window_ms`` counters (admits, drops,
served, violations, SLO attainment, decisions), window-end gauges
(backlog, queue depth, in-flight rounds, per-tier occupancy), and a
log-spaced end-to-end-latency histogram all accumulate on device — the
host sees them once, after the run, via ``telemetry_report``.

Run on a ``round_synchronous_stream`` (all arrivals on round boundaries,
counts ≤ n_max), the engine degenerates to exactly the round-replay
gateway's behavior — the parity tests enforce ART/violation agreement
with ``replay_trace`` at 1e-5.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.economy.tiers import (EconomyProfile, TierEconomyState,
                                 advance_economy)
from repro.fleet import latency
from repro.fleet.env import FleetConfig, FleetState, make_fleet_env
from repro.fleet.workload import FleetScenario
from repro.kernels.orchestration import queue_admit_lax, queue_admit_pallas
from repro.policy.api import (Policy, act_batch, refresh_params,
                              require_jittable)
from repro.serve.metrics import request_report
from repro.serve.stream import RequestStream
from repro.sharding.runtime import CELLS_AXIS, exchange_psum, get_mesh_info
from repro.telemetry.profiling import (compile_counts, compiles_since,
                                       recording, span)
from repro.telemetry.metrics import (MetricBuffer, buffer_series,
                                     count_event, merge_shard_buffers,
                                     metrics_init, observe_values,
                                     set_gauge, window_of)

# per-window counters and gauges the engine's telemetry records; counters
# scatter-add per tick, gauges keep the last (= window-end) snapshot
TEL_COUNTERS = ("admitted", "dropped", "served", "violated", "attained",
                "decisions")
TEL_GAUGES = ("backlog", "queue_depth", "inflight",
              "occ_local", "occ_edge", "occ_cloud")
# appended when ServeConfig.economy is set: per-window economy events
# (spend in µ$, energy in mJ — integers, so the audit's conservation law
# Σ window spend == run spend holds exactly) and tier-state gauges
ECON_COUNTERS = ("cold_starts", "preemptions", "spend_uusd", "energy_mj")
ECON_GAUGES = ("warm_tiers", "warming_tiers")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine configuration.  ``tick_ms`` is the wall-clock width of one
    decision tick; a full ``n_max``-request round spans ``round_ms =
    n_max * tick_ms``, which keeps queueing delays commensurate with the
    latency model's service times (hundreds of ms) and with the 150–800 ms
    SLO target pool.  ``queue_cap`` bounds each cell's backlog; arrivals
    beyond it are dropped and counted."""
    n_max: int = 5
    obs_spec: str = "base"
    tick_ms: float = 50.0
    queue_cap: int = 64
    quiet: bool = False
    shared_cloud: bool = False
    shared_edge: bool = False
    # telemetry: per-window metric series (queue depth, backlog, per-tier
    # occupancy, admits/drops, attainment) + a log-spaced latency
    # histogram, accumulated on device inside the tick scan.  Off by
    # default — the telemetry-off engine compiles to the same program as
    # before the feature existed.
    telemetry: bool = False
    window_ms: float = 1000.0
    # economy: optional per-tier cost/energy/startup profile
    # (repro.economy.EconomyProfile).  When set, a TierEconomyState rides
    # on FleetState.econ and is advanced every tick — cold starts and
    # preemptions delay recorded service, and µ$/mJ spend accumulates on
    # device.  economy=None compiles to the exact pre-feature program.
    economy: Optional[EconomyProfile] = None

    @property
    def round_ms(self) -> float:
        return self.n_max * self.tick_ms

    def fleet(self, cell_axis: Optional[str] = None,
              cell_axis_size: int = 1) -> FleetConfig:
        return FleetConfig(n_max=self.n_max, obs_spec=self.obs_spec,
                           quiet=self.quiet,
                           shared_cloud=self.shared_cloud,
                           shared_edge=self.shared_edge,
                           cell_axis=cell_axis,
                           cell_axis_size=cell_axis_size,
                           economy=self.economy)


class RequestRecords(NamedTuple):
    """Per-request outcome arrays, shape (S, N+1) — S is the mesh
    cell-shard count (1 off-mesh): every shard scatters into its own
    copy (a request is written by exactly one shard, the one serving its
    cell), and ``serve_stream`` merges the copies once at run end
    (floats sum, flags any, actions max).  Slot N is the scatter scratch
    for padded lanes; both it and the shard axis are gone by reporting
    time."""
    wait_ms: jnp.ndarray     # queueing delay: round start − arrival
    service_ms: jnp.ndarray  # response time of this request's slot
    art_ms: jnp.ndarray      # its round's ART (round-replay-compatible)
    served: jnp.ndarray      # bool — round completed within the horizon
    dropped: jnp.ndarray     # bool — rejected on queue overflow
    violated: jnp.ndarray    # bool — its round violated the accuracy SLO
    action: jnp.ndarray      # int32 — the tier/model chosen for its slot
    #                          (-1 until served); feeds the request trace


class EngineState(NamedTuple):
    env: FleetState
    key: jnp.ndarray
    q_ids: jnp.ndarray        # (C, Q) int32 — queued request ids (ring)
    q_head: jnp.ndarray       # (C,) int32
    q_len: jnp.ndarray        # (C,) int32
    cur_n: jnp.ndarray        # (C,) int32 — in-flight round size, 0 = idle
    cur_ids: jnp.ndarray      # (C, n_max) int32 — ids in the round's slots
    round_start: jnp.ndarray  # (C,) float32
    rec: RequestRecords
    tel: Optional[MetricBuffer] = None  # per-window metrics (None = off)


class ServeEngine(NamedTuple):
    """``init(key, scenario, n_requests)`` and the jitted
    ``run_epoch(params, scenario, state, tick_ids, tick_now, stream_t,
    stream_cell) -> (state', n_decisions)``.  ``epoch_traces()`` is how
    many times ``run_epoch``'s body has been traced.  ``n_shards`` is the
    cells-mesh size the epoch step is shard_mapped over (1 = single
    device)."""
    init: Callable
    run_epoch: Callable
    epoch_traces: Callable[[], int]
    cfg: ServeConfig
    n_shards: int = 1


def make_serve_engine(policy: Policy, cfg: ServeConfig,
                      live=None, mesh: Optional[Mesh] = None) -> ServeEngine:
    """``live`` is an optional ``repro.telemetry.LiveEmitter``; when set
    (requires ``cfg.telemetry``) the tick scan reports each closed
    metric window to the host through ``io_callback`` — windowed series
    stream out as NDJSON *while* the jitted epoch runs.  ``live=None``
    leaves the compiled program exactly as before.

    ``mesh`` is an optional one-axis ``("cells",)`` mesh (see
    ``repro.sharding.runtime.cells_mesh``): the epoch step is then
    ``shard_map``-ped over it — each device owns ``C / S`` cells' queues,
    env state, and record/telemetry copies, and only the cross-cell
    couplings (shared-cloud occupancy, edge-group occupancy, fleet load
    aggregates) and the decision count cross shards, via ``psum``
    (``exchange_psum``: tagged ``stage="exchange"``).
    Because the env keys background draws by *global* cell id and the
    PRNG key is replicated, the sharded engine is numerically identical
    to the single-device one for deterministic-per-cell policies (the
    parity tests enforce 1e-5 on records, telemetry, and report
    figures).  ``init`` always takes the *global* scenario; ``run_epoch``
    accepts global arrays and lets jit shard them per its specs.
    ``live`` is host-callback-based and is not supported under a mesh."""
    require_jittable(policy, "the request-level serving engine")
    if live is not None and not cfg.telemetry:
        raise ValueError("live streaming requires ServeConfig.telemetry "
                         "(the window series it exports)")
    sharded = mesh is not None
    if sharded:
        if CELLS_AXIS not in mesh.axis_names:
            raise ValueError(f"serve mesh must carry a {CELLS_AXIS!r} "
                             f"axis, got {mesh.axis_names}")
        if live is not None:
            raise ValueError("live streaming (io_callback) is not "
                             "supported under a cells mesh — run the "
                             "live serve single-device")
        Pc = P(CELLS_AXIS)
        # pytree-prefix specs: a bare spec at a subtree position covers
        # all its leaves.  Replicated: params, PRNG keys, the stream
        # arrays, tick times, histogram edges.  Sharded over cells: the
        # scenario, queues, env state, and the per-shard record /
        # telemetry copies (their leading S axis *is* the mesh axis).
        state_spec = EngineState(
            env=FleetState(key=P(), actions=Pc, user=Pc, charged=Pc,
                           bg=Pc,
                           econ=(Pc if cfg.economy is not None else None)),
            key=P(), q_ids=Pc, q_head=Pc, q_len=Pc, cur_n=Pc,
            cur_ids=Pc, round_start=Pc, rec=Pc,
            tel=(MetricBuffer(edges=P(), hist=Pc, counters=Pc, gauges=Pc)
                 if cfg.telemetry else None))
        # init places the state as run_epoch returns it, so that every
        # epoch, the first too, calls one program
        state_place = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   state_spec)
    S = int(mesh.shape[CELLS_AXIS]) if sharded else 1
    env = make_fleet_env(cfg.fleet(CELLS_AXIS if sharded else None, S))
    # init runs outside shard_map (no axis to query): a mesh-free twin
    # env builds the global initial state; its background draws match the
    # sharded env's exactly because both key draws by global cell id
    env_init = make_fleet_env(cfg.fleet()) if sharded else env
    n_max, Q = cfg.n_max, cfg.queue_cap
    slot = jnp.arange(n_max)
    # metric names are fixed at init (they are pytree structure); the
    # economy series ride in the same buffer when the profile is set
    counters = TEL_COUNTERS + (ECON_COUNTERS if cfg.economy else ())
    gauges = TEL_GAUGES + (ECON_GAUGES if cfg.economy else ())

    def _expand_tel(tel: MetricBuffer) -> MetricBuffer:
        return MetricBuffer(edges=tel.edges, hist=tel.hist[None],
                            counters={n: v[None]
                                      for n, v in tel.counters.items()},
                            gauges={n: v[None]
                                    for n, v in tel.gauges.items()})

    def _squeeze_tel(tel: MetricBuffer) -> MetricBuffer:
        return MetricBuffer(edges=tel.edges, hist=tel.hist[0],
                            counters={n: v[0]
                                      for n, v in tel.counters.items()},
                            gauges={n: v[0]
                                    for n, v in tel.gauges.items()})

    def init(key, scenario: FleetScenario, n_requests: int,
             n_windows: int = 1) -> EngineState:
        C = scenario.n_cells
        k_env, key = jax.random.split(key)
        # distinct buffers per field: the donated epoch step may not
        # receive the same buffer aliased across record arrays
        zf = lambda: jnp.zeros((S, n_requests + 1), jnp.float32)
        zb = lambda: jnp.zeros((S, n_requests + 1), bool)
        zi = jnp.full((S, n_requests + 1), -1, jnp.int32)
        tel = None
        if cfg.telemetry:
            t0 = metrics_init(n_windows, counters, gauges)
            tile = lambda x: jnp.tile(x[None], (S,) + (1,) * x.ndim)
            tel = MetricBuffer(
                edges=t0.edges, hist=tile(t0.hist),
                counters={n: tile(v) for n, v in t0.counters.items()},
                gauges={n: tile(v) for n, v in t0.gauges.items()})
        state = EngineState(
            env=env_init.init(k_env, scenario),
            key=key,
            q_ids=jnp.full((C, Q), -1, jnp.int32),
            q_head=jnp.zeros((C,), jnp.int32),
            q_len=jnp.zeros((C,), jnp.int32),
            cur_n=jnp.zeros((C,), jnp.int32),
            cur_ids=jnp.full((C, n_max), -1, jnp.int32),
            round_start=jnp.zeros((C,), jnp.float32),
            rec=RequestRecords(zf(), zf(), zf(), zb(), zb(), zb(), zi),
            tel=tel)
        return jax.device_put(state, state_place) if sharded else state

    traces = [0]  # run_epoch_body's traces (a Python count, trace time)

    def run_epoch_body(params, scenario: FleetScenario, state: EngineState,
                       tick_ids, tick_now, tick_live, stream_t,
                       stream_cell, stream_slo):
        """One epoch = a jitted scan over its ticks.  ``tick_ids`` is
        (T_e, S, A) int32 — the ids arriving at each (tick, cell-shard),
        -1-padded to the trace's max per-tick-per-shard burst;
        ``tick_now`` (T_e,) float32 is each tick's wall-clock time;
        ``tick_live`` (T_e,) bool marks real serving ticks —
        epoch-padding ticks are inert (``lax.cond`` skips them entirely)
        so the serving window is a function of the stream horizon alone,
        never of the epoch split.  ``stream_t``/``stream_cell`` are the
        (N+1,)-padded per-request arrays (replicated under sharding).
        Returns the advanced state and the number of real (non-idle)
        request decisions issued, summed across shards.

        Inside ``shard_map`` every array is this shard's block: the
        scenario and queues are its C/S cells, ``tick_ids`` its (T_e, 1,
        A) arrival rows, and the record/telemetry copies its (1, N+1) /
        (1, W) slices — squeezed here, re-expanded on return."""
        traces[0] += 1
        scratch = stream_t.shape[0] - 1  # slot N: padded-lane scatter sink
        # global id of this shard's first cell: local queue index =
        # stream cell id - cell0
        if sharded:
            cell0 = jax.lax.axis_index(CELLS_AXIS) * scenario.n_cells
        else:
            cell0 = jnp.int32(0)
        # Scenario-borne params (greedy's per-cell constraint, guarded
        # combinators' targets) are re-derived *here*, against this
        # shard's scenario block, so they arrive correctly sharded no
        # matter what shape the caller's (replicated) params carry.
        # Idempotent: refresh rebinds scenario-derived entries and keeps
        # learned weights, so the single-device program is unchanged.
        params = refresh_params(policy, params, scenario)

        def live_tick(st, ids, now):
            # each stage's device operations carry its name as an XLA
            # frontend attribute (stage="admit", ...), which survives
            # fusion and names the operation in a profiler trace

            # -- 1. admit this tick's arrivals into the per-cell rings --
            # the admission kernel runs the sequential per-lane loop and
            # one scatter writes the ring slots (the lax reference is the
            # same loop, parity-tested).  The bucketer routes each
            # arrival to its cell's shard, so valid lanes are always
            # local here.
            with set_xla_metadata(stage="admit"):
                valid = ids >= 0
                c_loc = stream_cell[jnp.maximum(ids, 0)] - cell0
                admit_fn = (queue_admit_pallas if latency.USE_KERNELS
                            else queue_admit_lax)
                q_ids, q_len, admitted = admit_fn(
                    st.q_ids, st.q_head, st.q_len, ids, c_loc, valid)
                rejected = valid & ~admitted
                dropped = st.rec.dropped.at[
                    jnp.where(rejected, ids, scratch)].set(True)
                n_adm = admitted.sum().astype(jnp.int32)
                n_drop = rejected.sum().astype(jnp.int32)

            # -- 2. form rounds at idle cells with backlog --
            with set_xla_metadata(stage="rounds"):
                start = (st.cur_n == 0) & (q_len > 0)
                n_new = jnp.where(start, jnp.minimum(q_len, n_max), 0)
                pos = (st.q_head[:, None] + slot[None, :]) % Q
                cand = jnp.take_along_axis(q_ids, pos, axis=1)
                taken = slot[None, :] < n_new[:, None]
                cur_ids = jnp.where(start[:, None],
                                    jnp.where(taken, cand, -1), st.cur_ids)
                q_head = (st.q_head + n_new) % Q
                q_len = q_len - n_new
                cur_n = jnp.where(start, n_new, st.cur_n)
                round_start = jnp.where(start, now, st.round_start)

            # -- 3. one fleet-wide micro-batched decision + env step --
            with set_xla_metadata(stage="observe"):
                active = cur_n > 0
                n_eff = jnp.maximum(cur_n, 1)
                scn_t = scenario._replace(n_users=n_eff)
                obs = env.observe(scn_t, st.env)
            with set_xla_metadata(stage="act"):
                key, k_act = jax.random.split(st.key)
                a = act_batch(policy, params, obs, k_act, n_users=n_eff)
                # idle cells run a phantom 1-user round pinned to d0-local
                # so they add no edge/cloud occupancy under shared
                # couplings; their results are masked out of every record
                a = jnp.where(active, a, 0)
            with set_xla_metadata(stage="step"):
                env2, _, _, done, info = env.step(scn_t, st.env, a)

            # -- 4. scatter per-request records for completed rounds --
            fin = done & active
            rec_mask = fin[:, None] & (slot[None, :] < cur_n[:, None])
            in_round = active[:, None] & (slot[None, :] < cur_n[:, None])
            service, art = info["times"], info["art"]
            if cfg.economy is not None:
                # advance the tier state machine: this tick's decisions
                # may trigger cold starts (charged to their slot), idle
                # tiers scale to zero, spot tiers preempt, µ$/mJ accrue
                with set_xla_metadata(stage="economy"):
                    key, k_pre = jax.random.split(key)
                    u_cur = jnp.minimum(st.env.user, n_max - 1)
                    econ2, pen, ev = advance_economy(
                        cfg.economy, st.env.econ, tick_ms=cfg.tick_ms,
                        action=a, cursor=u_cur, active=active, now=now,
                        round_start=round_start,
                        round_actions=info["actions"], in_round=in_round,
                        rec_mask=rec_mask, times=info["times"], fin=fin,
                        key=k_pre,
                        cell_ids=cell0 + jnp.arange(cur_n.shape[0]))
                    env2 = env2._replace(econ=econ2)
                    # completed requests waited out their tier's warmup:
                    # the wait lands in their service latency and the
                    # round's ART
                    pen_rec = jnp.where(rec_mask, pen, 0.0)
                    service = service + pen_rec
                    art = art + pen_rec.sum(-1) / n_eff.astype(jnp.float32)
            with set_xla_metadata(stage="scatter"):
                rid = jnp.where(rec_mask, cur_ids, scratch)
                flat = rid.reshape(-1)
                wait_lanes = round_start[:, None] - stream_t[rid]
                rec = st.rec._replace(dropped=dropped)
                rec = rec._replace(
                    wait_ms=rec.wait_ms.at[flat].set(
                        wait_lanes.reshape(-1)),
                    service_ms=rec.service_ms.at[flat].set(
                        service.reshape(-1)),
                    art_ms=rec.art_ms.at[flat].set(
                        jnp.broadcast_to(art[:, None],
                                         rid.shape).reshape(-1)),
                    served=rec.served.at[flat].set(True),
                    violated=rec.violated.at[flat].set(
                        jnp.broadcast_to(info["violated"][:, None],
                                         rid.shape).reshape(-1)),
                    action=rec.action.at[flat].set(
                        info["actions"].reshape(-1)))

            n_decisions = active.sum().astype(jnp.int32)
            tel = st.tel
            if cfg.telemetry:
                # -- 5. per-window device accumulators (no host sync) --
                with set_xla_metadata(stage="telemetry"):
                    w = window_of(tel, now, cfg.window_ms)
                    e2e = wait_lanes + service
                    attained = rec_mask & (e2e <= stream_slo[rid] + 1e-6)
                    for name, n in (
                            ("admitted", n_adm), ("dropped", n_drop),
                            ("decisions", n_decisions),
                            ("served", rec_mask.sum()),
                            ("violated",
                             (rec_mask & info["violated"][:, None]).sum()),
                            ("attained", attained.sum())):
                        tel = count_event(tel, name, w, n)
                    tel = observe_values(tel, e2e, rec_mask)
                    if cfg.economy is not None:
                        # same integers as the run totals — the audit's
                        # spend/energy conservation laws compare them
                        # exactly
                        for name in ECON_COUNTERS:
                            tel = count_event(tel, name, w, ev[name])
                        for name in ECON_GAUGES:
                            tel = set_gauge(tel, name, w, ev[name])
                    # window-end snapshots of queue/round/tier
                    # occupancy; tiers count this tick's committed slots
                    # of active rounds
                    acts = info["actions"]
                    decided = in_round & (acts >= 0)
                    for name, g in (
                            ("backlog", q_len.sum()),
                            ("queue_depth", q_len.mean()),
                            ("inflight", jnp.where(active, cur_n, 0).sum()),
                            ("occ_local", (decided
                                           & (acts < latency.N_MODELS)).sum()),
                            ("occ_edge", (decided
                                          & (acts == latency.A_EDGE)).sum()),
                            ("occ_cloud",
                             (decided & (acts == latency.A_CLOUD)).sum())):
                        tel = set_gauge(tel, name, w, g)
                    if live is not None:
                        # report this tick's window to the host; the
                        # window is closed (final) once the next tick
                        # falls past it — serve_stream's live.finish()
                        # flushes the last one
                        w2 = window_of(tel, now + cfg.tick_ms,
                                       cfg.window_ms)
                        io_callback(
                            live.on_window, None, w, w2 > w, now,
                            jnp.stack([tel.counters[n][w]
                                       for n in counters]),
                            jnp.stack([tel.gauges[n][w]
                                       for n in gauges]),
                            ordered=False)

            st2 = EngineState(
                env=env2, key=key, q_ids=q_ids, q_head=q_head,
                q_len=q_len, cur_n=jnp.where(fin, 0, cur_n),
                cur_ids=cur_ids, round_start=round_start, rec=rec,
                tel=tel)
            return st2, n_decisions

        def tick(st, xs):
            ids, now, live = xs
            return jax.lax.cond(
                live,
                lambda s: live_tick(s, ids, now),
                lambda s: (s, jnp.int32(0)),
                st)

        st0 = state._replace(
            rec=jax.tree.map(lambda x: x[0], state.rec),
            tel=(_squeeze_tel(state.tel) if cfg.telemetry else None))
        st1, n_act = jax.lax.scan(
            tick, st0, (tick_ids[:, 0], tick_now, tick_live))
        n = n_act.sum()
        if sharded:
            n = exchange_psum(n, CELLS_AXIS)
        st1 = st1._replace(
            rec=jax.tree.map(lambda x: x[None], st1.rec),
            tel=(_expand_tel(st1.tel) if cfg.telemetry else None))
        return st1, n

    if sharded:
        run_epoch = jax.shard_map(
            run_epoch_body, mesh=mesh,
            in_specs=(P(), Pc, state_spec, P(None, CELLS_AXIS),
                      P(), P(), P(), P(), P()),
            out_specs=(state_spec, P()),
            check_vma=False)
    else:
        run_epoch = run_epoch_body

    # the engine state (queues, records, telemetry accumulators) is
    # donated: each epoch's buffers are reused in place on backends that
    # support donation instead of being copied every chunk
    return ServeEngine(init=init,
                       run_epoch=jax.jit(run_epoch, donate_argnums=(2,)),
                       epoch_traces=lambda: traces[0], cfg=cfg, n_shards=S)


def _tick_buckets(stream: RequestStream, tick_ms: float,
                  ticks_per_epoch: int, n_shards: int = 1):
    """Host-side admission schedule: bucket request ids by the first tick
    whose wall clock reaches their arrival time, and — under a cells
    mesh — by the shard owning their cell (shard ``s`` holds cells
    ``[s·C/S, (s+1)·C/S)``, matching the mesh's block partition of the
    scenario).  Returns (T, S, A) -1-padded id rows (A = the max
    per-tick-per-shard burst, under a mesh rounded up to 1/8 of its power
    of two), the (T,) tick times, the (T,) live-tick mask, and the epoch
    count.  The rows are filled by one stable sort of the admitted
    requests on ``tick·S + shard``, each request going to its rank within
    its bucket.  The sort keeps ascending request id inside a bucket, so
    a row lists its ids in stream order (arrival order for a stream
    sorted by time, as ``RequestStream`` documents; the sort does not
    rely on it) and per-cell FIFO admission order is shard-invariant.

    The serving window is a function of the horizon alone: the
    ``n_ticks = ceil(horizon/tick) + 1`` live ticks cover every arrival
    strictly before ``horizon_ms`` (the +1 reaches the last partial
    interval).  T is then padded up to a whole number of epochs — one
    compiled epoch shape — but pad ticks are marked dead in the live
    mask and the engine skips them, so served/deferred/SLO accounting
    cannot shift with the epoch split; requests admitted but unfinished
    at tick ``n_ticks`` are deferred regardless of padding."""
    n_ticks = max(1, int(np.ceil(stream.horizon_ms / tick_ms))) + 1
    n_epochs = -(-n_ticks // ticks_per_epoch)
    T = n_epochs * ticks_per_epoch
    tick_of = np.ceil(np.asarray(stream.t_ms, np.float64)
                      / tick_ms).astype(np.int64)
    ok = tick_of < n_ticks
    shard_of = (np.asarray(stream.cell, np.int64)
                // (stream.n_cells // n_shards))
    key = (tick_of * n_shards + shard_of)[ok]
    counts = np.bincount(key, minlength=T * n_shards)
    A = max(1, int(counts.max()) if counts.size else 1)
    if n_shards > 1:
        # a shard's largest burst varies from stream to stream even where
        # the fleet's is fixed: round it up to an eighth of its power of
        # two, so streams of one rate share one compiled program
        q = 1 << max(0, A.bit_length() - 4)
        A = -(-A // q) * q
    # keys held in the narrowest unsigned type: at 16 bits or fewer
    # NumPy's stable sort is a radix sort
    order = np.argsort(key.astype(np.min_scalar_type(T * n_shards)),
                       kind="stable")
    row = key[order]
    rank = np.arange(row.size) - (np.cumsum(counts) - counts)[row]
    ids = np.full((T * n_shards, A), -1, np.int32)
    ids[row, rank] = np.flatnonzero(ok)[order]
    ids = ids.reshape(T, n_shards, A)
    now = (np.arange(T, dtype=np.float64) * tick_ms).astype(np.float32)
    live = np.arange(T) < n_ticks
    return ids, now, live, n_epochs


def _stream_arrays(stream: RequestStream):
    """The (N+1,)-padded per-request arrays every epoch reads: arrival
    time, cell, SLO budget (slot N is the padded-lane scratch)."""
    return (jnp.asarray(np.append(stream.t_ms, 0.0), jnp.float32),
            jnp.asarray(np.append(stream.cell, 0), jnp.int32),
            jnp.asarray(np.append(stream.slo_ms, 0.0), jnp.float32))


def _n_windows(n_ticks: int, cfg: ServeConfig) -> int:
    # windows cover the live serving ticks: the last live tick's wall
    # clock decides the count, epoch padding can never add a window
    return int((n_ticks - 1) * cfg.tick_ms // cfg.window_ms) + 1


def first_epoch_args(engine: ServeEngine, policy: Policy, params,
                     scenario: FleetScenario, stream: RequestStream,
                     key) -> tuple:
    """The arguments of ``engine.run_epoch`` for the stream's first
    epoch, prepared as :func:`serve_stream` prepares them (``key`` seeds
    the engine state) — to lower, compile or run one epoch program on
    its own."""
    cfg = engine.cfg
    ticks_per_epoch = max(1, int(round(stream.epoch_ms / cfg.tick_ms)))
    ids, now, live_ticks, _ = _tick_buckets(
        stream, cfg.tick_ms, ticks_per_epoch, n_shards=engine.n_shards)
    state = engine.init(key, scenario, stream.n_requests,
                        _n_windows(int(live_ticks.sum()), cfg))
    return (refresh_params(policy, params, scenario), scenario, state,
            jnp.asarray(ids[:ticks_per_epoch]),
            jnp.asarray(now[:ticks_per_epoch]),
            jnp.asarray(live_ticks[:ticks_per_epoch]),
            *_stream_arrays(stream))


# engines serve_stream built, least recently used first: a later call
# with an equal (policy, cfg, mesh) on a stream of the same shape reuses
# the jitted programs, so it traces, lowers and looks up nothing again
_ENGINES: OrderedDict = OrderedDict()
_MAX_ENGINES = 4


def _cached_engine(policy: Policy, cfg: ServeConfig, live,
                   mesh: Optional[Mesh],
                   shape: tuple) -> tuple[ServeEngine, bool]:
    """The engine for ``(policy, cfg, mesh)`` and a stream of ``shape``,
    and whether it was reused.  It is built by ``make_serve_engine`` on a
    miss.  The shape is in the key, so each cached engine holds the
    compiled programs of one stream shape and the cache bounds the
    executables a process keeps, not only the engines.  ``live``
    bypasses the cache: its emitter is per-run state the traced program
    closes over.  Module state the epoch program reads while it is
    traced (the functions of this module and of ``fleet.latency`` it
    calls, ``latency.USE_KERNELS``) is not in the key either: a reused
    engine serves the program traced when it was built, so a caller that
    changes such state needs a new policy (``Policy`` compares its
    callables by identity, and every constructor makes new ones)."""
    if live is not None:
        return make_serve_engine(policy, cfg, live=live, mesh=mesh), False
    key = (policy, cfg, mesh, shape)
    engine = _ENGINES.pop(key, None)
    reused = engine is not None
    if not reused:
        engine = make_serve_engine(policy, cfg, mesh=mesh)
    _ENGINES[key] = engine
    while len(_ENGINES) > _MAX_ENGINES:
        _ENGINES.popitem(last=False)
    return engine, reused


def serve_stream(policy: Policy, params, scenario: FleetScenario,
                 stream: RequestStream, cfg: ServeConfig, *, key=None,
                 on_epoch: Optional[Callable] = None,
                 live=None, verbose: bool = False,
                 mesh: Optional[Mesh] = None) -> dict:
    """Serve a :class:`RequestStream` end to end.  Returns the per-request
    report of ``repro.serve.metrics.request_report`` plus engine timing
    (steady-state = excluding the first epoch, which on the first call
    on an engine bears the compile):
    ``decisions_per_s`` counts every lane decided through ``Policy.act``
    — C per tick, phantom idle lanes included, the same accounting the
    round-replay gateway uses (C · n_max per round) so the two figures
    compare overhead apples-to-apples — and ``active_decisions_per_s``
    counts only decisions for real in-flight requests.  Under
    ``"records"``: the raw per-request numpy arrays.

    ``on_epoch(epoch_idx, params) -> params`` runs at every stream epoch
    boundary (default: re-derive scenario-borne params via
    ``Policy.refresh``) — this is where a caller hot-swaps a freshly
    trained PolicyBundle's params into live serving.

    ``live`` (a ``repro.telemetry.LiveEmitter``, requires
    ``cfg.telemetry``) streams each closed metric window as NDJSON from
    inside the jitted tick scan, writes an ``epoch`` progress record at
    every chunk boundary, and is flushed (final window + run summary)
    before this function returns.

    ``mesh`` shard_maps the engine over a ``("cells",)`` mesh (see
    ``make_serve_engine``); ``mesh=None`` picks up a cells mesh from the
    ``repro.sharding.runtime`` registry when one is set, else runs
    single-device.  The cell count must divide evenly across the mesh.
    Per-shard record and telemetry copies are merged here before
    reporting, so the returned report is shard-count-invariant (and
    ``report["mesh_cells"]`` records the shard count used).

    The engine is built once per equal ``(policy, cfg, mesh)`` and
    stream shape (cell count, request count, largest tick, ticks per
    epoch, window count) and kept for later calls (a few, least recently
    used; see ``_cached_engine``); only ``init`` runs on every call, so
    each call starts from fresh, donated state.  A call with ``live``
    builds its own.  Only a caller that serves several streams of one
    shape with the same policy object gains: ``serve_fleet`` makes one
    call per policy.

    The call's host work is recorded as spans (``repro.telemetry.span``,
    which also show in an active ``jax.profiler`` trace):
    ``serve.bucket`` (``_tick_buckets``), ``serve.arrays``
    (``_stream_arrays``), ``serve.build`` (engine build or reuse, and
    state init), one ``serve.epoch`` per epoch — holding ``serve.refresh``
    (``on_epoch``), ``serve.h2d`` (the epoch's tick slices to the
    device), ``serve.dispatch`` (``run_epoch`` up to its return: trace,
    lowering, cache lookup or compile, enqueue), ``serve.wait`` (until
    its outputs are ready), ``serve.count`` (its decision count to the
    host, from the second epoch on) and, with ``verbose`` or ``live``,
    ``serve.progress`` — and ``serve.report``, holding ``serve.merge``
    (the shard copies of records and telemetry to the host, merged).
    ``report["spans"]`` is their ``SpanRecord.summary()``;
    ``compile_time_s`` is the first ``serve.epoch`` and ``run_time_s``
    the others.  ``report["counters"]`` holds ``epoch_traces`` (times
    this call traced the epoch program), ``engine_reused`` (1 if the
    engine came from an earlier call) and the call's ``compiles_since``
    counts."""
    if scenario.n_cells != stream.n_cells:
        raise ValueError(f"stream built for {stream.n_cells} cells, "
                         f"scenario has {scenario.n_cells}")
    if mesh is None:
        mi = get_mesh_info()
        if mi is not None and mi.cells_axis is not None:
            mesh = mi.mesh
    S = int(mesh.shape[CELLS_AXIS]) if mesh is not None else 1
    if scenario.n_cells % S:
        raise ValueError(f"{scenario.n_cells} cells do not divide over "
                         f"the {S}-way {CELLS_AXIS!r} mesh")
    key = jax.random.PRNGKey(0) if key is None else key
    compiles0 = compile_counts()
    with recording() as spans:
        ticks_per_epoch = max(1, int(round(stream.epoch_ms / cfg.tick_ms)))
        with span("serve.bucket"):
            ids, now, live_ticks, n_epochs = _tick_buckets(
                stream, cfg.tick_ms, ticks_per_epoch, n_shards=S)
        N = stream.n_requests
        n_ticks = int(live_ticks.sum())
        with span("serve.arrays"):
            stream_t, stream_cell, stream_slo = _stream_arrays(stream)
        n_windows = _n_windows(n_ticks, cfg)
        with span("serve.build"):
            engine, reused = _cached_engine(
                policy, cfg, live, mesh,
                (scenario.n_cells, N, ids.shape[-1], ticks_per_epoch,
                 n_windows))
            traces0 = engine.epoch_traces()
            k_init, key = jax.random.split(key)
            state = engine.init(k_init, scenario, N, n_windows)
        params_t, lanes, active = params, 0, 0
        for e in range(n_epochs):
            lo, hi = e * ticks_per_epoch, (e + 1) * ticks_per_epoch
            with span("serve.epoch"):
                with span("serve.refresh"):
                    params_t = (refresh_params(policy, params, scenario)
                                if on_epoch is None
                                else on_epoch(e, params_t))
                with span("serve.h2d") as h2d:
                    ticks = (jnp.asarray(ids[lo:hi]),
                             jnp.asarray(now[lo:hi]),
                             jnp.asarray(live_ticks[lo:hi]))
                with span("serve.dispatch"):
                    out = engine.run_epoch(params_t, scenario, state,
                                           *ticks, stream_t, stream_cell,
                                           stream_slo)
                # held on, the epoch's tick slices would stay on the
                # device beside the next epoch's and raise its peak
                del ticks
                with span("serve.wait") as wait:
                    state, n_act = jax.block_until_ready(out)
                # the first epoch of the first call on an engine pays
                # the XLA compile
                if e > 0:
                    with span("serve.count"):
                        lanes += scenario.n_cells * int(
                            live_ticks[lo:hi].sum())
                        active += int(n_act)
                if verbose or live is not None:
                    with span("serve.progress"):
                        _progress(e, lo, hi, N, state, live, verbose,
                                  wall_s=wait.end - h2d.start)

        with span("serve.report"):
            report = _report(stream, cfg, state, live, S, n_epochs,
                             n_ticks)
    summary = spans.summary()
    epochs = summary["serve.epoch"]
    # wall-clock split: the first epoch (on the first call on an engine
    # it carries the XLA compile) and the rest, steady-state execution
    wall = epochs["total_s"] - epochs["first_s"]
    report["compile_time_s"] = epochs["first_s"]
    report["run_time_s"] = wall
    # None when there is no steady-state window (single epoch)
    report["decisions_per_s"] = (lanes / wall
                                 if lanes and wall > 0 else None)
    report["active_decisions_per_s"] = (active / wall
                                        if active and wall > 0 else None)
    report["spans"] = summary
    report["counters"] = {"epoch_traces": engine.epoch_traces() - traces0,
                          "engine_reused": int(reused),
                          **compiles_since(compiles0)}
    return report


def _progress(e: int, lo: int, hi: int, n_requests: int,
              state: EngineState, live, verbose: bool,
              wall_s: float) -> None:
    """The per-epoch progress record (``live``) and line (``verbose``)."""
    done = int(np.asarray(state.rec.served)[:, :n_requests].any(0).sum())
    backlog = int(np.asarray(state.q_len).sum())
    if live is not None:
        live.epoch(e, ticks=hi - lo, served=done, n_requests=n_requests,
                   backlog=backlog,
                   dropped=int(np.asarray(
                       state.rec.dropped)[:, :n_requests].any(0).sum()),
                   wall_s=round(wall_s, 4))
    if verbose:
        print(f"  epoch {e:3d}: ticks [{lo}, {hi}), "
              f"{done:6d}/{n_requests} requests served, "
              f"backlog {backlog}")


def _report(stream: RequestStream, cfg: ServeConfig, state: EngineState,
            live, S: int, n_epochs: int, n_ticks: int) -> dict:
    """Merge the shard copies and build the report of a finished run
    (everything but its timing, spans and counters)."""
    N = stream.n_requests

    # merge the per-shard record copies: each request has exactly one
    # writer (its cell's shard), so floats sum over the zero-initialized
    # copies, flags or together, and actions (init -1) take the max
    def _merge_rec(name, v):
        v = np.asarray(v)
        if v.dtype == np.bool_:
            return v.any(axis=0)
        if name == "action":
            return v.max(axis=0)
        return v.sum(axis=0)

    with span("serve.merge"):
        records = {k: _merge_rec(k, v)[:N] for k, v in
                   state.rec._asdict().items()}
        # shards partition the cells, so counters/histogram sum; gauges
        # are extensive totals except queue_depth, a per-cell mean
        tel = (merge_shard_buffers(state.tel,
                                   gauge_reduce={"queue_depth": "mean"})
               if cfg.telemetry else None)
    report = request_report(stream, records)
    report["mesh_cells"] = S
    report["n_epochs"] = n_epochs
    report["n_ticks"] = n_ticks
    report["tick_ms"] = cfg.tick_ms
    report["records"] = records
    if cfg.economy is not None:
        # lifetime per-cell integer totals (µ$ / mJ) summed over the
        # fleet — the same integers the telemetry windows accumulated,
        # so the audit's conservation laws compare them exactly
        econ = state.env.econ
        tot = lambda v: int(np.asarray(v, np.int64).sum())
        spend_uusd, energy_mj = tot(econ.spend_uusd), tot(econ.energy_mj)
        n_served = int(report["served_requests"])
        report["economy"] = {
            "profile": cfg.economy.name,
            "spend_uusd_total": spend_uusd,
            "cost_usd_total": spend_uusd / 1e6,
            "energy_j_total": energy_mj / 1e3,
            "cold_starts": tot(econ.cold_starts),
            "preemptions": tot(econ.preemptions),
            "cost_per_1k_requests": (spend_uusd / 1e3 / n_served
                                     if n_served else None),
            "joules_per_request": (energy_mj / 1e3 / n_served
                                   if n_served else None),
        }
    if cfg.telemetry:
        report["telemetry"] = telemetry_report(tel, cfg.window_ms)
        if live is not None:
            live.finish(report["telemetry"])
    return report


def telemetry_report(tel: MetricBuffer, window_ms: float) -> dict:
    """Host-side, JSON-safe view of the engine's metric buffer: per-window
    series (counts, window-end gauges, derived attainment) plus the
    latency histogram and its p50/p95/p99."""
    s = buffer_series(tel)
    served = s["counters"]["served"].astype(np.float64)
    attained = s["counters"]["attained"].astype(np.float64)
    attainment = [None if n == 0 else float(a / n)
                  for a, n in zip(attained, served)]
    series = {n: v.tolist() for n, v in s["counters"].items()}
    series.update({n: [None if np.isnan(x) else float(x) for x in v]
                   for n, v in s["gauges"].items()})
    series["attainment"] = attainment
    return {
        "window_ms": window_ms,
        "n_windows": tel.n_windows,
        "series": series,
        "latency_hist": s["hist"].tolist(),
        "latency_hist_edges_ms": np.round(s["edges"], 4).tolist(),
        "hist_p50_latency_ms": s["hist_percentiles"]["p50"],
        "hist_p95_latency_ms": s["hist_percentiles"]["p95"],
        "hist_p99_latency_ms": s["hist_percentiles"]["p99"],
    }
