"""The registry of jit entrypoints: every compiled program in the system.

Each :class:`Entry` names one jit entrypoint and knows how to ``build()``
it — a *fresh* jitted callable plus concrete (small) example arguments —
so :func:`repro.analysis.contracts.trace_contract` can trace and lower
it abstractly.  Building constructs host-side arrays and closures but
never executes the traced program; a contract sweep runs in seconds on a
machine with no accelerator.

Shapes are deliberately tiny (4 cells, 3-request rounds, 16-request
streams): a program's *contract* — which collectives it issues, which
callbacks it opens, which dtypes it touches, whether donation survives —
is shape-independent, and the committed baseline stays readable.  Two
exceptions mirror production config on purpose:

- ``serve_epoch_sharded`` uses the exact benchmark sweep configuration
  (``n_max=5``, ``full`` spec, ``shared_cloud + shared_edge``) on a
  one-device ``("cells",)`` mesh, so its psum inventory *is* the per-tick
  collective budget the ROADMAP's fusion item tracks — psums appear in
  the jaxpr through ``shard_map`` regardless of mesh size.
- ``serve_epoch_economy`` uses the benchmark's ``spot`` profile and
  ``full_economy`` spec, with an entry check pinning billing to int32.
"""
from __future__ import annotations

import dataclasses
import functools
import io
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.analysis import contracts
from repro.economy.routing import cost_greedy_policy
from repro.economy.tiers import advance_economy, builtin_profile
from repro.fleet.workload import random_fleet
from repro.hltrain.trainer import FleetHLParams, make_hl_trainer
from repro.fleet.env import FleetConfig
from repro.kernels.ops import flash_attention
from repro.kernels.orchestration import (group_occupancy_pallas,
                                         queue_admit_pallas)
from repro.policy.adapters import heuristic_greedy_policy, oracle_policy
from repro.serve.engine import (ECON_COUNTERS, ECON_GAUGES, TEL_COUNTERS,
                                TEL_GAUGES, ServeConfig, first_epoch_args,
                                make_serve_engine)
from repro.serve.stream import poisson_request_stream
from repro.specs.observation import make_spec, spec_dim
from repro.telemetry.live import (CALLBACK_WHITELIST, LiveEmitter,
                                  NdjsonSink, TrainLiveEmitter)


class Entry(NamedTuple):
    """One registered jit entrypoint."""
    name: str
    build: Callable      # () -> (jitted_fn, args, kwargs), fresh each call
    declared_donate: tuple = ()
    check: Optional[Callable] = None  # () -> [problem messages]


# ---------------------------------------------------------------------------
# serve engine


def _serve_build(cfg: ServeConfig, *, n_cells: int = 4, sharded: bool = False,
                 live: bool = False):
    """Build a serve engine at ``cfg`` and the abstract inputs of one
    ``run_epoch`` call, mirroring ``serve_stream``'s preparation."""
    key = jax.random.PRNGKey(0)
    k_fleet, k_stream, k_init, k_pol = jax.random.split(key, 4)
    scenario = random_fleet(k_fleet, n_cells, n_max=cfg.n_max,
                            cells_per_edge=2)
    spec = make_spec(cfg.obs_spec, cfg.n_max)
    if cfg.economy is not None:
        policy = cost_greedy_policy(spec, cfg.economy, tick_ms=cfg.tick_ms)
    else:
        policy = heuristic_greedy_policy(spec)
    mesh = None
    if sharded:
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("cells",))
    emitter = None
    if live:
        counters = TEL_COUNTERS + (ECON_COUNTERS if cfg.economy else ())
        gauges = TEL_GAUGES + (ECON_GAUGES if cfg.economy else ())
        emitter = LiveEmitter(NdjsonSink(io.StringIO()), counters, gauges,
                              window_ms=cfg.window_ms)
    engine = make_serve_engine(policy, cfg, live=emitter, mesh=mesh)
    stream = poisson_request_stream(k_stream, scenario, 400.0, rate=1.0,
                                    round_ms=cfg.round_ms, epoch_ms=200.0)
    args = first_epoch_args(engine, policy, policy.init(k_pol), scenario,
                            stream, k_init)
    return engine.run_epoch, args, {}


_SERVE_CFG = ServeConfig(n_max=3, obs_spec="base", queue_cap=8)
# the benchmark sweep's exact production config (benchmarks/serve.py):
# its psum-per-tick inventory is the collective-fusion item's baseline
_SERVE_SHARDED_CFG = ServeConfig(n_max=5, obs_spec="full", tick_ms=50.0,
                                 shared_cloud=True, shared_edge=True)
_SERVE_LIVE_CFG = ServeConfig(n_max=3, obs_spec="base", queue_cap=8,
                              telemetry=True)
_SERVE_ECON_CFG = ServeConfig(n_max=3, obs_spec="full_economy",
                              queue_cap=8, telemetry=True,
                              economy=builtin_profile("spot"))


# ---------------------------------------------------------------------------
# hltrain


_HL_PARAMS = FleetHLParams(epochs=2, n_direct=1, t_direct=2, n_world=1,
                           n_suggest=1, t_suggest=2, n_plan=1, k_best=2,
                           batch=8, direct_cap=64, world_cap=64,
                           plan_cap=32, hidden=(8, 8))


def _hltrain_build(telemetry: bool = False, live: bool = False):
    hp = (dataclasses.replace(_HL_PARAMS, telemetry=True) if telemetry
          else _HL_PARAMS)
    emitter = TrainLiveEmitter(NdjsonSink(io.StringIO())) if live else None
    trainer = make_hl_trainer(FleetConfig(n_max=3, obs_spec="base"),
                              hp, live=emitter)
    key = jax.random.PRNGKey(0)
    scenario = random_fleet(key, 4, n_max=3)
    state = trainer.init(key, scenario)
    return trainer.run, (state, scenario, 0), {"n_epochs": 1}


# ---------------------------------------------------------------------------
# policy decision surfaces


def _oracle_build():
    n_max, C = 3, 4
    spec = make_spec("base", n_max)
    policy = oracle_policy(spec)
    # abstract trace: the table's *values* are irrelevant, only shapes
    params = {"table": jnp.zeros((C, n_max, n_max), jnp.int32),
              "n_users": jnp.full((C,), n_max, jnp.int32)}
    obs = jnp.zeros((C, spec_dim(spec)), jnp.float32)
    return policy.act, (params, obs, jax.random.PRNGKey(0)), {}


def _cost_greedy_build():
    n_max, C = 3, 4
    spec = make_spec("full_economy", n_max)
    policy = cost_greedy_policy(spec, builtin_profile("spot"), tick_ms=50.0)
    scenario = random_fleet(jax.random.PRNGKey(0), C, n_max=n_max)
    params = policy.refresh(policy.init(jax.random.PRNGKey(1)), scenario)
    obs = jnp.zeros((C, spec_dim(spec)), jnp.float32)
    return policy.act, (params, obs, jax.random.PRNGKey(2)), {}


# ---------------------------------------------------------------------------
# kernels


def _group_occupancy_build():
    fn = jax.jit(lambda own, groups: group_occupancy_pallas(own, groups))
    own = jnp.ones((8,), jnp.float32)
    groups = jnp.zeros((8,), jnp.int32)
    return fn, (own, groups), {}


def _queue_admit_build():
    fn = jax.jit(queue_admit_pallas)
    C, Q, A = 4, 8, 3
    return fn, (jnp.full((C, Q), -1, jnp.int32), jnp.zeros((C,), jnp.int32),
                jnp.zeros((C,), jnp.int32), jnp.arange(A, dtype=jnp.int32),
                jnp.zeros((A,), jnp.int32), jnp.ones((A,), bool)), {}


def _flash_attention_build():
    B, S, H, D = 1, 16, 2, 8
    q = jnp.zeros((B, S, H, D), jnp.float32)
    k = jnp.zeros((B, S, H, D), jnp.float32)
    v = jnp.zeros((B, S, H, D), jnp.float32)
    return flash_attention, (q, k, v), {"q_blk": 8, "kv_blk": 8}


# ---------------------------------------------------------------------------
# economy


def _economy_build():
    profile = builtin_profile("spot")
    C, n_max = 4, 3
    fn = jax.jit(functools.partial(advance_economy, profile, tick_ms=50.0))
    from repro.economy.tiers import init_economy
    econ = init_economy(profile, C, n_max)
    z = jnp.zeros((C,), jnp.int32)
    zf = jnp.zeros((C,), jnp.float32)
    mask = jnp.zeros((C, n_max), bool)
    kwargs = dict(action=z, cursor=z, active=jnp.ones((C,), bool),
                  now=jnp.float32(0.0), round_start=zf,
                  round_actions=jnp.full((C, n_max), -1, jnp.int32),
                  in_round=mask, rec_mask=mask,
                  times=jnp.zeros((C, n_max), jnp.float32),
                  fin=jnp.zeros((C,), bool), key=jax.random.PRNGKey(0),
                  cell_ids=jnp.arange(C, dtype=jnp.int32))
    return fn, (econ,), kwargs


def _check_billing_integer():
    """Billing stays integer: the advanced economy state's µ$/mJ ledgers
    must be int32 at the abstract level (conservation-law audits compare
    them exactly; floats would drift)."""
    fn, args, kwargs = _economy_build()
    econ2, _pen, events = jax.eval_shape(fn, *args, **kwargs)
    problems = []
    for field in ("spend_uusd", "energy_mj", "cold_starts", "preemptions"):
        dt = getattr(econ2, field).dtype
        if dt != jnp.int32:
            problems.append(f"[economy_advance] {field} must be int32 "
                            f"(integer billing), got {dt}")
    for name in ("spend_uusd", "energy_mj"):
        if events[name].dtype != jnp.int32:
            problems.append(f"[economy_advance] event {name} must be "
                            f"int32, got {events[name].dtype}")
    return problems


# ---------------------------------------------------------------------------
# the registry


ENTRIES = (
    Entry("serve_epoch",
          lambda: _serve_build(_SERVE_CFG), declared_donate=(2,)),
    Entry("serve_epoch_sharded",
          lambda: _serve_build(_SERVE_SHARDED_CFG, sharded=True),
          declared_donate=(2,)),
    Entry("serve_epoch_live",
          lambda: _serve_build(_SERVE_LIVE_CFG, live=True),
          declared_donate=(2,)),
    Entry("serve_epoch_economy",
          lambda: _serve_build(_SERVE_ECON_CFG), declared_donate=(2,)),
    Entry("hltrain_run", _hltrain_build, declared_donate=(0,)),
    Entry("hltrain_run_live",
          lambda: _hltrain_build(telemetry=True, live=True),
          declared_donate=(0,)),
    Entry("oracle_act", _oracle_build),
    Entry("cost_greedy_act", _cost_greedy_build),
    Entry("orch_group_occupancy", _group_occupancy_build),
    Entry("orch_queue_admit", _queue_admit_build),
    Entry("flash_attention", _flash_attention_build),
    Entry("economy_advance", _economy_build, check=_check_billing_integer),
)


def trace_all(only: Optional[Sequence[str]] = None,
              entries: Sequence[Entry] = ENTRIES) -> dict:
    """Trace every (selected) entry to its contract.  Unknown ``--only``
    names raise — a CI assertion on a renamed entry must fail loudly."""
    if only is not None:
        known = {e.name for e in entries}
        unknown = sorted(set(only) - known)
        if unknown:
            raise KeyError(f"unknown registry entries {unknown}; "
                           f"known: {sorted(known)}")
        entries = [e for e in entries if e.name in set(only)]
    out = {}
    for e in entries:
        out[e.name] = contracts.trace_contract(
            e.name, e.build, declared_donate=e.declared_donate)
    return out


def run_check(current: dict, baseline: Optional[dict],
              entries: Sequence[Entry] = ENTRIES,
              *, partial: bool = False) -> list:
    """Policy checks + entry checks + baseline diff → problem messages.

    ``partial=True`` (a ``--only`` subset) diffs only the traced names
    against their baseline records instead of requiring the full set."""
    problems = []
    for name, c in current.items():
        problems.extend(contracts.contract_problems(
            c, callback_whitelist=CALLBACK_WHITELIST))
    by_name = {e.name: e for e in entries}
    for name in current:
        e = by_name.get(name)
        if e is not None and e.check is not None:
            problems.extend(e.check())
    if baseline is not None:
        base = baseline
        if partial:
            base = {k: v for k, v in baseline.items() if k in current}
            missing = sorted(set(current) - set(baseline))
            if missing:
                problems.append(
                    f"entries {missing} are traced but absent from the "
                    f"committed baseline — run --update")
        problems.extend(contracts.diff_contracts(base, current))
    return problems
