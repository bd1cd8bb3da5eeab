"""Plain reference of the request-level serving engine's semantics.

Written from the semantics, not from the program: NumPy over the cells,
one loop iteration per tick, no kernels and no scan.  Per live tick:

1. admission: the tick's arrivals, in arrival order, join their cell's
   FIFO queue while it holds fewer than ``queue_cap``; the rest are
   dropped;
2. round formation: every idle cell with a backlog takes
   min(backlog, n_max) requests from the head of its queue as a round and
   stamps the round's start with the tick's time;
3. one decision per cell: an active round's user at the cursor gets an
   action from the policy; an idle cell runs a one-user round pinned to
   action 0;
4. the environment step: response times of every slot of the round under
   the actions so far (undecided slots count as d7 on the device), with
   the edge seen by a cell holding its edge group's edge requests and the
   cloud holding the whole fleet's cloud requests when the couplings are
   on, plus each cell's background load; a round is done when its last
   user has decided;
5. a done round writes one record per request: queueing wait (round start
   minus arrival), its slot's response time, the round's mean response
   time, served, the round's accuracy-constraint violation and the
   slot's action.  A done cell draws a fresh background;
6. telemetry, when the configuration has it: the tick's counts (admitted,
   dropped, decisions, served, violated, attained) add into the window
   of the tick's time; the window-end gauges (backlog, mean queue depth,
   requests in flight, decided slots on device, edge and cloud) take the
   tick's values; each served request's end-to-end latency adds one to
   its bin of a log-spaced histogram.  Teacher-forced, a window whose
   snapshot holds decisions of a round the program never finished (and
   so never recorded) is marked ``unforced``: those decisions are the
   reference's own.

The background draws follow the engine's documented keying: threefry
keys split from the serve key, one ``fold_in`` per global cell id.

:func:`simulate` runs this either free (the reference picks every action
itself) or teacher-forced by the program's records: where the program
served a request, its recorded action is the one applied, and the
reference scores that action against its own best choice (the decision
gap).  Teacher forcing keeps a near-tie, which a float rounding may
decide either way, from changing everything downstream of it, while a
wrong or worse action still shows as a gap.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import testbed as tb

BIG_GAP = 1e9  # the gap of an action the reference finds infeasible


class Setup(NamedTuple):
    """What the reference is given: the configuration's numbers, the
    fleet and the stream the benchmark generated, and the seeded keys and
    weights (never anything the program made)."""
    n_max: int
    queue_cap: int
    tick_ms: float
    shared_cloud: bool
    shared_edge: bool
    bg_busy_prob: float
    fleet: dict          # numpy arrays, chipbench.lib.fleet.FIELDS
    t_ms: np.ndarray     # (N,) float32, ascending
    cell: np.ndarray     # (N,) int32
    slo_ms: np.ndarray   # (N,) float32
    horizon_ms: float
    serve_key: np.ndarray  # raw uint32[2] key handed to the engine
    policy: str          # "greedy" | "dqn"
    dqn_layers: Optional[list] = None  # [(w, b), ...] numpy float32
    window_ms: Optional[float] = None  # telemetry windows; None: no telemetry
    hist: Optional[tuple] = None       # (lo_ms, hi_ms, bins) of the histogram


# ----------------------------------------------------------- background
@functools.partial(jax.jit, static_argnames=("n_cells", "n_max", "n_ticks",
                                             "p"))
def _backgrounds(serve_key, *, n_cells, n_max, n_ticks, p):
    """Backgrounds before tick 0 and the ones drawn at each live tick:
    six (n_ticks+1, C, ...) arrays."""
    def draw(key):
        def one(cid):
            ks = jax.random.split(jax.random.fold_in(key, cid), 6)
            u = lambda k, shape: jax.random.uniform(k, shape)
            return (u(ks[0], (n_max,)) < p, u(ks[1], (n_max,)) < p,
                    u(ks[2], ()) < p, u(ks[3], ()) < p,
                    (u(ks[4], ()) < p / 2).astype(jnp.int32),
                    (u(ks[5], ()) < p / 2).astype(jnp.int32))
        return jax.vmap(one)(jnp.arange(n_cells))

    # serve_stream: k_init, _ = split(key); engine.init: k_env, _ =
    # split(k_init); env.init: env_key, sub = split(k_env)
    k_init, _ = jax.random.split(serve_key)
    k_env, _ = jax.random.split(k_init)
    env_key, sub = jax.random.split(k_env)
    first = draw(sub)

    def tick(key, _):
        key, sub = jax.random.split(key)
        return key, draw(sub)

    _, rest = jax.lax.scan(tick, env_key, None, length=n_ticks)
    return tuple(jnp.concatenate([f[None], r]) for f, r in zip(first, rest))


# ------------------------------------------------------------ decisions
def greedy_costs(u, n, busy_p, busy_m, busy_m_e, busy_m_c, weak_e, k_edge,
                 k_cloud, committed, constraint, dt=np.float64):
    """(C, 10) latency estimate of each action for the user at the
    cursor, +inf where the action's accuracy would leave the round's
    constraint out of reach; where nothing is feasible, the most accurate
    actions by their estimate.  The estimate sees occupancy on the
    observation's 9-level scale (0..8)."""
    f = lambda x: np.asarray(x, dt)
    cell = np.arange(u.shape[0])
    bp = f(np.where(busy_p[cell, u], tb.BUSY_CPU, 1.0))
    bm = f(np.where(busy_m[cell, u], tb.BUSY_MEM, 1.0))
    tl = f(tb.T_LOCAL)[None, :] * bp[:, None] * bm[:, None]
    ke = f(np.minimum(k_edge, tb.OCC_LEVELS))
    kc = f(np.minimum(k_cloud, tb.OCC_LEVELS))
    one = f(1.0)
    te = (f(tb.T_EDGE) * np.maximum(one, ke + one)
          * f(np.where(busy_m_e, tb.BUSY_MEM, 1.0))
          + f(np.where(weak_e, tb.WEAK_E_EDGE, 0.0)))
    tc = (f(tb.T_CLOUD) * np.maximum(one, kc + one)
          * f(np.where(busy_m_c, tb.BUSY_MEM, 1.0))
          + f(np.where(weak_e, tb.WEAK_E_CLOUD, 0.0)))
    lat = np.concatenate([tl, te[:, None], tc[:, None]], -1).astype(
        np.float64)
    nf = f(n)
    remaining = np.maximum(one, nf - f(u))
    need = (f(constraint) * nf - f(committed)) / remaining
    feasible = (f(tb.ACC_MENU)[None, :] + f(tb.ACC_TOL) / remaining[:, None]
                >= need[:, None])
    best_acc = tb.ACC_MENU >= tb.ACC_MENU.max() - 1e-6
    fallback = np.where(best_acc[None, :], lat, np.inf)
    return np.where(feasible.any(-1)[:, None],
                    np.where(feasible, lat, np.inf), fallback)


def mlp_q(layers, obs, dt=np.float64):
    """Q-values of the served network.  ``dt`` float64 is the reference;
    an 8-bit float rounds the operands of every product (the control)."""
    x = np.asarray(obs, np.float64)
    for i, (w, b) in enumerate(layers):
        if dt == np.float64:
            x = x @ np.asarray(w, np.float64) + np.asarray(b, np.float64)
        else:
            xq = x.astype(np.float32).astype(dt).astype(np.float32)
            wq = np.asarray(w, np.float32).astype(dt).astype(np.float32)
            x = (xq @ wq + np.asarray(b, np.float32)).astype(np.float64)
        if i < len(layers) - 1:
            x = np.maximum(x, 0.0)
    return x


def observe(s: Setup, u, n_eff, busy_p, busy_m, weak_e, busy_m_e, busy_m_c,
            k_edge, k_cloud, acc_sum, cloud_fleet, edge_group):
    """(C, 4·n_max+12) observation of the ``full`` layout: the Table II
    state and round context, fleet cloud load, edge-group load, and the
    constraint targets."""
    f = lambda x: np.asarray(x, np.float64)[:, None]
    n = f(n_eff)
    onehot = (np.arange(s.n_max)[None, :] == u[:, None]).astype(np.float64)
    occ = lambda k: np.minimum(f(k), tb.OCC_LEVELS) / tb.OCC_LEVELS
    load = lambda v: np.minimum(f(v), tb.LOAD_CAP) / tb.LOAD_CAP
    return np.concatenate([
        onehot, busy_p.astype(np.float64), busy_m.astype(np.float64),
        s.fleet["weak_s"].astype(np.float64),
        occ(k_edge), f(busy_m_e), f(weak_e),
        occ(k_cloud), f(busy_m_c), f(weak_e),
        f(acc_sum) / (tb.ACC_NORM * n), f(u) / n,
        load(cloud_fleet), load(edge_group),
        f(s.fleet["constraint"]) / tb.ACC_NORM,
        f(s.fleet["latency_target"]) / tb.LATENCY_NORM], axis=1)


# ----------------------------------------------------------- simulation
def simulate(s: Setup, teacher: Optional[dict] = None,
             dt=np.float32, q_dt=np.float64) -> dict:
    """Run the stream through the reference.  ``teacher`` is the program's
    records (``action``, ``served``); ``dt`` is the dtype of the latency
    arithmetic and ``q_dt`` that of the network's products (lower ones
    make the control).  Returns the records plus ``decision_gap`` (the
    widest gap of a teacher action, 0 without one), ``decisions`` (how
    many were scored) and ``ambiguous`` (requests whose round accuracy
    lies within 1e-3 of its constraint, where float rounding may decide
    the violation flag either way)."""
    fl = s.fleet
    C, M, Q = fl["weak_e"].shape[0], s.n_max, s.queue_cap
    N = s.t_ms.shape[0]
    f = lambda x: np.asarray(x, dt)
    tick_of = np.ceil(s.t_ms.astype(np.float64) / s.tick_ms).astype(np.int64)
    n_ticks = max(1, int(np.ceil(s.horizon_ms / s.tick_ms))) + 1
    bounds = np.searchsorted(tick_of, np.arange(n_ticks + 1))
    bg = [np.asarray(x) for x in _backgrounds(
        jnp.asarray(s.serve_key), n_cells=C, n_max=M, n_ticks=n_ticks,
        p=float(s.bg_busy_prob))]
    groups = fl["edge_group"].astype(np.int64)
    gsize = np.bincount(groups, minlength=C)[groups]
    gsum = lambda v: np.bincount(groups, weights=v, minlength=C)[groups]
    weak_s, weak_e = fl["weak_s"], fl["weak_e"]
    constraint = fl["constraint"].astype(np.float64)
    slot = np.arange(M)
    cells = np.arange(C)

    ring = np.full((C, Q), -1, np.int64)
    q_head = np.zeros(C, np.int64)
    q_len = np.zeros(C, np.int64)
    cur_n = np.zeros(C, np.int64)
    cur_ids = np.full((C, M), -1, np.int64)
    round_start = np.zeros(C, np.float32)
    acts_prev = np.full((C, M), -1, np.int64)
    user = np.zeros(C, np.int64)
    bsel = np.zeros(C, np.int64)  # which draw each cell's background is
    rec = {"wait_ms": np.zeros(N, np.float32),
           "service_ms": np.zeros(N, np.float32),
           "art_ms": np.zeros(N, np.float32),
           "served": np.zeros(N, bool), "dropped": np.zeros(N, bool),
           "violated": np.zeros(N, bool),
           "action": np.full(N, -1, np.int32)}
    ambiguous = np.zeros(N, bool)
    gap, n_scored = 0.0, 0
    tel = None if s.window_ms is None else _tel_init(s, n_ticks)

    for t in range(n_ticks):
        now = np.float32(t * s.tick_ms)
        busy_p, busy_m, busy_m_e, busy_m_c, bg_edge, bg_cloud = (
            x[bsel, cells] for x in bg)
        # 1. admission, in arrival order
        ids = np.arange(bounds[t], bounds[t + 1])
        if ids.size:
            c = s.cell[ids].astype(np.int64)
            order = np.argsort(c, kind="stable")
            cs = c[order]
            first = np.r_[0, np.flatnonzero(np.diff(cs)) + 1]
            rank = np.empty_like(c)
            rank[order] = (np.arange(cs.size)
                           - np.repeat(first, np.diff(np.r_[first, cs.size])))
            ok = q_len[c] + rank < Q
            pos = (q_head[c] + q_len[c] + rank) % Q
            ring[c[ok], pos[ok]] = ids[ok]
            rec["dropped"][ids[~ok]] = True
            q_len += np.bincount(c[ok], minlength=C)
        n_adm = int(ok.sum()) if ids.size else 0
        n_drop = int(ids.size - n_adm)
        # 2. rounds at idle cells with a backlog
        start = (cur_n == 0) & (q_len > 0)
        n_new = np.where(start, np.minimum(q_len, M), 0)
        take = ring[cells[:, None], (q_head[:, None] + slot[None, :]) % Q]
        cur_ids = np.where(start[:, None],
                           np.where(slot[None, :] < n_new[:, None], take, -1),
                           cur_ids)
        q_head = (q_head + n_new) % Q
        q_len = q_len - n_new
        cur_n = np.where(start, n_new, cur_n)
        round_start = np.where(start, now, round_start)
        # 3. one decision per cell
        active = cur_n > 0
        n_eff = np.maximum(cur_n, 1)
        mask = slot[None, :] < n_eff[:, None]
        own_e = ((acts_prev == tb.A_EDGE) & mask).sum(1)
        own_c = ((acts_prev == tb.A_CLOUD) & mask).sum(1)
        k_edge = own_e + bg_edge
        k_cloud = own_c + bg_cloud
        if s.shared_edge:
            k_edge = k_edge + gsum(own_e) - own_e
        if s.shared_cloud:
            k_cloud = k_cloud + own_c.sum() - own_c
        decided = (acts_prev >= 0) & mask
        acc_sum = (tb.ACC_MENU[np.maximum(acts_prev, 0)] * decided).sum(1)
        if s.policy == "greedy":
            cost = greedy_costs(user, n_eff, busy_p, busy_m, busy_m_e,
                                busy_m_c, weak_e, k_edge, k_cloud, acc_sum,
                                constraint, dt=dt)
            a_ref = np.argmin(cost, -1)
            score = -cost  # higher is better
        else:
            obs = observe(s, user, n_eff, busy_p, busy_m, weak_e, busy_m_e,
                          busy_m_c, k_edge, k_cloud, acc_sum,
                          np.full(C, (own_c + bg_cloud).sum() / C),
                          gsum(own_e + bg_edge) / np.maximum(1, gsize))
            score = mlp_q(s.dqn_layers, obs, q_dt)
            a_ref = np.argmax(score, -1)
        a = a_ref
        if teacher is not None:
            rid = cur_ids[cells, user]
            known = active & (rid >= 0)
            known[known] = teacher["served"][rid[known]]
            if known.any():
                a_prog = np.clip(teacher["action"][rid[known]], 0,
                                 tb.N_ACTIONS - 1)
                sc = score[known]
                g = (sc.max(-1) - sc[np.arange(a_prog.size), a_prog])
                bad = (teacher["action"][rid[known]] != a_prog)
                g = np.where(np.isfinite(g) & ~bad, g, BIG_GAP)
                gap = max(gap, float(g.max()))
                n_scored += int(known.sum())
                a = a_ref.copy()
                a[known] = a_prog
        a = np.where(active, a, 0)
        # 4. environment step
        acts = acts_prev.copy()
        acts[cells, user] = a
        a_eff = np.where(acts >= 0, acts, tb.N_MODELS - 1)
        is_l = (a_eff < tb.N_MODELS) & mask
        is_e = (a_eff == tb.A_EDGE) & mask
        is_c = (a_eff == tb.A_CLOUD) & mask
        ke = is_e.sum(1) + bg_edge
        kc = is_c.sum(1) + bg_cloud
        if s.shared_edge:
            ke = ke + gsum(is_e.sum(1)) - is_e.sum(1)
        if s.shared_cloud:
            kc = kc + is_c.sum() - is_c.sum(1)
        tl = (f(tb.T_LOCAL)[np.minimum(a_eff, tb.N_MODELS - 1)]
              * f(np.where(busy_p, tb.BUSY_CPU, 1.0))
              * f(np.where(busy_m, tb.BUSY_MEM, 1.0)))
        te = (f(tb.T_EDGE) * f(np.maximum(1, ke))
              * f(np.where(busy_m_e, tb.BUSY_MEM, 1.0))
              + f(np.where(weak_e, tb.WEAK_E_EDGE, 0.0)))
        tc = (f(tb.T_CLOUD) * f(np.maximum(1, kc))
              * f(np.where(busy_m_c, tb.BUSY_MEM, 1.0))
              + f(np.where(weak_e, tb.WEAK_E_CLOUD, 0.0)))
        tt = np.where(is_l, tl, f(0.0))
        tt = np.where(is_e, te[:, None], tt)
        tt = np.where(is_c, tc[:, None], tt)
        tt = tt + f(np.where(weak_s & mask, tb.WEAK_S, 0.0))
        times = np.where(mask, tt, f(0.0)).astype(dt)
        done = user + 1 >= n_eff
        art = times.sum(1, dtype=dt) / f(n_eff)
        acc = (tb.ACC_MENU[np.where(acts >= 0, acts, 0)] * mask).sum(1) / n_eff
        violated = acc < constraint - 1e-9
        amb = np.abs(acc - constraint) < 1e-3
        # 5. records of done rounds
        fin = done & active
        rmask = fin[:, None] & (slot[None, :] < cur_n[:, None])
        rc, rj = np.nonzero(rmask)
        rid = cur_ids[rc, rj]
        rec["wait_ms"][rid] = (round_start[rc].astype(dt)
                               - s.t_ms[rid].astype(dt))
        rec["service_ms"][rid] = times[rc, rj]
        rec["art_ms"][rid] = art[rc]
        rec["served"][rid] = True
        rec["violated"][rid] = violated[rc]
        rec["action"][rid] = acts[rc, rj]
        ambiguous[rid] = amb[rc]
        if tel is not None:
            v = violated[rc]
            if teacher is not None:  # inside the rounding band: either
                v = np.where(amb[rc], teacher["violated"][rid], v)
            e2e = (rec["wait_ms"][rid].astype(dt)
                   + rec["service_ms"][rid].astype(dt)).astype(np.float32)
            in_round = active[:, None] & (slot[None, :] < cur_n[:, None])
            decided = in_round & (acts >= 0)
            # a round the program never finished has no record, so its
            # decisions are the reference's own and may split otherwise
            unforced = teacher is not None and bool(
                (decided.any(1) & ~teacher["served"][
                    np.maximum(cur_ids[:, 0], 0)]).any())
            tel["unforced"][min(int(now // s.window_ms),
                                tel["unforced"].size - 1)] = unforced
            _tel_tick(tel, s, now, e2e, {
                "admitted": n_adm, "dropped": n_drop,
                "decisions": int(active.sum()), "served": rid.size,
                "violated": int(v.sum()),
                "attained": int((e2e <= s.slo_ms[rid] + np.float32(1e-6))
                                .sum())}, {
                "backlog": q_len.sum(), "queue_depth": q_len.mean(),
                "inflight": np.where(active, cur_n, 0).sum(),
                "occ_local": (decided & (acts < tb.N_MODELS)).sum(),
                "occ_edge": (decided & (acts == tb.A_EDGE)).sum(),
                "occ_cloud": (decided & (acts == tb.A_CLOUD)).sum()})
        # done cells reset and draw a fresh background
        acts_prev = np.where(done[:, None], -1, acts)
        user = np.where(done, 0, user + 1)
        bsel = np.where(done, t + 1, bsel)
        cur_n = np.where(fin, 0, cur_n)
    return {"records": rec, "decision_gap": gap, "decisions": n_scored,
            "ambiguous": ambiguous, "telemetry": tel}


# ------------------------------------------------------------ telemetry
COUNTERS = ("admitted", "dropped", "served", "violated", "attained",
            "decisions")
GAUGES = ("backlog", "queue_depth", "inflight", "occ_local", "occ_edge",
          "occ_cloud")


def _tel_init(s: Setup, n_ticks: int) -> dict:
    # the windows cover the live ticks: the last one's time decides
    n_win = int((n_ticks - 1) * s.tick_ms // s.window_ms) + 1
    lo, hi, bins = s.hist
    return {"counters": {n: np.zeros(n_win, np.int64) for n in COUNTERS},
            "gauges": {n: np.full(n_win, np.nan) for n in GAUGES},
            "edges": np.geomspace(float(lo), float(hi),
                                  int(bins) + 1).astype(np.float32),
            "hist": np.zeros(int(bins), np.int64),
            # the window's snapshot holds decisions of unrecorded rounds
            "unforced": np.zeros(n_win, bool)}


def _tel_tick(tel: dict, s: Setup, now, e2e, counts: dict, gauges: dict):
    n_win = tel["counters"]["served"].size
    w = min(max(int(np.floor(float(now) / s.window_ms)), 0), n_win - 1)
    for n, v in counts.items():
        tel["counters"][n][w] += int(v)
    for n, v in gauges.items():
        tel["gauges"][n][w] = float(v)
    # bin b holds [edge_b, edge_b+1); values outside go to the end bins
    b = np.clip(np.searchsorted(tel["edges"], e2e, side="right") - 1, 0,
                tel["hist"].size - 1)
    tel["hist"] += np.bincount(b, minlength=tel["hist"].size)


def hist_percentile(hist, edges, p: float):
    """Nearest rank: the bin holding order statistic ceil(p/100 · n),
    given as its geometric midpoint; None on an empty histogram."""
    hist = np.asarray(hist, np.int64)
    n = int(hist.sum())
    if n == 0:
        return None
    rank = min(max(1, int(np.ceil(p / 100.0 * n))), n)
    b = int(np.searchsorted(np.cumsum(hist), rank))
    e = np.asarray(edges, np.float64)
    return float(np.sqrt(e[b] * e[b + 1]))


# ------------------------------------------------------------- reduction
PERCENTILES = (50.0, 95.0, 99.0)


def report(records: dict, slo_ms: np.ndarray) -> dict:
    """The serving report's figures from per-request records: counts,
    SLO attainment over all requests (a dropped or unfinished request
    misses), the violation rate among served requests, means and
    end-to-end latency percentiles of the served ones."""
    served = records["served"]
    wait = records["wait_ms"].astype(np.float64)
    service = records["service_ms"].astype(np.float64)
    e2e = wait + service
    n, k = served.size, int(served.sum())
    attained = served & (e2e <= slo_ms.astype(np.float64) + 1e-6)
    out = {"n_requests": n, "served_requests": k,
           "dropped_requests": int(records["dropped"].sum()),
           "slo_attainment": float(attained.sum() / n) if n else 1.0}
    out["deferred_requests"] = n - k - out["dropped_requests"]
    if k:
        out["violation_rate"] = float(records["violated"][served].mean())
        out["mean_latency_ms"] = float(e2e[served].mean())
        out["mean_wait_ms"] = float(wait[served].mean())
        out["mean_service_ms"] = float(service[served].mean())
        out["mean_art_ms"] = float(
            records["art_ms"].astype(np.float64)[served].mean())
        for p in PERCENTILES:
            out[f"p{p:g}_latency_ms"] = float(np.percentile(e2e[served], p))
    return out
