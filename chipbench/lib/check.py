"""The comparison that decides ``correct``, and the numbers it prints.

Serve cells compare four numbers, each with its own limit from
``chipbench/limits/<cell>.json`` (PERF.md gives the readings each limit
was set from):

``decision_gap``  the widest gap by which a decision the program served
                  scores below the reference's best decision in the same
                  state (greedy: ms of its latency estimate; DQN: Q-value
                  units); an action the reference finds infeasible reads
                  ``BIG``
``record_err``    the widest relative difference, |program - reference|
                  over max(|reference|, 1 ms), of a served request's
                  queueing wait, service time or round ART; a request
                  whose served, dropped, violated (outside the rounding
                  band, see ``reference.serve.simulate``) or action
                  record differs from the reference's reads ``BIG``
``report_err``    the widest relative difference of a figure of the
                  program's serving report from the same reduction of
                  the reference's records
``telemetry_err`` the widest difference of the program's telemetry from
                  the reference's: for each per-window counter and for
                  the latency histogram, the summed absolute difference
                  over its total; for each window-end gauge, the widest
                  relative difference (against at least 1; in a window
                  whose snapshot holds decisions of rounds the program
                  never finished, whose actions it never recorded, the
                  device, edge and cloud slot counts are compared by
                  their sum); for the
                  histogram's p50/p95/p99, the relative difference; a
                  window count, bin edge or missing series that differs
                  reads ``BIG``
"""
from __future__ import annotations

import math

import numpy as np

from chipbench.reference import serve as ref

FIELDS = ("wait_ms", "service_ms", "art_ms")
NUMBERS = ("decision_gap", "record_err", "report_err", "telemetry_err")
OCC = ("occ_local", "occ_edge", "occ_cloud")
BIG = ref.BIG_GAP
FLOOR_MS = 1.0  # relative errors of values under 1 ms are taken against 1 ms


def compare_serve(setup: ref.Setup, report: dict) -> dict:
    prog = {k: np.asarray(v) for k, v in report["records"].items()}
    out = ref.simulate(setup, teacher=prog)
    r, amb = out["records"], out["ambiguous"]
    both = prog["served"] & r["served"]
    err = 0.0
    if both.any():
        for k in FIELDS:
            want = r[k][both].astype(np.float64)
            got = prog[k][both].astype(np.float64)
            err = max(err, float((np.abs(got - want)
                                  / np.maximum(np.abs(want), FLOOR_MS)).max()))
    mism = (int((prog["served"] != r["served"]).sum())
            + int((prog["dropped"] != r["dropped"]).sum())
            + int(((prog["violated"] != r["violated"]) & both & ~amb).sum())
            + int(((prog["action"] != r["action"]) & both).sum()))
    # inside the rounding band either flag is right: take the program's
    settled = dict(r, violated=np.where(amb, prog["violated"], r["violated"]))
    want = ref.report(settled, setup.slo_ms)
    rel = 0.0
    for k, v in want.items():
        got = report.get(k)
        if got is None:
            rel = math.inf
            continue
        rel = max(rel, abs(float(got) - v) / max(abs(v), 1e-12))
    return {"decision_gap": float(out["decision_gap"]),
            "record_err": BIG if mism else err, "report_err": rel,
            "telemetry_err": telemetry_err(out["telemetry"],
                                           report.get("telemetry")),
            "mismatched": mism}


def telemetry_err(want, got) -> float:
    """``want``: the reference's telemetry (None where the configuration
    has none); ``got``: the ``telemetry`` block of the program's report."""
    if want is None:
        return 0.0 if got is None else BIG
    if got is None or got.get("n_windows") != want["counters"]["served"].size:
        return BIG
    series = got["series"]
    share = lambda g, w: float(np.abs(g - w).sum() / max(1, w.sum()))
    err = 0.0
    for n, w in want["counters"].items():
        g = np.asarray(series.get(n, []), np.float64)
        if g.shape != w.shape:
            return BIG
        err = max(err, share(g, w))
    gauges = {}
    for n, w in want["gauges"].items():
        g = np.asarray([np.nan if x is None else x
                        for x in series.get(n, [])], np.float64)
        if g.shape != w.shape or (np.isnan(g) != np.isnan(w)).any():
            return BIG
        gauges[n] = (g, w)
    loose = want["unforced"]
    total = tuple(sum(gauges[n][i] for n in OCC) for i in (0, 1))
    for n in OCC:
        gauges[n] = tuple(np.where(loose, 0.0, x) for x in gauges[n])
    gauges["occ_sum"] = tuple(np.where(loose, x, 0.0) for x in total)
    for g, w in gauges.values():
        ok = ~np.isnan(w)
        if ok.any():
            err = max(err, float((np.abs(g[ok] - w[ok])
                                  / np.maximum(np.abs(w[ok]), 1.0)).max()))
    edges = np.asarray(got.get("latency_hist_edges_ms", []), np.float64)
    g = np.asarray(got.get("latency_hist", []), np.float64)
    if (edges.shape != want["edges"].shape or g.shape != want["hist"].shape
            or not np.allclose(edges, want["edges"], rtol=1e-6, atol=1e-4)):
        return BIG
    err = max(err, share(g, want["hist"]))
    for p in ref.PERCENTILES:
        w = ref.hist_percentile(want["hist"], want["edges"], p)
        v = got.get(f"hist_p{p:g}_latency_ms")
        if (v is None) != (w is None):
            return BIG
        if w is not None:
            err = max(err, abs(float(v) - w) / w)
    return err


def control_report(setup: ref.Setup, dt, q_dt) -> dict:
    """The control in the program's place: the reference run free at a
    lower precision, its records and report shaped as the program's."""
    out = ref.simulate(setup, teacher=None, dt=dt, q_dt=q_dt)
    rep = ref.report(out["records"], setup.slo_ms)
    rep["records"] = out["records"]
    if out["telemetry"] is not None:
        rep["telemetry"] = telemetry_report(out["telemetry"])
    return rep


def telemetry_report(tel: dict) -> dict:
    """The reference's telemetry in the shape of the program's report."""
    series = {n: v.tolist() for n, v in tel["counters"].items()}
    series.update({n: [None if np.isnan(x) else float(x) for x in v]
                   for n, v in tel["gauges"].items()})
    out = {"n_windows": int(tel["counters"]["served"].size),
           "series": series, "latency_hist": tel["hist"].tolist(),
           "latency_hist_edges_ms": np.round(
               tel["edges"].astype(np.float64), 4).tolist()}
    for p in ref.PERCENTILES:
        out[f"hist_p{p:g}_latency_ms"] = ref.hist_percentile(
            tel["hist"], tel["edges"], p)
    return out


def control_dtypes(config: dict) -> tuple:
    """(latency dtype, network operand dtype) of a configuration's
    control, named in its ``control`` block."""
    import ml_dtypes
    c = config["control"]
    pick = lambda n: np.dtype(getattr(ml_dtypes, n, None) or n)
    return pick(c["latency_dtype"]), pick(c["network_dtype"])


def reference_dtypes() -> tuple:
    return np.dtype(np.float32), np.dtype(np.float64)


def worst(a, b):
    if a is None:
        return dict(b)
    return {k: max(a[k], b[k]) for k in a}


def judge(numbers: dict, limits: dict) -> dict:
    checks = {}
    for name in NUMBERS:
        v = numbers[name]
        checks[name] = {"value": v if math.isfinite(v) else 1e300,
                        "limit": limits[name]}
    ok = all(math.isfinite(numbers[n]) and numbers[n] <= limits[n]
             for n in NUMBERS)
    return {"correct": ok, "checks": checks}
