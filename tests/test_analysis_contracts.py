"""Layer-1 acceptance: the contract checker catches each tampering class.

The four ISSUE-mandated demonstrations — an added device-side psum, a
removed donate_argnums, an injected f64 op, an injected non-whitelisted
io_callback — all run through the real ``run_check`` machinery on toy
entries (cheap to trace), plus positive controls showing the same
machinery passes the untampered program.  Registry-level tests assert
the committed baseline's structure; satellite retrace tests pin the
one-cache-entry property of ``cost_greedy_policy`` and the economy
observation encoders.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import io_callback
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.analysis import contracts
from repro.analysis.registry import ENTRIES, Entry, run_check, trace_all
from repro.analysis.__main__ import load_baseline
from repro.economy.routing import cost_greedy_policy
from repro.economy.tiers import EconomyProfile, builtin_profile
from repro.fleet.workload import random_fleet
from repro.specs.observation import ObsInputs, make_spec, spec_dim
from repro.telemetry.live import CALLBACK_WHITELIST

BASELINE_PATH = Path(__file__).resolve().parent.parent / \
    "results" / "analysis_contracts.json"


def _contract_of(fn, args, declared_donate=(), name="toy"):
    return contracts.trace_contract(
        name, lambda: (fn, args, {}), declared_donate=declared_donate)


def _problems_of(contract):
    return contracts.contract_problems(
        contract, callback_whitelist=CALLBACK_WHITELIST)


# ---------------------------------------------------------------------------
# tamper demo 1: an added device-side psum


class TestPsumDrift:
    def _toy(self, with_psum: bool, check_vma: bool = False):
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("cells",))

        def body(x):
            y = x * 2.0
            return jax.lax.psum(y, "cells") if with_psum else y

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("cells"),
                                   out_specs=(P() if with_psum
                                              else P("cells")),
                                   check_vma=check_vma))
        return fn, (jnp.ones((4,), jnp.float32),)

    def test_added_psum_fails_check_with_named_contract(self):
        clean = _contract_of(*self._toy(False), name="toy_psum")
        tampered = _contract_of(*self._toy(True), name="toy_psum")
        baseline = {"toy_psum": clean.to_dict()}
        msgs = contracts.diff_contracts(baseline, {"toy_psum": tampered})
        assert msgs, "an added psum must be reported"
        assert any("[toy_psum]" in m and "collectives" in m for m in msgs)

    def test_clean_tree_passes(self):
        clean = _contract_of(*self._toy(False), name="toy_psum")
        assert contracts.diff_contracts(
            {"toy_psum": clean.to_dict()}, {"toy_psum": clean}) == []
        assert _problems_of(clean) == []

    def test_psum_counted_on_cells_axis(self):
        c = _contract_of(*self._toy(True), name="toy_psum")
        assert c.psum_cells == 1
        assert c.collectives == {"psum": {"cells": 1}}

    def test_psum_cannot_hide_behind_check_rep(self):
        # check_vma=True binds psum as psum_invariant in the body jaxpr;
        # the inventory must still count it as a cells-axis psum
        c = _contract_of(*self._toy(True, check_vma=True), name="toy_psum")
        assert c.psum_cells == 1


# ---------------------------------------------------------------------------
# tamper demo 2: dropped donate_argnums (the toy-scan regression)


def _toy_scan(donate: bool):
    def run(state, xs):
        def step(carry, x):
            return carry + x, carry.sum()
        return jax.lax.scan(step, state, xs)

    fn = jax.jit(run, donate_argnums=(0,)) if donate else jax.jit(run)
    args = (jnp.zeros((8,), jnp.float32), jnp.ones((5, 8), jnp.float32))
    return fn, args


class TestDonationDrop:
    def test_dropped_donation_is_caught(self):
        # the refactor "lost" donate_argnums but the registry still
        # declares it: the checker must fail, naming the contract
        c = _contract_of(*_toy_scan(donate=False),
                         declared_donate=(0,), name="toy_scan")
        assert c.donated == {"declared": [0], "aliased_outputs": 0}
        msgs = _problems_of(c)
        assert any("[toy_scan]" in m and "donat" in m for m in msgs), msgs

    def test_donating_scan_passes_and_aliases(self):
        c = _contract_of(*_toy_scan(donate=True),
                         declared_donate=(0,), name="toy_scan")
        assert c.donated["aliased_outputs"] >= 1
        assert _problems_of(c) == []

    def test_donation_survives_to_compiled_hlo(self):
        # end-to-end positive control: the optimized executable carries
        # the input/output alias, not just the StableHLO attribute
        fn, args = _toy_scan(donate=True)
        compiled = fn.trace(*args).lower().compile()
        assert contracts.compiled_input_output_aliases(
            compiled.as_text()) >= 1
        fn2, args2 = _toy_scan(donate=False)
        compiled2 = fn2.trace(*args2).lower().compile()
        assert contracts.compiled_input_output_aliases(
            compiled2.as_text()) == 0

    def test_baseline_diff_reports_lost_donation(self):
        with_d = _contract_of(*_toy_scan(True), declared_donate=(0,),
                              name="toy_scan")
        without = _contract_of(*_toy_scan(False), name="toy_scan")
        msgs = contracts.diff_contracts(
            {"toy_scan": with_d.to_dict()}, {"toy_scan": without})
        assert any("[toy_scan]" in m and "donated" in m for m in msgs)


# ---------------------------------------------------------------------------
# tamper demo 3: injected f64


class TestF64Injection:
    def test_injected_f64_fails(self):
        with jax.enable_x64(True):
            fn = jax.jit(lambda x: x.astype(jnp.float64).sum())
            c = _contract_of(fn, (jnp.ones((4,), jnp.float32),),
                             name="toy_f64")
        assert "float64" in c.dtypes
        msgs = _problems_of(c)
        assert any("[toy_f64]" in m and "float64" in m for m in msgs), msgs

    def test_f32_passes(self):
        fn = jax.jit(lambda x: x.sum())
        c = _contract_of(fn, (jnp.ones((4,), jnp.float32),), name="toy_f64")
        assert _problems_of(c) == []


# ---------------------------------------------------------------------------
# tamper demo 4: non-whitelisted io_callback


def _rogue_target(x):
    return None


class TestRogueCallback:
    def _toy(self, rogue: bool):
        def run(x):
            if rogue:
                io_callback(_rogue_target, None, x, ordered=False)
            return x * 2

        return jax.jit(run), (jnp.ones((4,), jnp.float32),)

    def test_rogue_callback_fails_with_named_contract(self):
        c = _contract_of(*self._toy(True), name="toy_cb")
        assert c.callbacks == ["io_callback:_rogue_target"]
        msgs = _problems_of(c)
        assert any("[toy_cb]" in m and "_rogue_target" in m
                   for m in msgs), msgs

    def test_whitelisted_lanes_pass(self):
        # the real live entries carry exactly the whitelisted targets
        base = load_baseline(BASELINE_PATH)
        assert base["serve_epoch_live"]["callbacks"] == \
            ["io_callback:on_window"]
        assert base["hltrain_run_live"]["callbacks"] == \
            ["io_callback:on_epoch"]

    def test_new_callback_is_baseline_drift_too(self):
        clean = _contract_of(*self._toy(False), name="toy_cb")
        rogue = _contract_of(*self._toy(True), name="toy_cb")
        msgs = contracts.diff_contracts(
            {"toy_cb": clean.to_dict()}, {"toy_cb": rogue})
        assert any("[toy_cb]" in m and "callbacks" in m for m in msgs)


# ---------------------------------------------------------------------------
# retrace stability


class TestRetraceStability:
    def test_unstable_static_is_caught(self):
        # a config mutated between builds -> different jaxpr each trace
        counter = {"n": 0}

        def build():
            counter["n"] += 1
            scale = float(counter["n"])
            fn = jax.jit(lambda x: x * scale)
            return fn, (jnp.ones((4,), jnp.float32),), {}

        c = contracts.trace_contract("toy_unstable", build)
        assert not c.retrace_stable
        msgs = _problems_of(c)
        assert any("[toy_unstable]" in m and "retrace" in m
                   for m in msgs), msgs

    def test_cost_greedy_one_cache_entry(self):
        # two traces at equal abstract shapes must share one cache entry
        n_max, C = 3, 4
        spec = make_spec("full_economy", n_max)
        policy = cost_greedy_policy(spec, builtin_profile("spot"),
                                    tick_ms=50.0)
        scenario = random_fleet(jax.random.PRNGKey(0), C, n_max=n_max)
        params = policy.refresh(policy.init(jax.random.PRNGKey(1)),
                                scenario)
        for seed in (2, 3):
            obs = jnp.zeros((C, spec_dim(spec)), jnp.float32)
            policy.act(params, obs, jax.random.PRNGKey(seed))
        assert policy.act._cache_size() == 1

    @pytest.mark.parametrize("variant", ["economy", "full_economy"])
    def test_economy_encoders_one_cache_entry(self, variant):
        n_max, C = 3, 4
        spec = make_spec(variant, n_max)
        enc = jax.jit(spec.encode_jnp)

        def inputs(seed):
            k = np.random.default_rng(seed)
            f = lambda *s: jnp.asarray(k.random(s), jnp.float32)
            b = lambda *s: jnp.asarray(k.random(s) < 0.5)
            i3 = lambda: jnp.asarray(k.integers(0, 3, (C, 3)), jnp.int32)
            return ObsInputs(
                user=jnp.zeros((C,), jnp.int32),
                n_users=jnp.full((C,), n_max, jnp.int32),
                busy_p_s=b(C, n_max), busy_m_s=b(C, n_max),
                weak_s=b(C, n_max), weak_e=b(C), busy_m_e=b(C),
                busy_m_c=b(C), k_edge=f(C), k_cloud=f(C),
                acc_sum=f(C), cloud_fleet=f(C), edge_group=f(C),
                constraint=f(C), latency_target=f(C),
                econ_state=i3(), econ_warm_ticks=i3(),
                econ_price=f(C, 3))

        out1 = enc(inputs(0))
        out2 = enc(inputs(1))
        assert out1.shape == out2.shape == (C, spec.dim)
        assert enc._cache_size() == 1


# ---------------------------------------------------------------------------
# the committed baseline + registry structure


class TestBaseline:
    def test_baseline_committed_and_complete(self):
        base = load_baseline(BASELINE_PATH)
        assert base is not None, "results/analysis_contracts.json missing"
        assert len(base) >= 6
        assert set(base) == {e.name for e in ENTRIES}

    def test_sharded_serve_records_cells_psums(self):
        base = load_baseline(BASELINE_PATH)
        sharded = base["serve_epoch_sharded"]
        assert sharded["psum_cells"] > 0
        assert sharded["collectives"]["psum"]["cells"] == \
            sharded["psum_cells"]
        # the single-device tick must stay collective-free
        assert base["serve_epoch"]["collectives"] == {}

    def test_all_contracts_declare_donation_where_jitted_with_donate(self):
        base = load_baseline(BASELINE_PATH)
        for name in ("serve_epoch", "serve_epoch_sharded",
                     "serve_epoch_live", "serve_epoch_economy"):
            assert base[name]["donated"]["declared"] == [2]
            assert base[name]["donated"]["aliased_outputs"] > 0
        for name in ("hltrain_run", "hltrain_run_live"):
            assert base[name]["donated"]["declared"] == [0]
            assert base[name]["donated"]["aliased_outputs"] > 0

    def test_no_f64_and_stable_everywhere(self):
        base = load_baseline(BASELINE_PATH)
        for name, c in base.items():
            assert "float64" not in c["dtypes"], name
            assert c["retrace_stable"], name

    def test_run_check_flags_missing_entry(self):
        c = _contract_of(jax.jit(lambda x: x + 1),
                         (jnp.ones((2,), jnp.float32),), name="toy_new")
        toy_entry = Entry("toy_new",
                          lambda: (jax.jit(lambda x: x + 1),
                                   (jnp.ones((2,), jnp.float32),), {}))
        msgs = run_check({"toy_new": c}, {}, (toy_entry,))
        assert any("toy_new" in m for m in msgs)

    def test_cheap_entries_trace_and_pass(self):
        current = trace_all(only=["oracle_act", "orch_group_occupancy",
                                  "economy_advance"])
        base = load_baseline(BASELINE_PATH)
        assert run_check(current, base, ENTRIES, partial=True) == []


# ---------------------------------------------------------------------------
# EconomyProfile static-arg validation (registry support)


class TestEconomyProfileValidation:
    def test_list_valued_field_rejected(self):
        with pytest.raises(TypeError, match="3-tuple"):
            dataclasses.replace(builtin_profile("spot"),
                                cold_start_ticks=[0, 20, 0])

    def test_wrong_arity_rejected(self):
        with pytest.raises(TypeError, match="3-tuple"):
            dataclasses.replace(builtin_profile("spot"),
                                preempt_prob=(0.0, 0.0))

    def test_array_entries_rejected(self):
        with pytest.raises(TypeError, match="hashable"):
            dataclasses.replace(
                builtin_profile("spot"),
                energy_j_per_req=(np.float32(1.0), np.ones(()), 2.0))

    def test_builtin_profiles_hashable(self):
        for name in ("local", "serverless", "spot"):
            hash(builtin_profile(name))
