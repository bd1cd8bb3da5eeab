"""A whole run on the CPU with the timed path broken underneath: the
output check must come out false.  One case for each fault a serve cell
can have on one chip (no exchange between chips exists there): an epoch
that returns its state unchanged, half of each tick's arrivals left out,
a decision altered where it is made, and the telemetry's latency
histogram binned approximately."""
import json

import jax.numpy as jnp
import pytest

from chipbench import run

SEED = str(2**31 + 77)


def _run(root, wl, capsys):
    rc = run.main(["--workload", wl, "--seed", SEED, "--seconds", "1",
                   "--trace", "0"], require_tpu=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _state_unchanged(monkeypatch):
    from repro.serve import engine
    orig = engine.make_serve_engine

    def broken(policy, cfg, live=None, mesh=None):
        eng = orig(policy, cfg, live=live, mesh=mesh)
        return eng._replace(run_epoch=lambda p, s, state, *a: (
            state, jnp.int32(0)))
    monkeypatch.setattr(engine, "make_serve_engine", broken)


def _half_batch(monkeypatch):
    from repro.serve import engine
    orig = engine.queue_admit_pallas

    def half(q_ids, q_head, q_len, rid, cell, valid):
        valid = valid & (jnp.arange(valid.shape[0]) % 2 == 0)
        return orig(q_ids, q_head, q_len, rid, cell, valid)
    monkeypatch.setattr(engine, "queue_admit_pallas", half)


def _answer_altered(monkeypatch):
    from repro.serve import engine
    orig = engine.act_batch

    def altered(policy, params, obs, key, n_users=None):
        a = orig(policy, params, obs, key, n_users=n_users)
        return a.at[0].set((a[0] + 1) % 10)
    monkeypatch.setattr(engine, "act_batch", altered)


def _histogram_approximated(monkeypatch):
    from repro.serve import engine
    orig = engine.observe_values

    def coarse(buf, values, mask=None):
        # every latency binned one bin width (about 5.5%) too high
        return orig(buf, values * 1.056, mask)
    monkeypatch.setattr(engine, "observe_values", coarse)


@pytest.mark.parametrize("wl", ["small_replay", "small_live"])
def test_sound_run_is_correct(small_root, wl, capsys):
    out = _run(small_root, wl, capsys)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] >= 1


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _histogram_approximated])
@pytest.mark.parametrize("wl", ["small_replay", "small_live"])
def test_fault_is_caught(small_root, wl, fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = _run(small_root, wl, capsys)
    assert out["correct"] is False


def test_traced_run_reports_per_layer(small_root, capsys):
    rc = run.main(["--workload", "small_replay", "--seed", SEED,
                   "--seconds", "1", "--trace", "1"], require_tpu=False,
                  root=small_root)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    # off the chip only the host-clock metric finds something to read
    assert set(out["metrics"]) == {"host_prep_share.serve"}
    rc = run.main(["--workload", "small_live", "--seed", SEED,
                   "--seconds", "1", "--trace", "1"], require_tpu=False,
                  root=small_root)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["metrics"] == {}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
