"""A run with the timed path broken underneath comes out not correct:
once for each fault the cell can have.  The sound path, at the same
size, comes out correct."""

import dataclasses
import importlib

import numpy as np
import pytest

from small import correct, small_run

SIM = "sim.fattree64.rag"


def test_sim_sound_run_is_correct():
    run, _ = small_run(SIM)
    assert run.counters["rows_checked"] > 0
    assert run.counters["waterfills_checked"] > 0
    assert correct(run), run.checks


def test_sim_answer_altered_where_produced(monkeypatch):
    """The scorer's winner moved to another candidate."""
    ns = importlib.import_module("repro.kernels.netkv_score")
    real = ns.netkv_score_cohort

    def broken(*a, **kw):
        costs, best = real(*a, **kw)
        return costs, (best + 1) % costs.shape[-1]

    monkeypatch.setattr(ns, "netkv_score_cohort", broken)
    run, _ = small_run(SIM)
    assert run.checks["winner_mismatch"][0] > 0
    assert run.checks["decision_mismatch"][0] > 0
    assert not correct(run)


def _broken_dispatch(monkeypatch, change):
    from repro.sim import Simulation

    real = Simulation._dispatch

    def broken(sim, rs, decision, now):
        return real(sim, rs, change(sim, decision), now)

    monkeypatch.setattr(Simulation, "_dispatch", broken)


def test_sim_decision_not_applied(monkeypatch):
    """The simulator sends the request to another instance than the
    scorer chose."""
    def other(sim, d):
        ids = [int(i) for i in sim.view.ids[:sim.view.n]]
        return dataclasses.replace(
            d, instance_id=ids[(ids.index(d.instance_id) + 1) % len(ids)])

    _broken_dispatch(monkeypatch, other)
    run, _ = small_run(SIM)
    assert run.checks["decision_mismatch"][0] > 0
    assert not correct(run)


def test_sim_bytes_altered(monkeypatch):
    """The transfer moves half of Eq. (2)'s bytes."""
    _broken_dispatch(monkeypatch,
                     lambda sim, d: dataclasses.replace(d, s_eff=d.s_eff / 2))
    run, _ = small_run(SIM)
    assert run.checks["bytes_mismatch"][0] > 0
    assert not correct(run)


@pytest.mark.parametrize("fault", ["unchanged", "halved"])
def test_sim_waterfill_broken(monkeypatch, fault):
    """FlowPlane leaves its rates as they were, or halves them."""
    from repro.cluster.network import FlowPlane

    real = FlowPlane._recompute_rates

    def broken(net, *a, **kw):
        if fault == "halved":
            real(net, *a, **kw)
            net.f_rate *= 0.5

    monkeypatch.setattr(FlowPlane, "_recompute_rates", broken)
    run, _ = small_run(SIM)
    assert run.checks["rate_rel_err"][0] >= 0.5
    assert not correct(run)


def test_sim_half_the_requests_left_out(monkeypatch):
    """Every other arrival is dropped before it reaches a prefill instance."""
    from repro.sim import Simulation

    real = Simulation._on_arrival

    def broken(sim, rs, now):
        if rs.req.request_id % 2 == 0:
            real(sim, rs, now)

    monkeypatch.setattr(Simulation, "_on_arrival", broken)
    run, _ = small_run(SIM)
    assert run.checks["unserved"][0] > 0
    assert not correct(run)


def test_sim_decode_faster_than_its_tokens(monkeypatch):
    """The decode instances run their iterations in half the model's time."""
    from repro.core.cost import IterTimeModel
    from repro.sim import Simulation

    real = Simulation.__init__

    def broken(sim, cfg):
        real(sim, cfg)
        m = sim.engine.iter_model
        sim.engine.iter_model = IterTimeModel(a=m.a / 2, b=m.b / 2)

    monkeypatch.setattr(Simulation, "__init__", broken)
    run, _ = small_run(SIM)
    assert run.checks["unserved"][0] > 0
    assert not correct(run)


def test_sim_cohort_rows_are_checked(monkeypatch):
    """Same-instant prefills make one cohort kernel call; each of its rows
    is held to the reference like a single decision, and a winner moved
    in the cohort call is caught."""
    from bench import traffic

    def burst(win, k):
        return [traffic.SimRequest(i, 0.1 + 0.4 * (i // 4), 8192, 20,
                                   tuple(("r", i, j) for j in range(512)), -1, 5.0)
                for i in range(8)]

    monkeypatch.setattr(traffic.MooncakeWindow, "trace", burst)
    run, _ = small_run(SIM, seconds=0.1)
    st = run.notes["state"]
    assert max(a[3].shape[0] for a, _, _ in st.calls) > 1
    # The window replays the burst trace once per simulation: 8 decisions each.
    assert st.k >= 1 and len(st.decided) == 8 * st.k
    assert {k for k, _ in st.decided} == set(range(st.k))
    assert correct(run), run.checks

    ns = importlib.import_module("repro.kernels.netkv_score")
    real = ns.netkv_score_cohort

    def broken(*a, **kw):
        costs, best = real(*a, **kw)
        if a[3].ndim == 2 and a[3].shape[0] > 1:      # the costliest instead
            c = np.asarray(costs).reshape(a[3].shape[0], -1)
            worst = np.argmax(np.where(c < ns.BIG / 2, c, -1.0), axis=1)
            best = worst.reshape(np.shape(best)).astype(np.asarray(best).dtype)
        return costs, best

    monkeypatch.setattr(ns, "netkv_score_cohort", broken)
    run, _ = small_run(SIM, seconds=0.1)
    assert run.checks["decision_mismatch"][0] > 0
    assert not correct(run)
