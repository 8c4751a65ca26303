"""The event-loop simulator as a benchmark system.

The window runs whole simulations (``repro.sim.Simulation``) back to back
until ``--seconds`` have passed; the last one finishes.  ``sim_speed``
counts each simulation's fixed trace horizon (the mix's ``duration``), not
the time at which its event loop stopped.

What the window's simulations do is recorded as they run and, once the
window has closed, held against the plain references of the configuration
(the file its ``reference`` key names):

* every call of the scoring kernel: its winner and cost against
  Eq. (2)-(7) on the call's inputs;
* every request: the decode instance it was sent to is the reference's
  best on the snapshot its decision was scored on, the bytes put on the
  fabric for it are the reference's ``fabric_bytes`` for that instance,
  and it finishes with its ``output_len`` tokens, its timestamps in order;
* every water-filling pass of FlowPlane: the rates of all live flows
  against a plain max-min water-fill of their paths over the links'
  capacities.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import os
import time

import numpy as np

from bench import harness, traffic

# A kernel winner agrees with the reference when it is the reference's
# winner or ties it within this relative cost (f32 kernel vs f64).
TIE_RTOL = 1e-5
# Cohort sizes warmed in set-up: R=1 and R=2 share one program (the kernel
# pads a single row to two); Poisson-like arrivals rarely form larger ones.
WARM_ROWS = (1, 3, 4)


# ``ModelKVSpec`` fields the harness sets itself; a configuration's
# ``kv_spec`` block may set any other.
_SPEC_SET = ("name", "n_layers", "n_kv_heads", "d_head", "bytes_per_elem", "tp")


def _sim_config(config: dict, seed: int):
    """The program's configuration.  Its ``ModelKVSpec`` takes the four
    published Eq. (1) keys of ``kv`` and every key of the optional
    ``kv_spec`` block, passed on verbatim."""
    from repro.core.cost import IterTimeModel, ModelKVSpec, PrefillTimeModel
    from repro.sim import SimConfig

    topo, kv, a = config["topology"], config["kv"], config["assumed"]
    extra = config.get("kv_spec", {})
    fields = {f.name for f in dataclasses.fields(ModelKVSpec)}
    for key in extra:
        if key not in fields or key in _SPEC_SET:
            raise harness.BenchError(
                f"kv_spec key {key!r}: not a ModelKVSpec field the "
                f"configuration may set ({sorted(fields - set(_SPEC_SET))})")
    return SimConfig(
        scheduler=config["scheduler"],
        scheduler_kwargs={"backend": config["backend"]},
        n_pods=topo["n_pods"], racks_per_pod=topo["racks_per_pod"],
        servers_per_rack=topo["servers_per_rack"],
        gpus_per_server=topo["gpus_per_server"],
        n_tor_uplinks=topo["n_tor_uplinks"], n_agg_uplinks=topo["n_agg_uplinks"],
        tp=config["tp"], n_prefill=config["n_prefill"],
        beta_max=config["beta_max"], hbm_free_per_gpu=a["hbm_free_per_gpu"],
        m_min=config["m_min"],
        kv_spec=ModelKVSpec(name=config["model"], n_layers=kv["num_hidden_layers"],
                            n_kv_heads=kv["num_key_value_heads"],
                            d_head=kv["head_dim"],
                            bytes_per_elem=kv["bytes_per_elem"], tp=config["tp"],
                            **extra),
        iter_model=IterTimeModel(a=a["iter_a"], b=a["iter_b"]),
        prefill_model=PrefillTimeModel(c=a["prefill_c"], d=a["prefill_d"]),
        warmup=config["warmup"], measure=config["measure"], seed=seed)


def _n_decode(cfg: dict) -> int:
    t = cfg["topology"]
    gpus = t["n_pods"] * t["racks_per_pod"] * t["servers_per_rack"] * t["gpus_per_server"]
    return gpus // cfg["tp"] - cfg["n_prefill"]


def deployment(cfg: dict, ref) -> dict:
    """The configuration's side of the mix's capacity model; the bytes a
    request puts on the fabric come from the reference ``ref``."""
    a = cfg["assumed"]
    return dict(n_prefill=cfg["n_prefill"], n_decode=_n_decode(cfg),
                beta_max=cfg["beta_max"],
                fabric_bytes=functools.partial(ref.fabric_bytes, cfg["kv"]),
                iter_ab=(a["iter_a"], a["iter_b"]),
                prefill_cd=(a["prefill_c"], a["prefill_d"]), **cfg["fabric"])


def reference(config: dict):
    """The configuration's plain reference: the file its ``reference`` key
    names, relative to ``bench/configs``."""
    return harness.load_module(os.path.join(harness.BENCH, "configs",
                                            config["reference"]))


class State:
    def __init__(self, win):
        self.win = win
        self.k = 0               # the simulation the window is in
        self.calls = []          # (inputs, kw, out) per kernel call
        self.decided = {}        # (k, request id) -> (call, row, ids)
        self.n_decided = 0
        self.dispatched = {}     # (k, request id) -> (instance, bytes sent)
        self.sent = []           # bytes of each transfer started
        self.fills = []          # (k, paths, rates) per water-filling pass
        self.caps = []           # per simulation: link capacities + pad
        self.records = []        # per simulation: its request states
        self.undo = []

    def patch(self, owner, attr: str, make) -> None:
        real = getattr(owner, attr)
        self.undo.append((owner, attr, real))
        setattr(owner, attr, make(real))

    def unpatch(self) -> None:
        while self.undo:
            owner, attr, real = self.undo.pop()
            setattr(owner, attr, real)


def _record(st: State, run: harness.Run) -> None:
    """Hooks on the layers the check covers; each keeps what it saw and
    calls the program's own code unchanged."""
    from repro.cluster.network import FlowPlane
    from repro.core.dispatch import CohortSelector
    from repro.core.schedulers import NetKVFull
    from repro.sim import Simulation

    ns = importlib.import_module("repro.kernels.netkv_score")

    def kernel(real):                      # every caller looks it up per call
        def rec(*args, **kw):
            # NumPy columns are live views of the simulator's state: copy them.
            snap = [a.copy() if isinstance(a, np.ndarray) else a for a in args]
            out = real(*args, **kw)
            st.calls.append((snap, dict(kw), out))
            return out
        return rec

    def select(real):                      # one request, one kernel call
        def rec(sched, req, prefill_id, cv, *a, **kw):
            n0 = len(st.calls)
            d = real(sched, req, prefill_id, cv, *a, **kw)
            call = n0 if len(st.calls) == n0 + 1 else -1
            st.decided[(st.k, req.request_id)] = (call, 0, cv.ids[:cv.n].copy())
            st.n_decided += 1
            return d
        return rec

    def build(real):                       # a cohort's one kernel call
        def rec(sel, *a, **kw):
            real(sel, *a, **kw)
            sel.bench_call = len(st.calls) - 1
        return rec

    def row(real):                         # a cohort row: cached or rescored
        def rec(sel, k, req, *a, **kw):
            n = st.n_decided
            d = real(sel, k, req, *a, **kw)
            if st.n_decided == n:
                i = getattr(sel, "_pl_rows", {}).get(k)
                call = getattr(sel, "bench_call", -1) if i is not None else -1
                st.decided[(st.k, req.request_id)] = (
                    call, i or 0, sel._cv.ids[:sel._cv.n].copy())
                st.n_decided += 1
            return d
        return rec

    def start(real):
        def rec(net, src, dst, total_bytes, *a, **kw):
            st.sent.append(float(total_bytes))
            return real(net, src, dst, total_bytes, *a, **kw)
        return rec

    def dispatch(real):
        def rec(sim, rs, decision, now):
            n0 = len(st.sent)
            real(sim, rs, decision, now)
            st.dispatched[(st.k, rs.req.request_id)] = (
                int(decision.instance_id), float(sum(st.sent[n0:])))
        return rec

    def fill(real):
        def rec(net, *a, **kw):
            real(net, *a, **kw)
            slots = np.fromiter(net._slot_order, np.intp, len(net._slot_order))
            if slots.size:
                st.fills.append((st.k, net.f_path[slots].copy(),
                                 net.f_rate[slots].copy()))
        return rec

    if run.traced:
        real = run.spans.wrap(FlowPlane, "_recompute_rates", "waterfill")
        st.undo.append((FlowPlane, "_recompute_rates", real))
    st.patch(ns, "netkv_score_cohort", kernel)
    st.patch(NetKVFull, "_select_pallas", select)
    st.patch(CohortSelector, "_build_pallas", build)
    st.patch(CohortSelector, "_pallas_row", row)
    st.patch(FlowPlane, "start_transfer", start)
    st.patch(Simulation, "_dispatch", dispatch)
    st.patch(FlowPlane, "_recompute_rates", fill)


def setup(run: harness.Run) -> State:
    from repro.kernels.netkv_score import netkv_score_cohort
    from repro.kernels.ops import interpret_mode
    from repro.sim import Simulation

    cfg, mix = run.config, run.mix
    if mix.get("kind") != "mooncake":
        raise harness.BenchError(f"the sim system runs mooncake mixes, not "
                                 f"{mix.get('kind')!r}")
    a = cfg["assumed"]
    st = State(traffic.MooncakeWindow(mix, deployment(cfg, reference(cfg))))
    run.counters["offered_rps"] = st.win.rps
    # Warm: one short simulation on a trace of its own, then the kernel at
    # the cohort sizes the short run may not have formed.
    warm = traffic.mooncake_trace(mix["profile"], duration=mix["warm_duration"],
                                  target_rps=st.win.rps, seed=mix["base_seed"] - 1)
    Simulation(_sim_config(cfg, traffic.small_seed(run.seed, 5))).run(
        warm, drain=mix["drain"])
    n_dec = _n_decode(cfg)
    for r in WARM_ROWS:
        out = netkv_score_cohort(
            np.full(n_dec, 1e12), np.zeros(n_dec), np.zeros(n_dec),
            np.zeros((r, n_dec), np.float32), np.zeros((r, n_dec), np.int32),
            np.ones(n_dec), np.ones(n_dec), [1e10] * 4, [1e-5] * 4, [0.0] * 4,
            np.zeros((r, 4), np.float32), s_r=[1e9] * r, input_len=[1024.0] * r,
            iter_a=a["iter_a"], iter_b=a["iter_b"], m_min=cfg["m_min"],
            beta_max=cfg["beta_max"], interpret=interpret_mode())
        np.asarray(out[1])
    return st


def window(run: harness.Run, st: State) -> None:
    from repro.sim import Simulation

    events = requests = rejected = 0
    decision_s = []
    try:
        _record(st, run)
        t0 = time.perf_counter()
        with run.spans.span("window"):
            while True:
                trace = st.win.trace(st.k)
                sim = Simulation(_sim_config(run.config,
                                             traffic.small_seed(run.seed, 4, st.k)))
                if not sim.bg.is_static:
                    raise harness.BenchError("the water-fill check assumes "
                                             "static link capacities")
                st.caps.append(np.append(sim.tree.link_capacity, np.inf))
                with run.spans.span("simulation"):
                    sim.run(trace, drain=run.mix["drain"])
                st.records.append(sim.records)
                events += sim.loop.processed
                requests += len(sim.records)
                rejected += sim.rejected
                decision_s.extend(sim.decision_latencies)
                st.k += 1
                if time.perf_counter() - t0 >= run.seconds:
                    break
        t1 = time.perf_counter()
    finally:
        st.unpatch()
    run.window = (t0, t1)
    sim_s = st.k * float(run.mix["duration"])
    run.notes["state"] = st
    run.notes["score_shapes"] = [(int(a[3].shape[0]) if a[3].ndim == 2 else 1,
                                  int(a[0].shape[0])) for a, _, _ in st.calls]
    run.e2e["sim_speed"] = sim_s / (t1 - t0)
    run.counters.update(simulations=st.k, sim_seconds=sim_s, events=events,
                        kernel_calls=len(st.calls), waterfills=len(st.fills))
    run.samples["select_s"] = decision_s
    run.attempted, run.failed = int(requests), int(rejected)


def free(run: harness.Run, st: State) -> None:
    st.win = None


# Scorer keywords the reference reads under another name.
_KW_NAMES = {"input_len": "l_r"}


def kernel_rows(calls):
    """Per call: the inputs as f64 NumPy arrays and scalars, and the
    kernel's cost rows and winners.  Every keyword of the call is in the
    inputs under its own name (``input_len`` as ``l_r``): a per-row one
    raveled, a scalar one as a float."""
    for args, kw, out in calls:
        cols = [np.asarray(a, np.float64) for a in args]
        r = cols[3].reshape(-1, cols[0].shape[0]).shape[0]
        inputs = dict(
            free=cols[0], queued=cols[1], batch=cols[2],
            hit=cols[3].reshape(r, -1), tier=cols[4].reshape(r, -1).astype(np.int64),
            healthy=cols[5], scale=cols[6], bw=cols[7].ravel(),
            lat=cols[8].ravel(), cong=cols[9].ravel(),
            infl=cols[10].reshape(r, 4))
        for name, v in kw.items():
            v = np.asarray(v, np.float64)
            inputs[_KW_NAMES.get(name, name)] = float(v) if v.ndim == 0 else v.ravel()
        costs, best = (np.asarray(o) for o in out)
        yield inputs, costs.reshape(r, -1).astype(np.float64), best.ravel()


def _accepted(want, feasible):
    """Per row: the candidates the reference accepts as its winner (its
    best, or a tie within ``TIE_RTOL``); none where nothing is feasible."""
    best = np.where(feasible, want, np.inf).min(axis=1, keepdims=True)
    return feasible & (np.abs(want - best)
                       <= TIE_RTOL * np.maximum(np.abs(best), 1e-9))


def compare(ref, calls, scorer=None):
    """(rows whose winner disagrees with the f64 reference, largest
    relative error of a feasible winner's cost, rows, the reference's
    (inputs, accepted) per call).  ``scorer`` puts another computation
    in the kernel's place (the control)."""
    mismatch, err, rows, refs = 0, 0.0, 0, []
    for inputs, costs, best in kernel_rows(calls):
        want, feasible = ref.score(inputs, np.float64)
        ok = _accepted(want, feasible)
        refs.append((inputs, ok))
        if scorer is not None:
            got, _ = scorer(inputs)
            got = np.where(feasible, got, ref.BIG)
            best = np.argmin(got, axis=1)
            costs = got
        for i in range(want.shape[0]):
            rows += 1
            j = int(best[i])
            if not feasible[i].any():
                mismatch += int(costs[i, j] < ref.BIG / 2)
                continue
            mismatch += int(not ok[i, j])
            if feasible[i, j]:
                err = max(err, abs(costs[i, j] - want[i, j])
                          / max(abs(want[i, j]), 1e-9))
    return mismatch, err, rows, refs


def _in_order(rs) -> bool:
    ts = (rs.req.arrival, rs.prefill_end, rs.sched_time, rs.transfer_end,
          rs.admit_time, rs.first_token, rs.finish)
    return all(t >= 0 for t in ts[1:]) and all(
        a <= b for a, b in zip(ts, ts[1:]))


def check_requests(ref, config, st: State, refs) -> dict:
    """Per request: decision, bytes and completion against the reference."""
    decision = nbytes = unserved = 0
    a, b = config["assumed"]["iter_a"], config["assumed"]["iter_b"]
    for k, records in enumerate(st.records):
        for rs in records:
            rid = rs.req.request_id
            call, row, ids = st.decided.get((k, rid), (-1, 0, None))
            if call < 0 or call >= len(refs):
                decision += 1
                unserved += int(not rs.rejected and not _served(rs, a, b))
                continue
            inputs, ok = refs[call]
            if not ok[row].any():          # nothing feasible: rejected
                decision += int(not rs.rejected)
                continue
            if rs.rejected:
                decision += 1
                continue
            decision += int(rs.decode_instance not in set(ids[ok[row]].tolist()))
            unserved += int(not _served(rs, a, b))
            inst, sent = st.dispatched.get((k, rid), (-1, -1.0))
            j = np.flatnonzero(ids == rs.decode_instance)
            if inst != rs.decode_instance or j.size != 1:
                nbytes += 1
                continue
            l_r = float(rs.req.input_len)
            hit = min(float(inputs["hit"][row, j[0]]), l_r) / max(l_r, 1.0)
            want = ref.fabric_bytes(config["kv"], l_r, hit)
            nbytes += int(abs(sent - want) >= 1.0)
    return {"decision_mismatch": decision, "bytes_mismatch": nbytes,
            "unserved": unserved}


def _served(rs, iter_a: float, iter_b: float) -> bool:
    """Finished with all its tokens, its timestamps in order, and no faster
    than its tokens allow: one iteration each, of at least a + b (its own
    slot in the batch)."""
    n = rs.req.output_len
    return (rs.finish >= 0 and rs.tokens_out == n and _in_order(rs)
            and rs.finish - rs.admit_time >= n * (iter_a + iter_b) * (1 - 1e-9))


def waterfill_err(ref, st: State, dtype=None) -> float:
    """Largest relative gap between a flow's rate after a water-filling
    pass and the reference's max-min rate.  With ``dtype`` the reference
    computed in that precision stands in the program's place."""
    err = 0.0
    for k, paths, rates in st.fills:
        want = ref.waterfill(paths, st.caps[k], np.float64)
        if dtype is not None:
            rates = ref.waterfill(paths, st.caps[k], dtype).astype(np.float64)
        gap = np.abs(rates - want) / np.maximum(np.abs(want), 1.0)
        err = max(err, float(gap.max()))
    return err


def control(run: harness.Run) -> dict:
    """The references one precision step down in the program's place, on
    the window's own calls: the score in bfloat16 where the kernel scores
    in float32, water-filling in float32 where FlowPlane fills in float64."""
    import ml_dtypes

    ref = reference(run.config)
    st = run.notes["state"]
    mismatch, err, _, _ = compare(ref, st.calls,
                                  scorer=lambda x: ref.score(x, ml_dtypes.bfloat16))
    return {"winner_mismatch": float(mismatch), "cost_rel_err": float(err),
            "rate_rel_err": waterfill_err(ref, st, np.float32)}


def check(run: harness.Run) -> None:
    ref = reference(run.config)
    st = run.notes["state"]
    mismatch, err, rows, refs = compare(ref, st.calls)
    per_req = check_requests(ref, run.config, st, refs)
    lim = run.config["limits"]
    run.counters["rows_checked"] = rows
    run.counters["waterfills_checked"] = len(st.fills)
    # A window in which the kernel scored nothing has checked nothing.
    run.checks["rows_unscored"] = (float(rows == 0), 0.0)
    run.checks["winner_mismatch"] = (float(mismatch), lim["winner_mismatch"])
    run.checks["cost_rel_err"] = (float(err), lim["cost_rel_err"])
    for name, v in per_req.items():
        run.checks[name] = (float(v), lim[name])
    run.checks["rate_rel_err"] = (waterfill_err(ref, st), lim["rate_rel_err"])
