"""Disaggregated serving cluster: real models + NetKV routing + timed fabric.

The executable end-to-end driver (examples/disaggregated_cluster.py):
prefill engines and decode engines hold REAL weights; the KV cache moves
through kv_pack/kv_unpack; the flow-level fat-tree provides transfer
*timing*; NetKV (or any ladder policy) picks the decode instance per
request.  Generated tokens are exact (tests compare against a monolithic
forward), while TTFT statistics come from the simulated clock.

Engines are placed on the devices the cluster is given (all local devices
by default): prefill engines on the first, decode engines round-robin on
the rest, or all on the one device when there is only one.  The packed
KV buffers are put on the decode engine's device before they are unpacked,
so on several chips the transfer crosses the chip interconnect.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np

from repro.cluster.network import BackgroundTraffic, FlowNetwork
from repro.cluster.topology import FatTree, make_instances
from repro.core.cost import B_TOK, IterTimeModel, PrefillTimeModel
from repro.core.oracle import NetworkCostOracle, SelfContentionTracker
from repro.core.schedulers import RequestInfo, make_scheduler
from repro.core.view import ClusterView
from repro.models.model import ModelConfig, init_params
from .engine import DecodeEngine, PrefillEngine
from .transfer import pack_transfer, unpack_transfer


@dataclasses.dataclass
class ServeRequest:
    request_id: int
    prompt: np.ndarray
    max_new: int
    arrival: float = 0.0


@dataclasses.dataclass
class ServeResult:
    request_id: int
    tokens: list[int]
    prefill_instance: int
    decode_instance: int
    tier: int
    transfer_bytes: int
    hit_pages: int        # prompt pages the decode engine already held
    ttft: float           # simulated-clock TTFT
    transfer_time: float


class DisaggregatedCluster:
    """Small-cluster executable disaggregated serving with NetKV routing."""

    def __init__(self, cfg: ModelConfig, *, scheduler: str = "netkv-full",
                 n_prefill: int = 2, n_decode: int = 4, n_slots: int = 4,
                 cache_len: int = 256, seed: int = 0,
                 tree: FatTree | None = None, background: float = 0.2,
                 devices: Sequence | None = None):
        self.cfg = cfg
        self.cache_len = cache_len
        params = init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=cfg.compute_dtype)
        devices = list(devices or jax.local_devices())
        pre_devs, dec_devs = devices[:1], (devices[1:] or devices)
        placed: dict = {}

        def params_on(dev):
            if dev not in placed:
                placed[dev] = jax.device_put(params, dev)
            return placed[dev]

        self.tree = tree or FatTree()
        self.net = FlowNetwork(self.tree, BackgroundTraffic(background), seed=seed)
        pre_meta, dec_meta = make_instances(self.tree, tp=4,
                                            n_prefill=max(n_prefill, 1))
        pre_meta = pre_meta[:n_prefill]
        dec_meta = dec_meta[:n_decode]
        self.prefill = [
            PrefillEngine(m.instance_id, cfg,
                          params_on(pre_devs[i % len(pre_devs)]), cache_len)
            for i, m in enumerate(pre_meta)
        ]
        self.decode = []
        for i, m in enumerate(dec_meta):
            dev = dec_devs[i % len(dec_devs)]
            self.decode.append(DecodeEngine(
                m.instance_id, cfg, params_on(dev), n_slots=n_slots,
                cache_len=cache_len, device=dev))
        self._server_of = {m.instance_id: m.server for m in (*pre_meta, *dec_meta)}
        self.iter_model = IterTimeModel(a=0.0124, b=1.6e-5)
        self.oracle = NetworkCostOracle(
            tier_of=lambda a, b: self.tree.tier(self._server_of[a], self._server_of[b]),
            topology=self.tree,
            telemetry_fn=lambda now: self.net.tier_congestion(now),
        )
        self.inflight = SelfContentionTracker()
        self.sched = make_scheduler(scheduler, self.iter_model, beta_max=n_slots,
                                    m_min=0.0)
        self.clock = 0.0

    # ------------------------------------------------------------------ serve
    def serve(self, requests: Sequence[ServeRequest]) -> list[ServeResult]:
        results = []
        for req in sorted(requests, key=lambda r: r.arrival):
            self.clock = max(self.clock, req.arrival)
            # 1. prefill on the least-loaded prefill engine (round robin here).
            pe = self.prefill[req.request_id % len(self.prefill)]
            pre = pe.run(req.request_id, req.prompt)
            prefill_time = 5e-5 * len(req.prompt) + 0.015
            t_prefill_done = self.clock + prefill_time

            # 2. decode-instance selection (Algorithm 1 over columnar state).
            view = self.oracle.view(t_prefill_done)
            cv = ClusterView(tier_fn=view.tier_of, capacity=len(self.decode))
            for d in self.decode:
                cv.add_instance(
                    d.instance_id,
                    free_memory=float(len(d.free_slots())) * 1e12,  # slot-gated
                    queued=0,
                    batch=d.beta,
                    hit_tokens=float(d.hit_pages(req.prompt) * B_TOK),
                    healthy=len(d.free_slots()) > 0,
                )
            info = RequestInfo(req.request_id, len(req.prompt), float(pre.kv_bytes),
                               state_bytes=float(pre.state_bytes))
            decision = self.sched.select(info, pe.instance_id, cv, view, self.inflight)
            assert decision is not None, "no feasible decode instance"
            de = next(d for d in self.decode if d.instance_id == decision.instance_id)

            # 3. pack + timed transfer + unpack (real tensors move).
            hit_pages = de.hit_pages(req.prompt)
            buffers, nbytes = pack_transfer(pre.cache, hit_pages)
            done = []
            self.net.start_transfer(
                self._server_of[pe.instance_id], self._server_of[de.instance_id],
                float(max(nbytes, 1)), t_prefill_done,
                on_complete=lambda tr, t: done.append(t), n_flows=4,
            )
            t = t_prefill_done
            while not done:
                nxt = self.net.next_completion_time(t)
                if nxt is None:
                    break
                t = nxt
                self.net.advance(t)
            t_transfer_done = done[0] if done else t_prefill_done
            moved = {name: (jax.device_put(buf, de.device), table)
                     for name, (buf, table) in buffers.items()}
            cache = de.fill_prefix(
                unpack_transfer(moved, pre.cache, device=de.device),
                req.prompt, hit_pages)
            cache["pos"] = pre.cache["pos"]
            pre_landed = dataclasses.replace(pre, cache=cache)

            # 4. decode until done.
            de.admit(req.request_id, pre_landed, req.max_new)
            if self.sched.uses_self_contention:
                self.inflight.decr(pe.instance_id, decision.tier)
            de.remember(req.prompt, cache)
            toks = [pre.first_token]
            while any(s.active and s.request_id == req.request_id for s in de.slots):
                emitted = de.step()
                toks.extend(t for rid, t in emitted if rid == req.request_id)
            t_first = t_transfer_done + self.iter_model(de.beta + 1)
            results.append(ServeResult(
                request_id=req.request_id,
                tokens=toks,
                prefill_instance=pe.instance_id,
                decode_instance=de.instance_id,
                tier=decision.tier,
                transfer_bytes=nbytes,
                hit_pages=hit_pages,
                ttft=t_first - req.arrival + prefill_time,
                transfer_time=t_transfer_done - t_prefill_done,
            ))
            self.clock = t_transfer_done
        return results
