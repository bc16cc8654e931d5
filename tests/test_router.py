"""Cluster router: placement, cluster-wide quotas, graceful drain,
engine loss — plus the wire transport driven through the SAME queue
trace driver that pins the loopback TransferQueue (tests/test_disagg.py)
and the two-process TCP smoke (the CI drain scenario).

The hypothesis property suite (ISSUE 7's list) runs on a lightweight
fake pair so thousands of random schedules fit a CI budget; every
invariant also runs on seeded traces against the real engines below, so
the machinery is covered without hypothesis.
"""
import os
import random
import subprocess
import sys
import time
from collections import deque
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, MemoryPlan, RunConfig
from repro.configs.base import MeshPlan, ShapeConfig
from repro.models.model import build_model
from repro.serve.disagg import build_disagg
from repro.serve.engine import Request
from repro.serve.quota import QuotaManager, TenantQuota
from repro.serve.router import (ACTIVE, DETACHED, DRAINING, EngineView,
                                LeastLoaded, PrefixAffinity, Router,
                                RoundRobin, build_placement, build_router,
                                registered_placements, replay_trace,
                                synth_prompt)
from repro.serve.session import Session, SessionState
from repro.serve.transport import (WireReceiver, WireSender, build_wire_pair,
                                   memory_pair)

from test_disagg import run_transfer_queue_trace

CFG = ARCHS["smollm-135m"].reduced()


@pytest.fixture(scope="module")
def model_and_params():
    run = RunConfig(model=CFG, shape=ShapeConfig("t", 64, 2, "decode"),
                    mesh=MeshPlan((1,), ("data",)),
                    memory=MemoryPlan(policy="none"))
    m = build_model(run)
    return m, m.init(jax.random.PRNGKey(0))


def _prompts(n, base=4):
    return [((np.arange(base + i, dtype=np.int32) * (i + 2) + 1)
             % CFG.vocab_size) for i in range(n)]


# ---------------------------------------------------------------------------
# placement policies (pure)
def _views(*loads, window=8):
    return [EngineView(i, load, window - load)
            for i, load in enumerate(loads)]


def _sess(uid, prompt=(1, 2, 3)):
    return Session(request=Request(uid=uid, prompt=list(prompt)), seq=uid)


def test_registry():
    assert set(registered_placements()) >= {
        "least_loaded", "prefix_affinity", "round_robin"}
    assert isinstance(build_placement("round_robin"), RoundRobin)
    with pytest.raises(KeyError, match="unknown placement"):
        build_placement("darts")


def test_least_loaded_breaks_ties_low_index():
    pol = LeastLoaded()
    assert pol.choose(_views(3, 1, 1), _sess(0)) == 1
    assert pol.choose(_views(0, 0, 0), _sess(0)) == 0


def test_round_robin_rotates():
    pol = RoundRobin()
    got = [pol.choose(_views(0, 0, 0), _sess(i)) for i in range(6)]
    assert got == [0, 1, 2, 0, 1, 2]


def test_round_robin_survives_drain_mid_rotation():
    """Bugfix: the positional ``turn % len(views)`` cursor shifted when an
    engine drained mid-rotation — the rotation must continue from the
    last-placed engine *identity* over the survivors."""
    pol = RoundRobin()
    assert pol.choose(_views(0, 0, 0), _sess(0)) == 0
    assert pol.choose(_views(0, 0, 0), _sess(1)) == 1
    # engine 1 drains: views shrink to {0, 2}; a positional cursor
    # (turn=2) would pick views[0] == engine 0 — double-placing on 0
    # while engine 2 starves
    survivors = [v for v in _views(0, 0, 0) if v.index != 1]
    assert pol.choose(survivors, _sess(2)) == 2
    assert pol.choose(survivors, _sess(3)) == 0
    assert pol.choose(survivors, _sess(4)) == 2
    # engine 1 comes back: it rejoins the rotation in index order
    assert pol.choose(_views(0, 0, 0), _sess(5)) == 0
    assert pol.choose(_views(0, 0, 0), _sess(6)) == 1
    assert pol.choose(_views(0, 0, 0), _sess(7)) == 2


def test_prefix_affinity_is_sticky_and_minimally_disruptive():
    pol = PrefixAffinity(prefix_len=4)
    same = [_sess(i, prompt=[7, 7, 7, 7, i]) for i in range(10)]
    other = [_sess(100 + i, prompt=[9, 9, 9, 9, i]) for i in range(10)]
    views3 = _views(0, 0, 0)
    a = {pol.choose(views3, s) for s in same}
    b = {pol.choose(views3, s) for s in other}
    assert len(a) == 1 and len(b) == 1          # shared prefix -> one home
    # rendezvous property: removing an unrelated engine never moves a
    # prefix whose home survives
    home = a.pop()
    survivors = [v for v in views3 if v.index != (home + 1) % 3]
    assert {pol.choose(survivors, s) for s in same} == {home}


def test_prefix_affinity_spills_when_home_full():
    pol = PrefixAffinity(prefix_len=4)
    s = _sess(0, prompt=[7, 7, 7, 7])
    views = _views(0, 0, 0)
    home = pol.choose(views, s)
    full = [EngineView(v.index, 8, 0) if v.index == home else v
            for v in views]
    assert pol.choose(full, s) != home


# ---------------------------------------------------------------------------
# a lightweight pair: real Sessions, fake compute (one token per step)
class _Sched:
    def __init__(self):
        self.q = deque()

    def submit(self, s):
        self.q.append(s)

    def waiting(self):
        return tuple(self.q)

    def next_ready(self):
        return self.q.popleft() if self.q else None


class FakePair:
    """Duck-types the pair surface Router drives, with instant prefill
    and one decoded token per step — placement/drain/loss logic runs
    thousands of random schedules in milliseconds."""

    def __init__(self, slots=2, quota=None):
        self.prefill = SimpleNamespace(scheduler=_Sched(),
                                       cache=SimpleNamespace(
                                           running=lambda: []),
                                       quota=quota, sessions=[], batch=1)
        self.decode = SimpleNamespace(scheduler=_Sched(),
                                      cache=SimpleNamespace(
                                          running=lambda: list(self._res)),
                                      sessions=[], batch=slots)
        self.transfer = SimpleNamespace(depth=lambda: 0)
        self.slots = slots
        self._res = []

    def submit(self, req=None, on_token=None, session=None):
        sess = session
        self.prefill.sessions.append(sess)
        self.prefill.scheduler.submit(sess)
        return sess

    def step(self):
        self._res = [s for s in self._res if not s.done]
        while len(self._res) < self.slots:
            s = self.prefill.scheduler.next_ready()
            if s is None:
                break
            if s.done:
                continue
            s.state = SessionState.RUNNING
            self.decode.sessions.append(s)
            self._res.append(s)
        for s in list(self._res):
            s.length += 1
            s.emit(int(s.length))
            if len(s.tokens) >= s.request.max_new_tokens:
                s.finish("length")
                self._res.remove(s)
        return len(self._res)

    def has_work(self):
        return bool(self.prefill.scheduler.q) or bool(self._res)

    def traffic_report(self):
        return {}


def _fake_router(n=3, slots=2, placement="least_loaded", **kw):
    return Router([FakePair(slots=slots) for _ in range(n)],
                  placement=placement, **kw)


class SpyPolicy:
    """Wraps a policy; records every choice and asserts the router only
    ever showed it ACTIVE engines."""

    def __init__(self, inner, router_ref):
        self.inner = inner
        self.router_ref = router_ref
        self.choices = []
        self.name = f"spy({inner.name})"

    def choose(self, views, sess):
        router = self.router_ref()
        for v in views:
            assert router.engines[v.index].state == ACTIVE, \
                f"policy offered a {router.engines[v.index].state} engine"
        idx = self.inner.choose(views, sess)
        self.choices.append((sess.uid, idx))
        return idx

    def describe(self):
        return self.name


def _run_ops(ops, n_engines=3, slots=2, policy="least_loaded"):
    """Drive a router through a random submit/drain/fail/step schedule;
    returns the router.  Core invariants assert inline."""
    router = _fake_router(n=n_engines, slots=slots, placement=policy)
    router.policy = SpyPolicy(router.policy, lambda: router)
    uid = 0
    for op, arg in ops:
        if op == "submit":
            router.submit(Request(uid=uid, prompt=[1 + arg % 5] * 4,
                                  max_new_tokens=1 + arg % 4))
            uid += 1
        elif op == "drain":
            live = [e for e in router.engines if e.state == ACTIVE]
            if len(live) > 1:           # keep one engine to finish on
                router.drain(live[arg % len(live)].index)
        elif op == "fail":
            live = [e for e in router.engines if e.state == ACTIVE]
            if len(live) > 1:
                router.fail(live[arg % len(live)].index)
        router.step()
    router.run(max_steps=5000)
    return router


def _assert_invariants(router):
    dropped = [s for s in router.sessions.values() if not s.done]
    assert not dropped, f"dropped sessions: {[s.uid for s in dropped]}"
    for eng in router.engines:
        if eng.state == DRAINING:
            assert not eng.pair.has_work()
    # every drained engine stopped receiving placements after its drain
    assert not router.queue


def test_router_random_schedules_seeded():
    rng = random.Random(99)
    for _ in range(25):
        ops = [(rng.choice(["submit", "submit", "submit", "drain",
                            "fail"]), rng.randrange(32))
               for _ in range(rng.randrange(5, 40))]
        pol = rng.choice(["least_loaded", "round_robin", "prefix_affinity"])
        _assert_invariants(_run_ops(ops, policy=pol))


# ---------------------------------------------------------------------------
# the wire through the loopback queue's trace driver (seeded twin of the
# hypothesis property in tests/test_serve_properties.py)
def test_wire_queue_random_traces_seeded():
    """The byte-serialized wire driven through the SAME trace driver
    that pins the loopback TransferQueue: FIFO pages, exactly-once
    delivery, no starvation, no leaked payloads — now across frames."""
    rng = random.Random(2718)
    for _ in range(15):
        ops = [(rng.choice(["publish", "adopt", "adopt", "cancel"]),
                rng.randrange(16)) for _ in range(60)]
        q, adopted = run_transfer_queue_trace(
            ops, max_depth=rng.choice([None, 2, 4]),
            make_queue=_make_wire_queue)
        assert q.depth() == 0


class _WireLoop:
    """Sender+receiver glued into the TransferQueue surface, every
    handoff crossing a real (in-memory, fragmented) byte channel."""

    def __init__(self, max_depth):
        class _NullRuntime:
            def meter_transfer(self, *a, **k):
                pass

            def traffic_report(self):
                return {}

        tx, rx = memory_pair(max_chunk=97)
        self.sender = WireSender(tx, _NullRuntime(), max_depth=max_depth,
                                 backoff=0.0, sleep=lambda _: None)
        self.receiver = WireReceiver(rx, _NullRuntime(), backoff=0.0,
                                     sleep=lambda _: None)

    # prefill side
    def has_room(self, pending=0):
        return self.sender.has_room(pending)

    def publish(self, handoff, pages, slot_one=None):
        self.sender.publish(handoff, pages, slot_one)

    # decode side
    def next_ready(self):
        return self.receiver.next_ready()

    def requeue(self, h):
        self.receiver.requeue(h)

    def fetch_pages(self, h):
        return self.receiver.fetch_pages(h)

    def fetch_slot_leaves(self, h):
        return self.receiver.fetch_slot_leaves(h)

    def discard(self, h):
        self.receiver.discard(h)

    def parked_uids(self):
        self.receiver.pump()
        return self.receiver.parked_uids()

    def depth(self):
        return self.receiver.depth()

    @property
    def _parked(self):
        self.receiver.pump()
        return self.receiver._parked

    @property
    def adopted_pages(self):
        return self.receiver.adopted_pages

    def sweep_cancelled(self):
        swept = self.receiver.sweep_cancelled()
        return swept + self.sender.sweep_cancelled()


def _make_wire_queue(max_depth):
    loop = _WireLoop(max_depth)

    def leak_check():
        loop.receiver.pump()
        assert not loop.receiver._parked, "handoffs parked at drain"
        loop.sender.pump()          # drain the last ACKs off the channel
        assert not loop.sender._inflight, \
            "published handoffs never ACKed — sender credits leaked"
    return loop, leak_check


# ---------------------------------------------------------------------------
# real engines: cluster quota bound, drain, loss, wire engine
def test_cluster_quota_shared_across_engines(model_and_params):
    """Satellite property (real-engine twin): one tenant's pages are
    bounded by its quota ACROSS engines, because every engine charges
    the same ledger; and the ledger never exceeds the summed quotas."""
    m, params = model_and_params
    quota = QuotaManager(default_quota=TenantQuota(max_pages=4))
    router = build_router(m, params, engines=2, quota=quota,
                          batch=2, max_len=64, page_size=16,
                          transfer="host", spill="host")
    shared = router.engines[0].pair.prefill.quota
    assert shared is router.engines[1].pair.prefill.quota  # ONE ledger
    cap = 4 * 2  # two tenants in play
    seen = []
    ss = [router.submit(Request(uid=i, prompt=p, max_new_tokens=4,
                                tenant=f"t{i % 2}"))
          for i, p in enumerate(_prompts(8, base=18))]
    while router.has_work():
        router.step()
        pages = sum(u["pages"] for u in shared.usage().values())
        seen.append(pages)
        assert pages <= cap, f"cluster admitted {pages} > {cap} pages"
    assert max(seen) > 0
    # 2-page sessions under a 4-page cap: rejected sessions only when
    # genuinely over quota, and everything else finished
    for s in ss:
        assert s.done


def test_drain_zero_dropped_real_engines(model_and_params):
    m, params = model_and_params
    router = build_router(m, params, engines=2, batch=2, max_len=64,
                          page_size=16, transfer="host", spill="host")
    ss = [router.submit(Request(uid=i, prompt=p, max_new_tokens=4))
          for i, p in enumerate(_prompts(8))]
    fired = []

    def hook(r):
        if r.now == 2 and not fired:
            fired.append(True)
            r.drain(0)

    done = router.run(on_step=hook)
    assert len(done) == 8 and all(s.done for s in ss)
    assert router.engines[0].state == DETACHED
    assert all(s.finish_reason in ("eos", "length") for s in ss)


def test_engine_loss_requeues_and_streams_survive(model_and_params):
    """Losing an engine mid-run re-prefills its sessions elsewhere; at
    temperature 0 the final streams match an undisturbed router run."""
    m, params = model_and_params
    prompts = _prompts(6)

    def run(lose):
        router = build_router(m, params, engines=2, batch=2, max_len=64,
                              page_size=16, transfer="host", spill="host")
        ss = [router.submit(Request(uid=i, prompt=p, max_new_tokens=5))
              for i, p in enumerate(prompts)]
        fired = []

        def hook(r):
            if lose and r.now == 2 and not fired:
                fired.append(True)
                r.fail(1)

        router.run(on_step=hook)
        return router, [s.result() for s in ss]

    _, want = run(lose=False)
    router, got = run(lose=True)
    assert got == want
    assert router.engines[1].state == "lost"


def test_router_with_wire_engine(model_and_params):
    """A mixed cluster: engine 0 speaks the byte-framed wire, engine 1
    the loopback — streams identical to an all-loopback cluster."""
    m, params = model_and_params
    prompts = _prompts(6)

    def run(wire):
        kw = dict(batch=2, max_len=64, page_size=16, spill="host")
        if wire:
            pairs = [build_wire_pair(m, params, seed=0, **kw),
                     build_disagg(m, params, transfer="host", seed=2, **kw)]
            router = Router(pairs, placement="round_robin")
        else:
            router = build_router(m, params, engines=2,
                                  placement="round_robin",
                                  transfer="host", **kw)
        ss = [router.submit(Request(uid=i, prompt=p, max_new_tokens=4))
              for i, p in enumerate(prompts)]
        router.run()
        return [s.result() for s in ss]

    assert run(wire=True) == run(wire=False)


def test_replay_trace_through_real_router(model_and_params):
    from repro.sim.workloads import TrafficSpec, generate_traffic

    m, params = model_and_params
    trace = generate_traffic(TrafficSpec(
        sessions=10, horizon_s=100.0, prompt_mean=8.0, prompt_max=20,
        decode_mean=4.0, decode_max=8, prefix_len=6, seed=5))
    router = build_router(m, params, engines=2, batch=2, max_len=64,
                          page_size=16, transfer="host", spill="host",
                          placement="prefix_affinity")
    done = replay_trace(router, trace, CFG.vocab_size,
                        arrivals_per_step=2.0)
    assert len(done) == 10
    assert all(len(r.out_tokens) > 0 for r in done)
    # shared-prefix sessions really share their prefix tokens
    by_prefix = {}
    for s in trace:
        if s.prefix_id is not None:
            by_prefix.setdefault(s.prefix_id, []).append(
                tuple(synth_prompt(s, CFG.vocab_size)[:s.prefix_len]))
    for pid, heads in by_prefix.items():
        assert len(set(heads)) == 1


# ---------------------------------------------------------------------------
# the two-process CI smoke: prefill router and decode worker in separate
# processes over localhost TCP; drain the wire engine mid-run; all
# sessions must finish (zero dropped — the launcher asserts it too)
def test_two_process_router_drain_over_tcp(tmp_path):
    """The CI drain scenario: router with a TCP wire engine 0 in one
    process, the decode worker in another; drain the wire engine
    mid-run; both exit clean with zero dropped sessions.

    Children log to FILES, not pipes — an undrained pipe buffer would
    deadlock the pair once either side logs more than 64KB."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    # importing repro.launch.dryrun (test_dryrun_roofline) leaks a
    # 512-host-device XLA_FLAGS into this process's environ; the smoke
    # children must see a clean single-device platform
    env.pop("XLA_FLAGS", None)
    # the launcher's persistent compile cache stays off in tests
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    args = [sys.executable, "-m", "repro.launch.serve", "--arch",
            "smollm-135m", "--smoke", "--batch", "2", "--max-len", "64",
            "--page-size", "16"]
    rlog, wlog = tmp_path / "router.log", tmp_path / "worker.log"
    with open(rlog, "w") as rf, open(wlog, "w") as wf:
        router = subprocess.Popen(
            args + ["--router", "--engines", "2", "--listen", "0",
                    "--requests", "6", "--new-tokens", "4",
                    "--drain-after", "4", "--drain-engine", "0"],
            stdout=rf, stderr=subprocess.STDOUT, env=env)
        worker = None
        try:
            port = None
            deadline = time.time() + 240
            while time.time() < deadline and port is None:
                for line in rlog.read_text().splitlines():
                    if "listening on" in line:
                        port = int(line.rsplit(" ", 1)[-1])
                        break
                if port is None:
                    assert router.poll() is None, \
                        "router died early:\n" + rlog.read_text()
                    time.sleep(0.5)
            assert port, "router never opened its port:\n" + rlog.read_text()
            worker = subprocess.Popen(
                args + ["--role", "decode", "--connect",
                        f"127.0.0.1:{port}"],
                stdout=wf, stderr=subprocess.STDOUT, env=env)
            assert router.wait(timeout=240) == 0, rlog.read_text()
            assert worker.wait(timeout=240) == 0, wlog.read_text()
        finally:
            for proc in (router, worker):
                if proc is not None and proc.poll() is None:
                    proc.kill()
    log, wout = rlog.read_text(), wlog.read_text()
    assert "0 dropped" in log, log
    assert "drained engine 0" in log, log
    assert "decode worker done" in wout, wout
