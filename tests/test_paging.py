"""Paged KV cache: PageTable bookkeeping, paged==unpaged decode streams,
per-page spill metering, tenant quotas, SRPT/deadline scheduling, and the
derive_cache_shape page/0-batch fixes.

The trace drivers (`run_table_trace` / `run_scheduler_trace`) are shared
with the hypothesis property suite (tests/test_serve_properties.py); here
they run on seeded-random traces so the machinery is exercised even when
hypothesis is not installed.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, MemoryPlan, MeshPlan, RunConfig
from repro.configs.base import ShapeConfig
from repro.models import transformer as tfm
from repro.models.model import build_model
from repro.serve.engine import Engine, Request
from repro.serve.paging import PageError, PageTable, SharedPayload
from repro.serve.quota import (QuotaManager, TenantQuota, parse_quota_spec)
from repro.serve.scheduler import FairScheduler, build_scheduler
from repro.serve.session import Session

CFG = ARCHS["smollm-135m"].reduced()
PLAN1 = MeshPlan((1,), ("data",))


@pytest.fixture(scope="module")
def model_and_params():
    run = RunConfig(model=CFG, shape=ShapeConfig("t", 64, 2, "decode"),
                    mesh=PLAN1, memory=MemoryPlan(policy="none"))
    m = build_model(run)
    return m, m.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# PageTable unit behaviour
def test_page_table_alloc_free_cycle():
    t = PageTable(num_pages=4, page_size=8)
    pids = [t.alloc(1) for _ in range(3)]
    assert len(set(pids)) == 3 and t.num_free() == 1
    assert t.resident_pids(1) == pids
    t.check()
    assert t.free_session(1) == []          # nothing spilled -> no payloads
    assert t.num_free() == 4
    t.check()
    assert t.free_session(1) == []          # double free is a no-op


def test_page_table_exhaustion_and_lazy_evict():
    t = PageTable(num_pages=2, page_size=4)
    t.alloc(1), t.alloc(2)
    with pytest.raises(PageError):          # both pages hot
        t.alloc(3)
    log = []
    t.mark_cold(1)                          # owner 1 paused
    pid = t.alloc(3, evict=lambda sid, pos, p: log.append((sid, pos, p))
                  or f"payload{p}")
    assert log == [(1, 0, pid)]             # LRU cold page was reclaimed
    assert t.evictions == 1
    assert t.resident_pids(1) == [None]     # spilled marker
    assert t.spilled_positions(1) == [0]
    t.check()
    # resume owner 1: its page must come back via set_resident (refetch)
    t.mark_hot(1)
    assert t.readmits_free == 0             # the page was gone
    with pytest.raises(PageError):          # everything hot again
        t.set_resident(1, 0)
    t.free_session(3)
    new_pid = t.set_resident(1, 0)
    assert t.refetches == 1 and t.resident_pids(1) == [new_pid]
    t.check()


def test_page_table_copy_free_readmit():
    t = PageTable(num_pages=4, page_size=4)
    t.ensure(7, rows=9)                     # 3 pages
    t.mark_cold(7)
    assert t.num_cold() == 3
    assert t.mark_hot(7) == 3               # nothing was evicted
    assert t.readmits_free == 0             # counted only on commit...
    assert t.note_resumed(7) == 3           # ...of a successful resume
    assert t.readmits_free == 3 and t.evictions == 0
    t.check()


def test_page_table_ensure_is_idempotent():
    t = PageTable(num_pages=8, page_size=4)
    assert len(t.ensure(1, rows=10)) == 3
    assert t.ensure(1, rows=10) == []
    assert t.ensure(1, rows=12) == []       # still 3 pages
    assert len(t.ensure(1, rows=13)) == 1
    assert t.pages_for(1) == 1 and t.pages_for(4) == 1 and t.pages_for(5) == 2


# ---------------------------------------------------------------------------
# trace drivers (shared with tests/test_serve_properties.py)
def run_table_trace(ops, num_pages=6, page_size=4):
    """Drive a PageTable through (op, sid) steps with a fake spill ledger.

    Model: sessions own rows; 'pause' marks cold, 'resume' re-homes
    spilled positions, 'free' retires, 'share' binds another session's
    resident page read-only (prefix-cache hit) and 'fork' models the
    copy-on-write divergence: a share immediately followed by a private
    allocation for the diverging tail.  After every step the table's
    internal invariants are checked and the spill ledger is cross-checked:
    a page fetched on resume must return exactly the payload its eviction
    stored (for a shared page: the ONE payload every holder references),
    and metered transfers must equal the table's counters.
    """
    t = PageTable(num_pages=num_pages, page_size=page_size)
    ledger = set()                          # outstanding spill payloads
    stashes, fetches = [], []

    def evict_cb(sid, pos, pid):
        payload = ("page", sid, pos, pid, len(stashes))
        ledger.add(payload)
        stashes.append(payload)
        return payload

    def share_donor(sid):
        """A resident pid of some other session that sid doesn't hold."""
        for other in t.sessions():
            if other == sid:
                continue
            for pid in t.resident_pids(other):
                if pid is not None and pid not in t.resident_pids(sid):
                    return pid
        return None

    state = {}                              # sid -> "live" | "paused"
    for op, sid in ops:
        if op == "grow" and state.get(sid) == "live":
            rows = (t.holds(sid) * page_size) + 1
            try:
                t.ensure(sid, rows, evict_cb)
            except PageError:
                pass                        # all hot: legal, nothing changed
        elif op in ("share", "fork") and state.get(sid) != "paused":
            pid = share_donor(sid)
            if pid is not None:
                t.share(sid, pid)
                state[sid] = "live"
                if op == "fork":            # diverging tail: private page
                    try:
                        t.alloc(sid, evict_cb)
                    except PageError:
                        pass
        elif op == "pause" and state.get(sid) == "live":
            t.mark_cold(sid)
            state[sid] = "paused"
        elif op == "resume" and state.get(sid) == "paused":
            t.mark_hot(sid)
            try:
                while True:
                    # re-computed each round: refetching a shared page
                    # re-homes OTHER holders' positions in the same call
                    spilled = t.spilled_positions(sid)
                    if not spilled:
                        break
                    pos = spilled[0]
                    parked = t.entries(sid)[pos].payload
                    inner = parked.payload \
                        if isinstance(parked, SharedPayload) else parked
                    assert inner in ledger, "payload mixed up"
                    t.set_resident(sid, pos, evict_cb)
                    ledger.discard(inner)
                    fetches.append(inner)
                t.note_resumed(sid)
                state[sid] = "live"
            except PageError:
                t.mark_cold(sid)            # stay paused (engine retries)
                state[sid] = "paused"
        elif op == "free" and sid in state:
            for payload in t.free_session(sid):
                assert payload in ledger, "orphaned payload unknown"
                ledger.discard(payload)
            state.pop(sid)
        elif op == "new" and sid not in state:
            try:
                t.ensure(sid, 1, evict_cb)
                state[sid] = "live"
            except PageError:
                pass
        t.check()
    assert t.evictions == len(stashes)
    assert t.refetches == len(fetches)
    # bytes invariant: every transfer moved exactly one page
    assert t.evictions * page_size == sum(page_size for _ in stashes)
    return t, state


def test_page_table_random_traces_seeded():
    rng = random.Random(1234)
    for _ in range(25):
        ops = [(rng.choice(["new", "grow", "pause", "resume", "free"]),
                rng.randrange(5)) for _ in range(120)]
        t, state = run_table_trace(ops)
        for sid in list(state):             # drain THE trace's table
            t.free_session(sid)
            t.check()
        assert t.num_free() == t.num_pages  # whole pool recovered


def test_page_table_random_shared_traces_seeded():
    """Same recovery invariant with prefix sharing in the op mix: shared
    holds, forks, shared evictions/refetches — still no leaked frames."""
    rng = random.Random(99)
    shared_seen = 0
    for _ in range(25):
        ops = [(rng.choice(["new", "grow", "pause", "resume", "free",
                            "share", "fork"]),
                rng.randrange(5)) for _ in range(120)]
        t, state = run_table_trace(ops)
        shared_seen += t.shared_binds
        for sid in list(state):
            t.free_session(sid)
            t.check()
        assert t.num_free() == t.num_pages
    assert shared_seen > 0                  # the mix actually shared


# ---------------------------------------------------------------------------
# prefix sharing: refcounted pages, COW bookkeeping
def test_share_refcounts_and_last_holder_frees():
    t = PageTable(num_pages=4, page_size=4)
    pid = t.alloc(1)
    assert t.refcount(pid) == 1 and t.num_shared() == 0
    assert t.share(2, pid) == 0             # bound at 2's position 0
    assert t.share(3, pid) == 0
    assert t.refcount(pid) == 3 and t.num_shared() == 1
    assert t.shared_binds == 2
    t.check()
    t.free_session(1)                       # two holders survive
    assert t.refcount(pid) == 2 and t.num_free() == 3
    t.free_session(2)
    assert t.refcount(pid) == 1 and t.num_shared() == 0
    t.free_session(3)                       # last holder out: frame returns
    assert t.num_free() == 4
    t.check()


def test_share_requires_resident_page():
    t = PageTable(num_pages=2, page_size=4)
    with pytest.raises(PageError):
        t.share(1, 0)                       # nobody owns page 0 yet
    pid = t.alloc(1)
    with pytest.raises(ValueError):
        t.share(1, pid)                     # self-share would alias
    t.share(2, pid)
    with pytest.raises(ValueError):
        t.share(2, pid)                     # double bind would alias
    t.check()


def test_shared_page_evictable_only_when_all_holders_pause():
    t = PageTable(num_pages=2, page_size=4)
    pid = t.alloc(1)
    t.share(2, pid)
    t.alloc(3)
    t.mark_cold(1)                          # holder 2 still hot
    assert t.num_cold() == 0
    with pytest.raises(PageError):
        t.alloc(4, evict=lambda *a: "p")    # nothing evictable
    t.mark_cold(2)
    assert t.num_cold() == 1                # now every holder is paused
    log = []
    t.alloc(4, evict=lambda sid, pos, vpid: log.append((sid, pos, vpid))
            or "spilled-bytes")
    assert len(log) == 1                    # ONE stash for both holders
    assert t.evictions == 1
    # both holders' entries reference the one SharedPayload
    p1, p2 = t.entries(1)[0].payload, t.entries(2)[0].payload
    assert isinstance(p1, SharedPayload) and p1 is p2
    assert p1.payload == "spilled-bytes"
    assert sorted(p1.holders) == [(1, 0), (2, 0)]
    t.check()


def test_shared_refetch_rehomes_every_holder():
    t = PageTable(num_pages=2, page_size=4)
    pid = t.alloc(1)
    t.share(2, pid)
    t.alloc(3)
    t.mark_cold(1), t.mark_cold(2)
    t.alloc(4, evict=lambda *a: "bytes")    # shared page spilled once
    t.free_session(3), t.free_session(4)
    t.mark_hot(1)
    new = t.set_resident(1, 0)              # ONE fetch...
    assert t.refetches == 1
    assert t.resident_pids(1) == [new]
    assert t.resident_pids(2) == [new]      # ...re-homed holder 2 too
    assert t.refcount(new) == 2
    # holder 2 is still paused; the frame is pinned by hot holder 1
    assert t.num_cold() == 0
    t.mark_hot(2)
    assert t.spilled_positions(2) == []     # nothing left to fetch
    t.check()
    # the shared spill payload was consumed: frees orphan nothing
    assert t.free_session(1) == [] and t.free_session(2) == []
    assert t.num_free() == t.num_pages


def test_shared_payload_discarded_only_by_last_holder():
    t = PageTable(num_pages=2, page_size=4)
    pid = t.alloc(1)
    t.share(2, pid)
    t.alloc(3)
    t.mark_cold(1), t.mark_cold(2)
    t.alloc(4, evict=lambda *a: "bytes")
    assert t.free_session(1) == []          # payload still referenced by 2
    assert t.free_session(2) == ["bytes"]   # last holder surrenders it
    t.check()


def test_set_resident_on_resident_position_raises():
    t = PageTable(num_pages=2, page_size=4)
    t.alloc(1)
    with pytest.raises(ValueError):
        t.set_resident(1, 0)


def test_free_session_double_free_guard_raises():
    t = PageTable(num_pages=2, page_size=4)
    pid = t.alloc(1)
    t._free.append(pid)                     # corrupt: frame freed underfoot
    with pytest.raises(ValueError):
        t.free_session(1)


def test_claim_alias_guard_raises_value_error():
    t = PageTable(num_pages=4, page_size=4)
    t.alloc(7)
    with pytest.raises(ValueError):         # not an assert: survives -O
        t.claim(7, 1)


def test_unset_resident_rolls_back_failed_fetch():
    """Bugfix: when the spill-tier fetch dies after set_resident handed
    out a frame, the rollback must return the frame and re-park the
    position over the SAME payload so a retry can still fetch it."""
    t = PageTable(num_pages=1, page_size=4)
    t.alloc(1)
    t.mark_cold(1)
    t.alloc(2, evict=lambda *a: "bytes")    # 1's page spilled
    t.free_session(2)
    t.mark_hot(1)
    pid = t.set_resident(1, 0)
    assert t.refetches == 1
    t.unset_resident(1, 0, "bytes")         # fetch failed: roll back
    assert t.refetches == 0                 # metering undone
    assert t.spilled_positions(1) == [0]
    assert t.entries(1)[0].payload == "bytes"
    t.check()
    assert t.set_resident(1, 0) is not None  # retry succeeds
    t.check()


def test_unset_resident_rejects_spilled_position():
    t = PageTable(num_pages=1, page_size=4)
    t.alloc(1)
    t.mark_cold(1)
    t.alloc(2, evict=lambda *a: "bytes")    # 1's only page spilled
    with pytest.raises(ValueError):         # nothing to roll back
        t.unset_resident(1, 0, "bytes")


def run_scheduler_trace(name, ops, slots=2, **kwargs):
    """Drive a scheduler through submit/admit/tick/pause/retire/cancel ops,
    asserting the policy invariants the ISSUE names:

    * no session is lost or double-scheduled,
    * FCFS pops fresh sessions in arrival order,
    * SRPT never runs a longer job while a shorter one waits,
    * EDF never idles while an unmet deadline waits and always picks the
      earliest deadline.
    """
    sched = build_scheduler(name, **kwargs)
    sessions, running, waiting = [], [], set()
    fresh_pops = []

    def submit(max_new, deadline):
        req = Request(uid=len(sessions), prompt=np.zeros(2, np.int32),
                      max_new_tokens=max_new, deadline=deadline)
        s = Session(request=req, seq=len(sessions))
        sessions.append(s)
        waiting.add(s.uid)
        sched.submit(s)
        return s

    for op, a, b in ops:
        if op == "submit":
            submit(a, b)
        elif op == "admit" and len(running) < slots:
            s = sched.next_ready()
            if s is None:
                assert not any(not sessions[u].done for u in waiting), \
                    f"{name} idles while work waits"
                continue
            assert s.uid in waiting, f"double-scheduled {s.uid}"
            assert not s.done, "scheduled a finished session"
            waiting.discard(s.uid)
            live = [sessions[u] for u in waiting if not sessions[u].done]
            if name == "srpt":
                assert all(s.remaining <= w.remaining for w in live), \
                    "SRPT ran a longer job while a shorter one waited"
            if name == "deadline":
                assert all(s.deadline <= w.deadline for w in live), \
                    "EDF skipped an earlier deadline"
            if name == "fcfs" and s.preemptions == 0:
                fresh_pops.append(s.seq)
            running.append(s)
        elif op == "tick":
            sched.on_step()
            for s in running:
                s.emit(0)
        elif op == "pause" and running:
            s = running.pop(a % len(running))
            s.preemptions += 1
            waiting.add(s.uid)
            sched.requeue(s)
        elif op == "retire" and running:
            s = running.pop(a % len(running))
            s.finish("length")
            sched.on_retire(s)
        elif op == "cancel" and sessions:
            s = sessions[a % len(sessions)]
            if not s.done:
                s.cancel()
                waiting.discard(s.uid)
    # drain: every surviving session comes out exactly once — none lost
    while True:
        s = sched.next_ready()
        if s is None:
            break
        assert s.uid in waiting, f"lost or duplicated session {s.uid}"
        waiting.discard(s.uid)
    assert not any(not sessions[u].done for u in waiting), \
        f"{name} lost sessions: {waiting}"
    if name == "fcfs":
        assert fresh_pops == sorted(fresh_pops), \
            "FCFS broke arrival order for fresh sessions"
    return sched, sessions


SCHED_NAMES = ("fcfs", "priority", "fair", "srpt", "deadline")


@pytest.mark.parametrize("name", SCHED_NAMES)
def test_scheduler_random_traces_seeded(name):
    rng = random.Random(99)
    for _ in range(20):
        ops = []
        for _ in range(80):
            kind = rng.choice(["submit", "admit", "tick", "pause",
                               "retire", "cancel"])
            ops.append((kind, rng.randrange(8),
                        rng.choice([None, rng.randrange(1, 30)])))
        run_scheduler_trace(name, ops)


def test_deadline_miss_accounting_in_trace():
    ops = ([("submit", 3, 1)] +                   # deadline 1: must miss
           [("admit", 0, None)] +
           [("tick", 0, None)] * 5 +
           [("retire", 0, None)])
    sched, sessions = run_scheduler_trace("deadline", ops)
    assert sched.miss_report()["missed"] == 1
    assert sched.misses_by_tenant == {"default": 1}


# ---------------------------------------------------------------------------
# transformer paged helpers
def test_paged_pool_gather_scatter_roundtrip():
    caches = tfm.init_caches(CFG, 3, 32, jnp.float32)
    caches = jax.tree.map(
        lambda c: jax.random.normal(jax.random.PRNGKey(c.size % 89), c.shape),
        caches)
    pool, slot_tree = tfm.paged_pool(caches, 8)
    pmap = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    view = tfm.gather_pages(pool, slot_tree, pmap)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), caches, view)
    # shuffled map still round-trips through scatter
    perm = jnp.asarray(np.random.default_rng(0).permutation(12)
                       .reshape(3, 4).astype(np.int32))
    pool2 = tfm.scatter_pages(pool, view, perm)
    view2 = tfm.gather_pages(pool2, slot_tree, perm)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        tfm.split_paged(caches)[0], tfm.split_paged(view2)[0])


def test_paged_pool_rejects_bad_shapes():
    caches = tfm.init_caches(CFG, 2, 32, jnp.float32)
    with pytest.raises(ValueError):
        tfm.paged_pool(caches, 7)           # does not divide max_len
    ssm = tfm.init_caches(ARCHS["mamba2-370m"].reduced(), 2, 32, jnp.float32)
    with pytest.raises(ValueError):
        tfm.paged_pool(ssm, 8)              # pure SSM: nothing to page


# ---------------------------------------------------------------------------
# paged engine end-to-end
def _solo(m, params, prompt, n_new):
    eng = Engine(m, params, batch=1, max_len=64)
    s = eng.submit(Request(uid=0, prompt=np.asarray(prompt, np.int32),
                           max_new_tokens=n_new))
    eng.run()
    return s.result()


def test_paged_streams_identical_to_unpaged(model_and_params):
    """Acceptance: the paged path is a pure storage change — same tokens."""
    m, params = model_and_params
    prompts = [((np.arange(4 + i, dtype=np.int32) * (i + 2) + 1)
                % CFG.vocab_size) for i in range(5)]
    want = [_solo(m, params, p, 6) for p in prompts]

    def drive(**kw):
        eng = Engine(m, params, batch=2, max_len=64, **kw)
        ss = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
              for i, p in enumerate(prompts)]
        eng.run()
        return eng, [s.result() for s in ss]

    for kw in ({"page_size": 64, "spill": "host", "scheduler": "srpt"},
               {"page_size": 16, "spill": "host"},
               {"page_size": 16, "pages": 3, "spill": "host",
                "scheduler": FairScheduler(quantum=2)}):
        eng, got = drive(**kw)
        assert got == want, kw
    # the last (overcommitted) run actually moved pages through the tier
    pages = eng.traffic_report()["pages"]
    assert pages["evictions"] > 0 and pages["refetches"] > 0


def test_paged_streams_identical_with_staggered_retires(model_and_params):
    """Regression: with unequal max_new_tokens a session retires mid-step
    and a queued one admits into the freed slot WITHOUT crossing a page
    boundary — a stale cached page map then gathered the newcomer's decode
    from the scratch page (silent stream corruption)."""
    m, params = model_and_params
    prompts = [((np.arange(4, dtype=np.int32) * (i + 2) + 1)
                % CFG.vocab_size) for i in range(4)]
    new_tokens = [3, 9, 4, 6]
    want = [_solo(m, params, p, n) for p, n in zip(prompts, new_tokens)]

    def drive(**kw):
        eng = Engine(m, params, batch=2, max_len=64, **kw)
        ss = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=n))
              for i, (p, n) in enumerate(zip(prompts, new_tokens))]
        eng.run()
        return [s.result() for s in ss]

    assert drive(page_size=16, spill="host") == want
    assert drive(page_size=16, pages=3, spill="host",
                 scheduler=FairScheduler(quantum=2)) == want


def test_deadline_ignores_unserved_sessions(model_and_params):
    """Rejected / cancelled-in-queue requests are outside the SLO — they
    must not inflate the met/missed deadline accounting."""
    m, params = model_and_params
    eng = Engine(m, params, batch=1, max_len=8, scheduler="deadline")
    rejected = eng.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                                  max_new_tokens=4, deadline=100))
    served = eng.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                                max_new_tokens=3, deadline=100))
    cancelled = eng.submit(Request(uid=2, prompt=np.arange(4, dtype=np.int32),
                                   max_new_tokens=3, deadline=100))
    cancelled.cancel()
    eng.run()
    assert rejected.finish_reason == "rejected"
    rep = eng.scheduler.miss_report()
    assert rep["met"] + rep["missed"] == 1  # only the served session counts


def test_quota_from_cli_codec_is_fleet_wide_default():
    """Regression: --page-codec must also fill named --tenant-quota
    clauses that don't pick their own codec."""
    from repro.serve.quota import quota_from_cli
    q = quota_from_cli("a:pages=8;b:codec=fp8", "int8")
    assert q.codec_for("a") == "int8"       # filled by the default
    assert q.codec_for("b") == "fp8"        # explicit choice wins
    assert q.codec_for("anyone-else") == "int8"
    assert q.quota_for("a").max_pages == 8  # caps preserved
    assert quota_from_cli(None, None) is None
    assert quota_from_cli(None, "fp8").codec_for("x") == "fp8"


def test_paged_lazy_spill_is_copy_free_without_pressure(model_and_params):
    """A full-size pool never moves a byte even under heavy preemption —
    pausing marks pages cold, resuming readmits them in place."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=64, page_size=16,
                 scheduler=FairScheduler(quantum=2), spill="host")
    ss = [eng.submit(Request(uid=i, prompt=np.arange(4, dtype=np.int32) + i,
                             max_new_tokens=6)) for i in range(5)]
    eng.run()
    assert sum(s.preemptions for s in ss) > 0
    rep = eng.traffic_report()
    assert rep["pages"]["evictions"] == 0
    assert rep["pages"]["readmits_free"] > 0
    assert "kv_stash" not in rep            # zero spill traffic


def test_paged_spill_bytes_metering(model_and_params):
    """kv_stash bytes == evictions x (bytes of one page across the kv
    leaves) — the per-page metering invariant, end to end."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=64, page_size=16, pages=3,
                 scheduler=FairScheduler(quantum=2), spill="host")
    for i in range(5):
        eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32) + i,
                           max_new_tokens=6))
    eng.run()
    rep = eng.traffic_report()
    ev, rf = rep["pages"]["evictions"], rep["pages"]["refetches"]
    assert ev > 0 and rf > 0
    page_leaves = jax.tree_util.tree_leaves(
        tfm.page_slice(eng.cache.pool, 0))
    page_bytes = sum(x.size * x.dtype.itemsize for x in page_leaves)
    assert rep["kv_stash"]["calls"] == ev * len(page_leaves)
    assert rep["kv_stash"]["wire_bytes"] == ev * page_bytes
    assert rep["kv_fetch"]["wire_bytes"] == rf * page_bytes
    # drained: every page either free or owned by nothing
    assert eng.cache.table.sessions() == ()
    assert eng.cache.table.num_free() == eng.cache.table.num_pages


def test_paged_tenant_codec_halves_spill_bytes(model_and_params):
    m, params = model_and_params

    def spill_bytes(quota):
        eng = Engine(m, params, batch=2, max_len=64, page_size=16, pages=3,
                     scheduler=FairScheduler(quantum=2), spill="host",
                     quota=quota)
        for i in range(5):
            eng.submit(Request(uid=i,
                               prompt=np.arange(5, dtype=np.int32) + i,
                               max_new_tokens=6))
        eng.run()
        rep = eng.traffic_report()
        return (rep["kv_stash"]["wire_bytes"] / rep["pages"]["evictions"],
                [r.out_tokens for r in sorted(eng.finished,
                                              key=lambda r: r.uid)])

    raw, out_raw = spill_bytes(None)
    int8, out_int8 = spill_bytes(TenantQuota(codec="int8"))
    # int8 page payloads are half the bf16/f32 wire bytes... the reduced
    # config serves f32 caches: int8 is 1/4 of f32 (+ tiny scale overhead)
    assert int8 < raw / 1.9, (raw, int8)
    assert len(out_int8) == len(out_raw) == 5   # lossy but completes


def test_quota_sessions_defer_and_release(model_and_params):
    m, params = model_and_params
    q = QuotaManager({"A": TenantQuota(max_sessions=1)})
    eng = Engine(m, params, batch=2, max_len=64, quota=q)
    sa = [eng.submit(Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                             max_new_tokens=4, tenant="A"))
          for i in range(3)]
    sb = eng.submit(Request(uid=9, prompt=np.arange(5, dtype=np.int32),
                            max_new_tokens=4, tenant="B"))
    eng.step()
    assert sorted(s.tenant for s in eng.cache.running()) == ["A", "B"]
    assert eng.quota_report()["A"]["sessions"] == 1
    eng.run()
    assert all(s.finish_reason == "length" for s in sa + [sb])
    assert eng.quota_report()["A"]["sessions"] == 0     # released


def test_quota_page_budget_rejects_impossible(model_and_params):
    m, params = model_and_params
    q = QuotaManager({"Z": TenantQuota(max_pages=1)})
    eng = Engine(m, params, batch=2, max_len=64, page_size=16, quota=q,
                 spill="host")
    big = eng.submit(Request(uid=0, prompt=np.arange(20, dtype=np.int32),
                             max_new_tokens=40, tenant="Z"))
    ok = eng.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                            max_new_tokens=4, tenant="Z"))
    eng.run()
    assert big.finish_reason == "quota"     # needs 4 pages, quota is 1
    assert ok.finish_reason == "length"     # fits: admitted normally


def test_quota_page_budget_serializes_tenant(model_and_params):
    """Two sessions of 2 pages each under a 2-page budget run one after
    the other; a second tenant is unaffected."""
    m, params = model_and_params
    q = QuotaManager({"A": TenantQuota(max_pages=2)})
    eng = Engine(m, params, batch=2, max_len=64, page_size=16, quota=q,
                 spill="host")
    a = [eng.submit(Request(uid=i, prompt=np.arange(20, dtype=np.int32),
                            max_new_tokens=10, tenant="A"))  # 30 rows: 2 pages
         for i in range(2)]
    b = eng.submit(Request(uid=5, prompt=np.arange(20, dtype=np.int32),
                           max_new_tokens=10, tenant="B"))
    eng.step()
    tenants = sorted(s.tenant for s in eng.cache.running())
    assert tenants == ["A", "B"]            # A's 2nd waits on the budget
    eng.run()
    assert all(s.finish_reason == "length" for s in a + [b])


def test_srpt_prefers_short_jobs(model_and_params):
    m, params = model_and_params
    eng = Engine(m, params, batch=1, max_len=64, scheduler="srpt")
    long_ = eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new_tokens=12))
    eng.step()                              # the long job is resident
    short = eng.submit(Request(uid=1, prompt=np.arange(5, dtype=np.int32),
                               max_new_tokens=3))
    eng.run()
    # the short job finished first even though it arrived second
    assert [r.uid for r in eng.finished] == [1, 0]
    assert long_.preemptions >= 1           # SRPT preempted the long job


def test_deadline_scheduler_orders_and_accounts(model_and_params):
    m, params = model_and_params
    eng = Engine(m, params, batch=1, max_len=64, scheduler="deadline")
    late = eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                              max_new_tokens=4, deadline=100))
    tight = eng.submit(Request(uid=1, prompt=np.arange(5, dtype=np.int32),
                               max_new_tokens=4, deadline=2))
    eng.run()
    assert eng.finished[0].uid == 1         # EDF ran the tight deadline first
    rep = eng.scheduler.miss_report()
    assert rep["missed"] >= 1 and rep["met"] >= 1


def test_paged_cancel_while_paused_frees_pages(model_and_params):
    m, params = model_and_params
    eng = Engine(m, params, batch=1, max_len=64, page_size=16,
                 scheduler=FairScheduler(quantum=1), spill="host")
    s0 = eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32) + 1,
                            max_new_tokens=8))
    s1 = eng.submit(Request(uid=1, prompt=np.arange(5, dtype=np.int32) + 2,
                            max_new_tokens=8))
    eng.step()                              # s0 resident
    eng.step()                              # s0 paused (quantum), s1 in
    assert s0.slot is None and eng.cache.table.holds(0) > 0
    s0.cancel()
    eng.run()
    assert eng.cache.table.sessions() == () # pages swept, not leaked
    assert len(s1.result()) == 8


def test_paged_pool_pressure_retires_or_preempts(model_and_params):
    """A 1-page pool with a growing session: once the page is full and no
    cold page exists, the engine retires the session cache_full instead of
    deadlocking; a queued session then gets the pool."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=64, page_size=16, pages=1,
                 spill="host")
    a = eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=40))
    b = eng.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=4))
    eng.run()
    assert a.finish_reason == "cache_full"
    assert a.length <= 16                   # confined to the single page
    assert b.finish_reason == "length"      # admitted after a's retire
    assert eng.cache.table.num_free() == 1


def _assert_parked_sessions_hold_no_hot_pages(eng):
    """Invariant: every page owned by a non-running session is cold (in
    the eviction queue) or spilled — never hot, which would make it
    unevictable while its owner cannot use it."""
    t = eng.cache.table
    cold = set(t._cold)
    for sess in eng.sessions:
        if sess.slot is not None:
            continue
        for pid in (t.resident_pids(sess.uid)
                    if sess.uid in t._entries else []):
            if pid is not None:
                assert pid in cold, \
                    f"parked session {sess.uid} owns hot page {pid}"


def test_grow_pages_never_allocates_to_freshly_paused(model_and_params):
    """Regression: _grow_pages used to iterate a stale running() snapshot,
    so a session paused mid-loop by pressure relief still got a page
    allocated — hot, with a parked owner, hence unevictable forever."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=16, page_size=4, pages=5,
                 spill="host")
    ss = [eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                             max_new_tokens=10)),
          eng.submit(Request(uid=1, prompt=np.arange(8, dtype=np.int32),
                             max_new_tokens=10))]
    for _ in range(200):
        n = eng.step()
        _assert_parked_sessions_hold_no_hot_pages(eng)
        eng.cache.table.check()
        if n == 0 and not eng.scheduler.has_waiting():
            break
    assert all(s.done for s in ss)
    assert eng.cache.table.sessions() == ()


def test_failed_admission_rolls_back_partial_pages(model_and_params):
    """Regression: a PageError mid-prepare_slot used to leave the still-
    queued session pinning hot pages it could never use or release."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=16, page_size=4, pages=4,
                 spill="host")
    a = eng.submit(Request(uid=0, prompt=np.arange(12, dtype=np.int32),
                           max_new_tokens=4))
    eng.step()                              # a resident: 3-4 hot pages
    b = eng.submit(Request(uid=1, prompt=np.arange(12, dtype=np.int32),
                           max_new_tokens=4))
    for _ in range(200):
        # while b waits it must hold zero pages (prepare rolled back)
        if not b.done and b.slot is None:
            assert eng.cache.table.holds(1) == 0
        _assert_parked_sessions_hold_no_hot_pages(eng)
        if eng.step() == 0 and not eng.scheduler.has_waiting():
            break
    assert a.finish_reason == "length"
    assert b.finish_reason == "length"      # admitted once a released


def test_failed_resume_does_not_inflate_readmit_count(model_and_params):
    """Regression: each failed resume attempt used to re-count the
    session's surviving pages as copy-free readmits."""
    m, params = model_and_params
    from repro.serve.cache_manager import PagedKVCacheManager
    mgr = PagedKVCacheManager(m, 2, 32, page_size=16, pages=3,
                              spill="spill")
    mk = lambda uid: Session(request=Request(
        uid=uid, prompt=np.zeros(2, np.int32)), seq=uid)
    a, b = mk(0), mk(1)
    mgr.prepare_slot(0, a, rows=32)         # a: 2 pages
    mgr.bind(0, a, 32)
    mgr.pause(a)                            # both pages cold
    mgr.prepare_slot(1, b, rows=16)         # evicts a's LRU page
    mgr.bind(1, b, 16)
    assert mgr.table.evictions == 0         # 1 free page absorbed it...
    mgr.prepare_slot(1, b, rows=32)         # ...now b's growth evicts
    assert mgr.table.evictions == 1
    assert mgr.table.spilled_positions(0) == [0]
    # resume a: its surviving page readmits, the spilled one cannot be
    # re-homed (b holds every other frame hot) -> PageError, undone count
    before = mgr.table.readmits_free
    for _ in range(3):                      # retries must not inflate
        with pytest.raises(PageError):
            mgr.resume(a, 0)
    assert mgr.table.readmits_free == before
    mgr.release(b)                          # frees b's frames
    mgr.resume(a, 0)
    assert mgr.table.readmits_free == before + 1    # one true readmit
    assert mgr.table.refetches == 1
    mgr.table.check()


def test_failed_fetch_mid_resume_reparks_position(model_and_params):
    """Bugfix: a spill-tier fetch dying AFTER set_resident handed out a
    frame used to leave the position resident over an unfilled frame —
    the rolled-back position must stay spilled (same payload) and a
    retry with a healed tier must succeed."""
    m, params = model_and_params
    from repro.serve.cache_manager import PagedKVCacheManager
    mgr = PagedKVCacheManager(m, 2, 32, page_size=16, pages=3,
                              spill="spill")
    mk = lambda uid: Session(request=Request(
        uid=uid, prompt=np.zeros(2, np.int32)), seq=uid)
    a, b = mk(0), mk(1)
    mgr.prepare_slot(0, a, rows=32)         # a: 2 pages
    mgr.bind(0, a, 32)
    mgr.pause(a)
    mgr.prepare_slot(1, b, rows=32)         # free page + evict one of a's
    mgr.bind(1, b, 32)
    assert mgr.table.spilled_positions(0) == [0]
    mgr.release(b)
    real_fetch = mgr.spill_runtime.fetch
    calls = {"n": 0}

    def flaky_fetch(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 2:                 # die mid-tree (leaf 2 of N)
            raise RuntimeError("spill tier glitch")
        return real_fetch(*args, **kw)

    mgr.spill_runtime.fetch = flaky_fetch
    with pytest.raises(RuntimeError):
        mgr.resume(a, 0)
    # rolled back: still spilled over the intact payload, fetch un-metered
    assert mgr.table.spilled_positions(0) == [0]
    assert mgr.table.refetches == 0
    assert mgr.table.entries(0)[0].payload is not None
    mgr.table.check()
    mgr.spill_runtime.fetch = real_fetch    # tier heals: retry works
    mgr.resume(a, 0)
    assert mgr.table.spilled_positions(0) == []
    assert mgr.table.refetches == 1
    mgr.table.check()


@pytest.mark.parametrize("codec", [None, "fp8", "int8"])
def test_shared_page_spill_refetch_roundtrip_codecs(model_and_params, codec):
    """A SHARED page through the real spill tier, per codec: evicted once
    (one stash funds every holder), refetched once (re-homing all of
    them), and the bytes that come back are the codec's round-trip of the
    frame that left — table invariants checked after every step.  (The
    hypothesis suite drives the same share/fork machinery through random
    traces; this pins the array/codec surgery deterministically.)"""
    from repro.core.compress import decode_tensor, encode_tensor, get_codec
    from repro.serve.cache_manager import PagedKVCacheManager
    m, _ = model_and_params
    mgr = PagedKVCacheManager(m, 2, 32, page_size=16, pages=3,
                              spill="spill",
                              codec_for=lambda tenant: codec)
    mk = lambda uid: Session(request=Request(
        uid=uid, prompt=np.zeros(2, np.int32)), seq=uid)
    a, b, c = mk(0), mk(1), mk(2)
    mgr.prepare_slot(0, a, rows=16)         # a: one private page
    mgr.bind(0, a, 16)
    pid = mgr.table.resident_pids(0)[0]
    # fill the frame with deterministic non-trivial bytes
    proto = tfm.page_slice(mgr.pool, pid)
    filled = jax.tree_util.tree_map(
        lambda x: (jnp.arange(x.size, dtype=jnp.float32)
                   .reshape(x.shape) % 7 - 3).astype(x.dtype), proto)
    mgr.pool = tfm.page_insert(mgr.pool, filled, pid)
    # b shares the page read-only (what prepare_slot does on a hit)
    mgr._sessions[1] = b
    mgr._codec_by_uid[1] = codec
    mgr.table.share(1, pid)
    assert mgr.table.refcount(pid) == 2
    mgr.table.check()
    mgr.pause(a)                            # a paused...
    mgr.table.mark_cold(1)                  # ...and so is sharer b
    mgr.table.check()
    stash_before = mgr.spill_runtime.traffic_report().get(
        "kv_stash", {"calls": 0})["calls"]
    mgr.prepare_slot(1, c, rows=48)         # 2 free frames + evict shared
    mgr.bind(1, c, 48)
    assert mgr.table.evictions == 1         # ONE spill for both holders
    from repro.serve.paging import SharedPayload as SP
    parked = mgr.table.entries(0)[0].payload
    assert isinstance(parked, SP)
    assert mgr.table.entries(1)[0].payload is parked
    n_leaves = len(jax.tree_util.tree_leaves(proto))
    stash_calls = mgr.spill_runtime.traffic_report()["kv_stash"]["calls"]
    assert stash_calls - stash_before == n_leaves   # one page's leaves
    mgr.table.check()
    mgr.release(c)                          # room to come back
    mgr.resume(a, 0)                        # ONE fetch re-homes b too
    assert mgr.table.refetches == 1
    new_pid = mgr.table.resident_pids(0)[0]
    assert mgr.table.resident_pids(1) == [new_pid]
    assert mgr.table.refcount(new_pid) == 2
    mgr.table.check()
    # bytes round-trip: exactly the codec's encode->decode of what left
    got = tfm.page_slice(mgr.pool, new_pid)
    cdc = get_codec(codec) if codec else None
    for want_leaf, got_leaf in zip(jax.tree_util.tree_leaves(filled),
                                   jax.tree_util.tree_leaves(got)):
        if cdc is not None and cdc.applies_to(want_leaf):
            q, scale = encode_tensor(cdc, want_leaf)
            want_leaf = decode_tensor(cdc, q, scale, want_leaf.dtype)
        np.testing.assert_array_equal(np.asarray(want_leaf),
                                      np.asarray(got_leaf))


# ---------------------------------------------------------------------------
# prefix sharing through the real engine
def _shared_prefix_prompts(n=4, head_len=20, tail_len=12):
    # head crosses one full page (rows 0-15) and diverges INSIDE the
    # second registered page (row 20 of 16..31): hits page 0, forks page 1
    head = (np.arange(head_len, dtype=np.int32) * 3 + 5) % CFG.vocab_size
    return [np.concatenate([
        head, (np.arange(tail_len, dtype=np.int32) * (i + 2) + i)
        % CFG.vocab_size]).astype(np.int32) for i in range(n)]


def test_prefix_share_streams_identical_and_hit(model_and_params):
    """Acceptance: --prefix-share is a pure storage optimisation — the
    streams match the sharing-off and unpaged runs bit-for-bit while the
    prefix cache actually hits (shared binds + forks observed)."""
    m, params = model_and_params
    prompts = _shared_prefix_prompts()
    want = [_solo(m, params, p, 6) for p in prompts]

    def drive(**kw):
        eng = Engine(m, params, batch=2, max_len=64, spill="host", **kw)
        ss = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
              for i, p in enumerate(prompts)]
        eng.run()
        return eng, [s.result() for s in ss]

    _, base = drive(page_size=16)
    eng, got = drive(page_size=16, prefix_share=True)
    assert got == want and base == want
    rep = eng.traffic_report()["prefix"]
    assert rep["enabled"] and rep["hits"] > 0 and rep["forks"] > 0
    assert rep["hit_rate"] > 0
    assert eng.cache.table.shared_binds > 0
    eng.cache.table.check()


def test_prefix_share_identical_under_eviction_pressure(model_and_params):
    """Shared pages spilling once and re-homing on refetch must not
    perturb the streams even when the overcommitted pool thrashes."""
    m, params = model_and_params
    prompts = _shared_prefix_prompts()
    want = [_solo(m, params, p, 6) for p in prompts]
    eng = Engine(m, params, batch=2, max_len=64, page_size=16, pages=4,
                 spill="host", prefix_share=True,
                 scheduler=FairScheduler(quantum=2))
    ss = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
          for i, p in enumerate(prompts)]
    eng.run()
    assert [s.result() for s in ss] == want
    eng.cache.table.check()


def test_prefix_share_charges_only_private_pages(model_and_params):
    """Quota: pages bound read-only from the prefix cache were already
    paid for by the donor — a matching session is charged less."""
    m, params = model_and_params
    prompts = _shared_prefix_prompts(n=2)
    quota = QuotaManager({"default": TenantQuota(max_pages=64)})
    eng = Engine(m, params, batch=2, max_len=64, page_size=16,
                 spill="host", prefix_share=True, quota=quota)
    ss = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
          for i, p in enumerate(prompts)]
    eng.step()                              # both admitted together
    used = quota.usage()["default"]["pages"]
    # solo demand: ceil(34/16)=3 pages each; the second session matched
    # at least the first full prefix page, so the pair charged < 6
    assert used < 6
    eng.run()
    assert all(s.finish_reason == "length" for s in ss)


def test_overcommitted_pool_is_physically_smaller(model_and_params):
    """pages=N must shrink the resident pool itself (the paper's pooled-
    capacity saving), not just simulate eviction pressure."""
    m, _ = model_and_params
    from repro.serve.cache_manager import PagedKVCacheManager
    full = PagedKVCacheManager(m, 2, 64, page_size=16, spill=None)
    small = PagedKVCacheManager(m, 2, 64, page_size=16, pages=3,
                                spill=None)
    fb = sum(x.size for x in jax.tree_util.tree_leaves(full.pool))
    sb = sum(x.size for x in jax.tree_util.tree_leaves(small.pool))
    assert fb * 4 == sb * 9             # 8+1 frames vs 3+1 frames
    assert small.scratch_id == 3 and full.scratch_id == 8
    with pytest.raises(ValueError):
        PagedKVCacheManager(m, 2, 64, page_size=16, pages=9, spill=None)


def test_paged_engine_with_temperature_sampling(model_and_params):
    """Non-greedy sampling through the paged path exercises the PRNG
    branch; the stream stays inside the vocab and completes."""
    m, params = model_and_params
    eng = Engine(m, params, batch=2, max_len=64, page_size=16,
                 temperature=0.8, seed=7, spill="host")
    s = eng.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                           max_new_tokens=5))
    eng.run()
    assert len(s.result()) == 5
    assert all(0 <= t < CFG.vocab_size for t in s.result())


# ---------------------------------------------------------------------------
# derive_cache_shape: page sizing + the explicit-0/None regression
def test_derive_cache_shape_batch_zero_means_auto(model_and_params):
    m, _ = model_and_params
    from repro.serve.kv_cache import derive_cache_shape
    auto = derive_cache_shape(m.cfg, m.runtime, None, None)
    zero = derive_cache_shape(m.cfg, m.runtime, 0, 0)
    assert zero == auto                     # 0 no longer leaks through
    assert zero["batch"] >= 1 and zero["max_len"] >= 16


def test_derive_cache_shape_joint_solve_tiny_budget(model_and_params):
    """batch=None, max_len=None at a starvation budget: the halving loop
    floors at 16 rows and the packer still returns a sane >=1 slot."""
    m, _ = model_and_params
    from repro.serve.kv_cache import derive_cache_shape
    sized = derive_cache_shape(m.cfg, m.runtime, None, None,
                               hbm_frac=1e-12)
    assert sized["batch"] == 1 and sized["max_len"] == 16
    assert sized["report"]["capacity_bytes"] > 0
    # paged twin: the floor rounds to whole pages
    paged = derive_cache_shape(m.cfg, m.runtime, None, None,
                               hbm_frac=1e-12, page_size=8)
    assert paged["max_len"] % 8 == 0 and paged["max_len"] >= 8
    assert paged["report"]["num_pages"] == \
        paged["batch"] * paged["report"]["pages_per_slot"]


def test_derive_cache_shape_page_rounding(model_and_params):
    m, _ = model_and_params
    from repro.serve.kv_cache import derive_cache_shape
    up = derive_cache_shape(m.cfg, m.runtime, 2, 50, page_size=16)
    assert up["max_len"] == 64              # explicit max_len rounds UP
    assert up["report"]["pages_per_slot"] == 4
    with pytest.raises(ValueError):
        derive_cache_shape(m.cfg, m.runtime, 2, 64, page_size=0)


# ---------------------------------------------------------------------------
# quota plumbing
def test_parse_quota_spec_grammar():
    per, default = parse_quota_spec("pages=16,sessions=2")
    assert per == {} and default == TenantQuota(16, 2, None)
    per, default = parse_quota_spec(
        "interactive:sessions=4;batch:pages=8,codec=int8")
    assert per["interactive"] == TenantQuota(None, 4, None)
    assert per["batch"] == TenantQuota(8, None, "int8")
    assert default == TenantQuota()
    with pytest.raises(ValueError):
        parse_quota_spec("pages")
    with pytest.raises(ValueError):
        parse_quota_spec("rows=4")
    with pytest.raises(KeyError):
        parse_quota_spec("codec=zstd")      # unknown codec fails fast


def test_quota_manager_ledger():
    q = QuotaManager({"a": TenantQuota(max_pages=4, max_sessions=2)})
    assert q.can_admit("a", 3) and q.admissible("a", 4)
    assert not q.admissible("a", 5)
    q.admit("a", 3)
    assert not q.can_admit("a", 2)          # page budget
    q.admit("a", 1)
    assert not q.can_admit("a", 0)          # session cap
    q.release("a", 3)
    q.release("a", 1)
    assert q.usage()["a"] == {"sessions": 0, "pages": 0}
    assert q.can_admit("other", 10**6)      # default quota is unlimited
    assert "quota[" in q.describe()
