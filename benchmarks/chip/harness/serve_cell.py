"""A serving cell: ``repro.serve.engine.Engine`` over the paged KV cache,
driven by the mix's requests.

Set-up builds the weights on the device (one jitted call from the seed)
and the engine, then warms every prompt length the mix uses through the
engine itself.  An open-loop mix (``poisson``) submits each request at
its due time during the window and steps the engine in between; a
preloaded mix submits every session in set-up and steps until each has
been prefilled once.  Each emitted token is stamped on the host clock by
the session's ``on_token`` callback.

After the window, the engine keeps stepping (no new requests) until each
request due in the window has its first token, for at most a minute; a
request that never gets one failed.  Then, with the program's state
freed, the plain reference re-reads a seeded sample of the served
sessions (the longest among them) and ``correct`` compares the widest gap
by which a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import collections
import gc
import sys
import time
from typing import Any, Dict, List

import numpy as np

from harness import common, flops, spec, traffic

GRACE_S = 60.0
NOTHING = 1e30      # stands for a number that could not be read (JSON has
                    # no infinity): it fails every limit and every bound


def build(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
    import jax
    from repro.configs.base import (MemoryPlan, MeshPlan, RunConfig,
                                    ShapeConfig, TrainConfig)
    from repro.models.model import build_model
    from repro.serve.engine import Engine
    from repro.serve.scheduler import build_scheduler

    es = mix["engine"]
    run = RunConfig(model=common.model_config(cfg),
                    shape=ShapeConfig("serve", es["max_len"], es["slots"],
                                      "decode"),
                    mesh=MeshPlan((1,), ("data",)),
                    memory=MemoryPlan(policy="none"), train=TrainConfig())
    model = build_model(run)
    params = jax.jit(model.init)(jax.random.PRNGKey(traffic.key_seed(seed)))
    sched = {"quantum": es["quantum"]} if "quantum" in es else {}
    eng = Engine(model, params, batch=es["slots"], max_len=es["max_len"],
                 scheduler=build_scheduler(es["scheduler"], **sched),
                 spill=es["spill"], page_size=es["page_size"],
                 pages=None if es["pages"] == "all" else int(es["pages"]),
                 decode_kernel=bool(es["decode_kernel"]))
    return model, params, eng


class Driver:
    """Submits requests, steps the engine and keeps the host-clock stamps
    and the counters the metrics read."""

    def __init__(self, eng, cfg: Dict[str, Any], page: int):
        self.eng, self.cfg, self.page = eng, cfg, page
        self.stamps: Dict[int, List[float]] = collections.defaultdict(list)
        self.sessions: Dict[int, Any] = {}
        self.steps = 0
        self.flops = 0.0
        self.calls: List[tuple] = []
        note = eng.cache.note_decode

        def note_decode(length, n_active):       # the program's counter
            self.calls.append((length, n_active))
            note(length, n_active)

        eng.cache.note_decode = note_decode

    def on_token(self, sess, tok) -> None:
        self.stamps[sess.uid].append(time.perf_counter())
        if len(sess.tokens) == 1:
            self.flops += flops.prefill_flops(self.cfg, sess.length)
        else:
            self.flops += flops.decode_flops(self.cfg, sess.length)

    def submit(self, req) -> None:
        from repro.serve.engine import Request
        with common.span("bench.submit"):
            self.sessions[req.uid] = self.eng.submit(
                Request(uid=req.uid, prompt=req.prompt,
                        max_new_tokens=req.max_new_tokens),
                on_token=self.on_token)

    def busy(self) -> bool:
        return bool(self.eng.cache.running()) or \
            self.eng.scheduler.has_waiting()

    def step(self) -> None:
        with common.span("bench.engine_step"):
            self.eng.step()
        self.steps += 1

    def spill_bytes(self) -> float:
        rep = self.eng.traffic_report()
        return sum(rep.get(k, {}).get("wire_bytes", 0.0)
                   for k in ("kv_stash", "kv_fetch"))

    def pages(self, key: str) -> int:
        return self.eng.traffic_report()["pages"][key]

    def decode_calls(self) -> int:
        return self.eng.traffic_report()["decode_io"]["steps"]


def _instrument(eng) -> None:
    """Host spans around the engine's phases (traced runs only)."""
    for obj, prefix, names in (
            (eng, "bench.engine.", ("_preempt", "_admit", "_grow_pages",
                                    "_sample", "_decode_paged_kernel",
                                    "_decode_paged", "_prefill_paged")),
            (eng.cache, "bench.cache.", ("resume", "pause", "_evict_cb"))):
        for name in names:
            fn = getattr(obj, name)

            def wrapped(*a, _fn=fn, _span=prefix + name.lstrip("_"), **kw):
                with common.span(_span):
                    return _fn(*a, **kw)

            setattr(obj, name, wrapped)


def warm(drv: Driver, mix: Dict[str, Any], vocab: int, seed: int) -> None:
    """Prefill and decode once at every prompt length the mix uses."""
    from harness.traffic import Request
    p = mix["prompt"]
    sizes = p.get("buckets") or p.get("values") or [p.get("value")]
    rng = traffic.rng_for(seed, 6)
    for i, n in enumerate(sorted(set(int(s) for s in sizes))):
        drv.submit(Request(uid=10 ** 9 + i, due_s=0.0,
                           prompt=rng.integers(0, vocab, size=n,
                                               dtype=np.int32),
                           max_new_tokens=2))
    while drv.busy():
        drv.step()


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        devices, limits: Dict[str, float], control: bool = False
        ) -> Dict[str, Any]:
    import jax

    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    es = mix["engine"]
    vocab = cfg["vocab_size"]
    counter = common.CompileCounter()
    t0 = common.now()
    model, params, eng = build(cfg, mix, seed)
    drv = Driver(eng, cfg, es["page_size"])
    t_built = common.now()
    warm(drv, mix, vocab, seed)
    t_warm = common.now()
    reqs = traffic.serve_requests(mix, seed, seconds, vocab)
    pending = collections.deque(reqs)
    preloaded = mix["arrivals"]["process"] == "preloaded"
    if preloaded:
        while pending:
            drv.submit(pending.popleft())
        # every session prefilled once; and where pages have left for the
        # spill tier, until one has come back, so that the window's
        # evictions and fetches run programs already compiled
        while any(not drv.stamps[r.uid] for r in reqs) or (
                drv.pages("evictions") and not drv.pages("refetches")):
            drv.step()
    jax.block_until_ready(eng.cache.pool)
    setup_s = common.now() - t0
    phases = {"build_s": t_built - t0, "warm_s": t_warm - t_built,
              "preload_s": setup_s - (t_warm - t0)}
    if trace:
        _instrument(eng)

    # -------------------------------------------------------------- window
    prof = common.Profiler() if trace else None
    t_on = seconds * float(mix.get("trace_from", 0.3))
    t_off = t_on + float(mix.get("traced_seconds", 3.0))
    late: List[float] = []
    counter.on = True
    start = common.now()
    end = start + seconds
    snap: Dict[str, Any] = {}

    def tick(until: float) -> None:
        while True:
            t = common.now()
            if t >= until:
                return
            while pending and start + pending[0].due_s <= t:
                req = pending.popleft()
                late.append(t - start - req.due_s)
                drv.submit(req)
            if drv.busy():
                drv.step()
            else:
                nxt = start + pending[0].due_s if pending else until
                time.sleep(max(0.0, min(nxt, until) - common.now()))

    def counters() -> Dict[str, Any]:
        return {"t": common.now(), "steps": drv.steps, "flops": drv.flops,
                "calls": len(drv.calls), "decode_calls": drv.decode_calls(),
                "spill": drv.spill_bytes(),
                "tokens": sum(len(s) for s in drv.stamps.values())}

    snap["start"] = counters()
    if prof is None:
        tick(end)
    else:
        tick(start + t_on)
        with prof.window():
            snap["a"] = counters()
            tick(start + t_off)
            snap["b"] = counters()
        tick(end)
    snap["end"] = counters()
    counter.on = False
    queued = len(eng.scheduler.waiting())
    due = [r for r in reqs if r.due_s < seconds]
    wait_until = common.now() + GRACE_S
    while (any(not drv.stamps[r.uid] for r in due) and drv.busy()
           and common.now() < wait_until):
        drv.step()
    peak = common.memory_peak_bytes(devices)

    # ------------------------------------------------------------- metrics
    in_win = [t for s in drv.stamps.values() for t in s if start <= t <= end]
    gaps = [b - a for r in reqs for a, b in zip(drv.stamps[r.uid],
                                                 drv.stamps[r.uid][1:])
            if a >= start and b <= end]
    gave_up = common.now()
    firsts = [drv.stamps[r.uid][0] - (start + r.due_s) for r in due
              if drv.stamps[r.uid]]
    # a request that never got a token counts with the time it waited
    waits = [gave_up - (start + r.due_s) for r in due
             if not drv.stamps[r.uid]]
    served = [drv.sessions[r.uid] for r in reqs if r.uid in drv.sessions]
    failed = len(waits) if not preloaded else sum(
        s.finish_reason not in (None, "length", "eos") for s in served)
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": len(in_win) / seconds,
           "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps
           else NOTHING}
    if not preloaded:
        e2e["ttft_p95_ms"] = 1e3 * float(np.percentile(firsts + waits, 95))
    finished = {s.uid: (s.request.prompt, list(s.tokens)) for s in served
                if len(s.tokens) >= 2
                and (preloaded or s.finish_reason in ("length", "eos"))}
    rep = eng.traffic_report()
    log = {"window_s": seconds, "engine_steps": drv.steps,
           "setup_phases": phases,
           "queued_at_close": queued,
           "requests_due": len(due), "first_tokens": len(firsts),
           "itl_samples": len(gaps), "tokens_in_window": len(in_win),
           "generator_late_max_s": max(late, default=0.0),
           "traces_in_window": counter.traces,
           "compiles_in_window": counter.compiles,
           "pages": rep.get("pages"), "finished": len(finished)}
    del eng, params, model, drv.eng
    gc.collect()

    # ----------------------------------------------------------- reference
    sample = pick_sample(finished, mix.get("check", {}), seed)
    ref = spec.reference(cfg)
    t_ref = common.now()
    key = jax.random.PRNGKey(traffic.key_seed(seed))
    gaps_ref = ref.serve_gaps(cfg, key, [finished[u] for u in sample],
                              control=control)
    log.update(checked_sessions=len(sample), checked_tokens=gaps_ref["tokens"])
    if control:
        log["control_fp8"] = {"served_logit_gap":
                              gaps_ref["control_logit_gap"]}
    print(f"reference: {common.now() - t_ref:.1f} s for {len(sample)} "
          f"sessions", file=sys.stderr)
    # no session to check compares nothing, and is not correct
    gap = gaps_ref["served_logit_gap"] if gaps_ref["tokens"] else NOTHING
    checks = {"served_logit_gap": {"value": gap,
                                   "limit": limits["served_logit_gap"]}}

    res: Dict[str, Any] = {"e2e": e2e, "attempted": len(reqs),
                           "failed": int(failed), "memory_peak_bytes": peak,
                           "checks": checks, "log": log}
    if prof is not None:
        # what the trace reads covers the traced stretch; the program's
        # counters (decode calls, spill bytes) cover the whole window, so
        # that a stretch between two rotations cannot miss the spill
        a, b = snap["a"], snap["b"]
        w0, w1 = snap["start"], snap["end"]
        page, L = es["page_size"], cfg["num_hidden_layers"]
        res["traced"] = prof.result
        res["counters"] = {
            "kind": "serve", "chips": len(devices), "page_size": page,
            "model_flops": b["flops"] - a["flops"],
            "paged_need": [flops.paged_decode_need(cfg, ln, n, page)
                           for ln, n in drv.calls[a["calls"]:b["calls"]]],
            "layers": L, "decode_kernel": bool(es["decode_kernel"]),
            "engine_steps": w1["steps"] - w0["steps"],
            "decode_calls": w1["decode_calls"] - w0["decode_calls"],
            "tokens": w1["tokens"] - w0["tokens"],
            "spill_bytes": w1["spill"] - w0["spill"]}
    return res


def pick_sample(finished: Dict[int, tuple], check: Dict[str, Any],
                seed: int) -> List[int]:
    """A seeded sample of the served sessions, the longest among them."""
    if not finished:
        return []
    uids = sorted(finished)
    longest = max(uids, key=lambda u: len(finished[u][0])
                  + len(finished[u][1]))
    rest = [u for u in uids if u != longest]
    k = min(len(rest), int(check.get("sessions", 8)) - 1)
    pick = traffic.rng_for(seed, 8).choice(len(rest), size=k, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
