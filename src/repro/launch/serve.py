"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the tier-aware serving stack (serve/engine.py facade over Scheduler /
KVCacheManager / Session) over pooled KV caches.  The full config serves on
the devices present (``launch/mesh.mesh_for_devices``); ``--smoke`` swaps in
the reduced twin.

``--batch`` / ``--max-len`` may be omitted: the cache manager then sizes
the decode slots from the serving tier's ``cache_tier_report``.  Cold KV
(preempted sessions under ``--scheduler fair/priority/srpt/deadline``)
goes to the ``--spill`` tier; with ``--page-size`` the cache is *paged* —
cold pages spill lazily, per page, through the per-tenant ``--page-codec``
— and ``--pages`` overcommits the pool below batch x pages_per_slot.
``--tenant-quota`` caps what each tenant may hold (see
serve/quota.parse_quota_spec for the grammar); ``--tenants N`` spreads the
synthetic requests over N tenant names.  The run prints the spill/page
traffic report, per-tenant usage and (for ``--scheduler deadline``, with
``--deadline-slack`` steps of slack) the deadline-miss accounting.

``--role`` disaggregates prefill from decode (serve/disagg.py):
``both`` runs the two-engine loopback in this process — prompts prefill
on a prefill-role engine, KV pages ship through the ``--transfer-tier``
(metered, printed as the transfer report with time-to-first-token), and
a decode-role engine adopts them; ``prefill`` runs the prefill worker
alone (publishes into a local queue and reports what shipped — useful to
price the transfer path); ``decode --connect HOST:PORT`` runs the decode
worker of a two-process deployment over the TCP wire transport
(serve/transport.py), adopting handoffs off the socket and streaming
RESULTs back — standalone decode without ``--connect`` is still
rejected.  Omit ``--role`` for the classic colocated engine.

``--router`` runs the cluster front-end (serve/router.py) over
``--engines`` prefill/decode pairs with ``--placement`` choosing where
sessions land; ``--transport memory|tcp`` makes engine 0 a wire pair
(every page byte-serialized through frames), ``--listen PORT`` makes it
the prefill half of a two-process pair (start the peer with ``--role
decode --connect``), ``--drain-after N`` gracefully drains
``--drain-engine`` after N router steps (the CI smoke asserts zero
dropped sessions), and ``--trace N`` replays N sessions of the synthetic
diurnal/burst/shared-prefix traffic mix (sim/workloads.py) instead of
the uniform synthetic requests.

Scale-out wire: ``--wire-streams N`` stripes each handoff page-wise over
N parallel TCP connections (both the ``--listen`` prefill half and the
``--connect`` decode worker must agree), ``--wire-bufsize`` sizes the
socket buffers, ``--transport shm`` takes the zero-copy same-host path
(payloads through a shared-memory arena, headers over the socket), and
``--peer HOST:PORT`` / ``--fed-listen PORT`` federate two router
processes so overflow admissions forward to the peer cluster.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.configs import MemoryPlan, RunConfig, TrainConfig, get_arch
from repro.configs.base import ShapeConfig
from repro.core.runtime import MemoryRuntime
from repro.launch.mesh import enable_compile_cache, mesh_for_devices
from repro.models.model import build_model
from repro.serve.disagg import TransferQueue, build_disagg
from repro.serve.engine import Engine, Request
from repro.serve.quota import quota_from_cli
from repro.serve.scheduler import build_scheduler, registered_schedulers


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=None,
                    help="decode slots (default: auto from the tier report)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="cache rows per slot (default: auto)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--stagger", type=int, default=0,
                    help="request i decodes new-tokens + i*stagger tokens "
                         "(unequal service times: lets srpt/deadline sort)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", default="fcfs",
                    choices=registered_schedulers())
    ap.add_argument("--quantum", type=int, default=8,
                    help="fair-scheduler decode quantum")
    ap.add_argument("--spill", default="spill",
                    help="secondary tier policy for cold KV")
    ap.add_argument("--page-size", type=int, default=None,
                    help="page the KV cache (rows per page; default: "
                         "monolithic slots)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size (default batch*max_len/page_size; "
                         "smaller overcommits)")
    ap.add_argument("--page-codec", default=None,
                    help="default spill codec for cold pages (fp8/int8/...)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="share common prompt-prefix pages copy-on-write "
                         "across sessions (paged cache only)")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="decode in place over the page table (paged "
                         "attention kernel; reads only the pages each "
                         "session holds instead of gathering the pool)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="draw the first N prompt tokens from a common "
                         "prefix so --prefix-share has something to hit")
    ap.add_argument("--tenant-quota", default=None,
                    help="per-tenant caps, e.g. 'pages=16,sessions=2' or "
                         "'a:pages=8;b:sessions=1,codec=int8'")
    ap.add_argument("--tenants", type=int, default=1,
                    help="spread requests over N tenant names t0..tN-1")
    ap.add_argument("--deadline-slack", type=int, default=None,
                    help="per-request deadline = slack + (i+1)*new-tokens "
                         "engine steps (with --scheduler deadline)")
    ap.add_argument("--role", default=None,
                    choices=("prefill", "decode", "both"),
                    help="disaggregate prefill/decode (both: in-process "
                         "two-engine loopback; default: colocated engine)")
    ap.add_argument("--transfer-tier", default="spill",
                    help="tier policy carrying KV handoffs between roles "
                         "(spill: pooled HBM->host; host: PCIe DRAM)")
    ap.add_argument("--transfer-depth", type=int, default=None,
                    help="max handoffs parked in the transfer queue "
                         "(prefill admission stalls past it)")
    ap.add_argument("--router", action="store_true",
                    help="run the cluster router over --engines pairs")
    ap.add_argument("--engines", type=int, default=2,
                    help="prefill/decode pairs behind the router")
    ap.add_argument("--placement", default="least_loaded",
                    help="placement policy "
                         "(least_loaded/prefix_affinity/round_robin)")
    ap.add_argument("--transport", default=None,
                    choices=("memory", "tcp", "shm"),
                    help="make router engine 0 a wire pair over this "
                         "byte channel (pages cross as serialized frames; "
                         "shm: zero-copy same-host arena, only headers "
                         "cross the socket)")
    ap.add_argument("--wire-streams", type=int, default=1,
                    help="stripe each wire handoff page-wise across N "
                         "parallel sub-channels (1: single stream)")
    ap.add_argument("--wire-bufsize", type=int, default=None,
                    help="SO_SNDBUF/SO_RCVBUF for wire TCP sockets "
                         "(default: kernel autotuning)")
    ap.add_argument("--peer", default=None, metavar="HOST:PORT",
                    help="router mode: federate with the router at this "
                         "address (forward admissions we cannot place)")
    ap.add_argument("--fed-listen", type=int, default=None,
                    help="router mode: accept one federation peer on this "
                         "port (0: ephemeral, printed)")
    ap.add_argument("--listen", type=int, default=None,
                    help="two-process mode: engine 0 (or --role prefill) "
                         "serves prefill over TCP on this port (0: "
                         "ephemeral, printed); peer runs --role decode "
                         "--connect")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="with --role decode: adopt handoffs from this "
                         "prefill/router process")
    ap.add_argument("--drain-after", type=int, default=None,
                    help="router mode: gracefully drain --drain-engine "
                         "after N router steps")
    ap.add_argument("--drain-engine", type=int, default=0)
    ap.add_argument("--trace", type=int, default=None,
                    help="router mode: replay N synthetic traffic "
                         "sessions (diurnal/burst/shared-prefix mix)")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    if args.role == "decode" and args.connect is None:
        ap.error("--role decode needs a peer feeding the transfer queue; "
                 "use --role both for the in-process loopback, or pass "
                 "--connect HOST:PORT for the two-process wire")
    if (args.role is not None or args.router) and not args.page_size:
        ap.error("--role/--router ship page-shaped KV: pass --page-size")
    if args.prefix_share and not args.page_size:
        ap.error("--prefix-share reuses whole pages: pass --page-size")
    if args.prefix_share and (args.role is not None or args.router):
        ap.error("--prefix-share is a colocated-engine feature for now")
    if args.decode_kernel and not args.page_size:
        ap.error("--decode-kernel reads through the page table: pass "
                 "--page-size")
    if args.decode_kernel and (args.role is not None or args.router):
        ap.error("--decode-kernel is a colocated-engine feature for now")
    if args.listen is not None and args.batch is None:
        ap.error("--listen needs explicit --batch/--max-len (the remote "
                 "decode geometry cannot be negotiated over the wire)")
    if args.wire_streams < 1:
        ap.error("--wire-streams must be >= 1")
    if args.wire_streams > 1 and args.transport == "shm":
        ap.error("--transport shm is header-only on one control socket; "
                 "striping it is meaningless (drop --wire-streams)")
    if args.trace and (args.peer or args.fed_listen is not None):
        ap.error("--trace replays against one cluster; it does not "
                 "compose with federation (--peer/--fed-listen)")
    logging.basicConfig(level=logging.INFO)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    mesh, plan = mesh_for_devices(multi_pod=args.multi_pod)

    shape = ShapeConfig("serve", args.max_len or 128, args.batch or 4,
                        "decode")
    run = RunConfig(model=cfg, shape=shape, mesh=plan,
                    memory=MemoryPlan(policy="none"), train=TrainConfig())
    model = build_model(run, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))

    quota = quota_from_cli(args.tenant_quota, args.page_codec)

    if args.role == "decode":
        _run_decode_worker(model, params, args)
        return
    if args.router:
        _run_router(model, params, cfg, quota, args)
        return

    sched = (build_scheduler("fair", quantum=args.quantum)
             if args.scheduler == "fair" else build_scheduler(args.scheduler))
    if args.role == "both":
        eng = build_disagg(model, params, batch=args.batch,
                           max_len=args.max_len, page_size=args.page_size,
                           pages=args.pages, transfer=args.transfer_tier,
                           max_depth=args.transfer_depth,
                           scheduler=args.scheduler,
                           decode_scheduler=sched, spill=args.spill,
                           quota=quota, temperature=args.temperature)
    elif args.role == "prefill":
        runtime = MemoryRuntime(
            model.plan,
            MemoryPlan(policy=args.transfer_tier,
                       placement=model.memory.placement),
            model.mesh, planner=model.planner)
        eng = Engine(model, params, batch=args.batch, max_len=args.max_len,
                     temperature=args.temperature, scheduler=sched,
                     spill=None, page_size=args.page_size, quota=quota,
                     role="prefill",
                     transfer=TransferQueue(runtime,
                                            max_depth=args.transfer_depth))
    else:
        eng = Engine(model, params, batch=args.batch, max_len=args.max_len,
                     temperature=args.temperature, scheduler=sched,
                     spill=args.spill, page_size=args.page_size,
                     pages=args.pages, quota=quota,
                     prefix_share=args.prefix_share,
                     decode_kernel=args.decode_kernel)
    print(eng.describe())
    rng = np.random.default_rng(0)
    shared_head = rng.integers(
        0, cfg.vocab_size,
        size=(max(0, args.shared_prefix),)).astype(np.int32)
    t0 = time.perf_counter()
    first_token_at = {}
    sessions = []
    for i in range(args.requests):
        deadline = (args.deadline_slack + (i + 1) * args.new_tokens
                    if args.deadline_slack is not None else None)
        tail_len = max(1, args.prompt_len - len(shared_head))
        prompt = np.concatenate([
            shared_head,
            rng.integers(0, cfg.vocab_size,
                         size=(tail_len,)).astype(np.int32)])
        sessions.append(eng.submit(Request(
            uid=i,
            prompt=prompt,
            max_new_tokens=args.new_tokens + i * args.stagger,
            priority=i % 3 if args.scheduler == "priority" else 0,
            tenant=f"t{i % max(1, args.tenants)}",
            deadline=deadline),
            on_token=lambda s, t: first_token_at.setdefault(
                s.uid, time.perf_counter())))
    done = eng.run()
    dt = time.perf_counter() - t0
    total_new = sum(len(s.result()) for s in sessions)
    print(f"served {len(done)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    if first_token_at:
        ttft = [first_token_at[s.uid] - t0 for s in sessions
                if s.uid in first_token_at]
        print(f"ttft: mean {1e3 * sum(ttft) / len(ttft):.1f}ms, "
              f"max {1e3 * max(ttft):.1f}ms")
    for s in sessions[:3]:
        print(f"  req {s.uid}: {s.finish_reason}, "
              f"preempted {s.preemptions}x, {s.result()[:8]}...")
    if args.role in ("both", "prefill"):
        trep = eng.transfer.traffic_report()
        tq = trep["transfer"]
        from repro.core.runtime import fmt_bytes
        pub = trep.get("kv_publish", {"wire_bytes": 0.0, "calls": 0})
        print(f"transfer[{eng.transfer.runtime.tier.describe()}]: "
              f"{tq['shipped_pages']} pages shipped "
              f"({fmt_bytes(pub['wire_bytes'])}), "
              f"{tq['requeued']} requeued, depth {tq['depth']}")
        if args.role == "prefill":
            return
        report = eng.decode.traffic_report()
    else:
        report = eng.traffic_report()
    if report.get("kv_stash"):
        from repro.core.runtime import fmt_bytes
        fetch = report.get("kv_fetch", {"wire_bytes": 0.0, "calls": 0})
        print(f"spill[{report['tier']}]: "
              f"stash {fmt_bytes(report['kv_stash']['wire_bytes'])}"
              f"/{report['kv_stash']['calls']}x, "
              f"fetch {fmt_bytes(fetch['wire_bytes'])}"
              f"/{fetch['calls']}x")
    if report.get("pages"):
        p = report["pages"]
        print(f"pages[{p['num_pages']}x{p['page_size']}]: "
              f"{p['evictions']} evicted, {p['refetches']} refetched, "
              f"{p['readmits_free']} readmitted copy-free, "
              f"{p['adoptions']} adopted")
    if report.get("decode_io", {}).get("in_place"):
        from repro.core.runtime import fmt_bytes
        dio = report["decode_io"]
        frac = (dio["bytes_touched"] / dio["bytes_gather_equiv"]
                if dio["bytes_gather_equiv"] else 0.0)
        print(f"decode_io[in-place]: {dio['steps']} steps read "
              f"{fmt_bytes(dio['bytes_touched'])} of KV "
              f"({frac:.1%} of the {fmt_bytes(dio['bytes_gather_equiv'])} "
              f"a full gather touches), "
              f"{dio['compressed_resident']} pages compressed-resident "
              f"({dio['compressed_adopts']} adoptions)")
    if report.get("prefix", {}).get("enabled"):
        pf = report["prefix"]
        print(f"prefix: {pf['hits']} page hits, {pf['forks']} forks, "
              f"{pf['rows_reused']}/{pf['rows_prompted']} prompt rows "
              f"reused (hit rate {pf['hit_rate']:.1%})")
    if quota is not None:
        print("tenants:", {t: u for t, u in eng.quota_report().items()})
    sched_obj = eng.decode.scheduler if args.role == "both" else eng.scheduler
    if hasattr(sched_obj, "miss_report"):
        print("deadlines:", sched_obj.miss_report())


def _run_decode_worker(model, params, args) -> None:
    """``--role decode --connect HOST:PORT``: the remote decode half."""
    from repro.core.runtime import fmt_bytes
    from repro.serve.transport import (ShmChannel, run_decode_worker,
                                       tcp_connect, tcp_connect_striped)

    host, _, port = args.connect.rpartition(":")
    host = host or "127.0.0.1"
    if args.wire_streams > 1:
        channel = tcp_connect_striped(host, int(port), args.wire_streams,
                                      bufsize=args.wire_bufsize)
    else:
        channel = tcp_connect(host, int(port), bufsize=args.wire_bufsize)
        if args.transport == "shm":
            channel = ShmChannel(channel)
    print(f"decode worker: connected to {args.connect} "
          f"({args.wire_streams} stream(s)"
          f"{', shm' if args.transport == 'shm' else ''})", flush=True)
    eng = run_decode_worker(model, params, channel, batch=args.batch,
                            max_len=args.max_len, page_size=args.page_size,
                            pages=args.pages, scheduler=args.scheduler,
                            spill=args.spill,
                            temperature=args.temperature)
    rep = eng.transfer.traffic_report()
    tq = rep["transfer"]
    wire = rep.get("kv_wire", {"wire_bytes": 0.0, "calls": 0})
    print(f"decode worker done: adopted {tq['adopted_pages']} pages "
          f"({tq['published']} handoffs), sent "
          f"{fmt_bytes(wire['wire_bytes'])} of result/ack frames")


def _run_router(model, params, cfg, quota, args) -> None:
    """``--router``: the cluster front-end over N engine pairs."""
    from repro.serve.quota import QuotaManager
    from repro.serve.router import FederatedRouter, Router, replay_trace
    from repro.serve.transport import (ShmChannel, build_wire_pair,
                                       build_wire_prefill, tcp_accept,
                                       tcp_accept_striped, tcp_connect,
                                       tcp_listen)

    shared = quota if isinstance(quota, QuotaManager) else \
        (QuotaManager(dict(quota)) if quota else None)
    pair_kw = dict(batch=args.batch, max_len=args.max_len,
                   page_size=args.page_size, pages=args.pages,
                   scheduler=args.scheduler, spill=args.spill,
                   quota=shared, temperature=args.temperature)
    pairs = []
    for i in range(args.engines):
        if i == 0 and args.listen is not None:
            listener, port = tcp_listen(port=args.listen,
                                        backlog=args.wire_streams)
            # port stays the last token: the two-process smokes (CI and
            # tests/test_router.py) scrape it off this line
            print(f"router: engine 0 [{args.wire_streams} stream(s)] "
                  f"listening on {port}", flush=True)
            if args.wire_streams > 1:
                channel = tcp_accept_striped(listener, args.wire_streams,
                                             bufsize=args.wire_bufsize)
            else:
                channel = tcp_accept(listener, bufsize=args.wire_bufsize)
                if args.transport == "shm":
                    channel = ShmChannel(channel)
            print("router: decode worker attached", flush=True)
            pairs.append(build_wire_prefill(
                model, params, channel, max_len=args.max_len,
                page_size=args.page_size, scheduler=args.scheduler,
                quota=shared, window_hint=2 * (args.batch or 4),
                temperature=args.temperature, seed=0))
        elif i == 0 and args.transport is not None:
            pairs.append(build_wire_pair(model, params,
                                         transport=args.transport,
                                         streams=args.wire_streams,
                                         seed=0, **pair_kw))
        else:
            pairs.append(build_disagg(model, params,
                                      transfer=args.transfer_tier,
                                      max_depth=args.transfer_depth,
                                      seed=2 * i, **pair_kw))
    router = Router(pairs, placement=args.placement)
    fed = None
    if args.peer is not None or args.fed_listen is not None:
        fed = FederatedRouter(router)
        if args.fed_listen is not None:
            fed_listener, fed_port = tcp_listen(port=args.fed_listen)
            print(f"federation: listening on {fed_port}", flush=True)
            fed.add_peer("peer", tcp_accept(fed_listener,
                                            bufsize=args.wire_bufsize))
        else:
            host, _, port = args.peer.rpartition(":")
            fed.add_peer("peer", tcp_connect(host or "127.0.0.1", int(port),
                                             bufsize=args.wire_bufsize))
        print(f"federation: peered ({fed.describe()})", flush=True)
    print(router.describe())

    t0 = time.perf_counter()
    first_tok_s = {}

    def on_token(sess, tok):
        first_tok_s.setdefault(sess.uid, time.perf_counter() - t0)

    driver = fed if fed is not None else router
    if args.trace:
        from repro.sim.workloads import TrafficSpec, generate_traffic
        trace = generate_traffic(TrafficSpec(sessions=args.trace,
                                             horizon_s=3600.0))
        done = replay_trace(router, trace, cfg.vocab_size,
                            arrivals_per_step=2.0,
                            on_step=_drain_hook(args, router))
    else:
        rng = np.random.default_rng(0)
        sessions = []
        for i in range(args.requests):
            sessions.append(driver.submit(Request(
                uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=(args.prompt_len,)
                                    ).astype(np.int32),
                max_new_tokens=args.new_tokens + i * args.stagger,
                tenant=f"t{i % max(1, args.tenants)}"), on_token=on_token))
        done = driver.run(on_step=_drain_hook(args, router))
    dt = time.perf_counter() - t0

    total_new = sum(len(r.out_tokens) for r in done)
    dropped = sum(1 for s in router.sessions.values() if not s.done)
    print(f"router served {len(done)}/{len(router.sessions)} sessions, "
          f"{total_new} tokens in {dt:.2f}s ({total_new / dt:.1f} tok/s), "
          f"{dropped} dropped, {router.requeues} requeued")
    by_engine = {}
    for _, idx in router.placement_log:
        by_engine[idx] = by_engine.get(idx, 0) + 1
    print(f"placement[{args.placement}]: {by_engine}; "
          f"ttft(steps): {router.ttft_report()}")
    if first_tok_s:
        vals = sorted(first_tok_s.values())
        print(f"ttft(wall): mean {1e3 * sum(vals) / len(vals):.1f}ms, "
              f"max {1e3 * vals[-1]:.1f}ms")
    if any(s.request.deadline is not None
           for s in router.sessions.values()):
        print("slo:", router.slo_report())
    if fed is not None:
        print(fed.describe())
        fed.close()
    for eng in router.engines:
        print(" ", eng.describe())
        if hasattr(eng.pair, "close"):      # wire prefill: BYE the worker
            eng.pair.close()
    if shared is not None:
        print("tenants:", dict(shared.usage()))
    assert dropped == 0, f"{dropped} sessions dropped"


def _drain_hook(args, router):
    state = {"done": False}

    def hook(_driver) -> None:
        if (args.drain_after is not None and not state["done"]
                and router.now >= args.drain_after):
            state["done"] = True
            router.drain(args.drain_engine)
            print(f"drained engine {args.drain_engine} "
                  f"at step {router.now}", flush=True)

    return hook


if __name__ == "__main__":
    main()
