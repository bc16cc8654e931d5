"""Bring-up smoke test on a TPU: train and serve smollm-135m at its full
published width through the repo's own entry points, in one process.

    python chip_smoke.py              # one chip: device, train, serve
    python chip_smoke.py --chips 4    # four chips: the pooled-HBM phase only

Phases, each printing one line before the final JSON line:

* device — platform, device kind and count; anything but a TPU exits
  non-zero with no result.
* train  — ``build_model`` + ``train/loop.train`` (launch/train.py's path)
  under ``--policy none`` and ``--policy host`` from one seed: finite
  losses that agree step by step, stash bytes metered, and a stashed
  payload that lives in ``pinned_host`` memory.
* serve  — ``Engine`` over an overcommitted paged cache (page 16, host
  spill, int8 page codec, fair scheduler), once with the compiled Pallas
  paged-decode kernel and once with the gather path: equal stash and
  fetch bytes, evictions and refetches, every session finished, and the
  two paths' logits in agreement.
* ``--chips 4`` — a 2x2 (data, model) mesh under ``--policy mcdla`` and
  ``--policy none``: agreeing losses, metered stash bytes, payloads spread
  over all four devices, pool collectives in the compiled step, and each
  device's peak memory.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.  Weights and data come from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "smollm-135m"
# bf16 model and gradients: the wrapped layer's backward is recomputed
# from the fetched input and XLA fuses it differently from the plain
# backward, so updates differ by bf16 rounding and the losses drift apart
# by far less than this relative bound over a few steps.  The host
# transfer itself is lossless.
LOSS_RTOL = 1e-2
# the paged kernel attends in f32 with an online softmax over pages and
# the gather path in XLA's order; both cast to the bf16 pool dtype, so
# 30 layers of bf16 rounding bound the logit gap, relative to the logits'
# own magnitude (greedy tokens can still flip at near-ties)
LOGIT_RTOL = 1e-1


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
def train_run(cfg, mesh, plan, policy: str, batch: int, seq: int,
              steps: int, seed: int):
    """launch/train.py's path: build_model + train/loop.train."""
    from repro.configs import MemoryPlan, RunConfig, TrainConfig
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    from repro.train.loop import train

    losses, stamps = [], []

    def on_log(step, m):
        losses.append(m["loss"])
        stamps.append(time.perf_counter())

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tc = TrainConfig(total_steps=steps, warmup_steps=1,
                         learning_rate=3e-3, seed=seed, log_every=1,
                         checkpoint_every=10 * steps,
                         checkpoint_dir=ckpt_dir)
        run = RunConfig(model=cfg, shape=ShapeConfig("train", seq, batch,
                                                     "train"),
                        mesh=plan, memory=MemoryPlan(policy=policy),
                        train=tc)
        model = build_model(run, mesh=mesh)
        source = SyntheticLM(cfg, batch=batch, seq=seq, seed=seed)
        t0 = time.perf_counter()
        train(model, tc, iter(source), hooks={"on_log": on_log})
    check(len(losses) == steps, f"{policy}: {len(losses)}/{steps} steps")
    check(all(np.isfinite(losses)), f"{policy}: non-finite loss {losses}")
    # the loop waits for each step's device work before logging it, so
    # the gaps between log calls are step times; the first includes the
    # compile
    timing = (f"{policy} first step {stamps[0] - t0:.1f}s, then "
              f"{1e3 * float(np.median(np.diff(stamps))):.1f} ms/step")
    return model, tc, losses, timing


def compare_losses(a, b, what: str) -> float:
    gap = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    check(gap <= LOSS_RTOL, f"{what}: losses differ by {gap:.3g} relative "
                            f"(bound {LOSS_RTOL}): {a} vs {b}")
    return gap


def stash_probe(model, batch: int, seq: int):
    """Stash one layer input through the model's tier, outside the step,
    so the payload can be inspected as a concrete array."""
    import jax
    import jax.numpy as jnp
    from repro.core.tiers import TransferHints
    x = jnp.ones((batch, seq, model.cfg.d_model), model.dtype)
    spec = model.runtime.residual_spec("probe")
    payload = model.runtime.tier.stash(x, TransferHints(compute_spec=spec))
    return jax.tree_util.tree_leaves(payload)[0]


def phase_train(cfg, seed: int) -> None:
    batch, seq, steps = 4, 512, 5
    _, _, base_loss, base_t = train_run(cfg, None, _one_plan(), "none",
                                        batch, seq, steps, seed)
    host, _, host_loss, host_t = train_run(cfg, None, _one_plan(), "host",
                                           batch, seq, steps, seed)
    gap = compare_losses(host_loss, base_loss, "train none vs host")
    rep = host.runtime.traffic_report()
    stash = rep.get("stash", {}).get("wire_bytes", 0.0)
    check(stash > 0, f"host policy metered no stash bytes: {rep}")
    kind = stash_probe(host, batch, seq).sharding.memory_kind
    check(kind == "pinned_host", f"host stash landed in {kind!r}")
    print(f"train: {ARCH} full width, batch {batch} x seq {seq}, {steps} "
          f"steps; none losses {_fmt(base_loss)}; host losses "
          f"{_fmt(host_loss)}; max relative gap {gap:.3g}; host stash "
          f"{stash:.0f} B in {steps} steps, payload memory_kind {kind}; "
          f"smoke timing (not a metric): {base_t}; {host_t}")


# ---------------------------------------------------------------------------
def serve_run(model, params, decode_kernel: bool, seed: int):
    """An overcommitted paged Engine; returns (engine, sessions, logits by
    (uid, token index), memory kinds of the spilled pages, seconds)."""
    import jax
    from repro.serve.engine import Engine, Request
    from repro.serve.quota import quota_from_cli
    from repro.serve.scheduler import build_scheduler

    eng = Engine(model, params, batch=4, max_len=64,
                 scheduler=build_scheduler("fair", quantum=4),
                 spill="host", page_size=16, pages=10,
                 quota=quota_from_cli(None, "int8"),
                 decode_kernel=decode_kernel)
    check(eng.cache.report["num_pages"]
          < eng.batch * eng.cache.pages_per_slot,
          "the page pool is not overcommitted")
    kinds = set()
    stash = eng.cache.spill_runtime.stash

    def stash_and_look(*a, **kw):
        payload = stash(*a, **kw)
        kinds.update(leaf.sharding.memory_kind
                     for leaf in jax.tree_util.tree_leaves(payload))
        return payload

    eng.cache.spill_runtime.stash = stash_and_look
    logits, last = {}, []
    sample = eng._sample

    def sample_and_keep(lg):
        last[:] = [np.asarray(lg, np.float32)]
        return sample(lg)

    eng._sample = sample_and_keep
    rng = np.random.default_rng(seed)
    sessions = [eng.submit(
        Request(uid=i, prompt=rng.integers(0, model.cfg.vocab_size, size=24)
                .astype(np.int32), max_new_tokens=32),
        on_token=lambda s, t: logits.__setitem__(
            (s.uid, len(s.tokens) - 1), last[0]))
        for i in range(8)]
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    return eng, sessions, logits, kinds, dt


def check_spill(eng, sessions, kinds, what: str) -> str:
    rep = eng.traffic_report()
    st = rep.get("kv_stash", {}).get("wire_bytes", 0.0)
    fe = rep.get("kv_fetch", {}).get("wire_bytes", 0.0)
    pages = rep["pages"]
    check(st > 0 and st == fe, f"{what}: stash {st} B vs fetch {fe} B")
    check(pages["evictions"] > 0 and pages["refetches"] > 0,
          f"{what}: no page left HBM and came back: {pages}")
    check(kinds == {"pinned_host"}, f"{what}: spilled pages in {kinds}")
    unfinished = [s.uid for s in sessions if s.finish_reason != "length"]
    check(not unfinished, f"{what}: sessions {unfinished} did not finish")
    return (f"stash {st:.0f} B = fetch {fe:.0f} B, {pages['evictions']} "
            f"evicted, {pages['refetches']} refetched")


def compare_logits(a, b, toks_a, toks_b):
    """Logit gap of the two decode paths over every step whose inputs are
    still identical (the greedy streams agree up to it)."""
    worst, steps, same = 0.0, 0, 0
    for uid in toks_a:
        ta, tb = toks_a[uid], toks_b[uid]
        agree = next((k for k in range(len(ta)) if ta[k] != tb[k]), len(ta))
        same += agree
        for k in range(1, min(agree + 1, len(ta))):
            la, lb = a[(uid, k)], b[(uid, k)]
            worst = max(worst, float(np.max(np.abs(la - lb))
                                     / np.max(np.abs(lb))))
            steps += 1
    return worst, steps, same


def phase_serve(cfg, seed: int) -> None:
    import jax
    from repro.configs import MemoryPlan, RunConfig, TrainConfig
    from repro.configs.base import ShapeConfig
    from repro.kernels import ops
    from repro.models.model import build_model

    check(not ops._interpret(), "Pallas kernels would run interpreted")
    check(ops._PAGED_IMPL["default"] == "pallas", "paged impl is not pallas")
    run = RunConfig(model=cfg, shape=ShapeConfig("serve", 64, 4, "decode"),
                    mesh=_one_plan(), memory=MemoryPlan(policy="none"),
                    train=TrainConfig())
    model = build_model(run)
    params = model.init(jax.random.PRNGKey(seed))
    out = {}
    for dk in (True, False):
        eng, sessions, logits, kinds, dt = serve_run(model, params, dk, seed)
        what = "kernel" if dk else "gather"
        line = check_spill(eng, sessions, kinds, what)
        if dk:
            dio = eng.traffic_report().get("decode_io", {})
            check(dio.get("in_place"), f"kernel path did not decode in "
                                       f"place: {dio}")
        ntok = sum(len(s.tokens) for s in sessions)
        out[what] = ({s.uid: list(s.tokens) for s in sessions}, logits,
                     line, ntok / dt)
    (tk, lk, line_k, rate_k), (tg, lg, line_g, rate_g) = \
        out["kernel"], out["gather"]
    worst, steps, same = compare_logits(lk, lg, tk, tg)
    check(steps >= len(tk), f"only {steps} decode steps had equal inputs")
    check(worst <= LOGIT_RTOL, f"kernel vs gather logits differ by "
                               f"{worst:.3g} relative (bound {LOGIT_RTOL})")
    total = sum(len(t) for t in tk.values())
    print(f"serve: {ARCH} full width, 8 requests x 32 new tokens, 4 slots "
          f"over 10 pages of 16 rows, host spill + int8 codec, fair "
          f"quantum 4; kernel: {line_k}; gather: {line_g}; greedy tokens "
          f"shared before the first divergence {same}/{total}; logits over "
          f"{steps} equal-input steps within {worst:.3g} relative; smoke "
          f"timing (not a metric): kernel {rate_k:.1f} tok/s, gather "
          f"{rate_g:.1f} tok/s, compiles included")


# ---------------------------------------------------------------------------
def phase_pool(cfg, seed: int) -> None:
    """Four chips: the paper's pooled HBM tier against no offload."""
    import jax
    from repro.launch.mesh import mesh_for_devices
    from repro.train.loop import jit_train_step
    from repro.train.train_state import abstract_state

    mesh, plan = mesh_for_devices()
    check(plan.shape == (2, 2), f"expected a 2x2 mesh, got {plan.shape}")
    batch, seq, steps = 8, 512, 5
    runs = {}
    for policy in ("mcdla", "none"):
        model, tc, losses, timing = train_run(cfg, mesh, plan, policy,
                                              batch, seq, steps, seed)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
                 for d in jax.devices()]
        runs[policy] = (model, tc, losses, timing, peaks)
    (mc, _, mc_loss, mc_s, mc_peak), (_, _, no_loss, no_s, no_peak) = \
        runs["mcdla"], runs["none"]
    gap = compare_losses(mc_loss, no_loss, "train none vs mcdla")
    rep = mc.runtime.traffic_report()
    stash = rep.get("stash", {}).get("wire_bytes", 0.0)
    check(stash > 0, f"mcdla metered no stash bytes: {rep}")
    payload = stash_probe(mc, batch, seq)
    spread = len(payload.sharding.device_set)
    shard = payload.addressable_shards[0].data.size
    check(spread == 4 and shard * 4 == payload.size,
          f"pooled stash not spread over 4 devices: {payload.sharding}")
    # the step as train() compiled it (same model and config: the
    # persistent compile cache serves it again)
    collectives, temps = {}, {}
    for policy in ("mcdla", "none"):
        model, tc = runs[policy][:2]
        st = abstract_state(model, tc)
        b = {k: jax.ShapeDtypeStruct((batch, seq), np.int32)
             for k in ("tokens", "labels", "positions")}
        compiled = jit_train_step(model, tc).lower(st, b).compile()
        text = compiled.as_text()
        collectives[policy] = sum(text.count(op) for op in
                                  ("all-gather", "all-to-all",
                                   "collective-permute", "reduce-scatter"))
        temps[policy] = compiled.memory_analysis().temp_size_in_bytes
    check(collectives["mcdla"] > collectives["none"],
          f"no pool collectives in the mcdla step: {collectives}")
    print(f"pool: {ARCH} full width on a 2x2 (data, model) mesh, batch "
          f"{batch} x seq {seq}, {steps} steps; none losses "
          f"{_fmt(no_loss)}; mcdla losses {_fmt(mc_loss)}; max relative gap "
          f"{gap:.3g}; mcdla stash {stash:.0f} B in {steps} steps, payload "
          f"on {spread} devices at 1/{payload.size // shard} each; "
          f"gather/scatter/permute ops in the step: {collectives}; step "
          f"temporaries per device (compiled): {temps}; peak_bytes_in_use "
          f"per device (process high-water mark) after mcdla {mc_peak}, "
          f"after none {no_peak}; smoke timing (not a metric): {mc_s}; "
          f"{no_s}")


# ---------------------------------------------------------------------------
def _one_plan():
    from repro.configs.base import MeshPlan
    return MeshPlan((1,), ("data",))


def _fmt(xs) -> str:
    return "[" + ", ".join(f"{x:.4f}" for x in xs) + "]"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    logging.basicConfig(level=logging.WARNING)
    import jax
    from repro.configs import get_arch
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU: this smoke test measures nothing elsewhere",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    cfg = get_arch(ARCH)
    try:
        if args.chips == 4:
            phase_pool(cfg, args.seed)
        else:
            phase_train(cfg, args.seed)
            phase_serve(cfg, args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
