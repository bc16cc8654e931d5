"""A training cell: ``repro.train.loop``'s jitted step at the cell's batch,
sequence and memory policy, fed from the seed.

Set-up builds the model, the step and its state on the device (one jitted
call from the seed), and drives that same step through the first
``check_steps`` steps on the window's own feed, reading what ``correct``
compares: each step's loss, the first clipped gradient's norm per leaf
(from the Adam moment after one step) and each leaf's change after the
last of them.  The window then keeps driving the same step object until
the deadline.  After it, with the program's state freed, the plain
reference repeats those steps from the seed and the readings are compared.
"""
from __future__ import annotations

import contextlib
import gc
import statistics
import sys
from typing import Any, Dict

import numpy as np

from harness import common, flops, spec, traffic


def build(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
    import jax
    from repro.configs.base import (MemoryPlan, MeshPlan, RunConfig,
                                    ShapeConfig, TrainConfig)
    from repro.models.model import build_model
    from repro.train import loop
    from repro.train.train_state import init_state

    hp = mix["optimizer"]
    tc = TrainConfig(learning_rate=hp["learning_rate"],
                     warmup_steps=hp["warmup_steps"],
                     total_steps=hp["total_steps"],
                     weight_decay=hp["weight_decay"], beta1=hp["beta1"],
                     beta2=hp["beta2"], eps=hp["eps"],
                     grad_clip=hp["grad_clip"])
    mcfg = common.model_config(cfg)
    run = RunConfig(model=mcfg, shape=ShapeConfig("train", mix["seq"],
                                                  mix["batch"], "train"),
                    mesh=MeshPlan((1,), ("data",)),
                    memory=MemoryPlan(policy=mix["policy"]), train=tc)
    model = build_model(run)
    step = loop.jit_train_step(model, tc)
    key = jax.random.PRNGKey(traffic.key_seed(seed))
    state = jax.jit(lambda k: init_state(model, tc, k))(key)
    return model, tc, step, state


class Feed:
    """The window's feed: batch t of the mix, moved to the device."""

    def __init__(self, seed: int, mix: Dict[str, Any], vocab: int):
        self.seed, self.mix, self.vocab = seed, mix, vocab
        self.t = 0

    def host_batch(self, t: int) -> Dict[str, np.ndarray]:
        return traffic.train_batch(self.seed, t, self.mix["batch"],
                                   self.mix["seq"], self.vocab)

    def __next__(self):
        import jax
        b = jax.device_put(self.host_batch(self.t))
        self.t += 1
        return b


def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    def norms(tree, scale=1.0):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            * scale, tree)

    return jax.jit(norms), jax.jit(lambda a, b: norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)))


def _by_path(tree) -> Dict[str, float]:
    import jax
    return {jax.tree_util.keystr(p): float(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        devices, limits: Dict[str, float], control: bool = False
        ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    hp = mix["optimizer"]
    n_check = int(mix.get("check_steps", 3))
    counter = common.CompileCounter()

    t0 = common.now()
    model, tc, step, state = build(cfg, mix, seed)
    feed = Feed(seed, mix, cfg["vocab_size"])
    norms, diff_norms = _leaf_norms_fn()
    p0 = jax.jit(lambda p: jax.tree.map(jnp.copy, p))(state["params"])
    prog: Dict[str, Any] = {"losses": []}
    for t in range(1, n_check + 1):
        state, m = step(state, next(feed))
        prog["losses"].append(float(m["loss"]))
        if t == 1:
            # m_1 = (1 - beta1) * clip * g: the gradient the optimizer got
            prog["grad_norms"] = _by_path(norms(state["opt"]["m"],
                                                1.0 / (1 - hp["beta1"])))
    prog["update_norms"] = _by_path(diff_norms(state["params"], p0))
    del p0
    jax.block_until_ready(state)
    setup_s = common.now() - t0

    # -------------------------------------------------------------- window
    tokens_per_step = mix["batch"] * mix["seq"]
    prof = common.Profiler() if trace else None
    counter.on = True
    steps = traced = 0
    start = common.now()
    deadline = start + seconds
    end = start
    while end < deadline:
        tracing = prof is not None and steps == 1
        ctx = prof.window() if tracing else contextlib.nullcontext()
        with ctx:
            n = int(mix.get("traced_steps", 3)) if tracing else 1
            for _ in range(n):
                with common.span("bench.feed"):
                    batch = next(feed)
                with common.span("bench.step"):
                    state, m = step(state, batch)
                with common.span("bench.wait"):
                    jax.block_until_ready(m)
                steps += 1
                traced += tracing
        end = common.now()
    counter.on = False
    window_s = end - start
    peak = common.memory_peak_bytes(devices)
    tier = model.runtime.traffic_report() if model.runtime.moves_bytes \
        else {}
    del state, m, batch
    gc.collect()

    # ----------------------------------------------------------- reference
    ref_mod = spec.reference(cfg)
    t_ref = common.now()
    key = jax.random.PRNGKey(traffic.key_seed(seed))
    batches = [feed.host_batch(t) for t in range(n_check)]
    ref = ref_mod.train_readings(cfg, key, batches, hp)
    checks = compare(prog, ref, limits)
    print(f"reference: {common.now() - t_ref:.1f} s for {n_check} steps",
          file=sys.stderr)
    extra = {}
    if control:
        # the readings that set the upper end of each limit: the reference
        # in fp8 in the program's place, and a planted fault (half of each
        # batch left out, the mean taken over the rest)
        for name, kw in (("control_fp8", {"precision": "fp8"}),
                         ("fault_half_batch", {"keep_rows": "half"})):
            other = ref_mod.train_readings(cfg, key, batches, hp, **kw)
            extra[name] = readings(other, ref)

    res: Dict[str, Any] = {
        "e2e": {"setup_s": setup_s,
                "train_tokens_per_s": steps * tokens_per_step / window_s},
        "attempted": steps, "failed": 0,
        "memory_peak_bytes": peak, "checks": checks,
        "log": {"window_s": window_s, "steps": steps,
                "traces_in_window": counter.traces,
                "compiles_in_window": counter.compiles,
                "program_readings": readings(prog, ref),
                "program_losses": prog["losses"],
                "reference_losses": ref["losses"], **extra,
                "tier_traffic": {k: v for k, v in tier.items()
                                 if isinstance(v, dict)}},
    }
    if prof is not None:
        tr = prof.result
        res["traced"] = tr
        res["counters"] = {
            "kind": "train", "chips": len(devices),
            "model_flops": traced * flops.train_step_flops(
                cfg, mix["batch"], mix["seq"]),
            "steps": traced, "policy": mix["policy"],
            "tier_bytes_per_step": sum(
                v.get("wire_bytes", 0.0) for k, v in tier.items()
                if isinstance(v, dict) and k in ("stash", "fetch"))
            * cfg["num_hidden_layers"]}
    return res


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers ``correct`` compares, each beside its limit.

    * ``grad_norm_gap``: by the worst leaf, the gap between the program's
      and the reference's norm of the first clipped gradient, over the
      larger of that leaf's reference norm and the median leaf's;
    * ``update_norm_gap``: the same for each leaf's change after the
      checked steps, over the leaves whose reference gradient is not
      nought to rounding (at least a thousandth of the median leaf's).

    ``loss_gap`` (the largest relative gap of a step's loss) is read and
    logged but has no limit: neither the fp8 control nor a planted fault
    moves it past what sound runs read (see PERF.md).
    """
    def worst(p: Dict[str, float], r: Dict[str, float], keys) -> float:
        med = statistics.median(r[k] for k in keys)
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    raw = ref["raw_grad_norms"]
    med_g = statistics.median(raw.values())
    moved = [k for k in raw if raw[k] >= 1e-3 * med_g]
    vals = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                            zip(prog["losses"], ref["losses"])),
            "grad_norm_gap": worst(prog["grad_norms"], ref["grad_norms"],
                                   list(raw)),
            "update_norm_gap": worst(prog["update_norms"],
                                     ref["update_norms"], moved)}
    return {k: {"value": v, "limit": limits[k]} for k, v in vals.items()
            if k in limits}


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """Every number compare() can read, whether or not it has a limit."""
    every = {k: float("inf") for k in ("loss_gap", "grad_norm_gap",
                                       "update_norm_gap")}
    return {k: v["value"] for k, v in compare(prog, ref, every).items()}
