"""``MemoryRuntime`` — the single facade over the memory-tier machinery.

The paper's runtime (§III-B) is one object: it knows the mesh, the backing
store, and the stash/prefetch schedule, and the model simply runs layers.
This module is that object for the repro.  Built once from
``(MeshPlan, MemoryPlan)``, it owns the sharding planner, the mesh handle
and the :class:`~repro.core.tiers.MemoryTier` stack, and exposes the one
``wrap_layer`` entry point the rest of the codebase uses:

  forward:  y = layer(params, x)            (compute uses the exact x)
            payload = tier.stash(x)         (copy-out to the backing store)
  residual: (params, payload, aux)          (x itself is NOT saved)
  backward: x' = tier.fetch(payload)        (prefetch ahead of use)
            recompute layer vjp from x'

Under ``jax.lax.scan`` over layers, XLA's latency-hiding scheduler overlaps
the stash collective of layer *i* with the compute of layer *i+1* — the TPU
analogue of the paper's DMA/compute overlap.  Cheap intermediates are
recomputed in backward (footnote 4) because the vjp re-runs the layer body.

Every stash/fetch is metered in bytes per direction.  Inside the train
step that ``train/loop.jit_train_step`` builds, what tracing the step meters
is one step's traffic (``per_step``, a scanned layer counted once per trip
through :meth:`repeat`), added to the totals each time the step runs;
eager callers add as they run.  :meth:`traffic_report` gives both, and
``launch/dryrun.py`` surfaces the per-step figure next to XLA's
``memory_analysis()`` numbers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import hw
from repro.configs.base import MemoryPlan, MeshPlan
from repro.core import policy as policy_mod
from repro.core.dag import LayerDAG, build_dag
from repro.core.tiers import MemoryTier, TransferHints, build_tier
from repro.parallel.sharding import ShardingPlanner

log = logging.getLogger(__name__)

# big float aux (e.g. encoder states feeding cross-attention) pool too
AUX_STASH_NDIM = 3


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TierTraffic:
    """Transfer meter for one direction through the tier: calls and bytes
    moved (executed totals, or one step's record in ``per_step``)."""

    calls: int = 0
    raw_bytes: float = 0.0        # tensor bytes before compression
    wire_bytes: float = 0.0       # bytes that actually cross the interconnect

    def add(self, raw: float, wire: float, calls: int = 1) -> None:
        self.calls += calls
        self.raw_bytes += raw
        self.wire_bytes += wire


class MemoryRuntime:
    """Facade: planner + mesh + tier + per-call accounting.

    Everything the old call sites hand-threaded — ``(planner, mesh, memory,
    compute_spec, batch_dim)`` — lives here; model code asks for
    ``wrap_layer`` and nothing else.
    """

    def __init__(self, plan: MeshPlan, memory: MemoryPlan,
                 mesh: Optional[Mesh] = None,
                 planner: Optional[ShardingPlanner] = None,
                 chip: Optional[hw.Chip] = None,
                 tier: Optional[MemoryTier] = None):
        self.plan = plan
        self.memory = memory
        self.mesh = mesh
        self.chip = chip if chip is not None else hw.attached_chip()
        self.planner = planner if planner is not None else ShardingPlanner(plan)
        # ``tier`` overrides the registry resolution — used for runtimes
        # whose tier is built out-of-band (the pipeline stage runtime wraps
        # the configured backing store in a PipelineStageTier).
        self.tier: MemoryTier = tier if tier is not None \
            else build_tier(memory, self.planner, mesh)
        self._traffic: Dict[str, TierTraffic] = {}     # executed totals
        self._per_step: Dict[str, TierTraffic] = {}    # one step, as traced
        self._step_book: Optional[Dict[str, TierTraffic]] = None
        self._times = 1           # executions one traced transfer stands for
        self.step_traces = 0
        self._said_degenerate = False

    # ------------------------------------------------------------------
    # traits
    @property
    def offloads(self) -> bool:
        """Whether wrapped layers actually move their saved tensors."""
        return self.tier.offloads

    @property
    def moves_bytes(self) -> bool:
        """Whether a stash leaves this device's HBM on this runtime's mesh.

        A tier that offloads in principle but is degenerate here (pooled
        HBM over a pool of one device) says so once in the log."""
        if not self.offloads:
            return False
        if self.tier.leaves_device():
            return True
        if not self._said_degenerate:
            self._said_degenerate = True
            log.info("memory tier %s has nowhere to stash on mesh %s: "
                     "layers run unwrapped and nothing leaves HBM",
                     self.tier.describe(), "x".join(map(str, self.plan.shape)))
        return False

    def describe(self) -> str:
        return (f"runtime[tier={self.tier.describe()} "
                f"mesh={'x'.join(map(str, self.plan.shape))}]")

    # ------------------------------------------------------------------
    # layout defaults
    def residual_spec(self, name: str = "resid") -> Callable[[Sequence[int]], P]:
        """Shape-aware compute layout of the residual stream: batch axes on
        dim 0, sequence-parallel dim 1 over the tensor axes when enabled."""

        def spec(shape):
            roles: list = [self.planner.axes.batch] + [None] * (len(shape) - 1)
            if self.memory.seq_parallel and len(shape) >= 3:
                roles[1] = self.planner.axes.tensor
            return self.planner.spec(shape, roles, name=name)

        return spec

    def _aux_spec(self, compute_spec, shape) -> Optional[P]:
        """Layout for a fetched *aux* tensor.

        Aux tensors (encoder states, caches, ...) generally differ in
        rank/shape from the residual stream, so a static residual
        ``compute_spec`` must NOT be applied to them — derive a layout from
        the planner instead (shape-aware callables already do)."""
        if callable(compute_spec):
            return compute_spec(shape)
        roles = [self.planner.axes.batch] + [None] * (len(shape) - 1)
        return self.planner.spec(shape, roles, name="aux_fetch")

    # ------------------------------------------------------------------
    # accounting
    def _add(self, direction: str, raw: float, wire: float,
             calls: int = 1) -> None:
        """Meter one transfer: into the step being traced, if any (see
        :meth:`recording_step`), else into the executed totals."""
        book = self._traffic if self._step_book is None else self._step_book
        n = self._times
        book.setdefault(direction, TierTraffic()).add(raw * n, wire * n,
                                                      calls * n)

    def _meter(self, direction: str, x: jax.Array,
               hints: Optional[TransferHints] = None) -> None:
        raw = float(x.size) * jnp.dtype(x.dtype).itemsize
        self._add(direction, raw,
                  raw * self.tier.wire_ratio(x, hints or TransferHints()))

    def meter_transfer(self, direction: str, raw_bytes: float,
                       wire_bytes: float, calls: int = 1) -> None:
        """Account an out-of-band transfer in this runtime's report.

        ``stash``/``fetch`` meter tier traffic implicitly; transfers that
        bypass the tier stack — e.g. serialized wire frames in
        serve/transport.py, metered as ``kv_wire`` with the exact frame
        byte count — record themselves here so ``traffic_report()`` stays
        the single reconciliation point for every byte that moved."""
        self._add(direction, raw_bytes, wire_bytes, calls)

    @contextlib.contextmanager
    def _times_as(self, n: int):
        prev, self._times = self._times, n
        try:
            yield
        finally:
            self._times = prev

    def repeat(self, n: int):
        """Context: each transfer traced inside stands for ``n`` executed
        ones — a layer body under ``jax.lax.scan`` traces once and runs
        once per trip.  Nested repeats multiply."""
        return self._times_as(self._times * n)

    @contextlib.contextmanager
    def recording_step(self):
        """Context for the body of a jitted step, which runs only when the
        step is traced: what is metered inside becomes the step's
        ``per_step`` record (replacing the last trace's, never adding to
        it), counted by :meth:`count_step` each time the step runs."""
        outer, self._step_book = self._step_book, {}
        try:
            yield
            self._per_step = self._step_book
            self.step_traces += 1
        finally:
            self._step_book = outer

    def count_step(self) -> None:
        """One executed step: add its recorded traffic to the totals."""
        for direction, t in self._per_step.items():
            self._traffic.setdefault(direction, TierTraffic()).add(
                t.raw_bytes, t.wire_bytes, t.calls)

    def reset_traffic(self) -> None:
        self._traffic = {}

    def traffic_report(self) -> Dict[str, Any]:
        """Per-direction executed calls and bytes of every metered transfer;
        ``per_step``: the wire bytes one run of the traced step moves."""
        report: Dict[str, Any] = {"tier": self.tier.describe(),
                                  "step_traces": self.step_traces}
        total_wire = 0.0
        for direction in sorted(set(self._traffic) | set(self._per_step)):
            t = self._traffic.get(direction, TierTraffic())
            report[direction] = {
                "calls": t.calls, "raw_bytes": t.raw_bytes,
                "wire_bytes": t.wire_bytes,
                "per_step": self._per_step.get(
                    direction, TierTraffic()).wire_bytes,
            }
            total_wire += t.wire_bytes
        report["wire_bytes_total"] = total_wire
        return report

    def traffic_summary(self) -> str:
        r = self.traffic_report()
        per = {d: f"{fmt_bytes(v['wire_bytes'])}/{v['calls']}x"
               for d, v in r.items() if isinstance(v, dict)}
        return f"tier={r['tier']} wire={fmt_bytes(r['wire_bytes_total'])} {per}"

    # ------------------------------------------------------------------
    # data path (metered tier passthrough).  ``direction`` labels the
    # traffic-report bucket: training residuals use the default
    # "stash"/"fetch", the serving KVCacheManager meters its cold-slot
    # traffic as "kv_stash"/"kv_fetch" so a report tells the two apart.
    def stash(self, x: jax.Array, hints: Optional[TransferHints] = None,
              direction: str = "stash"):
        hints = hints or TransferHints()
        if self.offloads:
            self._meter(direction, x, hints)
        return self.tier.stash(x, hints)

    def fetch(self, payload, hints: Optional[TransferHints] = None,
              direction: str = "fetch"):
        hints = hints or TransferHints()
        x = self.tier.fetch(payload, hints)
        if self.offloads:
            self._meter(direction, x, hints)
        return x

    # ------------------------------------------------------------------
    # snapshots (checkpoint-as-a-tier).  Unlike stash/fetch these meter the
    # *actual* payload bytes — the manifest the CheckpointManager commits
    # accounts the same bytes, so `traffic_report["ckpt_save"]` is checkable
    # against on-disk truth for any codec stack.
    def _payload_bytes(self, payload) -> float:
        return sum(float(leaf.size) * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(payload)
                   if hasattr(leaf, "size"))

    def snapshot(self, x: jax.Array, hints: Optional[TransferHints] = None,
                 direction: str = "ckpt_save"):
        """Stash one snapshot leaf through the tier, metered ``ckpt_save``."""
        hints = hints or TransferHints()
        payload = self.tier.stash(x, hints)
        raw = float(x.size) * jnp.dtype(x.dtype).itemsize
        self._add(direction, raw, self._payload_bytes(payload))
        return payload

    def restore_snapshot(self, payload,
                         hints: Optional[TransferHints] = None,
                         direction: str = "ckpt_load") -> jax.Array:
        """Fetch one snapshot leaf back, metered ``ckpt_load``."""
        hints = hints or TransferHints()
        wire = self._payload_bytes(payload)
        x = self.tier.fetch(payload, hints)
        raw = float(x.size) * jnp.dtype(x.dtype).itemsize
        self._add(direction, raw, wire)
        return x

    def discard(self, payload) -> None:
        """Release a parked payload's capacity-contract charge.

        Serving paths (cold-KV slots, spilled pages, disaggregated KV
        handoffs) park payloads in the tier and drop them out of band; a
        :class:`~repro.core.tiers.SpillTier` leg in the stack gets its
        budget back here.  No-op for tiers without a byte ledger."""
        from repro.core.tiers import SpillTier
        tier = self.tier
        while tier is not None:
            if isinstance(tier, SpillTier):
                tier.discard(payload)
                return
            tier = getattr(tier, "inner", None)

    # ------------------------------------------------------------------
    # the one wrapper
    def wrap_layer(self, layer_fn: Callable,
                   compute_spec: Optional[object] = "auto",
                   batch_dim: int = 0,
                   name: str = "layer") -> Callable:
        """Wrap ``layer_fn(params, x, *aux) -> y`` so the saved-for-backward
        copy of ``x`` lives in this runtime's tier.

        * ``compute_spec``: the layout to restore on fetch — a static
          PartitionSpec, a shape-aware callable, None, or the default
          ``"auto"`` (the residual-stream layout for this memory plan).
        * params and small aux are saved by reference; float aux with
          ndim >= 3 are stashed too (uncompressed — they must round-trip
          bit-exactly for the cotangent path).
        """
        if not self.offloads:
            return layer_fn
        if compute_spec == "auto":
            compute_spec = self.residual_spec(name)
        tier = self.tier
        runtime = self

        def hints_for(dtype=None, allow_compress=True) -> TransferHints:
            return TransferHints(compute_spec=compute_spec,
                                 batch_dim=batch_dim, dtype=dtype,
                                 allow_compress=allow_compress, name=name)

        # the backward is traced after the forward's scan has returned: it
        # meters its fetches with the trip count the forward saw
        trips = [1]

        @jax.custom_vjp
        def f(params, x, *aux):
            return layer_fn(params, x, *aux)

        def f_fwd(params, x, *aux):
            trips[0] = runtime._times
            y = layer_fn(params, x, *aux)
            payload = runtime.stash(x, hints_for())
            witness = jnp.zeros((), x.dtype)    # dtype token (residuals must
            flags = _split_aux(aux)             # be JAX types)
            saved_aux = []
            for a, fl in zip(aux, flags):
                if (runtime.memory.stash_aux and fl
                        and getattr(a, "ndim", 0) >= AUX_STASH_NDIM):
                    saved_aux.append(runtime.stash(
                        a, hints_for(allow_compress=False)))
                else:
                    saved_aux.append(a)
            return y, (params, payload, witness, tuple(saved_aux))

        def f_bwd(res, gy):
            params, payload, witness, saved_aux = res
            with runtime._times_as(trips[0]):
                x = runtime.fetch(payload, hints_for(dtype=witness.dtype))
                aux = []
                for sa in saved_aux:
                    if isinstance(sa, tuple):
                        # aux tensors differ in rank/shape from the
                        # residual — they derive their own fetch layout
                        # (never the static residual compute_spec).  The
                        # payload's first array leaf carries the stashed
                        # shape (tier payloads may wrap it, e.g.
                        # SpillTier's leg-routing node).
                        shape = jax.tree_util.tree_leaves(sa)[0].shape
                        aux.append(runtime.fetch(sa, TransferHints(
                            compute_spec=runtime._aux_spec(compute_spec,
                                                           shape),
                            batch_dim=batch_dim, dtype=witness.dtype,
                            allow_compress=False, name=f"{name}_aux")))
                    else:
                        aux.append(sa)
            aux = tuple(aux)
            flags = _split_aux(aux)
            diff_aux = tuple(a for a, fl in zip(aux, flags) if fl)

            def call(p, xx, *da):
                it = iter(da)
                full = tuple(next(it) if fl else a
                             for a, fl in zip(aux, flags))
                return layer_fn(p, xx, *full)

            with jax.named_scope("tier.recompute"):
                _, vjp = jax.vjp(call, params, x, *diff_aux)
            grads = vjp(gy)
            dp, dx, d_diff = grads[0], grads[1], list(grads[2:])
            if compute_spec is not None:
                # constrain the residual-stream cotangent to the same layout
                # as the primal: GSPMD can then turn the TP backward
                # all-reduces into reduce-scatters (Megatron-SP; §Perf)
                spec = compute_spec(dx.shape) if callable(compute_spec) \
                    else compute_spec
                dx = tier._constrain(dx, spec)
            d_aux = tuple(d_diff.pop(0) if fl else None for fl in flags)
            return (dp, dx) + d_aux

        f.defvjp(f_fwd, f_bwd)
        return f

    # ------------------------------------------------------------------
    # pipeline stages: whole stage-input pytrees through the stage tier
    def wrap_stage(self, stage_fn: Callable, name: str = "stage") -> Callable:
        """Wrap ``stage_fn(params, tree) -> tree`` so every float leaf of
        the input tree is saved-for-backward through this runtime's tier.

        The pipeline-schedule analogue of :meth:`wrap_layer`: a 1F1B stage
        stashes its microbatch input when it runs the forward and fetches
        it right before the backward, metered as ``act_stash`` /
        ``act_fetch`` so :meth:`traffic_report` covers training pipelines.
        The stage body is recomputed from the fetched input (same
        footnote-4 behaviour as the layer wrapper)."""
        if not self.offloads:
            return stage_fn
        runtime = self

        def hints_for(dtype=None) -> TransferHints:
            return TransferHints(compute_spec=None, dtype=dtype, name=name)

        def is_float(leaf) -> bool:
            return (isinstance(leaf, (jax.Array, jnp.ndarray)) and
                    jnp.issubdtype(jnp.result_type(leaf), jnp.inexact))

        @jax.custom_vjp
        def f(params, tree):
            return stage_fn(params, tree)

        def f_fwd(params, tree):
            y = stage_fn(params, tree)
            saved = jax.tree.map(
                lambda leaf: StashedLeaf(
                    runtime.stash(leaf, hints_for(), direction="act_stash"),
                    jnp.zeros((), leaf.dtype)) if is_float(leaf) else leaf,
                tree)
            return y, (params, saved)

        def f_bwd(res, gy):
            params, saved = res
            tree = jax.tree.map(
                lambda leaf: runtime.fetch(
                    leaf.payload, hints_for(dtype=leaf.witness.dtype),
                    direction="act_fetch")
                if isinstance(leaf, StashedLeaf) else leaf,
                saved, is_leaf=lambda l: isinstance(l, StashedLeaf))
            with jax.named_scope("tier.recompute"):
                _, vjp = jax.vjp(stage_fn, params, tree)
            return vjp(gy)

        f.defvjp(f_fwd, f_bwd)
        return f

    # ------------------------------------------------------------------
    # planning (KEEP/POOL/RECOMPUTE through the tier cost contract)
    def plan_report(self, dag: LayerDAG,
                    model_state_bytes: float = 0.0,
                    pipeline=None, n_micro_candidates=None,
                    checkpoint=None, ckpt_tier=None):
        return policy_mod.plan_memory(dag, self.plan, self.memory,
                                      chip=self.chip,
                                      model_state_bytes=model_state_bytes,
                                      tier=self.tier, pipeline=pipeline,
                                      n_micro_candidates=n_micro_candidates,
                                      checkpoint=checkpoint,
                                      ckpt_tier=ckpt_tier)

    def stash_fraction(self, dag: LayerDAG,
                       model_state_bytes: float = 0.0) -> float:
        """Fraction of layers this runtime stashes: 0 when the tier keeps
        everything resident, 1 for stash-all tiers, cost-model-derived
        otherwise."""
        if not self.offloads:
            return 0.0
        if self.tier.stash_all:
            return 1.0
        report = self.plan_report(dag, model_state_bytes=model_state_bytes)
        pooled = report.count("pool") + report.count("recompute")
        return pooled / max(len(report.decisions), 1)

    def resolve_stash_groups(self, cfg, shape, n_groups: int) -> int:
        """Number of scanned layer groups to stash (largest reuse distance
        first, matching the planner's eviction order)."""
        if not self.offloads:
            return 0
        if self.tier.stash_all:
            return n_groups
        dag = build_dag(cfg, shape)
        opt_bytes = 2 + (8 if self.memory.opt_state_bits == 32 else 2) + 4
        frac = self.stash_fraction(
            dag, model_state_bytes=cfg.param_count() * opt_bytes)
        k = int(round(n_groups * frac))
        return max(0, min(n_groups, k))


# ---------------------------------------------------------------------------
class StashedLeaf:
    """Residual marker for one stage-tier-stashed tensor: the tier payload
    plus a zero-size dtype witness (residuals must be JAX types).  A pytree
    node, so custom_vjp residual trees carry the stashed/raw distinction
    structurally."""

    __slots__ = ("payload", "witness")

    def __init__(self, payload, witness):
        self.payload = payload
        self.witness = witness


jax.tree_util.register_pytree_node(
    StashedLeaf,
    lambda s: ((s.payload, s.witness), None),
    lambda _, children: StashedLeaf(*children))


# ---------------------------------------------------------------------------
def fmt_bytes(n: float) -> str:
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= div:
            return f"{n/div:.2f}{unit}"
    return f"{n:.0f}B"


# ---------------------------------------------------------------------------
def _split_aux(aux: Sequence[Any]):
    """Partition aux leaves into differentiable / non-differentiable."""
    return tuple(
        isinstance(a, (jax.Array, jnp.ndarray)) and
        jnp.issubdtype(jnp.result_type(a), jnp.inexact)
        for a in aux)
