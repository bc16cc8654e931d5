"""KVCacheManager: decode-slot / page allocation over the memory tiers.

One of the three serving APIs behind the ``Engine`` facade (DESIGN.md §6).
The manager owns the KV cache storage and everything about where a
session's cache lives:

* **sizing** — when the caller leaves ``batch``/``max_len`` unspecified,
  :func:`~repro.serve.kv_cache.derive_cache_shape` sizes them from the
  serving tier's ``cache_tier_report`` (the paper's capacity contract
  answering "how much cache can one device address?").
* **slot lifecycle** — allocate / bind / release of the fixed decode slots
  (the hot, HBM-resident working set).
* **spill** — a paused (preempted / waiting) session's KV leaves HBM
  through a secondary :class:`~repro.core.runtime.MemoryRuntime` whose
  tier defaults to ``spill`` (pooled HBM overflowing to host DRAM — the
  Buddy-Compression cold-page pattern, arXiv:1903.02596) and is fetched
  back on resume.  Every leg is metered: the runtime's
  ``traffic_report()`` shows ``kv_stash``/``kv_fetch`` byte counts.

Two storage models share that contract:

* :class:`KVCacheManager` — the monolithic slot: one contiguous
  ``max_len``-row region per session, spilled/fetched whole.
* :class:`PagedKVCacheManager` — the paper's pooled-memory model applied
  to serving: KV lives in a pool of fixed-size **pages**
  (``models/transformer.paged_pool``/``gather_pages``), a session holds a
  page list (:class:`~repro.serve.paging.PageTable`), pausing merely marks
  pages *cold*, and spill happens **lazily per page** — through the spill
  tier with a per-tenant codec from the ``core/compress.py`` registry —
  only when an allocation actually needs the frame.  A session resumed
  before its pages were reclaimed re-binds with zero copies.

Per-slot cache surgery uses the models/transformer helpers
(:func:`~repro.models.transformer.slot_cache` /
:func:`~repro.models.transformer.merge_slot_cache`), jitted once here.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.configs.base import MemoryPlan
from repro.core.compress import (Codec, decode_tensor, encode_tensor,
                                 get_codec)
from repro.core.runtime import MemoryRuntime, fmt_bytes
from repro.core.tiers import TransferHints
from repro.models import transformer as tfm
from repro.serve.kv_cache import (DEFAULT_HBM_FRAC, DEFAULT_MAX_BATCH,
                                  DEFAULT_MAX_LEN, derive_cache_shape)
from repro.serve.paging import PageError, PageTable, SharedPayload
from repro.serve.session import Session, SessionState

log = logging.getLogger(__name__)


@dataclasses.dataclass
class PrefixMatch:
    """Result of matching a new prompt against the prefix index.

    ``pids`` are fully-matched pages the admission binds **read-only**
    (refcount bump, no copy, no prefill compute for their rows);
    ``fork_pid`` is the donor frame whose first ``rows - len(pids) *
    page_size`` rows match — it is **copied** into a private frame before
    the prefill scatter (copy-on-write fork at the first divergent
    token).  ``rows`` is the total prompt rows covered; the suffix
    prefill starts there."""

    pids: List[int]
    fork_pid: Optional[int]
    rows: int

    @property
    def shared_pages(self) -> int:
        """Pages bound read-only — the quota charge excludes these."""
        return len(self.pids)

    @property
    def write_from(self) -> int:
        """First page column the prefill scatter may write (the forked
        page is private and writable; the shared ones route to scratch)."""
        return len(self.pids)


@dataclasses.dataclass
class _SpilledSlot:
    """One paused session's slot-shaped cache, parked in the secondary tier."""

    session: Session                  # owner (for cancelled-entry sweeps)
    treedef: Any                      # cache tree structure
    payloads: List[Any]               # one tier payload per cache leaf
    dtypes: List[Any]                 # restore dtypes on fetch


@dataclasses.dataclass
class _SpilledPage:
    """One evicted page, parked in the secondary tier (paged manager)."""

    treedef: Any                      # page tree structure
    items: List[Tuple[Any, Any, Any]]  # (tier payload, codec scale, dtype)
    codec: Optional[str]              # codec name ('' semantics: None=raw)


class KVCacheManager:
    """Slot allocation + tier placement for the serving KV cache."""

    #: storage model marker (the Engine branches its jitted paths on this)
    paged: bool = False
    #: page size in cache rows (None: monolithic slots)
    page_size: Optional[int] = None

    def __init__(self, model, batch: Optional[int] = None,
                 max_len: Optional[int] = None, *,
                 spill: Union[str, MemoryRuntime, None] = "spill",
                 hbm_frac: float = DEFAULT_HBM_FRAC,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 default_max_len: int = DEFAULT_MAX_LEN,
                 dtype_bytes: int = 2):
        self.model = model
        sized = derive_cache_shape(
            model.cfg, model.runtime, batch, max_len,
            page_size=self.page_size, hbm_frac=hbm_frac,
            max_batch=max_batch, default_max_len=default_max_len,
            dtype_bytes=dtype_bytes)
        self.batch: int = sized["batch"]
        self.max_len: int = sized["max_len"]
        self.report: Dict[str, Any] = sized["report"]
        self.auto_sized = not batch or not max_len

        self.slots: List[Optional[Session]] = [None] * self.batch
        self._spilled: Dict[int, _SpilledSlot] = {}
        self._init_storage()

        # secondary tier for cold slots/pages (None: preemption unsupported)
        if isinstance(spill, MemoryRuntime):
            self.spill_runtime: Optional[MemoryRuntime] = spill
        elif spill is None:
            self.spill_runtime = None
        else:
            self.spill_runtime = MemoryRuntime(
                model.plan,
                MemoryPlan(policy=spill, placement=model.memory.placement),
                model.mesh, planner=model.planner)

        self._slot_get = jax.jit(tfm.slot_cache)
        self._slot_put = jax.jit(tfm.merge_slot_cache)
        log.info("kv cache [%s]: batch=%d max_len=%d (%s/device, fits=%s)%s%s",
                 self.report["tier"], self.batch, self.max_len,
                 fmt_bytes(self.report["per_device_bytes"]),
                 self.report["fits"],
                 " [auto-sized]" if self.auto_sized else "",
                 f" [pages={self.report['num_pages']}"
                 f"x{self.page_size}]" if self.paged else "")

    def _init_storage(self) -> None:
        self.caches = self.model.init_cache(self.batch, self.max_len)

    # ------------------------------------------------------------------
    # slot lifecycle
    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def num_free(self) -> int:
        return sum(1 for s in self.slots if s is None)

    def running(self) -> List[Session]:
        return [s for s in self.slots if s is not None]

    def fits_prompt(self, prompt_len: int) -> bool:
        """A prompt must leave at least one cache row for decode writes."""
        return prompt_len < self.max_len

    def session_pages(self, prompt_len: int, max_new: int) -> int:
        """Worst-case page reservation for one session (0: unpaged — page
        budgets only bind in paged mode)."""
        return 0

    def match_prefix(self, prompt) -> Optional[PrefixMatch]:
        """Hook: look the prompt up in the prefix index (paged manager
        with ``prefix_share=True`` only).  Read-only — admission calls it
        before the quota check so shared pages are not charged."""
        return None

    def note_prefilled(self, sess: Session, prompt,
                       match: Optional[PrefixMatch] = None) -> None:
        """Hook: an admission finished its prefill — register its full
        prompt pages in the prefix index (paged manager only)."""

    def prepare_slot(self, slot: int, sess: Session, rows: int,
                     match: Optional[PrefixMatch] = None) -> None:
        """Hook: back ``rows`` cache rows for a fresh admission (paged:
        allocate the prompt's pages before the prefill gather, binding
        ``match``'s shared pages read-only first)."""

    def abort_prepare(self, sess: Session) -> None:
        """Hook: undo a failed :meth:`prepare_slot` (paged: return the
        partially-allocated pages — a deferred queued session must not
        pin hot, unevictable pages while it waits)."""

    def ensure_rows(self, sess: Session, rows: int) -> None:
        """Hook: grow a resident session to ``rows`` cache rows (paged:
        demand page allocation, evicting cold pages as needed)."""

    def bind(self, slot: int, sess: Session, length: int) -> None:
        assert self.slots[slot] is None, (slot, self.slots[slot])
        self.slots[slot] = sess
        sess.slot = slot
        sess.length = length
        sess.state = SessionState.RUNNING
        sess.steps_since_admit = 0

    def release(self, sess: Session) -> None:
        """Retire a session's slot (its cache rows are dead)."""
        if sess.slot is not None:
            self.slots[sess.slot] = None
            sess.slot = None
        self.drop_spilled(sess)

    @property
    def can_preempt(self) -> bool:
        return self.spill_runtime is not None

    # ------------------------------------------------------------------
    # disaggregated handoff (prefill role: ship a finished prompt's KV)
    def export_slot(self, sess: Session):
        """Copy one resident session's single-slot cache tree out of the
        batched storage — the prefill-role handoff unit, chunked into
        page-shaped trees by :func:`repro.models.transformer.slot_pages`."""
        assert sess.slot is not None, sess
        return self._slot_get(self.caches, sess.slot)

    # ------------------------------------------------------------------
    # spill / resume (cold slots through the secondary tier)
    def pause(self, sess: Session) -> None:
        """Preempt: move the session's KV out of HBM into the spill tier."""
        assert sess.slot is not None, sess
        assert self.spill_runtime is not None, \
            "KVCacheManager(spill=None) cannot preempt sessions"
        self._park_slot(self.caches, sess)
        self._clear_slot(sess)

    def resume(self, sess: Session, slot: int) -> None:
        """Fetch a paused session's KV back from the spill tier into
        ``slot`` and make it resident again."""
        one = self._unpark_slot(sess)
        self.caches = self._slot_put(self.caches, one, slot)
        self.bind(slot, sess, sess.length)

    def _park_slot(self, tree, sess: Session) -> None:
        """Stash one slot of ``tree`` (leaf-wise) into the spill tier."""
        one = self._slot_get(tree, sess.slot)
        leaves, treedef = jax.tree_util.tree_flatten(one)
        payloads, dtypes = [], []
        for x in leaves:
            payloads.append(self.spill_runtime.stash(
                x, TransferHints(dtype=x.dtype, batch_dim=1,
                                 name="kv_spill"),
                direction="kv_stash"))
            dtypes.append(x.dtype)
        self._spilled[sess.uid] = _SpilledSlot(sess, treedef, payloads,
                                               dtypes)

    def _unpark_slot(self, sess: Session):
        entry = self._spilled.pop(sess.uid)
        leaves = []
        for payload, dt in zip(entry.payloads, entry.dtypes):
            leaves.append(self.spill_runtime.fetch(
                payload, TransferHints(dtype=dt, batch_dim=1,
                                       name="kv_spill"),
                direction="kv_fetch"))
            self._discard(payload)
        return jax.tree_util.tree_unflatten(entry.treedef, leaves)

    def _clear_slot(self, sess: Session) -> None:
        self.slots[sess.slot] = None
        sess.slot = None
        sess.state = SessionState.PAUSED
        sess.steps_since_admit = 0
        sess.preemptions += 1

    def drop_spilled(self, sess: Session) -> None:
        """Discard a paused session's parked cache (cancel/retire)."""
        entry = self._spilled.pop(sess.uid, None)
        if entry is not None:
            for payload in entry.payloads:
                self._discard(payload)

    def sweep_cancelled(self) -> None:
        """Drop parked caches whose owner was cancelled while paused —
        returns their SpillTier budget instead of leaking it."""
        for entry in list(self._spilled.values()):
            if entry.session.done:
                self.drop_spilled(entry.session)

    def _discard(self, payload) -> None:
        """Return capacity-contract budget to a SpillTier leg, if any."""
        if self.spill_runtime is not None:
            self.spill_runtime.discard(payload)

    def spilled_uids(self) -> List[int]:
        return sorted(self._spilled)

    # ------------------------------------------------------------------
    def traffic_report(self) -> Dict[str, Any]:
        """Spill-tier byte accounting (kv_stash / kv_fetch directions)."""
        if self.spill_runtime is None:
            return {}
        return self.spill_runtime.traffic_report()

    def describe(self) -> str:
        spill = (self.spill_runtime.tier.describe()
                 if self.spill_runtime else "none")
        return (f"kv[batch={self.batch} max_len={self.max_len} "
                f"tier={self.report['tier']} spill={spill}]")


# ---------------------------------------------------------------------------
class PagedKVCacheManager(KVCacheManager):
    """Paged KV: sessions hold page lists over a shared pool.

    Storage is the (pool, slot_tree) pair from
    :func:`~repro.models.transformer.paged_pool`: self-attention K/V rows
    live in ``num_pages`` fixed-size pages (+1 scratch page absorbing
    masked writes); SSM / cross-attention state stays slot-shaped and is
    parked whole on preemption, exactly like the base manager.

    * ``pages`` < batch x pages_per_slot **overcommits** the pool —
      admission is funded by typical usage instead of the worst case,
      which is the paper's pooled-capacity argument; pool pressure then
      evicts cold pages or, at the limit, preempts (Engine policy).
    * ``codec_for(tenant)`` picks the spill codec per tenant from the
      ``core/compress.py`` registry (None: raw pages).  ``codec_kernel``
      routes the quantize/pack through the Pallas kernel twin
      (``kernels/offload_pack.py``) instead of the jnp reference.
    * ``prefix_share=True`` turns on the radix prefix index: admission
      matches a new prompt against cached prefixes page-by-page
      (:meth:`match_prefix`), binds fully-matched pages read-only
      (refcount bump in the :class:`~repro.serve.paging.PageTable`), and
      forks — copies into a private frame — the page holding the first
      divergent token.  Only models whose serving state is pure KV can
      share (recurrent SSM/conv slot state is a running summary of the
      whole prefix and cannot be grafted mid-sequence); the flag
      self-disables otherwise.
    """

    paged = True

    def __init__(self, model, batch: Optional[int] = None,
                 max_len: Optional[int] = None, *,
                 page_size: int = 64,
                 pages: Optional[int] = None,
                 codec_for: Optional[Callable[[str], Optional[str]]] = None,
                 codec_kernel: bool = False,
                 decode_kernel: bool = False,
                 prefix_share: bool = False,
                 **kwargs):
        self.page_size = int(page_size)
        self._pages_override = pages
        self.codec_for = codec_for or (lambda tenant: None)
        self.codec_kernel = codec_kernel
        self.decode_kernel = bool(decode_kernel)
        self._sessions: Dict[int, Session] = {}       # uid -> owner
        self._codec_by_uid: Dict[int, Optional[str]] = {}
        self.prefix_share = bool(prefix_share)
        # radix index over page-sized token chunks: node maps a page's
        # token tuple -> [pid, child_node]; a page's KV depends only on
        # the token chain up to its last row (causal attention), so the
        # chain IS the cache key
        self._prefix_root: Dict[Tuple[int, ...], List[Any]] = {}
        self._pid_nodes: Dict[int, Tuple[Dict, Tuple[int, ...]]] = {}
        self.prefix_hits = 0           # pages bound read-only
        self.prefix_forks = 0          # COW page copies
        self.prefix_rows_reused = 0    # prompt rows skipped at prefill
        self.prefix_rows_prompted = 0  # prompt rows seen (hit-rate denom)
        super().__init__(model, batch, max_len, **kwargs)
        cfg = model.cfg
        if self.prefix_share and (
                self._has_slot_leaves or cfg.is_encoder_decoder
                or getattr(cfg, "mrope_sections", None)):
            log.warning("prefix sharing disabled: model carries recurrent "
                        "slot state (or enc-dec/mrope positions) that "
                        "cannot be grafted mid-sequence")
            self.prefix_share = False

    def _init_storage(self) -> None:
        caches = self.model.init_cache(self.batch, self.max_len)
        self.pool, self.slot_tree = tfm.paged_pool(caches, self.page_size)
        self.pages_per_slot = self.max_len // self.page_size
        full = self.batch * self.pages_per_slot
        num = self._pages_override if self._pages_override else full
        if not 1 <= num <= full:
            raise ValueError(f"pages must be in [1, {full}]: {num}")
        if num < full:
            # overcommit REALLY shrinks the resident pool: keep num frames
            # plus the trailing scratch frame — the capacity saving is
            # physical, not just simulated eviction pressure
            import jax.numpy as jnp
            self.pool = jax.tree.map(
                lambda c: jnp.concatenate([c[:, :num], c[:, -1:]], axis=1),
                self.pool)
        self.table = PageTable(num, self.page_size)
        # frames die (evicted / freed) -> the prefix index must forget
        # them — and a compressed side-pool frame must be returned —
        # before the frame id is reused for different contents
        self.table.on_release = self._on_pid_release
        self.scratch_id = num                     # pool holds num+1 frames
        self._pmap_cache = None
        self._pmap_np: Optional[np.ndarray] = None
        self.report["num_pages"] = num
        self._has_slot_leaves = bool(jax.tree_util.tree_leaves(self.slot_tree))
        # ---- in-kernel decode state (decode_kernel=True) -------------
        # compressed side pool: int8 payload frames + one scale per
        # (group-stack row, frame); page-map ids >= num+1 address frame
        # ``id - (num+1)`` here and the paged-attention kernel dequants
        # them in the K/V load (fused codec decode).  Only codecs whose
        # payload is int8 (int8 / blocksparse) are residency-eligible.
        import jax.numpy as jnp
        self._cframe_by_pid: Dict[int, Tuple] = {}   # pid -> (ci, codec,
        self._cframe_free: List[int] = []            #   treedef, scales,
        if self.decode_kernel:                       #   dtypes)
            self.cpool = jax.tree.map(
                lambda c: jnp.zeros(c.shape[:1] + (num,) + c.shape[2:],
                                    jnp.int8), self.pool)
            self.cscale = jax.tree.map(
                lambda c: jnp.zeros(c.shape[:1] + (num, 1), jnp.float32),
                self.pool)
            self._cframe_free = list(range(num))
        self._cframe_adopts = 0
        # decode-io metering: pages the attention actually reads per step
        # (the paper's claim is that this scales with rows held, not pool
        # size — gather_equiv is what the legacy materialize-all path reads)
        cfg = self.model.cfg
        self._decode_window = cfg.window if cfg.attention == "swa" else 0
        self._decode_steps = 0
        self._decode_pages_touched = 0
        self._decode_pages_gather = 0
        self._page_frame_bytes = sum(
            int(np.prod(c.shape[:1] + c.shape[2:])) * c.dtype.itemsize
            for c in jax.tree_util.tree_leaves(self.pool))

    # ------------------------------------------------------------------
    # page-backed rows
    def session_pages(self, prompt_len: int, max_new: int) -> int:
        """Worst-case reservation: rows the session can ever occupy."""
        return self.table.pages_for(min(self.max_len, prompt_len + max_new))

    # ------------------------------------------------------------------
    # prefix sharing: radix index over page-sized token chunks
    def _on_pid_release(self, pid: int) -> None:
        """A frame id died (evicted / freed): forget its prefix-index
        chain and return its compressed side-pool frame, if any.  Runs
        AFTER the evict callback, so an eviction stashes the compressed
        payload before the side frame is recycled."""
        self._drop_prefix_pid(pid)
        entry = self._cframe_by_pid.pop(pid, None)
        if entry is not None:
            self._cframe_free.append(entry[0])
            self._pmap_cache = None

    def _drop_prefix_pid(self, pid: int) -> None:
        entry = self._pid_nodes.pop(pid, None)
        if entry is None:
            return
        parent, key = entry
        child = parent.get(key)
        if child is not None and child[0] == pid:
            # drops the whole subtree with it: a child chain without its
            # parent chain is unreachable by construction
            del parent[key]

    def match_prefix(self, prompt) -> Optional[PrefixMatch]:
        """Walk the radix index page-by-page along the prompt.

        Fully-matched pages are returned for read-only binding; at the
        first divergence the best partially-matching sibling becomes the
        COW fork donor.  At least one prompt token is always left to the
        suffix prefill (its logits sample the first new token), so a
        fully-cached prompt still matches only ``len(prompt) - 1``
        rows.  Read-only: admission calls this *before* the quota check
        (shared pages are not charged) and nothing mutates the table
        between the match and :meth:`prepare_slot`."""
        if not self.prefix_share:
            return None
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        ps = self.page_size
        limit = len(toks) - 1
        node = self._prefix_root
        pids: List[int] = []
        i = 0
        while i + ps <= limit:
            child = node.get(tuple(toks[i:i + ps]))
            if child is None or not self.table.is_resident_pid(child[0]):
                break
            pids.append(child[0])
            node = child[1]
            i += ps
        fork_pid, fork_rows = None, 0
        for key, (pid, _child) in node.items():
            if not self.table.is_resident_pid(pid):
                continue
            depth, cap = 0, min(len(key), limit - i)
            while depth < cap and key[depth] == toks[i + depth]:
                depth += 1
            if depth > fork_rows:
                fork_rows, fork_pid = depth, pid
        if not pids and not fork_rows:
            return None
        if not fork_rows:
            fork_pid = None
        return PrefixMatch(pids=pids, fork_pid=fork_pid, rows=i + fork_rows)

    def note_prefilled(self, sess: Session, prompt,
                       match: Optional[PrefixMatch] = None) -> None:
        """Register the admission's full prompt pages in the prefix index
        (shared pages are already there under the donor's pid; a forked
        page registers as a sibling chain) and tally the hit-rate."""
        if not self.prefix_share:
            return
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        self.prefix_rows_prompted += len(toks)
        if match is not None:
            self.prefix_rows_reused += match.rows
        ps = self.page_size
        pids = self.table.resident_pids(sess.uid)
        node = self._prefix_root
        for p in range(len(toks) // ps):
            key = tuple(toks[p * ps:(p + 1) * ps])
            child = node.get(key)
            if child is None:
                pid = pids[p]
                if pid is None:
                    break
                child = [pid, {}]
                node[key] = child
                self._pid_nodes[pid] = (node, key)
            node = child[1]

    def prepare_slot(self, slot: int, sess: Session, rows: int,
                     match: Optional[PrefixMatch] = None) -> None:
        """Back the prompt's rows with pages before the prefill gather.

        With a prefix ``match``: the fully-matched pages bind read-only
        (refcount bump, logical positions 0..n-1), the fork donor — if
        any — is copied into a fresh private frame (copy-on-write,
        *before* the prefill scatter ever runs), and only the remaining
        positions allocate fresh frames.  Raises
        :class:`~repro.serve.paging.PageError` when the pool cannot
        cover them (every page hot) — the Engine then aborts (undoing
        the shared binds) and defers admission."""
        self._sessions[sess.uid] = sess
        self._codec_by_uid[sess.uid] = self.codec_for(sess.tenant)
        if match is not None:
            for pid in match.pids:
                self.table.share(sess.uid, pid)
            if match.fork_pid is not None:
                new_pid = self.table.alloc(sess.uid, self._evict_cb)
                # COW fork: the donor's rows up to the divergence are
                # valid as-is; the suffix prefill overwrites the tail.
                # (If the alloc just evicted the donor itself, the frame
                # still holds the donor's bytes — the copy is the
                # identity and stays correct.)
                self.pool = tfm.page_insert(
                    self.pool, tfm.page_slice(self.pool, match.fork_pid),
                    new_pid)
                self.prefix_forks += 1
            self.prefix_hits += len(match.pids)
        self.table.ensure(sess.uid, rows, self._evict_cb)

    def abort_prepare(self, sess: Session) -> None:
        for entry in self.table.free_session(sess.uid):
            self._discard_page(entry)
        self._sessions.pop(sess.uid, None)
        self._codec_by_uid.pop(sess.uid, None)

    def bind(self, slot: int, sess: Session, length: int) -> None:
        # a session entering a slot changes the gather map — a stale cache
        # here silently routes its decode through the scratch page
        super().bind(slot, sess, length)
        self._pmap_cache = None

    def ensure_rows(self, sess: Session, rows: int) -> None:
        """Demand paging for decode growth (may evict cold pages)."""
        if self.table.ensure(sess.uid, rows, self._evict_cb):
            self._pmap_cache = None

    def page_map(self) -> jax.Array:
        """(batch, pages_per_slot) int32 pool indices for the decode
        gather; unowned positions point at the scratch page.  Cached on
        device — the map only changes on admission/growth/preemption, not
        per decode step — and invalidated by every mutating path.

        With ``decode_kernel`` the map is *translated*: a page resident
        in the compressed side pool emits ``scratch_id + 1 + ci`` (ids
        past the raw pool address side-pool frames; the kernel dequants
        them in the K/V load)."""
        if self._pmap_cache is None:
            self._pmap_np = self._build_map(translate=self.decode_kernel)
            self._pmap_cache = jax.numpy.asarray(self._pmap_np)
        return self._pmap_cache

    def page_map_host(self) -> np.ndarray:
        """Host copy of :meth:`page_map` (same translation) — the Engine
        derives each step's write frame from it without a device sync."""
        self.page_map()
        return self._pmap_np

    def _build_map(self, translate: bool = False) -> np.ndarray:
        m = np.full((self.batch, self.pages_per_slot), self.scratch_id,
                    np.int32)
        for slot, sess in enumerate(self.slots):
            if sess is not None:
                self._fill_row(m, slot, sess, translate)
        return m

    def page_map_for(self, slot: int, sess: Session) -> np.ndarray:
        """Page map with a *pending* admission's pages already in ``slot``
        (the prefill gather runs before :meth:`bind`).  Untranslated: the
        prefill path gathers the raw pool, and an admission's own pages
        are always raw (fresh frames or raw prefix pages)."""
        m = self._build_map()
        self._fill_row(m, slot, sess, False)
        return m

    def _fill_row(self, m: np.ndarray, slot: int, sess: Session,
                  translate: bool = False) -> None:
        for pos, pid in enumerate(self.table.resident_pids(sess.uid)):
            assert pid is not None, \
                f"resident session {sess.uid} has a spilled page {pos}"
            if translate and pid in self._cframe_by_pid:
                m[slot, pos] = self.scratch_id + 1 + self._cframe_by_pid[pid][0]
            else:
                m[slot, pos] = pid

    # ------------------------------------------------------------------
    # per-page spill path (lazy: only on real pool pressure)
    def _evict_cb(self, uid: int, pos: int, pid: int):
        assert self.spill_runtime is not None, \
            "page eviction needs a spill tier " \
            "(PagedKVCacheManager(spill=None) cannot overcommit)"
        centry = self._cframe_by_pid.get(pid)
        if centry is not None:
            # the page's live bytes sit in the compressed side pool (the
            # raw frame is stale): stash the already-quantized payloads
            # as-is — no re-encode, and the recorded per-leaf scales /
            # dtypes ride along so a later resume round-trips exactly
            ci, codec_name, treedef, scales, dtypes = centry
            qleaves = jax.tree_util.tree_leaves(
                tfm.page_slice(self.cpool, ci))
            items = []
            for q, scale, dtype in zip(qleaves, scales, dtypes):
                payload = self.spill_runtime.stash(
                    q, TransferHints(dtype=q.dtype, batch_dim=0,
                                     allow_compress=False, name="kv_page"),
                    direction="kv_stash")
                items.append((payload, scale, dtype))
            return _SpilledPage(treedef, items, codec_name)
        page = tfm.page_slice(self.pool, pid)
        leaves, treedef = jax.tree_util.tree_flatten(page)
        codec_name = self._codec_by_uid.get(uid)
        codec = get_codec(codec_name) if codec_name else None
        items = []
        for x in leaves:
            dtype = x.dtype
            if codec is not None and codec.applies_to(x):
                q, scale = encode_tensor(codec, x, kernel=self.codec_kernel)
            else:
                q, scale = x, None
            payload = self.spill_runtime.stash(
                q, TransferHints(dtype=q.dtype, batch_dim=0,
                                 allow_compress=False, name="kv_page"),
                direction="kv_stash")
            items.append((payload, scale, dtype))
        return _SpilledPage(treedef, items, codec_name)

    def _unstash_page(self, entry: _SpilledPage):
        """Fetch + decode a spilled page, all-or-nothing: the stashed
        payloads are only discarded after EVERY leaf fetched and decoded,
        so a mid-tree failure leaves the payload intact and the caller
        can re-park the position for a later retry."""
        codec = get_codec(entry.codec) if entry.codec else None
        leaves = []
        for payload, scale, dtype in entry.items:
            q = self.spill_runtime.fetch(
                payload, TransferHints(dtype=dtype, batch_dim=0,
                                       allow_compress=False, name="kv_page"),
                direction="kv_fetch")
            if scale is not None:
                q = decode_tensor(codec, q, scale, dtype,
                                  kernel=self.codec_kernel)
            leaves.append(q)
        for payload, _, _ in entry.items:
            self._discard(payload)
        return jax.tree_util.tree_unflatten(entry.treedef, leaves)

    def _discard_page(self, entry: _SpilledPage) -> None:
        for payload, _, _ in entry.items:
            self._discard(payload)

    # ------------------------------------------------------------------
    # compressed residency (decode_kernel=True): a resumed cold page may
    # stay quantized in the int8 side pool and be dequanted inside the
    # paged-attention kernel instead of inflating into a raw frame
    def _compressible_resume(self, sess: Session, pos: int, parked,
                             entry: _SpilledPage) -> bool:
        """Eligibility for fused-decode residency.  The tail page (the
        one the next decode step writes into) must resume raw; shared
        (prefix) pages stay raw so COW forks always copy live pool
        bytes; only int8-payload codecs fit the side pool."""
        return (self.decode_kernel
                and entry.codec in ("int8", "blocksparse")
                and bool(self._cframe_free)
                and not isinstance(parked, SharedPayload)
                and pos < sess.length // self.page_size
                and all(s is not None for _, s, _ in entry.items))

    def _adopt_compressed(self, entry: _SpilledPage, pid: int) -> None:
        """Fetch a quantized page into side-pool frame ``ci`` verbatim
        (no decode) and record the pid -> ci mapping the page-map
        translation and a later re-evict both key off."""
        import jax.numpy as jnp
        ci = self._cframe_free[-1]          # popped only after all fetches
        qleaves, scales, dtypes = [], [], []
        for payload, scale, dtype in entry.items:
            q = self.spill_runtime.fetch(
                payload, TransferHints(dtype=dtype, batch_dim=0,
                                       allow_compress=False, name="kv_page"),
                direction="kv_fetch")
            qleaves.append(q)
            scales.append(scale)
            dtypes.append(dtype)
        for payload, _, _ in entry.items:
            self._discard(payload)
        self._cframe_free.pop()
        qpage = jax.tree_util.tree_unflatten(entry.treedef, qleaves)
        self.cpool = tfm.page_insert(self.cpool, qpage, ci)
        self.cscale = jax.tree.map(
            lambda s, sc: s.at[:, ci].set(
                jnp.asarray(sc, jnp.float32).reshape(())),
            self.cscale, jax.tree_util.tree_unflatten(entry.treedef, scales))
        self._cframe_by_pid[pid] = (ci, entry.codec, entry.treedef,
                                    scales, dtypes)
        self._cframe_adopts += 1
        self._pmap_cache = None

    # ------------------------------------------------------------------
    # pause / resume: pages go cold in place; slot-shaped leaves park whole
    def pause(self, sess: Session) -> None:
        assert sess.slot is not None, sess
        assert self.spill_runtime is not None, \
            "PagedKVCacheManager(spill=None) cannot preempt sessions"
        if self._has_slot_leaves:
            self._park_slot(self.slot_tree, sess)
        self.table.mark_cold(sess.uid)
        self._clear_slot(sess)
        self._pmap_cache = None

    def resume(self, sess: Session, slot: int) -> None:
        """Re-bind a paused session: surviving pages readmit copy-free,
        evicted ones are fetched (and decoded) into fresh frames.  A
        page evicted while *shared* carries one payload for all holders:
        the single fetch re-homes every holder onto the fresh frame."""
        uid = sess.uid
        self.table.mark_hot(uid)
        try:
            while True:
                spilled = self.table.spilled_positions(uid)
                if not spilled:
                    break
                pos = spilled[0]
                parked = self.table.entries(uid)[pos].payload
                inner = parked.payload \
                    if isinstance(parked, SharedPayload) else parked
                pid = self.table.set_resident(uid, pos, self._evict_cb)
                try:
                    if self._compressible_resume(sess, pos, parked, inner):
                        # fused-decode residency: the quantized payload
                        # lands in the compressed side pool as-is and the
                        # decode kernel dequants it per attention read —
                        # no inflate pass, no raw-pool frame bytes
                        self._adopt_compressed(inner, pid)
                        continue
                    page = self._unstash_page(inner)
                except Exception:
                    # the fetch failed AFTER the position went resident:
                    # roll it back to spilled over the (still intact)
                    # payload — leaving it resident would park garbage
                    # in the pool and a later resume would serve it
                    self.table.unset_resident(uid, pos, parked)
                    raise
                self.pool = tfm.page_insert(self.pool, page, pid)
        except Exception:
            # pool too hot to re-home every page: stay paused, pages
            # return to the eviction queue, the Engine retries later
            # (readmits are only counted by note_resumed on success)
            self.table.mark_cold(uid)
            raise
        if uid in self._spilled:
            one = self._unpark_slot(sess)
            self.slot_tree = self._slot_put(self.slot_tree, one, slot)
        self.table.note_resumed(uid)
        self.bind(slot, sess, sess.length)

    # ------------------------------------------------------------------
    # disaggregated adoption (decode role: take ownership of shipped pages)
    def adopt(self, slot: int, sess: Session, handoff, queue) -> None:
        """Install a transferred session into ``slot`` (cross-role handoff).

        Ownership passes whole: the decode table *claims* fresh frames
        (never aliasing an existing owner — the shipped pages become the
        only copy this role serves from), the transfer queue's payloads
        are fetched into them, slot-shaped leaves merge into the slot row,
        and the session binds at its prefill length.  A
        :class:`~repro.serve.paging.PageError` (pool too hot, nothing
        cold to evict) rolls the claim back BEFORE any page bytes are
        fetched — backpressure leaves the pages parked in the transfer
        tier, not re-prefilled."""
        uid = sess.uid
        self._sessions[uid] = sess
        self._codec_by_uid[uid] = self.codec_for(sess.tenant)
        try:
            pids = self.table.claim(uid, handoff.num_pages, self._evict_cb)
        except PageError:
            self._sessions.pop(uid, None)
            self._codec_by_uid.pop(uid, None)
            raise
        for pid, page in zip(pids, queue.fetch_pages(handoff)):
            self.pool = tfm.page_insert(self.pool, page, pid)
        slot_one = queue.fetch_slot_leaves(handoff)
        if slot_one is not None:
            self.slot_tree = self._slot_put(self.slot_tree, slot_one, slot)
        self.bind(slot, sess, handoff.length)

    def release(self, sess: Session) -> None:
        super().release(sess)          # slot + parked slot-shaped leaves
        self._pmap_cache = None
        for entry in self.table.free_session(sess.uid):
            self._discard_page(entry)
        self._sessions.pop(sess.uid, None)
        self._codec_by_uid.pop(sess.uid, None)

    def sweep_cancelled(self) -> None:
        super().sweep_cancelled()
        for uid in list(self.table.sessions()):
            sess = self._sessions.get(uid)
            if sess is not None and sess.done and sess.slot is None:
                self.release(sess)

    @property
    def caches(self):
        """Debug/legacy view: the contiguous cache tree gathered from the
        page pool at the current page map (a copy, not the storage).
        Compressed-resident frames are inflated into a pool *copy* first
        so the gather always reads live bytes (the storage itself stays
        quantized)."""
        import jax.numpy as jnp
        pool = self.pool
        for pid, (ci, codec_name, treedef, scales, dtypes) \
                in self._cframe_by_pid.items():
            codec = get_codec(codec_name)
            qleaves = jax.tree_util.tree_leaves(
                tfm.page_slice(self.cpool, ci))
            leaves = [decode_tensor(codec, q, s, d)
                      for q, s, d in zip(qleaves, scales, dtypes)]
            pool = tfm.page_insert(
                pool, jax.tree_util.tree_unflatten(treedef, leaves), pid)
        pm = jnp.asarray(self._build_map())
        return tfm.gather_pages(pool, self.slot_tree, pm)

    # ------------------------------------------------------------------
    # decode-io metering: what the attention read this step
    def note_decode(self, length: int, n_active: int) -> None:
        """Record one decode step for ``n_active`` sessions at ``length``
        rows.  In-place decode touches only the pages covering the rows
        the query can see (sliding window excluded); the gather path
        reads the whole ``batch x pages_per_slot`` view regardless."""
        lo = 0
        if self._decode_window > 0:
            lo = max(0, length - self._decode_window + 1) // self.page_size
        touched = self.table.pages_for(length + 1) - lo
        gather = self.batch * self.pages_per_slot
        self._decode_steps += 1
        self._decode_pages_touched += \
            touched * n_active if self.decode_kernel else gather
        self._decode_pages_gather += gather

    # ------------------------------------------------------------------
    def traffic_report(self) -> Dict[str, Any]:
        report = dict(super().traffic_report())
        report["pages"] = {
            "page_size": self.page_size,
            "num_pages": self.table.num_pages,
            "evictions": self.table.evictions,
            "refetches": self.table.refetches,
            "readmits_free": self.table.readmits_free,
            "adoptions": self.table.adoptions,
            "shared_binds": self.table.shared_binds,
        }
        report["decode_io"] = {
            "in_place": self.decode_kernel,
            "steps": self._decode_steps,
            "pages_touched": self._decode_pages_touched,
            "pages_gather_equiv": self._decode_pages_gather,
            "bytes_touched":
                self._decode_pages_touched * self._page_frame_bytes,
            "bytes_gather_equiv":
                self._decode_pages_gather * self._page_frame_bytes,
            "compressed_resident": len(self._cframe_by_pid),
            "compressed_adopts": self._cframe_adopts,
        }
        prompted = self.prefix_rows_prompted
        report["prefix"] = {
            "enabled": self.prefix_share,
            "hits": self.prefix_hits,
            "forks": self.prefix_forks,
            "rows_reused": self.prefix_rows_reused,
            "rows_prompted": prompted,
            "hit_rate": (self.prefix_rows_reused / prompted
                         if prompted else 0.0),
        }
        return report

    def describe(self) -> str:
        return (f"{super().describe()[:-1]} "
                f"{self.table.describe()}]")
