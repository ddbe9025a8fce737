"""Family-polymorphic serving state pools for the geo engine.

Every BPRR block carries per-session serving state whose SHAPE depends on the
block's family: KV tensors (or MLA latents) for attention blocks, SSD+conv
state for mamba mixers, wkv/shift state for rwkv, self-KV plus encoder
cross-KV for enc-dec decoder blocks — and zamba2's shared-attention blocks
carry BOTH mamba state and a KV cache.  :class:`StateSpec` names that
contract per block kind; ``state_specs(cfg)`` derives the per-block spec
tuple from ``models.blocks.stack_block_kinds`` — the single dispatch point
replacing the old one-kind-per-engine restriction.

Two granularities:

* ``new_block_cache`` — single-session per-(server, session, layer) caches.
  Kept for API compatibility and for callers that manage their own cache
  dicts.
* ``CachePool`` — the continuous-batching layout: per server, ONE stacked
  state tree per *run* of same-kind hosted blocks, leaves
  ``(run_layers, n_rows, ...)``, so a single jitted step (over all rows,
  scanned over each run) serves every session resident on that server.  The
  pooled step factories take the server's static per-layer kind tuple and
  dispatch each run to its family's block functions — the program still
  traces exactly once per server, heterogeneous or not.  They also take the
  engine's compute ``backend`` ("xla" oracle | "pallas" kernels with
  per-call XLA fallback, see ``repro.kernels.runtime``) and thread it into
  every block call; backend choice never changes round results
  (docs/serving.md).

Slot accounting follows eq. (5)/(20) of the paper unchanged (the memory
model is family-agnostic): a server hosting ``m`` blocks has
``⌊(M_j − s_m·m_j)/s_c⌋`` cache *block-slots*; a session routed through
``k`` of the server's blocks occupies ``k`` block-slots from start to
retirement.  ``CachePool`` enforces both the row budget (physical arrays)
and the block-slot budget — the no-overbooking commitment.

Two cache layouts share that accounting:

* ``layout="slab"`` (default, the exact reference twin): every row owns a
  fixed-width ``(max_len, ...)`` stripe of each time-indexed leaf, so one
  admitted session books worst-case memory whatever its actual length.
* ``layout="paged"``: the time axis of every *self-KV* leaf is carved into
  ``page_size``-token pages held in shared physical page arrays
  ``(layers, n_pages + 1, page_size, ...)``; a :class:`PagePool` free list
  plus one int32 page table ``(n_rows, max_pages)`` per server map row
  time-slices to physical pages (page id 0 is the reserved trash page for
  unassigned entries).  Admission books only the pages a prompt needs, and
  eq. (5)'s budget becomes page-granular: ``cap_units = cap_slots ×
  max_pages`` page-units against which a session through ``k`` blocks
  holding ``p`` pages charges ``k·p`` — the same ⌊(M_j − s_m·m_j)/s_c⌋
  bytes, metered at page rather than worst-case-sequence granularity.
  Recurrent / cross-KV leaves (wkv, ssm+conv, ck/cv) stay row-resident:
  their footprint is length-independent, so paging buys nothing there.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# StateSpec: the per-block serving-state contract
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StateSpec:
    """What one BPRR block needs from the serving layer.

    * ``kind``          — block kind (``models.blocks.stack_block_kinds``).
    * ``recurrent``     — carries order-sensitive recurrent state: prefill
      must run at the EXACT prompt length in one shot (no padding, no
      chunked resume) — rwkv and mamba mixers.
    * ``needs_emb0``    — consumes the stack's original embedding alongside
      the hidden state (zamba2's shared attention on concat(h, emb0)).
    * ``cross``         — holds encoder cross-KV (enc-dec decoder blocks);
      prefill needs the encoder output, decode needs the session's encoder
      length to mask the over-allocated cross cache.
    * ``decode_active`` — does per-token decode work at all (encoder blocks
      do not: their contribution is frozen into the cross-KV at prefill).
    """

    kind: str
    recurrent: bool = False
    needs_emb0: bool = False
    cross: bool = False
    decode_active: bool = True


_STATE_SPECS: Dict[str, StateSpec] = {
    "decoder": StateSpec("decoder"),
    "rwkv": StateSpec("rwkv", recurrent=True),
    "mamba": StateSpec("mamba", recurrent=True),
    "mamba_shared": StateSpec("mamba_shared", recurrent=True,
                              needs_emb0=True),
    "enc": StateSpec("enc", decode_active=False),
    "dec": StateSpec("dec", cross=True),
}

SUPPORTED_KINDS: Tuple[str, ...] = tuple(sorted(_STATE_SPECS))


def state_spec_for(kind: str) -> StateSpec:
    """The :class:`StateSpec` of one block kind; ``ValueError`` (naming the
    supported set) for anything else — no dead-end ``NotImplementedError``."""
    try:
        return _STATE_SPECS[kind]
    except KeyError:
        raise ValueError(
            f"no serving StateSpec for block kind {kind!r}; supported kinds: "
            + ", ".join(SUPPORTED_KINDS)) from None


def state_specs(cfg: ModelConfig) -> Tuple[StateSpec, ...]:
    """Per-block StateSpec tuple (length ``cfg.n_layers``) for a config."""
    from repro.models.blocks import stack_block_kinds

    return tuple(state_spec_for(k) for k in stack_block_kinds(cfg))


def kind_runs(kinds: Sequence[str]) -> Tuple[Tuple[str, int, int], ...]:
    """Maximal contiguous same-kind runs: ((kind, lo, hi), ...) covering
    ``range(len(kinds))``.  The pooled steps scan per run; a server's run
    structure is static, so its program still traces exactly once."""
    runs: List[Tuple[str, int, int]] = []
    for i, k in enumerate(kinds):
        if runs and runs[-1][0] == k:
            runs[-1] = (k, runs[-1][1], i + 1)
        else:
            runs.append((k, i, i + 1))
    return tuple(runs)


# ---------------------------------------------------------------------------
# Single-session caches (legacy granularity, used by failover replay helpers)
# ---------------------------------------------------------------------------


def new_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    enc_len: int = 0):
    """Allocate one per-(server, session, layer) cache for any supported
    block kind: KV tensors for ``decoder`` (MLA latent/krope when
    ``cfg.attn_kind == 'mla'``), recurrent state for ``rwkv``/``mamba``,
    state + shared-attention KV for ``mamba_shared``, self-KV + encoder
    cross-KV for ``dec`` (``enc_len`` positions), and ``{}`` for the
    stateless ``enc`` blocks."""
    cdt = jnp.dtype(cfg.param_dtype)
    if kind == "decoder":
        if cfg.attn_kind == "mla":
            return {
                "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cdt),
                "krope": jnp.zeros((batch, max_len, cfg.rope_head_dim), cdt),
            }
        kv = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt)}
    if kind == "rwkv":
        h, hd = cfg.ssm_heads, cfg.ssm_head_dim
        return {
            "wkv": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "shift_tm": jnp.zeros((batch, cfg.d_model), jnp.float32),
            "shift_cm": jnp.zeros((batch, cfg.d_model), jnp.float32),
        }
    if kind in ("mamba", "mamba_shared"):
        h, p, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        out = {
            "ssm": jnp.zeros((batch, h, p, ns), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim),
                              jnp.float32),
        }
        if kind == "mamba_shared":
            kv = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            out["k"] = jnp.zeros(kv, cdt)
            out["v"] = jnp.zeros(kv, cdt)
        return out
    if kind == "enc":
        return {}  # bidirectional encoder blocks hold no serving state
    if kind == "dec":
        kv = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        ckv = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt),
                "ck": jnp.zeros(ckv, cdt), "cv": jnp.zeros(ckv, cdt)}
    raise ValueError(
        f"no engine cache for block kind {kind!r}; supported kinds: "
        + ", ".join(SUPPORTED_KINDS))


def write_prefill_kv(cache: Dict, kv, length: int) -> Dict:
    """Insert full-sequence K/V (or MLA latent) into a preallocated cache."""
    out = dict(cache)
    if "latent" in cache:
        latent, krope = kv
        out["latent"] = cache["latent"].at[:, :length].set(
            latent.astype(cache["latent"].dtype))
        out["krope"] = cache["krope"].at[:, :length].set(
            krope.astype(cache["krope"].dtype))
    else:
        k, v = kv
        out["k"] = cache["k"].at[:, :length].set(k.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, :length].set(v.astype(cache["v"].dtype))
    return out


# ---------------------------------------------------------------------------
# Batched slot pools (continuous batching)
# ---------------------------------------------------------------------------

# leaf names that index TIME along axis 2 of a pooled (layers, rows, T, ...)
# leaf — written per chunk at [offset, offset+T)
_SELF_KV_KEYS = frozenset({"k", "v", "latent", "krope"})
# encoder cross-KV leaves — written once, at [0, enc_len)
_CROSS_KV_KEYS = frozenset({"ck", "cv"})


def new_state_pool_tree(cfg: ModelConfig, kind: str, n_layers: int,
                        n_rows: int, max_len: int, enc_len: int = 0):
    """Stacked per-kind serving state: leaves (n_layers, n_rows, ...)."""
    cdt = jnp.dtype(cfg.param_dtype)
    L, N, T = n_layers, n_rows, max_len
    if kind == "decoder":
        if cfg.attn_kind == "mla":
            return {
                "latent": jnp.zeros((L, N, T, cfg.kv_lora_rank), cdt),
                "krope": jnp.zeros((L, N, T, cfg.rope_head_dim), cdt),
            }
        kv = (L, N, T, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt)}
    if kind == "rwkv":
        h, hd = cfg.ssm_heads, cfg.ssm_head_dim
        return {
            "wkv": jnp.zeros((L, N, h, hd, hd), jnp.float32),
            "shift_tm": jnp.zeros((L, N, cfg.d_model), jnp.float32),
            "shift_cm": jnp.zeros((L, N, cfg.d_model), jnp.float32),
        }
    if kind in ("mamba", "mamba_shared"):
        h, p, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        tree = {
            "ssm": jnp.zeros((L, N, h, p, ns), jnp.float32),
            "conv": jnp.zeros((L, N, cfg.conv_width - 1, conv_dim),
                              jnp.float32),
        }
        if kind == "mamba_shared":
            kv = (L, N, T, cfg.n_kv_heads, cfg.head_dim)
            tree["k"] = jnp.zeros(kv, cdt)
            tree["v"] = jnp.zeros(kv, cdt)
        return tree
    if kind == "enc":
        return {}
    if kind == "dec":
        kv = (L, N, T, cfg.n_kv_heads, cfg.head_dim)
        ckv = (L, N, enc_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, cdt), "v": jnp.zeros(kv, cdt),
                "ck": jnp.zeros(ckv, cdt), "cv": jnp.zeros(ckv, cdt)}
    raise ValueError(
        f"no state pool for block kind {kind!r}; supported kinds: "
        + ", ".join(SUPPORTED_KINDS))


def new_cache_pool_tree(cfg: ModelConfig, kind: str, n_layers: int,
                        n_rows: int, max_len: int):
    """Homogeneous-stack compatibility alias of ``new_state_pool_tree``."""
    return new_state_pool_tree(cfg, kind, n_layers, n_rows, max_len)


# ---------------------------------------------------------------------------
# Paged layout: free-list page allocator + paged state trees
# ---------------------------------------------------------------------------

TRASH_PAGE = 0  # physical page id 0: write target of every unassigned entry


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` cache positions (0 for 0)."""
    n_tokens = int(n_tokens)
    assert n_tokens >= 0
    return -(-n_tokens // int(page_size))


class PagePool:
    """Deterministic free-list page allocator (the vLLM block-table trick).

    Physical pages are numbered ``1..n_pages``; id ``TRASH_PAGE == 0`` is
    reserved as the write target of unassigned page-table entries, so the
    jitted gather/scatter never needs a validity branch.  ``table`` is the
    shared int32 page table ``(n_rows, max_pages_per_row)``: row ``r``'s
    time-slice ``[i*page_size, (i+1)*page_size)`` lives in physical page
    ``table[r, i]`` (0 = unassigned).  Rows grow monotonically
    (``grow_to``) and free wholesale (``free_row`` — preemption and
    retirement are the same operation to the allocator).

    The free list is LIFO and all operations are pure functions of the
    call sequence — replaying the same sequence reproduces the same
    tables bit-for-bit (the property suite in tests/test_paged_pools.py
    fuzzes exactly these invariants via ``check_invariants``).
    """

    def __init__(self, n_pages: int, n_rows: int, max_pages_per_row: int):
        self.n_pages = int(n_pages)
        self.n_rows = int(n_rows)
        self.max_pages_per_row = int(max_pages_per_row)
        assert self.n_pages >= 0 and self.n_rows >= 1
        assert self.max_pages_per_row >= 1
        self.table = np.zeros((self.n_rows, self.max_pages_per_row),
                              np.int32)
        self.count = np.zeros((self.n_rows,), np.int32)
        # LIFO free list; initialized so the first pops hand out 1, 2, 3...
        self._free: List[int] = list(range(self.n_pages, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return int(self.count.sum())

    def pages_of(self, row: int) -> List[int]:
        """The live page ids of ``row`` in table order."""
        return [int(self.table[row, i])
                for i in range(int(self.count[row]))]

    def can_grow(self, row: int, n_pages: int) -> bool:
        return n_pages - int(self.count[row]) <= len(self._free)

    def grow_to(self, row: int, n_pages: int) -> List[int]:
        """Extend ``row`` to ``n_pages`` pages (no-op when already there);
        returns the newly assigned page ids.  Raises on free-list
        exhaustion — callers must check ``can_grow``."""
        have = int(self.count[row])
        if n_pages <= have:
            return []
        if n_pages > self.max_pages_per_row:
            raise RuntimeError(
                f"row {row}: {n_pages} pages exceed the per-row table "
                f"width {self.max_pages_per_row}")
        if n_pages - have > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: row {row} needs {n_pages - have} "
                f"pages, {len(self._free)} free")
        fresh = []
        for i in range(have, n_pages):
            pid = self._free.pop()
            self.table[row, i] = pid
            fresh.append(pid)
        self.count[row] = n_pages
        return fresh

    def free_row(self, row: int) -> List[int]:
        """Return every page of ``row`` to the free list (reverse order, so
        alloc→free→alloc round-trips reproduce the same page ids).
        Returns the freed page ids."""
        freed = []
        for i in reversed(range(int(self.count[row]))):
            pid = int(self.table[row, i])
            self._free.append(pid)
            freed.append(pid)
            self.table[row, i] = 0
        self.count[row] = 0
        return freed

    def check_invariants(self):
        """Allocator invariants (the property-test contract):
        * entries beyond ``count[r]`` are 0; entries below are in
          ``[1, n_pages]`` — tables only reference live pages,
        * no physical page is referenced twice (no double-booking),
        * live ∪ free is a partition of ``{1..n_pages}`` (conservation).
        """
        live: List[int] = []
        for r in range(self.n_rows):
            c = int(self.count[r])
            assert 0 <= c <= self.max_pages_per_row
            assert (self.table[r, c:] == 0).all(), f"row {r}: stale entries"
            ids = self.table[r, :c].tolist()
            assert all(1 <= p <= self.n_pages for p in ids), \
                f"row {r}: out-of-range page id"
            live.extend(ids)
        assert len(live) == len(set(live)), "double-booked page"
        free = self._free
        assert len(free) == len(set(free)), "duplicate free-list entry"
        assert not set(live) & set(free), "page both live and free"
        assert len(live) + len(free) == self.n_pages, "page leak"


def new_paged_pool_tree(cfg: ModelConfig, kind: str, n_layers: int,
                        n_rows: int, max_len: int, page_size: int,
                        n_phys: int, enc_len: int = 0):
    """Paged-layout state tree: self-KV leaves become shared physical page
    arrays ``(n_layers, n_phys, page_size, ...)`` (``n_phys`` includes the
    trash page) addressed through the pool's page table; every other leaf
    keeps its row-resident ``(n_layers, n_rows, ...)`` slab layout."""
    template = new_state_pool_tree(cfg, kind, n_layers, 1, max_len, enc_len)
    out = {}
    for key, leaf in template.items():
        if key in _SELF_KV_KEYS:
            out[key] = jnp.zeros(
                (n_layers, n_phys, page_size) + leaf.shape[3:], leaf.dtype)
        else:
            out[key] = jnp.zeros((n_layers, n_rows) + leaf.shape[2:],
                                 leaf.dtype)
    return out


class CachePool:
    """Row + block-slot bookkeeping around the stacked state trees of ONE
    server.

    * the hosted block range is described by its per-layer ``kinds``; the
      state lives in one stacked subtree per same-kind run
      (``self.tree[r]`` for ``self.runs[r]``),
    * ``n_rows`` physical rows (the vmapped batch extent of the jitted step),
    * ``cap_slots`` block-slots per eq. (5): ⌊(M_j − s_m·m_j)/s_c⌋ — a
      session holding ``k`` of this server's blocks consumes ``k`` slots.

    ``layout="paged"`` carves the self-KV time axis into ``page_size``-token
    pages (see the module docstring): the budget becomes ``cap_units =
    cap_slots × max_pages`` page-units, a session through ``k`` blocks
    holding ``p`` pages charges ``k·p`` units, and physical page arrays are
    sized to the SAME byte budget (``cap_slots × max_pages / n_layers``
    pages, clamped to what the rows could ever reference) — so both the
    accounting and the free list enforce eq. (5), just page-granular.
    """

    def __init__(self, cfg: ModelConfig, kinds: Sequence[str], n_rows: int,
                 max_len: int, cap_slots: int, enc_len: int = 0,
                 layout: str = "slab", page_size: int = 0):
        assert layout in ("slab", "paged"), layout
        self.cfg = cfg
        self.kinds = tuple(kinds)
        self.runs = kind_runs(self.kinds)
        self.n_layers = len(self.kinds)
        self.n_rows = n_rows
        self.max_len = max_len
        self.enc_len = int(enc_len)
        self.cap_slots = int(cap_slots)
        self.layout = layout
        if layout == "paged":
            page_size = int(page_size)
            if page_size < 1 or max_len % page_size != 0:
                raise ValueError(
                    f"page_size {page_size} must be >= 1 and divide "
                    f"max_len {max_len} (keeps the paged time axis "
                    "identical to the slab reference)")
            self.page_size = page_size
            self.max_pages = max_len // page_size
            self.cap_units = self.cap_slots * self.max_pages
            n_phys = max(1, min(
                self.cap_units // max(1, self.n_layers),
                n_rows * self.max_pages))
            self.pages = PagePool(n_phys, n_rows, self.max_pages)
            self.units_used = 0
            self.sid_pages: Dict[int, int] = {}  # sid -> pages held
            self.tree = tuple(
                new_paged_pool_tree(cfg, kind, hi - lo, n_rows, max_len,
                                    page_size, n_phys + 1, self.enc_len)
                for kind, lo, hi in self.runs)
        else:
            self.page_size = 0
            self.tree: Tuple[Dict, ...] = tuple(
                new_state_pool_tree(cfg, kind, hi - lo, n_rows, max_len,
                                    self.enc_len)
                for kind, lo, hi in self.runs)
        self._free: List[int] = list(range(n_rows))
        self.rows: Dict[int, int] = {}  # sid -> row
        self.blocks: Dict[int, int] = {}  # sid -> k block-slots held
        self.slots_used = 0

    # -- admission ----------------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        """Pages covering ``n_tokens`` cache positions (paged layout)."""
        return pages_for(n_tokens, self.page_size)

    def fits(self, sid: int, k_blocks: int, n_pages: int = 0,
             worst_pages: Optional[int] = None) -> bool:
        """No-overbooking check.  Paged layout: ``n_pages`` is the page
        count to book now (ignored on re-entry — the resident pages are
        shared across the session's hops) and ``worst_pages`` optionally
        asserts solo-completability: the fully-grown session must fit this
        server ALONE, so a preempted session can always eventually resume
        (the deadlock-freedom guarantee preemption relies on)."""
        if self.layout == "paged":
            p = self.sid_pages.get(sid, 0) if sid in self.rows \
                else int(n_pages)
            k_total = self.blocks.get(sid, 0) + k_blocks
            if worst_pages is not None:
                if (k_total * int(worst_pages) > self.cap_units
                        or int(worst_pages) > min(self.pages.n_pages,
                                                  self.max_pages)):
                    return False
            if sid in self.rows:
                return self.units_used + k_blocks * p <= self.cap_units
            return (bool(self._free)
                    and self.units_used + k_blocks * p <= self.cap_units
                    and p <= self.pages.free_pages)
        if sid in self.rows:
            # re-entry (failover chain revisiting this server): no new row,
            # but the ADDITIONAL blocks still count against the budget
            return self.slots_used + k_blocks <= self.cap_slots
        return bool(self._free) and (self.slots_used + k_blocks
                                     <= self.cap_slots)

    def alloc(self, sid: int, k_blocks: int, n_pages: int = 0) -> int:
        """Claim one row + ``k_blocks`` slots (slab) or one row +
        ``n_pages`` pages charged at ``k_blocks × n_pages`` page-units
        (paged).  Raises if over budget — the scheduler must check
        ``fits`` first (no-overbooking commitment)."""
        if self.layout == "paged":
            p = self.sid_pages[sid] if sid in self.rows else int(n_pages)
            if self.units_used + k_blocks * p > self.cap_units:
                raise RuntimeError(
                    f"page-unit overbooking: {self.units_used}+"
                    f"{k_blocks}*{p} > {self.cap_units}")
            if sid in self.rows:  # re-entry: charge the extra blocks
                self.blocks[sid] += int(k_blocks)
                self.units_used += int(k_blocks) * p
                return self.rows[sid]
            if not self._free:
                raise RuntimeError("cache pool rows exhausted")
            row = self._free.pop()
            self.pages.grow_to(row, p)
            self.rows[sid] = row
            self.blocks[sid] = int(k_blocks)
            self.sid_pages[sid] = p
            self.units_used += int(k_blocks) * p
            return row
        if self.slots_used + k_blocks > self.cap_slots:
            raise RuntimeError(
                f"block-slot overbooking: {self.slots_used}+{k_blocks} > "
                f"{self.cap_slots}")
        if sid in self.rows:  # re-entry: charge the extra blocks
            self.blocks[sid] += int(k_blocks)
            self.slots_used += int(k_blocks)
            return self.rows[sid]
        if not self._free:
            raise RuntimeError("cache pool rows exhausted")
        row = self._free.pop()
        self.rows[sid] = row
        self.blocks[sid] = int(k_blocks)
        self.slots_used += int(k_blocks)
        return row

    # -- page growth (paged layout) -----------------------------------------
    def can_grow(self, sid: int, n_pages: int) -> bool:
        """True iff ``sid`` can be extended to ``n_pages`` total pages
        within both the page-unit budget and the physical free list."""
        assert self.layout == "paged"
        extra = int(n_pages) - self.sid_pages[sid]
        if extra <= 0:
            return True
        return (self.units_used + self.blocks[sid] * extra <= self.cap_units
                and self.pages.can_grow(self.rows[sid], int(n_pages)))

    def grow_pages(self, sid: int, n_pages: int):
        """Extend ``sid`` to ``n_pages`` total pages (decode growth).
        Raises on overbooking — callers check ``can_grow`` first."""
        assert self.layout == "paged"
        extra = int(n_pages) - self.sid_pages[sid]
        if extra <= 0:
            return
        if self.units_used + self.blocks[sid] * extra > self.cap_units:
            raise RuntimeError(
                f"page-unit overbooking on grow: {self.units_used}+"
                f"{self.blocks[sid]}*{extra} > {self.cap_units}")
        self.pages.grow_to(self.rows[sid], int(n_pages))
        self.sid_pages[sid] = int(n_pages)
        self.units_used += self.blocks[sid] * extra

    def release(self, sid: int):
        row = self.rows.pop(sid, None)
        if row is None:
            return
        if self.layout == "paged":
            self.units_used -= self.blocks.pop(sid, 0) * \
                self.sid_pages.pop(sid, 0)
            self.pages.free_row(row)
        else:
            self.slots_used -= self.blocks.pop(sid, 0)
        self._free.append(row)
        # stale row contents are never observable: a new occupant's prefill
        # overwrites [:prompt_len] (recurrent states entirely), decode
        # attention masks kv_pos <= pos, and cross-attention masks
        # kv_pos < enc_len — so no zeroing (a full pool copy per retirement)
        # is needed.  The paged layout leans on the same invariant: freed
        # pages re-enter the free list with stale contents, and a reader
        # only ever sees a page through its own table entries at masked-in
        # positions it has itself written.

    def usage(self) -> Tuple[int, int]:
        """(used, capacity) in the layout's accounting unit: block-slots
        for slab, page-units (block-slots × pages) for paged."""
        if self.layout == "paged":
            return self.units_used, self.cap_units
        return self.slots_used, self.cap_slots

    def page_table(self) -> jnp.ndarray:
        """The device copy of the shared page table (paged layout)."""
        return jnp.asarray(self.pages.table)

    def n_sessions(self) -> int:
        return len(self.rows)

    # -- prefill writes -----------------------------------------------------
    def write_prefill_range(self, lo_rel: int, hi_rel: int, row: int,
                            entries: List[Dict], length: int):
        """Insert single-session per-layer cache entries (batch dim 1, one
        per layer in [lo_rel, hi_rel)) into the pool row.  Staged as ONE
        ranged update per leaf per run — a per-layer loop would copy the
        whole pool O(layers) times.  Self-KV leaves write [:length];
        cross-KV leaves write their own (encoder) length; recurrent state
        leaves overwrite whole.  Paged layout: self-KV tokens scatter into
        the row's physical pages (one ranged update per page — the serial
        reference path, so a handful of dispatches is fine)."""
        assert len(entries) == hi_rel - lo_rel
        new_tree = list(self.tree)
        for r, (kind, rlo, rhi) in enumerate(self.runs):
            lo, hi = max(lo_rel, rlo), min(hi_rel, rhi)
            if lo >= hi:
                continue
            sub = entries[lo - lo_rel: hi - lo_rel]
            t = dict(new_tree[r])
            for key in t:
                stacked = jnp.stack([e[key][0] for e in sub]).astype(
                    t[key].dtype)
                if key in _SELF_KV_KEYS:
                    if self.layout == "paged":
                        X = t[key]
                        pg = self.page_size
                        for pi in range(self.pages_needed(length)):
                            ppid = int(self.pages.table[row, pi])
                            a, b = pi * pg, min(length, (pi + 1) * pg)
                            X = X.at[lo - rlo:hi - rlo, ppid, :b - a].set(
                                stacked[:, a:b])
                        t[key] = X
                    else:
                        t[key] = t[key].at[lo - rlo:hi - rlo, row,
                                           :length].set(stacked[:, :length])
                elif key in _CROSS_KV_KEYS:
                    el = stacked.shape[1]
                    t[key] = t[key].at[lo - rlo:hi - rlo, row,
                                       :el].set(stacked)
                else:  # recurrent state: whole overwrite
                    t[key] = t[key].at[lo - rlo:hi - rlo, row].set(stacked)
            new_tree[r] = t
        self.tree = tuple(new_tree)


# ---------------------------------------------------------------------------
# Prompt-length bucketing (batched prefill)
# ---------------------------------------------------------------------------


def default_prefill_buckets(max_prompt_len: int, base: int = 8
                            ) -> Tuple[int, ...]:
    """Power-of-two bucket lengths up to ``max_prompt_len``.

    The returned tuple always ends with ``max_prompt_len`` itself, so by
    default every admissible prompt fits some bucket and chunking never
    triggers; pass an explicit smaller bucket set to the engine to force
    chunked prefill for long prompts.
    """
    max_prompt_len = int(max_prompt_len)
    assert max_prompt_len >= 1
    out: List[int] = []
    b = base
    while b < max_prompt_len:
        out.append(b)
        b *= 2
    out.append(max_prompt_len)
    return tuple(out)


def bucket_for(buckets: Sequence[int], length: int,
               specs: Optional[Sequence[StateSpec]] = None) -> Optional[int]:
    """Smallest bucket >= ``length``; None when the prompt overflows every
    bucket (the engine then chunks it into max-bucket-sized pieces).

    Family-aware rule: when ``specs`` contains any layer with RECURRENT
    state (rwkv, mamba — order-sensitive; trailing pad tokens would corrupt
    it), the bucket is the exact prompt length: grouping still batches
    equal lengths, but padding and chunking are attention-only."""
    if specs is not None and any(s.recurrent for s in specs):
        return int(length)
    for b in sorted(buckets):  # callers need not pre-sort
        if b >= length:
            return int(b)
    return None


# ---------------------------------------------------------------------------
# Kind-dispatched pooled steps (ONE jitted program per server)
# ---------------------------------------------------------------------------


def _mask_tree(new, old, active):
    """Keep ``old`` on inactive rows; leaves are (n_rows, ...)."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)),
            n.astype(o.dtype), o),
        new, old)


def _write_token(tree, layer, entry, pos, active):
    """Write each active row's new token into pooled leaves, in place.

    ``entry`` leaves are (n_rows, 1, ...); row r's lands at
    ``(layer, r, pos[r])`` of the (layers, n_rows, T, ...) leaf of the same
    key.  Inactive rows write nothing: their time index falls off the end
    and the scatter drops it.  ``pos`` clamps into [0, T) as
    ``dynamic_update_slice`` clamps it."""
    out = dict(tree)
    for key, new in entry.items():
        leaf = tree[key]
        n_rows, T = leaf.shape[1], leaf.shape[2]
        t = jnp.where(active, jnp.clip(pos, 0, T - 1), T)
        out[key] = leaf.at[layer, jnp.arange(n_rows), t].set(
            new[:, 0].astype(leaf.dtype), mode="drop")
    return out


def _masked_ranged_write(cache, chunk, active, keys, lo, span):
    """Ranged [lo, lo+span) masked write of chunk leaves named ``keys``."""
    out = dict(cache)
    for key in keys:
        old = cache[key][:, lo:lo + span]
        msk = active.reshape((-1,) + (1,) * (chunk[key].ndim - 1))
        out[key] = cache[key].at[:, lo:lo + span].set(
            jnp.where(msk, chunk[key].astype(old.dtype), old))
    return out


def _prefill_step_body(cfg: ModelConfig, kinds: Tuple[str, ...],
                       backend: str):
    """The UNJITTED prefill step body shared by :func:`make_pool_prefill_step`
    (every pool row), :func:`make_pool_row_prefill_step` (one indexed row,
    the served path) and their paged twins (which wrap it in the page
    gather/scatter).

    pstep(run_params, shared_params, pool_trees, h, emb0, enc_rows,
          layer_active, layer_ids, offset, phase) -> (h, pool_trees)

    * ``run_params``: tuple of per-run stacked block params (axis 0 = the
      run's layers); ``shared_params``: zamba2's parameter-shared attention
      block (None otherwise),
    * ``pool_trees``: tuple of per-run state subtrees (see ``CachePool``)
      whose row axis is the ``n_rows`` this call computes: the whole pool,
      or the one-row slab the row step takes out of it,
    * ``h``: (n_rows, T_chunk, d) right-padded hidden rows, row for row
      with ``pool_trees``,
    * ``emb0``: (n_rows, T_chunk, d) original embeddings for shared-attn
      blocks (a dummy leaf when no block needs it),
    * ``enc_rows``: (n_rows, T_enc, d) encoder outputs for cross-attention
      blocks (dummy otherwise),
    * ``offset``: STATIC chunk start (0 for unchunked prompts); attention
      rows attend over their pool cache [0, offset) plus the chunk and the
      chunk's K/V is written at [offset, offset+T_chunk),
    * ``phase``: STATIC — "all" (single-phase stacks), "enc" (run only
      encoder blocks; ``h`` carries frame embeddings) or "dec" (run only
      non-encoder blocks; ``h`` carries token embeddings),
    * ``layer_active``: (n_layers, n_rows) bool — row r runs layer l iff
      set; inactive rows keep their hidden state and state untouched.

    Like the decode step, the program depends only on shapes — never on
    which rows carry sessions — so per-session results are bit-for-bit
    identical between a group of one and a full bucket group.  Recurrent
    kinds (rwkv, mamba, mamba_shared) require ``offset == 0`` and
    ``T_chunk`` equal to the TRUE prompt length: their state is
    order-sensitive, so trailing pad tokens would corrupt it.  The engine
    therefore groups recurrent-stack sessions by exact prompt length.
    """
    from repro.kernels.runtime import resolve_backend
    from repro.models import blocks as B
    from repro.models.layers import NULL_SH

    resolve_backend(backend)
    runs = kind_runs(kinds)
    mla = cfg.attn_kind == "mla"

    def step(run_params, shared_params, pool_trees, h, emb0, enc_rows,
             layer_active, layer_ids, offset, phase):
        T = h.shape[1]
        positions = offset + jnp.arange(T)
        new_trees = list(pool_trees)
        for r, (kind, lo, hi) in enumerate(runs):
            if phase == "enc" and kind != "enc":
                continue
            if phase == "dec" and kind == "enc":
                continue
            if kind in ("rwkv", "mamba", "mamba_shared") and offset != 0:
                raise ValueError(
                    f"recurrent-state kind {kind!r} cannot resume prefill "
                    "at a nonzero chunk offset")
            p_stack, tree = run_params[r], pool_trees[r]
            act, lids = layer_active[lo:hi], layer_ids[lo:hi]

            if kind == "decoder":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr, cr):
                        if mla:
                            prefix = (cr["latent"][None, :offset],
                                      cr["krope"][None, :offset])
                        else:
                            prefix = (cr["k"][None, :offset],
                                      cr["v"][None, :offset])
                        hh, cc, _ = B.decoder_block_full(
                            p, cfg, NULL_SH, hr[None], positions, lid,
                            prefix_kv=prefix, backend=backend)
                        return hh[0], jax.tree.map(lambda x: x[0], cc)

                    h2, chunk = jax.vmap(one)(hc, cache)
                    c2 = _masked_ranged_write(cache, chunk, active,
                                              tuple(chunk), offset, T)
                    h2 = jnp.where(active[:, None, None], h2, hc)
                    return h2, c2
            elif kind in ("rwkv", "mamba"):
                blk = (B.rwkv_block_full if kind == "rwkv"
                       else B.mamba_block_full)

                def body(hc, xs, blk=blk):
                    p, cache, active, lid = xs

                    def one(hr):
                        hh, st = blk(p, cfg, NULL_SH, hr[None],
                                     backend=backend)
                        return hh[0], jax.tree.map(lambda x: x[0], st)

                    h2, st = jax.vmap(one)(hc)
                    c2 = _mask_tree(st, cache, active)
                    h2 = jnp.where(active[:, None, None], h2, hc)
                    return h2, c2
            elif kind == "mamba_shared":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr, er):
                        hh, st = B.mamba_block_full(p, cfg, NULL_SH, hr[None],
                                                    backend=backend)
                        hh, kv = B.zamba_shared_full(
                            shared_params, cfg, NULL_SH, hh, er[None],
                            positions, backend=backend)
                        return hh[0], {
                            "ssm": st["ssm"][0], "conv": st["conv"][0],
                            "k": kv["k"][0], "v": kv["v"][0]}

                    h2, st = jax.vmap(one)(hc, emb0)
                    c2 = dict(cache, **_mask_tree(
                        {"ssm": st["ssm"], "conv": st["conv"]},
                        {"ssm": cache["ssm"], "conv": cache["conv"]},
                        active))
                    c2 = _masked_ranged_write(c2, st, active, ("k", "v"),
                                              0, T)
                    h2 = jnp.where(active[:, None, None], h2, hc)
                    return h2, c2
            elif kind == "enc":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr):
                        return B.encoder_block_full(
                            p, cfg, NULL_SH, hr[None], positions,
                            backend=backend)[0]

                    h2 = jax.vmap(one)(hc)
                    h2 = jnp.where(active[:, None, None], h2, hc)
                    return h2, cache
            elif kind == "dec":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr, er, cr):
                        prefix = (cr["k"][None, :offset],
                                  cr["v"][None, :offset])
                        # cross-KV is offset-independent: computed on the
                        # first chunk, read back from the pool after
                        enc_kv = None if offset == 0 else (
                            cr["ck"][None, :er.shape[0]],
                            cr["cv"][None, :er.shape[0]])
                        hh, cc = B.cross_decoder_block_full(
                            p, cfg, NULL_SH, hr[None], positions, er[None],
                            prefix_kv=prefix, enc_kv=enc_kv,
                            backend=backend)
                        return hh[0], jax.tree.map(lambda x: x[0], cc)

                    h2, chunk = jax.vmap(one)(hc, enc_rows, cache)
                    c2 = _masked_ranged_write(cache, chunk, active,
                                              ("k", "v"), offset, T)
                    if offset == 0:  # cross-KV is chunk-independent
                        c2 = _masked_ranged_write(
                            c2, chunk, active, ("ck", "cv"), 0,
                            chunk["ck"].shape[1])
                    h2 = jnp.where(active[:, None, None], h2, hc)
                    return h2, c2
            else:
                raise ValueError(kind)

            h, new_tree = jax.lax.scan(body, h, (p_stack, tree, act, lids))
            new_trees[r] = new_tree
        return h, tuple(new_trees)

    return step


def _mesh_constraints(mesh, frozen_rules):
    """Sharding-constraint closures for a TP/EP device-group server.

    Returns ``(pools, rows, repl)``:

    * ``pools(trees)`` constrains a pool-tree tuple (slab or paged) to its
      :func:`repro.launch.sharding.pool_tree_shardings` layout,
    * ``rows(x, *logical)`` constrains one activation/vector by logical
      axes through the divisibility-guarded spec,
    * ``repl(x)`` pins per-round index vectors / masks replicated.

    Only built on the ``mesh is not None`` factory paths — the
    ``mesh=None`` twin never routes through this module's sharding code.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.sharding import (guarded_spec, pool_tree_shardings,
                                       thaw_rules)

    rules = thaw_rules(frozen_rules)

    def pools(trees):
        sh = pool_tree_shardings(mesh, rules, trees)
        return jax.tree.map(jax.lax.with_sharding_constraint, trees, sh)

    def rows(x, *logical):
        spec = guarded_spec(logical, x.shape, rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def repl(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))

    return pools, rows, repl


@functools.lru_cache(maxsize=None)
def make_pool_prefill_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                           backend: str = "xla", mesh=None, rules=None):
    """THE jitted multi-session prefill step for a hosted block range,
    shared per (cfg, per-layer kind tuple, compute backend[, mesh]) — see
    :func:`_prefill_step_body` for the calling contract.

    Pool trees donated: chunk writes update the pool in place (same
    aliasing contract as make_pool_decode_step — the caller rebinds its
    pool reference to the returned tree and never reads the old one).

    ``mesh``/``rules``: optional device-group sharding (``rules`` is a
    frozen rules mapping, see ``launch.sharding.freeze_rules``).  With a
    mesh, pool trees / hidden rows / params follow the NamedShardings the
    rules derive and XLA partitions the step across the group;
    ``mesh=None`` is the byte-identical single-device reference twin."""
    if mesh is None:
        return jax.jit(_prefill_step_body(cfg, kinds, backend),
                       static_argnums=(8, 9), donate_argnums=(2,))
    body = _prefill_step_body(cfg, kinds, backend)
    pools, rows, repl = _mesh_constraints(mesh, rules)

    def step(run_params, shared_params, pool_trees, h, emb0, enc_rows,
             layer_active, layer_ids, offset, phase):
        pool_trees = pools(pool_trees)
        h = rows(h, "batch", None, None)
        emb0 = rows(emb0, "batch", None, None)
        enc_rows = rows(enc_rows, "batch", None, None)
        layer_active, layer_ids = repl(layer_active), repl(layer_ids)
        h, new_trees = body(run_params, shared_params, pool_trees, h, emb0,
                            enc_rows, layer_active, layer_ids, offset,
                            phase)
        return rows(h, "batch", None, None), pools(new_trees)

    return jax.jit(step, static_argnums=(8, 9), donate_argnums=(2,))


def _take_row(pool_trees, row, whole=frozenset()):
    """Row ``row``'s slab of every pooled leaf, (layers, 1, ...), by a
    dynamic index on the row axis; leaves named in ``whole`` (the paged
    layout's physical page arrays) pass through as they are."""
    return tuple(
        {k: x if k in whole else jax.lax.dynamic_slice_in_dim(x, row, 1, 1)
         for k, x in t.items()}
        for t in pool_trees)


def _put_row(pool_trees, row_trees, row, whole=frozenset()):
    """Write one-row slabs back at ``row`` by a dynamic update on the row
    axis (in place into a donated pool); leaves named in ``whole`` are
    taken from ``row_trees`` as they are."""
    return tuple(
        {k: r[k] if k in whole else jax.lax.dynamic_update_slice_in_dim(
            x, r[k].astype(x.dtype), row, 1)
         for k, x in t.items()}
        for t, r in zip(pool_trees, row_trees))


@functools.lru_cache(maxsize=None)
def make_pool_row_prefill_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                               backend: str = "xla", mesh=None, rules=None):
    """THE served prefill dispatch: one session's prompt chunk, computed on
    its own pool row alone, indexed into the donated pool.

    step(run_params, shared_params, pool_trees, h, emb0, enc_rows, row,
         layer_active, layer_ids, offset, phase) -> (h, pool_trees)

    * ``h``: (1, T_chunk, d) the session's right-padded hidden rows;
      ``emb0`` (1, T_chunk, d) / ``enc_rows`` (1, T_enc, d) or the dummy,
    * ``row``: int32 scalar, the session's pool row,
    * ``layer_active``: (n_layers, 1) bool — the hop's layers,
    * the rest as in :func:`_prefill_step_body`.

    One jitted program: take the row's slab out of every pool leaf, run
    :func:`_prefill_step_body` over that one-row sub-pool, write it back
    in place.  No other row is read or rewritten.  The width is always one
    row, so a session runs the same program (one per ``T_chunk``) alone,
    in a group of any size and in failover replay: bit-identical by
    construction.  Pool trees donated, as in :func:`make_pool_prefill_step`;
    ``mesh``/``rules`` likewise (the one-row inputs pinned replicated)."""
    body = _prefill_step_body(cfg, kinds, backend)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def step(run_params, shared_params, pool_trees, h, emb0, enc_rows, row,
             layer_active, layer_ids, offset, phase):
        if cons is not None:
            pools, _rows, repl = cons
            pool_trees = pools(pool_trees)
            h, emb0, enc_rows = repl(h), repl(emb0), repl(enc_rows)
            layer_active, layer_ids = repl(layer_active), repl(layer_ids)
        h, sub = body(run_params, shared_params, _take_row(pool_trees, row),
                      h, emb0, enc_rows, layer_active, layer_ids, offset,
                      phase)
        new_trees = _put_row(pool_trees, sub, row)
        if cons is not None:
            h, new_trees = cons[2](h), cons[0](new_trees)
        return h, new_trees

    return jax.jit(step, static_argnums=(9, 10), donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def make_prefill_block(cfg: ModelConfig, kind: str, backend: str = "xla"):
    """Jitted single-session per-layer prefill (the serial reference path),
    shared across every server of the same (cfg, kind, backend) — jax's jit
    cache then reuses compiled programs for servers with identical shapes."""
    from repro.kernels.runtime import resolve_backend
    from repro.models import blocks as B
    from repro.models.layers import NULL_SH

    resolve_backend(backend)
    if kind == "decoder":
        return jax.jit(lambda p, h, positions, lid: B.decoder_block_full(
            p, cfg, NULL_SH, h, positions, lid, backend=backend))
    if kind == "rwkv":
        return jax.jit(lambda p, h: B.rwkv_block_full(p, cfg, NULL_SH, h,
                                                      backend=backend))
    if kind == "mamba":
        return jax.jit(lambda p, h: B.mamba_block_full(p, cfg, NULL_SH, h,
                                                       backend=backend))
    if kind == "mamba_shared":
        def f(p, shared, h, emb0, positions):
            h, st = B.mamba_block_full(p, cfg, NULL_SH, h, backend=backend)
            h, kv = B.zamba_shared_full(shared, cfg, NULL_SH, h, emb0,
                                        positions, backend=backend)
            return h, {"ssm": st["ssm"], "conv": st["conv"],
                       "k": kv["k"], "v": kv["v"]}
        return jax.jit(f)
    if kind == "enc":
        return jax.jit(lambda p, h, positions: B.encoder_block_full(
            p, cfg, NULL_SH, h, positions, backend=backend))
    if kind == "dec":
        return jax.jit(lambda p, h, positions, enc_h:
                       B.cross_decoder_block_full(p, cfg, NULL_SH, h,
                                                  positions, enc_h,
                                                  backend=backend))
    raise ValueError(
        f"no prefill block for kind {kind!r}; supported kinds: "
        + ", ".join(SUPPORTED_KINDS))


def _ep_row_grid(cfg: ModelConfig, mesh, frozen_rules, p_stack,
                 n_rows: int) -> Optional[Tuple[int, int]]:
    """(B, S) factorization of the decode row grid that routes a decoder
    run's MoE FFN through the pure-EP shard_map path, or None to keep the
    per-row reference trace.

    The gate mirrors ``moe._ep_eligible`` exactly — mesh present, PADDED
    expert weights, the (data, model) extents divide the regrouped
    ``(n_data, n_rows / n_data)`` token grid, batch rule mapped — plus the
    no-drop bound ``n_rows <= 8 * n_ep``: with at most 8 local tokens per
    EP shard no expert can exceed the minimum dispatch capacity, so the
    batched all-to-all path emits exactly the per-row reference mixture
    and the engine's token-parity contract survives.  Unpadded reduced
    configs always return None (byte-identical trace to today)."""
    if mesh is None or not cfg.is_moe:
        return None
    ffn = p_stack.get("ffn") if isinstance(p_stack, dict) else None
    if not isinstance(ffn, dict) or "wg" not in ffn:
        return None
    E_alloc = int(ffn["wg"].shape[1])  # (run_layers, E_alloc, d, f)
    if E_alloc == cfg.n_experts:
        return None
    from repro.launch.sharding import thaw_rules

    if thaw_rules(frozen_rules).get("batch") is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)
    n_data = int(np.prod([sizes[a] for a in ("pod", "data") if a in sizes]))
    n_ep = n_data * model
    if (n_rows % n_data or (n_rows // n_data) % model or E_alloc % n_ep
            or n_rows > 8 * n_ep):
        return None
    return n_data, n_rows // n_data


def _decode_step_body(cfg: ModelConfig, kinds: Tuple[str, ...],
                      backend: str, mesh=None, rules=None):
    """The UNJITTED pooled decode-step body shared by
    :func:`make_pool_decode_step` (row-buffer entry point) and
    :func:`make_pool_round_step` (the fused round-resident entry point).

    decode_step(run_params, shared_params, pool_trees, h, pos, emb0,
                enc_len, layer_active, layer_ids) -> (h, pool_trees)

    * ``run_params`` / ``shared_params`` / ``pool_trees``: as in
      :func:`make_pool_prefill_step`,
    * ``h``: (n_rows, 1, d_model) hidden rows,
    * ``pos``: (n_rows,) int32 cache write/attend position per row,
    * ``emb0``: (n_rows, 1, d_model) current-token embeddings for
      shared-attention blocks (dummy otherwise),
    * ``enc_len``: (n_rows,) int32 valid encoder length per row — masks the
      over-allocated cross-KV of enc-dec decoder blocks,
    * ``layer_active``: (n_layers, n_rows) bool — row r runs layer l iff set
      (a session's hop covers a contiguous sub-range of the server's blocks),
    * ``layer_ids``: (n_layers,) int32 absolute layer indices (for per-layer
      sliding-window patterns).

    Encoder runs are statically skipped (their StateSpec is not
    decode-active).  Decoder runs write each active row's new token into
    the pool in place; the other kinds compute whole per-row states and
    keep the old ones on inactive rows.  The computation always spans ALL
    rows with fixed shapes: adding or removing sessions changes only the
    mask, never the traced program, so per-session results are bit-for-bit
    identical between a crowded pool and a pool with a single resident
    session.
    """
    from repro.kernels.runtime import resolve_backend
    from repro.models import blocks as B
    from repro.models.layers import NULL_SH

    resolve_backend(backend)
    runs = kind_runs(kinds)

    ep_sh = None
    if mesh is not None:
        from repro.launch.sharding import thaw_rules
        from repro.models.layers import ShardingCtx

        ep_sh = ShardingCtx(mesh, thaw_rules(rules))

    def decoder_run(p_stack, tree, h, pos, active, layer_ids):
        """One run of decoder layers.  The pool rides the scan's carry, so
        the donated leaves are written in place, one token per active row
        per layer (:func:`_write_token`), and attention reads each layer
        where it lies.  The FFN half stays per row (or on the EP grid)."""
        grid = _ep_row_grid(cfg, mesh, rules, p_stack, h.shape[0])
        if grid is None:
            def ffn(p, h2):
                return jax.vmap(lambda hr: B.decoder_block_ffn(
                    p, cfg, NULL_SH, hr[None])[0])(h2)
        else:
            # Padded-MoE EP route: the position-free FFN half regroups the
            # rows into a (n_data, rows/n_data) token grid so apply_moe
            # takes the pure-EP all-to-all inside the pooled step.
            # _ep_row_grid's no-drop bound makes this emit the per-row
            # reference mixture exactly (token parity).
            n_data, rows_per = grid

            def ffn(p, h2):
                return B.decoder_block_ffn(
                    p, cfg, ep_sh,
                    h2.reshape(n_data, rows_per, h2.shape[-1]),
                ).reshape(h2.shape)

        def body(carry, xs):
            hc, cache = carry
            p, i, act, lid = xs
            query, new = B.decoder_decode_entry(p, cfg, hc, pos)
            cache = _write_token(cache, i, new, pos, act)
            h2 = B.decoder_decode_attend(
                p, cfg, hc, query, {k: cache[k][i] for k in new}, pos, lid,
                backend=backend)
            h2 = ffn(p, h2)
            return (jnp.where(act[:, None, None], h2, hc), cache), None

        (h, tree), _ = jax.lax.scan(
            body, (h, tree),
            (p_stack, jnp.arange(active.shape[0]), active, layer_ids))
        return h, tree

    def decode_step(run_params, shared_params, pool_trees, h, pos, emb0,
                    enc_len, layer_active, layer_ids):
        new_trees = list(pool_trees)
        for r, (kind, lo, hi) in enumerate(runs):
            if kind == "enc":  # stateless: no decode-time work
                continue
            p_stack, tree = run_params[r], pool_trees[r]
            act, lids = layer_active[lo:hi], layer_ids[lo:hi]

            if kind == "decoder":
                h, new_trees[r] = decoder_run(p_stack, tree, h, pos, act,
                                              lids)
                continue
            if kind in ("rwkv", "mamba"):
                blk = (B.rwkv_block_decode if kind == "rwkv"
                       else B.mamba_block_decode)

                def body(hc, xs, blk=blk):
                    p, cache, active, lid = xs

                    def one(hr, cr):
                        hh, cc = blk(p, cfg, NULL_SH, hr[None],
                                     jax.tree.map(lambda x: x[None], cr),
                                     backend=backend)
                        return hh[0], jax.tree.map(lambda x: x[0], cc)

                    h2, c2 = jax.vmap(one)(hc, cache)
                    return (jnp.where(active[:, None, None], h2, hc),
                            _mask_tree(c2, cache, active))
            elif kind == "mamba_shared":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr, er, cr, pr):
                        hh, st = B.mamba_block_decode(
                            p, cfg, NULL_SH, hr[None],
                            {"ssm": cr["ssm"][None], "conv": cr["conv"][None]},
                            backend=backend)
                        hh, kv = B.zamba_shared_decode(
                            shared_params, cfg, NULL_SH, hh, er[None],
                            {"k": cr["k"][None], "v": cr["v"][None]}, pr,
                            backend=backend)
                        return hh[0], {
                            "ssm": st["ssm"][0], "conv": st["conv"][0],
                            "k": kv["k"][0], "v": kv["v"][0]}

                    h2, c2 = jax.vmap(one)(hc, emb0, cache, pos)
                    return (jnp.where(active[:, None, None], h2, hc),
                            _mask_tree(c2, cache, active))
            elif kind == "dec":
                def body(hc, xs):
                    p, cache, active, lid = xs

                    def one(hr, cr, pr, el):
                        hh, cc = B.cross_decoder_block_decode(
                            p, cfg, NULL_SH, hr[None],
                            jax.tree.map(lambda x: x[None], cr), pr,
                            enc_len=el, backend=backend)
                        return hh[0], jax.tree.map(lambda x: x[0], cc)

                    h2, c2 = jax.vmap(one)(hc, cache, pos, enc_len)
                    return (jnp.where(active[:, None, None], h2, hc),
                            _mask_tree(c2, cache, active))
            else:
                raise ValueError(kind)

            h, new_tree = jax.lax.scan(body, h, (p_stack, tree, act, lids))
            new_trees[r] = new_tree
        return h, tuple(new_trees)

    return decode_step


@functools.lru_cache(maxsize=None)
def make_pool_decode_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                          backend: str = "xla", mesh=None, rules=None):
    """Jitted pooled decode step (see :func:`_decode_step_body` for the
    contract), shared per (cfg, per-layer kind tuple, compute backend) —
    each server calls it with its own (layers, rows) shapes.

    The pool trees (arg 2) are DONATED: the call updates each server's
    cache pool in place instead of copying every leaf per round.  Aliasing
    contract: after the call the input tree is dead — the caller MUST
    rebind its pool reference to the returned tree and never touch the old
    one (reading a donated leaf raises ``RuntimeError: Array has been
    deleted``).  ``BlockServer.decode_rows``/``round_rows`` do exactly
    that; see docs/serving.md "Round anatomy".

    ``mesh``/``rules``: optional TP/EP device-group sharding — see
    :func:`make_pool_prefill_step`.  ``mesh=None`` stays the untouched
    reference twin.
    """
    if mesh is None:
        return jax.jit(_decode_step_body(cfg, kinds, backend),
                       donate_argnums=(2,))
    body = _decode_step_body(cfg, kinds, backend, mesh, rules)
    pools, rows, repl = _mesh_constraints(mesh, rules)

    def decode_step(run_params, shared_params, pool_trees, h, pos, emb0,
                    enc_len, layer_active, layer_ids):
        pool_trees = pools(pool_trees)
        h = rows(h, "batch", None, None)
        pos = rows(pos, "batch")
        emb0 = rows(emb0, "batch", None, None)
        enc_len, layer_active, layer_ids = (repl(enc_len),
                                            repl(layer_active),
                                            repl(layer_ids))
        h, new_trees = body(run_params, shared_params, pool_trees, h, pos,
                            emb0, enc_len, layer_active, layer_ids)
        return rows(h, "batch", None, None), pools(new_trees)

    return jax.jit(decode_step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def make_pool_round_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                         backend: str = "xla", mesh=None, rules=None):
    """Build THE fused per-(hop, server) dispatch of a device-resident
    decode round: gather the hop's rows out of the round buffers, run the
    pooled decode step, scatter the results back — ONE jitted call, no host
    round-trip between hops.

    hop(run_params, shared_params, pool_trees, h_round, pos_round,
        emb0_round, encl_round, slot_of_row, row_of_slot, layer_active,
        layer_ids) -> (h_round, pool_trees)

    * ``h_round``: (W, 1, d) round-resident hidden states — one slot per
      session of the round (W is the engine's fixed round width, so the
      program never re-traces as sessions come and go),
    * ``pos_round`` (W,) / ``encl_round`` (W,): per-slot cache position and
      encoder length; ``emb0_round``: (W, 1, d) round-start embeddings for
      shared-attention stacks (the engine's constant-shape dummy otherwise),
    * ``slot_of_row``: (n_rows,) int32 — for each pool row, the round slot
      feeding it this hop (-1 for rows not in the hop; they receive a
      clipped placeholder gather that ``layer_active`` masks out),
    * ``row_of_slot``: (W,) int32 — for each round slot, the pool row whose
      result it takes back (-1 keeps the slot's hidden state untouched),
    * ``layer_active`` / ``layer_ids``: as in the decode step.

    Per-slot results are bit-identical to staging the same rows through
    :func:`make_pool_decode_step`: the gather feeds each ACTIVE row exactly
    the values the host path would have scattered in, rows are computed
    independently, and inactive rows/slots are masked.  The
    pool trees (arg 2) are DONATED — same aliasing contract as
    :func:`make_pool_decode_step`.

    ``mesh``/``rules``: optional TP/EP device-group sharding.  The round
    buffers and per-round index vectors (``slot_of_row``/``row_of_slot``)
    are pinned replicated over the group; the pool trees follow the cache
    rules — the resharding between the two layouts is XLA's, still ONE
    dispatch per (hop, server).
    """
    body = _decode_step_body(cfg, kinds, backend, mesh, rules)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def hop(run_params, shared_params, pool_trees, h_round, pos_round,
            emb0_round, encl_round, slot_of_row, row_of_slot, layer_active,
            layer_ids):
        W = h_round.shape[0]
        n_rows = slot_of_row.shape[0]
        if cons is not None:
            pools, _rows, repl = cons
            pool_trees = pools(pool_trees)
            h_round, pos_round = repl(h_round), repl(pos_round)
            slot_of_row, row_of_slot = repl(slot_of_row), repl(row_of_slot)
        src = jnp.clip(slot_of_row, 0, W - 1)
        h = h_round[src]
        pos = pos_round[src]
        # the dummy emb0 is (1, 1, 1): clip separately so the gather stays
        # in bounds whatever the engine passed
        emb0 = emb0_round[jnp.clip(src, 0, emb0_round.shape[0] - 1)]
        enc_len = encl_round[src]
        h_out, new_trees = body(run_params, shared_params, pool_trees, h,
                                pos, emb0, enc_len, layer_active, layer_ids)
        back = h_out[jnp.clip(row_of_slot, 0, n_rows - 1)]
        keep = (row_of_slot >= 0)[:, None, None]
        out = jnp.where(keep, back, h_round)
        if cons is not None:
            out, new_trees = cons[2](out), cons[0](new_trees)
        return out, new_trees

    return jax.jit(hop, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# Paged step factories: gather pages -> run the slab body -> scatter back
# ---------------------------------------------------------------------------
#
# The paged entry points do NOT reimplement any block math.  They gather
# each row's pages into a scratch tree whose self-KV leaves have the exact
# (layers, n_rows, max_len, ...) slab shape, run the UNCHANGED slab step
# body on it, and scatter the written positions back into the physical
# page arrays.  Bit-exactness vs the slab layout follows from two facts:
# positions inside a session's pages carry the same values either way, and
# positions outside (trash-page garbage where slab holds stale rows) are
# only ever read through the causal / enc-len masks, whose -1e30 logits
# underflow to EXACTLY zero probability in both layouts.  One trace per
# server is preserved: the page table is a runtime int32 operand.


def _gather_paged(runs, pool_trees, page_table, page_size: int):
    """Expand physical pages into slab-shaped scratch: self-KV leaves
    (L, n_phys, page, ...) -> (L, n_rows, max_pages*page, ...) via one
    fancy-indexed gather per leaf; row-resident leaves pass through."""
    n_rows, max_pages = page_table.shape
    scratch = []
    for r, _run in enumerate(runs):
        t = dict(pool_trees[r])
        for key in t:
            if key in _SELF_KV_KEYS:
                X = t[key]
                g = X[:, page_table]  # (L, n_rows, max_pages, page, ...)
                t[key] = g.reshape((X.shape[0], n_rows,
                                    max_pages * page_size) + X.shape[3:])
        scratch.append(t)
    return tuple(scratch)


def _scatter_paged(runs, pool_trees, scratch, page_table, page_size: int,
                   pos=None):
    """Fold the body's scratch updates back into the physical page arrays.

    ``pos is None`` (prefill): every table entry writes back its page —
    rows the body masked out write their own gathered values (a no-op).
    ``pos`` (n_rows,) (decode): only the single page containing each row's
    write position scatters back (a vmapped dynamic slice) — all other
    pages are untouched by a decode step.  Unassigned entries target the
    shared trash page 0; its content is unspecified but unobservable
    (masked-in positions always live in assigned pages).  Row-resident
    leaves take the body's output directly."""
    n_rows, max_pages = page_table.shape
    new_trees = list(pool_trees)
    for r, _run in enumerate(runs):
        t = dict(scratch[r])
        for key in pool_trees[r]:
            if key not in _SELF_KV_KEYS:
                continue
            X = pool_trees[r][key]  # (L, n_phys, page, ...) — donated
            S = scratch[r][key]     # (L, n_rows, max_len, ...)
            if pos is None:
                val = S.reshape((S.shape[0], n_rows, max_pages, page_size)
                                + S.shape[3:])
                t[key] = X.at[:, page_table].set(val)
            else:
                pidx = jnp.clip(pos // page_size, 0, max_pages - 1)
                ppid = jnp.take_along_axis(page_table, pidx[:, None],
                                           axis=1)[:, 0]

                def one(s_row, p):
                    return jax.lax.dynamic_slice_in_dim(
                        s_row, p * page_size, page_size, axis=1)

                val = jax.vmap(one, in_axes=(1, 0), out_axes=1)(S, pidx)
                t[key] = X.at[:, ppid].set(val)
        new_trees[r] = t
    return tuple(new_trees)


@functools.lru_cache(maxsize=None)
def make_paged_decode_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                           backend: str = "xla", page_size: int = 16,
                           mesh=None, rules=None):
    """Paged twin of :func:`make_pool_decode_step`: same contract with one
    extra runtime operand, the int32 page table, inserted after the pool
    trees.  The pool trees (arg 2) are donated — same aliasing contract.
    ``mesh``/``rules``: optional device-group sharding (page table pinned
    replicated; physical page arrays follow the cache rules)."""
    body = _decode_step_body(cfg, kinds, backend, mesh, rules)
    runs = kind_runs(kinds)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def decode_step(run_params, shared_params, pool_trees, page_table, h,
                    pos, emb0, enc_len, layer_active, layer_ids):
        if cons is not None:
            pools, rows, repl = cons
            pool_trees, page_table = pools(pool_trees), repl(page_table)
            h, pos = rows(h, "batch", None, None), rows(pos, "batch")
        scratch = _gather_paged(runs, pool_trees, page_table, page_size)
        h_out, new_scratch = body(run_params, shared_params, scratch, h,
                                  pos, emb0, enc_len, layer_active,
                                  layer_ids)
        new_trees = _scatter_paged(runs, pool_trees, new_scratch,
                                   page_table, page_size, pos)
        if cons is not None:
            h_out = cons[1](h_out, "batch", None, None)
            new_trees = cons[0](new_trees)
        return h_out, new_trees

    return jax.jit(decode_step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def make_paged_prefill_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                            backend: str = "xla", page_size: int = 16,
                            mesh=None, rules=None):
    """Paged twin of :func:`make_pool_prefill_step` (page table inserted
    after the pool trees; ``offset``/``phase`` stay static).
    ``mesh``/``rules``: optional device-group sharding."""
    body = _prefill_step_body(cfg, kinds, backend)
    runs = kind_runs(kinds)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def step(run_params, shared_params, pool_trees, page_table, h, emb0,
             enc_rows, layer_active, layer_ids, offset, phase):
        if cons is not None:
            pools, rows, repl = cons
            pool_trees, page_table = pools(pool_trees), repl(page_table)
            h = rows(h, "batch", None, None)
        scratch = _gather_paged(runs, pool_trees, page_table, page_size)
        h_out, new_scratch = body(run_params, shared_params, scratch, h,
                                  emb0, enc_rows, layer_active, layer_ids,
                                  offset, phase)
        new_trees = _scatter_paged(runs, pool_trees, new_scratch,
                                   page_table, page_size)
        if cons is not None:
            h_out = cons[1](h_out, "batch", None, None)
            new_trees = cons[0](new_trees)
        return h_out, new_trees

    return jax.jit(step, static_argnums=(9, 10), donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def make_paged_row_prefill_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                                backend: str = "xla", page_size: int = 16,
                                mesh=None, rules=None):
    """Paged twin of :func:`make_pool_row_prefill_step` (page table inserted
    after the pool trees; ``offset``/``phase`` stay static): gathers the
    row's pages alone through its page-table row, runs the same body on
    that one-row scratch, scatters those pages back, and writes the row's
    row-resident leaves in place.  Other rows' pages are not touched."""
    body = _prefill_step_body(cfg, kinds, backend)
    runs = kind_runs(kinds)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def step(run_params, shared_params, pool_trees, page_table, h, emb0,
             enc_rows, row, layer_active, layer_ids, offset, phase):
        if cons is not None:
            pools, _rows, repl = cons
            pool_trees, page_table = pools(pool_trees), repl(page_table)
            h, emb0, enc_rows = repl(h), repl(emb0), repl(enc_rows)
            layer_active, layer_ids = repl(layer_active), repl(layer_ids)
        pages = jax.lax.dynamic_slice_in_dim(page_table, row, 1, 0)
        sub = _take_row(pool_trees, row, _SELF_KV_KEYS)
        scratch = _gather_paged(runs, sub, pages, page_size)
        h, new_scratch = body(run_params, shared_params, scratch, h, emb0,
                              enc_rows, layer_active, layer_ids, offset,
                              phase)
        sub = _scatter_paged(runs, sub, new_scratch, pages, page_size)
        new_trees = _put_row(pool_trees, sub, row, _SELF_KV_KEYS)
        if cons is not None:
            h, new_trees = cons[2](h), cons[0](new_trees)
        return h, new_trees

    return jax.jit(step, static_argnums=(10, 11), donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def make_paged_round_step(cfg: ModelConfig, kinds: Tuple[str, ...],
                          backend: str = "xla", page_size: int = 16,
                          mesh=None, rules=None):
    """Paged twin of :func:`make_pool_round_step`: the fused
    gather+step+scatter hop over the round buffers, with the page
    gather/scatter wrapped around the same decode body.  Rows outside the
    hop scatter their own gathered page back (their ``pos`` placeholder is
    arbitrary but the page it selects belongs to the row — a no-op write,
    or the trash page when unassigned).  ``mesh``/``rules``: optional
    device-group sharding (round buffers + page table replicated)."""
    body = _decode_step_body(cfg, kinds, backend, mesh, rules)
    runs = kind_runs(kinds)
    cons = None if mesh is None else _mesh_constraints(mesh, rules)

    def hop(run_params, shared_params, pool_trees, page_table, h_round,
            pos_round, emb0_round, encl_round, slot_of_row, row_of_slot,
            layer_active, layer_ids):
        W = h_round.shape[0]
        n_rows = slot_of_row.shape[0]
        if cons is not None:
            pools, _rows, repl = cons
            pool_trees, page_table = pools(pool_trees), repl(page_table)
            h_round, pos_round = repl(h_round), repl(pos_round)
            slot_of_row, row_of_slot = repl(slot_of_row), repl(row_of_slot)
        src = jnp.clip(slot_of_row, 0, W - 1)
        h = h_round[src]
        pos = pos_round[src]
        emb0 = emb0_round[jnp.clip(src, 0, emb0_round.shape[0] - 1)]
        enc_len = encl_round[src]
        scratch = _gather_paged(runs, pool_trees, page_table, page_size)
        h_out, new_scratch = body(run_params, shared_params, scratch, h,
                                  pos, emb0, enc_len, layer_active,
                                  layer_ids)
        new_trees = _scatter_paged(runs, pool_trees, new_scratch,
                                   page_table, page_size, pos)
        back = h_out[jnp.clip(row_of_slot, 0, n_rows - 1)]
        keep = (row_of_slot >= 0)[:, None, None]
        out = jnp.where(keep, back, h_round)
        if cons is not None:
            out, new_trees = cons[2](out), cons[0](new_trees)
        return out, new_trees

    return jax.jit(hop, donate_argnums=(2,))
