"""Geo-distributed serving engine: the PETALS architecture natively in JAX,
with continuous batching across sessions.

Executes REAL block-level forward passes according to a BPRR placement with
client-centric (hub-spoke) communication and client-side input caches —
the paper's Fig. 1 — while a virtual clock accounts time with the validated
performance models (eq. (1)): the engine cross-validates the simulator.

Multi-session execution (eq. (5)/(20) semantics):

* every server keeps ONE family-polymorphic stacked state pool
  (``repro.serving.kv_cache``) whose rows are per-session slots; a single
  jitted step — vmapped over rows, scanned over the server's hosted block
  runs — decodes every resident session at once.  The decode round is
  DEVICE-RESIDENT (``decode_mode="fused"``): one batched embed, one fused
  gather+step+scatter dispatch per (hop, server) over fixed-width round
  buffers, one fused lm_head+sample tail, one host sync per round, with
  every pooled step donating its cache pool (in-place update — see
  docs/serving.md "Round anatomy" for the aliasing contract).  Which
  state a block row
  carries (KV tensors, MLA latents, SSM+conv state, wkv/shift state,
  self-KV + encoder cross-KV) is dispatched per block via its
  :class:`~repro.serving.kv_cache.StateSpec`; the pool shape is fixed, so
  the step traces exactly once per server: admitting/retiring sessions
  flips mask bits instead of re-tracing, and per-session results are
  bit-for-bit identical whether a session runs alone or among
  ``max_sessions`` neighbours.
* cache block-slots follow the paper's memory model: server j has
  ⌊(M_j − s_m·m_j)/s_c⌋ slots; a session routed through k_j of its blocks
  occupies k_j slots from start to retirement (no-overbooking commitment).
  ``try_admit_session``/``retire_session`` enforce the budget; the
  continuous-batching scheduler (repro.serving.scheduler) defers sessions
  that do not fit and re-admits them as slots free.

Fault tolerance (DESIGN.md §7) is unchanged in spirit and now concurrent:
client-side per-hop input caches let a failed block range be re-routed over
surviving servers and replayed exactly — with any number of co-resident
sessions.  Elastic join/leave triggers CG-BP re-placement at the slow time
scale; stragglers feed per-server slowdowns into the routing costs.

Supported block families (``kv_cache.SUPPORTED_KINDS``): "decoder" (dense /
MoE / VLM / gemma-pattern), "rwkv" (attention-free), "mamba" /
"mamba_shared" (zamba2 hybrids), and "enc" / "dec" (seamless
encoder-decoder).  Enc-dec sessions carry encoder ``frames`` alongside the
decoder prompt; hybrid stacks thread the original embedding (``emb0``) to
the parameter-shared attention blocks.  Token selection is per-session
policy (``repro.serving.sampling.SamplingSpec``): seeded greedy /
temperature / top-k, threaded through the pooled rounds as vmapped row
inputs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tracing
from repro.configs.base import ModelConfig
from repro.core.perf_model import Placement, Problem, Route
from repro.core.placement import petals_bp
from repro.core.routing import petals_route, shortest_path_route
from repro.models.layers import NULL_SH, embed_frames, embed_tokens, lm_head
from repro.models.model import block_param_range
from repro.serving.faults import (FailureDetector, FaultPlan,
                                  NoCapacityError, recovery_replay_cost)
from repro.serving.kv_cache import (CachePool, bucket_for,
                                    default_prefill_buckets, kind_runs,
                                    make_paged_decode_step,
                                    make_paged_round_step,
                                    make_paged_row_prefill_step,
                                    make_pool_decode_step,
                                    make_pool_round_step,
                                    make_pool_row_prefill_step,
                                    make_prefill_block, pages_for,
                                    state_specs)
from repro.serving.sampling import (SamplingSpec, make_round_tail,
                                    make_sampler)


@dataclass
class EngineSession:
    """Client-side state for one session: its route, token buffer, per-hop
    input history (the failover replay cache), and the virtual-clock
    accounting (prefill / per-token / end times per eq. (1)).

    Enc-dec sessions additionally carry the encoder input ``frames``
    (S_enc, frame_dim), its length, and — once prefilled — the encoder
    output ``enc_out`` (a client-side artifact, like the hop histories:
    failover replay rebuilds cross-KV from it)."""

    sid: int
    client: int
    route: Route
    prompt_len: int
    n_new: int
    arrival: float = 0.0
    start: float = 0.0
    pos: int = 0  # next cache write position
    tokens: List[int] = field(default_factory=list)  # prompt + generated
    n_generated: int = 0
    # admitted | prefilling | active | preempted | failed | done —
    # "preempted": evicted from every route server (page pressure, or a
    # capacity-starved failover deferral), resumable via the
    # failover-replay machinery
    state: str = "admitted"
    # machine-readable reason when state == "failed" (e.g. "no_route",
    # "no_capacity", "server_lost_mid_prefill")
    fail_reason: Optional[str] = None
    n_preemptions: int = 0  # times this session was swapped out
    # failure-recovery accounting (timeout detection -> backoff -> billed
    # replay; see docs/concurrency.md "Failure model")
    n_detections: int = 0  # timeout-detected server losses on this route
    n_retries: int = 0  # backoff probes sent while confirming a suspect
    n_replays: int = 0  # cache rebuilds billed (failover or resume)
    detect_time: float = 0.0  # deadline waits (virtual seconds)
    backoff_time: float = 0.0  # probe backoff sleeps (virtual seconds)
    replay_time: float = 0.0  # replay compute + input RTTs (virtual s)
    n_defer_resumes: int = 0  # capacity-deferral resume attempts
    # per-hop input history (the PETALS fault-tolerance cache); entry 0 is
    # the prompt-phase record — a plain array for single-phase stacks, a
    # {"enc": ..., "dec": ...} dict for enc-dec — followed by one record per
    # decoded token that flowed through the hop: a (1, 1, d) array on the
    # host-staged paths, or a lazy ((members, 1, d) hop gather, index)
    # tuple on the fused path (materialized by GeoServingSystem._hop_record
    # only if a failover replays it)
    hop_inputs: List[List] = field(default_factory=list)
    sampling: SamplingSpec = field(default_factory=SamplingSpec)
    frames: Optional[np.ndarray] = None  # encoder input (enc-dec only)
    enc_len: int = 0
    enc_out: Optional[jnp.ndarray] = None  # encoder output (client cache)
    virtual_time: float = 0.0  # accumulated service time (prefill + decode)
    prefill_time: float = 0.0
    per_token_time: float = 0.0
    end: float = float("inf")
    # logits behind tokens[-1]: a concrete (V,)/(1, V) array, or — on the
    # fused round path — a lazy ((W, V) rows, slot) reference materialized
    # on first read so the round hot loop never pays per-session slicing
    # dispatches (see the ``last_logits`` property below)
    _logits_box: Optional[object] = None
    # transient per-round hidden state / original embedding
    _h: Optional[jnp.ndarray] = None
    _emb0: Optional[jnp.ndarray] = None

    @property
    def last_logits(self) -> Optional[jnp.ndarray]:
        box = self._logits_box
        if isinstance(box, tuple):  # lazy (rows, slot) from a fused round
            rows, g = box
            box = rows[g]
            self._logits_box = box
        return box

    @last_logits.setter
    def last_logits(self, value):
        self._logits_box = value

    @property
    def recovery_time(self) -> float:
        """Total virtual-clock time this session spent recovering from
        failures: detection waits + backoff sleeps + billed replay."""
        return self.detect_time + self.backoff_time + self.replay_time


class BlockServer:
    """One 'server': params for its block range + a stacked session pool.

    The hosted range may mix block families; ``self.kinds`` is its static
    per-layer kind tuple and ``self.runs`` the contiguous same-kind runs
    the pooled steps scan over.  Exposes the pooled compute entry points,
    each tracing once per server (and, for prefill, per chunk width):
    :meth:`round_rows` / :meth:`decode_rows` (one token for every masked
    row) and :meth:`prefill_row` (one session's padded prompt chunk on its
    own pool row, indexed into the donated pool).
    """

    def __init__(self, sid: int, cfg: ModelConfig, params, a: int, m: int,
                 *, n_rows: int, max_len: int, cap_slots: int,
                 enc_len: int = 0, slowdown: float = 1.0,
                 backend: str = "xla", cache_layout: str = "slab",
                 page_size: int = 0, mesh=None, mesh_rules=None,
                 group=None):
        self.sid = sid
        self.backend = backend
        self.cfg = cfg
        self.a, self.m = int(a), int(m)
        self.specs = state_specs(cfg)[self.a: self.a + self.m]
        self.kinds = tuple(s.kind for s in self.specs)
        self.runs = kind_runs(self.kinds)
        self.n_enc = cfg.n_enc_layers
        # per-run stacked block params (axis 0 over the run's layers)
        self.run_params = tuple(
            block_param_range(params, cfg, kind, self.a + lo, self.a + hi)
            for kind, lo, hi in self.runs)
        self.shared = params.get("shared")  # zamba2 shared attention
        self.layer_ids = jnp.arange(self.a, self.a + self.m, dtype=jnp.int32)
        self.cache_layout = cache_layout
        self.pool = CachePool(cfg, self.kinds, n_rows, max_len, cap_slots,
                              enc_len=enc_len, layout=cache_layout,
                              page_size=page_size)
        self.alive = True
        # crashed: the server stopped responding but no client has noticed
        # yet — dispatches to it miss their deadline and the engine bills
        # timeout detection before flipping ``alive`` (FaultPlan path).
        # suspected: it was once declared dead by timeout; routing keeps an
        # additive cost penalty against it even after a rejoin.
        self.crashed = False
        self.suspected = False
        self.slowdown = slowdown
        # Optional TP/EP device group: this server's params + pool live
        # sharded over the group's mesh per the logical-axis rules, and its
        # pooled steps constrain every operand accordingly (docs/serving.md
        # "Device-group servers").  A solo group (mesh=None) is the
        # single-device twin.  `group` is the DeviceGroup descriptor (or a
        # bare Mesh / None); `mesh`/`mesh_rules` remain as sugar for a
        # single-group server.
        from repro.launch.sharding import DeviceGroup, as_device_group

        if group is None:
            group = as_device_group(mesh)
            if mesh_rules is not None and group.rules is None:
                group = DeviceGroup(mesh=group.mesh, rules=mesh_rules)
        else:
            group = as_device_group(group)
        self.group = group
        self.mesh = mesh = group.mesh
        self.n_chips = group.n_chips
        # the device this server computes on (its group's first; the solo
        # twin runs on the default device)
        self.device = (group.devices or jax.devices())[0]
        # where hop inputs must live: a round buffer arrives committed to
        # the previous hop server's devices, which may be other chips
        self._inputs_at = (None if mesh is None
                           else NamedSharding(mesh, P()))
        if mesh is not None:
            from repro.launch.sharding import (
                block_param_shardings, pool_tree_shardings, thaw_rules)
            from repro.models.model import block_param_axes

            frozen = group.frozen_rules_for(cfg, n_rows, max_len)
            rules = thaw_rules(frozen)
            self.mesh_rules = rules
            self.run_params = tuple(
                jax.device_put(p, block_param_shardings(
                    mesh, rules, block_param_axes(cfg, kind), p))
                for p, (kind, _lo, _hi) in zip(self.run_params, self.runs))
            if self.shared is not None:
                self.shared = jax.device_put(
                    self.shared, jax.tree.map(
                        lambda _: NamedSharding(mesh, P()), self.shared))
            self.pool.tree = jax.device_put(
                self.pool.tree,
                pool_tree_shardings(mesh, rules, self.pool.tree))
        else:
            self.mesh_rules = None
            frozen = None
        if cache_layout == "paged":
            self._step = make_paged_decode_step(cfg, self.kinds, backend,
                                                page_size, mesh, frozen)
            self._round_step = make_paged_round_step(cfg, self.kinds,
                                                     backend, page_size,
                                                     mesh, frozen)
            self._prefill_row = make_paged_row_prefill_step(
                cfg, self.kinds, backend, page_size, mesh, frozen)
        else:
            self._step = make_pool_decode_step(cfg, self.kinds, backend,
                                               mesh, frozen)
            self._round_step = make_pool_round_step(cfg, self.kinds,
                                                    backend, mesh, frozen)
            self._prefill_row = make_pool_row_prefill_step(
                cfg, self.kinds, backend, mesh, frozen)
        self._prefill_blocks = {k: make_prefill_block(cfg, k, backend)
                                for k in set(self.kinds)}
        # constant-shape filler for unused emb0/enc_rows step inputs, so the
        # jit trace key never varies with them
        self._dummy = jnp.zeros((1, 1, 1), jnp.float32)
        self._zero_encl = jnp.zeros((n_rows,), jnp.int32)

    # -- session admission bookkeeping --------------------------------------
    def fits(self, sid: int, k_blocks: int, n_pages: int = 0,
             worst_pages: Optional[int] = None) -> bool:
        if self.cache_layout == "paged":
            return self.pool.fits(sid, k_blocks, n_pages, worst_pages)
        return self.pool.fits(sid, k_blocks)

    def admit(self, sid: int, k_blocks: int, n_pages: int = 0) -> int:
        return self.pool.alloc(sid, k_blocks, n_pages)

    def evict(self, sid: int):
        self.pool.release(sid)

    def n_sessions(self) -> int:
        return self.pool.n_sessions()

    # -- compute ------------------------------------------------------------
    def _local(self, *xs):
        """Hop inputs moved onto this server's devices (a no-op for the solo
        twin and for inputs already there)."""
        if self._inputs_at is None:
            return xs
        return jax.device_put(xs, self._inputs_at)

    def _layer_params(self, l_rel: int):
        for r, (kind, lo, hi) in enumerate(self.runs):
            if lo <= l_rel < hi:
                return jax.tree.map(lambda x: x[l_rel - lo],
                                    self.run_params[r])
        raise IndexError(l_rel)

    def prefill_range(self, sid: int, h, lo: int, hi: int, positions,
                      emb0=None, enc_h=None):
        """Prefill blocks [lo, hi) for one session (serial reference path);
        fills its pool row.  ``emb0``/``enc_h``: the extra inputs shared-
        attention / cross-attention blocks need."""
        assert self.alive, f"server {self.sid} is dead"
        row = self.pool.rows[sid]
        S = h.shape[1]
        entries = []
        for l in range(lo, hi):
            kind = self.kinds[l - self.a]
            p = self._layer_params(l - self.a)
            fb = self._prefill_blocks[kind]
            if kind == "decoder":
                h, cache, _ = fb(p, h, positions, jnp.int32(l))
            elif kind in ("rwkv", "mamba"):
                h, cache = fb(p, h)
            elif kind == "mamba_shared":
                h, cache = fb(p, self.shared, h, emb0, positions)
            elif kind == "enc":
                h = fb(p, h, positions)
                cache = {}
            else:  # dec
                h, cache = fb(p, h, positions, enc_h)
            entries.append(cache)
        self.pool.write_prefill_range(lo - self.a, hi - self.a, row,
                                      entries, S)
        return h

    def hop_layers(self, lo: int, hi: int):
        """(m, 1) layer mask of one row through absolute blocks [lo, hi)."""
        mask = np.zeros((self.m, 1), bool)
        mask[lo - self.a: hi - self.a] = True
        return jnp.asarray(mask)

    def prefill_row(self, h, row: int, layer_active, offset: int = 0,
                    phase: str = "all", emb0=None, enc=None):
        """THE prefill dispatch: one session's (padded) prompt chunk ``h``
        (1, T_chunk, d) starting at ``offset``, through the layers
        ``layer_active`` (m, 1) marks, computed on pool row ``row`` alone
        and written into the donated pool in place (see
        make_pool_row_prefill_step).  ``phase`` selects encoder vs
        non-encoder runs for enc-dec stacks; ``emb0`` / ``enc``: the
        session's (1, ., d) shared-attention / encoder inputs."""
        assert self.alive, f"server {self.sid} is dead"
        args = self._local(h, self._dummy if emb0 is None else emb0,
                           self._dummy if enc is None else enc,
                           np.int32(row), layer_active) + (
                               self.layer_ids, offset, phase)
        if self.cache_layout == "paged":
            h_out, self.pool.tree = self._prefill_row(
                self.run_params, self.shared, self.pool.tree,
                self.pool.page_table(), *args)
        else:
            h_out, self.pool.tree = self._prefill_row(
                self.run_params, self.shared, self.pool.tree, *args)
        return h_out

    def decode_rows(self, h_rows, pos_rows, layer_active, emb0_rows=None,
                    enc_len_rows=None):
        """THE batched step: one jitted call decodes all masked rows.

        The pool tree is donated into the step (cache updated in place);
        the stale input tree is rebound here and must never be read again
        — see docs/serving.md "Round anatomy" for the aliasing contract."""
        assert self.alive, f"server {self.sid} is dead"
        args = self._local(
            h_rows, pos_rows,
            self._dummy if emb0_rows is None else emb0_rows,
            self._zero_encl if enc_len_rows is None else enc_len_rows,
            layer_active) + (self.layer_ids,)
        if self.cache_layout == "paged":
            h_out, self.pool.tree = self._step(
                self.run_params, self.shared, self.pool.tree,
                self.pool.page_table(), *args)
        else:
            h_out, self.pool.tree = self._step(
                self.run_params, self.shared, self.pool.tree, *args)
        return h_out

    def round_rows(self, h_round, pos_round, encl_round, slot_of_row,
                   row_of_slot, layer_active, emb0_round=None):
        """The fused device-resident hop: gather this server's rows out of
        the round buffers, decode them through the pooled step, scatter the
        results back — ONE dispatch, donated pool, no host transfer."""
        assert self.alive, f"server {self.sid} is dead"
        args = self._local(
            h_round, pos_round,
            self._dummy if emb0_round is None else emb0_round,
            encl_round, slot_of_row, row_of_slot,
            layer_active) + (self.layer_ids,)
        if self.cache_layout == "paged":
            h_round, self.pool.tree = self._round_step(
                self.run_params, self.shared, self.pool.tree,
                self.pool.page_table(), *args)
        else:
            h_round, self.pool.tree = self._round_step(
                self.run_params, self.shared, self.pool.tree, *args)
        return h_round

    def decode_range(self, sid: int, h, lo: int, hi: int, pos: int,
                     emb0=None, enc_len: int = 0):
        """Single-session decode of blocks [lo, hi) via the pooled step (the
        same program as the batched path — bit-for-bit identical).  Encoder
        blocks in the range are skipped (no decode-time work)."""
        lo = max(lo, self.n_enc)
        if lo >= hi:
            return h
        row = self.pool.rows[sid]
        N = self.pool.n_rows
        h_rows = jnp.zeros((N,) + h.shape[1:], h.dtype).at[row].set(h[0])
        pos_rows = jnp.zeros((N,), jnp.int32).at[row].set(pos)
        emb0_rows = None
        if emb0 is not None:
            emb0_rows = jnp.zeros((N,) + emb0.shape[1:],
                                  emb0.dtype).at[row].set(emb0[0])
        encl_rows = None
        if enc_len:
            encl_rows = self._zero_encl.at[row].set(enc_len)
        mask = np.zeros((self.m, N), bool)
        mask[lo - self.a: hi - self.a, row] = True
        h_out = self.decode_rows(h_rows, pos_rows, jnp.asarray(mask),
                                 emb0_rows, encl_rows)
        return h_out[row][None]

    # -- cost introspection -------------------------------------------------
    def decode_step_cost(self):
        """CostSummary of THE pooled decode step this server dispatches per
        round, from an ahead-of-time lowering+compile on abstract operands
        (no execution).  With a mesh the numbers are per-device after SPMD
        partitioning — the basis for the device-group τ calibration
        (``GeoServingSystem.calibrate_taus``)."""
        from repro.launch import costs as C

        def abst(x):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                x)

        N = self.pool.n_rows
        d = self.cfg.d_model
        act = jnp.dtype(getattr(self.cfg, "act_dtype", "float32"))
        h = jax.ShapeDtypeStruct((N, 1, d), act)
        pos = jax.ShapeDtypeStruct((N,), jnp.int32)
        emb0 = (jax.ShapeDtypeStruct((N, 1, d), act)
                if any(s.needs_emb0 for s in self.specs)
                else abst(self._dummy))
        encl = jax.ShapeDtypeStruct((N,), jnp.int32)
        la = jax.ShapeDtypeStruct((self.m, N), jnp.bool_)
        lids = abst(self.layer_ids)
        args = (abst(self.run_params), abst(self.shared),
                abst(self.pool.tree))
        if self.cache_layout == "paged":
            args += (abst(self.pool.page_table()),)
        args += (h, pos, emb0, encl, la, lids)
        compiled = self._step.lower(*args).compile()
        return C.summarize_compiled(compiled)


@dataclass
class _PrefillGroup:
    """Co-admitted sessions sharing one route, one prompt-length bucket and
    (enc-dec) one encoder length, prefilled in the same chunk rounds, each
    member on its own pool row through the row-indexed prefill step.

    ``bucket is None`` marks a chunked group: prompts longer than the
    largest bucket, processed in max-bucket-sized chunks that interleave
    with decode rounds (``GeoServingSystem.prefill_round``).
    """

    route: Route
    bucket: Optional[int]
    members: List[EngineSession]
    enc_len: int = 0  # shared encoder length (enc-dec groups)
    offset: int = 0  # tokens prefilled so far (next chunk start)
    # per-sid per-hop activation chunks, stitched into the client-side
    # failover cache (EngineSession.hop_inputs) at completion
    hop_chunks: Dict[int, List[List[jnp.ndarray]]] = field(
        default_factory=dict)
    # per-sid per-hop ENC-phase inputs (enc-dec groups; None elsewhere)
    enc_inputs: Dict[int, List[Optional[jnp.ndarray]]] = field(
        default_factory=dict)


class GeoServingSystem:
    """Client-centric distributed inference with online BPRR and
    continuous batching across sessions — for both decode (one pooled step
    per server per round) and prefill (bucket groups of co-admitted
    sessions, padded to a shared prompt-length bucket).

    ``prefill_mode``: "batched" (default) coalesces same-round admissions
    into bucket groups; "serial" keeps the legacy one-session-per-call
    prefill — the bit-for-bit reference path for the batched one.
    ``prefill_buckets``: prompt-length buckets; prompts are right-padded to
    the smallest fitting bucket, and prompts longer than the largest bucket
    are prefilled in max-bucket-sized chunks that interleave with decode
    rounds.  Defaults to powers of two up to ``max_seq_len`` (no chunking).
    Stacks with recurrent state (rwkv, zamba2 hybrids) always prefill at
    the exact prompt length — grouping batches equal lengths instead.
    ``max_enc_len``: cross-KV pool capacity for enc-dec stacks (defaults to
    ``max_seq_len``).
    ``decode_mode``: "fused" (default) keeps each decode round resident on
    device end to end — ONE batched embed dispatch, one fused
    gather+step+scatter dispatch per (hop, server), ONE fused
    lm_head+sample tail, and a single host sync on the round's token
    vector; "serial" is the pre-refactor reference path (per-session embed
    and lm_head, host-staged row buffers between hops) kept for
    round-for-round comparison and as the per-session throughput baseline.
    Token streams, admission, and the virtual clock are identical between
    the two modes; logits agree to float-ulp (the fused tail projects all
    round slots in one GEMM, whose per-row reduction order XLA may pick
    differently than the width-1 reference — see ``make_round_tail``).
    Within ONE mode, solo-vs-grouped stays bit-exact: the fused round's
    fixed-width buffers make it structural, exactly like the pooled step.
    ``backend``: compute backend for every pooled step — ``"xla"`` (default;
    the oracle paths, runs everywhere) or ``"pallas"`` (the
    ``repro.kernels`` TPU kernels; interpret mode off-TPU).  Dispatch is
    per block call: a kernel whose ``*_unsupported`` predicate rejects the
    call's feature set falls back to the XLA path, so backend choice can
    never change which features work — and round RESULTS (token streams,
    admission, virtual clock) are backend-independent (logits agree to
    float-eps; see docs/serving.md).
    ``cache_layout``: ``"slab"`` (default) books worst-case fixed-width
    cache rows at admission — the exact reference twin; ``"paged"`` books
    ``page_size``-token pages instead (page-granular eq. (5)/(20)
    accounting, see docs/serving.md "Paged pools"): admission charges only
    the prompt's pages, sessions grow page-by-page during decode, and
    under page pressure the engine PREEMPTS a victim session (its pages
    are freed; its client-side hop histories remain) and later resumes it
    through the failover-replay machinery — token streams are bit-identical
    to the slab layout and to an unpreempted run, and the virtual clock
    differs from them by EXACTLY the billed resume-replay cost (zero when
    nothing was preempted).
    ``page_size``: tokens per page; must divide ``max_seq_len`` (defaults
    to the largest divisor ≤ 16).
    ``fault_plan`` / ``detector``: deterministic fault injection on the
    virtual clock and the timeout/backoff policy that prices failure
    detection — see docs/concurrency.md "Failure model".
    """

    def __init__(self, cfg: ModelConfig, params, problem: Problem,
                 algorithm: str = "proposed", R: Optional[int] = None,
                 max_new_tokens: int = 64, max_sessions: int = 8,
                 max_seq_len: Optional[int] = None,
                 prefill_mode: str = "batched",
                 prefill_buckets: Optional[Tuple[int, ...]] = None,
                 max_enc_len: Optional[int] = None,
                 decode_mode: str = "fused",
                 backend: str = "xla",
                 cache_layout: str = "slab",
                 page_size: Optional[int] = None,
                 mesh=None, mesh_rules=None, device_groups=None,
                 fault_plan: Optional[FaultPlan] = None,
                 detector: Optional[FailureDetector] = None):
        from repro.kernels.runtime import resolve_backend

        assert problem.L == cfg.n_layers
        assert prefill_mode in ("batched", "serial"), prefill_mode
        assert decode_mode in ("fused", "serial"), decode_mode
        assert cache_layout in ("slab", "paged"), cache_layout
        self.backend = resolve_backend(backend)
        self.cfg = cfg
        self.params = params
        self.problem = problem
        # Optional device-group serving.  Two spellings:
        #   * `device_groups={server_id: DeviceGroup | Mesh | None}` — the
        #     heterogeneous form: every BlockServer shards over ITS OWN
        #     group (missing / None entries are the solo-device twin), so a
        #     2-device TP server and a 4-device EP server coexist and
        #     calibrate_taus() yields a genuinely per-server τ vector;
        #   * the legacy `mesh=` (+ optional `mesh_rules=`) kwarg — sugar
        #     that broadcasts ONE group to all servers, byte-identical to
        #     the old global-mesh behavior.
        # `mesh_rules` overrides the derived logical-axis rules (see
        # launch.sharding.serving_rules); accepted as a dict or a frozen
        # tuple-of-pairs.
        from repro.launch.sharding import DeviceGroup, as_device_group

        if device_groups is not None and mesh is not None:
            raise ValueError(
                "pass either device_groups= or the global mesh= sugar, "
                "not both")
        self.mesh = mesh
        if mesh_rules is not None and not isinstance(mesh_rules, tuple):
            from repro.launch.sharding import freeze_rules
            mesh_rules = freeze_rules(dict(mesh_rules))
        self.mesh_rules = mesh_rules
        if device_groups is not None:
            self.device_groups = {int(j): as_device_group(g)
                                  for j, g in device_groups.items()}
        elif mesh is not None:
            g = DeviceGroup(mesh=mesh, rules=mesh_rules)
            self.device_groups = {j: g for j in range(problem.n_servers)}
        else:
            self.device_groups = {}
        self.algorithm = algorithm
        self.max_new_tokens = max_new_tokens
        self.max_sessions = int(max_sessions)
        self.max_seq_len = int(
            max_seq_len if max_seq_len is not None
            else problem.workload.l_in + max_new_tokens + 32)
        self.cache_layout = cache_layout
        if cache_layout == "paged":
            if page_size is None:  # largest divisor of max_seq_len <= 16
                page_size = next(p for p in range(min(16, self.max_seq_len),
                                                  0, -1)
                                 if self.max_seq_len % p == 0)
            page_size = int(page_size)
            if page_size < 1 or self.max_seq_len % page_size != 0:
                raise ValueError(
                    f"page_size {page_size} must divide max_seq_len "
                    f"{self.max_seq_len}")
        else:
            page_size = 0
        self.page_size = page_size
        # FIFO resume queue + preemption bookkeeping (paged layout)
        self._preempt_order: List[int] = []
        self.prefill_mode = prefill_mode
        self.specs = state_specs(cfg)
        self._recurrent = any(s.recurrent for s in self.specs)
        self._needs_emb0 = any(s.needs_emb0 for s in self.specs)
        self._n_enc = int(cfg.n_enc_layers)
        self._is_enc_dec = cfg.is_enc_dec
        self.max_enc_len = int(max_enc_len) if max_enc_len is not None \
            else self.max_seq_len
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(self.max_seq_len)
        self.prefill_buckets = tuple(sorted(
            {min(int(b), self.max_seq_len) for b in prefill_buckets}))
        assert self.prefill_buckets, "prefill_buckets must be non-empty"
        self._prefill_groups: List[_PrefillGroup] = []
        if algorithm == "proposed":
            from repro.core.placement import auto_R, cg_bp
            self.R = R if R is not None else auto_R(problem, 0.1, 60.0)
            self.placement, _ = cg_bp(problem, self.R)
        else:
            self.R = R
            self.placement = petals_bp(problem)
        self._client_device = jax.devices()[0]
        self.servers: Dict[int, BlockServer] = {}
        self._build_servers()
        self.sessions: Dict[int, EngineSession] = {}
        self._sid = 0
        self._embed = jax.jit(
            lambda emb, tok: embed_tokens(emb, cfg, NULL_SH, tok))
        self._embed_frames = jax.jit(
            lambda emb, fr: embed_frames(emb, cfg, NULL_SH, fr))
        self._lm_head = jax.jit(
            lambda emb, h: lm_head(emb, cfg, NULL_SH, h))
        self._sampler = make_sampler()
        self.decode_mode = decode_mode
        self._round_tail = make_round_tail(cfg)
        # fixed round width: the device-resident round buffers span W slots
        # whatever the round's membership, so the fused programs trace once
        # and per-session results are bit-identical solo or grouped.  Grown
        # (rare re-trace) if a round ever exceeds it.
        self._round_width = max(1, self.max_sessions)
        # per-round dispatch accounting (the perf contract: ONE embed, ONE
        # lm_head+sample tail, one fused dispatch per (hop, server), ONE
        # host sync — tests/test_round_fusion.py asserts against this)
        self.round_stats = {"rounds": 0, "embed_dispatches": 0,
                            "tail_dispatches": 0, "hop_dispatches": 0,
                            "preemptions": 0, "resumes": 0,
                            "detections": 0, "retries": 0, "replays": 0,
                            "rejoins": 0, "dispatch_errors": 0,
                            "detect_s": 0.0, "backoff_s": 0.0,
                            "replay_s": 0.0}
        # fault injection: a seedable FaultPlan drives crashes / rejoins /
        # stragglers / dispatch errors on the virtual clock via
        # ``apply_faults(now)``; the detector prices timeout detection +
        # backoff (docs/concurrency.md "Failure model")
        self.fault_plan = fault_plan
        self.detector = detector if detector is not None else \
            FailureDetector()
        self._fault_cursor = 0
        # servers with a pending one-shot admission-dispatch fault
        self._dispatch_faults: set = set()
        # calibration-time taus: set_slowdown() factors are ABSOLUTE
        # multipliers over these, so straggler intervals restore cleanly
        self._base_taus = [float(s.tau) for s in problem.servers]

    # ------------------------------------------------------------------
    def _cap_slots(self, j: int, m: int) -> int:
        spec = self.problem.servers[j]
        cap = int(np.floor(
            (spec.mem_bytes - self.problem.s_m * m) / self.problem.s_c))
        return max(cap, 0)

    def _build_servers(self):
        for j in range(self.problem.n_servers):
            a, m = int(self.placement.a[j]), int(self.placement.m[j])
            if m <= 0:
                continue
            if j in self.servers:
                continue  # keep live objects (running sessions hold caches)
            cap = self._cap_slots(j, m)
            # pool arrays need >= 1 row for fixed jit shapes, but the
            # block-slot budget stays honest: cap == 0 admits nothing.
            # Paged layout: rows are cheap (the expensive self-KV bytes
            # live in the shared page arrays), so every session the engine
            # could ever co-host gets a row — co-residency is then bounded
            # by the page-unit budget, not the worst-case slot count.
            if self.cache_layout == "paged":
                n_rows = max(1, self.max_sessions)
            else:
                n_rows = max(1, min(self.max_sessions, cap))
            self.servers[j] = BlockServer(
                j, self.cfg, self.params, a, m, n_rows=n_rows,
                max_len=self.max_seq_len, cap_slots=cap,
                enc_len=self.max_enc_len if self._is_enc_dec else 0,
                backend=self.backend, cache_layout=self.cache_layout,
                page_size=self.page_size,
                group=self.device_groups.get(j))

    def alive_placement(self) -> Placement:
        a = np.array(self.placement.a)
        m = np.array(self.placement.m)
        for j in range(len(m)):
            if j in self.servers and not self.servers[j].alive:
                m[j] = 0
            if j not in self.servers:
                m[j] = 0
        return Placement(a=a, m=m)

    # ------------------------------------------------------------------
    # τ calibration from the (sharded) pooled step
    # ------------------------------------------------------------------
    def calibrate_taus(self, device_kind: Optional[str] = None
                       ) -> Dict[int, float]:
        """Per-server τ (per-block per-token decode seconds, eq. (1))
        derived from each server's ACTUAL pooled decode step: AOT
        lowering + compile, ``launch.costs`` roofline over the per-device
        cost analysis.  With device groups, each server's step is ITS OWN
        SPMD-partitioned program over its own ``srv.n_chips`` devices, so
        a heterogeneous deployment (solo next to 2-device TP next to
        4-device EP) yields a genuinely non-constant τ vector — that
        heterogeneity flows straight into the perf model the placement
        (MILP/CG-BP), eq. (20) routing, and the simulator consume.

        ``device_kind``: the chip whose peaks price the steps; defaults to
        the kind of the device each server runs on.  A kind without
        published peaks (the CPU) raises, so a CPU caller names the chip it
        models."""
        from repro.launch import costs as C

        taus = {}
        for j, srv in self.servers.items():
            kind = device_kind or srv.device.device_kind
            cost = srv.decode_step_cost()
            taus[j] = C.tau_from_step_cost(cost, srv.n_chips, srv.m,
                                           srv.pool.n_rows, device_kind=kind)
        return taus

    def calibrated_problem(self, device_kind: Optional[str] = None
                           ) -> Problem:
        """A copy of ``self.problem`` whose server τs come from
        :meth:`calibrate_taus` — feed it back into placement / the
        simulator.  The live engine's virtual clock keeps the original
        problem: swapping τ mid-flight would break the mesh-vs-reference
        parity contract."""
        from repro.core.perf_model import with_server_taus

        return with_server_taus(self.problem,
                                self.calibrate_taus(device_kind))

    # ------------------------------------------------------------------
    # Session lifecycle (continuous batching API)
    # ------------------------------------------------------------------
    def create_session(self, tokens: np.ndarray, client: int, route: Route,
                       n_new: int, arrival: float = 0.0,
                       frames: Optional[np.ndarray] = None,
                       sampling: Optional[SamplingSpec] = None) -> int:
        """Register an admitted session (no compute, no slots yet).

        ``frames``: (S_enc, frame_dim) encoder input — required for enc-dec
        stacks, rejected otherwise.  ``sampling``: per-session token policy
        (defaults to greedy)."""
        S = len(tokens)
        if S + n_new > self.max_seq_len:
            raise ValueError(
                f"prompt {S} + n_new {n_new} exceeds max_seq_len "
                f"{self.max_seq_len}; raise max_seq_len at engine build")
        enc_len = 0
        if self._is_enc_dec:
            if frames is None:
                raise ValueError(
                    "enc-dec stacks need encoder `frames` per session")
            frames = np.asarray(frames)
            if frames.ndim != 2 or frames.shape[1] != self.cfg.frame_dim:
                raise ValueError(
                    f"frames must be (S_enc, {self.cfg.frame_dim}); got "
                    f"{frames.shape}")
            enc_len = int(frames.shape[0])
            if enc_len > self.max_enc_len:
                raise ValueError(
                    f"encoder input {enc_len} exceeds max_enc_len "
                    f"{self.max_enc_len}; raise max_enc_len at engine build")
        elif frames is not None:
            raise ValueError("`frames` is only meaningful for enc-dec stacks")
        sid = self._sid
        self._sid += 1
        self.sessions[sid] = EngineSession(
            sid=sid, client=client, route=route, prompt_len=S, n_new=n_new,
            arrival=arrival, tokens=[int(t) for t in np.asarray(tokens)],
            hop_inputs=[[] for _ in route.servers],
            sampling=sampling if sampling is not None else SamplingSpec(),
            frames=frames, enc_len=enc_len)
        return sid

    def _prompt_pages(self, sess: EngineSession) -> int:
        """Pages booked at admission: enough for the prompt (paged)."""
        return pages_for(sess.prompt_len, self.page_size)

    def _worst_pages(self, sess: EngineSession) -> int:
        """Fully-grown page count — the solo-completability bound admission
        asserts so preempted sessions can always eventually resume."""
        return pages_for(sess.prompt_len + sess.n_new, self.page_size)

    def fits_session(self, sid: int) -> bool:
        """True iff every route server has a free row AND block-slots
        (slab) / prompt pages plus solo-completability headroom (paged)
        for this session (no-overbooking check)."""
        sess = self.sessions[sid]
        if self.cache_layout == "paged":
            p, w = self._prompt_pages(sess), self._worst_pages(sess)
            return all(self.servers[j].alive
                       and self.servers[j].fits(sid, k, p, w)
                       for j, k in zip(sess.route.servers,
                                       sess.route.blocks))
        return all(self.servers[j].alive and self.servers[j].fits(sid, k)
                   for j, k in zip(sess.route.servers, sess.route.blocks))

    def try_admit_session(self, sid: int, now: float = 0.0) -> bool:
        """Claim slots and run the prefill to completion (synchronous
        single-session admission; any other pending prefill groups are also
        driven to completion).  Returns False (and claims nothing) when
        some server's pool is exhausted — the caller defers and re-admits
        after a retirement."""
        ok = self.try_admit_sessions([sid], now=now)
        if ok:
            self.drain_prefill()
        return bool(ok)

    def try_admit_sessions(self, sids: List[int], now: float = 0.0
                           ) -> List[int]:
        """Claim slots for every session that fits and coalesce the admitted
        ones into bucket groups for batched prefill.  Returns the admitted
        sids; the rest claimed nothing (the caller defers them).

        Within one batch, admission is FIFO per client: once an earlier
        session of a client fails to fit, later sessions of the same client
        are not attempted (they would otherwise overtake it).

        Prefill compute does NOT run here — the caller advances it with
        :meth:`prefill_round` (interleaving decode rounds between chunks) or
        :meth:`drain_prefill`.  In ``prefill_mode="serial"`` the legacy
        one-session-at-a-time prefill runs immediately instead.
        """
        with tracing.span("admit", offered=len(sids)) as sp:
            got = self._admit_sessions(sids, now)
            if sp:
                sp.set(granted=len(got), sids=got)
            return got

    def _admit_sessions(self, sids: List[int], now: float) -> List[int]:
        admitted: List[EngineSession] = []
        failed_clients: set = set()
        for sid in sids:
            sess = self.sessions[sid]
            # one-shot admission-dispatch fault (FaultPlan kind
            # "dispatch_error"): the admit RPC through a faulted server
            # fails once; the caller defers and retries like a full pool
            faulted = [j for j in sess.route.servers
                       if j in self._dispatch_faults]
            if faulted:
                self._dispatch_faults.difference_update(faulted)
                self.round_stats["dispatch_errors"] += 1
                failed_clients.add(sess.client)
                continue
            if sess.client in failed_clients or not self.fits_session(sid):
                failed_clients.add(sess.client)
                continue
            n_pages = (self._prompt_pages(sess)
                       if self.cache_layout == "paged" else 0)
            for j, k in zip(sess.route.servers, sess.route.blocks):
                self.servers[j].admit(sid, k, n_pages=n_pages)
            sess.start = now
            admitted.append(sess)
        if not admitted:
            return []
        if self.prefill_mode == "serial":
            for sess in admitted:
                self._prefill_serial(sess)
                self._finalize_prefill(sess, sess._h[:, -1:])
            return [s.sid for s in admitted]
        # batched: group by (route, bucket[, enc_len]).  Stacks with
        # recurrent state (rwkv, mamba) use the EXACT prompt length as the
        # bucket (no padding, no chunking — bucket_for's family rule);
        # attention-family prompts longer than the largest bucket go to the
        # chunked group of their route (bucket None).
        groups: Dict[Tuple[Route, Optional[int], int],
                     List[EngineSession]] = {}
        for sess in admitted:
            sess.state = "prefilling"
            b = bucket_for(self.prefill_buckets, sess.prompt_len, self.specs)
            groups.setdefault((sess.route, b, sess.enc_len),
                              []).append(sess)
        for (route, b, enc_len), members in groups.items():
            self._prefill_groups.append(_PrefillGroup(
                route=route, bucket=b, members=members, enc_len=enc_len,
                hop_chunks={s.sid: [[] for _ in route.servers]
                            for s in members},
                enc_inputs={s.sid: [None] * len(route.servers)
                            for s in members}))
        return [s.sid for s in admitted]

    # -- batched prefill ------------------------------------------------
    def has_pending_prefill(self) -> bool:
        """True while some admitted session still has prompt chunks left."""
        return bool(self._prefill_groups)

    def prefill_round(self) -> List[int]:
        """Advance every pending bucket group by ONE chunk round (all hops).
        Sessions whose prompt completes become active and emit their first
        token.  Returns their sids.  Callers interleave this with
        :meth:`decode_round` so long chunked prompts do not head-of-line
        block resident sessions."""
        done: List[int] = []
        still: List[_PrefillGroup] = []
        with tracing.span("prefill_round", groups=len(self._prefill_groups)):
            for g in self._prefill_groups:
                done.extend(self._prefill_group_round(g))
                if any(s.state == "prefilling" and s.prompt_len > g.offset
                       for s in g.members):
                    still.append(g)
        self._prefill_groups = still
        return done

    def drain_prefill(self):
        """Run prefill rounds until no admitted session is mid-prompt."""
        while self._prefill_groups:
            self.prefill_round()

    def _prefill_plan(self, prompt_len: int) -> List[Tuple[int, int, int]]:
        """Deterministic chunk plan [(offset, span, t_pad), ...] for one
        prompt — a function of the prompt length ONLY (never of group
        co-members), so a session runs the exact same pooled programs
        whether admitted alone or inside a bucket group, and failover
        replay can rebuild bit-identical caches from the plan."""
        if self._recurrent:  # order-sensitive state: exact length, one shot
            return [(0, prompt_len, prompt_len)]
        b = bucket_for(self.prefill_buckets, prompt_len)
        if b is not None:
            return [(0, prompt_len, min(b, self.max_seq_len))]
        chunk_unit = max(self.prefill_buckets)
        plan: List[Tuple[int, int, int]] = []
        off = 0
        while off < prompt_len:
            t_pad = min(chunk_unit, self.max_seq_len - off)
            plan.append((off, min(prompt_len - off, t_pad), t_pad))
            off += t_pad
        return plan

    def _prefill_enc_phase(self, g: _PrefillGroup,
                           active: List[EngineSession]):
        """One exact-length pooled pass over the encoder blocks of a group's
        route (enc-dec stacks; runs once, before the first decoder chunk).
        Leaves each member's encoder output on ``sess.enc_out``."""
        for s in active:
            s._h = self._embed_frames(
                self.params["embed"],
                jnp.asarray(s.frames, jnp.float32)[None])
        e = 0
        for hop, (j, k) in enumerate(zip(g.route.servers, g.route.blocks)):
            if e >= self._n_enc:
                break
            srv = self.servers[j]
            layers = srv.hop_layers(e, min(e + k, self._n_enc))
            for s in active:
                g.enc_inputs[s.sid][hop] = s._h
                s._h = srv.prefill_row(s._h, srv.pool.rows[s.sid], layers,
                                       phase="enc")
            e += k
        for s in active:
            s.enc_out = s._h

    def _prefill_group_round(self, g: _PrefillGroup) -> List[int]:
        """One chunk round for one bucket group: embed the (padded) token
        chunk of every member, dispatch the row-indexed prefill step once
        per member per hop, account the virtual clock, and finalize members
        whose prompt completed.

        A route server lost mid-prefill (dead, crashed, or wiped by a
        rejoin) fails the group's in-flight members with a machine-readable
        reason: the per-hop input histories that failover replay needs are
        only complete at prompt completion, so there is nothing to splice
        from yet.  Crashed-but-undetected servers bill timeout detection on
        the members first (their dispatch is what discovers the loss);
        sessions in other groups and already-active sessions are untouched
        and keep their bit-exact streams."""
        active = [s for s in g.members
                  if s.state == "prefilling" and s.prompt_len > g.offset]
        if not active:
            return []
        lost = [j for j in g.route.servers
                if j not in self.servers or not self.servers[j].alive
                or self.servers[j].crashed
                or any(s.sid not in self.servers[j].pool.rows
                       for s in active)]
        if lost:
            for j in lost:
                srv = self.servers.get(j)
                if srv is not None and srv.alive and srv.crashed:
                    self._detect_crash(j, [
                        (s, self._expected_hop_prefill(s, j))
                        for s in active])
            for s in active:
                self._abort_session(s, reason="server_lost_mid_prefill")
            return []
        # this round's padded width comes from the SAME plan failover replay
        # uses (any active member's plan has an entry at g.offset, and t_pad
        # is session-independent by construction) — one source of truth for
        # the chunk schedule
        ref_len = max(s.prompt_len for s in active)
        t_pad = next(tp for off, _, tp in self._prefill_plan(ref_len)
                     if off == g.offset)
        spans = {s.sid: min(s.prompt_len - g.offset, t_pad) for s in active}
        if self._is_enc_dec and g.offset == 0:
            self._prefill_enc_phase(g, active)
        for s in active:
            chunk = s.tokens[g.offset: g.offset + spans[s.sid]]
            chunk = chunk + [0] * (t_pad - len(chunk))
            s._h = self._embed(self.params["embed"],
                               jnp.asarray([chunk], jnp.int32))
            if self._needs_emb0:
                s._emb0 = s._h
        e = 0
        phase = "dec" if self._is_enc_dec else "all"
        for hop, (j, k) in enumerate(zip(g.route.servers, g.route.blocks)):
            srv = self.servers[j]
            lo, hi = max(e, self._n_enc), e + k
            if lo < hi:  # hop hosts decode-phase blocks
                with tracing.span("prefill.hop") as sp:
                    if sp:
                        sp.set(server=j, rows=len(active), t_pad=t_pad,
                               m=srv.m,
                               tok_layers_useful=(hi - lo) * sum(
                                   spans.values()),
                               tok_layers_computed=len(active) * t_pad
                               * srv.m)
                    layers = srv.hop_layers(lo, hi)
                    for s in active:
                        # client-side failover cache: the UNPADDED chunk
                        # entering this hop (stitched to the full prompt at
                        # completion)
                        g.hop_chunks[s.sid][hop].append(
                            s._h[:, : spans[s.sid]])
                        s._h = srv.prefill_row(
                            s._h, srv.pool.rows[s.sid], layers,
                            offset=g.offset, phase=phase,
                            emb0=s._emb0 if self._needs_emb0 else None,
                            enc=s.enc_out if self._is_enc_dec else None)
            # Virtual clock, consistent with eq. (1): the group's chunk
            # travels the hop as ONE message — its members share a single
            # RTT — and each session is charged its own (weighted) k·τ^I of
            # block compute (member rows overlap inside the pooled step).
            # The accounting is family-agnostic like the paper's model:
            # encoder blocks bill their prefill compute here even though
            # they do no decode-phase work.  Per-session latency therefore
            # equals the serial eq. (1) value for unchunked groups; chunked
            # prompts pay one RTT per chunk per hop plus τ^I evaluated at
            # the actual chunk length.
            # unchunked groups bill the workload's nominal l_in (like the
            # simulator); chunked prompts bill the actual span.  Encoder-
            # only hops are traversed exactly once (the encoder phase, at
            # offset 0), so later chunk rounds do not bill them again.
            if lo < hi or g.offset == 0:
                for s in active:
                    tau = self.problem.servers[j].tau_prefill(
                        self.problem.workload.l_in if g.bucket is not None
                        else spans[s.sid])
                    s.prefill_time += (
                        self.problem.rtt_prefill[s.client, j]
                        + self.problem.llm.tau_weight(e, e + k)
                        * tau * srv.slowdown)
            e += k
        g.offset += t_pad
        done: List[int] = []
        for s in active:
            if s.prompt_len <= g.offset:
                for hop in range(len(g.route.servers)):
                    parts = g.hop_chunks[s.sid][hop]
                    stitched = (None if not parts
                                else parts[0] if len(parts) == 1
                                else jnp.concatenate(parts, axis=1))
                    if self._is_enc_dec:
                        s.hop_inputs[hop].append(
                            {"enc": g.enc_inputs[s.sid][hop],
                             "dec": stitched})
                    else:
                        s.hop_inputs[hop].append(stitched)
                self._finalize_prefill(s, s._h[:, spans[s.sid] - 1:
                                               spans[s.sid]])
                done.append(s.sid)
        return done

    def _prefill_serial(self, sess: EngineSession):
        """Legacy one-session-per-call prefill — the exact-length reference
        path for the bucketed one (identical token streams; the bucketed
        path's *structural* bit guarantee is solo-vs-group through the same
        pooled program): per-layer block calls, eq. (1) accounting."""
        if self._is_enc_dec:
            eh = self._embed_frames(
                self.params["embed"],
                jnp.asarray(sess.frames, jnp.float32)[None])
            enc_recs: List[Optional[jnp.ndarray]] = \
                [None] * len(sess.route.servers)
            e = 0
            for hop, (j, k) in enumerate(zip(sess.route.servers,
                                             sess.route.blocks)):
                if e >= self._n_enc:
                    break
                lo, hi = e, min(e + k, self._n_enc)
                enc_recs[hop] = eh
                eh = self.servers[j].prefill_range(
                    sess.sid, eh, lo, hi, jnp.arange(sess.enc_len))
                e += k
            sess.enc_out = eh
        prompt = jnp.asarray(sess.tokens[: sess.prompt_len],
                             jnp.int32)[None, :]
        h = self._embed(self.params["embed"], prompt)
        emb0 = h if self._needs_emb0 else None
        positions = jnp.arange(sess.prompt_len)
        e = 0
        for hop, (j, k) in enumerate(zip(sess.route.servers,
                                         sess.route.blocks)):
            srv = self.servers[j]
            lo, hi = max(e, self._n_enc), e + k
            if self._is_enc_dec:
                sess.hop_inputs[hop].append(
                    {"enc": enc_recs[hop], "dec": h if lo < hi else None})
            else:
                sess.hop_inputs[hop].append(h)
            if lo < hi:
                h = srv.prefill_range(sess.sid, h, lo, hi, positions,
                                      emb0=emb0, enc_h=sess.enc_out)
            sess.prefill_time += (
                self.problem.rtt_prefill[sess.client, j]
                + self.problem.llm.tau_weight(e, e + k)
                * self.problem.servers[j].tau_prefill(
                    self.problem.workload.l_in) * srv.slowdown)
            e += k
        sess._h = h

    def _finalize_prefill(self, sess: EngineSession, h_last):
        """Prefill done: close the virtual-clock accounting and emit the
        first generated token from the prompt's last-position logits via
        the session's sampling policy."""
        sess.pos = sess.prompt_len
        sess.virtual_time += sess.prefill_time
        sess.per_token_time = self._route_per_token(sess)
        sess.state = "active"
        sess.end = (sess.start + sess.prefill_time
                    + max(sess.n_new - 1, 0) * sess.per_token_time)
        with tracing.span("prefill.finalize", sid=sess.sid):
            logits = self._lm_head(self.params["embed"],
                                   self._at_client(h_last))
            sess.last_logits = logits[0, 0]
            sess.tokens.append(self._sample_tokens([sess])[0])
        sess.n_generated = 1
        sess._h = None
        sess._emb0 = None

    def _at_client(self, h):
        """The last hop's hidden states, brought back to the client (the
        default device, which holds the embedding) for the lm_head."""
        return jax.device_put(h, self._client_device)

    def _sample_tokens(self, sessions: List[EngineSession]) -> List[int]:
        """One vmapped sampler call for a round's sessions: per-row
        (temperature, top_k, key) inputs — policies vary per session
        without retracing.  Session ``s`` draws the key for token index
        ``s.n_generated`` (deterministic per (seed, index))."""
        logits = jnp.stack([s.last_logits for s in sessions])
        temps, topks, keys = [], [], []
        for s in sessions:
            t, k = s.sampling.row_params()
            temps.append(t)
            topks.append(k)
            keys.append(s.sampling.key_for(s.n_generated))
        toks = self._sampler(logits, jnp.asarray(temps, jnp.float32),
                             jnp.asarray(topks, jnp.int32), jnp.stack(keys))
        return [int(t) for t in np.asarray(toks)]

    def _route_per_token(self, sess: EngineSession) -> float:
        t = 0.0
        e = 0
        for j, k in zip(sess.route.servers, sess.route.blocks):
            t += (self.problem.rtt_token[sess.client, j]
                  + self.problem.llm.tau_weight(e, e + k)
                  * self.problem.servers[j].tau
                  * self.servers[j].slowdown)
            e += k
        return t

    # ------------------------------------------------------------------
    # Timeout-based failure detection (docs/concurrency.md "Failure model")
    # ------------------------------------------------------------------
    def _expected_hop_decode(self, sess: EngineSession, hop: int) -> float:
        """Eq. (1) expected decode hop time — the client's dispatch
        deadline is ``detector.timeout_factor`` times this."""
        j = sess.route.servers[hop]
        e_lo, e_hi = self._hop_span(sess, hop)
        return (self.problem.rtt_token[sess.client, j]
                + self.problem.llm.tau_weight(e_lo, e_hi)
                * self.problem.servers[j].tau * self.servers[j].slowdown)

    def _expected_hop_prefill(self, sess: EngineSession, j: int) -> float:
        """Expected prefill hop time for route server ``j`` (deadline
        basis when the loss is discovered mid-prefill)."""
        e = 0
        for jj, k in zip(sess.route.servers, sess.route.blocks):
            if jj == j:
                return (self.problem.rtt_prefill[sess.client, j]
                        + self.problem.llm.tau_weight(e, e + k)
                        * self.problem.servers[j].tau_prefill(
                            self.problem.workload.l_in)
                        * self.servers[j].slowdown)
            e += k
        return float(self.problem.rtt_prefill[sess.client, j])

    def _detect_crash(self, j: int, affected):
        """Declare crashed server ``j`` dead by timeout: every session in
        ``affected`` — ``(session, expected_hop_time)`` pairs, all of them
        concurrently blocked on the same silent server — bills the missed
        deadline plus ``max_probes`` backoff probes on its virtual clock,
        then the server is marked dead + suspected (routing penalty)."""
        srv = self.servers[j]
        backoff = self.detector.backoff_time()
        for sess, expected in affected:
            detect = self.detector.detect_time(expected)
            sess.detect_time += detect
            sess.backoff_time += backoff
            sess.virtual_time += detect + backoff
            sess.n_detections += 1
            sess.n_retries += self.detector.max_probes
            self.round_stats["detections"] += 1
            self.round_stats["retries"] += self.detector.max_probes
            self.round_stats["detect_s"] += detect
            self.round_stats["backoff_s"] += backoff
        srv.alive = False
        srv.suspected = True

    def _hop_needs_failover(self, sess: EngineSession, hop: int) -> bool:
        """A hop must be spliced when its server is gone (dead / removed)
        or no longer holds the session's cache row (it rejoined with an
        empty pool, or a resume skipped it while dead)."""
        j = sess.route.servers[hop]
        srv = self.servers.get(j)
        return (srv is None or not srv.alive
                or sess.sid not in srv.pool.rows)

    def decode_round(self, sids: Optional[List[int]] = None) -> Dict[int, int]:
        """One continuous-batching round: every listed active session (all
        unfinished active sessions when ``sids`` is None) advances one token
        through its route; co-resident sessions share ONE pooled step per
        (hop, server) group.  Returns {sid: new_token}.

        In ``decode_mode="fused"`` (default) the round is device-resident:
        one batched embed, one fused gather+step+scatter dispatch per
        (hop, server), one fused lm_head+sample tail, and a single host
        sync on the sampled token vector.  ``decode_mode="serial"`` runs
        the pre-refactor per-session reference — identical tokens, logits
        and virtual-clock accounting.

        Preempted sessions (paged page pressure, or a capacity-starved
        failover deferral on either layout) are resumed (FIFO) when they
        fit again — resume replay is billed on the virtual clock.  Paged
        layout additionally grows every decoding session's pages to cover
        the write position, preempting victims under page pressure (see
        ``preempt_session``)."""
        with tracing.span("decode_round") as sp:
            with tracing.span("round.prepare"):
                group = self._round_group(sids)
                fused = bool(group) and self.decode_mode != "serial"
                if fused:
                    inputs = self._round_inputs(group)
            if sp:
                sp.set(sessions=len(group), W=self._round_width)
            if not group:
                return {}
            if not fused:
                return self._decode_round_serial(group)
            return self._decode_round_fused(group, *inputs)

    def _round_group(self, sids: Optional[List[int]]
                     ) -> List[EngineSession]:
        """The sessions a round decodes (``sids`` as in
        :meth:`decode_round`), once due faults have fired, preempted
        sessions that fit again have resumed and, paged, every member's
        pages cover its write position."""
        explicit = sids is not None
        if self.fault_plan is not None:
            # virtual-clock fault injection: events due by the round's
            # earliest member clock fire before the round dispatches
            clock = [s.virtual_time + s.start
                     for s in self.sessions.values()
                     if s.state in ("active", "preempted")]
            if clock:
                self.apply_faults(min(clock))
        self._resume_preempted()
        if sids is None:
            sids = [s.sid for s in self.sessions.values()
                    if s.state == "active" and s.n_generated < s.n_new]
        group = [self.sessions[sid] for sid in sids
                 if self.sessions[sid].state == "active"]
        if self.cache_layout == "paged":
            group = self._ensure_page_capacity(group)
        if not group and not explicit and any(
                s.state == "preempted" and s.n_generated < s.n_new
                for s in self.sessions.values()):
            # nothing resident could decode, but swapped-out sessions
            # still owe tokens: force-resume the queue head (evicting
            # finished-but-unretired holdouts) so the round makes
            # progress — admission's solo-fit bound guarantees the
            # oldest preempted session eventually fits
            self._resume_preempted(force=True)
            group = [s for s in self.sessions.values()
                     if s.state == "active" and s.n_generated < s.n_new]
            if self.cache_layout == "paged":
                group = self._ensure_page_capacity(group)
            if not group:
                # livelock guard: even a forced resume could not seat the
                # queue head (e.g. its failover replacement chain is
                # capacity-starved for good) — fail it with a reason so
                # the caller's drive loop terminates
                self._abort_stuck_head()
        return group

    # ------------------------------------------------------------------
    # Paged layout: page growth, preemption, resume
    # ------------------------------------------------------------------
    def _pick_victim(self, j: int, protect: set,
                     finished_only: bool = False) -> Optional[int]:
        """Choose a session to preempt on server ``j``: finished-but-
        unretired sessions first (their caches are dead weight — no replay
        ever needed), then the latest-admitted active session (LIFO — the
        earliest-admitted session always survives, so every round makes
        forward progress).  Mid-prefill sessions are never victims: their
        pages are exactly their in-flight prompt."""
        cands = []
        for sid in self.servers[j].pool.rows:
            if sid in protect:
                continue
            s = self.sessions.get(sid)
            if s is None or s.state != "active":
                continue
            finished = s.n_generated >= s.n_new
            if finished_only and not finished:
                continue
            cands.append((0 if finished else 1, -sid, sid))
        return min(cands)[2] if cands else None

    def preempt_session(self, sid: int):
        """Swap a session out under page pressure: free its rows/pages on
        EVERY route server.  The client-side artifacts that survive — hop
        input histories, tokens, ``enc_out``, the sampling policy — are
        exactly the failover-replay cache, so ``_try_resume`` can rebuild
        bit-identical server state later.  Swapping OUT is free, but the
        rebuild is real compute: ``_try_resume`` bills the replay (prompt
        prefill + k·τ per regenerated token per hop, eq. (1)) on the
        virtual clock, exactly like a failover replay."""
        sess = self.sessions[sid]
        assert sess.state == "active", sess.state
        sess.last_logits  # materialize a lazy fused-round logits box
        sess.state = "preempted"
        sess.n_preemptions += 1
        sess._h = None
        sess._emb0 = None
        for j in set(sess.route.servers):
            if j in self.servers:
                self.servers[j].evict(sid)
        self._preempt_order.append(sid)
        self.round_stats["preemptions"] += 1

    def _grow_session(self, sess: EngineSession, need: int,
                      protect: set) -> bool:
        """Grow ``sess`` to ``need`` pages on every route server,
        preempting victims under pressure.  False when even preempting
        every candidate cannot make room (the caller then self-preempts
        the session; partial growth is harmless — pages stay booked)."""
        for j, k in zip(sess.route.servers, sess.route.blocks):
            srv = self.servers.get(j)
            if srv is None or not srv.alive or sess.sid not in srv.pool.rows:
                continue  # dead / not-yet-resident hop: _failover re-books
            pool = srv.pool
            while not pool.can_grow(sess.sid, need):
                victim = self._pick_victim(j, protect)
                if victim is None:
                    return False
                self.preempt_session(victim)
            pool.grow_pages(sess.sid, need)
        return True

    def _ensure_page_capacity(self, group: List[EngineSession]
                              ) -> List[EngineSession]:
        """Before a decode round: every member needs pages covering its
        write position.  Members are grown oldest-first (admission order);
        one that cannot fit even after evicting every victim preempts
        ITSELF and retries in a later round.  Returns the surviving group
        in the caller's order."""
        kept: List[EngineSession] = []
        for sess in sorted(group, key=lambda s: s.sid):
            if sess.state != "active":  # preempted as a victim just now
                continue
            need = pages_for(sess.pos + 1, self.page_size)
            if self._grow_session(sess, need,
                                  protect={s.sid for s in kept}
                                  | {sess.sid}):
                kept.append(sess)
            else:
                self.preempt_session(sess.sid)
        order = {s.sid: i for i, s in enumerate(group)}
        return sorted(kept, key=lambda s: order[s.sid])

    def _resume_preempted(self, force: bool = False):
        """Resume swapped-out sessions in preemption (FIFO) order while
        they fit; stop at the first that does not (no overtaking — the
        queue head's admission-time solo-fit bound guarantees it
        eventually fits).  ``force``: additionally evict finished-but-
        unretired page holders to make room for the queue head."""
        while self._preempt_order:
            sid = self._preempt_order[0]
            sess = self.sessions.get(sid)
            if (sess is None or sess.state != "preempted"
                    or sess.n_generated >= sess.n_new):
                self._preempt_order.pop(0)  # retired / finished meanwhile
                continue
            if not self._try_resume(sess, evict_finished=force):
                return
            self._preempt_order.pop(0)
            force = False  # only the queue head gets the forced eviction

    def _try_resume(self, sess: EngineSession,
                    evict_finished: bool = False) -> bool:
        """Re-admit a preempted session on its route's ALIVE servers and
        replay its client-side history — each hop independently replays
        its own recorded inputs (prompt chunks through the deterministic
        chunk plan, then one pooled decode per generated token), exactly
        the failover machinery, so the rebuilt caches are bit-identical.
        The rebuild is billed on the virtual clock (the swap carve-out is
        gone): per replayed hop, one input round-trip + weighted prompt
        prefill + k·τ per regenerated token.  Dead route servers are
        skipped: the next traverse splices them out via ``_failover`` once
        the session is resident again."""
        paged = self.cache_layout == "paged"
        need = pages_for(max(sess.pos, 1), self.page_size) if paged else 0
        worst = self._worst_pages(sess) if paged else None
        e = 0
        hops = []  # (hop index, server, block range) of alive hops
        for hop, (j, k) in enumerate(zip(sess.route.servers,
                                         sess.route.blocks)):
            lo, hi = e, e + k
            e += k
            if j in self.servers and self.servers[j].alive:
                hops.append((hop, j, lo, hi))
        if not hops:
            # the whole route died while swapped out: resume holding
            # nothing — the next traverse's ``_failover`` splices a full
            # replacement chain (booking its own pages) from the client-
            # side history, exactly as for a resident session
            sess.state = "active"
            self.round_stats["resumes"] += 1
            return True
        for _, j, lo, hi in hops:
            while not self.servers[j].fits(sess.sid, hi - lo, need, worst):
                if not evict_finished:
                    return False
                victim = self._pick_victim(j, protect={sess.sid},
                                           finished_only=True)
                if victim is None:
                    return False
                self.preempt_session(victim)
        for _, j, lo, hi in hops:
            self.servers[j].admit(sess.sid, hi - lo, n_pages=need)
        self._replay_session(sess)
        # bill the rebuild: each replayed hop re-ran its prompt prefill
        # plus one decode step per recorded token (eq. (1) terms)
        cost = 0.0
        for hop, j, lo, hi in hops:
            n_tok = max(len(sess.hop_inputs[hop]) - 1, 0) \
                if max(lo, self._n_enc) < hi else 0
            cost += recovery_replay_cost(
                self.problem, sess.client, [(j, lo, hi)], n_tok,
                slowdown_of=lambda jj: self.servers[jj].slowdown)
        sess.replay_time += cost
        sess.virtual_time += cost
        sess.n_replays += 1
        sess.end = (sess.start + sess.virtual_time
                    + max(sess.n_new - sess.n_generated, 0)
                    * sess.per_token_time)
        self.round_stats["replays"] += 1
        self.round_stats["replay_s"] += cost
        sess.state = "active"
        self.round_stats["resumes"] += 1
        return True

    def _replay_session(self, sess: EngineSession):
        """Rebuild a preempted session's caches on its (alive) route
        servers from the client-side hop histories.  Unlike ``_failover``
        — which chains activations through a REPLACEMENT chain — every
        original hop has its own complete input history, so hops replay
        independently and the outputs are discarded."""
        S = sess.prompt_len
        e = 0
        for hop, (j, k) in enumerate(zip(sess.route.servers,
                                         sess.route.blocks)):
            e_lo, e_hi = e, e + k
            e += k
            if j not in self.servers or not self.servers[j].alive:
                continue
            rec = sess.hop_inputs[hop][0]
            if self._is_enc_dec:
                hs_enc = rec.get("enc") if isinstance(rec, dict) else None
                hs_dec = rec.get("dec") if isinstance(rec, dict) else rec
                self._replay_prefill_encdec(sess, j, e_lo, e_hi, hs_enc,
                                            hs_dec)
            else:
                self._replay_prefill_range(sess, j, e_lo, e_hi, rec)
            if max(e_lo, self._n_enc) >= e_hi:
                continue  # encoder-only hop: no decode records
            for t_idx, h_tok in enumerate(sess.hop_inputs[hop][1:]):
                h_tok = self._hop_record(h_tok)
                pos = S + t_idx
                emb0 = None
                if self._needs_emb0:
                    emb0 = self._embed(
                        self.params["embed"],
                        jnp.asarray([[sess.tokens[pos]]], jnp.int32))
                self.servers[j].decode_range(
                    sess.sid, h_tok, max(e_lo, self._n_enc), e_hi, pos,
                    emb0=emb0, enc_len=sess.enc_len)

    def _decode_round_serial(self, group: List[EngineSession]
                             ) -> Dict[int, int]:
        """The pre-refactor round: per-session embed / lm_head dispatches
        and host-staged row buffers between hops (``_traverse``).  Kept as
        the reference (identical tokens/clock, float-ulp logits) and the
        per-session throughput baseline for the fused path
        (``BENCH_engine.json`` ``decode.tput.*``)."""
        for sess in group:
            tok = jnp.asarray([[sess.tokens[-1]]], jnp.int32)
            sess._h = self._embed(self.params["embed"], tok)
            sess._emb0 = sess._h
        self._traverse(group)
        emit = [s for s in group if s.state == "active"]
        for sess in emit:  # aborted-by-failover sessions are excluded
            sess.pos += 1
            logits = self._lm_head(self.params["embed"], sess._h)
            sess.last_logits = logits[0, 0]
        out: Dict[int, int] = {}
        if emit:
            for sess, nxt in zip(emit, self._sample_tokens(emit)):
                sess.tokens.append(nxt)
                sess.n_generated += 1
                sess.virtual_time += sess.per_token_time
                sess._h = None
                sess._emb0 = None
                out[sess.sid] = nxt
        return out

    def _round_inputs(self, group: List[EngineSession]):
        """A device-resident round's inputs over fixed-width (W, ...)
        buffers: each session's slot, and on the device the batched embed
        of every slot's last token, its cache position and its encoder
        length."""
        if len(group) > self._round_width:  # rare: re-trace at the new W
            self._round_width = len(group)
        W = self._round_width
        slot = {s.sid: i for i, s in enumerate(group)}
        tok_buf = np.zeros((W, 1), np.int32)
        pos_buf = np.zeros((W,), np.int32)
        encl_buf = np.zeros((W,), np.int32)
        for i, s in enumerate(group):
            tok_buf[i, 0] = s.tokens[-1]
            pos_buf[i] = s.pos
            encl_buf[i] = s.enc_len
        h_round = self._embed(self.params["embed"], jnp.asarray(tok_buf))
        self.round_stats["embed_dispatches"] += 1
        return slot, h_round, jnp.asarray(pos_buf), jnp.asarray(encl_buf)

    def _decode_round_fused(self, group: List[EngineSession],
                            slot: Dict[int, int], h_round, pos_round,
                            encl_round) -> Dict[int, int]:
        """Device-resident round over the fixed-width buffers of
        :meth:`_round_inputs`: the hidden states never leave the device
        between the embed and the round tail, and the ONLY host sync is
        the final batched token readback (one ``np.asarray``)."""
        W = self._round_width
        emb0_round = h_round if self._needs_emb0 else None
        h_round = self._traverse_fused(group, slot, h_round, pos_round,
                                       emb0_round, encl_round)
        emit = [s for s in group if s.state == "active"]
        out: Dict[int, int] = {}
        if emit:
            with tracing.span("round.tail"):
                temps = np.zeros((W,), np.float32)
                topks = np.zeros((W,), np.int32)
                # uint32: the full SamplingSpec.seed range (validated there)
                seeds = np.zeros((W,), np.uint32)
                tindex = np.zeros((W,), np.int32)
                for s in emit:
                    g = slot[s.sid]
                    temps[g], topks[g] = s.sampling.row_params()
                    seeds[g] = s.sampling.seed
                    tindex[g] = s.n_generated
                toks_dev, logits_rows = self._round_tail(
                    self.params["embed"], self._at_client(h_round),
                    jnp.asarray(temps),
                    jnp.asarray(topks), jnp.asarray(seeds),
                    jnp.asarray(tindex))
                self.round_stats["tail_dispatches"] += 1
            with tracing.span("round.sync"):
                toks = np.asarray(toks_dev)  # THE one host sync of the round
            for s in emit:
                g = slot[s.sid]
                s.pos += 1
                s._logits_box = (logits_rows, g)  # lazy: sliced on read
                nxt = int(toks[g])
                s.tokens.append(nxt)
                s.n_generated += 1
                s.virtual_time += s.per_token_time
                out[s.sid] = nxt
        self.round_stats["rounds"] += 1
        return out

    def _hop_span(self, sess: EngineSession, hop: int) -> Tuple[int, int]:
        e_lo = sum(sess.route.blocks[:hop])
        return e_lo, e_lo + sess.route.blocks[hop]

    def _hop_layers(self, sess: EngineSession, hop: int) -> Tuple[int, int]:
        """The blocks [lo, hi) of the hop that do decode-time work (its
        encoder blocks do none); empty where the hop holds only those."""
        e_lo, e_hi = self._hop_span(sess, hop)
        return max(e_lo, self._n_enc), e_hi

    def _traverse_core(self, group: List[EngineSession], process_group):
        """THE decode traversal skeleton shared by the host-staged and
        device-resident paths: advance every session in ``group`` through
        its full route (one token's worth of work), batching per
        (hop, server).  Hops hosting only encoder blocks are skipped —
        they do no decode-time work (and need no failover: their blocks
        are stateless).  ``process_group(srv, members, progress)`` runs
        ONE (server, members) hop group — the only thing the two variants
        differ in — after which each member's progress advances."""
        progress = {s.sid: 0 for s in group}

        def skip_enc_hops(s):
            while (s.state == "active"
                   and progress[s.sid] < len(s.route.servers)):
                lo, hi = self._hop_layers(s, progress[s.sid])
                if lo < hi:
                    return
                progress[s.sid] += 1

        while True:
            for s in group:
                skip_enc_hops(s)
            pending = [s for s in group
                       if s.state == "active"
                       and progress[s.sid] < len(s.route.servers)]
            if not pending:
                return
            # timeout detection first: a crashed-but-undetected server is
            # discovered by the dispatches that miss their deadline THIS
            # round — every session concurrently waiting on it bills the
            # detection wait + backoff probes, then the server is declared
            # dead (suspected) and the failovers below splice it out
            crashed_now = sorted({
                s.route.servers[progress[s.sid]] for s in pending
                if (srv := self.servers.get(
                    s.route.servers[progress[s.sid]])) is not None
                and srv.alive and srv.crashed})
            for j in crashed_now:
                self._detect_crash(j, [
                    (s, self._expected_hop_decode(s, progress[s.sid]))
                    for s in pending
                    if s.route.servers[progress[s.sid]] == j])
            # failover: splice routes of sessions facing a dead server or
            # one that lost their cache row (rejoined with an empty pool,
            # or a resume that skipped then-dead hops)
            for s in pending:
                hop = progress[s.sid]
                while self._hop_needs_failover(s, hop):
                    try:
                        self._failover(s, hop)
                    except NoCapacityError:
                        # transient: capacity frees as co-residents retire.
                        # Park the session in the resume queue instead of
                        # failing it; a lone legacy-decode session still
                        # propagates (its caller owns the retry).
                        if len(group) == 1:
                            raise
                        self._defer_session(s)
                        break
                    except RuntimeError:
                        # no surviving chain covers the blocks: fail it
                        # alone — co-resident sessions must keep decoding.
                        # A lone session propagates (legacy decode semantics).
                        if len(group) == 1:
                            raise
                        self._abort_session(s, reason="no_route")
                        break
            pending = [s for s in pending if s.state == "active"]
            groups: Dict[int, List[EngineSession]] = {}
            for s in pending:
                groups.setdefault(s.route.servers[progress[s.sid]],
                                  []).append(s)
            for j, members in groups.items():
                srv = self.servers[j]
                with tracing.span("round.hop") as sp:
                    if sp:  # attrs set here: nothing is built when off
                        sp.set(server=j, rows=srv.pool.n_rows, m=srv.m,
                               row_layers_useful=sum(
                                   hi - lo for lo, hi in (
                                       self._hop_layers(s, progress[s.sid])
                                       for s in members)),
                               row_layers_computed=srv.pool.n_rows * srv.m)
                    process_group(srv, members, progress)
                for s in members:
                    progress[s.sid] += 1

    def _traverse(self, group: List[EngineSession]):
        """Host-staged traversal (``decode_mode="serial"`` and the legacy
        per-session ``decode``): per-session hidden states are scattered
        into (N, ...) row buffers on the host before every hop.  The
        device-resident round uses ``_traverse_fused`` — same skeleton
        (``_traverse_core``), different hop-group body."""

        def process_group(srv, members, progress):
            N = srv.pool.n_rows
            d = members[0]._h.shape[-1]
            dt = np.asarray(members[0]._h).dtype
            h_buf = np.zeros((N, 1, d), dt)
            pos_buf = np.zeros((N,), np.int32)
            emb0_buf = (np.zeros((N, 1, d), dt)
                        if self._needs_emb0 else None)
            encl_buf = (np.zeros((N,), np.int32)
                        if self._is_enc_dec else None)
            mask = np.zeros((srv.m, N), bool)
            rows = {}
            for s in members:
                hop = progress[s.sid]
                row = srv.pool.rows[s.sid]
                lo, hi = self._hop_layers(s, hop)
                s.hop_inputs[hop].append(s._h)
                h_buf[row] = np.asarray(s._h[0])
                pos_buf[row] = s.pos
                if emb0_buf is not None:
                    emb0_buf[row] = np.asarray(s._emb0[0])
                if encl_buf is not None:
                    encl_buf[row] = s.enc_len
                mask[lo - srv.a: hi - srv.a, row] = True
                rows[s.sid] = row
            h_out = srv.decode_rows(
                jnp.asarray(h_buf), jnp.asarray(pos_buf),
                jnp.asarray(mask),
                None if emb0_buf is None else jnp.asarray(emb0_buf),
                None if encl_buf is None else jnp.asarray(encl_buf))
            for s in members:
                s._h = h_out[rows[s.sid]][None]

        self._traverse_core(group, process_group)

    def _traverse_fused(self, group: List[EngineSession],
                        slot: Dict[int, int], h_round, pos_round,
                        emb0_round, encl_round):
        """Device-resident traversal: the round's hidden states live in
        ``h_round`` (W, 1, d) and flow hop to hop through the fused
        gather+step+scatter dispatch (``BlockServer.round_rows``) — only
        small int32 index/mask vectors cross the host boundary, never
        activations.  Control flow is ``_traverse_core``, shared with the
        host-staged ``_traverse``."""

        def process_group(srv, members, progress):
            nonlocal h_round
            N = srv.pool.n_rows
            W = h_round.shape[0]
            slot_of_row = np.full((N,), -1, np.int32)
            row_of_slot = np.full((W,), -1, np.int32)
            mask = np.zeros((srv.m, N), bool)
            gidx = []
            for s in members:
                row = srv.pool.rows[s.sid]
                lo, hi = self._hop_layers(s, progress[s.sid])
                slot_of_row[row] = slot[s.sid]
                row_of_slot[slot[s.sid]] = row
                mask[lo - srv.a: hi - srv.a, row] = True
                gidx.append(slot[s.sid])
            # client-side failover cache: ONE device gather of the hop's
            # member rows; each member holds a lazy (buffer, index) record
            # materialized to (1, 1, d) only if a failover ever replays it
            # (_hop_record).  Retained memory per (hop, round) is
            # members x d — the serial path's footprint, not
            # round-width x d.
            h_in = h_round[jnp.asarray(gidx)]
            for i, s in enumerate(members):
                s.hop_inputs[progress[s.sid]].append((h_in, i))
            h_round = srv.round_rows(
                h_round, pos_round, encl_round,
                jnp.asarray(slot_of_row), jnp.asarray(row_of_slot),
                jnp.asarray(mask), emb0_round=emb0_round)
            self.round_stats["hop_dispatches"] += 1

        self._traverse_core(group, process_group)
        return h_round

    def _abort_session(self, sess: EngineSession, reason: str = "no_route"):
        """Mark a session unservable and free its slots; the record stays
        in ``sessions`` for the scheduler to report as dropped, with a
        machine-readable ``fail_reason`` ("no_route", "no_capacity",
        "server_lost_mid_prefill", ...)."""
        sess.state = "failed"
        if sess.fail_reason is None:
            sess.fail_reason = reason
        sess._h = None
        sess._emb0 = None
        for j in set(sess.route.servers):
            if j in self.servers:
                self.servers[j].evict(sess.sid)

    def _defer_session(self, sess: EngineSession):
        """Capacity-starved failover (:class:`NoCapacityError`): park the
        session in the resume queue instead of hard-failing it — capacity
        frees up as co-residents retire.  The in-flight round's partial
        hop records are stripped first so every decode-capable hop keeps
        exactly (prompt + one record per COMPLETED round) and a later
        replay stays position-exact.  A session that keeps bouncing
        (deferred-resumed-deferred) is failed after a bounded number of
        attempts — the livelock guard for chains that never regain
        capacity."""
        if sess.n_defer_resumes >= 8:
            self._abort_session(sess, reason="no_capacity")
            return
        sess.n_defer_resumes += 1
        e = 0
        dec_hops = []
        for hop, k in enumerate(sess.route.blocks):
            lo, hi = e, e + k
            e += k
            if max(lo, self._n_enc) < hi:
                dec_hops.append(hop)
        if dec_hops:
            n = min(len(sess.hop_inputs[hop]) for hop in dec_hops)
            for hop in dec_hops:
                del sess.hop_inputs[hop][n:]
        self.preempt_session(sess.sid)

    def _abort_stuck_head(self):
        """Fail the resume queue's head with ``"no_capacity"`` — called
        when even a forced resume could not seat anything, so waiting
        longer cannot help (nothing is left to retire)."""
        while self._preempt_order:
            sid = self._preempt_order[0]
            sess = self.sessions.get(sid)
            if (sess is None or sess.state != "preempted"
                    or sess.n_generated >= sess.n_new):
                self._preempt_order.pop(0)
                continue
            self._preempt_order.pop(0)
            self._abort_session(sess, reason="no_capacity")
            return

    def retire_session(self, sid: int) -> Optional[EngineSession]:
        """Free the session's rows/block-slots on every server; returns the
        session record (metrics live on it)."""
        with tracing.span("retire", sid=sid):
            sess = self.sessions.pop(sid, None)
            if sess is None:
                return None
            if sess.state == "prefilling":  # dropped mid-prompt
                for g in self._prefill_groups:
                    g.members = [s for s in g.members if s.sid != sid]
                self._prefill_groups = [g for g in self._prefill_groups
                                        if g.members]
            if sess.state != "failed":
                sess.state = "done"
            for j in set(sess.route.servers):
                if j in self.servers:
                    self.servers[j].evict(sid)
            return sess

    def concurrency(self) -> int:
        """Sessions currently holding cache slots (prefilling or decoding)."""
        return sum(1 for s in self.sessions.values()
                   if s.state in ("active", "prefilling"))

    def slot_usage(self) -> Dict[int, Tuple[int, int]]:
        """{server: (used, capacity)} in the layout's eq. (5) accounting
        unit — block-slots (slab) or page-units (paged); the
        invariant-check hook."""
        return {j: srv.pool.usage() for j, srv in self.servers.items()}

    # ------------------------------------------------------------------
    # Legacy single-session API (implemented on the pooled machinery)
    # ------------------------------------------------------------------
    def submit(self, tokens: np.ndarray, client: int = 0, now: float = 0.0,
               frames: Optional[np.ndarray] = None,
               sampling: Optional[SamplingSpec] = None
               ) -> Tuple[int, jnp.ndarray]:
        """Start a session immediately (prefill).  Returns (sid, logits)."""
        alive = self.alive_placement()
        if self.algorithm == "proposed":
            route, _ = shortest_path_route(self.problem, alive, client)
        else:
            route = petals_route(self.problem, alive, client)
        if route is None:
            raise RuntimeError("no feasible route")
        sid = self.create_session(tokens, client, route,
                                  n_new=self.max_new_tokens, arrival=now,
                                  frames=frames, sampling=sampling)
        if not self.try_admit_session(sid, now=now):
            self.sessions.pop(sid)
            raise RuntimeError("no free cache slots for immediate admission")
        return sid, self.sessions[sid].last_logits[None]

    def decode(self, sid: int, token: int) -> jnp.ndarray:
        """One decode step through the session's chain.  The caller picks
        the token for the last position — a provisional sampled tail left by
        ``try_admit_session``/``decode_round`` is replaced, not duplicated."""
        sess = self.sessions[sid]
        if len(sess.tokens) == sess.pos + 1:
            sess.tokens[-1] = int(token)  # unprocessed provisional tail
        else:
            sess.tokens.append(int(token))
        sess.n_generated = len(sess.tokens) - sess.prompt_len
        if self.cache_layout == "paged":
            # legacy single-session semantics: growth failure propagates
            if not self._grow_session(sess,
                                      pages_for(sess.pos + 1,
                                                self.page_size),
                                      protect={sess.sid}):
                raise RuntimeError(
                    f"session {sid}: no page capacity for decode")
        tok = jnp.asarray([[int(token)]], jnp.int32)
        sess._h = self._embed(self.params["embed"], tok)
        sess._emb0 = sess._h
        self._traverse([sess])
        sess.pos += 1
        sess.virtual_time += self._route_per_token(sess)
        logits = self._lm_head(self.params["embed"], sess._h)
        sess.last_logits = logits[0, 0]
        sess._h = None
        sess._emb0 = None
        return logits[:, 0]

    def finish(self, sid: int):
        self.retire_session(sid)

    # ------------------------------------------------------------------
    # Fault tolerance
    # ------------------------------------------------------------------
    def kill_server(self, j: int):
        """ORACLE fail-stop: flip the server dead with instant, free
        detection (tests / back-compat).  For the realistic path — the
        crash is only discovered when a dispatch misses its deadline, and
        detection + backoff are billed — use :meth:`inject_crash` or a
        :class:`FaultPlan`.  Unknown or already-dead ids raise."""
        srv = self.servers.get(j)
        if srv is None or not srv.alive:
            alive = sorted(jj for jj, s in self.servers.items() if s.alive)
            raise ValueError(
                f"kill_server({j}): "
                + ("server is already dead" if srv is not None
                   else "no such server")
                + f"; alive servers: {alive}")
        srv.alive = False
        srv.crashed = False
        srv.suspected = True

    def inject_crash(self, j: int):
        """Timeout-detected crash: the server goes silent but ``alive``
        stays True — the next dispatch that misses its deadline detects
        the loss and bills detection + backoff (``_detect_crash``)."""
        srv = self.servers.get(j)
        if srv is None or not srv.alive:
            alive = sorted(jj for jj, s in self.servers.items() if s.alive)
            raise ValueError(
                f"inject_crash({j}): unknown or already-dead server; "
                f"alive servers: {alive}")
        srv.crashed = True

    def rejoin_server(self, j: int):
        """A crashed server returns — with an EMPTY pool (its RAM-resident
        caches died with it), whether or not anyone detected the outage.
        Sessions that still route through it lose their rows here and are
        spliced by the next traverse's residency failover (billed replay,
        no detection wait: the server answers promptly, just emptily).
        The ``suspected`` flag survives the rejoin so routing keeps its
        flap-avoidance penalty until the controller clears it."""
        srv = self.servers.get(j)
        if srv is None:
            raise ValueError(f"rejoin_server({j}): no such server; known "
                             f"servers: {sorted(self.servers)}")
        for sid in list(srv.pool.rows):
            srv.evict(sid)
        srv.alive = True
        srv.crashed = False
        self.round_stats["rejoins"] += 1

    def suspected_servers(self) -> List[int]:
        """Servers once declared dead by timeout (flap-avoidance input
        for the controller's suspicion-aware routing)."""
        return sorted(j for j, srv in self.servers.items() if srv.suspected)

    def apply_faults(self, now: float) -> List:
        """Apply every :class:`FaultPlan` event due by virtual time
        ``now`` (idempotent — a cursor tracks what already fired).
        Returns the events applied this call."""
        if self.fault_plan is None:
            return []
        due, self._fault_cursor = self.fault_plan.due(self._fault_cursor,
                                                      now)
        for ev in due:
            srv = self.servers.get(ev.server)
            if ev.kind == "crash":
                if srv is not None and srv.alive and not srv.crashed:
                    srv.crashed = True
            elif ev.kind == "rejoin":
                if srv is not None:
                    self.rejoin_server(ev.server)
            elif ev.kind == "straggler_start":
                self.set_slowdown(ev.server, ev.factor)
            elif ev.kind == "straggler_end":
                self.set_slowdown(ev.server, 1.0)
            elif ev.kind == "dispatch_error":
                self._dispatch_faults.add(ev.server)
        return due

    def join_server(self, spec, rtt_token_col, rtt_prefill_col):
        """Elastic scale-out: add a server and re-run placement (Alg. 2)."""
        servers = list(self.problem.servers) + [
            dataclasses.replace(spec, sid=self.problem.n_servers)]
        rtt_t = np.concatenate(
            [self.problem.rtt_token, np.asarray(rtt_token_col).reshape(-1, 1)],
            axis=1)
        rtt_p = np.concatenate(
            [self.problem.rtt_prefill,
             np.asarray(rtt_prefill_col).reshape(-1, 1)], axis=1)
        self.problem = Problem(self.problem.llm, servers,
                               self.problem.n_clients, rtt_t, rtt_p,
                               self.problem.workload)
        self._base_taus.append(float(spec.tau))
        if self.algorithm == "proposed":
            from repro.core.placement import cg_bp
            self.placement, _ = cg_bp(self.problem, self.R)
        else:
            self.placement = petals_bp(self.problem)
        # NOTE: re-placement applies to NEW sessions; running sessions keep
        # their routes and caches (slow-time-scale semantics of Alg. 2).
        self._build_servers()

    def _subchain(self, lo: int, hi: int, client: int
                  ) -> Optional[Tuple[int, ...]]:
        """Min-cost chain of ALIVE servers covering exactly blocks [lo, hi)."""
        alive = self.alive_placement()
        # clip hosted ranges into [lo, hi] and run the same DAG DP (both
        # ends clipped: a host starting past ``hi`` must not index the
        # subproblem's weight table out of range)
        a = np.clip(alive.a, lo, hi)
        end = np.clip(alive.a + alive.m, lo, hi)
        m = np.maximum(end - a, 0)
        m[alive.m <= 0] = 0
        sub = Placement(a=a - lo, m=m)
        subproblem = dataclasses.replace(self.problem)
        kw = dict(n_blocks=hi - lo)
        if self.problem.llm.block_tau is not None:
            kw["block_tau"] = self.problem.llm.block_tau[lo:hi]
        subproblem.llm = dataclasses.replace(self.problem.llm, **kw)
        route, _ = shortest_path_route(subproblem, sub, client)
        return route.servers if route is not None else None

    def _replay_prefill_range(self, sess: EngineSession, j: int, lo: int,
                              hi: int, h_full):
        """Failover replay of one hop's prompt prefill (single-phase
        stacks).  In batched mode the replay follows the session's
        deterministic chunk plan through the SAME pooled programs that
        built the original caches — zero pad columns are bit-equivalent to
        the originals because padded positions are causally masked out of
        every valid position's computation — so the rebuilt caches are
        bit-identical.  Recurrent stacks replay exact-length in one shot
        (their plan).  Serial mode keeps the legacy exact-length replay."""
        srv = self.servers[j]
        emb0_full = None
        if self._needs_emb0:
            emb0_full = self._embed(
                self.params["embed"],
                jnp.asarray([sess.tokens[: sess.prompt_len]], jnp.int32))
        if self.prefill_mode == "serial":
            return srv.prefill_range(sess.sid, h_full, lo, hi,
                                     jnp.arange(h_full.shape[1]),
                                     emb0=emb0_full)
        return self._replay_chunked(sess, srv, lo, hi, h_full, "all",
                                    emb0_full=emb0_full)

    def _replay_chunked(self, sess: EngineSession, srv: BlockServer,
                        lo: int, hi: int, h_full, phase: str,
                        enc=None, emb0_full=None):
        """Replay blocks [lo, hi) of one session's prompt through the row
        prefill step that built its caches, following its deterministic
        chunk plan — the ONE chunk-replay loop shared by the single-phase
        and enc-dec failover paths."""
        d = h_full.shape[-1]
        row = srv.pool.rows[sess.sid]
        layers = srv.hop_layers(lo, hi)
        outs = []
        for off, span, t_pad in self._prefill_plan(h_full.shape[1]):
            chunk = h_full[:, off: off + span]
            if t_pad > span:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((1, t_pad - span, d), chunk.dtype)], 1)
            emb0 = None
            if emb0_full is not None:  # recurrent plan: one exact chunk
                emb0 = emb0_full[:, off: off + t_pad]
            h_out = srv.prefill_row(chunk, row, layers, offset=off,
                                    phase=phase, emb0=emb0, enc=enc)
            outs.append(h_out[:, :span])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _replay_prefill_encdec(self, sess: EngineSession, j: int, lo: int,
                               hi: int, hs_enc, hs_dec):
        """Failover replay of one replacement hop of an enc-dec route: the
        encoder sub-range replays the exact-length frame activations (the
        blocks are stateless — this only threads the activations forward so
        later hop histories stay exact), the decoder sub-range replays the
        prompt per its chunk plan, rebuilding self-KV and cross-KV (from
        the session's cached ``enc_out``)."""
        srv = self.servers[j]
        n_enc = self._n_enc
        if lo < n_enc and hs_enc is not None:
            elo, ehi = lo, min(hi, n_enc)
            if self.prefill_mode == "serial":
                hs_enc = srv.prefill_range(sess.sid, hs_enc, elo, ehi,
                                           jnp.arange(hs_enc.shape[1]))
            else:
                hs_enc = srv.prefill_row(hs_enc, srv.pool.rows[sess.sid],
                                         srv.hop_layers(elo, ehi),
                                         phase="enc")
        if hi > n_enc and hs_dec is not None:
            dlo = max(lo, n_enc)
            if self.prefill_mode == "serial":
                hs_dec = srv.prefill_range(
                    sess.sid, hs_dec, dlo, hi,
                    jnp.arange(hs_dec.shape[1]), enc_h=sess.enc_out)
            else:
                hs_dec = self._replay_chunked(sess, srv, dlo, hi, hs_dec,
                                              "dec", enc=sess.enc_out)
        return hs_enc, hs_dec

    @staticmethod
    def _hop_record(rec):
        """Materialize one decode-token hop record: the fused round path
        stores lazy ((members, 1, d) hop gather, index) tuples; the
        host-staged paths store (1, 1, d) arrays directly."""
        if isinstance(rec, tuple):
            buf, g = rec
            return buf[g][None]
        return rec

    def _failover(self, sess: EngineSession, hop: int):
        """Replace the lost server at ``hop`` by a chain of alive servers
        and replay the client-side cached inputs to rebuild their caches.
        "Lost" covers dead servers AND alive ones that no longer hold the
        session's row (rejoined with an empty pool) — the latter may
        re-enter the replacement chain and simply get re-prefilled.  The
        replay is billed on the virtual clock (``recovery_replay_cost``):
        per replacement hop, one input round-trip + weighted prompt
        prefill + k·τ per replayed token."""
        dead_j = sess.route.servers[hop]
        e_lo = sum(sess.route.blocks[:hop])
        e_hi = e_lo + sess.route.blocks[hop]
        chain = self._subchain(e_lo, e_hi, sess.client)
        if chain is None:
            raise RuntimeError(
                f"no surviving servers cover blocks [{e_lo},{e_hi})")
        inputs = sess.hop_inputs[hop]
        rec = inputs[0]
        new_servers = list(sess.route.servers)
        new_blocks = list(sess.route.blocks)
        repl_routes = []
        e = e_lo
        alive = self.alive_placement()
        for j in chain:
            k = int(min(alive.a[j] + alive.m[j], e_hi) - e)
            repl_routes.append((j, e, e + k))
            e += k
        # claim slots on the replacement chain, then replay.  Paged layout:
        # the replacement hops book pages covering everything the replay
        # and the in-flight round will write ([0, pos] — the round that
        # triggered this failover writes position pos)
        n_pages = worst = None
        if self.cache_layout == "paged":
            n_pages = pages_for(min(sess.pos + 1, self.max_seq_len),
                                self.page_size)
            worst = self._worst_pages(sess)
        for j, lo, hi2 in repl_routes:
            if not self.servers[j].fits(sess.sid, hi2 - lo,
                                        n_pages or 0, worst):
                raise NoCapacityError(
                    f"failover target {j} has no free cache slots")
        for j, lo, hi2 in repl_routes:
            self.servers[j].admit(sess.sid, hi2 - lo,
                                  n_pages=n_pages or 0)
        # replay, recording each replacement hop's OWN input history so a
        # later failure of any replacement hop replays correct activations
        new_histories: List[List] = [[] for _ in repl_routes]
        if self._is_enc_dec:
            hs_enc = rec.get("enc") if isinstance(rec, dict) else None
            hs_dec = rec.get("dec") if isinstance(rec, dict) else rec
            for i, (j, lo, hi2) in enumerate(repl_routes):
                new_histories[i].append(
                    {"enc": hs_enc if lo < self._n_enc else None,
                     "dec": hs_dec if hi2 > self._n_enc else None})
                hs_enc, hs_dec = self._replay_prefill_encdec(
                    sess, j, lo, hi2, hs_enc, hs_dec)
        else:
            hs = rec
            for i, (j, lo, hi2) in enumerate(repl_routes):
                new_histories[i].append(hs)
                hs = self._replay_prefill_range(sess, j, lo, hi2, hs)
        # replay each decoded token (encoder-only replacement hops have no
        # decode-time work — and, symmetrically, an encoder-only dead hop
        # recorded no decode inputs)
        S = sess.prompt_len
        for t_idx, h_tok in enumerate(inputs[1:]):
            h_tok = self._hop_record(h_tok)
            pos = S + t_idx
            emb0 = None
            if self._needs_emb0:
                emb0 = self._embed(
                    self.params["embed"],
                    jnp.asarray([[sess.tokens[pos]]], jnp.int32))
            hh = h_tok
            for i, (j, lo, hi2) in enumerate(repl_routes):
                if hi2 <= self._n_enc:
                    continue
                new_histories[i].append(hh)
                hh = self.servers[j].decode_range(
                    sess.sid, hh, lo, hi2, pos, emb0=emb0,
                    enc_len=sess.enc_len)
        # splice the replacement chain into the route
        new_servers[hop: hop + 1] = [j for j, _, _ in repl_routes]
        new_blocks[hop: hop + 1] = [hi2 - lo for _, lo, hi2 in repl_routes]
        sess.hop_inputs[hop: hop + 1] = new_histories
        sess.route = Route(servers=tuple(new_servers),
                           blocks=tuple(new_blocks))
        # a rejoined server may sit in its own replacement chain — don't
        # evict the row the replay just rebuilt
        if dead_j in self.servers and \
                dead_j not in {j for j, _, _ in repl_routes}:
            self.servers[dead_j].evict(sess.sid)
        # bill the rebuild (eq. (1) terms): per replacement hop, one input
        # round-trip + weighted prompt prefill + k·τ per replayed token
        n_replay_tok = len(inputs) - 1
        cost = recovery_replay_cost(
            self.problem, sess.client, repl_routes, n_replay_tok,
            slowdown_of=lambda jj: self.servers[jj].slowdown)
        sess.replay_time += cost
        sess.virtual_time += cost
        sess.n_replays += 1
        self.round_stats["replays"] += 1
        self.round_stats["replay_s"] += cost
        # remaining tokens are billed at the NEW route's cost; the virtual
        # retirement time shifts accordingly
        sess.per_token_time = self._route_per_token(sess)
        sess.end = (sess.start + sess.virtual_time
                    + max(sess.n_new - sess.n_generated, 0)
                    * sess.per_token_time)

    # ------------------------------------------------------------------
    def set_slowdown(self, j: int, factor: float):
        """Straggler injection: server j runs ``factor``x its calibrated
        speed.  ``factor`` is ABSOLUTE over the construction-time tau (not
        cumulative), so ``set_slowdown(j, 1.0)`` ends a straggler interval
        cleanly.  The degraded tau lands in ``self.problem`` — routing of
        future sessions, the eq. (1) clock of new routes, and detection
        deadlines all see it."""
        servers = list(self.problem.servers)
        servers[j] = dataclasses.replace(servers[j],
                                         tau=self._base_taus[j] * factor)
        self.problem = dataclasses.replace(self.problem)
        self.problem.servers = servers
        # in-flight sessions routed through j decode at the degraded rate
        # from now on (and recover when the straggler interval ends)
        for sess in self.sessions.values():
            if (sess.state in ("active", "preempted")
                    and j in sess.route.servers):
                sess.per_token_time = self._route_per_token(sess)
                sess.end = (sess.start + sess.virtual_time
                            + max(sess.n_new - sess.n_generated, 0)
                            * sess.per_token_time)


def generate(system: GeoServingSystem, tokens: np.ndarray, n_new: int,
             client: int = 0, frames: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, float]:
    """End-to-end greedy generation driver.  Returns (tokens, virtual_time)."""
    sid, logits = system.submit(tokens, client, frames=frames)
    out = list(np.asarray(tokens))
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits[-1] if logits.ndim > 1 else logits))
        out.append(nxt)
        logits = system.decode(sid, nxt)
    vt = system.sessions[sid].virtual_time
    system.finish(sid)
    return np.asarray(out), vt
