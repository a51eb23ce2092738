"""Paged continuous-batching engine: chunked prefill interleaved with decode.

The dense ``Engine`` (serving/engine.py) admits one request at a time with a
blocking full-prompt prefill into per-slot ``max_len`` caches.  This engine
replaces both halves:

  * KV memory is a shared page pool (serving/kvcache.py) — footprint scales
    with resident tokens, and admission never over-reserves;
  * each ``step()`` runs a token-budget slice of pending *prefill chunks*
    (the ISO chunk boundaries from ``core/chunking.split_chunks`` are the
    scheduling quanta) and then ONE batched decode step for every request
    whose prompt is fully resident — Sarathi-style chunk/decode mixing across
    requests, ISO overlap order inside each prefill call.  Grants sharing a
    bucket-padded length are PACKED into one multi-row forward call per tick
    (``ServingConfig.prefill_batching``, attention-only stacks): per-row
    ``pos_offset``/``prefix_len``/``valid_len`` ride through ``StageCtx``
    into the paged flash-prefill kernel, so a fresh request (prefix 0) and
    resumed requests at arbitrary depths share one call and one ISO overlap
    schedule instead of N serialized batch-1 calls.

A request whose prompt is partially prefilled keeps its KV prefix in pages and
its recurrent (SSM/xLSTM) states in per-slot arrays across engine steps; the
next grant resumes with ``prefill(prefix_caches=..., pos_offset=start)``.
When the pool runs dry the scheduler evicts a victim (recompute preemption:
its pages are freed and prompt+generated re-enter the waiting queue).

Decode reads the page pools IN PLACE through the paged flash-decode kernel
(kernels/flash_decode.py) — no dense gather.  With ``mesh`` both jitted
closures run inside ``shard_map`` over the TP "model" axis, and the batched
decode uses the batch-split ISO schedule (core/iso.run_stack_decode_overlap)
so each half's all-reduce hides behind the other half's compute.  Requests
with a common prompt prefix share KV pages copy-on-write
(``PageAllocator.adopt``/``cow`` + ``PrefixCache``).  With
``ServingConfig.spec_k > 0`` the decode phase verifies a (spec_k+1)-token
self-drafted window per slot through the same kernel — the paper's
§Discussion decode-side regime where fatter steps amortise the memory-bound
cache walk — committing only accepted tokens and rolling rejected positions
back by ``pos`` invalidation.  See docs/serving.md.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import Config, ServingConfig
from repro.core.chunking import grant_buckets, round_to_bucket
from repro.core.overlap import AxisCtx
from repro.layers import embeddings as emb_lib
from repro.models import api
from repro.obs.registry import (ACCEPT_LEN_BUCKETS, GRANT_SIZE_BUCKETS,
                                MetricsRegistry, TPOT_BUCKETS_S,
                                TTFT_BUCKETS_S)
from repro.obs.trace import TraceRing
from repro.models.decoder import (cache_specs, decoder_param_specs,
                                  prefill_chunk_lengths)
from repro.serving.kvcache import (OutOfPages, PrefixCache, pages_for,
                                   scatter_pages, token_page_coords,
                                   window_page_coords)
from repro.serving.kvstate import KVPool
from repro.serving.requests import Request, RequestState
from repro.serving.sampler import sample
from repro.serving.scheduler import TokenBudgetScheduler, plan_chunks

# TP serving shards each page pool's KV-head axis: (Pd, page, HEADS, ps, hd)
KV_POOL_SPEC = P(None, None, "model", None, None)

# host time of each step phase: the self time of its span (TraceRing.span),
# seconds, cumulative.  With prefill_s + decode_s (which hold the dispatch
# spans and the device_wait fences) they add up to the engine/step spans.
HOST_COUNTERS = ("host_step_other_s", "host_admit_s", "host_schedule_s",
                 "host_pages_s", "host_inputs_s", "host_logits_s",
                 "host_sample_s", "host_release_s")


class PagedEngine:
    def __init__(self, config: Config, params, *, serving: ServingConfig = None,
                 mesh=None, phase: str = "mixed", kv_pool: KVPool = None):
        assert config.model.family != "audio", \
            "enc-dec (whisper) serving stays on the dense Engine"
        assert phase in ("mixed", "prefill", "decode"), phase
        self.config = config
        self.cfg = config.model
        self.params = params
        sv = serving or config.serving
        self.sv = sv
        self.ps = sv.page_size
        self.max_batch = sv.max_batch
        self.max_len = sv.max_len
        self.max_blocks = -(-sv.max_len // sv.page_size)
        num_pages = sv.num_pages or sv.max_batch * self.max_blocks
        cache_dtype = jax.tree_util.tree_leaves(params)[0].dtype

        # tensor-parallel serving: the whole engine step (prefill grants and
        # the batched decode) runs inside shard_map over the "model" axis
        self.mesh = mesh
        if mesh is not None:
            assert config.parallel.data == 1 and config.parallel.pods == 1, \
                "paged TP serving shards the model axis only"
            self.tp = config.parallel.model
            self._ctx = AxisCtx(tp_axis="model", tp=self.tp,
                                quantized_comm=config.iso.quantized_comm)
        else:
            self.tp = 1
            self._ctx = AxisCtx()
        # decode collective schedule (core/iso.py).  Ladder-wired configs
        # always run the ladder driver (the wiring is part of the model
        # function); decode_overlap only picks deferred vs immediate
        # collectives inside it.  Standard wiring: "auto" means the
        # batch-split schedule under TP (each half's all-reduce hides behind
        # the other half's attention), sequential otherwise; explicit
        # ServingConfig.decode_schedule forces sequential / batch_split /
        # cross_block.  A batch-split engine additionally falls back to a
        # sequential closure per step when < 2 requests are resident
        # (_decode_phase) — one active request has no second half.
        if self.cfg.residual_wiring == "ladder":
            self._decode_schedule = "ladder" if sv.decode_overlap \
                else "ladder_seq"
        elif sv.decode_schedule == "auto":
            self._decode_schedule = "batch_split" \
                if (mesh is not None and sv.decode_overlap
                    and sv.max_batch >= 2) else "sequential"
        else:
            assert sv.decode_schedule in ("sequential", "batch_split",
                                          "cross_block"), sv.decode_schedule
            self._decode_schedule = sv.decode_schedule
        # legacy view, pinned by tests: True iff batch-split is the schedule
        self._decode_overlap = self._decode_schedule == "batch_split"

        # observability (src/repro/obs): typed registry behind the legacy
        # dict view, structured trace ring the scheduler/allocator/phase
        # loops narrate into.  The registry is always on (counter bumps are
        # host-side nanoseconds); ``observability=False`` silences the trace.
        self.registry = MetricsRegistry()
        self.trace = TraceRing(capacity=sv.trace_events,
                               enabled=sv.observability)
        self.registry.histogram("ttft", TTFT_BUCKETS_S)
        self.registry.histogram("tpot", TPOT_BUCKETS_S)
        self.registry.histogram("grant_size", GRANT_SIZE_BUCKETS)
        self.registry.histogram("accept_len", ACCEPT_LEN_BUCKETS)
        self.registry.gauge("pool_occupancy")
        self.registry.gauge("free_list_fragmentation")

        # measured cost model (perf/costmodel.py): an injected CostModel, or
        # one loaded from ``cost_table`` ("" = off, "auto" = the bundled
        # per-platform table, else a path).  Load failures — missing file,
        # malformed table, wrong platform/mesh — emit ONE warning trace event
        # and leave the model None: every decision below then uses the
        # static-default path unchanged.
        self.cost_model = sv.cost_model
        if self.cost_model is None and sv.cost_table:
            from repro.perf.costmodel import load_cost_model
            self.cost_model = load_cost_model(
                sv.cost_table, platform=jax.default_backend(), tp=self.tp,
                trace=self.trace)

        # KV ownership lives OUTSIDE the engine (serving/kvstate.KVPool):
        # allocator + device page pools travel as one object, so KV state can
        # be exported/imported across engines (disaggregated serving, KV
        # offload/restore).  An injected pool is re-pointed at this engine's
        # trace ring so the replay-conservation oracle stays per-engine.
        if kv_pool is None:
            sharding = None if mesh is None else NamedSharding(
                mesh, KV_POOL_SPEC)
            kv_pool = KVPool.create(self.cfg, num_pages, self.ps, tp=self.tp,
                                    dtype=cache_dtype, trace=self.trace,
                                    sharding=sharding)
        else:
            assert kv_pool.page_size == self.ps, (kv_pool.page_size, self.ps)
            kv_pool.alloc.trace = self.trace
        self.pool = kv_pool
        self.alloc = kv_pool.alloc
        self.kv = kv_pool.kv
        # phase routing (disagg): "prefill" never runs the decode phase,
        # "decode" never admits/prefills; "mixed" = the single-engine default
        self.phase = phase
        self.states = api.init_state_caches(self.cfg, sv.max_batch, tp=self.tp,
                                            dtype=cache_dtype)
        # grant-size bucketing: pad every prefill grant up to a bucket length
        # so compilation is keyed on the bucket — O(#buckets) compiled
        # closures instead of one per distinct grant length.  Attention-only
        # stacks (pad tokens are masked out of attention and KV scatter, but
        # would advance recurrent SSM/xLSTM state), and no patch-carrying
        # models: patch grants run unbucketed, which would break the
        # max_prefill_compiles() bound their closures share.
        self._buckets = None
        if sv.grant_bucketing and self.cfg.num_patches == 0 and \
                all(k in ("attn_mlp", "attn_moe")
                    for k in self.cfg.block_pattern):
            self._buckets = grant_buckets(sv.max_len, sv.min_grant_bucket,
                                          sv.grant_buckets)
        self.scheduler = TokenBudgetScheduler(
            policy=sv.scheduler_policy,
            prefill_token_budget=sv.prefill_token_budget,
            grant_buckets=self._buckets, trace=self.trace,
            cost_model=self.cost_model, phase=phase)
        # batched multi-request prefill grants: pack same-padded-length grants
        # into ONE forward call per tick (per-row pos_offset/prefix_len/
        # valid_len threaded through StageCtx into the paged prefill kernel).
        # Attention-only stacks without patch embeddings — recurrent families
        # carry per-slot state the packed rows cannot share, and patch grants
        # have a row-heterogeneous embed layout.  The row count is padded to
        # a power-of-two ladder so closures stay keyed on
        # (length bucket, row bucket) — O(#buckets x #row_buckets) compiles.
        self._batch_prefill = (sv.prefill_batching and self.cfg.num_patches == 0
                               and all(k in ("attn_mlp", "attn_moe")
                                       for k in self.cfg.block_pattern))
        self._row_buckets = grant_buckets(sv.max_batch, min_bucket=1) \
            if self._batch_prefill else (1,)
        # copy-on-write prefix sharing: attention-only stacks (recurrent
        # families carry per-slot SSM/xLSTM state that pages cannot share)
        self.prefix_cache: Optional[PrefixCache] = None
        if sv.prefix_sharing and all(k in ("attn_mlp", "attn_moe")
                                     for k in self.cfg.block_pattern):
            self.prefix_cache = PrefixCache(self.ps)

        # speculative decoding: greedy-only self-drafting (serving/speculative
        # .py); attention-only stacks — a K-token verify would advance
        # recurrent SSM/xLSTM state for rejected tokens too
        self.spec_k = 0
        if sv.spec_k and all(k in ("attn_mlp", "attn_moe")
                             for k in self.cfg.block_pattern):
            self.spec_k = sv.spec_k
        self._drafts: List[Optional[Any]] = [None] * sv.max_batch

        self.slots: List[Optional[RequestState]] = [None] * sv.max_batch
        self.lengths = np.zeros(sv.max_batch, np.int64)   # tokens resident
        self.last_tokens = np.zeros(sv.max_batch, np.int64)
        self._by_rid: Dict[int, RequestState] = {}        # waiting + running
        self._finished: List[RequestState] = []
        self._prefill_fns: Dict[Tuple, Any] = {}
        self._decode_fns: Dict[Tuple[int, int], Any] = {}  # (K, kv_splits) -> fn
        # sequential fallback closures for a batch-split engine running with
        # < 2 resident requests — kept OUT of _decode_fns so the CI
        # compile-guard lane's pinned key set stays schedule-pure
        self._decode_fallback_fns: Dict[Tuple[int, int], Any] = {}
        # overlap-probe closures live OUTSIDE _decode_fns: the CI
        # compile-guard lane pins that cache's key set to real traffic
        self._probe_decode_fns: Dict[Tuple[str, bool], Any] = {}
        self._copy_page_fn = None
        # legacy counter key set, pre-registered so `metrics[k] == 0` holds
        # before first use; timed sums are fenced EXECUTION time, the
        # *_dispatch_s pair keeps the async (dispatch-only) view
        self.registry.counters((
            "prefill_s", "decode_s", "prefill_dispatch_s",
            "decode_dispatch_s", "prefill_tokens", "prefill_iso_tokens",
            "decode_tokens", "completed", "decode_calls", "prefill_calls",
            "steps", "preemptions", "ttft_sum", "ttft_n",
            "prefix_shared_tokens",
            "cow_copies", "peak_used_pages", "prefill_pad_tokens",
            "prefill_samples", "spec_calls", "spec_tokens", "prefill_grants",
            "resumed_grants", "prefill_pad_rows", "migrations",
            "migrated_pages", "migration_us") + HOST_COUNTERS)
        self.metrics = self.registry.view()
        self._host = {n: self.registry.counter(n) for n in HOST_COUNTERS}
        self._dispatch = {n: self.registry.counter(n + "_dispatch_s")
                          for n in ("prefill", "decode")}

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------
    def _eff_extra(self, req: Request) -> int:
        return req.patches.shape[0] if req.patches is not None else 0

    def add_request(self, req: Request) -> int:
        assert req.frames is None, "audio requests need the dense Engine"
        assert self.phase != "decode", \
            "decode-phase engine: requests arrive via attach_requests only"
        eff = len(req.prompt) + self._eff_extra(req)
        if eff + req.sampling.max_new_tokens > self.max_len:
            raise ValueError(f"request {req.rid}: {eff} prompt + "
                             f"{req.sampling.max_new_tokens} new tokens exceeds "
                             f"max_len={self.max_len}")
        need = pages_for(eff + req.sampling.max_new_tokens, self.ps)
        if need > self.alloc.num_pages:
            raise ValueError(f"request {req.rid}: needs {need} pages even with "
                             f"every other request evicted; pool has "
                             f"{self.alloc.num_pages} (raise "
                             f"ServingConfig.num_pages)")
        st = RequestState(request=req, slot=-1, t_submit=time.perf_counter())
        st.prompt_len = eff
        st.chunk_plan = plan_chunks(eff, self.config.iso, self.cfg,
                                    whole=req.patches is not None)
        self._by_rid[req.rid] = st
        self.scheduler.add(req.rid, priority=req.priority)
        return req.rid

    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not (free and self.scheduler.waiting):
            return
        with self.trace.span("engine/admit", self._host["host_admit_s"]):
            while free and self.scheduler.waiting:
                rid = self.scheduler.pop_waiting()
                st = self._by_rid[rid]
                st.slot = free.pop(0)
                st.prefilled = 0
                self.slots[st.slot] = st
                self.lengths[st.slot] = 0
                self.trace.emit("admit", rid=rid, slot=st.slot)
                self._try_share_prefix(st)

    def _try_share_prefix(self, st: RequestState) -> None:
        """Map a live donor's matching prompt-prefix pages into this request
        (refcounted, zero-copy); prefill then resumes after the shared part."""
        if self.prefix_cache is None or st.request.patches is not None:
            return
        rid = st.request.rid
        hit = self.prefix_cache.lookup(st.request.prompt, self.alloc,
                                       exclude=rid)
        if hit is not None:
            donor, t, pages = hit
            self.alloc.adopt(rid, pages, t)
            st.prefilled = t
            self.lengths[st.slot] = t
            self.metrics["prefix_shared_tokens"] += t
        self.prefix_cache.register(rid, st.request.prompt)

    def _copy_page(self, old: int, new: int) -> None:
        """Device-side page copy for copy-on-write (all layers + positions).
        One donated jitted call, compiled once for any (old, new) pair — the
        eager equivalent would rebuild every pool buffer per layer."""
        if self._copy_page_fn is None:
            def fn(arr, old_pg, new_pg):
                out = dict(arr)
                out["k"] = tuple(k.at[:, new_pg].set(k[:, old_pg])
                                 for k in arr["k"])
                out["v"] = tuple(v.at[:, new_pg].set(v[:, old_pg])
                                 for v in arr["v"])
                out["pos"] = arr["pos"].at[new_pg].set(arr["pos"][old_pg])
                return out
            self._copy_page_fn = jax.jit(fn, donate_argnums=(0,))
        with self._mesh_ctx():
            self.kv.arrays = self._copy_page_fn(self.kv.arrays,
                                                jnp.int32(old), jnp.int32(new))
        self.metrics["cow_copies"] += 1

    def _cow_range(self, rid: int, start: int, end: int) -> bool:
        """Copy-on-write every shared page the token range [start, end) will
        write into (evicting for the copy target if the pool is dry)."""
        table = self.alloc.tables.get(rid, [])
        for blk in range(start // self.ps, (end - 1) // self.ps + 1):
            if blk >= len(table):
                break                         # beyond the table: fresh pages
            while True:
                try:
                    pair = self.alloc.cow(rid, blk)
                    break
                except OutOfPages:
                    if not self._preempt_one(protect=[rid]):
                        return False
            if pair is not None:
                self._copy_page(*pair)
        return True

    def _release_pages(self, rid: int) -> None:
        """Free rid's pages and invalidate their position entries: attention
        validity is derived from ``pos >= 0``, so a reused page that is only
        partially overwritten must not expose the dead request's tail KV."""
        with self.trace.span("engine/release",
                             self._host["host_release_s"]) as sp:
            pages = self.alloc.free(rid)
            sp.payload["n_pages"] = len(pages)
            if pages:
                new_kv = dict(self.kv.arrays)
                new_kv["pos"] = new_kv["pos"].at[
                    jnp.asarray(pages, jnp.int32)].set(-1)
                self.kv.arrays = new_kv

    def _preempt_one(self, protect: List[int]) -> bool:
        """Evict one running request (recompute mode).  False if none left."""
        running = [s.request.rid for s in self.slots if s is not None]
        victim = self.scheduler.pick_victim(running, protect=protect)
        if victim is None:
            return False
        st = self._by_rid[victim]
        self.trace.emit("evict", rid=victim, slot=st.slot)
        self._release_pages(victim)
        self.slots[st.slot] = None
        self.lengths[st.slot] = 0
        self.last_tokens[st.slot] = 0
        self._drafts[st.slot] = None
        st.slot = -1
        # recompute mode: everything generated so far becomes prompt; the
        # re-prefill's last-position logits yield the next token exactly where
        # decode left off
        st.prefilled = 0
        eff = st.prompt_len + len(st.generated)
        st.chunk_plan = plan_chunks(eff, self.config.iso, self.cfg,
                                    whole=st.request.patches is not None)
        self.scheduler.requeue_front(victim)
        self.metrics["preemptions"] += 1
        return True

    def _ensure_pages(self, rid: int, n_tokens: int) -> bool:
        """Grow rid's block table to n_tokens capacity, evicting if needed."""
        while True:
            try:
                self.alloc.ensure(rid, n_tokens)
                return True
            except OutOfPages:
                if not self._preempt_one(protect=[rid]):
                    return False

    def _resident_tokens(self, st: RequestState) -> np.ndarray:
        """Token ids the request's prompt re-prefill covers (recompute mode
        folds generated tokens in)."""
        toks = np.asarray(st.request.prompt, np.int32)
        if st.generated:
            toks = np.concatenate([toks, np.asarray(st.generated, np.int32)])
        return toks

    # ------------------------------------------------------------------
    # jitted closures (wrapped in shard_map over the TP axis under a mesh)
    # ------------------------------------------------------------------
    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _kv_specs(self):
        n = len(self.kv.kv_positions)
        return {"k": (KV_POOL_SPEC,) * n, "v": (KV_POOL_SPEC,) * n,
                "pos": P(None, None)}

    def _state_specs(self):
        # recurrent-state leaves reuse the dense cache rules (names/ndims
        # only); batch stays unsharded — serving TP shards the model axis
        return cache_specs(jax.eval_shape(lambda: self.states),
                           batch_axes=None, shard_batch=False)

    def _wrap_prefill(self, fn, has_patches: bool):
        if self.mesh is None:
            return jax.jit(fn)
        p_specs = decoder_param_specs(jax.eval_shape(lambda: self.params))
        in_specs = (p_specs, P(None, None),
                    P(None, None, None) if has_patches else None,
                    self._kv_specs(), self._state_specs(),
                    P(None, None), P(), P())
        out_specs = (P(None, "model"), self._kv_specs(), self._state_specs())
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def _wrap_decode(self, fn):
        if self.mesh is None:
            return jax.jit(fn)
        p_specs = decoder_param_specs(jax.eval_shape(lambda: self.params))
        in_specs = (p_specs, P(None, None), P(None, None), P(None),
                    self._kv_specs(), self._state_specs(), P(None))
        out_specs = (P(None, None, "model"), self._kv_specs(),
                     self._state_specs())
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def _paged_prefix(self, kv_arrays, states_slot):
        """Per-position prefill caches exposing the page pools IN PLACE.

        The paged flash-prefill kernel (kernels/flash_prefill_paged.py) reads
        the prefix straight through the block table — no dense gather.  The
        kernel's ``k_pos < prefix_len`` masking also covers prefix sharing
        (the tail of a partially-shared page holds the DONOR's KV at
        positions >= the shared length, which this request must not attend).
        Recurrent positions carry their per-slot SSM/xLSTM state."""
        prefix, kv_i = [], 0
        for i, kind in enumerate(self.cfg.block_pattern):
            c = dict(states_slot[i])
            if i in self.kv.kv_positions:
                c["k_pages"] = kv_arrays["k"][kv_i]
                c["v_pages"] = kv_arrays["v"][kv_i]
                kv_i += 1
            prefix.append(c)
        return tuple(prefix)

    def _get_prefill(self, n_text: int, n_patches: int, resumed: bool):
        """Jitted prefill closure for a (padded) grant shape.

        ``n_text`` is the BUCKET-PADDED text length: with bucketing on, the
        key space is (bucket, patches, fresh|resumed) — O(#buckets) compiled
        closures total, regardless of how many distinct grant lengths the
        traffic produces.  The closure takes the REAL token count ``n_real``
        as a traced scalar: pad-tail tokens are masked out of attention
        (``valid_len`` -> StageCtx), scatter to the scratch page, and the
        sampled logits come from the last real position."""
        key = (n_text, n_patches, resumed)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg, iso, ctx = self.cfg, self.config.iso, self._ctx
        T = n_text + n_patches
        scratch = self.kv.scratch_page

        def fn(params, tokens, patches, kv_arrays, states_slot, bt_row, start,
               n_real):
            batch = {"tokens": tokens}
            if n_patches:
                batch["patches"] = patches
            prefix = self._paged_prefix(kv_arrays, states_slot) \
                if resumed else None
            out = api.prefill(
                params, cfg, ctx, iso, batch, logits_mode="none",
                prefix_caches=prefix, pos_offset=start,
                block_tables=bt_row if resumed else None,
                prefix_lens=jnp.reshape(start, (1,)) if resumed else None,
                valid_len=n_real, return_extras=True)
            # logits of the last REAL token (the pad tail carries garbage)
            h_last = jax.lax.dynamic_slice_in_dim(out["hidden"], n_real - 1, 1,
                                                  axis=1)
            logits_last = emb_lib.lm_head_local(params["embed"], h_last)[:, 0]
            positions = start + jnp.arange(T, dtype=jnp.int32)
            page, off = token_page_coords(positions, bt_row[0], self.ps, scratch)
            # pad-tail tokens must not scatter KV into live pages
            page = jnp.where(jnp.arange(T) < n_real, page, scratch)
            # anything routed to the scratch page must write pos -1, never a
            # real position: pos[scratch] >= 0 would be a validity leak for
            # any pos-driven gather (tests/test_paged_spec.py invariant)
            positions = jnp.where(page != scratch, positions, -1)
            new_kv = dict(kv_arrays)
            ks, vs = list(kv_arrays["k"]), list(kv_arrays["v"])
            new_states = []
            for i, kind in enumerate(cfg.block_pattern):
                ex = out["extras"][i]
                if i in self.kv.kv_positions:
                    kv_i = self.kv.kv_positions.index(i)
                    ks[kv_i] = scatter_pages(ks[kv_i], page, off,
                                             ex["kv_k"][:, 0])
                    vs[kv_i] = scatter_pages(vs[kv_i], page, off,
                                             ex["kv_v"][:, 0])
                new_states.append({sk: ex[sk] for sk in ("ssm", "mlstm", "slstm")
                                   if sk in ex})
            new_kv["k"], new_kv["v"] = tuple(ks), tuple(vs)
            new_kv["pos"] = kv_arrays["pos"].at[page, off].set(positions)
            return logits_last, new_kv, tuple(new_states)

        self._prefill_fns[key] = self._wrap_prefill(fn, n_patches > 0)
        return self._prefill_fns[key]

    def _wrap_prefill_batched(self, fn):
        if self.mesh is None:
            return jax.jit(fn)
        p_specs = decoder_param_specs(jax.eval_shape(lambda: self.params))
        in_specs = (p_specs, P(None, None), self._kv_specs(),
                    P(None, None), P(None), P(None))
        out_specs = (P(None, "model"), self._kv_specs())
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def _get_prefill_batched(self, n_text: int, rows: int, all_fresh: bool):
        """Jitted prefill closure for a PACK of grants: ``rows`` requests'
        grants (row-bucket-padded) run as one ``(rows, n_text)`` forward call.

        Every row resumes at its own absolute position: per-row
        ``starts`` doubles as the paged ``prefix_lens`` (a fresh request is
        simply a row with prefix 0 — the kernel returns the neutral partial
        state for it) and per-row ``n_reals`` masks each row's bucket-pad
        tail.  Pad ROWS (beyond the real pack size) carry all-(-1) block
        tables, start 0 and n_real 0: fully masked out of attention, KV
        routed to the scratch page with pos -1.  ``all_fresh`` packs (every
        row at start 0 — the common cold-prefill case) skip the paged
        kernel entirely: with no resident prefix the whole block-table walk
        would be masked, so they take the dense intra-call path like the
        batch-1 fresh closure did.  The key space is
        (length bucket, row bucket, all-fresh) — O(#buckets x #row_buckets)
        closures.  Attention-only stacks: no recurrent state crosses this
        call."""
        key = (n_text, rows, all_fresh)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg, iso, ctx = self.cfg, self.config.iso, self._ctx
        T = n_text
        ps = self.ps
        scratch = self.kv.scratch_page
        empty_states = tuple({} for _ in cfg.block_pattern)

        def fn(params, tokens, kv_arrays, bt, starts, n_reals):
            prefix = None if all_fresh else \
                self._paged_prefix(kv_arrays, empty_states)
            out = api.prefill(
                params, cfg, ctx, iso, {"tokens": tokens}, logits_mode="none",
                prefix_caches=prefix, pos_offset=starts,
                block_tables=None if all_fresh else bt,
                prefix_lens=None if all_fresh else starts,
                valid_len=n_reals, return_extras=True)
            # logits of each row's last REAL token (pad tails carry garbage)
            h_last = out["hidden"][jnp.arange(rows),
                                   jnp.clip(n_reals - 1, 0, T - 1)]
            logits_last = emb_lib.lm_head_local(params["embed"],
                                                h_last[:, None])[:, 0]
            positions = (starts[:, None]
                         + jnp.arange(T, dtype=jnp.int32)[None])   # (rows, T)
            page, off = jax.vmap(
                lambda p_, b_: token_page_coords(p_, b_, ps, scratch))(
                    positions, bt)
            # pad-tail tokens (and whole pad rows) must not scatter KV into
            # live pages; anything routed to scratch writes pos -1
            page = jnp.where(jnp.arange(T)[None] < n_reals[:, None],
                             page, scratch)
            positions = jnp.where(page != scratch, positions, -1)
            new_kv = dict(kv_arrays)
            ks, vs = list(kv_arrays["k"]), list(kv_arrays["v"])
            for kv_i, i in enumerate(self.kv.kv_positions):
                ex = out["extras"][i]
                ks[kv_i] = scatter_pages(ks[kv_i], page, off, ex["kv_k"])
                vs[kv_i] = scatter_pages(vs[kv_i], page, off, ex["kv_v"])
            new_kv["k"], new_kv["v"] = tuple(ks), tuple(vs)
            new_kv["pos"] = kv_arrays["pos"].at[page, off].set(positions)
            return logits_last, new_kv

        self._prefill_fns[key] = self._wrap_prefill_batched(fn)
        return self._prefill_fns[key]

    # ---- compile accounting (CI compile-guard lane) -------------------
    def prefill_compile_count(self) -> int:
        """Total prefill-closure compilations so far (one jit cache entry per
        compiled executable)."""
        return sum(fn._cache_size()
                   for fn in self._prefill_fns.values())

    def max_prefill_compiles(self) -> Optional[int]:
        """Upper bound on prefill compilations under bucketing.  With batched
        grants: one closure per (length bucket, row bucket) pair — every
        grant, fresh or resumed, single or packed, runs through the batched
        closure.  Batch-1 mode keeps the old bound of one closure per
        (bucket, fresh|resumed) pair.  None when bucketing is off (one
        closure per distinct grant length — unbounded under mixed traffic)."""
        if self._buckets is None:
            return None
        if self._batch_prefill:
            # (length bucket, row bucket, all-fresh|has-resumed)
            return 2 * len(self._buckets) * len(self._row_buckets)
        return 2 * len(self._buckets)

    def _kv_splits(self, K: int = 1) -> int:
        """Split count S for this decode step's flash-decode page walk
        (split-KV sequence parallelism — kernels/flash_decode.py).

        ``ServingConfig.decode_kv_splits`` 0 = auto: with a cost model
        loaded, S is the split count with the best MEASURED decode time at
        the deepest resident request's page depth (perf/costmodel.py —
        logged as a ``decision`` trace event with the static answer it
        replaced); without one, the static heuristic splits by
        ``decode_split_factor`` only when the walk spans at least
        ``decode_split_min_pages`` pages (shallow walks gain nothing from
        the extra reduce step).  1 = sequential; >1 forced — an explicit
        setting always beats the model.  Clamped to the block-table width so
        every span owns >= 1 page slot.  S is STATIC — part of the decode
        closure's (K, S) compile key.  Split count never changes tokens
        (split == sequential proven by tests/test_split_kv.py), so a modeled
        S may differ from the static one without a differential risk."""
        sv = self.sv
        s = sv.decode_kv_splits
        if s == 0:
            deepest = pages_for(int(self.lengths.max()) + K, self.ps)
            static = sv.decode_split_factor \
                if deepest >= sv.decode_split_min_pages else 1
            s = static
            if self.cost_model is not None:
                chosen = self.cost_model.decode_splits(
                    deepest, K, max_splits=self.max_blocks)
                if chosen is not None:
                    s = chosen
                    self.trace.emit("decision", point="kv_splits",
                                    chosen=int(chosen), static=int(static),
                                    depth=int(deepest), k=int(K))
        return max(1, min(int(s), self.max_blocks))

    def _get_decode(self, K: int = 1, S: int = 1):
        """Jitted decode closure for a K-token window (K=1 plain decode,
        K=spec_k+1 speculative verify) walking the pages in S split-KV
        spans — one compiled closure per (K, S), all built on the engine's
        decode schedule (``_decode_schedule``)."""
        key = (K, S)
        if key not in self._decode_fns:
            self._decode_fns[key] = self._build_decode_fn(
                K, schedule=self._decode_schedule, ctx=self._ctx,
                kv_splits=S)
        return self._decode_fns[key]

    def _get_fallback_decode(self, K: int = 1, S: int = 1):
        """Sequential decode closure for a batch-split engine step with < 2
        resident requests (one active slot has no second half to overlap
        with — core/iso.run_stack_decode_overlap would degrade anyway, and
        running two half-calls where one is pure scratch wastes the step).
        Cached apart from ``_decode_fns`` so the compile-guard key pins
        stay schedule-pure."""
        key = (K, S)
        if key not in self._decode_fallback_fns:
            self._decode_fallback_fns[key] = self._build_decode_fn(
                K, schedule="sequential", ctx=self._ctx, kv_splits=S)
        return self._decode_fallback_fns[key]

    def _get_probe_decode(self, schedule: str, comm: bool = True):
        """Decode closure variants for the overlap-efficiency probe
        (obs/overlap_probe.py): one per collective schedule (sequential /
        batch_split / cross_block / ladder / ladder_seq), plus a
        collectives-disabled compute floor (``comm=False`` swaps in a bare
        AxisCtx — psum degrades to identity inside the same shard_map).
        Cached in ``_probe_decode_fns``, never ``_decode_fns``, whose key
        set the compile-guard lane pins to real traffic."""
        key = (schedule, comm)
        if key not in self._probe_decode_fns:
            ctx = self._ctx if comm else AxisCtx()
            # probes always walk sequentially (kv_splits=1): the probe
            # measures overlap efficiency, not split-KV reduce cost
            self._probe_decode_fns[key] = self._build_decode_fn(
                1, schedule=schedule, ctx=ctx, kv_splits=1)
        return self._probe_decode_fns[key]

    def measure_overlap_efficiency(self, iters: int = 10, warmup: int = 3):
        """Time the decode collective schedules (sequential vs batch-split
        vs ladder vs cross-block) on identical synthetic batches; see
        obs/overlap_probe.decode_overlap_probe."""
        from repro.obs.overlap_probe import decode_overlap_probe
        return decode_overlap_probe(self, iters=iters, warmup=warmup)

    def _build_decode_fn(self, K: int, schedule: str, ctx: AxisCtx,
                         kv_splits: int = 1):
        cfg = self.cfg
        scratch = self.kv.scratch_page
        ps = self.ps

        def fn(params, toks, bt, lengths, kv_arrays, states, active):
            # paged flash decode: the stack reads the page pools in place
            # through the block tables (kernels/flash_decode.py) and scatters
            # the window's KV to its pages (core/iso.run_stack_decode)
            caches, kv_i = [], 0
            for i, kind in enumerate(cfg.block_pattern):
                c = dict(states[i])
                if i in self.kv.kv_positions:
                    c["k_pages"] = kv_arrays["k"][kv_i]
                    c["v_pages"] = kv_arrays["v"][kv_i]
                    kv_i += 1
                caches.append(c)
            logits, new_caches = api.decode_step(
                params, cfg, ctx, toks, tuple(caches), lengths,
                block_tables=bt, decode_mask=active, schedule=schedule,
                kv_splits=kv_splits)
            B = toks.shape[0]
            page, off, ok, positions = window_page_coords(
                lengths, bt, K, ps, scratch=scratch, decode_mask=active)
            ks, vs = list(kv_arrays["k"]), list(kv_arrays["v"])
            new_states = []
            for i, kind in enumerate(cfg.block_pattern):
                nc = new_caches[i]
                if i in self.kv.kv_positions:
                    kv_i = self.kv.kv_positions.index(i)
                    ks[kv_i] = nc["k_pages"]
                    vs[kv_i] = nc["v_pages"]
                # recurrent states advance only for slots that really decoded
                sel = {}
                for sk in ("ssm", "mlstm", "slstm"):
                    if sk in states[i]:
                        sel[sk] = jax.tree_util.tree_map(
                            lambda new, old: jnp.where(
                                active.reshape((1, B) + (1,) * (new.ndim - 2)),
                                new, old), nc[sk], states[i][sk])
                new_states.append(sel)
            new_kv = dict(kv_arrays)
            new_kv["k"], new_kv["v"] = tuple(ks), tuple(vs)
            # scratch-routed scatters (inactive slots, no capacity) must
            # write pos -1, never a real position
            new_kv["pos"] = kv_arrays["pos"].at[page, off].set(
                jnp.where(ok, positions, -1))
            return logits, new_kv, tuple(new_states)

        return self._wrap_decode(fn)

    # ------------------------------------------------------------------
    # step phases
    # ------------------------------------------------------------------
    def _pad_len(self, st: RequestState, n_tokens: int) -> int:
        """Bucket-rounded forward-call length for a grant (== n_tokens when
        bucketing is off or the request carries patch embeddings)."""
        if self._buckets is None or st.request.patches is not None:
            return n_tokens
        return round_to_bucket(n_tokens, self._buckets)

    def _device_call(self, name: str, phase: str, call):
        """Run one jitted engine call: ``call()`` dispatches under the span
        ``name`` (its time into ``<phase>_dispatch_s``), then every output
        is fenced under ``engine/device_wait``.  Returns the outputs, the
        dispatch start and the seconds from it to the fence — the fenced
        time ``prefill_s``/``decode_s`` sum.  Dispatch returns before the
        device finishes, and the KV scatter can outlive the logits, so the
        fence must cover EVERY output."""
        with self.trace.span(name, self._dispatch[phase]) as d, \
                self._mesh_ctx():
            out = call()
        with self.trace.span("engine/device_wait") as w:
            jax.block_until_ready(out)
        return out, d.t0, w.t1 - d.t0

    def _logits_to_host(self, logits) -> np.ndarray:
        with self.trace.span("engine/logits", self._host["host_logits_s"]):
            return np.asarray(jax.device_get(logits))

    def _count_prefill_tokens(self, T: int, n_real: int) -> None:
        """Real prompt tokens of one prefill call over ``T`` positions; those
        of a call that runs in two or more ISO chunks count again in
        ``prefill_iso_tokens``."""
        self.metrics["prefill_tokens"] += n_real
        if len(prefill_chunk_lengths(T, self.config.iso, self.cfg,
                                     self.tp)) > 1:
            self.metrics["prefill_iso_tokens"] += n_real

    def _run_grant(self, st: RequestState, start: int, n_tokens: int,
                   padded: int, last: bool) -> Optional[int]:
        """Execute one prefill grant; returns the sampled token if ``last``.

        ``padded``: bucket length of the forward call (>= n_tokens); the
        pad tail is zero tokens, masked out of attention and KV scatter."""
        req = st.request
        slot = st.slot
        with self.trace.span("engine/inputs", self._host["host_inputs_s"]):
            n_patches = self._eff_extra(req) if start == 0 else 0
            toks_all = self._resident_tokens(st)
            # text tokens covered by this grant (patches occupy the first
            # ``eff_extra`` effective positions of the first grant)
            t0 = max(0, start - self._eff_extra(req)) \
                if req.patches is not None else start
            n_text = n_tokens - n_patches
            buf = np.zeros(padded - n_patches, np.int32)
            buf[:n_text] = toks_all[t0:t0 + n_text]
            tokens = jnp.asarray(buf[None])
            patches = jnp.asarray(req.patches[None]) if n_patches else None

            bt_row = jnp.asarray(self.alloc.block_table(req.rid,
                                                        self.max_blocks)[None])
            states_slot = jax.tree_util.tree_map(
                lambda a: a[:, slot:slot + 1], self.states)
            fn = self._get_prefill(padded - n_patches, n_patches,
                                   resumed=start > 0)
        (logits_last, new_kv, new_states), t0_wall, dur = self._device_call(
            f"prefill/T={padded}", "prefill", lambda: fn(
                self.params, tokens, patches, self.kv.arrays, states_slot,
                bt_row, jnp.int32(start), jnp.int32(n_tokens)))
        if st.t_first_grant < 0:
            st.t_first_grant = t0_wall
        self.metrics["prefill_s"] += dur
        self._count_prefill_tokens(padded, n_tokens)
        self.metrics["prefill_pad_tokens"] += padded - n_tokens
        self.metrics["prefill_calls"] += 1
        self.trace.emit("prefill_call", rid=req.rid, slot=slot, dur=dur,
                        ts=t0_wall, tokens=n_tokens, pad=padded - n_tokens,
                        rows=1)

        self.kv.arrays = new_kv
        self.states = jax.tree_util.tree_map(
            lambda big, new: big.at[:, slot:slot + 1].set(new.astype(big.dtype)),
            self.states, new_states)
        logits_row = self._logits_to_host(logits_last)[0] if last else None
        with self.trace.span("engine/sample", self._host["host_sample_s"]):
            return self._commit_grant_row(st, start, n_tokens, logits_row,
                                          last)

    def _commit_grant_row(self, st: RequestState, start: int, n_tokens: int,
                          logits_row, last: bool) -> Optional[int]:
        """Post-forward bookkeeping for one grant (single or packed row):
        commit tokens to the allocator, advance prefill progress, and — for a
        prompt-finishing grant — sample the first token from ``logits_row``
        ((V,) fp32 of the last real position), stamp TTFT and (re)build the
        speculative self-draft."""
        req = st.request
        slot = st.slot
        self.alloc.commit(req.rid, n_tokens)
        st.prefilled = start + n_tokens
        self.lengths[slot] = st.prefilled
        self.metrics["prefill_grants"] += 1
        self.registry.histogram("grant_size").observe(n_tokens)
        if start > 0:
            self.metrics["resumed_grants"] += 1
        # scheduler-issued grants can be dropped and re-issued (packmate
        # eviction, deferred sharing) — the commit is the countable event
        self.trace.emit("grant_commit", rid=req.rid, slot=slot, start=start,
                        n=n_tokens, last=last)
        if not last:
            return None
        tok = sample(logits_row[:self.cfg.vocab_size], req.sampling,
                     step=len(st.generated))
        self.metrics["prefill_samples"] += 1
        first = st.t_first < 0
        if first:
            st.t_first = time.perf_counter()
            ttft = st.t_first - st.t_submit
            self.metrics["ttft_sum"] += ttft
            self.metrics["ttft_n"] += 1
            self.registry.histogram("ttft").observe(ttft)
        self.trace.emit("sample", rid=req.rid, slot=slot, first=first)
        if self.spec_k:
            # (re)build the self-draft over everything resident — after a
            # recompute preemption that includes the already-generated tokens
            from repro.serving.speculative import BigramDraft
            d = BigramDraft()
            d.observe([int(t) for t in self._resident_tokens(st)] + [int(tok)])
            self._drafts[slot] = d
        st.generated.append(tok)
        self.last_tokens[slot] = tok
        st.finish_check()
        return tok

    def _run_pack(self, group: List[Tuple], padded: int,
                  events: List[Tuple[int, int]]) -> None:
        """Execute a pack of prepped grants as ONE batched forward call.

        ``group``: [(st, start, n_tokens, padded, last), ...] sharing the
        same padded length.  The row count is padded up to a row bucket so
        the jitted closure is keyed on (length bucket, row bucket); pad rows
        carry empty block tables and n_real 0 (fully masked, scratch-routed).
        """
        R = len(group)
        rows = round_to_bucket(R, self._row_buckets)
        T = padded
        with self.trace.span("engine/inputs", self._host["host_inputs_s"]):
            toks = np.zeros((rows, T), np.int32)
            starts = np.zeros(rows, np.int32)
            n_reals = np.zeros(rows, np.int32)
            bts = np.full((rows, self.max_blocks), -1, np.int32)
            for r, (st, start, n, _, _last) in enumerate(group):
                toks_all = self._resident_tokens(st)
                toks[r, :n] = toks_all[start:start + n]
                starts[r] = start
                n_reals[r] = n
                bts[r] = self.alloc.block_table(st.request.rid,
                                                self.max_blocks)
            fn = self._get_prefill_batched(
                T, rows, all_fresh=bool(np.all(starts == 0)))
        (logits_last, new_kv), t0_wall, dur = self._device_call(
            f"prefill/T={T}x{rows}", "prefill", lambda: fn(
                self.params, jnp.asarray(toks), self.kv.arrays,
                jnp.asarray(bts), jnp.asarray(starts), jnp.asarray(n_reals)))
        for st, *_ in group:
            if st.t_first_grant < 0:
                st.t_first_grant = t0_wall
        n_total = int(n_reals.sum())
        self.metrics["prefill_s"] += dur
        self._count_prefill_tokens(T, n_total)
        self.metrics["prefill_pad_tokens"] += rows * T - n_total
        self.metrics["prefill_pad_rows"] += rows - R
        self.metrics["prefill_calls"] += 1
        self.trace.emit("prefill_call", dur=dur, ts=t0_wall, tokens=n_total,
                        pad=rows * T - n_total, rows=R)
        self.kv.arrays = new_kv
        logits_np = None
        if any(p[4] for p in group):
            logits_np = self._logits_to_host(logits_last)
        with self.trace.span("engine/sample", self._host["host_sample_s"]):
            for r, (st, start, n, _, last) in enumerate(group):
                tok = self._commit_grant_row(
                    st, start, n, logits_np[r] if last else None, last)
                if tok is not None:
                    events.append((st.request.rid, tok))
                    if st.done:
                        self._finish(st)

    def _finish(self, st: RequestState) -> None:
        # decode_tokens is tallied where tokens are produced (_decode_phase),
        # NOT here: the prefill-sampled first token is a prefill_samples
        # event, and in-flight requests must not vanish from the count
        self.metrics["completed"] += 1
        self.trace.emit("finish", rid=st.request.rid, slot=st.slot)
        self._release_pages(st.request.rid)
        if self.prefix_cache is not None:
            self.prefix_cache.forget(st.request.rid)
        self.scheduler.forget(st.request.rid)
        self._finished.append(st)
        self._by_rid.pop(st.request.rid, None)
        self.slots[st.slot] = None
        self.lengths[st.slot] = 0
        self.last_tokens[st.slot] = 0
        self._drafts[st.slot] = None
        st.slot = -1

    def _prep_grant(self, g) -> Optional[Tuple]:
        """Per-grant pre-work shared by the batch-1 and packed paths: prefix-
        sharing retry, page allocation growth and copy-on-write (both may
        evict).  Returns (st, start, n_tokens, padded, last) ready to run, or
        None when the grant dissolved (its request was preempted by an
        earlier grant's eviction, or same-step sharing covered it fully)."""
        st = self._by_rid.get(g.rid)
        if st is None or st.slot < 0:
            return None                       # preempted by an earlier grant
        start, end = g.start, g.start + g.n_tokens
        with self.trace.span("engine/pages", self._host["host_pages_s"]):
            if start == 0 and st.prefilled == 0:
                # retry prefix sharing: a donor granted EARLIER this step
                # (batch-1 mode: already ran; packed mode: earlier pack) has
                # committed its first chunks by now
                self._try_share_prefix(st)
                start = st.prefilled
                if end <= start:              # grant fully covered by sharing
                    return None
            if not self._ensure_pages(g.rid, end) or \
                    not self._cow_range(g.rid, start, end):
                # unreachable once add_request validated pool capacity; a
                # silent skip here would spin run_until_complete forever
                raise RuntimeError(
                    f"page pool too small for request {g.rid}'s prefill "
                    f"chunk even after evicting; increase "
                    f"ServingConfig.num_pages")
        # the scheduler owns grant rounding (g.padded); re-round only
        # when same-step prefix sharing shrank the grant, and never pad
        # patch-carrying grants (the scheduler is model-agnostic)
        n = end - start
        if st.request.patches is not None:
            padded = n
        elif start == g.start and n == g.n_tokens:
            padded = g.padded or n
        else:
            padded = self._pad_len(st, n)
        return st, start, n, padded, g.last

    def _prefill_phase(self, events: List[Tuple[int, int]]) -> None:
        # prefill target = sum(chunk_plan): the prompt at admission, or
        # prompt+generated after a recompute preemption
        pending = [(s.request.rid, s.prefilled, s.chunk_plan)
                   for s in self.slots
                   if s is not None and s.prefilled < sum(s.chunk_plan)]
        if not pending:
            return
        with self.trace.span("engine/schedule",
                             self._host["host_schedule_s"]) as sp:
            grants = self.scheduler.grant_prefill(pending)
            if self._batch_prefill:
                packs = self.scheduler.pack_grants(grants,
                                                   max_rows=self.max_batch)
            sp.payload["grants"] = [g.n_tokens for g in grants]
        if not self._batch_prefill:
            for g in grants:
                prep = self._prep_grant(g)
                if prep is None:
                    continue
                st, start, n, padded, last = prep
                tok = self._run_grant(st, start, n, padded, last)
                if tok is not None:
                    events.append((st.request.rid, tok))
                    if st.done:
                        self._finish(st)
            return
        # packed path: the scheduler groups compatible grants (same padded
        # length, policy order — scheduler.pack_grants); each pack runs as
        # ONE forward call.  Prep runs pack-by-pack in policy order, so
        # eviction/CoW semantics match the sequential path; a prep that
        # evicts a packmate drops it from the pack (slot check below), and
        # same-step sharing that SHRANK a grant re-buckets it into a
        # sub-group of its own padded length.  A fresh grant that could
        # prefix-share with a PACKMATE is deferred to a follow-up sub-pack:
        # sharing adopts only COMMITTED tokens, and packmates commit together
        # after the call — running donor and sharee in one call would
        # silently lose the share that the sequential path gets.
        for pack in packs:
            ready, deferred = [], []
            for g in pack:
                if self._defer_for_packmate_sharing(g, ready):
                    self.trace.emit("defer", rid=g.rid)
                    deferred.append(g)
                    continue
                prep = self._prep_grant(g)
                if prep is not None:
                    ready.append(prep)
            self._run_groups(ready, events)
            if deferred:
                # donors committed above; the normal grant-time sharing
                # retry inside _prep_grant now engages for the sharees
                self._run_groups(
                    [p for g in deferred
                     if (p := self._prep_grant(g)) is not None], events)

    def _defer_for_packmate_sharing(self, g, prepped: List[Tuple]) -> bool:
        """True if fresh grant ``g`` shares its first KV page's worth of
        prompt with an earlier member of the SAME pack — the only case where
        packing would lose a prefix share the batch-1 path gets (cross-pack
        donors have committed by the sharee's prep; packmates have not)."""
        if self.prefix_cache is None or not prepped:
            return False
        st = self._by_rid.get(g.rid)
        if st is None or st.slot < 0 or st.prefilled > 0 or g.start != 0:
            return False
        with self.trace.span("engine/schedule",
                             self._host["host_schedule_s"]):
            prompt = np.asarray(st.request.prompt, np.int32)
            if len(prompt) < self.ps:
                return False              # sharing needs a full page match
            head = prompt[:self.ps]
            for p_st, _, _, _, _ in prepped:
                donor = np.asarray(p_st.request.prompt, np.int32)
                if len(donor) >= self.ps and \
                        np.array_equal(donor[:self.ps], head):
                    return True
            return False

    def _run_groups(self, ready: List[Tuple],
                    events: List[Tuple[int, int]]) -> None:
        """Run prepped grants as packed calls, sub-grouped by their FINAL
        padded length (same-step sharing may have re-bucketed some)."""
        ready = [p for p in ready if p[0].slot >= 0]
        by_len: Dict[int, List[Tuple]] = {}
        for p in ready:
            by_len.setdefault(p[3], []).append(p)
        for padded, group in by_len.items():
            self._run_pack(group, padded, events)

    # accept-length samples the spec gate needs before trusting the
    # histogram mean over the static default (tests monkeypatch this)
    SPEC_GATE_MIN_SAMPLES = 8

    def _spec_window(self, active) -> int:
        """Verify-window width for this decode step: spec_k+1 when every
        active request can speculate (greedy sampling, drafted, and room for
        the whole window below max_len), else 1 (plain decode).  One batched
        call either way — mixed eligibility falls back for the step.

        With a cost model, the gate also weighs the MEASURED K-token verify
        cost against the plain-decode steps it would replace: once the
        ``accept_len`` histogram has enough samples, speculation is skipped
        (K=1) whenever ``verify_cost >= expected_accept * plain_cost``
        (perf/costmodel.CostModel.spec_worth).  Skipping speculation is
        token-neutral — greedy verify == plain decode is the PR 4
        differential invariant — so the gate can only trade speed."""
        if not self.spec_k:
            return 1
        K = self.spec_k + 1
        if self.cost_model is not None:
            hist = self.registry.histogram("accept_len")
            if hist.n >= self.SPEC_GATE_MIN_SAMPLES:
                deepest = pages_for(int(self.lengths.max()) + K, self.ps)
                worth = self.cost_model.spec_worth(K, deepest, hist.mean)
                if worth is False:
                    self.trace.emit("decision", point="spec_gate", chosen=1,
                                    static=K,
                                    expected_accept=float(hist.mean))
                    return 1
        need = 0
        for st in active:
            L = int(self.lengths[st.slot])
            if st.request.sampling.temperature > 0 or \
                    self._drafts[st.slot] is None or L + K > self.max_len:
                return 1
            need += max(0, pages_for(L + K, self.ps)
                        - len(self.alloc.tables.get(st.request.rid, ())))
        # the window must fit WITHOUT eviction: admission only validated the
        # plain-decode watermark, and evicting a request to speculate on
        # another would trade real progress for drafted guesses
        if need > self.alloc.free_pages:
            return 1
        return K

    def _decode_phase(self, events: List[Tuple[int, int]]) -> None:
        active = [s for s in self.slots
                  if s is not None and not s.done and s.generated
                  and s.prefilled >= sum(s.chunk_plan)]
        active = [s for s in active if s.slot >= 0]
        if not active:
            return
        with self.trace.span("engine/pages", self._host["host_pages_s"]):
            K = self._spec_window(active)
            # grow every decoder's capacity by the window width (may evict;
            # an evicted request drops out of `active` below — filtered by
            # slot, not list.remove, whose __eq__ scan would compare prompt
            # arrays)
            for st in active:
                if st.slot < 0:
                    continue
                L = int(self.lengths[st.slot])
                if not self._ensure_pages(st.request.rid, L + K) or \
                        not self._cow_range(st.request.rid, L, L + K):
                    raise RuntimeError(
                        f"page pool too small for a {K}-token decode step; "
                        f"increase ServingConfig.num_pages")
        active = [s for s in active if s.slot >= 0]
        if not active:
            return
        with self.trace.span("engine/inputs", self._host["host_inputs_s"]):
            B = self.max_batch
            mask = np.zeros(B, bool)
            for st in active:
                mask[st.slot] = True
            bt = np.stack([self.alloc.block_table(s.request.rid,
                                                  self.max_blocks)
                           if s is not None and mask[i] else
                           np.full(self.max_blocks, -1, np.int32)
                           for i, s in enumerate(self.slots)])
            toks = np.zeros((B, K), np.int32)
            toks[:, 0] = self.last_tokens.astype(np.int32)
            drafts: Dict[int, List[int]] = {}
            if K > 1:
                for st in active:
                    i = st.slot
                    drafts[i] = self._drafts[i].draft(self.spec_k)
                    toks[i, 1:] = drafts[i]
            lens = jnp.asarray(self.lengths.astype(np.int32))
            S = self._kv_splits(K)
            if self._decode_schedule == "batch_split" and len(active) < 2:
                # a single resident request has no second batch half to
                # overlap with — run the sequential closure for this step
                # instead of a batch-split call whose other half is pure
                # scratch work
                decode_fn = self._get_fallback_decode(K, S)
                self.trace.emit("decision", point="decode_schedule",
                                fallback=1, active=len(active), k=int(K))
            else:
                decode_fn = self._get_decode(K, S)
        (logits, new_kv, new_states), t0, dur = self._device_call(
            f"decode/K={K}/S={S}", "decode", lambda: decode_fn(
                self.params, jnp.asarray(toks), jnp.asarray(bt), lens,
                self.kv.arrays, self.states, jnp.asarray(mask)))
        logits = self._logits_to_host(logits)
        self.metrics["decode_s"] += dur
        self.metrics["decode_calls"] += 1
        self.trace.emit("decode_call", dur=dur, ts=t0, k=K, active=len(active))
        if K > 1:
            self.metrics["spec_calls"] += 1
        self.kv.arrays = new_kv
        self.states = new_states

        with self.trace.span("engine/sample", self._host["host_sample_s"]):
            self._accept_decoded(active, K, logits, drafts, dur, events)

    def _accept_decoded(self, active: List[RequestState], K: int,
                        logits: np.ndarray, drafts: Dict[int, List[int]],
                        dur: float, events: List[Tuple[int, int]]) -> None:
        """Sample (K=1) or accept (speculative verify) each active row's
        tokens from the decode call's host ``logits`` and commit them."""
        rollback: List[Tuple[int, int]] = []      # (page, offset) to unmap
        for st in active:
            i = st.slot
            if K == 1:
                acc = [sample(logits[i, 0][:self.cfg.vocab_size],
                              st.request.sampling, len(st.generated))]
                if self._drafts[i] is not None:
                    # keep the draft's anchor/table fresh across speculation
                    # fallbacks, or re-engaging verifies a stale successor
                    self._drafts[i].observe([int(acc[0])])
            else:
                # greedy accept: longest matching prefix of the drafted
                # window, plus the model's bonus token when all drafts hit
                from repro.serving.speculative import accept_greedy
                argmaxes = logits[i, :, :self.cfg.vocab_size].argmax(axis=-1)
                budget = st.request.sampling.max_new_tokens - len(st.generated)
                acc = accept_greedy(drafts[i], argmaxes)[:max(budget, 1)]
                self.metrics["spec_tokens"] += len(acc)
                self.registry.histogram("accept_len").observe(len(acc))
                self._drafts[i].observe([int(t) for t in acc])
                # rejected window positions: their KV was scattered but they
                # are NOT committed — invalidate their pos entries so no
                # pos-driven consumer can ever see them as live
                L = int(self.lengths[i])
                table = self.alloc.tables[st.request.rid]
                for pos in range(L + len(acc), L + K):
                    rollback.append((table[pos // self.ps], pos % self.ps))
            self.alloc.commit(st.request.rid, len(acc))
            self.metrics["decode_tokens"] += len(acc)
            self.trace.emit("accept", rid=st.request.rid, slot=i, n=len(acc),
                            spec=K > 1)
            self.registry.histogram("tpot").observe(dur / len(acc))
            for tok in acc:
                st.generated.append(int(tok))
                events.append((st.request.rid, int(tok)))
            self.lengths[i] += len(acc)
            self.last_tokens[i] = int(acc[-1])
            st.finish_check()
            if st.done:
                self._finish(st)
        if rollback:
            self.trace.emit("spec_rollback", n=len(rollback))
            pg = jnp.asarray([p for p, _ in rollback], jnp.int32)
            off = jnp.asarray([o for _, o in rollback], jnp.int32)
            new_kv = dict(self.kv.arrays)
            new_kv["pos"] = new_kv["pos"].at[pg, off].set(-1)
            self.kv.arrays = new_kv

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One engine iteration: admission -> budgeted prefill chunks ->
        batched decode.  Returns (rid, token) events."""
        with self.trace.span("engine/step", self._host["host_step_other_s"]):
            events: List[Tuple[int, int]] = []
            self.metrics["steps"] += 1
            if self.scheduler.runs_prefill:
                self._admit()
                self._prefill_phase(events)
            if self.scheduler.runs_decode:
                self._decode_phase(events)
            used = self.alloc.used_pages
            frag = self.alloc.fragmentation()
            self.registry.gauge("pool_occupancy").set(used)
            self.registry.gauge("free_list_fragmentation").set(frag)
            self.metrics["peak_used_pages"] = max(
                self.metrics["peak_used_pages"], used)
            self.trace.emit("pool", used=used, free=self.alloc.free_pages,
                            frag=frag)
            return events

    def run_until_complete(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            self.step()
            if not self.scheduler.waiting and \
                    all(s is None for s in self.slots):
                break
        for st in self._finished:
            out[st.request.rid] = st.generated
        return out

    # ------------------------------------------------------------------
    # disaggregated serving: detach / attach (serving/disagg.py)
    # ------------------------------------------------------------------
    def detach_requests(self, rids: List[int]) -> "Any":
        """Export ``rids``' KV pages + lifecycle state as a ``PageTransfer``
        and REMOVE the requests from this engine (slots cleared, pages freed,
        scheduler/prefix-cache entries dropped).

        The requests must be resident (slot >= 0) with their prompts fully
        committed — the disagg router migrates exactly that set.  Pages shared
        across the detached group are exported once (sharing survives the
        move); pages shared with a request that STAYS are copied by the
        export, and the stayer keeps its originals.  The transfer is pure
        host state — numpy payloads, plain-python records — so the receiving
        engine can live on another mesh."""
        from repro.serving.disagg import PageTransfer, RequestRecord
        t0 = time.perf_counter()
        blob = self.pool.export_pages(rids)
        records = []
        for rid in rids:
            st = self._by_rid[rid]
            slot = st.slot
            assert slot >= 0, f"detach of non-resident request {rid}"
            assert st.prefilled >= sum(st.chunk_plan), \
                f"detach of mid-prefill request {rid}"
            d = self._drafts[slot]
            records.append(RequestRecord(
                request=st.request, generated=list(st.generated),
                prompt_len=st.prompt_len, prefilled=st.prefilled,
                chunk_plan=tuple(st.chunk_plan), t_submit=st.t_submit,
                t_first=st.t_first, last_token=int(self.last_tokens[slot]),
                draft_table=dict(d.table) if d is not None else None,
                draft_last=d.last if d is not None else -1,
                t_first_grant=st.t_first_grant))
            self.trace.emit("detach", rid=rid, slot=slot)
            self._release_pages(rid)
            if self.prefix_cache is not None:
                self.prefix_cache.forget(rid)
            self.scheduler.forget(rid)
            self._by_rid.pop(rid, None)
            self.slots[slot] = None
            self.lengths[slot] = 0
            self.last_tokens[slot] = 0
            self._drafts[slot] = None
            st.slot = -1
        us = (time.perf_counter() - t0) * 1e6
        # one span per transfer, n = DISTINCT pages moved (a page shared by
        # several detached requests counts once) — replay reconstructs
        # migrations/migrated_pages from exactly these events
        self.trace.emit("migrate", n=blob["n_pages"], rids=len(rids), us=us)
        self.metrics["migrations"] += 1
        self.metrics["migrated_pages"] += blob["n_pages"]
        self.metrics["migration_us"] += us
        return PageTransfer(records=records, blob=blob)

    def attach_requests(self, transfer: "Any") -> None:
        """Adopt a ``PageTransfer``: import its pages into this pool and
        install the requests into free slots, decode-ready.

        Raises ``OutOfPages`` — atomically, nothing mutated — when the free
        list can't host the transfer's distinct pages; the router keeps the
        transfer queued and retries (defer-and-retry, never preemption: an
        attach must not evict a decode-resident request to make room).
        Free slots must cover the records (the router checks first)."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        assert len(free) >= len(transfer.records), \
            (len(free), len(transfer.records))
        t0 = time.perf_counter()
        self.pool.import_pages(transfer.blob)   # may raise OutOfPages: atomic
        from repro.serving.speculative import BigramDraft
        for rec in transfer.records:
            rid = rec.request.rid
            slot = free.pop(0)
            st = RequestState(request=rec.request, slot=slot,
                              generated=list(rec.generated),
                              prompt_len=rec.prompt_len,
                              t_submit=rec.t_submit)
            st.prefilled = rec.prefilled
            st.chunk_plan = tuple(rec.chunk_plan)
            st.t_first = rec.t_first
            st.t_first_grant = rec.t_first_grant
            self.slots[slot] = st
            self._by_rid[rid] = st
            # committed tokens came over in the blob's lengths; the first
            # generated token is NOT in KV yet (it is the next decode input)
            self.lengths[slot] = self.alloc.tokens(rid)
            self.last_tokens[slot] = rec.last_token
            if self.spec_k and rec.draft_table is not None:
                d = BigramDraft()
                d.table = dict(rec.draft_table)
                d.last = rec.draft_last
                self._drafts[slot] = d
            # arrival bookkeeping without queueing: pick_victim/order need a
            # key for migrated-in rids (router attaches in policy order)
            self.scheduler.register(rid, priority=rec.request.priority)
            self.trace.emit("attach", rid=rid, slot=slot)
        self.metrics["migration_us"] += (time.perf_counter() - t0) * 1e6

    def accepted_per_call(self) -> float:
        """Mean tokens emitted per speculative verify call (>= 1 once any
        verify ran; 0.0 when speculation never triggered).  The accept-rate
        metric tracked per push by benchmarks/ci_smoke.py."""
        if not self.metrics["spec_calls"]:
            return 0.0
        return self.metrics["spec_tokens"] / self.metrics["spec_calls"]

    # ------------------------------------------------------------------
    def page_stats(self) -> Dict[str, Any]:
        s = self.alloc.stats()
        s["kv_bytes_live"] = self.kv.kv_bytes(self.alloc)
        s["kv_bytes_reserved"] = self.kv.total_bytes()
        return s
