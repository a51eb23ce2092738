"""Disaggregated prefill/decode serving: two engines, KV pages migrate.

Prefill and decode have opposite resource shapes — prefill is compute-bound
(the ISO chunk schedule overlaps its collectives), decode is memory-bound
(the paged cache walk) — so serving them from ONE engine makes each phase
inherit the other's batching compromises.  This module splits them:

  * a ``phase="prefill"`` ``PagedEngine`` admits requests and runs chunked
    prefill ONLY (its scheduler never plans a decode step);
  * a ``phase="decode"`` ``PagedEngine`` decodes ONLY (it never admits — its
    requests arrive by ``attach_requests``);
  * the ``DisaggRouter`` moves each request between them the moment its
    prompt is fully resident: ``PagedEngine.detach_requests`` exports the KV
    pages + lifecycle state as a ``PageTransfer`` (host arrays + plain
    records — nothing engine- or mesh-local), and ``attach_requests``
    re-adopts it into the decode pool at remapped page ids.

Token streams are BYTE-IDENTICAL to single-engine serving: sampling is a pure
function of (seed, step index), prefill/decode math is row-independent, and
migration copies committed KV verbatim — the differential battery in
tests/test_disagg.py pins equality under prefix sharing, preemption,
speculation and batched prefill simultaneously.

Flow control: when the decode pool cannot host the next migration (no free
slot, or fewer free pages than the transfer's distinct pages) the request
simply STAYS on the prefill engine — admitted, fully prefilled, holding its
pages — until decode-side completions free room.  A transfer that was already
detached and then fails to attach (``OutOfPages`` is atomic — nothing is
mutated) queues host-side and retries with bounded backoff.  Neither path
preempts a decode-resident request, loses tokens, or raises.  Decode-side
preemption victims (pool pressure from growing decode windows) bounce BACK to
the prefill engine in recompute mode — the same prompt+generated re-prefill a
single-engine preemption does.  See docs/serving.md.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Tuple

from repro.config import Config, ServingConfig
from repro.serving.kvcache import OutOfPages, pages_for
from repro.serving.paged_engine import PagedEngine
from repro.serving.requests import Request


@dataclass
class RequestRecord:
    """One request's engine-external lifecycle state — everything the decode
    engine needs to continue the stream exactly where prefill left it."""
    request: Request
    generated: List[int]              # tokens sampled so far (>= 1 on migrate)
    prompt_len: int                   # effective prompt length (text+patches)
    prefilled: int                    # prompt tokens committed to KV
    chunk_plan: Tuple[int, ...]
    t_submit: float                   # TTFT/TPOT stamps travel with the
    t_first: float                    # request (TTFT is a prefill-side event)
    last_token: int                   # next decode input (not yet in KV)
    draft_table: Optional[Dict[int, int]]   # speculative self-draft state —
    draft_last: int                         # without it, spec streams diverge
    t_first_grant: float = -1.0       # queue-wait stamp, as t_submit


@dataclass
class PageTransfer:
    """The migration message: lifecycle records + the ``KVPool.export_pages``
    blob (numpy payloads, export-local page ids).  Pure host state."""
    records: List[RequestRecord]
    blob: Dict[str, Any] = field(repr=False)

    @property
    def n_pages(self) -> int:
        return self.blob["n_pages"]

    @property
    def rids(self) -> List[int]:
        return [r.request.rid for r in self.records]


class DisaggRouter:
    """One prefill engine + one decode engine + the migration loop.

    Single-process, two (optional) meshes — the transport is host memory, but
    the ``PageTransfer`` payload is already serialization-shaped, so a
    multi-host transport only swaps the hand-off, not the protocol.
    """

    # consecutive failed attach retries double the cooldown up to this many
    # router steps — bounded backoff, never preemption
    MAX_BACKOFF_STEPS = 8

    def __init__(self, config: Config, params, *,
                 serving: ServingConfig = None,
                 prefill_mesh=None, decode_mesh=None):
        sv = serving or config.serving
        assert all(k in ("attn_mlp", "attn_moe")
                   for k in config.model.block_pattern), \
            "disagg migrates KV pages only; recurrent per-slot state " \
            "(SSM/xLSTM) does not transfer"
        self.sv = sv
        dec_sv = sv if not sv.decode_pool_pages else \
            replace(sv, num_pages=sv.decode_pool_pages)
        self.prefill = PagedEngine(config, params, serving=sv,
                                   mesh=prefill_mesh, phase="prefill")
        self.decode = PagedEngine(config, params, serving=dec_sv,
                                  mesh=decode_mesh, phase="decode")
        self.migrate_batch = sv.migrate_batch
        self._pending: List[PageTransfer] = []    # detached, attach deferred
        self._cooldown = 0
        self._defers = 0
        self.stats = {"migrations": 0, "migrated_requests": 0,
                      "deferrals": 0, "bounce_backs": 0}

    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> int:
        """Admit to the prefill engine — after validating that the request
        can EVER live in the decode pool too (the prefill engine only checks
        its own pool; a request too big for the decode side would admit,
        prefill, then wedge the migration queue forever)."""
        eff = len(req.prompt) + \
            (req.patches.shape[0] if req.patches is not None else 0)
        need = pages_for(eff + req.sampling.max_new_tokens, self.decode.ps)
        if need > self.decode.alloc.num_pages:
            raise ValueError(
                f"request {req.rid}: needs {need} pages but the decode pool "
                f"has {self.decode.alloc.num_pages} (raise "
                f"ServingConfig.decode_pool_pages)")
        return self.prefill.add_request(req)

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One router iteration: prefill step -> migrate ready requests ->
        decode step -> bounce eviction victims back.  Returns the merged
        (rid, token) events of both engines."""
        events = self.prefill.step()
        self._retry_pending()
        self._migrate()
        events += self.decode.step()
        self._bounce_back()
        return events

    def done(self) -> bool:
        return (not self._pending
                and not self.prefill.scheduler.waiting
                and all(s is None for s in self.prefill.slots)
                and not self.decode.scheduler.waiting
                and all(s is None for s in self.decode.slots))

    def run_until_complete(self, max_steps: int = 10_000
                           ) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            self.step()
            if self.done():
                break
        for st in self.prefill._finished + self.decode._finished:
            out[st.request.rid] = st.generated
        return out

    # ------------------------------------------------------------------
    # migration
    # ------------------------------------------------------------------
    def _migrate(self) -> None:
        """Move every decode-ready request the decode pool can host NOW.

        Candidates — fully prefilled, holding their first sampled token, not
        finished — are taken in the prefill scheduler's policy order (so
        priority traffic migrates first and attach-side arrival order matches
        admission semantics), capped at ``migrate_batch`` per step (0 = all).
        The prefix that fits is computed against the decode side's free slots
        and free pages MINUS what already-deferred transfers will consume;
        what doesn't fit stays resident on the prefill engine — no detach
        without a home."""
        ready = [s for s in self.prefill.slots
                 if s is not None and not s.done and s.generated
                 and s.prefilled >= sum(s.chunk_plan)]
        if not ready:
            return
        rids = self.prefill.scheduler.order([s.request.rid for s in ready])
        if self.migrate_batch > 0:
            rids = rids[:self.migrate_batch]
        free_slots = sum(1 for s in self.decode.slots if s is None) \
            - sum(len(t.records) for t in self._pending)
        free_pages = self.decode.alloc.free_pages \
            - sum(t.n_pages for t in self._pending)
        take: List[int] = []
        pages: set = set()
        for rid in rids:
            grown = pages | set(self.prefill.alloc.tables[rid])
            if len(take) + 1 > free_slots or len(grown) > free_pages:
                break                 # decode pool full: the rest stays put
            take.append(rid)
            pages = grown
        if not take:
            if rids:
                self.stats["deferrals"] += 1
            return
        transfer = self.prefill.detach_requests(take)
        try:
            self.decode.attach_requests(transfer)
        except OutOfPages:
            # can only race the capacity check via deferred-transfer retries;
            # atomic — queue host-side and retry, never preempt
            self._note_defer()
            self._pending.append(transfer)
            return
        self.stats["migrations"] += 1
        self.stats["migrated_requests"] += len(take)

    def _note_defer(self) -> None:
        self.stats["deferrals"] += 1
        self._defers += 1
        self._cooldown = min(self.MAX_BACKOFF_STEPS, 1 << min(self._defers, 3))

    def _retry_pending(self) -> None:
        """Re-attach deferred transfers, oldest first, under bounded backoff
        (consecutive failures double the cooldown up to MAX_BACKOFF_STEPS
        router steps)."""
        if not self._pending:
            return
        if self._cooldown > 0:
            self._cooldown -= 1
            return
        still: List[PageTransfer] = []
        for t in self._pending:
            if still:                 # keep order: don't leapfrog a stuck one
                still.append(t)
                continue
            free_slots = sum(1 for s in self.decode.slots if s is None)
            if len(t.records) > free_slots:
                still.append(t)
                continue
            try:
                self.decode.attach_requests(t)
                self.stats["migrations"] += 1
                self.stats["migrated_requests"] += len(t.records)
            except OutOfPages:
                still.append(t)
        if still:
            self._note_defer()
        else:
            self._defers = 0
        self._pending = still

    def _bounce_back(self) -> None:
        """Decode-side preemption victims re-enter the PREFILL engine in
        recompute mode.  ``_preempt_one`` already freed their pages, reset
        ``prefilled`` and re-planned chunks over prompt+generated — exactly
        the single-engine recompute state — but a decode-phase engine can
        never re-prefill them, so the router moves the RequestState across
        and the normal admission path takes over."""
        while self.decode.scheduler.waiting:
            rid = self.decode.scheduler.pop_waiting()
            st = self.decode._by_rid.pop(rid)
            self.decode.scheduler.forget(rid)
            self.prefill._by_rid[rid] = st
            self.prefill.scheduler.add(rid, priority=st.request.priority)
            self.stats["bounce_backs"] += 1

    # ------------------------------------------------------------------
    def migration_stats(self) -> Dict[str, Any]:
        """Router + both engines' migration counters, one dict."""
        out = dict(self.stats)
        out["migrated_pages"] = self.prefill.metrics["migrated_pages"]
        out["migration_us"] = (self.prefill.metrics["migration_us"]
                               + self.decode.metrics["migration_us"])
        out["pending_transfers"] = len(self._pending)
        return out
