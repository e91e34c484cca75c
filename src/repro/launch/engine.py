"""Continuous-batching serving engine.

The layered decomposition of what used to be one monolithic
``PagedScheduler.run`` loop, shaped like the paper's dataflow discipline
(concurrently-executing stages connected by explicit state, not phases
run to completion):

* **load generation** (``launch/loadgen.py``) — timed request streams on
  a virtual clock;
* **admission / resources** (``launch/serve.PagedScheduler``) — page
  reservation, tables, reclamation, recycling;
* **batch composition** (:class:`BatchPolicy`, here) — each iteration
  picks a mix of page-sized prefill chunks from MULTIPLE waiting slots
  and decode steps for running slots under a per-iteration token budget;
* **step execution** (:class:`StepExecutor`, here) — issues the composed
  batch through the registry-routed paged kernels: ONE multi-slot
  ``prefill_attention`` forward (B = number of chunks) plus ONE batched
  ragged decode whose view masks non-decoding slots to the trash page;
* **metrics** (``launch/metrics.py``) — per-request TTFT and per-token
  latency on the same clock.

The engine loop (:class:`ContinuousEngine`) composes the stages and
keeps ``check_page_accounting`` invariants across interleaved
prefill/decode.  ``clock="wall"`` advances the clock by measured step
time (benchmarks); ``clock="tick"`` by a fixed tick (deterministic
tests and seeded load replay).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch
from . import tracing
from .loadgen import ArrivalQueue, Request
from .metrics import ServeMetrics
from .speculative import accept_longest_prefix


@dataclass
class StepPlan:
    """One engine iteration's work: ``prefill`` holds (slot, chunk start)
    pairs batched through ONE prefill forward; ``decode`` the slots that
    take a decode token; ``verify`` the draft tokens (per decode slot)
    the speculative mode admitted under the token budget — riding the
    same batched forward as the decode token they extend."""
    prefill: List[Tuple[int, int]] = field(default_factory=list)
    decode: List[int] = field(default_factory=list)
    verify: Dict[int, List[int]] = field(default_factory=dict)

    def empty(self) -> bool:
        return not self.prefill and not self.decode


@dataclass
class _PrefillState:
    """A slot's in-flight chunked prefill: page-padded prompt tokens,
    the true prompt length, and the next chunk's offset.  ``pos`` starts
    at ``skipped`` when a prefix-cache hit covered the leading chunks
    (their pages already hold valid K/V — nothing to compute)."""
    toks: np.ndarray
    ln: int
    pos: int = 0
    skipped: int = 0


class BatchPolicy:
    """Decode-first token-budget batch composition.

    Every running slot gets its decode token first (decode latency is
    the metric tail users feel); the remaining budget admits page-sized
    prefill chunks from distinct mid-prefill slots.  Chunks of one slot
    are sequential (chunk n+1 attends to chunk n's pages), so at most
    one chunk per slot per iteration — multi-slot batching is where the
    prefill parallelism comes from.  A budget smaller than one page
    still forces a chunk through when nothing is decoding, so admission
    can never livelock.

    Decode-first precedence is strict: the running decode set is never
    trimmed to fit the budget (stalling a mid-generation slot would just
    move its token to the next iteration while holding its pages), so
    when decodes alone meet or exceed the budget the remaining prefill
    allowance clamps to zero rather than going negative — the budget
    bounds *prefill admission*, decode cost is bounded by ``slots``.
    """

    def __init__(self, token_budget: int, page: int):
        self.token_budget = int(token_budget)
        self.page = int(page)

    def compose(self, running: List[int],
                prefilling: List[Tuple[int, int]],
                drafts: Optional[Dict[int, List[int]]] = None) -> StepPlan:
        """``drafts`` (speculative mode) maps running slots to proposed
        draft tokens; they are admitted AFTER the mandatory decode tokens
        and BEFORE prefill chunks, under the same budget — a verify chunk
        is cheaper than a prefill chunk (a few tokens vs a page) and its
        accepted tokens pay down decode latency directly, but it must
        never starve admission: leftover budget still prefills."""
        decode = list(running)
        left = max(0, self.token_budget - len(decode))
        verify: Dict[int, List[int]] = {}
        if drafts:
            for slot in decode:
                ks = drafts.get(slot, [])
                take = min(len(ks), left)
                if take > 0:
                    verify[slot] = list(ks[:take])
                    left -= take
        chunks: List[Tuple[int, int]] = []
        for slot, start in prefilling:
            if left < self.page:
                break
            chunks.append((slot, start))
            left -= self.page
        if not decode and not chunks and prefilling:
            chunks.append(prefilling[0])   # forced progress
        return StepPlan(prefill=chunks, decode=decode, verify=verify)


class StepExecutor:
    """Issues a composed :class:`StepPlan` through the scheduler's jitted
    paged forwards, accumulating per-phase wall time and the multi-slot
    batch-width stats the acceptance probes read.  ``t_prefill`` and
    ``t_decode`` are the seconds of each step's ``launch`` and ``wait``
    spans (``launch/tracing.py``): from the first host-to-device copy to
    the blocking read-back."""

    def __init__(self, sched):
        self.sched = sched
        self.t_prefill = 0.0
        self.t_decode = 0.0
        self.prefill_chunks = 0
        self.max_prefill_batch = 0

    def prefill(self, chunks: List[Tuple[int, int]],
                states: List[Optional[_PrefillState]]) -> np.ndarray:
        """One batched multi-slot prefill forward (B = len(chunks)).
        Returns (B, V) logits; row i is chunk i's last real position."""
        sched = self.sched
        page = sched.page
        with tracing.span("prefill") as sp:
            tracing.count("prefill_chunks", len(chunks))
            with tracing.span("prefill.prepare"):
                toks = np.stack([states[s].toks[st:st + page]
                                 for s, st in chunks])
                starts = np.asarray([st for _, st in chunks], np.int32)
                tables = sched.table[[s for s, _ in chunks]]
                last = np.asarray([min(states[s].ln, st + page) - 1 - st
                                   for s, st in chunks], np.int32)
            with tracing.span("prefill.launch"):
                rest = (tracing.to_device(toks), tracing.to_device(starts),
                        tracing.to_device(tables), tracing.to_device(last))
                logits, sched.cache = sched._prefill(sched.params,
                                                     sched.cache, *rest)
                sched.count_collectives("prefill", *rest)
            with tracing.span("prefill.wait"):
                logits = tracing.to_host(logits)
        self.t_prefill += sp.ns_of("prefill.launch", "prefill.wait") * 1e-9
        self.prefill_chunks += len(chunks)
        self.max_prefill_batch = max(self.max_prefill_batch, len(chunks))
        return logits

    def decode(self, cur: np.ndarray, decode_slots: List[int]) -> np.ndarray:
        """One batched ragged decode.  Non-decoding slots (mid-prefill or
        idle) ride along with a zero length and an all-trash table view,
        so their masked writes can never touch a live page."""
        sched = self.sched
        with tracing.span("decode") as sp:
            tracing.count("decode_rows", len(decode_slots))
            with tracing.span("decode.prepare"):
                sched.prepare_decode(decode_slots)   # copy-on-write sweep
                mask = np.zeros((sched.slots,), bool)
                mask[decode_slots] = True
                lengths = np.where(mask, sched.lengths, 0).astype(np.int32)
                table = np.where(mask[:, None], sched.table,
                                 0).astype(np.int32)
                # one layer's grid steps per kv head in the decode kernel
                # (its default tile geometry): the decoding rows attend to
                # their cached tokens and the new one, every other row to
                # nothing
                tracing.count("decode_tiles", dispatch.decode_work_count(
                    np.where(mask, lengths + 1, 0), sched.n_slot_pages,
                    sched.page, window=sched.window))
            nxt = sched.step(cur, view=(lengths, table))
        self.t_decode += sp.ns_of("decode.launch", "decode.wait") * 1e-9
        return nxt

    def verify(self, cur: np.ndarray, decode_slots: List[int],
               drafts: Dict[int, List[int]], width: int) -> np.ndarray:
        """One batched fixed-width verify forward replacing the decode
        step in speculative mode: slot rows carry [current token,
        drafts..., padding]; non-decoding slots ride along masked to the
        trash page exactly as in :meth:`decode`.  Returns (slots, width)
        greedy predictions."""
        sched = self.sched
        with tracing.span("verify") as sp:
            tracing.count("decode_rows", len(decode_slots))
            with tracing.span("verify.prepare"):
                sched.prepare_verify(decode_slots, width)  # full-span CoW
                toks = np.zeros((sched.slots, width), np.int32)
                mask = np.zeros((sched.slots,), bool)
                for slot in decode_slots:
                    mask[slot] = True
                    toks[slot, 0] = cur[slot]
                    ks = drafts.get(slot, [])
                    toks[slot, 1:1 + len(ks)] = ks
                lengths = np.where(mask, sched.lengths, 0).astype(np.int32)
                table = np.where(mask[:, None], sched.table,
                                 0).astype(np.int32)
            preds = sched.verify_step(toks, view=(lengths, table))
        self.t_decode += sp.ns_of("verify.launch", "verify.wait") * 1e-9
        return preds


class ContinuousEngine:
    """Admission -> compose -> execute -> account, once per iteration.

    Requests arrive on the virtual clock via an :class:`ArrivalQueue`;
    waiting requests admit FCFS into free slots by reserving their whole
    lifetime's pages up front (the scheduler's admission contract), then
    prefill chunk-by-chunk ACROSS iterations — so one long prompt never
    stalls the decode cadence of running slots, and multiple mid-prefill
    slots share one batched prefill forward.
    """

    def __init__(self, sched, *, token_budget: int = 0,
                 clock: str = "wall", tick: float = 1.0,
                 metrics: Optional[ServeMetrics] = None, drafter=None,
                 log=print):
        if clock not in ("wall", "tick"):
            raise ValueError(f"clock must be wall|tick, got {clock!r}")
        self.sched = sched
        self.policy = BatchPolicy(token_budget or sched.slots * sched.page,
                                  sched.page)
        self.executor = StepExecutor(sched)
        # speculative mode: a drafter swaps the decode step for a fixed-
        # width draft/verify/rollback step (launch/speculative.py)
        self.drafter = drafter
        self.verify_width = (drafter.max_draft + 1) if drafter else 0
        self.clock_mode = clock
        self.tick = float(tick)
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.log = log or (lambda *a, **k: None)
        self.clock = 0.0
        self.queue: Optional[ArrivalQueue] = None
        self.waiting: List[Request] = []
        self.states: List[Optional[_PrefillState]] = [None] * sched.slots
        self.cur = np.zeros((sched.slots,), np.int32)
        self.done: List[Request] = []
        self.admission_order: List[int] = []
        self.iterations = 0
        self.max_resident = 0
        # peak BYTES of live KV pool (pages x per-page bytes at the
        # active storage dtype, scales included) — the residency metric
        # that stays comparable across kv_dtype, unlike max_resident
        # (request count) or held pages (dtype-blind)
        self.max_resident_kv_bytes = 0
        self.trace_id = tracing.new_engine_id()
        self.step_calls = 0

    # ------------------------------------------------------------- warmup
    def warmup(self) -> None:
        """Compile every prefill batch width (1..slots) plus the masked
        decode step outside the timed/counted region; all warmup writes
        land on the trash page, so live state is untouched."""
        sched = self.sched
        if getattr(sched, "tp", 1) > 1:
            self.log(f"[engine] warmup on a tp={sched.tp} mesh "
                     f"(sharded decode/prefill steps)")
        for b in range(1, sched.slots + 1):
            _, sched.cache = sched._prefill(
                sched.params, sched.cache,
                jnp.zeros((b, sched.page), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, sched.n_slot_pages), jnp.int32),
                jnp.full((b,), sched.page - 1, jnp.int32))
        zeros = np.zeros((sched.slots,), np.int32)
        sched.step(zeros, view=(zeros, np.zeros_like(sched.table)))
        if self.drafter is not None:
            sched.verify_step(
                np.zeros((sched.slots, self.verify_width), np.int32),
                view=(zeros, np.zeros_like(sched.table)))
            sched.verify_steps = 0
        sched.decode_steps = 0
        sched.decode_tokens = 0

    # ---------------------------------------------------------- admission
    def _admit(self, now: float) -> None:
        sched = self.sched
        keep: List[Request] = []
        for r in self.waiting:
            if sched.admissible(r):
                keep.append(r)
                continue
            r.done = False
            sched.rejected += 1
            sched.rejected_requests.append(r)
            self.metrics.on_reject(r.rid, now)
            self.log(f"[engine] rejecting request {r.rid}: "
                     f"{sched._reject_reason(r)}")
        self.waiting = keep
        for slot in range(sched.slots):
            if not self.waiting:
                break
            if sched.active[slot] is not None:
                continue
            if not sched.reserve(self.waiting[0], slot):
                break                      # FCFS: never bypass the head
            r = self.waiting.pop(0)
            ln = len(r.prompt)
            shared = int(sched.shared_tokens[slot])
            if shared >= ln:
                # Fully covered by the prefix cache: every prompt position
                # already has valid K/V in shared pages, so no prefill
                # forward runs at all.  The slot goes straight to running
                # with lengths = ln-1 and the last prompt token teacher-
                # forced through the next batched decode — that decode's
                # append lands mid-page in a shared page and copy-on-
                # writes it (reserve stashed the spare page).
                sched.lengths[slot] = ln - 1
                self.cur[slot] = int(r.prompt[ln - 1])
                self.states[slot] = None
            else:
                # Partial coverage is page-aligned (trie matches whole
                # chunks), so prefill resumes at the first uncovered chunk.
                toks = np.zeros((-(-ln // sched.page) * sched.page,),
                                np.int32)
                toks[:ln] = r.prompt
                self.states[slot] = _PrefillState(toks, ln, pos=shared,
                                                  skipped=shared)
            self.admission_order.append(r.rid)
            self.metrics.on_admit(r.rid, now)

    def _maybe_truncate(self, r: Request, slot: int) -> None:
        """Called at finish time: a request stopped by the context wall
        rather than its own ``max_new`` is truncated — flagged, counted,
        logged, never silent."""
        r.truncated = len(r.out) < r.max_new
        if r.truncated:
            self.sched.truncated += 1
            self.metrics.on_truncate(r.rid)
            self.log(f"[engine] truncating request {r.rid} at the context "
                     f"wall: {len(r.out)}/{r.max_new} tokens "
                     f"(max_len={self.sched.max_len})")

    def _finish(self, slot: int, t: float) -> None:
        r = self.sched.active[slot]
        r.done = True
        self.done.append(r)
        self.metrics.on_finish(r.rid, t)
        self.sched._recycle(slot)
        self.states[slot] = None

    # ------------------------------------------------------ one iteration
    def step(self) -> bool:
        """One engine iteration; returns False once fully drained.  Each
        call leaves one record in ``launch/tracing.py``."""
        with tracing.iteration(self.trace_id, self.step_calls,
                               tp=self.sched.tp):
            self.step_calls += 1
            return self._step()

    def _step(self) -> bool:
        sched = self.sched
        now = self.clock
        with tracing.span("engine.admit"):
            if self.queue is not None:
                for r in self.queue.pop_ready(now):
                    self.metrics.on_arrival(r.rid, r.arrival)
                    self.waiting.append(r)
            self._admit(now)
            self.max_resident = max(
                self.max_resident,
                sum(1 for a in sched.active if a is not None))
            self.max_resident_kv_bytes = max(
                self.max_resident_kv_bytes, sched.kv_bytes_resident())

        with tracing.span("engine.compose"):
            running = [i for i in range(sched.slots)
                       if sched.active[i] is not None
                       and self.states[i] is None]
            prefilling = [(i, self.states[i].pos)
                          for i in range(sched.slots)
                          if self.states[i] is not None]
            drafts = (sched.draft_for(self.drafter, running)
                      if self.drafter is not None and running else None)
            plan = self.policy.compose(running, prefilling, drafts=drafts)

        if plan.empty():
            nxt = (self.queue.next_arrival()
                   if self.queue is not None else None)
            if nxt is not None:
                self.clock = max(self.clock, nxt)   # idle: jump forward
                return True
            if self.waiting:
                # unreachable by construction (an idle engine has every
                # page free, so only inadmissible requests can fail, and
                # those were rejected above) — defensive
                raise RuntimeError(
                    "admission deadlock: empty batch but queued requests "
                    "cannot reserve pages")
            return False

        t0 = time.perf_counter()
        logits = (self.executor.prefill(plan.prefill, self.states)
                  if plan.prefill else None)
        nxt_tok = preds = None
        if plan.decode:
            if self.drafter is not None:
                preds = self.executor.verify(self.cur, plan.decode,
                                             plan.verify, self.verify_width)
            else:
                nxt_tok = self.executor.decode(self.cur, plan.decode)
        with tracing.span("engine.account"):
            self.clock += ((time.perf_counter() - t0)
                           if self.clock_mode == "wall" else self.tick)
            self.iterations += 1
            self._account(plan, logits, nxt_tok, preds)
        return True

    def _account(self, plan: StepPlan, logits, nxt_tok, preds) -> None:
        """Emit the step's tokens; finish, truncate or reclaim slots."""
        sched = self.sched
        t = self.clock
        for row, (slot, _start) in enumerate(plan.prefill):
            st = self.states[slot]
            st.pos += sched.page
            if st.pos < st.ln:
                continue
            # last chunk: the first generated token is born (TTFT moment)
            r = sched.active[slot]
            sched.lengths[slot] = st.ln
            sched.prefill_tokens += st.ln - st.skipped
            sched.cache_prefix(slot, r.prompt)
            first = int(np.argmax(logits[row]))
            r.out.append(first)
            self.cur[slot] = first
            self.metrics.on_token(r.rid, t)
            self.states[slot] = None
            if (len(r.out) >= r.max_new
                    or int(sched.lengths[slot]) >= sched.max_len):
                self._maybe_truncate(r, slot)
                self._finish(slot, t)
            else:
                sched._reclaim_slot(slot)   # long prompts outrun the window

        for slot in plan.decode:
            r = sched.active[slot]
            if self.drafter is not None:
                # longest-correct-prefix acceptance + host rollback: the
                # emission loop replicates the plain decode path's
                # per-token finish checks exactly, so greedy streams
                # (including truncation points) are bit-identical to the
                # non-speculative engine
                ks = plan.verify.get(slot, [])
                emit = accept_longest_prefix(ks, preds[slot])
                accepted = len(emit) - 1
                emitted = 0
                finished = False
                for tok in emit:
                    sched.lengths[slot] += 1
                    r.out.append(tok)
                    self.cur[slot] = tok
                    emitted += 1
                    self.metrics.on_token(r.rid, t)
                    if (len(r.out) >= r.max_new
                            or int(sched.lengths[slot]) >= sched.max_len):
                        finished = True
                        break
                sched.note_spec(len(ks), accepted, emitted)
                self.metrics.on_spec_step(len(ks), accepted, emitted)
                if finished:
                    self._maybe_truncate(r, slot)
                    self._finish(slot, t)
                else:
                    sched._reclaim_slot(slot)
                continue
            sched.lengths[slot] += 1
            tok = int(nxt_tok[slot])
            r.out.append(tok)
            self.cur[slot] = tok
            self.metrics.on_token(r.rid, t)
            if (len(r.out) >= r.max_new
                    or int(sched.lengths[slot]) >= sched.max_len):
                self._maybe_truncate(r, slot)
                self._finish(slot, t)
            else:
                sched._reclaim_slot(slot)

    # ---------------------------------------------------------------- run
    def submit(self, requests: List[Request]) -> None:
        self.queue = ArrivalQueue(requests)

    def run(self, requests: Optional[List[Request]] = None) -> List[Request]:
        if requests is not None:
            self.submit(requests)
        while self.step():
            pass
        return self.done
