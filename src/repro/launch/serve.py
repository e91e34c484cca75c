"""Batched serving driver: paged-KV scheduler + legacy dense server.

Two cache layouts behind one CLI (``--cache {dense,paged}``):

* ``dense`` — the original fixed-slot continuous-batching decoder: one
  rectangular (slots, max_len) KV cache, prompts teacher-forced through the
  decode step one token at a time.
* ``paged`` — the serving runtime this module is really about.  The KV
  cache is a pool of fixed-size pages (paper §4.3 memory banking); a
  host-side scheduler does admission control (a request is admitted only
  when its whole lifetime's pages can be reserved), chunked prefill (the
  Pallas ragged multi-token kernel via ``dispatch.prefill_attention``,
  §2.1.4 cross-input interleaving against decode), batched decode over
  ragged lengths (every slot at its own position, the Pallas ragged
  kernel via ``dispatch.decode_attention``), sliding-window page
  reclamation (fully windowed stacks free pages wholly behind
  ``lengths - window`` mid-request), and slot recycling (finished
  sequences return their pages to the free list).  The split mirrors
  Chi et al.'s task-parallel decoupling: the scheduler computes
  addresses (page tables), the kernels only ever see dense tiles.

Two paged schedules (``--schedule {static,continuous}``):

* ``static`` — ``PagedScheduler.run``: admit a static request list,
  whole-prompt prefill on admission, decode rounds to completion.
* ``continuous`` — ``launch/engine.ContinuousEngine``: requests arrive
  on a virtual clock (``launch/loadgen``), each iteration composes a
  mix of multi-slot prefill chunks (one BATCHED ``prefill_attention``
  forward, B > 1) and decode steps under a token budget, and
  ``launch/metrics`` records TTFT + per-token latency percentiles.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \\
      --cache paged --schedule continuous --dispatch kernels \\
      --requests 8 --max-new 16 --rate 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch
from ..core.memory import DtypePolicy
from ..models.transformer import ExecOptions, Model, paged_supported
from . import tracing
from .loadgen import Request  # noqa: F401  (re-export: the historical home)
from .prefix import PrefixCache

DEFAULT_PAGE_SIZE = 64


class Server:
    """Fixed-slot continuous-batching decoder (dense rectangular cache)."""

    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 log=print):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.log = log or (lambda *a, **k: None)
        self.cache = model.init_cache(slots, max_len)
        self.active: List[Optional[Request]] = [None] * slots
        self.pos = 0
        self.truncated = 0                # requests cut short at the wall
        self.rejected = 0                 # unserved at the wall, counted
        self.rejected_requests: List[Request] = []
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def _feed_batch(self, tokens: np.ndarray) -> Dict[str, jax.Array]:
        batch = {"tokens": jnp.asarray(tokens)[:, None]}
        if self.model.cfg.mrope_sections:
            batch["positions"] = jnp.full(
                (self.slots, 1, len(self.model.cfg.mrope_sections)),
                self.pos, jnp.int32)
        return batch

    def step(self, tokens: np.ndarray) -> np.ndarray:
        logits, self.cache = self._decode(
            self.params, self.cache, self._feed_batch(tokens),
            jnp.int32(self.pos))
        self.pos += 1
        return np.asarray(jnp.argmax(logits, axis=-1))

    def run(self, requests: List[Request], greedy: bool = True
            ) -> List[Request]:
        queue = list(requests)
        cur = np.zeros((self.slots,), np.int32)
        prompt_cursor = np.zeros((self.slots,), np.int64)
        done: List[Request] = []
        while queue or any(r is not None for r in self.active):
            # fill free slots (continuous batching)
            for i in range(self.slots):
                if self.active[i] is None and queue:
                    self.active[i] = queue.pop(0)
                    prompt_cursor[i] = 0
                    cur[i] = self.active[i].prompt[0]
            nxt = self.step(cur)
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                prompt_cursor[i] += 1
                if prompt_cursor[i] < len(r.prompt):
                    cur[i] = r.prompt[prompt_cursor[i]]   # teacher-forced
                else:
                    r.out.append(int(nxt[i]))
                    cur[i] = nxt[i]
                    if len(r.out) >= r.max_new or self.pos >= self.max_len - 1:
                        r.done = True
                        r.truncated = len(r.out) < r.max_new
                        if r.truncated:
                            self.truncated += 1
                        done.append(r)
                        self.active[i] = None
            if self.pos >= self.max_len - 1:
                break
        # context wall: the shared ``pos`` hit max_len with work still in
        # flight.  Requests caught mid-prompt (or mid-generation) are
        # returned flagged — not silently dropped from ``active`` — and
        # requests never admitted are counted as rejected, mirroring the
        # paged scheduler's rejection accounting.
        for i, r in enumerate(self.active):
            if r is None:
                continue
            r.done = True
            r.truncated = True
            self.truncated += 1
            done.append(r)
            self.active[i] = None
            self.log(f"[dense] truncating request {r.rid} at the "
                     f"context wall (max_len={self.max_len}, "
                     f"{len(r.out)} tokens out)")
        for r in queue:
            r.done = False
            self.rejected += 1
            self.rejected_requests.append(r)
            self.log(f"[dense] rejecting request {r.rid}: context wall "
                     f"reached before admission (max_len={self.max_len})")
        return done


# --------------------------------------------------------------------------
# paged runtime
# --------------------------------------------------------------------------

class PageAllocator:
    """Host-side refcounted free list over the shared page pool.

    Physical page 0 is reserved as the TRASH page: inactive slots' tables
    point every logical page at it, so their masked decode writes can
    never corrupt a live sequence.

    Every live page carries a reference count: ``alloc`` hands out pages
    at refcount 1, ``share`` adds a holder (another slot's table binding,
    or the prefix cache), and ``release`` drops one — the page only
    returns to the free list when its last holder lets go.  Without
    sharing every page lives its whole life at refcount 1 and the
    allocator behaves exactly as before.
    """

    def __init__(self, total_pages: int):
        self.total = total_pages
        self._free = list(range(total_pages - 1, 0, -1))
        self.ref = [0] * total_pages
        # single choke point for owners that must react to page reuse:
        # called with the page list every ``alloc`` hands out.  The paged
        # scheduler resets quantization scale rows here — a recycled
        # page's stale scales must never leak into its next sequence.
        # Copy-on-write copies its payload AFTER alloc, so copied scales
        # survive the reset.
        self.on_alloc = None

    def available(self) -> int:
        return len(self._free)

    def held(self) -> int:
        """Pages with at least one holder (excl. the trash page)."""
        return sum(1 for p in range(1, self.total) if self.ref[p] > 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        got, self._free = self._free[-n:], self._free[:-n]
        got = got[::-1]
        for p in got:
            assert self.ref[p] == 0, f"page {p} allocated while referenced"
            self.ref[p] = 1
        if got and self.on_alloc is not None:
            self.on_alloc(got)
        return got

    def share(self, page: int) -> None:
        assert self.ref[page] > 0, f"cannot share free page {page}"
        self.ref[page] += 1

    def release(self, pages: List[int]) -> None:
        for p in reversed(pages):
            assert self.ref[p] > 0, f"double free of page {p}"
            self.ref[p] -= 1
            if self.ref[p] == 0:
                self._free.append(p)


def _copy_cache_page(cache, src, dst):
    """Copy one physical page across every layer's K/V pools (the
    copy-on-write payload).  Pool leaves are (P, Hkv, page, hd) and, for
    quantized pools, (P, Hkv) scale rows; scanned layer stacks carry a
    leading period axis (ndim 5 / 3).  Scales ride the same copy so a
    CoW'd page dequantizes identically to its source."""
    def cp(a):
        if a.ndim in (3, 5):
            return a.at[:, dst].set(a[:, src])
        return a.at[dst].set(a[src])
    return jax.tree.map(cp, cache)


def _reset_page_scales(cache, pages):
    """Zero the quantization scale rows of freshly-allocated pages.

    A recycled page still holds its previous sequence's int8 payload and
    scales; ``append_token_quantized`` treats scale 0 as "empty page" and
    wipes the stale payload on the first write, so resetting the scale
    row here is what makes page reuse sound under quantization.  No-op
    for float pools (no ``*_scale`` leaves)."""
    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if isinstance(k, str) and k.endswith("_scale"):
                    out[k] = (v.at[:, pages].set(0.0) if v.ndim == 3
                              else v.at[pages].set(0.0))
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(cache)


def _page_bytes(cache) -> int:
    """Bytes ONE physical page occupies across every cache leaf — K/V
    pools at the active storage dtype plus any scale rows.  Pool axis is
    0 for per-layer leaves ((P, Hkv, page, hd) pools, (P, Hkv) scales)
    and 1 for scanned stacks with a leading period axis."""
    total = 0
    for leaf in jax.tree.leaves(cache):
        pool_axis = 1 if leaf.ndim in (3, 5) else 0
        per_page = 1
        for i, s in enumerate(leaf.shape):
            if i != pool_axis:
                per_page *= s
        total += per_page * jnp.dtype(leaf.dtype).itemsize
    return total


def pick_page_size(backend: Optional[str] = None) -> int:
    """Choose the pool layout from tuned decode plans: among cached
    ``decode_attention`` entries for this backend, take the page size of
    the fastest kernel-level plan (layout is a tunable, §3.4); fall back
    to DEFAULT_PAGE_SIZE when nothing was tuned."""
    from ..tune.cache import default_cache, parse_key
    cache = default_cache()
    backend = backend or jax.default_backend()
    best_us, best_page = float("inf"), 0
    for key, entry in cache.entries.items():
        try:
            kernel, shape, _, kb = parse_key(key)
        except ValueError:
            continue
        if kernel != "decode_attention" or kb != backend:
            continue
        plan = entry.get("plan", {})
        page = plan.get("page_size", 0)
        us = entry.get("us", float("inf"))
        if page and us < best_us:
            best_us, best_page = us, page
    return best_page or DEFAULT_PAGE_SIZE


class PagedScheduler:
    """Admission, chunked prefill, batched ragged decode, slot recycling.

    With ``prefix_cache=True`` the scheduler also shares KV pages across
    requests: finished prefills publish their full pages into a token-id
    trie (``launch/prefix.PrefixCache``), ``reserve`` binds a new
    request's leading table rows to matching cached pages (refcounted,
    prefill skipped for covered chunks), and a decode append into a page
    with other holders triggers copy-on-write.  The kernels are oblivious
    — they resolve ``(slot, page_idx)`` through the same tables either
    way — so sharing is zero kernel changes.
    """

    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 page_size: int = 0, total_pages: int = 0,
                 prefix_cache: bool = False, mesh=None, log=print):
        if not paged_supported(model.cfg):
            raise ValueError(
                f"arch {model.cfg.name} has recurrent/stateful layers; "
                "paged serving requires attention-family stacks "
                "(use --cache dense)")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.log = log or (lambda *a, **k: None)
        self.page = page_size or model.cfg.kv_page_size or pick_page_size()
        self.n_slot_pages = -(-max_len // self.page)
        total = total_pages or 1 + slots * self.n_slot_pages
        self.alloc = PageAllocator(total)
        self.cache = model.init_paged_cache(slots, max_len, self.page,
                                            total_pages=total)
        # quantized pools carry per-page scale rows; their lifecycle is
        # slaved to the allocator via on_alloc (reset-on-reuse)
        self._has_scales = any(
            leaf.ndim in (2, 3) for leaf in jax.tree.leaves(self.cache))
        self._page_bytes = _page_bytes(self.cache)
        if self._has_scales:
            self.alloc.on_alloc = self._reset_scales
        self.table = np.zeros((slots, self.n_slot_pages), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        # sliding-window page reclamation: only sound when EVERY attention
        # layer is windowed (a single global-attention layer reads the
        # whole history, so its pages are never dead)
        self.window = model.cfg.window if all(
            m == "swa" for m, _ in model.cfg.layer_kinds()) else 0
        self.reclaimed = [0] * slots      # leading logical pages freed
        self.pages_reclaimed = 0
        self.prefill_tokens = 0
        self.decode_steps = 0
        self.decode_tokens = 0
        # ---- speculative decoding (launch/speculative.py) ----
        self.verify_steps = 0             # batched verify forwards
        self.spec_drafted = 0             # candidate tokens proposed
        self.spec_accepted = 0            # candidates the target agreed with
        self.spec_emitted = 0             # tokens emitted by verify steps
        self.rejected = 0                 # inadmissible requests, counted
        self.rejected_requests: List[Request] = []
        self.truncated = 0                # finished early at max_len
        # ---- prefix sharing (refcounted pages + copy-on-write) ----
        self.prefix = PrefixCache(self.page) if prefix_cache else None
        self.shared_tokens = np.zeros((slots,), np.int64)
        self.shared_tokens_total = 0      # prompt tokens never prefilled
        self.cow_copies = 0
        # a fully-covered request's first decode appends into a shared
        # page; its copy-on-write page is reserved at admission so the
        # reserve-on-admit contract (never stall mid-decode) still holds
        self.cow_stash: List[List[int]] = [[] for _ in range(slots)]
        # ---- tensor parallelism (runtime/tp.py) ----
        # a mesh shards params + KV pools over its "model" axis and swaps
        # the step fns for shard_map'd twins; the scheduler's host-side
        # page metadata (tables, lengths, allocator, trie) is device-free
        # and identical across shards, so nothing else changes
        self.mesh = mesh
        self.tp = int(mesh.shape["model"]) if mesh is not None else 1
        if mesh is not None:
            from ..runtime import tp as tp_mod
            err = tp_mod.tp_error(model.cfg, self.tp)
            if err:
                raise ValueError(err)
            self.params = tp_mod.shard_tree(
                params, tp_mod.param_pspecs(params, model.cfg, self.tp),
                mesh)
            self.cache = tp_mod.shard_tree(
                self.cache, tp_mod.cache_pspecs(self.cache, model.cfg,
                                                self.tp), mesh)
            dec, pre = tp_mod.sharded_paged_fns(model, mesh)
            self._decode = jax.jit(dec, donate_argnums=(1,))
            self._prefill = jax.jit(pre, donate_argnums=(1,))
            self._verify = None        # no sharded verify twin (yet)
        else:
            self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
            self._prefill = jax.jit(model.prefill_step_paged,
                                    donate_argnums=(1,))
            self._verify = jax.jit(model.verify_step_paged,
                                   donate_argnums=(1,))
        # page copies / scale resets are sharding-agnostic (they index the
        # replicated pool axis), so GSPMD propagates the pool sharding
        self._copy_page = jax.jit(_copy_cache_page, donate_argnums=(0,))

    # ------------------------------------------------------------ admission
    def pages_needed(self, r: Request) -> int:
        """Lifetime page budget, clamped to the context window: positions
        beyond ``max_len`` can never be written (the decode guard stops
        there), so reserving pages for them would only waste pool."""
        return -(-min(len(r.prompt) + r.max_new, self.max_len) // self.page)

    def admissible(self, r: Request) -> bool:
        """Can this request EVER be admitted?  Its prompt must leave room
        to generate at least one token inside ``max_len``, and its
        (max_len-clamped) lifetime page budget must fit one slot's table
        and the pool (minus the trash page)."""
        return (len(r.prompt) < self.max_len
                and self.pages_needed(r) <= min(self.n_slot_pages,
                                                self.alloc.total - 1))

    def _reject_reason(self, r: Request) -> str:
        if len(r.prompt) >= self.max_len:
            return (f"prompt {len(r.prompt)} tokens >= max_len "
                    f"{self.max_len}")
        return (f"needs {self.pages_needed(r)} pages "
                f"(> {self.n_slot_pages}/slot or pool)")

    def reserve(self, r: Request, slot: int) -> bool:
        """Reserve the request's whole-lifetime pages up front (admission
        control: a request never stalls mid-decode on an empty free
        list) and bind it to ``slot``.  Prefill is the caller's business:
        the static path prefills the whole prompt immediately
        (``try_admit``), the continuous engine spreads chunks across
        iterations.

        With a prefix cache, matching cached pages are bound shared
        (refcounted) instead of allocated: ``shared_tokens[slot]`` tells
        the caller how many leading prompt tokens already hold valid K/V
        — prefill starts there.  When the cache covers the whole prompt
        the request also reserves one copy-on-write page (its first
        decode append lands mid-page in shared memory).
        """
        need = self.pages_needed(r)
        if need > self.n_slot_pages:
            return False
        shared: List[int] = []
        covered = 0
        if self.prefix is not None:
            shared, covered = self.prefix.match(r.prompt)
            # pin before any eviction below can free them out from under us
            for p in shared:
                self.alloc.share(p)
        n_cow = 1 if covered >= len(r.prompt) else 0
        n_priv = need - len(shared) + n_cow
        if self.alloc.available() < n_priv and self.prefix is not None:
            self.prefix.evict(n_priv - self.alloc.available(), self.alloc)
        if self.alloc.available() < n_priv:
            self.alloc.release(shared)     # unpin: admission failed
            return False
        pages = self.alloc.alloc(n_priv)
        self.cow_stash[slot] = pages[need - len(shared):]
        pages = shared + pages[:need - len(shared)]
        self.slot_pages[slot] = pages
        self.reclaimed[slot] = 0
        self.table[slot] = 0
        self.table[slot, :need] = pages
        self.lengths[slot] = 0
        self.active[slot] = r
        self.shared_tokens[slot] = covered
        self.shared_tokens_total += covered
        self.check_page_accounting()
        return True

    def try_admit(self, r: Request, slot: int) -> bool:
        """Static-schedule admission: reserve, then chunk-prefill the
        (non-shared tail of the) prompt to completion.  A fully-covered
        prompt skips prefill outright: the first token is born from one
        masked ragged decode of the last prompt token (which is also the
        copy-on-write moment for the shared partial page it lands in)."""
        if not self.reserve(r, slot):
            return False
        ln = len(r.prompt)
        start = int(self.shared_tokens[slot])
        if start >= ln:
            self.lengths[slot] = ln - 1
            first = self._first_token_via_decode(slot, int(r.prompt[ln - 1]))
        else:
            first = self._prefill_prompt(r, slot, start=start)
        self.lengths[slot] = ln
        self.cache_prefix(slot, r.prompt)
        r.out.append(first)
        self._reclaim_slot(slot)    # long prompts can outrun the window
        return True

    def _prefill_prompt(self, r: Request, slot: int, start: int = 0) -> int:
        """Chunked prefill (chunk = one page) from page-aligned ``start``
        (shared-covered leading chunks already hold valid K/V); returns
        the first generated token from the last real prompt position's
        logits."""
        ln = len(r.prompt)
        padded = -(-ln // self.page) * self.page
        toks = np.zeros((padded,), np.int32)
        toks[:ln] = r.prompt
        table_row = tracing.to_device(self.table[slot])
        logits = None
        for t0 in range(start, ln, self.page):
            last = min(ln, t0 + self.page) - 1 - t0
            logits, self.cache = self._prefill(
                self.params, self.cache,
                tracing.to_device(toks[t0:t0 + self.page])[None],
                tracing.to_device(np.int32(t0)), table_row,
                tracing.to_device(np.int32(last)))
        self.prefill_tokens += ln - start
        return int(np.argmax(tracing.to_host(logits[0])))

    def _first_token_via_decode(self, slot: int, token: int) -> int:
        """One masked ragged decode advancing only ``slot`` (other slots'
        ride-along writes land on the trash page): teacher-forces the
        last prompt token at position ``lengths[slot]`` and returns the
        argmax of its logits — the fully-covered admission path's TTFT
        moment."""
        self.prepare_decode([slot])
        mask = np.zeros((self.slots,), bool)
        mask[slot] = True
        lengths = np.where(mask, self.lengths, 0).astype(np.int32)
        table = np.where(mask[:, None], self.table, 0).astype(np.int32)
        cur = np.zeros((self.slots,), np.int32)
        cur[slot] = token
        nxt = self.step(cur, view=(lengths, table))
        return int(nxt[slot])

    # --------------------------------------------------- prefix sharing
    def cache_prefix(self, slot: int, prompt) -> None:
        """Publish the slot's fully-prefilled prompt chunks into the
        prefix trie (no-op without a cache).  Sound under window
        reclamation too: reclaiming only drops the slot's own reference,
        and a trie-held page keeps valid K/V for its prompt positions."""
        if self.prefix is None:
            return
        self.prefix.insert(prompt, self.slot_pages[slot], self.alloc)
        self.check_page_accounting()

    def _cow_page(self, slot: int, idx: int) -> None:
        """Give ``slot`` a private copy of its logical page ``idx`` if it
        currently has other holders (prefix cache or sharer slots):
        stashed CoW page first, then eviction-backed allocation; payload
        (and int8 scale rows) copied, table rebound, source released."""
        src = self.slot_pages[slot][idx]
        if self.alloc.ref[src] <= 1:
            return
        if self.cow_stash[slot]:
            dst = self.cow_stash[slot].pop()
        else:
            need = 1 - self.alloc.available()
            if need > 0 and self.prefix is not None:
                self.prefix.evict(need, self.alloc)
            dst = self.alloc.alloc(1)[0]
        self.cache = self._copy_page(self.cache,
                                     tracing.to_device(np.int32(src)),
                                     tracing.to_device(np.int32(dst)))
        self.slot_pages[slot][idx] = dst
        self.table[slot, idx] = dst
        self.alloc.release([src])
        self.cow_copies += 1
        self.check_page_accounting()

    def prepare_decode(self, slots: List[int]) -> None:
        """Copy-on-write sweep before a batched decode step: any slot
        whose next append position sits in a page with other holders
        (prefix cache or sharer slots) gets a private copy first, so the
        write can never corrupt a shared prefix."""
        for slot in slots:
            pos = int(self.lengths[slot])
            idx = pos // self.page
            if idx >= len(self.slot_pages[slot]):
                continue                 # guard: decode loop ends the req
            self._cow_page(slot, idx)

    def prepare_verify(self, slots: List[int], width: int) -> None:
        """Copy-on-write sweep before a batched verify step.  A verify
        window writes the FULL fixed-width span ``[lengths, lengths +
        width)`` — including padded rows for slots with fewer drafts —
        so every reserved page the span touches must be privately held
        before the write, not just the page under the cursor.  Pages
        beyond the reserved span are redirected to the trash page by the
        model's write clamp and need no copy; reclaimed leading pages
        sit provably below the span (window reclamation only frees pages
        wholly behind ``lengths - window``)."""
        for slot in slots:
            lo = int(self.lengths[slot]) // self.page
            hi = min((int(self.lengths[slot]) + width - 1) // self.page,
                     len(self.slot_pages[slot]) - 1)
            for idx in range(max(lo, self.reclaimed[slot]), hi + 1):
                self._cow_page(slot, idx)

    def _reclaim_slot(self, slot: int) -> int:
        """Sliding-window page reclamation (delay buffering §2.2 applied
        to the cache): once every attention layer is windowed, a page
        whose last position sits wholly behind ``lengths - window`` can
        never be read again — every later mask starts at
        ``lengths + 1 - window``.  Free it now (its table entry moves to
        the trash page, so residual masked reads stay harmless) instead of
        holding it until the request retires; queued requests admit
        against the returned pages.  Returns the number of pages freed.
        """
        if not self.window or not self.slot_pages[slot]:
            return 0
        # logical page p covers [p*page, (p+1)*page); dead iff
        # (p+1)*page <= lengths - window  (conservative by one position)
        dead = max(0, (int(self.lengths[slot]) - self.window) // self.page)
        dead = min(dead, len(self.slot_pages[slot]))
        freed = 0
        while self.reclaimed[slot] < dead:
            j = self.reclaimed[slot]
            self.alloc.release([self.slot_pages[slot][j]])
            self.table[slot, j] = 0          # -> trash page (masked reads)
            self.reclaimed[slot] += 1
            freed += 1
        if freed:
            self.pages_reclaimed += freed
            self.check_page_accounting()
        return freed

    def _reset_scales(self, pages: List[int]) -> None:
        """Allocator ``on_alloc`` hook: zero the scale rows of every page
        the allocator just handed out (see ``_reset_page_scales``)."""
        self.cache = _reset_page_scales(
            self.cache, tracing.to_device(np.asarray(pages, np.int32)))

    def held_pages(self) -> int:
        """Physical pages with at least one holder (excl. trash page 0).
        A page shared by several slots and/or the prefix trie counts
        once — holders are tracked by the allocator's refcounts."""
        return self.alloc.held()

    def kv_bytes_resident(self) -> int:
        """Bytes of KV pool held by live pages, at the ACTIVE storage
        dtype (pools + scale rows): the byte-denominated residency that
        makes fp32/bf16/int8 serving directly comparable — int8 halves
        bf16's per-page cost and quarters fp32's, minus the small scale
        overhead."""
        return self.held_pages() * self._page_bytes

    def check_page_accounting(self) -> None:
        """Invariant, refcount-aware: every page is either free, held
        (refcount > 0), or the trash page — and the total reference count
        equals the number of holders we can name: live slot bindings
        (shared pages counted once per sharing slot), reserved
        copy-on-write pages, and prefix-trie nodes.  Sharing, CoW,
        reclamation, and recycling must never leak or double-free."""
        held = self.held_pages()
        free = self.alloc.available()
        assert held + free + 1 == self.alloc.total, (
            f"page accounting broken: held={held} free={free} "
            f"trash=1 != total={self.alloc.total}")
        expected = (sum(len(p) - r for p, r in zip(self.slot_pages,
                                                   self.reclaimed))
                    + sum(len(s) for s in self.cow_stash)
                    + (self.prefix.n_pages() if self.prefix else 0))
        refs = sum(self.alloc.ref[1:])
        assert refs == expected, (
            f"refcount accounting broken: sum(ref)={refs} != "
            f"slot bindings + cow stash + trie = {expected}")
        # post-rollback cursor sanity: speculative verify may write past
        # ``lengths`` and then roll back by NOT advancing it, so check the
        # cursor itself stayed inside the slot's live binding: at or below
        # the reserved span, at or above the reclaimed frontier
        for slot, r in enumerate(self.active):
            if r is None:
                continue
            ln = int(self.lengths[slot])
            span = len(self.slot_pages[slot]) * self.page
            assert ln <= span, (
                f"slot {slot} cursor {ln} past reserved span {span}")
            assert ln >= self.reclaimed[slot] * self.page, (
                f"slot {slot} cursor {ln} behind reclaimed frontier "
                f"{self.reclaimed[slot] * self.page}")
        # quantized pools: every int8 pages leaf must carry a companion
        # scale leaf sized to the same pool — scales are allocated with
        # their pages and recycled with them (reset via on_alloc), so a
        # missing or mis-sized scale buffer means a leak in that lockstep
        self._check_scale_lockstep()

    def _check_scale_lockstep(self) -> None:
        def walk(node):
            if isinstance(node, list):
                for v in node:
                    walk(v)
                return
            if not isinstance(node, dict):
                return
            for k, v in node.items():
                if isinstance(v, (dict, list)):
                    walk(v)
                elif k in ("k_pages", "v_pages") and v.dtype == jnp.int8:
                    s = node.get(k[0] + "_scale")
                    assert s is not None, (
                        f"int8 pool {k} has no companion {k[0]}_scale")
                    pool = v.shape[1] if v.ndim == 5 else v.shape[0]
                    spool = s.shape[1] if s.ndim == 3 else s.shape[0]
                    assert spool == pool, (
                        f"scale pool {spool} != page pool {pool} for {k}")
        walk(self.cache)

    def _recycle(self, slot: int) -> None:
        self.alloc.release(self.slot_pages[slot][self.reclaimed[slot]:]
                           + self.cow_stash[slot])
        self.slot_pages[slot] = []
        self.cow_stash[slot] = []
        self.reclaimed[slot] = 0
        self.table[slot] = 0
        self.lengths[slot] = 0
        self.shared_tokens[slot] = 0
        self.active[slot] = None
        self.check_page_accounting()

    # --------------------------------------------------------------- decode
    def _feed_batch(self, tokens: np.ndarray,
                    lengths: np.ndarray) -> Dict[str, jax.Array]:
        batch = {"tokens": tracing.to_device(tokens)[:, None]}
        if self.model.cfg.mrope_sections:
            batch["positions"] = jnp.broadcast_to(
                tracing.to_device(lengths)[:, None, None],
                (self.slots, 1, len(self.model.cfg.mrope_sections))
            ).astype(jnp.int32)
        return batch

    def step(self, tokens: np.ndarray, view=None) -> np.ndarray:
        """One batched ragged decode step: every active slot advances at
        its own length; inactive slots ride along masked (trash page).

        ``view`` = (lengths, table) overrides the scheduler's canonical
        arrays — the continuous engine masks mid-prefill slots to zero
        length and the trash page so their ride-along writes are inert.
        """
        lengths, table = view if view is not None \
            else (self.lengths, self.table)
        with tracing.span("decode.launch"):
            logits, self.cache = self._decode(
                self.params, self.cache, self._feed_batch(tokens, lengths),
                tracing.to_device(np.int32(0)),
                (tracing.to_device(lengths), tracing.to_device(table)))
            self.decode_steps += 1
            self.decode_tokens += int(np.count_nonzero(lengths))
            nxt = jnp.argmax(logits, axis=-1)
        with tracing.span("decode.wait"):
            return tracing.to_host(nxt)

    # --------------------------------------------------- speculative decoding
    def draft_for(self, drafter, slots: List[int]) -> Dict[int, List[int]]:
        """Propose draft tokens for the given active slots from their
        prompt + emitted histories, clamped so the accepted prefix plus
        bonus token can never step past the request's token budget, the
        context wall, or the slot's reserved pages (the clamp is what
        keeps rollback free: every REAL window write stays inside pages
        the slot already holds)."""
        hists = [list(self.active[i].prompt) + list(self.active[i].out)
                 for i in slots]
        proposals = drafter.propose(hists)
        drafts: Dict[int, List[int]] = {}
        for i, ks in zip(slots, proposals):
            r = self.active[i]
            cap = min(len(r.prompt) + r.max_new, self.max_len,
                      len(self.slot_pages[i]) * self.page)
            k = max(0, min(len(ks), drafter.max_draft,
                           cap - int(self.lengths[i]) - 1,
                           r.max_new - len(r.out) - 1))
            drafts[i] = [int(t) for t in ks[:k]]
        return drafts

    def verify_step(self, tokens: np.ndarray, view=None) -> np.ndarray:
        """One batched verify forward: every slot scores a fixed-width
        window ``[last_emitted, d1..d_{W-1}]`` starting at its own length
        through the ragged multi-token ``prefill_attention`` op (mid-page
        starts are legal: the mask is pure position arithmetic).  Returns
        the greedy argmax at EVERY window row — row t is the target's
        prediction for the token after position ``lengths + t``.  The
        forward ingests all W candidate K/V into the paged pool;
        rejecting a suffix costs nothing, the HOST just never advances
        ``lengths`` over it (the stale payload — and any int8
        running-max scale growth it caused — stays masked behind every
        later ``kpos < length`` read)."""
        if self._verify is None:
            raise RuntimeError(
                "speculative verify is not supported under --mesh "
                "tensor parallelism (no sharded verify twin yet); "
                "run unsharded or drop --speculate")
        lengths, table = view if view is not None \
            else (self.lengths, self.table)
        with tracing.span("verify.launch"):
            logits, self.cache = self._verify(
                self.params, self.cache, tracing.to_device(tokens),
                tracing.to_device(lengths), tracing.to_device(table))
            self.verify_steps += 1
            preds = jnp.argmax(logits, axis=-1)
        with tracing.span("verify.wait"):
            return tracing.to_host(preds)

    def note_spec(self, drafted: int, accepted: int, emitted: int) -> None:
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_emitted += emitted

    def run_speculative(self, requests: List[Request], drafter,
                        metrics=None) -> List[Request]:
        """Static-schedule speculative decoding: :meth:`run` with each
        decode round replaced by draft -> one fixed-width batched verify
        -> longest-correct-prefix acceptance -> host rollback.  Token
        emission replicates :meth:`run`'s per-token finish logic exactly
        (budget and context-wall checks after EVERY token), so greedy
        streams — including truncation points — are bit-identical to the
        non-speculative baseline: the bonus token of an empty acceptance
        IS the plain decode argmax."""
        from .speculative import accept_longest_prefix
        width = drafter.max_draft + 1
        queue = list(requests)
        cur = np.zeros((self.slots,), np.int32)
        for i, r in enumerate(self.active):    # resume pre-admitted slots
            if r is not None:
                cur[i] = r.out[-1]
        done: List[Request] = []
        while queue or any(r is not None for r in self.active):
            blocked = False
            for i in range(self.slots):
                while self.active[i] is None and queue and not blocked:
                    while queue and not self.admissible(queue[0]):
                        r = queue.pop(0)
                        r.done = False
                        self.rejected += 1
                        self.rejected_requests.append(r)
                        self.log(f"[paged] rejecting request {r.rid}: "
                                 f"{self._reject_reason(r)}")
                    if not queue or not self.try_admit(queue[0], i):
                        blocked = True             # wait for free pages
                        break
                    r = queue.pop(0)
                    cur[i] = r.out[-1]
                    if len(r.out) >= r.max_new:    # max_new == 1 edge
                        r.done = True
                        done.append(r)
                        self._recycle(i)
                if blocked:
                    break
            if not any(r is not None for r in self.active):
                if queue:
                    raise RuntimeError(
                        "admission deadlock: empty batch but queued "
                        "requests cannot reserve pages")
                break
            slots = [i for i, r in enumerate(self.active) if r is not None]
            drafts = self.draft_for(drafter, slots)
            self.prepare_verify(slots, width)
            toks = np.zeros((self.slots, width), np.int32)
            mask = np.zeros((self.slots,), bool)
            for i in slots:
                mask[i] = True
                toks[i, 0] = cur[i]
                toks[i, 1:1 + len(drafts[i])] = drafts[i]
            preds = self.verify_step(
                toks, view=(np.where(mask, self.lengths, 0).astype(np.int32),
                            np.where(mask[:, None], self.table, 0
                                     ).astype(np.int32)))
            for i in slots:
                r = self.active[i]
                ks = drafts[i]
                emit = accept_longest_prefix(ks, preds[i])
                accepted = len(emit) - 1
                emitted = 0
                finished = False
                for tok in emit:
                    self.lengths[i] += 1
                    r.out.append(tok)
                    cur[i] = tok
                    emitted += 1
                    if len(r.out) >= r.max_new \
                            or int(self.lengths[i]) >= self.max_len:
                        finished = True
                        break
                self.note_spec(len(ks), accepted, emitted)
                if metrics is not None:
                    metrics.on_spec_step(len(ks), accepted, emitted)
                if finished:
                    r.done = True
                    r.truncated = len(r.out) < r.max_new
                    if r.truncated:
                        self.truncated += 1
                        self.log(f"[paged] truncating request {r.rid} at "
                                 f"max_len={self.max_len} "
                                 f"({len(r.out)}/{r.max_new} tokens)")
                    done.append(r)
                    self._recycle(i)
                else:
                    self._reclaim_slot(i)
        return done

    def run(self, requests: List[Request]) -> List[Request]:
        queue = list(requests)
        cur = np.zeros((self.slots,), np.int32)
        for i, r in enumerate(self.active):    # resume pre-admitted slots
            if r is not None:
                cur[i] = r.out[-1]
        done: List[Request] = []
        while queue or any(r is not None for r in self.active):
            blocked = False
            for i in range(self.slots):
                # `while`, not `if`: a max_new == 1 request finishes right
                # out of prefill and frees its slot for the next in line
                while self.active[i] is None and queue and not blocked:
                    # reject permanently-oversized requests up front (they
                    # must not head-of-line-block servable traffic)
                    while queue and not self.admissible(queue[0]):
                        r = queue.pop(0)
                        r.done = False
                        self.rejected += 1
                        self.rejected_requests.append(r)
                        self.log(f"[paged] rejecting request {r.rid}: "
                                 f"{self._reject_reason(r)}")
                    if not queue or not self.try_admit(queue[0], i):
                        blocked = True             # wait for free pages
                        break
                    r = queue.pop(0)
                    cur[i] = r.out[-1]
                    if len(r.out) >= r.max_new:    # max_new == 1 edge
                        r.done = True
                        done.append(r)
                        self._recycle(i)
                if blocked:
                    break
            if not any(r is not None for r in self.active):
                if queue:
                    # unreachable by construction (an idle scheduler has
                    # every page free, so only inadmissible requests can
                    # fail, and those were rejected above) — defensive
                    raise RuntimeError(
                        "admission deadlock: empty batch but queued "
                        "requests cannot reserve pages")
                break
            self.prepare_decode([i for i, r in enumerate(self.active)
                                 if r is not None])
            nxt = self.step(cur)
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                self.lengths[i] += 1
                r.out.append(int(nxt[i]))
                cur[i] = nxt[i]
                if len(r.out) >= r.max_new \
                        or int(self.lengths[i]) >= self.max_len:
                    r.done = True
                    r.truncated = len(r.out) < r.max_new
                    if r.truncated:
                        self.truncated += 1
                        self.log(f"[paged] truncating request {r.rid} at "
                                 f"max_len={self.max_len} "
                                 f"({len(r.out)}/{r.max_new} tokens)")
                    done.append(r)
                    self._recycle(i)
                else:
                    self._reclaim_slot(i)
        return done


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--cache", default="dense", choices=("dense", "paged"),
                    help="KV-cache layout: dense rectangle or paged pool "
                         "(paged decodes through the ragged Pallas kernel)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged layout page size; 0 = pick from tuned "
                         "decode plans (fallback %d)" % DEFAULT_PAGE_SIZE)
    ap.add_argument("--total-pages", type=int, default=0,
                    help="page-pool size; 0 = full capacity "
                         "(slots x max_len); smaller oversubscribes")
    ap.add_argument("--kv-dtype", default="",
                    choices=("", "fp32", "bf16", "int8"),
                    help="paged KV pool storage dtype ('' = model compute "
                         "dtype); int8 stores symmetric-quantized pages "
                         "with per-(page, kv-head) f32 scales and the "
                         "ragged kernels dequantize at tile load")
    ap.add_argument("--weights-dtype", default="", choices=("", "int8"),
                    help="projection/MLP weight GEMMs: int8 routes through "
                         "dispatch.quantized_matmul (per-channel scales, "
                         "fused dequant, f32 accumulate)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged: share KV pages across requests with "
                         "common prompt prefixes (refcounted pages, "
                         "copy-on-write appends, prefill skipping)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="continuous loadgen: length of the common prompt "
                         "prefix sharing requests start with")
    ap.add_argument("--shared-frac", type=float, default=0.0,
                    help="continuous loadgen: fraction of requests that "
                         "carry the shared prefix (0..1)")
    ap.add_argument("--dispatch", default="auto",
                    choices=("auto", "kernels", "reference"),
                    help="kernel routing for every hot matmul/attention "
                         "(repro.kernels.dispatch)")
    ap.add_argument("--schedule", default="static",
                    choices=("static", "continuous"),
                    help="paged scheduling: static run-to-completion or "
                         "continuous batching on a virtual arrival clock")
    ap.add_argument("--speculate", default="", choices=("", "ngram", "model"),
                    help="paged: speculative decoding drafter — 'ngram' "
                         "(model-free suffix matching over emitted tokens) "
                         "or 'model' (truncated-sibling draft model sharing "
                         "the target's leading layers); draft tokens are "
                         "verified in one fixed-width batched forward "
                         "through the ragged prefill_attention op and "
                         "rejected suffixes rolled back host-side")
    ap.add_argument("--draft-tokens", type=int, default=3,
                    help="speculative: max draft tokens per verify window "
                         "(window width = draft_tokens + 1)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="continuous: max tokens composed per iteration "
                         "(0 = slots x page_size)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="continuous: Poisson arrival rate in requests "
                         "per clock unit (0 = burst at t=0)")
    ap.add_argument("--clock", default="wall", choices=("wall", "tick"),
                    help="continuous: virtual clock advances by measured "
                         "step wall time or a fixed tick")
    ap.add_argument("--tick", type=float, default=1.0,
                    help="continuous: clock increment per iteration in "
                         "tick mode")
    ap.add_argument("--seed", type=int, default=0,
                    help="load-generator seed (arrivals + prompt tokens)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="tensor-parallel degree: shard attention heads "
                         "and KV page pools over an N-device ('model',) "
                         "mesh (launch/mesh.make_serving_mesh). 0 = "
                         "unsharded; 1 = degenerate mesh (bit-identical "
                         "streams); N >= 2 needs N visible devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to simulate on CPU)")
    args = ap.parse_args(argv)

    from ..kernels import dispatch
    from ..runtime.compile_cache import enable_compile_cache
    from ..tune.cache import preload as preload_tuned
    print(f"[compile-cache] {enable_compile_cache()}")
    preload_tuned(log=print)
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, dispatch=args.dispatch,
                              kv_cache=args.cache,
                              kv_page_size=args.page_size,
                              kv_dtype=args.kv_dtype,
                              weights_dtype=args.weights_dtype)
    print(f"[dispatch] policy={args.dispatch}")
    if cfg.input_mode == "embeddings":
        raise SystemExit("serving demo drives token-mode archs")
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    mesh = None
    if args.mesh:
        if args.cache != "paged":
            raise SystemExit("--mesh requires --cache paged")
        from .mesh import make_serving_mesh
        mesh = make_serving_mesh(args.mesh)
        print(f"[mesh] model={args.mesh} "
              f"devices={len(jax.devices())} visible "
              f"(backend={jax.default_backend()})")
    drafter = None
    if args.speculate:
        if args.cache != "paged":
            raise SystemExit("--speculate requires --cache paged")
        if mesh is not None:
            raise SystemExit("--speculate is not supported with --mesh "
                             "(no sharded verify twin yet)")
        from .speculative import make_drafter
        # same rng key as the target params: the truncated-sibling draft
        # model's layers are then bit-identical to the target's leading
        # layers (early-exit drafting), which is what buys real acceptance
        drafter = make_drafter(args.speculate, cfg,
                               max_draft=args.draft_tokens,
                               dt=DtypePolicy(param=jnp.bfloat16),
                               rng_key=jax.random.key(0),
                               pad_to=args.max_len + args.draft_tokens,
                               batch_pad=args.slots)
        print(f"[spec] drafter={args.speculate} "
              f"draft_tokens={args.draft_tokens}")
    if args.cache == "paged":
        server = PagedScheduler(model, params, slots=args.slots,
                                max_len=args.max_len,
                                page_size=args.page_size,
                                total_pages=args.total_pages,
                                prefix_cache=args.prefix_cache,
                                mesh=mesh)
        print(f"[paged] page_size={server.page} "
              f"pool={server.alloc.total} pages "
              f"({server.n_slot_pages}/slot max, "
              f"kv_dtype={args.kv_dtype or 'compute'}, "
              f"page_bytes={server._page_bytes}, "
              f"prefix_cache={'on' if args.prefix_cache else 'off'}, "
              f"tp={server.tp})")
    else:
        server = Server(model, params, slots=args.slots,
                        max_len=args.max_len)

    if args.schedule == "continuous":
        if args.cache != "paged":
            raise SystemExit("--schedule continuous requires --cache paged")
        from .engine import ContinuousEngine
        from .loadgen import poisson_stream
        reqs = poisson_stream(args.requests, rate=args.rate,
                              vocab_size=cfg.vocab_size,
                              prompt_len=args.prompt_len,
                              max_new=args.max_new, seed=args.seed,
                              shared_prefix_len=args.shared_prefix_len,
                              shared_frac=args.shared_frac)
        engine = ContinuousEngine(server, token_budget=args.token_budget,
                                  clock=args.clock, tick=args.tick,
                                  drafter=drafter)
        # route counters tick at trace time, so reset BEFORE warmup: the
        # warmup compiles (every prefill width + masked decode) are exactly
        # the routes the run then executes from cache
        dispatch.reset_stats()
        engine.warmup()
        spans_before = tracing.totals()
        t0 = time.time()
        done = engine.run(reqs)
        dt = time.time() - t0
        s = engine.metrics.summary()
        total_new = sum(len(r.out) for r in done)
        print(f"served {len(done)} requests, {total_new} new tokens "
              f"in {dt:.2f}s ({total_new/dt:.1f} tok/s, {args.slots} "
              f"slots, schedule=continuous, "
              f"budget={engine.policy.token_budget})")
        print(f"[engine] iterations={engine.iterations} "
              f"max_prefill_batch={engine.executor.max_prefill_batch} "
              f"rejected={server.rejected}")
        print("[spans] mean ms (calls): " + " ".join(
            f"{k}={t.ns / t.calls * 1e-6:.3f}({t.calls})"
            for k, t in tracing.since(spans_before).items()))
        fmt = lambda v: "n/a" if v is None else f"{v:.4f}"
        print(f"[engine] ttft p50={fmt(s['ttft_p50'])} "
              f"p99={fmt(s['ttft_p99'])}  tok_latency "
              f"p50={fmt(s['tok_latency_p50'])} "
              f"p99={fmt(s['tok_latency_p99'])} ({args.clock} clock)")
    else:
        if args.shared_prefix_len > args.prompt_len:
            raise SystemExit("--shared-prefix-len exceeds --prompt-len")
        rng = np.random.default_rng(args.seed)
        prefix = (rng.integers(0, cfg.vocab_size, args.shared_prefix_len)
                  if args.shared_prefix_len > 0 else None)
        reqs = []
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size, args.prompt_len)
            if prefix is not None and float(rng.random()) < args.shared_frac:
                prompt = np.concatenate([prefix, prompt[len(prefix):]])
            reqs.append(Request(i, prompt, args.max_new))
        dispatch.reset_stats()
        t0 = time.time()
        done = (server.run_speculative(reqs, drafter) if drafter is not None
                else server.run(reqs))
        dt = time.time() - t0
        total_new = sum(len(r.out) for r in done)
        print(f"served {len(done)} requests, {total_new} new tokens "
              f"in {dt:.2f}s ({total_new/dt:.1f} tok/s, {args.slots} "
              f"slots, cache={args.cache})")
    if args.cache == "paged" and server.window:
        print(f"[paged] reclaimed {server.pages_reclaimed} window-dead "
              f"page(s) (window={server.window})")
    if args.speculate and server.verify_steps:
        rate = (server.spec_accepted / server.spec_drafted
                if server.spec_drafted else 0.0)
        print(f"[spec] verify_steps={server.verify_steps} "
              f"drafted={server.spec_drafted} "
              f"accepted={server.spec_accepted} "
              f"accept_rate={rate:.3f} emitted={server.spec_emitted} "
              f"tokens_per_step="
              f"{server.spec_emitted / server.verify_steps:.2f}")
    if args.cache == "paged":
        if server.truncated or server.rejected:
            print(f"[paged] truncated={server.truncated} "
                  f"rejected={server.rejected}")
        if server.prefix is not None:
            print(f"[prefix] hits={server.prefix.hits} "
                  f"misses={server.prefix.misses} "
                  f"shared_tokens={server.shared_tokens_total} "
                  f"cow_copies={server.cow_copies} "
                  f"evictions={server.prefix.evictions} "
                  f"cached_pages={server.prefix.n_pages()}")
    routes = dispatch.stats()
    for (op, route), n in sorted(routes.items()):
        print(f"[dispatch] {op:>16s} -> {route:<9s} x{n}")
    for r in done[:4]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    return done


if __name__ == "__main__":
    main()
