"""Serving engine, split into Scheduler (policy) + Runtime (this class).

The paper's runtime-programmability story (§IV-C) taken to its serving
conclusion: the Runtime owns exactly **two** hot executables —

  * one **fixed-shape chunked-prefill step** (``transformer.prefill_chunk``:
    ``chunk`` tokens of one slot, at a runtime offset, written straight
    into the slot's rows/pages of the batched caches), and
  * one **decode step** (batch = ``n_slots``, the synthesis-time maximum)
    — or, with ``speculative=True``, one fixed-width **verify step**
    (``transformer.verify_step``, width ``draft_k + 1``) that replaces it:
    a host-side prompt-lookup drafter proposes tokens, the verify forward
    scores all of them at once, and the engine accepts the longest
    matching prefix (token-identical to plain decode; see docs/serving.md),

so compilation count is O(1) for *any* prompt-length mix — no pow-2
prefill-bucket family, no per-length executables for recurrent
architectures.  Everything that varies per request — slot, offset, chunk
fill, lengths, page tables, sampling params — arrives as plain integer
operands: the TPU analogue of "reprogram the µB's loop bounds, never
re-synthesise".

All *policy* — admission, the per-step token budget, chunked-prefill
interleaving with decode, youngest-first preemption, fairness accounting —
lives in the pure-python :class:`~repro.serve.scheduler.Scheduler`.  Each
:meth:`step` executes one :class:`~repro.serve.scheduler.StepPlan`:
budgeted prefill chunks first, then one batched decode across the
decoding slots.  A long prompt thus prefills between other requests'
decode steps (no head-of-line blocking), and prompts are no longer
limited to what one prefill call can hold — only by cache capacity
(``max_seq``).

``prefill_mode="monolithic"`` keeps the legacy whole-prompt-at-admission
path (pow-2 bucketed, exact-length for recurrent archs) as the
comparison baseline for parity tests and benchmarks.

KV-cache layout remains a config switch (``cache_kind``): ``"contiguous"``
per-slot stripes or the ``"paged"`` shared pool with host-side
:class:`~repro.serve.paged.PageAllocator` admission control (see
docs/serving.md and serve/paged.py).  ``prefix_cache=True`` (paged +
chunked only) additionally aliases identical full prompt blocks across
requests through the allocator's refcounted content-hash index — admission
maps cached blocks straight into the page table and prefill starts at the
first uncached token; retirement publishes the request's prompt blocks
onto the cached-free LRU.  Architectures with per-slot recurrent or ring
state fall back to cold prefill (``prefix_cache_active`` False).

Observability is one injectable seam: pass
``observer=repro.obs.Observer(...)`` and every layer — engine step
phases, scheduler queues, page allocator, drafter — reports into its
metrics registry, and the engine opens its ``engine.*`` spans through the
Observer's span factory (``jax.profiler.TraceAnnotation``: the profiler's
timeline), host-side only and token-identical to the un-observed engine
(docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, LOCAL_ATTN, ModelConfig
from repro.core.famous import FamousConfig
from repro.core.flexible import next_pow2
from repro.models import transformer
from repro.obs.runtime import NULL_OBSERVER
from repro.obs.trace import now as _clock
from repro.parallel import sharding as shardlib
from repro.serve import sampling
from repro.serve.draft import PromptLookupDrafter
from repro.serve.paged import (PageAllocator, PagedCacheConfig,
                               PagePoolExhausted, block_hashes)
from repro.serve.scheduler import (DECODE, FREE, PREFILL, Scheduler,
                                   SchedulerConfig)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: list
    max_new: int = 16
    # per-request sampling params: temperature <= 0 -> greedy (default);
    # top_k == 0 -> full-vocab; seeded runs are reproducible regardless of
    # batch composition / slot placement (see serve/sampling.py).  seed=None
    # falls back to the request id, so unseeded sampling requests draw
    # *different* noise instead of all sharing seed 0.
    temperature: float = 0.0
    top_k: int = 0
    seed: Optional[int] = None
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None  # set when the page pool can never back it
    # wall-clock marks for TTFT/TPOT accounting, set by the engine from the
    # single monotonic clock source (repro.obs.trace.now — the repo's one
    # time.perf_counter call site).  t_admit is the FIRST admission (a
    # re-admission after preemption keeps it): TTFT = queue wait
    # (t_admit - t_submit) + admission to first token (t_first - t_admit)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


def _named(fn, **bound):
    """``fn`` with ``bound`` fixed, under ``fn``'s name: jit names the
    executable, and the profiler its launches, ``jit_<name>`` (a bare
    partial would be ``jit__unknown``)."""
    f = functools.partial(fn, **bound)
    f.__name__ = fn.__name__
    return f


def _jit_cache_size(fn) -> int:
    """Executables compiled for one jitted function.  Reads jax's own
    counter and lets any failure raise: a census that silently returned a
    placeholder would pass every ``<= N`` check."""
    return fn._cache_size()


class ServingEngine:
    """The Runtime: executes the Scheduler's plans against device state."""

    def __init__(self, params, cfg: ModelConfig, fcfg: FamousConfig,
                 n_slots: int = 4, max_seq: int = 256, dtype=jnp.float32,
                 cache_kind: str = "contiguous", page_size: int = 16,
                 n_pages: Optional[int] = None,
                 prefill_mode: str = "chunked", chunk: int = 32,
                 token_budget: int = 0, prefix_cache: bool = False,
                 speculative: bool = False, draft_k: int = 4,
                 drafter=None, kv_dtype: str = "fp",
                 mesh=None, sharding_rules=None, observer=None):
        """``observer``: optional :class:`repro.obs.runtime.Observer` —
        the one injectable seam every layer (engine, scheduler, page
        allocator, drafter) reports to: TTFT/TPOT histograms, queue
        depth, pool utilisation, prefix/speculation counters, the
        executable census, and (when it has a span factory) the engine's
        ``engine.*`` spans.  ``None`` resolves to the no-op
        :data:`~repro.obs.runtime.NULL_OBSERVER`; an enabled observer
        keeps serving token-identical and adds zero device syncs (all
        hooks take host ints — see docs/observability.md).

        ``mesh``: optional :class:`jax.sharding.Mesh` (see
        ``launch.mesh.make_serving_mesh``) — params and caches are placed
        with NamedShardings (tensor parallelism over attention heads /
        kv heads / FFN hidden on the "model" axis; ``sharding_rules``
        overrides :data:`repro.parallel.sharding.SERVE_TP_RULES`) and the
        hot executables pin their outputs with ``out_shardings`` so caches
        never migrate between steps.  Logits stay replicated, so sampling
        and the host bookkeeping loop are untouched.  ``mesh=None`` (the
        default) is the unsharded single-device baseline, bit-identical to
        the pre-mesh engine."""
        assert cache_kind in ("contiguous", "paged"), cache_kind
        assert prefill_mode in ("chunked", "monolithic"), prefill_mode
        assert kv_dtype in ("fp", "int8"), kv_dtype
        self.obs = observer if observer is not None else NULL_OBSERVER
        if kv_dtype == "int8":
            assert cache_kind == "paged", "kv_dtype='int8' requires paged cache"
        self.params = params
        self.cfg = cfg
        self.fcfg = fcfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.dtype = dtype
        self.cache_kind = cache_kind
        self.kv_dtype = kv_dtype
        self.paged = cache_kind == "paged"
        self.chunked = prefill_mode == "chunked"
        self.chunk = min(chunk, max_seq)
        if self.chunked:
            # pads stay inside the cache (positions < ceil(target/C)*C <=
            # max_seq) and the wkv6 chunked form needs S % min(64, S) == 0
            assert max_seq % self.chunk == 0, (max_seq, self.chunk)
            assert self.chunk <= 64 or self.chunk % 64 == 0, self.chunk
        # -- speculative decoding -------------------------------------------
        # The verify step writes K/V at positions [cache_len, cache_len+W)
        # and rolls back *by bookkeeping only* — rejected positions hold
        # junk that is causally masked and overwritten before it is ever
        # read.  That rollback-for-free argument needs position-addressed
        # storage: sliding-window rings overwrite their OLDEST entries and
        # recurrent state cannot rewind, so (like the prefix cache) only
        # all-global-ATTN stacks run speculatively; other archs fall back
        # to plain decode explicitly (`speculative_active` False).
        assert draft_k >= 1, draft_k
        self.draft_k = draft_k
        all_attn = all(
            k == ATTN for k in tuple(cfg.pattern_unit) + tuple(cfg.tail_layers))
        self.speculative_active = speculative and all_attn
        self.drafter = drafter if drafter is not None else \
            PromptLookupDrafter(observer=self.obs)
        self.spec_steps = 0      # verify steps executed
        self.spec_drafted = 0    # draft tokens proposed to the verifier
        self.spec_accepted = 0   # draft tokens accepted (bonus excluded)
        self.sched = Scheduler(n_slots, SchedulerConfig(
            chunk=self.chunk, token_budget=token_budget,
            decode_width=(draft_k + 1) if self.speculative_active else 1),
            observer=self.obs)
        if self.paged:
            assert max_seq % page_size == 0, (max_seq, page_size)
            if n_pages is None:  # drop-in capacity; pass n_pages to oversubscribe
                n_pages = PagedCacheConfig.default_pool(n_slots, max_seq,
                                                        page_size)
            self.pcfg = PagedCacheConfig(page_size=page_size, n_pages=n_pages)
            self.alloc = PageAllocator(self.pcfg, n_slots, max_seq,
                                       observer=self.obs)
            self.caches = transformer.make_caches(
                cfg, n_slots, max_seq, dtype, cache_kind="paged",
                page_size=page_size, n_pages=n_pages, kv_dtype=kv_dtype)
        else:
            self.caches = transformer.make_caches(cfg, n_slots, max_seq, dtype)
        # -- mesh placement -------------------------------------------------
        # Params and caches are committed to their NamedShardings once, here;
        # the executables below pin cache (and logits) outputs with
        # out_shardings so the placement is a fixed point of every step —
        # GSPMD inserts the only collectives (attention-output + FFN-down
        # all-reduces) inside the steps.  Host-side state (allocator, page
        # tables, cache_len/last_token numpy, scheduler) is device-agnostic:
        # page ids address whole (kv, page_size, dh) pages whose kv dim is
        # what actually shards, so one copy serves every device.
        self.mesh = mesh
        # every hot executable donates the caches it is handed, so the
        # updated caches reuse the input pool's buffers instead of a second
        # pool per step (the layer scan still stacks its new caches in one
        # pool-sized temporary)
        self._jit_kw_caches: dict = {}   # jits returning caches only
        self._jit_kw_logits: dict = {}   # jits returning (logits, caches)
        if mesh is not None:
            rules = sharding_rules or shardlib.SERVE_TP_RULES
            # Pallas launches are shard_mapped over the mesh axis these
            # rules give the heads; a kernel holds a query head's kv head,
            # so both head kinds must split the same way
            heads, kv_heads = (shardlib.spec_for_axes(mesh, (a,), rules)[0]
                               for a in ("heads", "kv_heads"))
            if fcfg.impl == "pallas" and heads != kv_heads:
                raise ValueError(
                    f"impl='pallas' needs heads and kv_heads on one mesh "
                    f"axis; the sharding rules give {heads!r} and "
                    f"{kv_heads!r}")
            self.fcfg = fcfg = dataclasses.replace(fcfg, mesh=mesh,
                                                   head_axis=heads)
            self.params = jax.device_put(
                self.params,
                shardlib.tree_shardings(mesh, transformer.param_axes(cfg),
                                        rules, self.params))
            cshard = shardlib.tree_shardings(
                mesh, transformer.cache_axes(cfg, cache_kind, kv_dtype),
                rules, self.caches)
            self.caches = jax.device_put(self.caches, cshard)
            repl = shardlib.replicated(mesh)
            self._jit_kw_caches = {"out_shardings": cshard}
            self._jit_kw_logits = {"out_shardings": (repl, cshard)}
        # -- prefix cache ---------------------------------------------------
        # Aliasing cached prompt blocks requires (a) paged storage, (b) a
        # chunked prefill that can start at the first uncached token, and
        # (c) an architecture whose *entire* prefix state lives in the page
        # pool.  Sliding-window rings and recurrent state (RG-LRU, RWKV) are
        # per-slot and not content-addressable, so hybrid/recurrent patterns
        # fall back to cold prefill explicitly (`prefix_cache_active` False).
        if prefix_cache:
            assert cache_kind == "paged", "prefix_cache requires paged cache"
            assert prefill_mode == "chunked", \
                "prefix_cache requires chunked prefill (runtime offsets)"
        self.prefix_shareable = all(
            k == ATTN for k in tuple(cfg.pattern_unit) + tuple(cfg.tail_layers))
        self.prefix_cache_active = prefix_cache and self.prefix_shareable
        self.prefix_lookups = 0        # admissions that probed the index
        self.prefix_hit_pages = 0      # pages aliased instead of allocated
        self.prefix_hit_tokens = 0     # tokens whose prefill was skipped
        self._slot_hashes: list[Optional[list]] = [None] * n_slots
        # per-slot sequence state lives on the HOST: slot-granular updates
        # are plain numpy writes (an eager jnp ``.at[].set`` costs a full
        # dispatch each, ~1.3 ms on CPU — more than a tiny-model forward)
        # and the arrays are materialized on device once per launch as
        # ordinary decode-step operands (repro.analysis lint rule RA002)
        self.cache_len = np.zeros((n_slots,), np.int32)
        self.last_token = np.zeros((n_slots,), np.int32)
        self._slot_seq: list[Optional[list]] = [None] * n_slots
        self._failed: list[Request] = []
        self._pt_version = -1          # device page-table cache key
        self._pt_device = None
        # -- the executables ----------------------------------------------
        self._prefill_exec: dict[int, callable] = {}    # monolithic only
        self._prefill_chunk_exec = jax.jit(
            _named(transformer.prefill_chunk, cfg=cfg, fcfg=fcfg),
            donate_argnums=2, **self._jit_kw_caches)
        self._decode = jax.jit(
            _named(transformer.decode_step, cfg=cfg, fcfg=fcfg),
            donate_argnums=2, **self._jit_kw_logits)
        # the speculative path REPLACES decode with one fixed-shape verify
        # executable (batch n_slots, width draft_k+1, per-slot runtime
        # offsets): a zero-draft slot verifies as a 1-valid-token decode,
        # so the census stays at three hot executables either way
        self._verify = jax.jit(
            _named(transformer.verify_step, cfg=cfg, fcfg=fcfg),
            donate_argnums=2, **self._jit_kw_logits)
        self._clear = jax.jit(
            _named(transformer.clear_slot, cfg=cfg, paged=self.paged),
            donate_argnums=0, **self._jit_kw_caches)
        self._sample = jax.jit(sampling.sample_tokens,
                               static_argnames=("k_cap",))
        self._sample_verify = jax.jit(sampling.verify_tokens,
                                      static_argnames=("k_cap",))
        # recurrent state cannot absorb junk pad tokens -> the monolithic
        # path prefills those archs at exact length (chunked masks pads)
        self.bucketed = all(k in (ATTN, LOCAL_ATTN) for k in cfg.pattern_unit)
        # the observer pulls the executable census through this source on
        # every snapshot/exposition, so repro_engine_compilations{exec=...}
        # and `engine.compilations` can never disagree (and retrace_guard
        # accepts either as its census subject)
        self.obs.register_census(lambda: self.compilations)

    # -- compiled helpers ---------------------------------------------------
    def _prefill_fn(self, length: int):
        """Monolithic path: one executable per padded prompt length."""
        if length not in self._prefill_exec:
            def fn(params, tokens, caches, slot, page_ids):
                one = transformer.make_caches(self.cfg, 1, self.max_seq,
                                              self.dtype)
                _, one = transformer.prefill(params, tokens, one, self.cfg,
                                             self.fcfg)
                return transformer.write_prefill_to_slot(
                    caches, one, slot, self.cfg,
                    page_ids=page_ids if self.paged else None)

            self._prefill_exec[length] = jax.jit(fn, **self._jit_kw_caches)
        return self._prefill_exec[length]

    @property
    def prefill_compilations(self) -> int:
        """Compiled prefill executables: O(1) chunked, O(buckets|lengths)
        monolithic."""
        if self.chunked:
            return _jit_cache_size(self._prefill_chunk_exec)
        return len(self._prefill_exec)

    @property
    def compilations(self) -> dict:
        """Executable census (the ≤-3 acceptance check lives on this)."""
        return {
            "prefill": self.prefill_compilations,
            "decode": _jit_cache_size(self._decode),
            "verify": _jit_cache_size(self._verify),
            "clear": _jit_cache_size(self._clear),
        }

    def cache_bytes_per_device(self) -> int:
        """KV/state cache bytes resident on EACH device.  Under a TP mesh
        the kv-head (or FFN-hidden) dims are sharded, so this shrinks to
        ~1/TP of the unsharded total — the memory headroom TP buys for
        bigger models / more pages per device."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.caches):
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    @property
    def acceptance_rate(self) -> float:
        """Accepted draft tokens / proposed draft tokens (bonus excluded)."""
        return self.spec_accepted / max(self.spec_drafted, 1)

    @property
    def accepted_per_step(self) -> float:
        """Mean tokens emitted per verify step (1.0 = plain-decode pace)."""
        return ((self.spec_steps + self.spec_accepted)
                / max(self.spec_steps, 1))

    @property
    def slot_req(self) -> list:
        """Requests by slot (None = free) — scheduler state, read-only."""
        return [None if s.state == FREE else s.req for s in self.sched.slots]

    def _page_table(self):
        """Device copy of the page table, re-uploaded only when the
        allocator actually mutated (steady-state decode re-uses it)."""
        if self._pt_version != self.alloc.version:
            self._pt_device = jnp.asarray(self.alloc.page_table)
            self._pt_version = self.alloc.version
        return self._pt_device

    # -- admission ------------------------------------------------------------
    def _prefix_hashes(self, req: Request, n: int):
        """(prompt-block hashes, lookup cap) for an admission of total
        sequence length ``n``.  Only *full* prompt blocks are shareable, and
        only blocks fully inside the first ``n - 1`` tokens may be aliased:
        decode restarts at token ``n - 1`` and writes its K/V, so the page
        holding position ``n - 1`` must always be private (the COW rule —
        the partial last block is prefilled into a fresh page, never
        copied).  Memoized on the request: a request deferred at the queue
        head is probed by ``_admissible`` every step, and its prompt only
        needs hashing once."""
        ps = self.pcfg.page_size
        cached = getattr(req, "_block_hashes", None)
        if cached is None or cached[0] != ps:
            cached = (ps, block_hashes(req.tokens, ps))
            req._block_hashes = cached
        hashes = cached[1]
        return hashes, min(len(hashes), (n - 1) // ps)

    def add_request(self, req: Request) -> int:
        """Admit a request into a free slot.  Paged mode reserves the full
        sequence's prompt pages first; on :class:`PagePoolExhausted` the
        engine state is untouched (clean admission control).  With the
        prefix cache active, every full prompt block that hits the index is
        aliased into the slot's page table instead of allocated+prefilled —
        the scheduler then starts chunked prefill at the first uncached
        token (the runtime-offset chunk executable needs no new compile).

        Chunked mode does **no prefill here** — the scheduler doles the
        prompt out as budget-sized chunks inside :meth:`step`, interleaved
        with everyone else's decode.  Monolithic mode prefills the whole
        prompt now (legacy comparison path).  A preempted request
        (non-empty ``req.out``) resumes identically either way: its full
        prefix (prompt + generated-so-far) is re-prefilled — minus any
        cached head — and decode continues token-identically.
        """
        slot = self.sched.free_slot()
        assert slot is not None, "no free slot"
        seq = list(req.tokens) + list(req.out)
        n = len(seq)
        assert 1 <= n <= self.max_seq
        # the span covers the prefix lookup, page growth and the bind (and
        # any launch the admission makes)
        with self.obs.span("engine.admit", rid=req.rid, slot=slot,
                           n_tokens=n) as sp:
            sp.set_metadata(cached=self._admit(req, slot, seq))
        return slot

    def _admit(self, req: Request, slot: int, seq: list) -> int:
        """:meth:`add_request`'s work; returns the tokens the prefix cache
        served."""
        n = len(seq)
        n_cached = 0
        if self.paged:
            if self.prefix_cache_active:
                hashes, cap = self._prefix_hashes(req, n)
                hits = self.alloc.lookup(hashes[:cap])
                self.prefix_lookups += 1
                self.obs.on_prefix_lookup(req.rid, len(hits),
                                          len(hits) * self.pcfg.page_size)
                if hits:
                    self.alloc.map_prefix(slot, hits)
                    n_cached = len(hits) * self.pcfg.page_size
                    self.prefix_hit_pages += len(hits)
                    self.prefix_hit_tokens += n_cached
                self._slot_hashes[slot] = hashes
            try:
                self.alloc.grow(slot, n)  # PagePoolExhausted if oversize
            except PagePoolExhausted:
                self.alloc.free(slot)     # roll back any mapped prefix
                self._slot_hashes[slot] = None
                raise
        state = self.sched.bind(slot, req, n, cached=n_cached)
        self._slot_seq[slot] = seq
        now = _clock()
        if req.t_submit is None:
            req.t_submit = now
        if req.t_admit is None:
            req.t_admit = now
        if not self.chunked and state == PREFILL:
            m = n - 1
            plen = min(next_pow2(m), self.max_seq) if self.bucketed else m
            toks = np.zeros((1, plen), np.int32)
            toks[0, :m] = seq[:m]
            page_ids = (jnp.asarray(self.alloc.page_table[slot]) if self.paged
                        else jnp.zeros((0,), jnp.int32))
            fn = self._prefill_fn(plen)
            self.caches = fn(self.params, jnp.asarray(toks), self.caches,
                             jnp.int32(slot), page_ids)
            self.sched.mark_prefilled(slot)
            state = DECODE
        if state == DECODE and self.sched.slots[slot].target == 0:
            # nothing to prefill: clear any stale per-slot state
            self.caches = self._clear(self.caches, jnp.int32(slot))
        if state == DECODE:
            # generation restarts at the last prompt token: it is re-decoded
            # so its K/V (or recurrent-state) entry lands at position n-1 —
            # always in a private page, even when everything before it was a
            # cache hit (a fully-cached prompt skips prefill entirely).
            self.cache_len[slot] = n - 1
            self.last_token[slot] = seq[-1]
        else:
            self.cache_len[slot] = n_cached
        return n_cached

    # -- preemption / page growth ---------------------------------------------
    def _preempt(self, slot: int) -> None:
        """Evict a running sequence: free its pages and queue it for
        re-admission ahead of fresh requests.  Generated tokens stay on the
        request; resuming is just a longer (chunked) prefill — no state is
        copied or swapped out.  Mid-prefill victims simply restart their
        prefill."""
        req = self.sched.preempt(slot)
        self.cache_len[slot] = 0
        self._slot_seq[slot] = None
        self._slot_hashes[slot] = None   # partial prefill: never published
        self.alloc.free(slot)
        self.sched.enqueue(req, front=True)

    def _fail_slot(self, slot: int, err: str) -> None:
        req = self.sched.release(slot)
        req.error, req.done = err, True
        req.t_done = _clock()
        self.cache_len[slot] = 0
        self._slot_seq[slot] = None
        self._slot_hashes[slot] = None
        if self.paged:
            self.alloc.free(slot)
        self.obs.on_retire(req, slot)
        self._failed.append(req)

    def _grow_active(self, active: list) -> list:
        """Reserve the next token's page for every decoding slot, preempting
        youngest-first (decoding *or* prefilling) when the pool runs dry.
        A lone sequence that cannot grow is failed rather than crashing."""
        for i in list(active):
            if i not in active:
                continue
            while True:
                try:
                    self.alloc.grow(i, int(self.cache_len[i]) + 1)
                    break
                except PagePoolExhausted as e:
                    victim = self.sched.preempt_victim()
                    if victim == i and len(self.sched.occupied()) == 1:
                        # nothing left to preempt: the pool can never back
                        # this sequence — fail it cleanly
                        self._fail_slot(i, str(e))
                        active.remove(i)
                        break
                    self._preempt(victim)
                    if victim in active:
                        active.remove(victim)
                    if victim == i:
                        break
        return active

    # -- the step -------------------------------------------------------------
    def step(self):
        """Execute one scheduler plan: budgeted prefill chunks, then one
        batched decode across the decoding slots.  Returns the requests
        that finished (or, paged mode, failed) this step."""
        with self.obs.span("engine.step", step_num=self.sched.step_count):
            finished = []
            self.obs.on_step(
                queue_depth=len(self.sched.resume) + len(self.sched.pending),
                occupied=len(self.sched.occupied()))
            plan = self.sched.plan()
            if plan.chunks:
                self._prefill_chunks(plan.chunks)
            active = list(plan.decode_slots)
            if self.speculative_active:
                self._decode_speculative(active, finished)
            else:
                self._decode_plain(active, finished)
            self.sched.tick()
        return finished

    def _prefill_chunks(self, chunks: list) -> None:
        """The plan's prefill chunks (fixed shape; one executable)."""
        pt = self._page_table() if self.paged else None
        for ch in chunks:
            with self.obs.phase("prefill_chunk", slot=ch.slot,
                                rid=self.sched.slots[ch.slot].req.rid,
                                start=ch.start, n=ch.n):
                seq = self._slot_seq[ch.slot]
                toks = np.zeros((1, self.chunk), np.int32)
                toks[0, :ch.n] = seq[ch.start:ch.start + ch.n]
                kw = {"page_table": pt} if self.paged else {}
                self.caches = self._prefill_chunk_exec(
                    self.params, jnp.asarray(toks), self.caches,
                    jnp.int32(ch.slot), jnp.int32(ch.start),
                    jnp.int32(ch.n), **kw)
            self.cache_len[ch.slot] = ch.start + ch.n
            if self.sched.on_chunk(ch.slot, ch.n):
                # prefill complete: decode restarts at the last token,
                # whose K/V entry is then written exactly once at n-1
                self.last_token[ch.slot] = seq[-1]

    def _ctx_tokens(self, active: list) -> int:
        """Cache entries the decoding slots attend over, summed (each
        slot's cache plus the token it decodes)."""
        return int(self.cache_len[active].sum()) + len(active)

    def _sampling_operands(self, active):
        """Per-slot sampling operands (host numpy, materialized once)."""
        temps = np.zeros((self.n_slots,), np.float32)
        topks = np.zeros((self.n_slots,), np.int32)
        seeds = np.zeros((self.n_slots,), np.uint32)
        idxs = np.zeros((self.n_slots,), np.int32)
        for i in active:
            r = self.sched.slots[i].req
            temps[i] = r.temperature
            topks[i] = r.top_k
            # rids/seeds may exceed 2^31 — fold, don't truncate (uint32)
            seeds[i] = sampling.fold_seed(r.rid if r.seed is None else r.seed)
            idxs[i] = len(r.out)
        return temps, topks, seeds, idxs

    def _maybe_retire(self, i: int, req: Request, now: float,
                      finished: list) -> None:
        """Release the slot when the request hit its length limits."""
        if (len(req.out) >= req.max_new
                or int(self.cache_len[i]) >= self.max_seq - 1):
            req.done = True
            req.t_done = now
            self.obs.on_retire(req, i)
            finished.append(req)
            self.sched.release(i)
            self._slot_seq[i] = None
            self.cache_len[i] = 0
            if self.paged:
                if self.prefix_cache_active and self._slot_hashes[i]:
                    # publish-on-retire: the slot's full prompt blocks
                    # (now completely written) become index entries; its
                    # pages drop to refcount 0 in free() below but stay
                    # warm on the cached-free LRU for future hits
                    self.alloc.publish(i, self._slot_hashes[i])
                self._slot_hashes[i] = None
                self.alloc.free(i)  # refcounts drop; pool or LRU

    def _decode_plain(self, active: list, finished: list) -> None:
        if self.paged and active:
            active = self._grow_active(active)
            finished.extend(self._failed)
            self._failed.clear()
        if not active:
            return
        # host numpy slot state is materialized on device here, once per
        # launch, as plain operands of the (warm) decode executable.  The
        # observer phase wraps the operand upload, dispatch AND the step's
        # one device->host sync, so the span is the true host-observed
        # decode latency; it opens only for a launch, after page growth
        # settled the slots that take part.
        with self.obs.phase("decode", slots=len(active)) as sp:
            if self.obs.spans_on:
                sp.set_metadata(ctx_tokens=self._ctx_tokens(active))
            act = np.zeros((self.n_slots,), bool)
            act[active] = True
            act_dev = jnp.asarray(act)
            kw = {"page_table": self._page_table()} if self.paged else {}
            logits, self.caches = self._decode(self.params,
                                               jnp.asarray(self.last_token),
                                               self.caches,
                                               jnp.asarray(self.cache_len),
                                               active=act_dev, **kw)
            temps, topks, seeds, idxs = self._sampling_operands(active)
            if temps.any():
                # k_cap: pow-2 roundup of the largest requested top-k, so
                # the sampler thresholds against a small static top_k
                # instead of a full-vocab sort (<= O(log V) executables)
                k_cap = next_pow2(max(int(topks.max()), 1))
                next_tok = self._sample(logits, jnp.asarray(temps),
                                        jnp.asarray(topks),
                                        jnp.asarray(seeds),
                                        jnp.asarray(idxs), k_cap=k_cap)
            else:  # all-greedy step (the default): skip the sampler's
                # top-k threshold + Gumbel draw on the hot path
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.obs.on_decode_launch()
            with self.obs.span("engine.sync"):
                toks = np.asarray(next_tok)   # the step's ONE host sync
        with self.obs.span("engine.retire") as sp:
            n0 = len(finished)
            self.cache_len[act] += 1
            self.last_token[act] = toks[act]
            self.obs.on_tokens(len(active))
            now = _clock()
            for i in active:
                req = self.sched.slots[i].req
                req.out.append(int(toks[i]))
                if req.t_first is None:
                    req.t_first = now
                self.sched.on_decode_token(i)
                self._maybe_retire(i, req, now, finished)
            sp.set_metadata(n_finished=len(finished) - n0)

    # -- speculative decode ---------------------------------------------------
    def _draft_for(self, i: int) -> list:
        """The slot's draft, capped so a full accept can neither overshoot
        ``max_new`` nor run ``cache_len`` past the ``max_seq - 1`` retire
        line.  Drafting is pure host policy over prompt + generated
        history; its failures are *per-request* (caught by the caller)."""
        req = self.sched.slots[i].req
        room = min(self.draft_k,
                   req.max_new - len(req.out) - 1,
                   self.max_seq - 2 - int(self.cache_len[i]))
        if room <= 0:
            return []
        seq = list(req.tokens) + list(req.out)
        return [int(t) for t in self.drafter.draft(seq, room)][:room]

    def _decode_speculative(self, active: list, finished: list) -> None:
        """One verify step: draft on the host, verify all slots' drafts in
        ONE fixed-shape forward (width ``draft_k + 1``), accept each
        slot's longest matching prefix plus the model's bonus/correction
        token, and roll back the rest by bookkeeping (contiguous: junk
        K/V past ``cache_len`` is masked/overwritten; paged: tail pages
        grown for rejected tokens shrink back to the pool)."""
        W = self.draft_k + 1
        drafts: dict[int, list] = {}
        for i in list(active):
            try:
                drafts[i] = self._draft_for(i)
            except Exception as e:   # a poisoned request fails alone
                self._fail_slot(i, f"drafter failed: {type(e).__name__}: {e}")
                active.remove(i)
        if self.paged and active:
            # baseline growth (next token's page) keeps plain-decode
            # semantics: preempt youngest-first, fail a lone un-backable
            # sequence.  Draft pages on top are OPPORTUNISTIC — a draft is
            # never worth preempting a live sequence for, so on exhaustion
            # the draft is dropped and the slot verifies as plain decode.
            active = self._grow_active(active)
            for i in list(active):
                d = drafts.get(i, [])
                if not d:
                    continue
                try:
                    self.alloc.grow(i, int(self.cache_len[i]) + 1 + len(d))
                except PagePoolExhausted:
                    drafts[i] = []
        finished.extend(self._failed)
        self._failed.clear()
        if not active:
            return
        toks = np.zeros((self.n_slots, W), np.int32)
        for i in active:
            toks[i, 0] = self.last_token[i]
            d = drafts.get(i, [])
            if d:
                toks[i, 1:1 + len(d)] = d
        kw = {"page_table": self._page_table()} if self.paged else {}
        with self.obs.phase("verify", slots=len(active),
                            drafted=sum(len(d) for d in drafts.values())
                            ) as sp:
            if self.obs.spans_on:
                sp.set_metadata(ctx_tokens=self._ctx_tokens(active))
            logits, self.caches = self._verify(self.params,
                                               jnp.asarray(toks),
                                               self.caches,
                                               jnp.asarray(self.cache_len),
                                               **kw)
            temps, topks, seeds, idxs = self._sampling_operands(active)
            if temps.any():
                k_cap = next_pow2(max(int(topks.max()), 1))
                cand = self._sample_verify(logits, jnp.asarray(temps),
                                           jnp.asarray(topks),
                                           jnp.asarray(seeds),
                                           jnp.asarray(idxs), k_cap=k_cap)
            else:
                cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.obs.on_decode_launch()
            with self.obs.span("engine.sync"):
                cand = np.asarray(cand)   # (n_slots, W); the ONE host sync
        with self.obs.span("engine.retire") as sp:
            n0 = len(finished)
            self._accept_drafts(active, drafts, cand, finished)
            sp.set_metadata(n_finished=len(finished) - n0)

    def _accept_drafts(self, active: list, drafts: dict, cand,
                       finished: list) -> None:
        """Each slot's longest verified draft prefix plus the model's
        bonus/correction token; the rest rolled back by bookkeeping."""
        now = _clock()
        self.spec_steps += 1
        self.obs.on_spec_step()
        for i in active:
            req = self.sched.slots[i].req
            d = drafts.get(i, [])
            # cand[i, j] is the token sequential decode would emit at
            # output index idxs[i]+j given the draft prefix d[:j]; draft
            # token j survives iff it predicted exactly that.  The first
            # mismatch position contributes the model's own token (the
            # bonus/correction), so every step emits 1..W tokens and the
            # stream equals plain decode's token for token.
            n_acc = 1
            for j, dt in enumerate(d):
                if int(cand[i, j]) != dt:
                    break
                n_acc += 1
            emitted = [int(t) for t in cand[i, :n_acc]]
            self.spec_drafted += len(d)
            self.spec_accepted += n_acc - 1
            self.obs.on_draft_verified(req.rid, len(d), n_acc - 1)
            self.obs.on_tokens(n_acc)
            self.sched.on_draft(i, len(d), n_acc - 1)
            self.cache_len[i] += n_acc
            self.last_token[i] = emitted[-1]
            req.out.extend(emitted)
            if req.t_first is None:
                req.t_first = now
            for _ in range(n_acc):
                self.sched.on_decode_token(i)
            if self.paged:
                # rollback: return the pages grown for rejected draft
                # tokens (a draft cut at a page boundary must not leak)
                self.alloc.shrink(i, int(self.cache_len[i]))
            self._maybe_retire(i, req, now, finished)

    # -- admission control ----------------------------------------------------
    def _admissible(self, req: Request) -> bool:
        """Paged admission control: admit only if the sequence's pages are
        free right now (retiring sequences release pages continuously, so
        deferred requests drain from the pending queue).  Raises
        :class:`PagePoolExhausted` for requests no pool state could ever
        admit."""
        if not self.paged:
            return True
        n = len(req.tokens) + len(req.out)
        if n > self.max_seq:
            raise PagePoolExhausted(
                f"request {req.rid} length {n} exceeds max_seq "
                f"{self.max_seq}")
        need = self.pcfg.pages_for(n)
        if need > self.pcfg.n_pages - 1:
            raise PagePoolExhausted(
                f"request {req.rid} needs {need} pages but the pool only "
                f"has {self.pcfg.n_pages - 1} allocatable")
        if self.prefix_cache_active:
            hashes, cap = self._prefix_hashes(req, n)
            return self.alloc.can_admit(n, hits=self.alloc.lookup(hashes[:cap]))
        return self.alloc.can_admit(n)

    # -- the loop -------------------------------------------------------------
    def run(self, requests: list[Request], max_steps: int = 1000):
        """Serve ``requests`` to completion.  Preempted sequences re-enter
        ahead of fresh ones; requests the pool can never back come back with
        ``req.error`` set instead of crashing the loop.  Exhausting
        ``max_steps`` returns *every* request: unfinished ones (still in a
        slot, preempted, or never admitted) come back with ``req.error``
        set, ``done=False`` and whatever ``req.out`` they produced."""
        now = _clock()
        for req in requests:
            if req.t_submit is None:
                req.t_submit = now
            self.sched.enqueue(req)
        done = []
        steps = 0
        while (self.sched.has_queued or self.sched.busy) \
                and steps < max_steps:
            while self.sched.has_queued and self.sched.free_slot() is not None:
                try:
                    if not self._admissible(self.sched.next_queued()):
                        break
                except PagePoolExhausted as e:
                    req = self.sched.pop_queued()
                    req.error, req.done = str(e), True
                    req.t_done = _clock()
                    self.obs.on_retire(req)
                    done.append(req)
                    continue
                self.add_request(self.sched.pop_queued())
            done.extend(self.step())
            steps += 1
        # max_steps exhausted with work still in flight: surface every
        # unfinished request (slot-bound, preempted-unresumed, and
        # never-admitted) with req.error set and partial req.out kept,
        # rather than letting any of them vanish from the return value.
        for slot in self.sched.occupied():
            req = self.sched.release(slot)
            self.cache_len[slot] = 0
            self._slot_seq[slot] = None
            if self.paged:
                self._slot_hashes[slot] = None
                self.alloc.free(slot)
            req.error = req.error or (
                f"evicted mid-flight at max_steps={max_steps}")
            done.append(req)
        for req in self.sched.resume:
            req.error = req.error or (
                f"preempted and not resumed within max_steps={max_steps}")
            done.append(req)
        self.sched.resume = []
        for req in self.sched.pending:
            req.error = req.error or (
                f"never admitted within max_steps={max_steps}")
            done.append(req)
        self.sched.pending = []
        now = _clock()
        for req in done:
            if req.error is not None and req.t_done is None:
                req.t_done = now   # terminal requests carry a completion mark
                self.obs.on_retire(req)
        return done
