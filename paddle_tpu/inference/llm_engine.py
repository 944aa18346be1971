"""Continuous-batching LLM serving engine with a paged KV cache.

The serving half of the framework the way `jit.TrainStep` is the
training half. The static-batch path (`GPTGenerationMixin.generate` +
the shape-bucketed `InferenceServer`) cannot admit a new request into a
running decode batch, so every mixed-length workload pays worst-case
padding and head-of-line blocking. This engine fixes both, TPU-style
(PAPERS.md "Ragged Paged Attention"; the capability the reference ships
as its analysis_predictor/serving stack):

* **Paged KV cache** — the cache is a pool of fixed-size pages
  [num_pages, page_size, heads, head_dim] per layer with per-sequence
  page tables. Pages are allocated as a sequence grows and freed the
  step it finishes, so HBM scales with LIVE TOKENS instead of
  batch × max_seq_len (padding-waste model: docs/PERF_NOTES.md
  "Serving"). Physical page 0 is a reserved trash page: padding-token
  writes land there and are never attended. The pool dtype is
  configurable (`kv_dtype` / PT_KV_DTYPE): "int8" runs the QUANTIZED
  pool — each written row carries a per-(token, head) fp32 scale in
  page-shaped scale planes, attention dequantizes on gather, and page
  bytes drop ~4× vs fp32 (~2× vs bf16), which is more live sequences
  per HBM byte (quantization runtime, docs/QUANTIZATION.md).

* **Continuous scheduler** — every step admits queued prompts into free
  decode slots, chunks their prefill into the running batch (a FLAT
  token budget: each step carries one decode token per running sequence
  plus as many prefill tokens as fit), samples at each sequence
  frontier, and evicts on EOS or token budget. Admission ORDER is the
  fleet_serving `SLAScheduler` — priority classes, per-tenant
  token-budget fair queuing, TTFT-SLO deadline boosting — which
  degrades to exact FIFO under the default single class. When the pool
  (or slot table) runs dry the lowest-priority / youngest sequence is
  preempted back to the queue (pages freed; greedy decode makes the
  re-run deterministic), after the prefix cache — when enabled — has
  given back its LRU unmapped pages.

* **Shared-prefix radix KV cache** (`LLMEngineConfig(prefix_cache=
  True)` / PT_PREFIX_CACHE) — fleet_serving.RadixPrefixCache indexes
  full prompt pages by token content; a new request whose prompt
  prefix is resident maps the shared pages copy-on-write into its page
  table and skips their prefill entirely, so a fleet sharing a system
  prompt pays its prefill once (docs/SERVING.md; greedy outputs stay
  token-identical — tests/test_fleet_serving.py pins it).

* **ONE compiled decode executable** — every scheduler tick calls the
  same fixed-shape program (`_CompiledPagedStep` over
  `GPTGenerationMixin._paged_decode_core`: token_budget flat tokens,
  num_slots page tables, the pools), so steady-state serving never
  recompiles. Built the `jit.TrainStep` way: weights thread through as
  jit ARGUMENTS (not baked constants — persistent-cache friendly) and
  the KV pools are DONATED, so the page writes are in-place HBM updates
  instead of per-step pool copies. The attention inside is
  `F.paged_attention` — jnp reference on CPU, the Pallas ragged kernel
  on real TPU.

Surface:

    server = inference.LLMServer(model)        # GPTForCausalLM
    with server:
        fut = server.submit(prompt_ids, max_new_tokens=64,
                            eos_token_id=50256)
        tokens = fut.result()   # np.int64 [prompt + generated]

Greedy decode is token-for-token identical to `generate()` (pinned by
tests/test_llm_engine.py); eos semantics follow the shared contract
(the emitted eos is kept, nothing after it).
"""
import collections
import contextlib
import itertools
import os
import queue
import threading
import time as _time
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from ..observability import metrics as _obs
from ..observability import reqtrace as _reqtrace
from ..observability.tracing import trace_span as _trace_span
from ..ops.pallas_kernels import paged_attention as _paged_kernel
from .structured.compiler import _STRUCT_CACHE_HITS, _STRUCT_REQS
from .fleet_serving import (Priority, RadixPrefixCache, RequestCancelled,
                            RequestShed, SLAScheduler, note_cancelled,
                            note_shed)
from .serving import _FutureQueueServer

__all__ = ["PagePool", "PoolExhausted", "LLMEngineConfig", "LLMEngine",
           "LLMServer"]

# serving telemetry (docs/OBSERVABILITY.md). Counters/histograms are
# process-global (engines in one process share them; `LLMServer.metrics()`
# reads this registry — the bench's attribution source). Gauges carry
# the most recent scheduler tick's view.
_REQS_TOTAL = _obs.counter("pt_llm_requests_total", "requests accepted")
_FINISHED_TOTAL = _obs.counter("pt_llm_finished_total",
                               "requests finished (eos or budget)")
_PREEMPTIONS_TOTAL = _obs.counter(
    "pt_llm_preemptions_total", "sequences preempted on a dry page pool")
_STEPS_TOTAL = _obs.counter("pt_llm_steps_total", "scheduler ticks")
_ABORTS_TOTAL = _obs.counter("pt_llm_aborts_total",
                             "abort_all events (device-error path)")
_TOKENS_TOTAL = _obs.counter(
    "pt_llm_tokens_total",
    "flat tokens through the compiled step: one decode token per "
    "sampling frontier, the rest chunked prefill",
    labelnames=("phase",))
_QUEUE_DEPTH = _obs.gauge("pt_llm_queue_depth", "requests waiting")
_LIVE_SLOTS = _obs.gauge("pt_llm_live_slots", "sequences decoding")
_SLOT_OCC = _obs.gauge("pt_llm_slot_occupancy",
                       "live slots / num_slots, last tick")
_PAGE_OCC = _obs.gauge("pt_llm_kv_page_occupancy",
                       "live KV pages / allocable pages")
_PAGE_FRAG = _obs.gauge(
    "pt_llm_kv_fragmentation",
    "internal fragmentation: 1 - written tokens / live page capacity")
_ADMIT_SECONDS = _obs.histogram("pt_llm_admission_seconds",
                                "submit -> first decode-slot admission")
_TTFT_SECONDS = _obs.histogram("pt_llm_ttft_seconds",
                               "submit -> first generated token")
_REQ_TOK_RATE = _obs.histogram(
    "pt_llm_request_tokens_per_sec",
    "per-request generated tok/s (admission -> finish)",
    buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000, 5000,
             10000))
_KV_POOL_BYTES = _obs.gauge(
    "pt_kv_pool_bytes",
    "resident KV page-pool bytes (pools + int8 scale planes), by the "
    "pool dtype (quantized runtime: docs/QUANTIZATION.md)",
    labelnames=("dtype",))
# fused multi-token decode (docs/SERVING.md "Fused decode"): host
# round trips vs tokens produced — the dispatch-overhead economics the
# decode_k knob trades TTFT granularity for
_FUSED_STEPS = _obs.counter(
    "pt_decode_fused_steps",
    "fused k-step decode windows dispatched (one host sync per window)")
_DISPATCHES = _obs.counter(
    "pt_decode_dispatches_total",
    "compiled decode-step dispatches (host round trips), single-tick "
    "or fused window")
_TOK_PER_DISPATCH = _obs.gauge(
    "pt_decode_tokens_per_dispatch",
    "generated tokens the LAST compiled-step dispatch produced (the "
    "fused-decode amortization: up to num_slots on a k=1 tick — one "
    "per sampling frontier — and up to k*num_slots per fused window)")
# shared with jit.TrainStep's probe — ONE definition (the registry
# would raise on a labelnames divergence between two copies)
from ..jit import _DONATION_HELD


class PoolExhausted(RuntimeError):
    """No free KV pages (the scheduler preempts and retries on this)."""


def _payload_trace(payload):
    """The TraceContext a KVPagePayload carries (restored once and
    cached on the payload), or None — the disaggregated hand-off's
    identity continuity, shared by `LLMServer.submit` and
    `LLMEngine.add_request` so NEITHER ingress mints a fresh trace
    over a payload that already has one."""
    ctx = getattr(payload, "trace_ctx", None)
    if ctx is None and getattr(payload, "trace", None):
        ctx = _reqtrace.TraceContext.from_dict(payload.trace)
        payload.trace_ctx = ctx
    return ctx


class PagePool:  # ptlint: thread-shared (scraped by /metrics)
    """Refcounted fixed-size KV-page allocator. Physical page 0 is
    reserved as the trash page (padding-token writes), so pages
    1..num_pages-1 are allocable. `alloc()` hands out a page at
    refcount 1; `share()` adds a holder (the prefix cache's trie and
    every request mapping a shared page each hold one reference);
    `free()` drops one reference per page and only returns the page to
    the free list at refcount 0. Strict double-free / free-list
    corruption / leak checking — the invariants the soak and refcount
    tests pin (a free of an already-free page RAISES instead of
    silently double-inserting it into the free list, which would later
    hand the same page to two sequences)."""

    def __init__(self, num_pages, page_size):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is trash)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free stack, seeded so the first allocs hand out 1, 2, ...
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._ref = {}  # live page id -> refcount (>= 1)

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_live(self):
        return len(self._ref)

    @property
    def num_shared(self):
        # list() copy: the metrics HTTP scrape thread reads this while
        # the engine thread alloc/frees (dict resize mid-iteration)
        return sum(1 for c in list(self._ref.values()) if c > 1)

    def refcount(self, page):
        return self._ref.get(int(page), 0)

    def alloc(self):
        if not self._free:
            raise PoolExhausted(
                f"all {self.num_pages - 1} KV pages in use")
        p = self._free.pop()
        if p in self._ref:  # a corrupted free list must fail loudly
            raise RuntimeError(
                f"corrupt free list: page {p} is already live")
        self._ref[p] = 1
        return p

    def share(self, page):
        """Add one holder to a LIVE page (shared-prefix mapping).
        Sharing a freed page is a use-after-free — the page may already
        belong to another sequence — so it raises."""
        p = int(page)
        if p not in self._ref:
            raise RuntimeError(
                f"share of non-live KV page {p}: the page was freed "
                "(or never allocated) — stale prefix-cache mapping?")
        self._ref[p] += 1
        return p

    def free(self, pages):
        for p in pages:
            p = int(p)
            if p not in self._ref:
                raise RuntimeError(
                    f"double free of KV page {p} (live: "
                    f"{len(self._ref)})")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)

    def assert_consistent(self):
        if len(self._free) != len(set(self._free)):
            raise RuntimeError("corrupt free list: duplicate pages")
        both = set(self._free) & set(self._ref)
        if both:
            raise RuntimeError(
                f"pages both free and live: {sorted(both)}")
        if 0 in self._ref or 0 in self._free:
            raise RuntimeError("trash page 0 entered circulation")
        total = len(self._free) + len(self._ref)
        if total != self.num_pages - 1:
            raise RuntimeError(
                f"page leak: {len(self._free)} free + "
                f"{len(self._ref)} live != {self.num_pages - 1}")


class _HeldPages:
    """What one request holds of one cache kind: the physical `pages`
    of its logical pages `first` …, a CONTIGUOUS run (growth appends, a
    window frees from the front). `len()` and iteration: logical."""

    def __init__(self):
        self.first = 0
        self.pages = []

    def __len__(self):
        return len(self.pages)

    def __iter__(self):
        return iter(range(self.first, self.first + len(self.pages)))


class _CacheKindState:
    """One kind of K/V cache of the model (`text/models/
    serving_protocol.py`; GPT has one, the engine's first is no
    exception): its page pool, its page table a slot indexed by LOGICAL
    page, and what each request holds of it (`req.kind_pages[index]`,
    a `_HeldPages`). A kind with a window keeps, for a sequence whose
    next position is p, only the pages that hold p - window + 1 … p:
    pages wholly behind are freed at step boundaries and their table
    entries return to 0 (the trash page, which the kernel's lower bound
    never reads). The kind whose pool carries the prefix trie has it in
    `trie`: a dry pool first reclaims the trie's LRU pages."""

    def __init__(self, index, kind, num_pages, page_size, num_slots,
                 pages_per_seq):
        self.index = index
        self.kind = kind
        self.pool = PagePool(num_pages, page_size)
        self.tables = np.zeros((num_slots, pages_per_seq), np.int32)
        self.trie = None

    def first_live_page(self, position):
        """The first logical page a query at `position` still reads."""
        if self.kind.window is None:
            return 0
        return max(0, position - self.kind.window + 1) \
            // self.pool.page_size

    def available(self):
        """Pages `alloc` can still hand out: the free ones and what the
        trie would give back."""
        if self.trie is None:
            return self.pool.num_free
        return self.pool.num_free + self.trie.reclaimable_pages()

    def pages_to_admit(self, n_tokens, token_budget):
        """Free pages a prompt of `n_tokens` asks for at admission: all
        of a full kind's; of a window kind's those of the first chunk
        and the window behind it, and one more (the page ahead is taken
        before the page behind is freed)."""
        if self.kind.window is None:
            return -(-n_tokens // self.pool.page_size)
        span = min(n_tokens, self.kind.window + token_budget)
        return -(-span // self.pool.page_size) + 1

    def alloc(self):
        try:
            return self.pool.alloc()
        except PoolExhausted:
            if self.trie is not None and self.trie.evict(1) > 0:
                return self.pool.alloc()
            raise

    def covered(self, req):
        """The position the request's pages reach: every position below
        it has its page, or lies behind the window."""
        held = req.kind_pages[self.index]
        return (held.first + len(held.pages)) * self.pool.page_size

    def _next_page(self, held, first_pos):
        """The next logical page to take for a request whose next
        position is `first_pos`: nothing held starts at the window."""
        if held.pages:
            return held.first + len(held.pages)
        return max(held.first, self.first_live_page(first_pos))

    def missing(self, req, first_pos, last_pos):
        """How many pages this kind lacks for queries first_pos …
        last_pos, `first_pos` being the request's next position."""
        return max(0, last_pos // self.pool.page_size + 1
                   - self._next_page(req.kind_pages[self.index],
                                     first_pos))

    def grow(self, slot, req, first_pos, last_pos):
        """Allocate what is `missing`. PoolExhausted leaves what was
        taken before it with the request."""
        held = req.kind_pages[self.index]
        if not held.pages:
            held.first = self._next_page(held, first_pos)
        for _ in range(self.missing(req, first_pos, last_pos)):
            page = self.alloc()
            self.tables[slot, held.first + len(held.pages)] = page
            held.pages.append(page)

    def adopt(self, slot, req, pages):
        """`pages`, whose references are already the request's (a mapped
        prefix, imported pages), become its logical pages 0 …"""
        held = req.kind_pages[self.index]
        held.first, held.pages = 0, list(pages)
        self.tables[slot, :] = 0
        self.tables[slot, :len(pages)] = pages

    def rows(self, slots, positions):
        """The pool row of each (slot, position), through the table."""
        ps = self.pool.page_size
        return self.tables[slots, positions // ps] * ps + positions % ps

    def trim(self, slot, req):
        """Free the pages wholly behind the window of the request's
        next position; returns how many."""
        if self.kind.window is None:
            return 0
        held = req.kind_pages[self.index]
        dead = min(len(held.pages),
                   self.first_live_page(req.n_prefilled) - held.first)
        if dead <= 0:
            return 0
        self.pool.free(held.pages[:dead])
        del held.pages[:dead]
        self.tables[slot, held.first:held.first + dead] = 0
        held.first += dead
        return dead

    def release(self, slot, req):
        """The request's references go: a page shared with the trie or
        another request stays live with theirs."""
        held = req.kind_pages[self.index]
        self.pool.free(held.pages)
        held.first, held.pages = 0, []
        self.tables[slot, :] = 0


class LLMEngineConfig:
    """Engine sizing. Defaults are safe (worst-case pool: no
    preemption); shrink `num_pages` to trade HBM for occasional
    preemption under load.

    num_slots     max concurrently-decoding sequences (the compiled
                  step's batch geometry)
    page_size     tokens per KV page
    num_pages     pool size incl. the trash page; default
                  num_slots * ceil(max_model_len / page_size) + 1. A
                  model with several cache kinds (docs/SERVING.md "Two
                  kinds of cache") takes `{kind name: pages}`; a kind
                  left out gets its worst case
    max_model_len per-sequence token cap; default model max_seq_len
    token_budget  flat tokens per step (>= num_slots); the surplus over
                  the decode tokens is the chunked-prefill bandwidth.
                  Default num_slots + max(num_slots, 8).
    kv_dtype      pool dtype: "float32" | "bfloat16" | "int8" | "int4"
                  (the quantized runtime — int8/int4 pools carry
                  per-row scale planes and dequantize on gather; int4
                  packs two nibbles per byte along head_dim, ~1.9×
                  the equal-bytes page capacity of int8 and ~7× fp32,
                  at a coarser 15-level grid — docs/QUANTIZATION.md
                  "int4"). Default: the PT_KV_DTYPE env var, else the
                  model compute dtype.
    prefix_cache  enable the shared-prefix radix KV cache
                  (fleet_serving.RadixPrefixCache): requests with a
                  cached prompt prefix map shared pages read-only and
                  skip their prefill. Default: the PT_PREFIX_CACHE env
                  var, else off.
    hash_block_tokens
                  content-hash granularity of the prefix trie, in
                  tokens. Must be a positive multiple of `page_size`
                  (a trie node maps WHOLE pages; a block that ends
                  mid-page would alias half-written KV). Default:
                  page_size.
    sla_policy    fleet_serving.SLAPolicy for the admission scheduler
                  (priority classes, tenant fair queuing, TTFT SLO
                  boost). Default policy degrades to FIFO when every
                  request uses the default tenant/priority.
    decode_k      fused-decode window size: pure-decode ticks run k
                  tokens per compiled dispatch (a `lax.scan` with
                  in-executable sampling + EOS masking), so the host
                  syncs once per k tokens. 1 (the default / env
                  PT_DECODE_K) keeps the single-tick host loop.
                  Admission, preemption, SLO escalation, and
                  prefix-cache publication happen at window
                  BOUNDARIES (docs/SERVING.md has the TTFT/SLO
                  granularity contract).
    seed          engine PRNG seed for temperature/top-p sampling
                  (threaded through the compiled step as an argument —
                  `reseed()` never recompiles). Greedy decode ignores
                  it.
    draft_model   optional small draft model (same GPT family, tied
                  tokenizer — vocab ids must match) enabling
                  SPECULATIVE DECODING (inference/speculative.py,
                  docs/SERVING.md): the draft proposes spec_k tokens
                  per live sequence through its own mirrored paged KV
                  pool, the big model verifies all k+1 positions per
                  slot in ONE ragged batched dispatch, and lossless
                  exact-match acceptance keeps greedy AND sampled
                  outputs token-identical to the non-speculative
                  engine. None (default) keeps the PR-8 fused /
                  single-tick paths.
    spec_k        draft tokens proposed per speculative window.
                  Default: the PT_SPEC_K env var, else 4. Ignored
                  without speculation enabled.
    spec_mode     speculation source: None (off unless draft_model is
                  set, which implies "draft"), "draft" (requires
                  draft_model), or "ngram" — draft-model-FREE
                  prompt-lookup proposals (inference/structured/
                  ngram.py): the request's own prompt+generated
                  suffix proposes spec_k tokens into the SAME ragged
                  verify executable, no second model resident.
                  "ngram" with a draft_model is a config error.
    token_strs    per-token surface strings (len == vocab_size) —
                  enables STRUCTURED DECODING (inference/structured,
                  docs/SERVING.md "Structured decoding"): per-request
                  `grammar=` / `json_schema=` constraints compile to
                  token-level DFAs masked inside the compiled scans.
                  None (default) = constrained requests are rejected
                  loudly at submit.
    grammar_states
                  grammar-arena DFA state budget (table rows resident
                  at once across all live grammars; row 0 is the
                  mask-identity). A grammar over the budget raises
                  GrammarError at submit. Default 128; ignored
                  without token_strs (the arena collapses to the
                  identity row).
    kv_tier       hierarchical KV memory below the device pool
                  (fleet_serving.kv_tier; docs/SERVING.md "KV memory
                  hierarchy"). Falsy (default) = off. True enables the
                  host-RAM spill tier with defaults; a dict passes
                  `KVTierStore` knobs through (`ram_bytes`,
                  `disk_dir`, `disk_bytes`, `max_pending`). Requires
                  prefix_cache: the tier spills/prefetches TRIE nodes.
    session_ttl_s persistent-chat session TTL (seconds a session's
                  frontier stays tracked after its last turn;
                  default 600). See `LLMServer.submit(session_id=)`.
    session_max   LRU cap on tracked sessions (default 256).
    """

    def __init__(self, num_slots=4, page_size=16, num_pages=None,
                 max_model_len=None, token_budget=None, kv_dtype=None,
                 prefix_cache=None, hash_block_tokens=None,
                 sla_policy=None, decode_k=None, seed=0,
                 draft_model=None, spec_k=None, kv_tier=None,
                 session_ttl_s=None, session_max=None, spec_mode=None,
                 token_strs=None, grammar_states=None):
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.num_pages = num_pages
        self.max_model_len = max_model_len
        self.token_budget = token_budget
        self.kv_dtype = kv_dtype
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PT_PREFIX_CACHE", "0").strip().lower() in (
                    "1", "true", "yes", "on")
        self.prefix_cache = bool(prefix_cache)
        self.hash_block_tokens = int(
            self.page_size if hash_block_tokens is None
            else hash_block_tokens)
        self.sla_policy = sla_policy
        if decode_k is None:
            decode_k = int(os.environ.get("PT_DECODE_K", "1"))
        self.decode_k = int(decode_k)
        self.seed = int(seed)
        self.draft_model = draft_model
        if spec_k is None:
            spec_k = int(os.environ.get("PT_SPEC_K", "4"))
        self.spec_k = int(spec_k)
        if spec_mode is None and draft_model is not None:
            spec_mode = "draft"
        if spec_mode not in (None, "draft", "ngram"):
            raise ValueError(
                "spec_mode must be None, 'draft', or 'ngram', got "
                f"{spec_mode!r}")
        if spec_mode == "draft" and draft_model is None:
            raise ValueError(
                "spec_mode='draft' needs draft_model= (pass "
                "spec_mode='ngram' for draft-model-free speculation)")
        if spec_mode == "ngram" and draft_model is not None:
            raise ValueError(
                "spec_mode='ngram' is draft-model-free — drop "
                "draft_model= (or use spec_mode='draft')")
        self.spec_mode = spec_mode
        self.token_strs = (None if token_strs is None
                           else list(token_strs))
        self.grammar_states = int(128 if grammar_states is None
                                  else grammar_states)
        if self.grammar_states < 2:
            raise ValueError(
                "grammar_states must be >= 2 (row 0 is the reserved "
                f"mask-identity row), got {self.grammar_states}")
        self.kv_tier = kv_tier
        self.session_ttl_s = float(600.0 if session_ttl_s is None
                                   else session_ttl_s)
        self.session_max = int(256 if session_max is None
                               else session_max)
        if self.kv_tier and not self.prefix_cache:
            raise ValueError(
                "kv_tier requires prefix_cache=True: the tier "
                "spills and prefetches radix-trie nodes, so without "
                "the trie there is nothing to tier")
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.decode_k < 1:
            raise ValueError("decode_k must be >= 1")
        if self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if self.hash_block_tokens < 1:
            raise ValueError("hash_block_tokens must be >= 1")
        if self.prefix_cache and (
                self.hash_block_tokens % self.page_size != 0):
            # silent misalignment would map pages whose tail rows hold
            # a DIFFERENT request's tokens — reject loudly at config
            # time, not with corrupted logits at serve time
            raise ValueError(
                f"prefix_cache requires page_size ({self.page_size}) "
                f"to divide hash_block_tokens "
                f"({self.hash_block_tokens}): a trie block must cover "
                "an exact number of KV pages, otherwise a shared "
                "mapping would alias a partially-matching page")

    @staticmethod
    def kv_bytes_per_page(model_config, page_size, kv_dtype=None,
                          kind=None):
        """Bytes ONE page costs across the k+v pools of every layer of
        one cache kind (`model_config.cache_kinds()`; `kind` its name,
        default the first), scale planes included — the unit of the
        capacity math below. int8 rows cost hd + 4 bytes per head;
        packed int4 rows cost hd/2 + 4 (two nibbles per byte — the
        scale plane is shared machinery, so its 4 bytes/head weigh
        relatively more: equal-bytes capacity lands ≈ ×1.9 over int8,
        ≈ ×7 over fp32 at hd 32)."""
        from ..quantization import runtime as _qrt

        dt, quantized = _qrt.resolve_kv_dtype(kv_dtype, jnp.float32)
        kinds = model_config.cache_kinds()
        ck = kinds[0] if kind is None else next(
            k for k in kinds if k.name == kind)
        if ck.latent:
            # one pool a layer, a row a token at the width it is stored
            return (len(ck.layers) * page_size * ck.row_store
                    * jnp.dtype(dt).itemsize)
        nh, hd = ck.kv_heads, ck.head_dim
        if quantized == 4:
            per_row = nh * (hd // 2)      # packed nibbles
        else:
            per_row = nh * hd * jnp.dtype(dt).itemsize
        if quantized:
            per_row += nh * 4  # fp32 scale per (row, head)
        return 2 * len(ck.layers) * page_size * per_row

    @classmethod
    def for_pool_budget(cls, model_config, budget_bytes, page_size=16,
                        kv_dtype=None, **kw):
        """Size `num_pages` to a page-pool BYTE budget — the equal-bytes
        capacity comparison the quantized-KV acceptance pins (int8 pools
        admit ~4× the pages of fp32 at the same budget). A model with
        several cache kinds takes a budget a kind: `{kind name:
        bytes}`."""
        def pages(budget, kind=None):
            per_page = cls.kv_bytes_per_page(model_config, page_size,
                                             kv_dtype, kind)
            return max(2, int(budget) // per_page + 1)  # + trash

        if isinstance(budget_bytes, dict):
            state = {k.name for k in model_config.cache_kinds() if k.state}
            if state & set(budget_bytes):
                raise ValueError(
                    f"cache kind(s) {sorted(state & set(budget_bytes))} "
                    "keep a fixed slab a slot, not pages: they take no "
                    "page budget (`pool_bytes()` counts their slabs)")
            num_pages = {k: pages(b, k) for k, b in budget_bytes.items()}
        else:
            num_pages = pages(budget_bytes)
        return cls(page_size=page_size, num_pages=num_pages,
                   kv_dtype=kv_dtype, **kw)


class _CompiledStepBase:
    """Shared shell of every compiled decode executable (single-tick,
    fused window, speculative propose/verify). Subclasses build
    `self._jit` (weights as ARGUMENTS, kv pytree DONATED) and dispatch
    it; the executables go through the persistent compilation cache
    like any other (a cache-loaded donating executable keeps its
    aliasing map on jax 0.9.0 — probed on the chip and the CPU, PR 21,
    docs/RESILIENCE.md)."""

    _jit = None
    # page-major paged-attention launches ONE dispatch makes, by the
    # body the kernel built for their shapes ({"mxu": n, "vpu": n});
    # known once the program is traced, i.e. from its first dispatch on
    launches = {}

    def cache_size(self):
        n = getattr(self._jit, "_cache_size", None)
        return int(n()) if callable(n) else -1

    @contextlib.contextmanager
    def _counting_launches(self, repeats=1):
        """Around the model's step body inside `pure`: the kernel call
        sites traced in the block, times `repeats` (the length of the
        scan they sit in), are what a dispatch launches."""
        with _paged_kernel.launch_sites() as sites:
            yield
        self.launches = {b: n * repeats for b, n in sites.items()}


class _CompiledPagedStep(_CompiledStepBase):
    """The engine's ONE decode executable, built the `jit.TrainStep`
    way: a pure function over (param_vals, step arrays, kv pools) under
    `jax.jit`. Weights ride as ARGUMENTS (structurally-equal engines
    share one correct persistent-cache entry — the same reasoning as
    TrainStep's base-key-as-argument note), and the kv-pool pytree is
    DONATED so the paged cache writes update HBM in place instead of
    copying every pool every tick."""

    def __init__(self, model):
        self._params = list(model.state_dict().values())

        def pure(param_vals, tok, pos, sid, widx, pt, klen, smp,
                 kv_state):
            from ..autograd import engine as eng
            from ..tensor_core import Tensor

            def t(v):
                return Tensor(v, stop_gradient=True)

            # kv_state = (pools, scale planes, PRNG key) — scales empty
            # for float pools; ONE donated pytree so int8 pools, their
            # scales, and the sampling key update in place together.
            # The single-tick step never consumes randomness (sampling
            # rows draw on the host through the SAME sample_tokens
            # math), so the key passes through untouched.
            kv_vals, kv_scales, key = kv_state
            originals = [p._value for p in self._params]
            for p, v in zip(self._params, param_vals):
                p._value = v
            try:
                with eng.no_grad_guard(), self._counting_launches():
                    out = model._paged_decode_core(
                        t(tok), t(pos), t(sid), t(widx), t(pt), t(klen),
                        t(smp), [t(v) for v in kv_vals],
                        kv_scales=(
                            [t(s) for s in kv_scales] if kv_scales
                            else None))
            finally:
                for p, v in zip(self._params, originals):
                    p._value = v
            logits, *new_kv = out
            n = len(kv_vals)
            state = ([x._value for x in new_kv[:n]],
                     [x._value for x in new_kv[n:n + len(kv_scales)]],
                     key)
            if counted:
                # the model's own counters (serving_protocol.py): one
                # more small result, read when the host next syncs
                return (logits._value, new_kv[-1]._value), state
            return logits._value, state

        counted = bool(getattr(model, "step_counters", ()))
        self._jit = jax.jit(pure, donate_argnums=(8,))

    def __call__(self, tok, pos, sid, widx, pt, klen, smp, kv_state):
        return self._jit([p._value for p in self._params], tok, pos,
                         sid, widx, pt, klen, smp, kv_state)


class _CompiledFusedStep(_CompiledStepBase):
    """The engine's fused k-step decode executable: `lax.scan` over the
    paged step (`GPTGenerationMixin._paged_decode_fused`) with sampling
    and EOS/budget masking INSIDE the scan — one host round trip per k
    tokens. Built exactly like `_CompiledPagedStep` (weights as jit
    ARGUMENTS, the kv pytree — pools + scale planes + PRNG key —
    DONATED). k is baked into the scan length, so one engine holds ONE
    fused executable per (k, geometry); window spill (pool pressure / short budgets) rides
    the `rem` argument instead of re-tracing a shorter scan."""

    def __init__(self, model, k, page_size):
        self._params = list(model.state_dict().values())
        self.k = int(k)
        ps = int(page_size)

        def pure(param_vals, tok0, pos0, rem, fin0, eos, temps, top_ps,
                 streams, gstate0, gtrans, gmask, pt, kv_state):
            from ..autograd import engine as eng

            kv_vals, kv_scales, key = kv_state
            originals = [p._value for p in self._params]
            for p, v in zip(self._params, param_vals):
                p._value = v
            try:
                with eng.no_grad_guard(), self._counting_launches(self.k):
                    emits, new_kv, new_scales, *counters = \
                        model._paged_decode_fused(
                            self.k, ps, tok0, pos0, rem, fin0, eos, temps,
                            top_ps, streams, pt, list(kv_vals),
                            list(kv_scales) if kv_scales else None, key,
                            gstate0=gstate0, gtrans=gtrans, gmask=gmask)
            finally:
                for p, v in zip(self._params, originals):
                    p._value = v
            if counters:
                # the model's own counters [k, C] ride the ONE array the
                # host reads a window: [k, S + C]
                emits = jnp.concatenate(
                    [emits, counters[0].astype(emits.dtype)], axis=1)
            return emits, (new_kv, new_scales, key)

        self._jit = jax.jit(pure, donate_argnums=(13,))

    def __call__(self, tok0, pos0, rem, fin0, eos, temps, top_ps,
                 streams, gstate0, gtrans, gmask, pt, kv_state):
        return self._jit([p._value for p in self._params], tok0, pos0,
                         rem, fin0, eos, temps, top_ps, streams,
                         gstate0, gtrans, gmask, pt, kv_state)


class _Request:
    _ids = itertools.count()

    def __init__(self, tokens, max_new_tokens, eos_token_id, future,
                 tenant="default", priority=None, ttft_slo_s=None,
                 temperature=0.0, top_p=1.0):
        self.rid = next(_Request._ids)
        self.tokens = [int(t) for t in tokens]  # prompt, grows as decoded
        self.prompt_len = len(self.tokens)
        self.max_new = int(max_new_tokens)
        self.eos = eos_token_id
        self.future = future if future is not None else Future()
        self.target = None        # total-token cap, set at add_request
        self.slot = None
        # what it holds of cache kind n (`_CacheKindState` keeps it)
        self.kind_pages = collections.defaultdict(_HeldPages)
        self.n_prefilled = 0      # kv-written tokens (reset on preempt)
        self.draft_prefilled = 0  # draft-pool valid prefix (speculative)
        self.admit_seq = None     # admission order (preemption picks max)
        self.preemptions = 0
        # fleet_serving fields (scheduler class / fairness / SLO)
        self.tenant = str(tenant)
        self.priority = int(Priority.STANDARD if priority is None
                            else priority)
        if self.priority < 0:
            # -1 is the scheduler's SLO-escalation rank: a client
            # priority below 0 would outrank every deadline-escalated
            # request AND compare its fair-queuing meter against their
            # absolute deadlines (meaningless tuple order)
            raise ValueError(
                f"priority must be >= 0, got {self.priority} "
                "(negative ranks are reserved for SLO escalation)")
        self.ttft_slo_s = ttft_slo_s
        # sampling contract: temperature 0 = greedy (the default,
        # token-identical to generate()); > 0 samples the temperature-
        # scaled top-p-truncated distribution, keyed on (engine seed,
        # sample_stream, position) — deterministic under preemption
        # replay and invariant to decode_k (gpt.py sample_tokens)
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")
        self.sample_stream = 0    # engine-assigned at add_request
        # disaggregated serving (fleet_serving.kv_transfer): a prefill-
        # only request stops AT its sampling frontier and resolves its
        # future to the exported KVPagePayload instead of tokens; a
        # request carrying _kv_import admits with its prompt KV written
        # from another replica's payload (consumed at admission — a
        # preemption replay falls back to ordinary prefill)
        self.prefill_only = False
        self._kv_import = None
        # persistent chat sessions (ISSUE 17): set by add_request;
        # _session_seen marks a RETURNING session (resume telemetry)
        self.session_id = None
        self._session_seen = False
        # structured decoding (inference/structured): the compiled
        # token-level DFA and the request's grammar-LOCAL state. The
        # state is a pure function of the generated tokens (the engine
        # replays every emitted token through `grammar.advance`), and
        # `tokens` survives preemption, so a preempted constrained
        # request resumes at the correct DFA state for free.
        self.grammar = None
        self.gstate = 0
        self.spec_off = False     # per-request spec_mode="off" opt-out
        self._arrival = None      # scheduler enqueue stamp
        self.cached_prefix = 0    # tokens served from the prefix cache
        self._cow_pending = 0     # COW splits taken by the last match
        self.published_blocks = 0  # trie blocks this mapping covers
        # telemetry stamps (admission latency / TTFT / per-request rate)
        self.t_submit = _time.perf_counter()
        self.t_first_admit = None
        self.t_first_token = None
        # hard deadline (absolute perf_counter; overload control plane)
        self.deadline_t = None
        # request-scoped trace identity + TTFT phase stamps
        # (observability.reqtrace; assigned by add_request)
        self.trace = None

    @property
    def pages(self):
        """The first cache kind's physical pages in logical order: that
        kind's own list, for readers (trie, KV wire, tier)."""
        return self.kind_pages[0].pages

    @property
    def do_sample(self):
        return self.temperature > 0.0

    @property
    def num_generated(self):
        return len(self.tokens) - self.prompt_len

    def result_array(self):
        return np.asarray(self.tokens, np.int64)


class LLMEngine:  # ptlint: thread-shared (scraped by /metrics)
    """Scheduler + paged-KV state around ONE compiled ragged decode step
    (module docstring has the design). Drive it directly —

        eng = LLMEngine(model)
        req = eng.add_request(prompt_ids, max_new_tokens=32)
        while eng.has_work():
            eng.step()
        tokens = req.future.result()

    — or through `LLMServer` for the threaded future/queue surface."""

    def __init__(self, model, config=None):
        model.eval()
        self.model = model
        mcfg = model.config
        cfg = config or LLMEngineConfig()
        self.num_slots = cfg.num_slots
        self.page_size = cfg.page_size
        self.max_model_len = int(cfg.max_model_len or mcfg.max_seq_len)
        if self.max_model_len > mcfg.max_seq_len:
            raise ValueError(
                f"max_model_len {self.max_model_len} exceeds the "
                f"model's max_seq_len {mcfg.max_seq_len}")
        self.pages_per_seq = -(-self.max_model_len // self.page_size)
        self.token_budget = int(
            cfg.token_budget
            or self.num_slots + max(self.num_slots, 8))
        if self.token_budget < self.num_slots:
            raise ValueError(
                f"token_budget {self.token_budget} < num_slots "
                f"{self.num_slots}: every running sequence needs one "
                "decode token per step")
        # the model's cache kinds (text/models/serving_protocol.py), a
        # `_CacheKindState` each. The FIRST keeps every page: the trie,
        # the tier, the KV wire and the draft pool hang on it
        all_kinds = list(mcfg.cache_kinds())
        # a STATE kind keeps one fixed slab a slot a layer and no pages:
        # it has no pool, no table and no `_CacheKindState` (a slot holds
        # its slab while it holds the slot, so growth, trimming and
        # release have nothing to do); `_state_kinds` keeps its gauges
        kinds = [k for k in all_kinds if not k.state]
        self._state_kinds = [k for k in all_kinds if k.state]
        if not kinds or all_kinds[:len(kinds)] != kinds:
            raise ValueError(
                "the model's cache kinds "
                f"{[k.name for k in all_kinds]} must list at least one "
                "PAGED kind, and the paged kinds first: the scheduler "
                "admits, grows and preempts by pages")
        if kinds[0].window is not None:
            raise ValueError(
                f"cache kind {kinds[0].name!r} comes first and has a "
                "window: the first kind keeps every page (list a full "
                "kind first)")
        worst = self.num_slots * self.pages_per_seq + 1
        if isinstance(cfg.num_pages, dict):
            unknown = set(cfg.num_pages) - {k.name for k in kinds}
            if unknown:
                raise ValueError(
                    f"num_pages names {sorted(unknown)}; the model's "
                    f"cache kinds are {[k.name for k in kinds]}")
            pages_of = [int(cfg.num_pages.get(k.name) or worst)
                        for k in kinds]
        else:
            pages_of = [int(cfg.num_pages or worst)] + [worst] * (
                len(kinds) - 1)
        num_pages = pages_of[0]
        self._caches = [
            _CacheKindState(n, kind, pages_of[n], self.page_size,
                            self.num_slots, self.pages_per_seq)
            for n, kind in enumerate(kinds)]
        # plain aliases, no state of their own: what benchmarks/builders/
        # and tests/benchmarks/ still reach for (ROADMAP C11)
        self.pool = self._caches[0].pool
        self._extra = self._caches[1:]
        # several kinds: what assumes one geometry refuses (below), and
        # the per-kind counters of `stats` exist
        self._several = len(kinds) > 1
        # a latent kind (one pool a layer, rows with no head axis) is
        # refused what reads `[page, heads, head_dim]` pools, as several
        # kinds are refused what assumes one geometry; both keep the
        # page gauges and counters a kind (`<kind>_pages_live`, …)
        self._latent = any(k.latent for k in kinds)
        self._kind_stats = self._several or self._latent \
            or bool(self._state_kinds)
        if self._kind_stats:
            on = [name for name, v in (
                ("prefix_cache=True", cfg.prefix_cache),
                ("kv_tier", cfg.kv_tier),
                ("speculative decoding", cfg.draft_model is not None
                 or cfg.spec_mode)) if v]
            if on:
                raise ValueError(
                    f"{', '.join(on)}: not with a model of "
                    f"{len(all_kinds)} cache kinds "
                    f"({[k.name for k in all_kinds]}"
                    f"{', latent' if self._latent else ''}). The prefix "
                    "trie, the tier store, the KV wire and the "
                    "speculative draft pool assume ONE page geometry of "
                    "`[page, heads, head_dim]` pools and one page table "
                    "a slot (ROADMAP.md B-I)" + (
                        ". A state kind's slab is not pages at all: a "
                        "shared prefix, a spilled block, an imported "
                        "payload or a rejected draft would each need a "
                        "SNAPSHOT of the state at that position, which "
                        "the engine does not keep (ROADMAP.md B-I.6)"
                        if self._state_kinds else ""))
        # pool in the configured kv_dtype (default: the model's compute
        # dtype — decode is HBM-bound, same reasoning as generate()'s
        # cache dtype; "int8" quantizes each written row per (token,
        # head) with fp32 scale planes alongside — quantization runtime,
        # docs/QUANTIZATION.md). The zero pools are COMMITTED with the
        # same replicated NamedSharding the step executable's outputs
        # carry (the TP layers' sharding constraints stamp the global
        # mesh on every output) — a placement mismatch between step 0's
        # pools and every later step's would cost a second
        # dispatch-cache entry (the zero-recompile probe would read 2
        # executables, not 1)
        from ..distributed import mesh as mesh_mod
        from ..quantization import runtime as _qrt

        compute_dt = model.compute_dtype()
        cache_dt, self.kv_quantized = _qrt.resolve_kv_dtype(
            cfg.kv_dtype, compute_dt)
        if self.kv_quantized and (
                self._kind_stats or any(k.head_major for k in kinds)):
            raise ValueError(
                f"kv_dtype={cfg.kv_dtype!r}: head-major and latent "
                "pools and models with several cache kinds or a state "
                "kind keep float pools (a state slab is float32 by its "
                "kind)")
        hd = kinds[0].head_dim
        # kv_quantized is the code width (0 float / 8 / 4 — truthy when
        # quantized); int4 packs two nibbles per byte along head_dim,
        # so the pool's last dim is hd/2 and attention unpacks on
        # gather (the shape IS the codec discriminator — gpt.py
        # _paged_cache_write_quant / F.paged_attention)
        hd_store = None       # None: every kind stores its own head_dim
        if self.kv_quantized == 4:
            if hd % 2:
                raise ValueError(
                    f"kv_dtype='int4' needs an even head_dim, got {hd} "
                    "(nibble packing pairs head_dim elements)")
            hd_store = hd // 2
            self.kv_dtype = "int4"
        else:
            self.kv_dtype = str(jnp.dtype(cache_dt))
        sharding = mesh_mod.named_sharding()  # replicated on the mesh

        # k0, v0, k1, v1 … in LAYER order, each pool shaped by its
        # layer's kind (a latent kind's layer has one pool, not two)
        kind_of = {i: n for n, k in enumerate(all_kinds)
                   for i in k.layers}
        n_layers = len(kind_of)
        if sorted(kind_of) != list(range(n_layers)) or n_layers != sum(
                len(k.layers) for k in all_kinds):
            raise ValueError("the cache kinds must cover every layer "
                             "once")

        def _layer_arrays(kind, n):
            """[(shape, dtype)] of what one layer of `kind` keeps."""
            if kind.state:
                return kind.slab_arrays(self.num_slots, compute_dt)
            return [(kind.pool_shape(pages_of[n], self.page_size,
                                     hd_store), cache_dt)
                    ] * kind.pools_per_layer

        def _fresh_pools():
            pools = [
                jax.device_put(jnp.zeros(shape, dt), sharding)
                for i in range(n_layers)
                for shape, dt in _layer_arrays(all_kinds[kind_of[i]],
                                               kind_of[i])]
            scales = []
            if self.kv_quantized:
                sshape = _qrt.kv_scale_shape(num_pages, self.page_size,
                                             kinds[0].kv_heads)
                scales = [
                    jax.device_put(jnp.zeros(sshape, jnp.float32),
                                   sharding)
                    for _ in range(2 * n_layers)]
            return pools, scales

        self._fresh_pools = _fresh_pools
        self._kv, self._kv_scales = _fresh_pools()
        self._spec = None  # set below; pool_bytes() reads it
        _KV_POOL_BYTES.labels(dtype=self.kv_dtype).set(self.pool_bytes())
        self._slots = [None] * self.num_slots
        # fused multi-token decode (decode_k > 1): pure-decode ticks go
        # through ONE k-step scan executable; the engine-owned PRNG key
        # rides the same donated pytree as the pools. Committed to the
        # pools' sharding for the same one-executable reason.
        self.decode_k = int(cfg.decode_k)
        self._seed = int(cfg.seed)
        self._key = jax.device_put(
            jax.random.PRNGKey(cfg.seed), sharding)
        self._sample_streams = itertools.count()
        self._fused_fn = None     # built lazily on the first window
        self._host_sample = None  # jitted sample_tokens for host ticks
        # staging cache: per-tick host arrays whose values depend only
        # on slot MEMBERSHIP (sid / sample_idx) are device-committed
        # once per slot-assignment generation instead of rebuilt and
        # re-uploaded every decode tick
        self._slot_gen = 0
        self._stage = None
        # fleet_serving: SLA admission (default policy degrades to
        # FIFO) + optional shared-prefix radix cache over the pool
        self.sched = SLAScheduler(cfg.sla_policy)
        self.hash_block_tokens = int(cfg.hash_block_tokens)
        self.prefix_cache = self._caches[0].trie = (
            RadixPrefixCache(self._caches[0].pool, self.page_size,
                             self.hash_block_tokens)
            if cfg.prefix_cache else None)
        self._admit_counter = itertools.count()
        # hierarchical KV memory (fleet_serving.kv_tier, ISSUE 17):
        # trie evictions spill D2H into the host-RAM/disk tiers; trie
        # misses probe the tier and prefetch H2D through the SAME
        # fixed-width import scatter every kv_import uses (one
        # executable — the zero-recompile contract covers prefetch)
        self.kv_tier = None
        if cfg.kv_tier:
            from .fleet_serving.kv_tier import KVTierStore

            kw = dict(cfg.kv_tier) if isinstance(cfg.kv_tier, dict) \
                else {}
            self.kv_tier = KVTierStore(**kw)
            self.prefix_cache.spill_fn = self._spill_node
        self._spill_count = 0     # spills queued (kv_spill stamping)
        # persistent chat sessions (docs/SERVING.md "KV memory
        # hierarchy"): session_id -> {last_used, turns}. The KV itself
        # is NOT here — a finished turn's blocks are published into
        # the trie (pinned) and age into the tier like any prefix;
        # this table only tracks liveness for TTL/LRU expiry and the
        # resumed/active telemetry. Engine-thread only.
        self._sessions = collections.OrderedDict()
        self.session_ttl_s = cfg.session_ttl_s
        self.session_max = cfg.session_max
        self._step_fn = _CompiledPagedStep(model)
        self.stats = {"steps": 0, "tokens_in": 0, "generated": 0,
                      "finished": 0, "preemptions": 0,
                      "occupancy_sum": 0.0, "fused_steps": 0,
                      "stage_hits": 0,
                      # which body the paged kernel's launches ran
                      # (`_note_launches`); both 0 on the jnp path
                      "paged_attn_mxu_launches": 0,
                      "paged_attn_vpu_launches": 0}
        if self._latent:       # the latent walk's launches
            self.stats["paged_attn_latent_launches"] = 0
        # the model's own step counters (e.g. an expert layer's), summed
        # into `stats` under their names; a tick's arrive with the next
        # read the ENGINE thread makes anyway (`_note_counters`: never a
        # scraper's, which would race the step and wait on the device),
        # so they are complete whenever no request is in flight
        self._counter_names = tuple(getattr(model, "step_counters", ()))
        self._pending_counters = []
        for name in self._counter_names:
            self.stats[name] = 0
        if self._kind_stats:
            self.stats["window_pages_freed"] = 0
            for k in kinds:
                self.stats[f"{k.name}_pages_live"] = 0
                self.stats[f"kv_positions_least_{k.name}"] = 0
        if self._state_kinds:
            # slabs (a slot a layer) running requests hold now, and
            # those an admission or a replay restarted from zero
            self._slab_layers = sum(len(k.layers)
                                    for k in self._state_kinds)
            self.stats["state_slabs_live"] = 0
            self.stats["state_slabs_zeroed"] = 0
        # recent per-request phase timelines (reqtrace), appended at
        # first token / prefill export — the `metrics()` drill-down
        self._timelines = collections.deque(maxlen=64)
        # overload control plane (fleet_serving.overload): the brownout
        # caps dict is REPLACED whole by apply_brownout (GIL-atomic) and
        # read at host decision points only — never inside a trace
        self._brownout = {}
        self._spec_stash = None    # spec decoder parked by brownout L2
        self._deadlines_armed = False  # any deadline request ever seen
        # speculative decoding (draft_model configured): draft pools
        # mirror this pool's page ids, the big model verifies k+1
        # ragged positions per slot in one dispatch — the spec window
        # replaces the fused window for pure-decode ticks
        # (inference/speculative.py; late import: train-only use must
        # not drag the speculative machinery in)
        # structured decoding (inference/structured, docs/SERVING.md
        # "Structured decoding"): the grammar arena's device tables
        # thread through the fused/verify executables at an
        # engine-static shape — [grammar_states, vocab] when token_strs
        # is configured, the lone mask-identity row otherwise (so
        # engines that never see a constraint pay a few KB, not MB).
        # The compile cache is lock-guarded: `LLMServer.submit`
        # compiles grammars on the CALLER's thread (loud reject at
        # submit), while add_request may compile on the engine thread.
        self.spec_mode = cfg.spec_mode
        self.token_strs = (list(cfg.token_strs)
                           if cfg.token_strs is not None else None)
        if (self.token_strs is not None
                and len(self.token_strs) != mcfg.vocab_size):
            raise ValueError(
                f"token_strs has {len(self.token_strs)} entries but "
                f"the model vocab is {mcfg.vocab_size} — one surface "
                "string per token id")
        from .structured.arena import GrammarArena, GrammarCache

        self.grammar_arena = GrammarArena(
            mcfg.vocab_size,
            cfg.grammar_states if self.token_strs is not None else 1)
        self._grammar_cache = GrammarCache()
        self.stats["structured_requests"] = 0
        if cfg.draft_model is not None:
            from .speculative import SpeculativeDecoder

            self._spec = SpeculativeDecoder(self, cfg.draft_model,
                                            cfg.spec_k)
            _KV_POOL_BYTES.labels(dtype=self.kv_dtype).set(
                self.pool_bytes())
        elif cfg.spec_mode == "ngram":
            # draft-model-free speculation: the request's own token
            # history proposes into the same ragged verify executable
            # (inference/structured/ngram.py) — no draft pool, so
            # pool_bytes/brownout-L2 accounting are untouched
            from .structured.ngram import NgramSpeculator

            self._spec = NgramSpeculator(self, cfg.spec_k)

    @property
    def waiting(self):
        """The admission queue (fleet_serving.SLAScheduler). Supports
        len() / bool() / iteration; admission ORDER is the scheduler's
        (docs/SERVING.md), not necessarily arrival."""
        return self.sched

    # ---- structured decoding: the constraint surface ----

    def compile_constraint(self, grammar=None, json_schema=None,
                           eos_token_id=None):
        """Compile one per-request constraint to a `CompiledGrammar`,
        through the engine's hash-keyed cache (a hot schema compiles
        once per replica — `pt_structured_cache_hits` counts reuse).
        Thread-safe: `LLMServer.submit` calls this on the CALLER's
        thread so a bad grammar raises at submit() time, never inside
        the serve loop. Raises GrammarError (a ValueError) for
        unsupported syntax or a DFA over the arena budget."""
        from .structured import (GrammarError, compiler as _gcomp,
                                 schema_to_regex)

        if self.token_strs is None:
            raise GrammarError(
                ("json_schema=" if json_schema is not None
                 else "grammar=") +
                ": this engine has no token_strs — pass "
                "LLMEngineConfig(token_strs=[...]) to enable "
                "structured decoding")
        if isinstance(grammar, _gcomp.CompiledGrammar):
            if grammar.vocab != len(self.token_strs):
                raise GrammarError(
                    f"grammar=: CompiledGrammar vocab {grammar.vocab} "
                    f"!= engine vocab {len(self.token_strs)}")
            return grammar
        if eos_token_id is None:
            raise GrammarError(
                ("json_schema=" if json_schema is not None
                 else "grammar=") +
                ": constrained decoding needs eos_token_id= (the "
                "grammar decides WHEN the output is complete by "
                "unmasking eos in accepting states)")
        pattern = (grammar if grammar is not None
                   else schema_to_regex(json_schema))
        ck = (pattern, int(eos_token_id))
        hit = self._grammar_cache.lookup(ck)
        if hit is not None:
            _STRUCT_CACHE_HITS.inc()
            return hit
        # compile OUTSIDE the cache lock (pure host work, possibly
        # slow); a racing duplicate compile is wasted work, not
        # corruption — GrammarCache.insert keeps the first copy
        try:
            cg = _gcomp.compile_regex(
                pattern, self.token_strs, eos_id=int(eos_token_id),
                max_states=self.grammar_arena.capacity)
        except GrammarError:
            self._grammar_cache.reject()
            raise
        return self._grammar_cache.insert(ck, cg)

    def _resolve_constraint(self, grammar, json_schema, eos_token_id,
                            spec_mode):
        """add_request's ingress gate: structural validation (shared
        with every remote ingress), engine-context checks, and the
        grammar compile. Returns the CompiledGrammar or None."""
        from .structured import validate_constraints

        validate_constraints(grammar=grammar, json_schema=json_schema,
                             spec_mode=spec_mode)
        if spec_mode not in (None, "off") and spec_mode != (
                self.spec_mode or "off"):
            raise ValueError(
                f"spec_mode={spec_mode!r}: this engine runs "
                f"spec_mode={self.spec_mode!r} — speculation is an "
                "engine resource; per-request spec_mode can only "
                "opt OUT ('off') or restate the engine's mode")
        if grammar is None and json_schema is None:
            return None
        return self.compile_constraint(grammar=grammar,
                                       json_schema=json_schema,
                                       eos_token_id=eos_token_id)

    def _live_grammar_hashes(self):
        """Hashes of grammars still referenced by queued or running
        requests — what arena compaction must keep."""
        live = set()
        for r in self._slots:
            if r is not None and r.grammar is not None:
                live.add(r.grammar.hash)
        for r in self.sched:
            if r.grammar is not None:
                live.add(r.grammar.hash)
        return live

    def _grammar_args(self, rows):
        """Per-dispatch grammar arguments for the fused/verify
        executables: arena-ABSOLUTE DFA states [num_slots] (0 = the
        mask-identity row unconstrained slots ride) plus the committed
        device tables. Shapes are engine-static — grammar churn swaps
        values, never triggers a retrace. Without token_strs no
        request can EVER be constrained, so all three are None and the
        executables compile the pre-structured graph — engines outside
        the constraint surface pay zero trace or dispatch cost."""
        if self.token_strs is None:
            return None, None, None
        gst = np.zeros((self.num_slots,), np.int32)
        for slot, req in rows:
            if req.grammar is not None:
                gst[slot] = (self.grammar_arena.base_of(req.grammar)
                             + req.gstate)
        gtrans, gmask = self.grammar_arena.device_tables()
        return gst, gtrans, gmask

    # ---- client side ----

    def add_request(self, prompt, max_new_tokens=32, eos_token_id=None,
                    future=None, tenant="default", priority=None,
                    ttft_slo_s=None, temperature=0.0, top_p=1.0,
                    prefill_only=False, kv_import=None, trace=None,
                    deadline_s=None, session_id=None, grammar=None,
                    json_schema=None, spec_mode=None):
        """Enqueue one request. The disaggregated-serving knobs
        (docs/SERVING.md "Disaggregated fleet"):

        prefill_only  run chunked prefill up to the SAMPLING FRONTIER
                      (prompt_len - 1 tokens written) and resolve the
                      future to the exported
                      `fleet_serving.KVPagePayload` — no token is ever
                      sampled, so a prefill replica never steals a
                      decode window. max_new_tokens is ignored.
        kv_import     a KVPagePayload from another replica's
                      `export_kv_pages`: the request admits with its
                      prompt KV written from the payload (skipping that
                      prefill) and decodes from its frontier. Geometry
                      must match this engine's pool exactly — checked
                      loudly HERE, not with corrupt logits at serve
                      time.
        session_id    persistent-chat identity (docs/SERVING.md "KV
                      memory hierarchy"): the finished turn's trie
                      blocks — generated tokens included — stay
                      pinned-then-tiered so the next turn resumes from
                      its frontier instead of re-prefilling the
                      history. Sessions expire by TTL/LRU; brownout
                      L4 sheds pinning before any traffic is
                      refused.

        Structured decoding (docs/SERVING.md "Structured decoding"):

        grammar       a regex string (or pre-compiled
                      structured.CompiledGrammar) constraining the
                      OUTPUT tokens — compiled to a token-level DFA
                      masked inside the decode executables. Requires
                      LLMEngineConfig(token_strs=...) and an
                      eos_token_id; rejected loudly HERE otherwise.
        json_schema   a JSON-schema dict lowered to a grammar
                      (structured.schema_to_regex) — canonical
                      no-whitespace JSON output. Mutually exclusive
                      with grammar=.
        spec_mode     per-request speculation override: None inherits
                      the engine's mode; "off"/the engine's own mode
                      are accepted; asking for a mode the engine
                      doesn't run raises (speculation is an ENGINE
                      resource — a request can't conjure a draft
                      model)."""
        grammar_obj = self._resolve_constraint(grammar, json_schema,
                                               eos_token_id, spec_mode)
        if self._kind_stats and (prefill_only or kv_import is not None):
            raise ValueError(
                "prefill_only / kv_import: the KV wire carries one page "
                "geometry of keys and values a head; this model has "
                f"{[c.kind.name for c in self._caches]} (ROADMAP.md B-I)")
        toks = np.asarray(prompt).reshape(-1)
        if toks.size == 0:
            raise ValueError("empty prompt")
        if toks.size > self.max_model_len:
            raise ValueError(
                f"prompt length {toks.size} exceeds max_model_len "
                f"{self.max_model_len}")
        short = self._pool_too_small(int(toks.size))
        if short is not None:
            raise ValueError(
                f"prompt needs more KV pages than the "
                f"{short.kind.name!r} pool holds "
                f"({short.pool.num_pages - 1})")
        req = _Request(toks, max_new_tokens, eos_token_id, future,
                       tenant=tenant, priority=priority,
                       ttft_slo_s=ttft_slo_s, temperature=temperature,
                       top_p=top_p)
        # per-engine sampling stream: stable across preemption replays
        # (assigned once, BEFORE any admission), so a replayed sampled
        # request reproduces its original continuation
        req.sample_stream = next(self._sample_streams)
        req.target = min(req.prompt_len + req.max_new, self.max_model_len)
        if grammar_obj is not None:
            # load into the arena NOW (loud GrammarError at submit,
            # not mid-serve); the device tables refresh lazily at the
            # next window dispatch — a value swap, never a recompile
            req.grammar = grammar_obj
            try:
                self.grammar_arena.load(
                    grammar_obj, live=self._live_grammar_hashes())
            except Exception:
                self._grammar_cache.reject()
                raise
            _STRUCT_REQS.inc()
            self.stats["structured_requests"] += 1
        req.spec_off = spec_mode == "off"
        if session_id is not None and self.prefix_cache is not None:
            req.session_id = str(session_id)
            req._session_seen = self._touch_session(req.session_id)
        _REQS_TOTAL.inc()
        # trace identity: the caller's (router/server — already stamped
        # `queued` at the ingress), else the payload's (a disaggregated
        # hand-off continues the prefill side's trace), else fresh
        if trace is None and kv_import is not None:
            trace = _payload_trace(kv_import)
        req.trace = trace if trace is not None else _reqtrace.new_trace()
        req.trace.stamp("queued")   # no-op when the ingress stamped it
        # overload control plane (docs/SERVING.md "Overload and
        # degradation"): brownout ingress caps + the hard deadline. A
        # shed RESOLVES the future typed (never raises out of here —
        # the server loop and direct drivers share one contract).
        caps = self._brownout
        sp = caps.get("shed_priority")
        if sp is not None and req.priority >= int(sp):
            return self._shed_at_admit(req, "brownout")
        if not prefill_only:
            cap = caps.get("max_new_cap")
            if cap is not None:
                req.target = min(req.target,
                                 req.prompt_len + max(1, int(cap)))
        if deadline_s is not None:
            ds = float(deadline_s)
            if ds <= 0.0:   # expired before admission: reject at submit
                return self._shed_at_admit(req, "deadline")
            req.deadline_t = req.t_submit + ds
            self._deadlines_armed = True
        if kv_import is not None:
            self._check_import(req, kv_import)
            req._kv_import = kv_import
        if prefill_only:
            req.prefill_only = True
            req.target = req.prompt_len
            if req.prompt_len == 1:
                # nothing before the frontier: an empty export (the
                # decode side prefills the single prompt token itself)
                if not req.future.cancelled():
                    req.future.set_result(
                        self._empty_payload(toks, req.trace))
                return req
        elif req.target <= req.prompt_len:
            # zero budget (same contract as generate()): prompt echoes back
            if not req.future.cancelled():
                req.future.set_result(req.result_array())
            return req
        self.sched.enqueue(req)
        _QUEUE_DEPTH.set(len(self.sched))
        return req

    def has_work(self):
        return bool(self.waiting) or any(
            r is not None for r in self._slots)

    @property
    def mean_occupancy(self):
        s = self.stats["steps"]
        return self.stats["occupancy_sum"] / s if s else 0.0

    def compile_stats(self, check_donation=False):
        """Executable count of the decode step (the jit dispatch-cache
        size) — the zero-recompile-after-warmup probe the engine test
        asserts on.

        `check_donation=True` additionally re-lowers the decode step
        through the live compile-cache path and reports whether the
        donated kv pools (and int8 scale planes) actually aliased
        outputs in the executable — donation silently dropping is the
        measured-25%-slower PR-2 serving bug (docs/RESILIENCE.md).
        Adds a `"donation"` key: {"expected", "aliased", "held",
        "dropped"}.

        THREADING: the donation probe re-TRACES the decode step, and
        the trace body temporarily swaps the model's live parameter
        values for tracers — call it from the thread that owns the
        engine (direct-drive callers; or around, never during, an
        `LLMServer` loop tick). The plain `check_donation=False` form
        is read-only and always safe.
        """
        out = {"executables": self._step_fn.cache_size()}
        if self._fused_fn is not None:
            # ONE fused executable per (k, geometry) — window spill and
            # EOS mid-window ride arguments, never a re-trace
            out["fused_executables"] = self._fused_fn.cache_size()
        if self._spec is not None:
            # ONE verify executable per (spec_k, geometry) — narrow
            # windows ride the width/rem arguments, never a re-trace
            out["verify_executables"] = self._spec._verify_fn.cache_size()
        if not check_donation:
            return out
        from .. import analysis

        rep = analysis.analyze_step(self, check_donation=True)
        out["donation"] = rep.donation
        _DONATION_HELD.labels(step="paged_decode").set(
            1.0 if rep.donation["held"] else 0.0)
        if self._fused_fn is not None:
            frep = analysis.analyze_step(self, check_donation=True,
                                         which="fused")
            out["fused"] = {"donation": frep.donation,
                            "host_calls": frep.host_calls}
            _DONATION_HELD.labels(step="fused_decode").set(
                1.0 if frep.donation["held"] else 0.0)
        if self._spec is not None:
            vrep = analysis.analyze_step(self, check_donation=True,
                                         which="verify")
            out["verify"] = {"donation": vrep.donation,
                             "host_calls": vrep.host_calls}
            _DONATION_HELD.labels(step="spec_verify").set(
                1.0 if vrep.donation["held"] else 0.0)
            # BOTH kv pytrees of the speculative contract: the draft
            # propose scan donates the draft pools + shared key too.
            # The n-gram speculator has no propose executable (its
            # proposals are host-mined), so only the verify probe
            # applies there.
            if getattr(self._spec, "_propose_fn", None) is not None:
                prep = analysis.analyze_step(self, check_donation=True,
                                             which="propose")
                out["propose"] = {"donation": prep.donation,
                                  "host_calls": prep.host_calls}
                _DONATION_HELD.labels(step="spec_propose").set(
                    1.0 if prep.donation["held"] else 0.0)
        return out

    def reseed(self, seed):
        """Swap the sampling PRNG key. The key is a step ARGUMENT (not
        a baked constant), so this never recompiles — pinned by the
        recompile probe in tests/test_fused_decode.py."""
        from ..distributed import mesh as mesh_mod

        self._seed = int(seed)
        self._key = jax.device_put(
            jax.random.PRNGKey(self._seed), mesh_mod.named_sharding())

    def pool_bytes(self):
        """Resident KV pool bytes across layers — int8 scale planes
        and the speculative draft pool included (a shared page costs
        big-bytes + draft-bytes; docs/SERVING.md has the sizing)."""
        total = int(sum(int(a.nbytes) for a in self._kv)
                    + sum(int(s.nbytes) for s in self._kv_scales))
        if self._spec is not None:
            total += self._spec.pool_bytes()
        return total

    # ---- disaggregated serving: KV-page export / import ----
    # (fleet_serving.kv_transfer; docs/SERVING.md "Disaggregated
    # fleet"). Both run on the thread that owns the engine — they read/
    # replace the donated pool arrays, so calling them while a step is
    # dispatching from another thread would race the donation.

    def export_kv_pages(self, req):
        """Cut the request's KV pages (every layer pool + scale plane,
        byte-for-byte, the partially-filled frontier page included)
        into a `fleet_serving.KVPagePayload`. The request keeps its
        pages — export is a read.

        The device gather runs at the FIXED `pages_per_seq` width
        (pad index 0 = the trash page, rows sliced off on the host):
        a per-page-count gather shape would compile one executable
        per distinct prompt length — a mid-traffic stall on exactly
        the prefill-storm path the disaggregation exists to protect."""
        from .fleet_serving.kv_transfer import KVPagePayload

        n = len(req.pages)
        kv, scales = self._gather_pages(req.pages)
        self.stats["kv_pages_exported"] = (
            self.stats.get("kv_pages_exported", 0) + n)
        req.trace.stamp("kv_export")
        return KVPagePayload(np.asarray(req.tokens, np.int32),
                             req.n_prefilled, self.page_size,
                             self.kv_dtype, kv, scales,
                             trace=req.trace.to_dict())

    def _gather_pages(self, page_ids):
        """ONE batched D2H gather of `page_ids` rows from every layer
        pool + scale plane, at the FIXED `pages_per_seq` width (pad
        index 0 = the trash page, rows sliced off on the host): the
        shared primitive of request export, trie-node spill, and
        hot-prefix migration — one gather shape, one executable,
        whatever the page count. Returns (kv, scales) owned host
        arrays (the PR-14 snapshot half: safe to hand to a background
        thread while the pool reuses the pages)."""
        n = len(page_ids)
        ids_np = np.zeros((self.pages_per_seq,), np.int32)
        ids_np[:n] = page_ids
        ids = jnp.asarray(ids_np)
        # ONE batched host transfer for all pools + scale planes (a
        # per-pool device_get would serialize 2L+ round trips inside
        # the serve loop, on the prefill-storm path)
        gathered = jax.device_get([p[ids] for p in self._kv]
                                  + [s[ids] for s in self._kv_scales])
        kv = [np.ascontiguousarray(a[:n])
              for a in gathered[:len(self._kv)]]
        scales = [np.ascontiguousarray(a[:n])
                  for a in gathered[len(self._kv):]]
        return kv, scales

    def import_kv_pages(self, payload, **kw):
        """Admit one request whose prompt KV arrives pre-computed (a
        prefill replica's `export_kv_pages`). The payload's tokens are
        the prompt; decoding starts at its frontier, so the first tick
        samples the first generated token without re-running the
        prompt. Accepts the `add_request` keyword surface."""
        return self.add_request(payload.tokens, kv_import=payload, **kw)

    def _empty_payload(self, toks, trace=None):
        from .fleet_serving.kv_transfer import KVPagePayload

        if trace is not None:
            trace.stamp("kv_export")
        return KVPagePayload(
            toks, 0, self.page_size, self.kv_dtype,
            [np.zeros((0,) + p.shape[1:], np.asarray(p[:0]).dtype)
             for p in self._kv],
            [np.zeros((0,) + s.shape[1:], np.float32)
             for s in self._kv_scales],
            trace=trace.to_dict() if trace is not None else None)

    def _check_import(self, req, payload):
        """Loud geometry validation at submit time (an import that
        reinterprets pages under a different page_size / kv_dtype /
        head layout would serve garbage logits, not an error)."""
        if payload.page_size != self.page_size:
            raise ValueError(
                f"kv_import page_size {payload.page_size} != engine "
                f"page_size {self.page_size}")
        if payload.kv_dtype != self.kv_dtype:
            raise ValueError(
                f"kv_import kv_dtype {payload.kv_dtype!r} != engine "
                f"kv_dtype {self.kv_dtype!r} (pools must match "
                "byte-for-byte; re-prefill instead)")
        if len(payload.kv) != len(self._kv):
            raise ValueError(
                f"kv_import carries {len(payload.kv)} pools, engine "
                f"has {len(self._kv)} (different num_layers?)")
        if len(payload.scales) != len(self._kv_scales):
            raise ValueError(
                "kv_import scale planes do not match the engine pool "
                f"({len(payload.scales)} vs {len(self._kv_scales)})")
        # EVERY pool and scale plane, not just kv[0]: a ragged payload
        # (per-layer page counts or a mis-shaped scale plane) must be
        # rejected here — failing later inside _write_imported_pages
        # would abort the whole serve loop (and every co-resident
        # request) for one bad payload. ALL mismatches ride one error:
        # a ragged payload usually disagrees in several pools at once,
        # and the first-mismatch-only message made the operator fix
        # and resubmit once per pool (satellite fix, ISSUE 17)
        n_pages = payload.num_pages
        bad = []
        for i, a in enumerate(payload.kv):
            want = (n_pages,) + tuple(self._kv[i].shape[1:])
            if tuple(a.shape) != want:
                bad.append(f"pool {i} shape {tuple(a.shape)} != {want}")
        for i, a in enumerate(payload.scales):
            want = (n_pages,) + tuple(self._kv_scales[i].shape[1:])
            if tuple(a.shape) != want:
                bad.append(f"scale plane {i} shape {tuple(a.shape)} "
                           f"!= {want}")
        if bad:
            raise ValueError(
                f"kv_import geometry mismatch (engine page geometry "
                f"x {n_pages} pages), {len(bad)} failing arrays: "
                + "; ".join(bad))
        if not 0 <= payload.n_prefilled <= req.prompt_len - 1:
            raise ValueError(
                f"kv_import n_prefilled {payload.n_prefilled} outside "
                f"[0, prompt_len-1] ({req.prompt_len - 1}): the decode "
                "side owns the frontier token")
        need = -(-payload.n_prefilled // self.page_size)
        if payload.num_pages != need:
            raise ValueError(
                f"kv_import ships {payload.num_pages} pages but "
                f"n_prefilled {payload.n_prefilled} needs {need}")

    def _write_imported_pages(self, page_ids, payload):
        """Write the payload's page rows into this engine's pools at
        freshly-allocated page ids — byte-for-byte (no dequant/requant:
        the parity the wire test pins). Replaces the pool arrays;
        re-committed to the pools' sharding so the next compiled-step
        dispatch sees the SAME placement signature (a committed/
        uncommitted flip would cost a second executable). Like the
        export gather, the scatter runs at the FIXED `pages_per_seq`
        width — pad rows land in trash page 0, whose rows are never
        attended — so every import reuses ONE compiled scatter instead
        of one per distinct page count (a mid-traffic compile stall on
        the decode tier's admission path)."""
        if not page_ids:
            return
        from ..distributed import mesh as mesh_mod
        from .fleet_serving.kv_transfer import _KV_PAGES_STREAMED

        sharding = mesh_mod.named_sharding()
        n = len(page_ids)
        ids_np = np.zeros((self.pages_per_seq,), np.int32)
        ids_np[:n] = page_ids
        ids = jnp.asarray(ids_np)

        def pad(rows):
            out = np.zeros((self.pages_per_seq,) + rows.shape[1:],
                           rows.dtype)
            out[:n] = rows
            return jnp.asarray(out)

        updated = [pool.at[ids].set(pad(rows))
                   for pool, rows in zip(self._kv, payload.kv)]
        updated += [plane.at[ids].set(pad(rows))
                    for plane, rows in zip(self._kv_scales,
                                           payload.scales)]
        # one batched placement for the whole pytree (mirrors the
        # export-side batching)
        placed = jax.device_put(updated, sharding)
        self._kv = placed[:len(self._kv)]
        self._kv_scales = placed[len(self._kv):]
        self.stats["kv_pages_imported"] = (
            self.stats.get("kv_pages_imported", 0) + len(page_ids))
        _KV_PAGES_STREAMED.inc(len(page_ids))

    # ---- hierarchical KV memory (fleet_serving.kv_tier, ISSUE 17) ----

    def _spill_node(self, node):
        """RadixPrefixCache spill hook: one dying trie node's pages
        D2H (synchronous snapshot through the SAME fixed-width gather
        export uses — the pages are reused the moment `_drop` frees
        them) and into the tier's spill queue (asynchronous commit —
        pack + index + disk never touch the engine thread). Keyed by
        the node's FULL token prefix; the payload carries only the
        node's own block pages (parents are separate entries).
        Swallows its own failures: eviction is relieving pool
        pressure, a lost spill only re-costs the re-prefill."""
        from .fleet_serving.kv_transfer import KVPagePayload
        from .fleet_serving.kv_tier import _TIER_EVICTIONS, prefix_key

        try:
            blocks = []
            n = node
            while n.block is not None:
                blocks.append(n.block)
                n = n.parent
            blocks.reverse()
            toks = np.asarray([t for blk in blocks for t in blk],
                              np.int32)
            kv, scales = self._gather_pages(node.pages)
            payload = KVPagePayload(toks, int(toks.size),
                                    self.page_size, self.kv_dtype,
                                    kv, scales)
            _TIER_EVICTIONS.labels(tier="hbm").inc(len(node.pages))
            if self.kv_tier.put(prefix_key(toks), payload):
                self._spill_count += 1
                self.stats["kv_pages_spilled"] = (
                    self.stats.get("kv_pages_spilled", 0)
                    + len(node.pages))
        except Exception:   # never block the eviction path
            self.stats["kv_spill_errors"] = (
                self.stats.get("kv_spill_errors", 0) + 1)

    def _prefetch_tier(self, req, cached, pages):
        """Extend a trie match from the spill tiers: for each block
        past the trie frontier whose prefix the tier holds, allocate
        fresh pages, scatter the frame H2D through the SAME fixed-width
        import executable (`_write_imported_pages` — zero recompiles),
        and re-insert the node so the request (and everyone after it)
        maps it as an ordinary trie hit. Stops at the first tier miss,
        a dry pool, or block `pages_per_seq` coverage. Returns the
        extended (cached, pages); `pages` grows by the engine's OWN
        alloc references (released through the ordinary request-page
        path, exactly like match()'s share references)."""
        from .fleet_serving.kv_tier import prefix_key

        bt = self.prefix_cache.block_tokens
        ppb = self.prefix_cache.pages_per_block
        toks = req.tokens
        hit = False
        while (cached + bt <= len(toks)
               and (len(pages) + ppb) <= self.pages_per_seq):
            payload = self.kv_tier.get(prefix_key(toks[:cached + bt]))
            if payload is None:      # tier miss (or a rotten frame)
                break
            new_pages = []
            try:
                for _ in range(ppb):
                    new_pages.append(self._caches[0].alloc())
            except PoolExhausted:
                # prefetch must never starve the request's own prompt
                # pages — give back and serve what we have
                self.prefix_cache.pool.free(new_pages)
                break
            self._write_imported_pages(new_pages, payload)
            self.prefix_cache.insert(toks[:cached + bt],
                                     pages + new_pages)
            pages.extend(new_pages)
            cached += bt
            hit = True
            self.stats["kv_pages_prefetched"] = (
                self.stats.get("kv_pages_prefetched", 0) + ppb)
        if hit:
            req.trace.stamp("kv_prefetch")
        return cached, pages

    def export_prefix(self, tokens):
        """Cut the trie's longest cached prefix of `tokens` into a
        `KVPagePayload` — the cross-replica migration source (router
        `_migrate`; docs/SERVING.md "KV memory hierarchy"). The
        payload satisfies the kv_import frontier contract for a
        request with these exact tokens (n_prefilled <= len-1, page
        count exact), so the pulling replica admits it through the
        ordinary import scatter — zero recompiles on either engine —
        and publishes it into ITS trie at the first window boundary.
        Returns None when nothing is cached. Engine-thread only (rides
        the LLMServer control queue)."""
        if self.prefix_cache is None:
            return None
        from .fleet_serving.kv_transfer import KVPagePayload

        toks = np.asarray(tokens).reshape(-1)
        cached, pages = self.prefix_cache.match(toks)
        bt = self.prefix_cache.block_tokens
        # the import contract leaves the frontier token to the decode
        # side: a fully-covered prompt exports one block less
        while pages and cached >= toks.size:
            cached -= self.prefix_cache.cow_split(pages)
        if not pages:
            return None
        kv, scales = self._gather_pages(pages)
        self.prefix_cache.pool.free(pages)   # match()'s share refs
        self.stats["kv_pages_migrated_out"] = (
            self.stats.get("kv_pages_migrated_out", 0) + cached // bt
            * self.prefix_cache.pages_per_block)
        return KVPagePayload(toks, cached, self.page_size,
                             self.kv_dtype, kv, scales)

    # ---- persistent chat sessions (ISSUE 17) ----

    def _touch_session(self, sid):
        """Create/refresh one session entry; TTL/LRU-expire the rest.
        Returns True when the session already existed (a RETURNING
        turn — the resume-telemetry precondition). Engine thread (and
        add_request callers driving the engine directly)."""
        from .fleet_serving.kv_tier import _SESSION_ACTIVE

        now = _time.perf_counter()
        seen = sid in self._sessions
        ent = self._sessions.pop(sid, None) or {"turns": 0}
        ent["last_used"] = now
        self._sessions[sid] = ent
        # cheap sweep at the LRU head: expiry only ever drops the
        # TRACKING entry — the session's KV ages out through the
        # ordinary trie-LRU -> tier-LRU path like any other prefix
        while self._sessions:
            head = next(iter(self._sessions))
            if (len(self._sessions) > self.session_max
                    or (now - self._sessions[head]["last_used"]
                        > self.session_ttl_s)):
                del self._sessions[head]
            else:
                break
        _SESSION_ACTIVE.set(len(self._sessions))
        return seen

    def _publish_session(self, req):
        """Pin a finished session turn: insert EVERY full block of the
        final token sequence — generated tokens included, unlike the
        prompt-only `_publish_prefix` — so the next turn (whose prompt
        embeds this turn's history) resumes from the conversation
        frontier. The trie holds the reference after `_release` frees
        the request's own ('pinned'); under pool pressure the blocks
        spill to the tier like any node ('tiered')."""
        if (req.session_id is None or self.prefix_cache is None
                or self._brownout.get("session_pin", True) is False):
            return
        bt = self.hash_block_tokens
        ppb = self.prefix_cache.pages_per_block
        nb = req.n_prefilled // bt     # only KV-written rows publish
        if nb:
            self.prefix_cache.insert(req.tokens[:nb * bt],
                                     req.pages[:nb * ppb])
        ent = self._sessions.get(req.session_id)
        if ent is not None:
            ent["turns"] += 1

    def _finish_prefill(self, slot, req):
        """Retire a prefill-only request AT its frontier: export the
        payload, release the slot/pages, resolve the future to the
        payload (docs/SERVING.md "Disaggregated fleet")."""
        req.trace.stamp("prefill_end")
        payload = self.export_kv_pages(req)
        self._note_timeline(req)
        self._release(slot, req)
        self.stats["finished"] += 1
        self.stats["prefill_exports"] = (
            self.stats.get("prefill_exports", 0) + 1)
        _FINISHED_TOTAL.inc()
        if not req.future.cancelled():
            req.future.set_result(payload)

    def _note_timeline(self, req):
        """Record the request's phase timeline (reqtrace) for the
        `metrics()["recent_requests"]` drill-down. Quiet traces
        (warm-up requests — their prefill segment is an XLA compile
        stall, not serving latency) stay out of the view."""
        if req.trace.quiet:
            return
        self._timelines.append({
            "rid": req.rid, "trace_id": req.trace.trace_id,
            "phases": req.trace.timeline(),
            # unrounded like the timeline's dt_s: the exported
            # invariant is sum(dt_s) == total_s (to float addition
            # error) — rounding one side would break it by up to 5e-7
            "total_s": req.trace.total_s()})

    def kv_fragmentation(self):
        """Internal fragmentation of the live KV pages: unwritten
        slots / (live pages × page_size). High values mean many
        sequences holding mostly-empty tail pages (page_size too big
        for the workload). Counted as per-request tail waste — NOT as
        1 − Σ n_prefilled / capacity, which double-counts shared-prefix
        tokens once per sharer and pins the gauge to 0 exactly when the
        prefix cache is busiest. Unwritten slots live only in a
        request's PRIVATE tail pages (shared and trie pages are full by
        construction), so the sum never double-counts."""
        first = self._caches[0]
        cap = first.pool.num_live * self.page_size
        if not cap:
            return 0.0
        waste = sum(first.covered(r) - r.n_prefilled
                    for r in self._slots if r is not None)
        return max(0.0, waste / cap)

    def _page_occupancy(self):
        """Share of the FULLER page pool in use (what the
        `kv_page_occupancy` metric and gauge report)."""
        return max(c.pool.num_live / (c.pool.num_pages - 1)
                   for c in self._caches)

    def _publish_load(self):
        """The whole-engine load gauges, after a window or a release:
        the slots held (a window's frontier rows AND a chunk-prefilling
        straggler), the pages in use, the tail waste."""
        live = sum(r is not None for r in self._slots)
        _LIVE_SLOTS.set(live)
        _SLOT_OCC.set(live / self.num_slots)
        _PAGE_OCC.set(self._page_occupancy())
        _PAGE_FRAG.set(self.kv_fragmentation())

    def metrics(self):
        """Live engine view + the process-global serving counters from
        the telemetry registry (docs/OBSERVABILITY.md) — what
        `LLMServer.metrics()` and the bench's llm_serve arm report."""
        live = sum(r is not None for r in self._slots)
        return {
            "queue_depth": len(self.waiting),
            "live_slots": live,
            "num_slots": self.num_slots,
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": self.pool_bytes(),
            "slot_occupancy": live / self.num_slots,
            "mean_slot_occupancy": self.mean_occupancy,
            "kv_page_occupancy": self._page_occupancy(),
            "kv_fragmentation": self.kv_fragmentation(),
            "kv_pages_shared": self._caches[0].pool.num_shared,
            "prefix_cache": (self.prefix_cache.snapshot()
                             if self.prefix_cache is not None else None),
            "sched": self.sched.snapshot(),
            "requests": int(_REQS_TOTAL.value),
            "finished": int(_FINISHED_TOTAL.value),
            "preemptions": int(_PREEMPTIONS_TOTAL.value),
            "steps": int(_STEPS_TOTAL.value),
            "aborts": int(_ABORTS_TOTAL.value),
            "prefill_tokens":
                int(_TOKENS_TOTAL.labels(phase="prefill").value),
            "decode_tokens":
                int(_TOKENS_TOTAL.labels(phase="decode").value),
            "decode_k": self.decode_k,
            "spec": self._spec_metrics(),
            "ngram": self._ngram_metrics(),
            "structured": self._structured_metrics(),
            "fused_steps": int(_FUSED_STEPS.value),
            "dispatches": int(_DISPATCHES.value),
            "paged_attn_mxu_launches":
                self.stats["paged_attn_mxu_launches"],
            "paged_attn_vpu_launches":
                self.stats["paged_attn_vpu_launches"],
            "tokens_per_dispatch": _TOK_PER_DISPATCH.value,
            "admission_p50_s": _ADMIT_SECONDS.quantile(0.5),
            "admission_p99_s": _ADMIT_SECONDS.quantile(0.99),
            "ttft_p50_s": _TTFT_SECONDS.quantile(0.5),
            "ttft_p95_s": _TTFT_SECONDS.quantile(0.95),
            "ttft_p99_s": _TTFT_SECONDS.quantile(0.99),
            "request_tok_per_s_p50": _REQ_TOK_RATE.quantile(0.5),
            # TTFT decomposition (observability.reqtrace): per-phase
            # percentiles + the last requests' full timelines
            "request_phase_seconds": _reqtrace.phase_summary(),
            "recent_requests": list(self._timelines),
            "executables": self._step_fn.cache_size(),
            "kv_tier": self._tier_metrics(),
            "sessions": {"active": len(self._sessions),
                         "resumed": self.stats.get("sessions_resumed",
                                                   0),
                         "shed": self.stats.get("sessions_shed", 0)},
        }

    def _tier_metrics(self):
        """kv_tier block of `metrics()`: None without a tier; else the
        store snapshot, with the hbm rung's gauges published alongside
        (the tier store only sees ram/disk — the device pool IS the
        top rung, so its live-page footprint reports here)."""
        if self.kv_tier is None:
            return None
        from .fleet_serving.kv_tier import _TIER_BYTES, _TIER_PAGES

        pool = self.prefix_cache.pool
        live = pool.num_live
        per_page = self.pool_bytes() / max(1, pool.num_pages)
        _TIER_PAGES.labels(tier="hbm").set(live)
        _TIER_BYTES.labels(tier="hbm").set(int(live * per_page))
        return self.kv_tier.snapshot()

    def _spec_metrics(self):
        """Speculative-decoding block of `metrics()`: None without a
        draft model; else the window/acceptance view (counters are
        PROCESS-cumulative — docs/OBSERVABILITY.md; the per-engine
        window/proposed/accepted splits ride `stats`). The n-gram
        speculator reports under the `ngram` block instead — its
        counters are a different family."""
        if self._spec is None or getattr(self._spec, "mode",
                                         "draft") != "draft":
            return None
        from .speculative import (_SPEC_ACCEPTED, _SPEC_DRAFT_SECONDS,
                                  _SPEC_PROPOSED)

        proposed = _SPEC_PROPOSED.value
        return {
            "spec_k": self._spec.k,
            "windows": self.stats.get("spec_windows", 0),
            "proposed": int(proposed),
            "accepted": int(_SPEC_ACCEPTED.value),
            "acceptance_rate": (
                _SPEC_ACCEPTED.value / proposed if proposed else None),
            "draft_seconds": round(float(_SPEC_DRAFT_SECONDS.value), 4),
            "draft_pool_bytes": self._spec.pool_bytes(),
        }

    def _ngram_metrics(self):
        """n-gram speculation block of `metrics()`: None unless this
        engine runs spec_mode='ngram'."""
        if getattr(self._spec, "mode", None) != "ngram":
            return None
        proposed = self.stats.get("ngram_proposed", 0)
        accepted = self.stats.get("ngram_accepted", 0)
        return {
            "spec_k": self._spec.k,
            "windows": self.stats.get("ngram_windows", 0),
            "proposed": int(proposed),
            "accepted": int(accepted),
            "acceptance_rate": (accepted / proposed if proposed
                                else None),
        }

    def _structured_metrics(self):
        """Structured-decoding block of `metrics()`: None unless the
        engine has token_strs (the constraint surface enabled).
        Engine-local counts — the `pt_structured_*` counters are
        process-cumulative across every engine in the process."""
        if self.token_strs is None:
            return None
        gc = self._grammar_cache.snapshot()
        return {
            "grammars_resident": len(self.grammar_arena._loaded),
            "states_used": self.grammar_arena.states_used,
            "state_budget": self.grammar_arena.n_states,
            "requests": self.stats.get("structured_requests", 0),
            "compiles": gc["compiles"],
            "cache_hits": gc["cache_hits"],
            "rejects": gc["rejects"],
        }

    def abort_all(self, exc):
        """Fail every live and queued request (device-error path),
        release all pages, and re-zero the pools — a step that died
        mid-donation leaves the old kv buffers deleted, so the engine
        must not reuse them."""
        try:
            from ..observability import flight_recorder as _fr

            _fr.dump("engine_abort", error=repr(exc), inflight=[
                {"rid": r.rid, "trace_id": r.trace.trace_id}
                for r in self._slots if r is not None])
        except Exception:  # ptlint: disable=PTL804 (the guard wraps the flight-recorder dump itself)
            pass
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._release(slot, req)
                if not req.future.done():
                    req.future.set_exception(exc)
        for req in self.sched.drain():
            if not req.future.done():
                req.future.set_exception(exc)
        if self.prefix_cache is not None:
            # the re-zeroed pools invalidate every cached KV page — a
            # stale trie mapping would serve zeros as a system prompt
            self.prefix_cache.clear()
        self._kv, self._kv_scales = self._fresh_pools()
        if self._spec is not None:
            # the draft pools ride their own donated pytree through the
            # draft executables — same consumed-buffer hazard
            self._spec.reset_pools()
        # the PRNG key rides the SAME donated pytree as the pools — a
        # consumed key leaf would wedge the recovered engine on its
        # next dispatch ("Array has been deleted")
        self.reseed(self._seed)
        _ABORTS_TOTAL.inc()
        _QUEUE_DEPTH.set(0)
        self._publish_load()

    def abort(self, request_id, reason="client", exc=None,
              counted=False):
        """Evict ONE request (client cancel / deadline expiry) wherever
        it lives. A slot occupant releases through `_release` — pool
        pages decref (shared trie pages keep the trie's own reference;
        the request's pins go), the page-table row zeroes, and its
        draft-pool rows need no touch (keyed by slot, overwritten by
        the next occupant's catch-up). A queued request leaves the
        scheduler with exact class/SLO bookkeeping (`sched.remove`).
        The future resolves with `exc` (default: RequestCancelled)
        unless already done. Returns False when the id is unknown —
        already finished — and touches nothing. Co-resident requests
        are unperturbed: no pool re-zero, no reseed, no executable
        churn (contrast `abort_all`). `counted=True` means the caller
        (the router's `cancel`) already counted this cancellation —
        pt_requests_cancelled_total stays exact, one per request."""
        rid = int(request_id)
        for slot, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                self._release(slot, req)
                self._resolve_cancel(req, reason, exc, counted=counted)
                self._publish_load()
                return True
        for req in list(self.sched):
            if req.rid == rid:
                if not self.sched.remove(req):
                    return False
                self._resolve_cancel(req, reason, exc, counted=counted)
                _QUEUE_DEPTH.set(len(self.sched))
                return True
        return False

    def _resolve_cancel(self, req, reason, exc=None, counted=False):
        """Shared tail of every cancellation path: count, stamp the
        phase timeline, flight-record (trace_id rides the event), and
        resolve the client future typed."""
        if not counted:
            note_cancelled(reason)
        req.trace.stamp("cancelled")
        self._note_timeline(req)
        try:
            from ..observability import flight_recorder as _fr

            _fr.record_event("request_cancelled", rid=req.rid,
                             trace_id=req.trace.trace_id, reason=reason)
        except Exception:  # ptlint: disable=PTL804 (the guard wraps the trace event itself)
            pass
        if not req.future.done():
            req.future.set_exception(
                exc if exc is not None
                else RequestCancelled(reason=reason,
                                      trace_id=req.trace.trace_id))

    def _shed_at_admit(self, req, reason):
        """Typed admission refusal (add_request): the future RESOLVES
        with RequestShed — no fleet work was consumed, nothing to
        release. Returns the request (add_request's contract)."""
        note_shed(reason)
        try:
            from ..observability import flight_recorder as _fr

            _fr.record_event("request_shed", rid=req.rid,
                             trace_id=req.trace.trace_id, reason=reason)
        except Exception:  # ptlint: disable=PTL804 (the guard wraps the trace event itself)
            pass
        if not req.future.done():
            req.future.set_exception(
                RequestShed(reason, trace_id=req.trace.trace_id))
        return req

    def _expire_deadlines(self):
        """Cancel every live/queued request whose hard deadline passed
        (top of step(), armed only once a deadline request exists)."""
        now = _time.perf_counter()
        hit = False
        for slot, req in enumerate(self._slots):
            if (req is not None and req.deadline_t is not None
                    and now > req.deadline_t):
                self._release(slot, req)
                self._resolve_cancel(req, "deadline")
                hit = True
        stale = [r for r in self.sched
                 if r.deadline_t is not None and now > r.deadline_t]
        for req in stale:
            if self.sched.remove(req):
                self._resolve_cancel(req, "deadline")
                hit = True
        if hit:
            self._publish_load()
            _QUEUE_DEPTH.set(len(self.sched))

    # ---- brownout (fleet_serving.overload) ----

    def apply_brownout(self, caps):
        """Install the fleet's brownout caps (BrownoutController
        apply_fn; {} = full service). Runs on the router monitor
        thread: the dict is replaced WHOLE (GIL-atomic) and read at
        host decision points only (admission caps, window clamps); the
        spec park/restore transition runs on the engine thread at the
        top of step() (`_sync_brownout`) — the draft pytree is only
        ever touched by the thread that dispatches on it."""
        self._brownout = dict(caps)

    def _sync_brownout(self):
        """Engine-thread half of the ladder's L2: park the speculative
        decoder and RELEASE its draft pool (the HBM returns to the
        fleet now, not at the next GC), or restore it — `reset_pools`
        rebuilds zeroed pools and the slots' draft_prefilled reset
        makes the next window's catch-up replay the draft KV."""
        caps = self._brownout
        enabled = caps.get("spec_enabled", True)
        if self._spec is not None and enabled is False:
            self._spec_stash, self._spec = self._spec, None
            self._spec_stash.release_pools()
            _KV_POOL_BYTES.labels(dtype=self.kv_dtype).set(
                self.pool_bytes())
        elif (self._spec is None and self._spec_stash is not None
                and enabled):
            self._spec, self._spec_stash = self._spec_stash, None
            self._spec.reset_pools()
            for r in self._slots:
                if r is not None:
                    r.draft_prefilled = 0   # draft pool is cold: replay
            _KV_POOL_BYTES.labels(dtype=self.kv_dtype).set(
                self.pool_bytes())
        # ladder L4: shed session pinning BEFORE shedding traffic —
        # only the TRACKING entries drop (future turns stop resuming);
        # already-pinned trie blocks age out through ordinary trie LRU
        if caps.get("session_pin", True) is False and self._sessions:
            from .fleet_serving.kv_tier import _SESSION_ACTIVE

            self.stats["sessions_shed"] = (
                self.stats.get("sessions_shed", 0)
                + len(self._sessions))
            self._sessions.clear()
            _SESSION_ACTIVE.set(0)

    def close(self):
        """Retire the engine: drop the prefix trie (its clear()
        publishes the NEGATIVE resident-pages delta, so a process that
        cycles engines doesn't leave pt_prefix_cache_resident_pages
        permanently inflated by gc'd tries). Idempotent; the engine
        stays usable — the trie just starts cold."""
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        if self.kv_tier is not None:
            self.kv_tier.close()

    # ---- scheduler ----

    def _admit_slabs(self, req):
        """A request takes its slot's slabs of every state kind: they
        restart from ZERO. Nothing is dispatched for it: the request's
        first row stands at position 0 (checked here: nothing may map a
        prefix or import pages beside a state kind), and the model's
        step starts a slot's state from zero at such a row
        (serving_protocol.py), whatever a finished or preempted request
        left in the slab."""
        if req.n_prefilled or req.cached_prefix:
            raise RuntimeError(
                f"request {req.rid} joins at position {req.n_prefilled}: "
                "a state slab can only be rebuilt from position 0")
        self.stats["state_slabs_zeroed"] += self._slab_layers
        self.stats["state_slabs_live"] += self._slab_layers

    def _release(self, slot, req):
        for c in self._caches:
            c.release(slot, req)
        if self._state_kinds:
            # the slab goes with the slot; a preempted request's replay
            # rebuilds it from position 0 (snapshots: ROADMAP.md B-I.6)
            self.stats["state_slabs_live"] -= self._slab_layers
        req.n_prefilled = 0
        req.draft_prefilled = 0   # preemption replay re-prefills BOTH pools
        req.cached_prefix = 0
        req.published_blocks = 0
        req.slot = None
        self._slots[slot] = None
        self._slot_gen += 1  # membership changed: staged arrays stale

    def _finish(self, slot, req):
        # session pinning reads req.pages — must precede the release
        self._publish_session(req)
        self._release(slot, req)
        self.stats["finished"] += 1
        _FINISHED_TOTAL.inc()
        if req.t_first_admit is not None and req.num_generated:
            dt = _time.perf_counter() - req.t_first_admit
            if dt > 0:
                _REQ_TOK_RATE.observe(req.num_generated / dt)
        # a client may have cancel()ed while the request was in flight —
        # set_result would raise InvalidStateError and the server loop
        # would read that as a device error and abort EVERYONE
        if not req.future.cancelled():
            req.future.set_result(req.result_array())

    def _preempt(self, slot, req, reason):
        """Evict-and-requeue one RUNNING sequence (the explicit
        preemption path: pool/slot exhaustion never surfaces as
        `PoolExhausted` while a lower-priority victim exists). The
        already-generated tokens are kept: greedy re-decode of
        prompt+generated reproduces the same continuation, so a
        preempted request stays deterministic — and with the prefix
        cache on, its replayed prefill re-hits the trie."""
        self._release(slot, req)
        req.preemptions += 1
        self.stats["preemptions"] += 1
        _PREEMPTIONS_TOTAL.inc()
        self.sched.note_preemption(reason)
        self.sched.push_front(req)

    def _preempt_one(self, keep_req, worse_than=None, reason="pool",
                     allow_equal=False):
        """Preempt the scheduler's victim pick (lowest priority class,
        then youngest). Returns False when there is no victim (or none
        `worse_than` allows)."""
        pick = self.sched.pick_victim(
            self._slots, keep=keep_req, worse_than=worse_than,
            now=_time.perf_counter(), allow_equal=allow_equal)
        if pick is None:
            return False
        self._preempt(*pick, reason=reason)
        return True

    def _grow(self, slot, req, n):
        """Every cache kind takes the pages that the next `n` positions
        of `req` need. Returns how many of them EVERY kind's pages then
        cover: fewer than n when a pool ran dry (what was taken stays
        with the request; a window narrows to it, `_plan` preempts)."""
        try:
            for c in self._caches:
                c.grow(slot, req, req.n_prefilled, req.n_prefilled + n - 1)
            return n
        except PoolExhausted:
            return min(n, min(c.covered(req) for c in self._caches)
                       - req.n_prefilled)

    def _pool_too_small(self, n_tokens):
        """The cache kind whose WHOLE pool could not admit a sequence of
        `n_tokens`, or None."""
        return next((c for c in self._caches
                     if c.pages_to_admit(n_tokens, self.token_budget)
                     > c.pool.num_pages - 1), None)

    def _map_prefix(self, req):
        """Match the request's tokens against the radix trie and map
        the shared pages. Returns the mapped page list (the request now
        holds one pool reference per page); `req.cached_prefix` tokens
        of prefill will be SKIPPED. Copy-on-write cap: at least one
        token must run through the model (the frontier logit), and its
        KV write may not land in a shared page — a fully-cached prompt
        splits its tail block back to private recompute."""
        cached, pages = self.prefix_cache.match(req.tokens)
        if self.kv_tier is not None:
            # extend the trie frontier from the spill tiers BEFORE the
            # COW cap: a prefetched block re-enters the trie, so the
            # fully-covered case splits its tail back like any hit
            cached, pages = self._prefetch_tier(req, cached, pages)
        splits = 0
        while pages and cached >= len(req.tokens):
            cached -= self.prefix_cache.cow_split(pages)
            splits += 1
        req.cached_prefix = cached
        req._cow_pending = splits
        return pages

    def _publish_prefix(self, req):
        """Register the request's newly-completed full PROMPT blocks in
        the radix trie (its own mapped blocks are already there —
        insert is idempotent). Generated-token pages stay private:
        the fleet workload shares SYSTEM PROMPTS, and restricting the
        trie to prompt content keeps its size bounded by distinct
        prompts, not distinct continuations."""
        bt = self.hash_block_tokens
        covered = min(req.n_prefilled, req.prompt_len)
        nblocks = covered // bt
        if nblocks > req.published_blocks:
            ppb = self.prefix_cache.pages_per_block
            self.prefix_cache.insert(req.tokens[:nblocks * bt],
                                     req.pages[:nblocks * ppb])
            req.published_blocks = nblocks

    def _try_admit(self, req):
        """Place one popped request into a slot: prefix-cache mapping,
        page-fit check (with trie eviction and lowest-priority
        preemption as pressure valves), page-table setup. Returns False
        — with every transient reference released — when the request
        cannot be placed yet."""
        spills0 = self._spill_count    # kv_spill phase stamp baseline
        # cheap bails FIRST — a blocked head-of-queue request must not
        # pay a full prefix match, a share/free refcount round-trip,
        # and an O(trie) feasibility walk on every engine tick.
        # (a) no free slot AND no legal victim:
        if None not in self._slots:
            now = _time.perf_counter()
            if not any(r is not None
                       and self.sched.less_urgent(r, req, now)
                       for r in self._slots):
                return False
        # speculative k-token reservation (docs/SERVING.md): leave one
        # page of headroom per live frontier slot (a window's k tokens
        # mostly fit the slot's tail page; one fresh page covers the
        # spill) so a burst of admissions can't drain the pool to where
        # every verify window collapses to 1-token widths — admission
        # waits behind the windows' working set, it never starves
        # (runners finish and the headroom shrinks with them)
        headroom = 0 if self._spec is None else sum(
            r is not None and r.n_prefilled == len(r.tokens) - 1
            for r in self._slots)
        # (b) a pool provably short even in the BEST case. A full kind:
        # the trie can map at most resident_pages into the prompt and
        # reclaim at most resident_pages more, so free + victims +
        # 2·resident < prompt pages is infeasible whatever match()
        # finds — O(slots), no trie walk. A window kind: wait for
        # runners to finish or to move their windows on (their pages
        # are not this request's to take)
        n_tokens = len(req.tokens)
        for c in self._caches:
            need_all = c.pages_to_admit(n_tokens, self.token_budget)
            free = c.pool.num_free - headroom
            if free >= need_all:
                continue
            if c.kind.window is not None:
                return False
            now = _time.perf_counter()
            avail = free + sum(
                len(r.kind_pages[c.index]) for r in self._slots
                if r is not None and self.sched.less_urgent(r, req, now))
            resident = c.trie.resident_pages if c.trie is not None else 0
            if avail + 2 * resident < need_all:
                return False
        # the first kind: the trie maps into it, the wire writes into
        # it, and a victim's pages of it are what a preemption frees
        first = self._caches[0]
        # an imported request's prompt KV arrives in its payload — a
        # trie mapping on top would alias pages the import must write
        pages = (self._map_prefix(req)
                 if self.prefix_cache is not None
                 and req._kv_import is None else [])

        def give_up():
            if pages:
                first.pool.free(pages)
            req.cached_prefix = 0
            return False

        # feasibility FIRST: preempting a runner destroys its generated
        # progress, so don't start evicting until a slot AND enough
        # reclaimable pages can possibly exist. The sum is an
        # upper bound (a page shared by two victims counts twice) — the
        # loops below still give up cleanly when eviction falls short.
        # Skipped entirely on the uncontended fast path (free slot +
        # pool already covers the prompt): the trie walk is O(nodes).
        need = (first.pages_to_admit(n_tokens, self.token_budget)
                - len(pages) + headroom)
        if None not in self._slots or first.pool.num_free < need:
            now = _time.perf_counter()
            victims = [r for r in self._slots if r is not None
                       and self.sched.less_urgent(r, req, now)]
            if None not in self._slots and not victims:
                return give_up()
            if first.available() + sum(
                    len(r.pages) for r in victims) < need:
                return give_up()
        # a slot: free one, or preempt a strictly-less-urgent runner
        if None not in self._slots:
            if not self._preempt_one(None, worse_than=req,
                                     reason="priority"):
                return give_up()
        # the prompt's remaining pages must fit (head-of-class
        # blocking: a short prompt never jumps its own class's queue)
        while first.pool.num_free < need:
            short = need - first.pool.num_free
            if first.trie is not None and first.trie.evict(short) > 0:
                continue
            if not self._preempt_one(None, worse_than=req,
                                     reason="priority"):
                return give_up()
        slot = self._slots.index(None)
        req.slot = slot
        req.admit_seq = next(self._admit_counter)
        req.n_prefilled = req.cached_prefix
        held = pages
        if req._kv_import is not None:
            # disaggregated hand-off: write the streamed pages and join
            # at the frontier. The payload is CONSUMED — a later
            # preemption replay re-prefills the prompt the ordinary way
            # (greedy replay reproduces the identical continuation).
            imp, req._kv_import = req._kv_import, None
            held = [first.alloc() for _ in range(imp.num_pages)]
            self._write_imported_pages(held, imp)
            req.n_prefilled = imp.n_prefilled
            req.trace.stamp("kv_import")
        first.adopt(slot, req, held)
        # mirrored draft pool: a shared page's draft rows were written
        # by the publishing request's own catch-up (same page ids, same
        # tokens, same draft model), so the mapped prefix is draft-valid
        # too. Worst case — a publisher that never ran a spec window —
        # leaves garbage draft rows there: proposals from them get
        # REJECTED by the lossless verify, costing acceptance rate,
        # never correctness.
        req.draft_prefilled = (req.cached_prefix
                               if self._spec is not None else 0)
        req.published_blocks = req.cached_prefix // self.hash_block_tokens
        self._slots[slot] = req
        self._slot_gen += 1  # membership changed: staged arrays stale
        if self._state_kinds:
            self._admit_slabs(req)
        if self.prefix_cache is not None:
            self.prefix_cache.note_mapped(
                req.cached_prefix, pages,
                cow_splits=getattr(req, "_cow_pending", 0))
        if req.t_first_admit is None:
            req.t_first_admit = _time.perf_counter()
            _ADMIT_SECONDS.observe(req.t_first_admit - req.t_submit)
        # phase stamps (first-wins: a preemption replay re-admits
        # without rewriting the original timeline)
        if self._spill_count > spills0:
            # this admission's pool pressure pushed trie pages to the
            # spill tier (prefix_cache.evict -> _spill_node)
            req.trace.stamp("kv_spill")
        if self.kv_tier is not None and req.cached_prefix > 0:
            from .fleet_serving.kv_tier import _TIER_HITS

            _TIER_HITS.labels(tier="hbm").inc()
        if (req.session_id is not None and req._session_seen
                and req.cached_prefix > 0):
            from .fleet_serving.kv_tier import _SESSION_RESUMED

            req._session_seen = False   # one resume per turn, not replay
            _SESSION_RESUMED.inc()
            self.stats["sessions_resumed"] = (
                self.stats.get("sessions_resumed", 0) + 1)
        if req.n_prefilled < len(req.tokens) - 1:
            req.trace.stamp("prefill_start")
        else:
            # a full import / full trie hit: the frontier is already
            # covered, no prefill ever runs on this engine
            req.trace.stamp("prefill_end")
        if (req.prefill_only
                and req.n_prefilled >= req.prompt_len - 1):
            # an import (or full trie hit) already covers the frontier:
            # nothing left for this replica to compute
            self._finish_prefill(slot, req)
        return True

    def _admit(self):
        """Admit from the queue while slots and pages last; returns how
        many requests joined."""
        now = _time.perf_counter()
        admitted = 0
        while self.sched:
            req = self.sched.pop_next(now)
            if req is None:
                break
            if not self._try_admit(req):
                self.sched.push_front(req)
                break
            admitted += 1
        return admitted

    def _active(self):
        """Running sequences in admission order (deterministic plan)."""
        return sorted(
            ((slot, req) for slot, req in enumerate(self._slots)
             if req is not None),
            key=lambda it: it[1].admit_seq)

    def _plan(self, only_slots=None):
        """Allot this step's flat token budget: one frontier token per
        running sequence first, then chunked prefill FIFO. Allocates the
        pages the planned tokens will write; a dry pool preempts the
        youngest sequence and replans. `only_slots` restricts the plan
        to those slots (the ragged-window straggler tick: frontier rows
        already took their window this step); victims of a dry pool are
        still picked from ALL running sequences."""
        while True:
            active = self._active()
            if only_slots is not None:
                active = [(s, r) for s, r in active if s in only_slots]
            if not active:
                return None
            alloc = {}
            budget = self.token_budget - len(active)
            for slot, req in active:
                remaining = len(req.tokens) - req.n_prefilled
                if req.prefill_only:
                    # the frontier token belongs to the DECODE side of
                    # the disaggregated hand-off: stop one short, so no
                    # logit is ever computed (and no token sampled) on
                    # a prefill replica
                    remaining -= 1
                take = 1 + min(remaining - 1, budget)
                budget -= take - 1
                alloc[slot] = take
            ok = True
            for slot, req in active:
                if self._grow(slot, req, alloc[slot]) < alloc[slot]:
                    # a dry pool. The victim may be no MORE urgent than the growing
                    # sequence: a BATCH job's page growth must never
                    # evict an INTERACTIVE runner (equal urgency keeps
                    # the pre-fleet preempt-youngest baseline)
                    if not self._preempt_one(req, worse_than=req,
                                             allow_equal=True):
                        if (self._pool_too_small(len(req.tokens)) is None
                                and any(r is not None and r is not req
                                        for r in self._slots)):
                            # every other runner outranks req: req
                            # itself yields its pages and requeues
                            self._preempt(slot, req, reason="pool")
                        else:
                            # kept tokens outgrew the whole pool:
                            # unservable even alone — requeueing would
                            # spin _try_admit forever
                            self._release(slot, req)
                            if not req.future.done():
                                req.future.set_exception(PoolExhausted(
                                    f"request {req.rid} needs more KV "
                                    f"pages than the pool holds"))
                    ok = False
                    break
            if ok:
                return [(slot, req, alloc[slot]) for slot, req in active]

    def step(self):
        """One scheduler tick: admit (deferred — new and preempted
        sequences only ever join HERE, i.e. at window boundaries) →
        either ONE multi-token decode window (speculative when a draft
        model is configured, else the fused k-scan when decode_k > 1)
        over the rows at their sampling frontier, or one single-tick
        compiled step → evict finished. Returns the list of requests
        finished this tick.

        RAGGED WINDOWS (the PR-8 leftover, fixed): a straggler row
        still chunk-prefilling no longer forces the whole engine onto
        single ticks — the frontier rows take their window and the
        straggler gets a prefill-only single tick in the same
        `step()` call (two dispatches, full progress on both fronts).
        The straggler joins windows at the boundary after its prefill
        completes, and per-request greedy/sampled outputs are
        schedule-invariant, so nothing observable changes per request."""
        out = self._step()
        if self._kind_stats:
            self._trim_windows()
        return out

    def _trim_windows(self):
        """The step boundary of a model with several cache kinds: every
        running sequence frees the pages now wholly behind its windows,
        and the page gauges of `stats` are brought up to date."""
        freed = 0
        for slot, req in enumerate(self._slots):
            if req is not None:
                for c in self._caches:
                    freed += c.trim(slot, req)
        self.stats["window_pages_freed"] += freed
        for c in self._caches:
            self.stats[f"{c.kind.name}_pages_live"] = c.pool.num_live

    def _step(self):
        with _trace_span("llm_engine.admit",
                         waiting=len(self.waiting)) as span:
            self._sync_brownout()
            if self._deadlines_armed:
                self._expire_deadlines()
            span.set(admitted=self._admit())
        if self._spec is not None or self.decode_k > 1:
            active = self._active()
            frontier = [(s, r) for s, r in active
                        if r.n_prefilled == len(r.tokens) - 1]
            if frontier:
                for _s, r in frontier:
                    if r.num_generated == 0:
                        r.trace.stamp("first_decode_dispatch")
                out = (self._spec.try_window(frontier)
                       if self._spec is not None
                       else self._try_step_fused(frontier))
                if out is not None:
                    stragglers = {s for s, r in active
                                  if r.n_prefilled != len(r.tokens) - 1}
                    if stragglers:
                        out = out + self._step_tick(
                            only_slots=stragglers)
                    return out
        return self._step_tick()

    # ---- fused multi-token decode window ----

    def _ensure_fused(self):
        """The fused k-step executable, built lazily: decode_k and the
        engine geometry are fixed per engine, so this is ONE executable
        per (k, config) — the zero-recompile probe's contract."""
        if self._fused_fn is None:
            self._fused_fn = _CompiledFusedStep(
                self.model, self.decode_k, self.page_size)
        return self._fused_fn

    def _try_step_fused(self, active):
        """One fused decode window over `active` (the caller's frontier
        rows — every one at its sampling frontier), or None when the
        pool cannot cover even a 1-token window (the single-tick path
        takes the tick and owns preemption). Page capacity for the
        window is reserved UP FRONT; when the pool (or a sequence's
        budget) can't cover a full k, the window spills to k' = what
        fits via the `rem` argument — the scan length never changes, so
        spill never recompiles."""
        if not active:
            return None
        with _trace_span("llm_engine.reserve",
                         rows=len(active)) as span:
            window = self._reserve_window(active)
            if self._kind_stats:  # `full_pages`, `latent_pages`, …
                span.set(window_pages_freed=self.stats[
                    "window_pages_freed"], **{
                        f"{c.kind.name}_pages": c.pool.num_live
                        for c in self._caches})
            if self._state_kinds:
                span.set(state_slabs=self.stats["state_slabs_live"])
        if window is None:
            return None
        (tok0, pos0, rem, fin0, eos, temps, tops, streams, gst, gtrans,
         gmask, gen_before) = window
        fused = self._ensure_fused()
        t0 = _time.perf_counter()
        try:
            with _trace_span("llm_engine.fused_step", k=self.decode_k,
                             rows=len(active), prefill_tokens=0,
                             decode_tokens=int(rem.sum())):
                emits, (self._kv, self._kv_scales, self._key) = fused(
                    tok0, pos0, rem, fin0, eos, temps, tops, streams,
                    gst, gtrans, gmask, self._step_tables(),
                    (self._kv, self._kv_scales, self._key))
                with _trace_span("llm_engine.sync"):
                    emits = np.asarray(emits)  # once-per-k host sync
                if self._counter_names:
                    # the model's counters rode the same array
                    self._note_counters(
                        emits[:, self.num_slots:].sum(axis=0))
                    emits = emits[:, :self.num_slots]
        except Exception as e:
            # same contract as the single tick: the donated pytree may
            # already be consumed — fail in-flight work and re-zero
            self.abort_all(e)
            raise
        # k-boundary SLO accounting: tell the scheduler how long a
        # window runs so escalation checks fire a boundary EARLY
        # instead of a boundary late (docs/SERVING.md)
        self.sched.note_boundary(_time.perf_counter() - t0)
        with _trace_span("llm_engine.emit"):
            return self._emit_window(active, emits, rem, gen_before)

    def _reserve_window(self, active):
        """Page reservation and host array fill of one fused window:
        the arguments of the dispatch, or None when the pool cannot
        cover a 1-token window."""
        k = self.decode_k

        # a dry pool's `alloc` reclaims the trie's LRU pages, so they
        # count (an upper bound: a short row spills further below)
        avail = [c.available() for c in self._caches]

        def fits(w):
            """Every cache kind covers a window of w."""
            for c, free in zip(self._caches, avail):
                need = 0
                for _, req in active:
                    writes = min(w, req.target - len(req.tokens))
                    need += c.missing(req, req.n_prefilled,
                                      req.n_prefilled + writes - 1)
                if need > free:
                    return False
            return True

        # brownout window cap: a smaller w rides the `rem` runtime
        # argument of the SAME k-scan executable — degrading the window
        # never recompiles (overload.BrownoutController L3)
        cap = self._brownout.get("decode_k_cap")
        w = k if cap is None else max(1, min(k, int(cap)))
        while w > 1 and not fits(w):
            w -= 1        # spill: the largest window the pools cover
        if not fits(w):
            return None   # not even 1 token/row: single tick preempts

        # reserve the window's pages up front
        S = self.num_slots
        rem = np.zeros((S,), np.int32)
        for slot, req in active:
            rem[slot] = self._grow(
                slot, req, min(w, req.target - len(req.tokens)))
            if rem[slot] < 1:
                return None

        tok0 = np.zeros((S,), np.int32)
        pos0 = np.zeros((S,), np.int32)
        fin0 = np.ones((S,), bool)        # empty slots: finished
        eos = np.full((S,), -1, np.int32)
        temps = np.zeros((S,), np.float32)
        tops = np.ones((S,), np.float32)
        streams = np.zeros((S,), np.int32)
        gen_before = {}
        for slot, req in active:
            tok0[slot] = req.tokens[-1]
            pos0[slot] = req.n_prefilled
            fin0[slot] = False
            if req.eos is not None:
                eos[slot] = int(req.eos)
            temps[slot] = req.temperature
            tops[slot] = req.top_p
            streams[slot] = req.sample_stream
            gen_before[slot] = req.num_generated

        gst, gtrans, gmask = self._grammar_args(active)
        return (tok0, pos0, rem, fin0, eos, temps, tops, streams, gst,
                gtrans, gmask, gen_before)

    def _emit_window(self, active, emits, rem, gen_before):
        """A fused window's tokens into their requests: append, finish,
        counters and gauges. Returns the requests finished."""
        self.stats["steps"] += 1
        self.stats["fused_steps"] += 1
        self.stats["occupancy_sum"] += len(active) / self.num_slots
        self._note_launches(self._fused_fn)
        _STEPS_TOTAL.inc()
        _FUSED_STEPS.inc()
        _DISPATCHES.inc()

        finished = []
        now = _time.perf_counter()
        total = 0
        for slot, req in active:
            emitted, done = 0, False
            for j in range(int(rem[slot])):
                t = int(emits[j, slot])
                req.tokens.append(t)
                if req.grammar is not None:
                    # host replay of the in-scan DFA advance: gstate
                    # stays a pure function of the emitted tokens
                    req.gstate = req.grammar.advance(req.gstate, t)
                emitted += 1
                if ((req.eos is not None and t == req.eos)
                        or len(req.tokens) >= req.target):
                    done = True   # in-executable masking already
                    break         # padded the rest of the window
            if self._kind_stats and emitted:
                self._note_attended(req.n_prefilled, 1, steps=emitted)
            req.n_prefilled += emitted
            total += emitted
            self.stats["generated"] += emitted
            self.sched.note_tokens(req.tenant, emitted)
            if gen_before[slot] == 0 and emitted > 0:
                ttft = now - req.t_submit
                req.t_first_token = now
                req.trace.stamp("first_token")
                self._note_timeline(req)
                _TTFT_SECONDS.observe(ttft)
                self.sched.note_first_token(req, ttft)
            if done:
                self._finish(slot, req)
                finished.append(req)
        self.stats["tokens_in"] += total
        _TOKENS_TOTAL.labels(phase="decode").inc(total)
        _TOK_PER_DISPATCH.set(total)
        _QUEUE_DEPTH.set(len(self.waiting))
        self._publish_load()
        return finished

    def _step_tables(self):
        """The page tables a step is dispatched with: the one table
        [S, MP], or with several cache kinds [kinds, S, MP] in the
        model's order (serving_protocol.py)."""
        if not self._several:
            return self._caches[0].tables
        return np.stack([c.tables for c in self._caches])

    def _write_index(self, slots, positions, kv_lens):
        """The pool row each row of a step writes its K/V to, [T] (0,
        the trash page's, where `kv_lens` is 0: padding), or with
        several cache kinds [kinds, T]: the same position through each
        kind's own table."""
        rows = np.flatnonzero(kv_lens)
        widx = np.zeros((len(self._caches), len(kv_lens)), np.int32)
        for c in self._caches:
            widx[c.index, rows] = c.rows(slots[rows], positions[rows])
        return widx if self._several else widx[0]

    def _note_attended(self, first_pos, rows, steps=1):
        """The least K/V a step must read, as positions a cache kind
        (`stats["kv_positions_least_<kind>"]`, several kinds only): one
        slot had `rows` consecutive positions from `first_pos` through
        the model in ONE step, and a layer must read the span they
        attend once, however the kernel blocks its rows: every earlier
        position, or from the first row's window on. `steps` > 1: that
        many steps of one row each (a fused window's iterations)."""
        for kind in (c.kind for c in self._caches):
            if steps == 1:
                lo = 0 if kind.window is None else max(
                    0, first_pos - kind.window + 1)
                n = first_pos + rows - lo
            else:
                ctx = range(first_pos + 1, first_pos + steps + 1)
                n = sum(ctx) if kind.window is None else sum(
                    min(c, kind.window) for c in ctx)
            self.stats[f"kv_positions_least_{kind.name}"] += n

    def _note_launches(self, step_fn):
        """A dispatch of `step_fn` was made: add the paged-attention
        launches it holds to `stats`, by the body they run (a tick adds
        a launch a layer, a window of k that k times)."""
        for body, n in step_fn.launches.items():
            self.stats[f"paged_attn_{body}_launches"] += n

    def _note_counters(self, totals=None):
        """Add the model's step counters to `stats`: `totals` [C] now,
        and every tick's result still pending (device arrays that the
        program order has completed by the time the host has read a
        LATER result — no sync of their own)."""
        pending, self._pending_counters = self._pending_counters, []
        for c in pending:
            c = np.asarray(c)
            totals = c if totals is None else totals + c
        if totals is not None:
            for name, v in zip(self._counter_names, totals):
                self.stats[name] += int(v)

    # ---- single-tick step (prefill / mixed / k=1) ----

    def _host_sample_rows(self, lv, reqs):
        """Temperature/top-p (+ greedy rows) for a host tick's frontier
        logits — the SAME `sample_tokens` math the fused scan runs
        in-executable, position-keyed on the SAME engine key, so a
        request's draws are identical whichever path serves the tick
        (that invariance is what makes sampled outputs reproducible
        across decode_k — tests/test_fused_decode.py pins it).

        Padded to num_slots so the jitted sampler traces ONCE per
        engine: the frontier row count varies tick-to-tick with
        arrivals/finishes, and a per-count specialization would stall
        the serving loop on a fresh vocab-sort compile mid-traffic."""
        if self._host_sample is None:
            from ..text.models.gpt import sample_tokens

            self._host_sample = jax.jit(sample_tokens)
        n, S = len(reqs), self.num_slots
        temps = np.zeros((S,), np.float32)   # pad rows: greedy, key 0
        tops = np.ones((S,), np.float32)
        streams = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        for j, r in enumerate(reqs):
            temps[j] = r.temperature
            tops[j] = r.top_p
            streams[j] = r.sample_stream
            positions[j] = len(r.tokens)  # index the new token takes
        lv = jnp.pad(lv, ((0, S - n), (0, 0)))
        return self._host_sample(lv, temps, tops, streams, positions,
                                 self._key)[:n]

    def _step_tick(self, only_slots=None):
        """One single-tick compiled step: plan → dispatch → sample
        frontiers on the host → evict finished. `only_slots` is the
        ragged-window straggler tick (prefill-only rows; see step())."""
        with _trace_span("llm_engine.plan"):
            planned = self._plan_tick(only_slots)
        if planned is None:
            return []
        (plan, i, tok, pos, sid, widx, klen, sample_idx,
         sample_slots) = planned
        with _trace_span("llm_engine.step", rows=len(plan),
                         prefill_tokens=i - len(sample_slots),
                         decode_tokens=len(sample_slots)):
            try:
                logits, (self._kv, self._kv_scales, self._key) = \
                    self._step_fn(
                        tok, pos, sid, widx, self._step_tables(), klen,
                        sample_idx,
                        (self._kv, self._kv_scales, self._key))
                if self._counter_names:
                    logits, counters = logits
                    self._pending_counters.append(counters)
            except Exception as e:
                # the donated pools may already be consumed by the
                # failed dispatch — fail the in-flight work and re-zero
                # so a direct-drive caller's engine stays serviceable
                # (the server loop's own abort_all then finds nothing
                # left to do)
                self.abort_all(e)
                raise
            # the span encloses the host's read of the result, so the
            # program it launched runs inside it on a profile
            with _trace_span("llm_engine.sync"):
                nxt = self._read_frontier(logits, sample_slots)
                if sample_slots and self._pending_counters:
                    self._note_counters()   # the device is past them
        with _trace_span("llm_engine.emit"):
            return self._emit_tick(plan, i, sample_slots, nxt,
                                   only_slots)

    def _plan_tick(self, only_slots):
        """The single tick's planning up to the dispatch: the plan, its
        pages, and the step's host arrays (staged copies reused for a
        pure-decode tick). None when nothing is runnable."""
        plan = self._plan(only_slots)
        if plan is None:
            return None

        T = self.token_budget
        # pure-decode staging cache: when every planned row is a
        # 1-token sampling frontier AND slot membership is unchanged,
        # sid / sample_idx are IDENTICAL to last tick's — reuse the
        # device-committed copies instead of rebuilding and re-uploading
        # them every tick (keyed on the slot-assignment generation).
        # Never staged for a restricted straggler tick: its row set is
        # a subset the generation counter doesn't describe.
        staged = None
        if only_slots is None and all(
                take == 1 and len(req.tokens) - req.n_prefilled == 1
                for _, req, take in plan):
            staged = self._stage
            if staged is None or staged["gen"] != self._slot_gen:
                from ..distributed import mesh as mesh_mod

                sid_np = np.zeros((T,), np.int32)
                sidx_np = np.zeros((self.num_slots,), np.int32)
                for row, (slot, _, _) in enumerate(plan):
                    sid_np[row] = slot
                    sidx_np[slot] = row
                sharding = mesh_mod.named_sharding()
                staged = self._stage = {
                    "gen": self._slot_gen,
                    "slots": [slot for slot, _, _ in plan],
                    "sid": jax.device_put(sid_np, sharding),
                    "sample_idx": jax.device_put(sidx_np, sharding)}
            else:
                self.stats["stage_hits"] += 1

        tok = np.zeros((T,), np.int32)
        pos = np.zeros((T,), np.int32)
        klen = np.zeros((T,), np.int32)   # 0 → padding token
        sid_np = np.zeros((T,), np.int32)
        if staged is not None:
            sid = staged["sid"]
            sample_idx = staged["sample_idx"]
            sample_slots = staged["slots"]
            for row, (slot, req, _) in enumerate(plan):
                p = req.n_prefilled
                sid_np[row] = slot
                tok[row] = req.tokens[p]
                pos[row] = p
                klen[row] = p + 1
                if req.num_generated == 0:
                    req.trace.stamp("first_decode_dispatch")
            i = len(plan)
        else:
            from ..distributed import mesh as mesh_mod

            # per-SLOT sampling frontier: the vocab head only runs on
            # these gathered rows (stale slots point at row 0; logits
            # ignored)
            sample_idx = np.zeros((self.num_slots,), np.int32)
            sample_slots = []
            i = 0
            for slot, req, take in plan:
                for k in range(take):
                    p = req.n_prefilled + k
                    tok[i] = req.tokens[p]
                    pos[i] = p
                    sid_np[i] = slot
                    klen[i] = p + 1
                    if p == len(req.tokens) - 1:
                        sample_idx[slot] = i
                        sample_slots.append(slot)
                        if req.num_generated == 0:
                            # this dispatch carries the frontier row:
                            # prefill ends and decode begins HERE (in
                            # that order — the timeline reads left to
                            # right even when one dispatch does both)
                            req.trace.stamp("prefill_end")
                            req.trace.stamp("first_decode_dispatch")
                    i += 1
            # committed like the staged copies: a committed/uncommitted
            # flip at one arg position would cost a second executable
            sharding = mesh_mod.named_sharding()
            sid = jax.device_put(sid_np, sharding)
            sample_idx = jax.device_put(sample_idx, sharding)
        widx = self._write_index(sid_np, pos, klen)
        return (plan, i, tok, pos, sid, widx, klen, sample_idx,
                sample_slots)

    def _read_frontier(self, logits, sample_slots):
        """The tick's host read: the next token of every slot at its
        sampling frontier (greedy argmax or `sample_tokens`), as a numpy
        array; empty when the tick carried prefill rows only."""
        if not sample_slots:
            return []
        rows = jnp.asarray(sample_slots, jnp.int32)
        lv = jnp.take(logits[0], rows, axis=0).astype(jnp.float32)
        frontier = [self._slots[s] for s in sample_slots]
        if any(r.grammar is not None for r in frontier):
            # host-path grammar masking: mask the logit VALUES before
            # the (single-trace) jitted sampler / argmax — identical
            # picks to the in-scan mask, zero new traces
            allow = np.ones((len(frontier), lv.shape[1]), bool)
            for jr, r in enumerate(frontier):
                if r.grammar is not None:
                    allow[jr] = r.grammar.allowed_np(r.gstate)
            lv = jnp.where(jnp.asarray(allow), lv, jnp.float32(-1e30))
        if any(r.do_sample for r in frontier):
            return np.asarray(self._host_sample_rows(lv, frontier))
        # greedy frontier sampling — same pick as generate()'s default
        # path, so outputs stay token-identical
        return np.asarray(jnp.argmax(lv, axis=-1))

    def _emit_tick(self, plan, i, sample_slots, nxt, only_slots):
        """A single tick's result into its requests: counters and
        gauges, prefill progress, token append, finish. Returns the
        requests finished."""
        self.stats["steps"] += 1
        self.stats["tokens_in"] += i
        self.stats["occupancy_sum"] += len(plan) / self.num_slots
        self._note_launches(self._step_fn)
        _STEPS_TOTAL.inc()
        _DISPATCHES.inc()
        # a ragged-window straggler tick covers only the PREFILL rows:
        # the load gauges count the whole engine's slots, and the
        # window's tokens-per-dispatch amortization stamp stays unless
        # this tick actually decoded something
        live_now = sum(r is not None for r in self._slots)
        if only_slots is None or sample_slots:
            _TOK_PER_DISPATCH.set(len(sample_slots))
        # the flat-budget split: one decode token per sampling frontier,
        # everything else is (chunked or preemption-replay) prefill
        _TOKENS_TOTAL.labels(phase="decode").inc(len(sample_slots))
        _TOKENS_TOTAL.labels(phase="prefill").inc(i - len(sample_slots))
        _QUEUE_DEPTH.set(len(self.waiting))
        _LIVE_SLOTS.set(live_now)
        _SLOT_OCC.set(live_now / self.num_slots)
        _PAGE_OCC.set(self._page_occupancy())

        finished = []
        for slot, req, take in plan:
            if self._kind_stats:
                self._note_attended(req.n_prefilled, take)
            req.n_prefilled += take
            if req.n_prefilled >= len(req.tokens) - 1:
                # the sampling frontier is reached: prefill is over
                # (first-wins — steady-state decode ticks are no-ops)
                req.trace.stamp("prefill_end")
            # per-tenant fair-queuing meter: flat tokens actually spent
            self.sched.note_tokens(req.tenant, take)
            if self.prefix_cache is not None:
                self._publish_prefix(req)
            if (req.prefill_only
                    and req.n_prefilled >= len(req.tokens) - 1):
                # disaggregated hand-off: the frontier is reached —
                # export the pages and retire (publish above already
                # registered the full prompt blocks in the trie)
                self._finish_prefill(slot, req)
                finished.append(req)
        _PAGE_FRAG.set(self.kv_fragmentation())
        now = _time.perf_counter()
        for slot, tok_id in zip(sample_slots, nxt):
            req = self._slots[slot]
            t = int(tok_id)
            req.tokens.append(t)
            if req.grammar is not None:
                req.gstate = req.grammar.advance(req.gstate, t)
            self.stats["generated"] += 1
            if req.num_generated == 1:      # replays don't re-count
                ttft = now - req.t_submit
                req.t_first_token = now
                req.trace.stamp("first_token")
                self._note_timeline(req)
                _TTFT_SECONDS.observe(ttft)
                self.sched.note_first_token(req, ttft)
            if ((req.eos is not None and t == req.eos)
                    or len(req.tokens) >= req.target):
                self._finish(slot, req)
                finished.append(req)
        return finished


# the full `LLMServer.submit` kwarg surface — remote ingresses
# (FleetRouter.submit) screen unknown kwargs against this set so a
# typo'd knob raises at submit() time with its name, instead of dying
# as a TypeError inside a replica's serve loop
SUBMIT_KWARGS = frozenset((
    "max_new_tokens", "eos_token_id", "tenant", "priority",
    "ttft_slo_s", "temperature", "top_p", "prefill_only", "kv_import",
    "trace", "deadline_s", "session_id", "grammar", "json_schema",
    "spec_mode"))


class LLMServer(_FutureQueueServer):
    """Continuous-batching text-generation server: the future/queue
    surface of `InferenceServer` over an `LLMEngine` (module docstring
    has the usage). One background thread owns the engine; `submit` is
    thread-safe."""

    _thread_name = "llm-engine"

    def __init__(self, model, config=None):
        super().__init__()
        self._engine = LLMEngine(model, config)
        self.stats = self._engine.stats  # shared view + request counts
        self.stats.setdefault("requests", 0)
        self._http = None

    @property
    def engine(self):
        return self._engine

    def metrics(self):
        """Engine telemetry snapshot (registry-sourced; see
        LLMEngine.metrics). Thread-safe: reads only."""
        return self._engine.metrics()

    def start_metrics_http(self, port=0, host="127.0.0.1"):
        """Optional stdlib-only pull endpoint: GET /metrics serves the
        process registry in Prometheus text format, /metrics.json the
        full snapshot with this engine's view under "extra". port=0
        picks a free port; returns the handle (`.url`, `.port`).
        Stopped automatically with the server."""
        if self._http is None:
            from ..observability import start_http_server

            self._http = start_http_server(port=port, host=host,
                                           extra_json=self.metrics)
        return self._http

    def stop(self):
        super().stop()
        self._engine.close()
        if self._http is not None:
            self._http.stop()
            self._http = None

    def submit(self, prompt, max_new_tokens=32, eos_token_id=None,
               tenant="default", priority=None, ttft_slo_s=None,
               temperature=0.0, top_p=1.0, prefill_only=False,
               kv_import=None, trace=None, deadline_s=None,
               session_id=None, grammar=None, json_schema=None,
               spec_mode=None):
        """Enqueue one prompt (1-D int token ids). Returns a Future
        resolving to np.int64 [prompt + generated] (eos kept, nothing
        after it) — or, with `prefill_only=True`, to the exported
        `fleet_serving.KVPagePayload` (the disaggregated hand-off;
        `kv_import` is the receiving side — see
        `LLMEngine.add_request`). The engine-side `_Request` is
        attached to the future as `fut.pt_request` once ingested (the
        router's TTFT source; None until the engine thread picks the
        submission up).

        Fleet fields (docs/SERVING.md): `tenant` groups requests for
        token-budget fair queuing, `priority` is a
        `fleet_serving.Priority` class (default STANDARD), and
        `ttft_slo_s` sets this request's TTFT SLO for deadline
        boosting and the attainment gauge. `session_id` marks the
        request as one turn of a persistent chat session (docs/
        SERVING.md "KV memory hierarchy"): its FINAL token sequence —
        generated tokens included — is pinned into the prefix trie on
        finish, so the next turn's prompt (which embeds this turn's
        history) resumes from the conversation frontier instead of
        re-prefilling it; under pool pressure the pinned blocks spill
        to the host/disk tier and prefetch back on resume.

        Sampling: `temperature` 0 (default) decodes greedily,
        token-identical to generate(); > 0 samples the temperature-
        scaled, `top_p`-truncated distribution, seeded from the engine
        PRNG key and keyed on (stream, position) — reproducible for a
        given engine seed whatever decode_k is.

        Structured decoding (docs/SERVING.md "Structured decoding"):
        `grammar=` (regex / CompiledGrammar) or `json_schema=` (dict)
        constrain the output tokens; `spec_mode=` opts a request out
        of ("off") or restates the engine's speculation mode. All
        three validate — and the grammar COMPILES, through the
        engine's hash-keyed cache — on THIS thread, so a malformed
        constraint raises here at submit() with the offending kwarg
        named, never inside the serve loop where it would abort
        co-resident requests (same hardening as `_check_import`)."""
        # loud submit-time gate: structural validation + engine-context
        # checks + grammar compile (GrammarError over the table budget)
        grammar = self._engine._resolve_constraint(
            grammar, json_schema, eos_token_id, spec_mode)
        fut = Future()
        fut.pt_request = None
        # trace identity minted at the INGRESS (this thread), so the
        # `queued` stamp covers the server queue, not just the engine's
        # — unless the payload already carries one (the cross-process
        # decode half: recv_and_decode -> submit_imported must CONTINUE
        # the prefill tier's trace, not start a fresh id)
        if trace is None and kv_import is not None:
            trace = _payload_trace(kv_import)
        if trace is None:
            trace = _reqtrace.new_trace()
        trace.stamp("queued")
        self._enqueue(dict(
            prompt=np.asarray(prompt).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            eos_token_id=eos_token_id, future=fut, tenant=tenant,
            priority=priority, ttft_slo_s=ttft_slo_s,
            temperature=float(temperature), top_p=float(top_p),
            prefill_only=bool(prefill_only), kv_import=kv_import,
            trace=trace, deadline_s=deadline_s,
            session_id=session_id, grammar=grammar,
            json_schema=None, spec_mode=spec_mode))
        return fut

    def export_prefix(self, tokens):
        """Cut the engine trie's longest cached prefix of `tokens`
        into a `KVPagePayload` (or None) — the hot-prefix migration
        source the router's pull path calls on the donor replica. The
        cut runs on the ENGINE thread (a control message on the same
        queue as submissions — the trie and pools are engine-thread
        state); this returns a Future resolving to the payload."""
        fut = Future()
        self._enqueue({"_export_prefix":
                       np.asarray(tokens).reshape(-1),
                       "_export_future": fut})
        return fut

    def generate(self, prompt, max_new_tokens=32, eos_token_id=None):
        return self.submit(prompt, max_new_tokens, eos_token_id).result()

    def abort(self, request_id, reason="client", counted=False):
        """Cancel ONE in-flight request by its engine rid (overload
        control plane; docs/SERVING.md "Overload and degradation").
        The abort rides the SAME queue as submissions, so the engine
        thread applies it between steps — no cross-thread engine
        access. Unknown/finished rids are a no-op on the engine; the
        caller (router `cancel`) owns the client-future resolution
        (and, with `counted=True`, the cancellation count)."""
        self._enqueue({"_abort": int(request_id),
                       "_abort_reason": str(reason),
                       "_abort_counted": bool(counted)})

    def _ingest(self, payload):
        if "_abort" in payload:   # control message, not a submission
            try:
                self._engine.abort(payload["_abort"],
                                   reason=payload.get("_abort_reason",
                                                      "client"),
                                   counted=payload.get("_abort_counted",
                                                       False))
            except Exception:  # ptlint: disable=PTL804 (abort of unknown rid is a no-op; never kill the serve loop)
                pass
            return
        if "_export_prefix" in payload:
            fut = payload["_export_future"]
            try:
                res = self._engine.export_prefix(
                    payload["_export_prefix"])
                if not fut.cancelled():
                    fut.set_result(res)
            except Exception as e:
                if not fut.done():
                    fut.set_exception(e)
            return
        fut = payload.pop("future")
        if fut.cancelled():
            # client cancelled between submit and ingest: the request
            # never reaches the engine (resolving a cancelled future
            # would raise InvalidStateError out of the serve loop)
            return
        try:
            fut.pt_request = self._engine.add_request(future=fut,
                                                      **payload)
            self.stats["requests"] += 1
        except Exception as e:  # bad request must not kill the loop
            if not fut.done():
                fut.set_exception(e)

    def _tick_hook(self):
        """Per-loop-iteration hook (fleet replica runtime: heartbeat +
        chaos kill — fleet_serving.replica overrides). Returning True
        aborts the serve loop DEAD: no drain, no future resolution —
        the process-death shape the router's failover requeues."""
        return False

    def _loop(self):
        eng = self._engine
        while self._running or not self._q.empty() or eng.has_work():
            if self._tick_hook():
                return
            try:
                while True:
                    self._ingest(self._q.get_nowait())
            except queue.Empty:
                pass
            if not eng.has_work():
                # idle: block briefly for the next submission
                try:
                    self._ingest(self._q.get(timeout=0.05))
                except queue.Empty:
                    continue
            try:
                eng.step()
            except Exception as e:  # defensive: never die silently
                eng.abort_all(e)
