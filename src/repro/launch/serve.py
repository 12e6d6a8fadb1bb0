"""Serving launcher: prefill/decode step construction + a continuous-batching
serving engine built on per-slot cache state, with an optional PAGED KV-cache
runtime (block-table attention, page allocator, prompt-prefix cache).

The decode step is the function the ``decode_*`` / ``long_*`` dry-run cells
lower; :class:`ContinuousBatchingEngine` is the runnable end-to-end driver
used by examples/serve_quantized.py and benchmarks/bench_throughput.py.

Engine architecture (DESIGN.md §10, §14):

* Every decode state carries a **per-slot position vector** ``pos (B,)`` —
  each batch slot is an independent timeline, so requests of different
  lengths decode in lock-step without sharing a global step counter.
* **Admission** runs the model's real prefill once on a batch-1 state (one
  batched pass over the whole prompt, not T decode steps). Prompts are
  padded to power-of-two **buckets** (compile count O(log S_max), not
  O(distinct lengths)); the model's ``length`` kwarg keeps the padded math
  exact and ``compile_stats()`` reports the trace inventory.
* The slot axis of every state leaf is inferred structurally (batch-2 vs
  batch-1 ``eval_shape`` diff), so the same engine serves KV-cache
  transformers, MLA latent caches, SSM/xLSTM recurrent states, and hybrid
  stacks without per-family splice code.
* **Paged mode** (``paged=True``): sequence-carrying cache leaves live in a
  global page pool shared by all slots (``models.common.init_paged_state``);
  a host-side :class:`PageAllocator` owns the free list and refcounts,
  admission is gated on free PAGES (not just free slots), eviction returns
  pages, and a :class:`PrefixCache` maps shared prompt prefixes (hashed at
  page granularity) into new slots copy-free so only the suffix re-prefills.
  The dense per-slot layout stays alive behind the flag as the A/B and
  correctness oracle.
* **Eviction** is host bookkeeping plus (paged) page release: a finished
  request frees its slot and pages; stale device state is invisible behind
  the per-slot mask / unmapped block-table rows. A request stopped by cache
  capacity before producing ``max_new`` tokens is flagged ``truncated``.
* Sampling is per-request (greedy / temperature / top-k) on the host.
* **Ragged mode** (``ragged=True``, requires paged + a family with
  ``ragged_step``): chunked prefill and decode are unified into ONE launch
  per engine step over a flat token batch capped at ``token_budget`` —
  decode latency stays flat while long prompts stream in, and the prefill
  bucket inventory collapses to a single token-budget trace. See
  docs/serving.md for the full lifecycle.
* **Request lifecycle** is an explicit state machine (:class:`RequestState`:
  ``QUEUED -> PREFILL -> DECODE -> {DONE, FAILED, CANCELLED, TIMED_OUT,
  PREEMPTED}``) with per-request error capture (``req.error`` holds a
  machine-readable reason code), ``engine.cancel(request_id)``, per-request
  deadlines (``deadline_steps`` / ``deadline_s``), and — in paged mode with
  ``preemption=True`` — preempt + requeue under page pressure: the victim's
  pages are released, its generated tokens are kept, and it re-enqueues
  with prompt+generated as the new prefix so the prefix cache restores the
  shared pages copy-free on readmission. Every exit path (done, failed,
  cancelled, timed out, preempted, stalled) releases pages and neutralizes
  bt/pos through the same ``_release_slot`` helper. A finite-logits guard
  at the sanctioned sync points quarantines a slot producing NaN/Inf logits
  (``status=FAILED``, ``error="nan_logits"``) without perturbing the rest
  of the batch. See docs/serving.md "Fault model & request lifecycle".
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.kernels.ragged_attention import ragged_work
from repro.models import common as C
from repro.models.registry import get_model


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """Bind cfg into the family's prefill: (params, tokens (B, S), state,
    **frontend) -> (last-position logits, filled state). The engine jits one
    instance per engine; the bucketed admission path drives it."""
    model = get_model(cfg)

    def prefill_step(params, tokens, state, **frontend):
        return model.prefill(params, cfg, tokens, state, **frontend)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    """Bind cfg into the family's decode step: (params, state, tokens (B, sq))
    -> (logits (B, sq, V), new state); sq == 1 plain decode, sq > 1 stacks
    speculative draft rows (paged dense/moe). The ``decode_*`` / ``long_*``
    dry-run cells lower exactly this function."""
    model = get_model(cfg)

    def decode_step(params, state, tokens):
        return model.decode_step(params, cfg, state, tokens)

    return decode_step


def make_ragged_step(cfg: ModelConfig) -> Callable:
    """Bind cfg into the family's unified ragged step (ragged engine mode):
    (params, state, tokens (T,), slot (T,), pos (T,), ctx (B,), logit_idx
    (B,)) -> (logits (B, V), new state). One launch carries every live
    slot's scheduled tokens — prefill chunks and decode tokens together.
    Only families exposing ``ragged_step`` (dense/moe) support it."""
    model = get_model(cfg)

    def ragged_step(params, state, tokens, slot, pos, ctx, logit_idx):
        return model.ragged_step(params, cfg, state, tokens, slot, pos, ctx, logit_idx)

    return ragged_step


# ---------------------------------------------------------------------------
# requests + sampling
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling. ``temperature <= 0`` means greedy; ``top_k > 0``
    restricts sampling to the k most likely tokens."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


class RequestState:
    """Explicit request lifecycle states (docs/serving.md "Fault model").

    ``NEW -> QUEUED -> PREFILL -> DECODE -> {DONE, FAILED, CANCELLED,
    TIMED_OUT}`` with ``PREEMPTED`` as the requeue detour (``PREFILL/DECODE
    -> PREEMPTED -> PREFILL`` on readmission). ``TERMINAL`` is the set of
    states a request never leaves; the engine enforces the transition table
    so an illegal edge is a loud bug, not silent state drift."""

    NEW = "NEW"
    QUEUED = "QUEUED"
    PREFILL = "PREFILL"
    DECODE = "DECODE"
    DONE = "DONE"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"
    PREEMPTED = "PREEMPTED"
    TERMINAL = frozenset({DONE, FAILED, CANCELLED, TIMED_OUT})


_TRANSITIONS: dict[str, frozenset] = {
    RequestState.NEW: frozenset({RequestState.QUEUED}),
    RequestState.QUEUED: frozenset({
        RequestState.PREFILL, RequestState.CANCELLED, RequestState.TIMED_OUT,
    }),
    RequestState.PREFILL: frozenset({
        RequestState.DECODE, RequestState.FAILED, RequestState.CANCELLED,
        RequestState.TIMED_OUT, RequestState.PREEMPTED,
    }),
    RequestState.DECODE: frozenset({
        RequestState.DONE, RequestState.FAILED, RequestState.CANCELLED,
        RequestState.TIMED_OUT, RequestState.PREEMPTED,
    }),
    RequestState.PREEMPTED: frozenset({
        RequestState.PREFILL, RequestState.CANCELLED, RequestState.TIMED_OUT,
    }),
    RequestState.DONE: frozenset(),
    RequestState.FAILED: frozenset(),
    RequestState.CANCELLED: frozenset(),
    RequestState.TIMED_OUT: frozenset(),
}

# terminal state -> the stats counter it bumps
_FINISH_COUNTER = {
    RequestState.DONE: "requests_done",
    RequestState.FAILED: "requests_failed",
    RequestState.CANCELLED: "requests_cancelled",
    RequestState.TIMED_OUT: "requests_timed_out",
}


class _SlotFault(RuntimeError):
    """Internal: a slot-attributable fault detected during admission (e.g.
    non-finite prefill logits); carries the machine-readable reason code."""

    def __init__(self, code: str, detail: str):
        super().__init__(detail)
        self.code = code
        self.detail = detail


def _fault_of(e: Exception) -> tuple[str, str]:
    """(code, detail) for an exception caught on a slot-attributable path."""
    if isinstance(e, _SlotFault):
        return e.code, e.detail
    return "prefill_exception", f"{type(e).__name__}: {e}"


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request: a prompt, a token quota, and sampling params.

    The engine writes results back onto the object: ``out`` (generated token
    ids), ``status`` (a :class:`RequestState` value), ``done`` (reached a
    terminal state), ``truncated`` (stopped by cache capacity before filling
    ``max_new``), and — on FAILED/TIMED_OUT exits — ``error`` (machine-
    readable reason code, e.g. ``nan_logits`` / ``deadline_steps``) plus
    ``error_detail`` (human-readable context). ``request_id`` is assigned at
    ``submit()`` when not provided; ``priority`` orders preemption victims
    (lower preempts first); ``deadline_steps`` / ``deadline_s`` bound the
    request's lifetime in engine steps / wall-clock seconds from submission.

    **Stream surface** (docs/serving.md "SLO metrics & traffic harness"):
    ``on_token`` is an optional per-token callback ``(request, token_id)``
    fired by the engine exactly once per emitted token, at the step that
    produced it (preemption + resume never re-fires already-emitted tokens).
    A raising callback is detached with a warning after its first exception —
    a sloppy consumer must not wedge the batch. The engine also stamps
    ``t_submit`` / ``t_first_token`` / ``t_done`` (``time.monotonic``) and
    appends one entry to ``token_times`` per emitted token, so TTFT and
    per-token latency are MEASURED, not inferred (``launch/metrics.py``).
    Fields prefixed ``_`` are engine-private."""

    prompt: Any  # (S,) int32
    max_new: int = 16
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    frontend: dict = dataclasses.field(default_factory=dict)  # vlm/encdec extras
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # stream + latency observability (docs/serving.md)
    on_token: Optional[Callable] = dataclasses.field(default=None, repr=False)
    t_submit: Optional[float] = dataclasses.field(default=None, repr=False)
    t_first_token: Optional[float] = dataclasses.field(default=None, repr=False)
    t_done: Optional[float] = dataclasses.field(default=None, repr=False)
    token_times: list = dataclasses.field(default_factory=list, repr=False)
    # set at eviction when the request hit cache capacity before filling its
    # max_new quota (prompt_len + max_new > engine.max_len)
    truncated: bool = False
    # lifecycle (docs/serving.md "Fault model & request lifecycle")
    request_id: Optional[str] = None  # assigned at submit() when None
    priority: int = 0  # preemption picks the lowest-priority victim first
    deadline_steps: Optional[int] = None  # engine steps allowed after submit
    deadline_s: Optional[float] = None  # wall-clock budget after submit
    status: str = RequestState.NEW
    error: Optional[str] = None  # machine-readable failure reason code
    error_detail: Optional[str] = None
    # engine-private
    _last_logits: Any = dataclasses.field(default=None, repr=False)
    _rng: Any = dataclasses.field(default=None, repr=False)
    # ragged mode: prompt tokens already written to the cache (chunk cursor)
    # and the prompt as a host int32 array, cached at admission
    _filled: int = dataclasses.field(default=0, repr=False)
    _prompt: Any = dataclasses.field(default=None, repr=False)
    # host copy of the ORIGINAL prompt (set at submit); preemption rebuilds
    # the effective prompt as _prompt_host + out without device transfers
    _prompt_host: Any = dataclasses.field(default=None, repr=False)
    # resolved deadlines (absolute engine step / monotonic time), set at submit
    _deadline_step: Any = dataclasses.field(default=None, repr=False)
    _deadline_t: Any = dataclasses.field(default=None, repr=False)
    _preemptions: int = dataclasses.field(default=0, repr=False)


# ---------------------------------------------------------------------------
# paged-pool host bookkeeping (DESIGN.md §14)
# ---------------------------------------------------------------------------


class AllocatorError(AssertionError):
    """A page-allocator bookkeeping violation — double release, unknown page
    id, or sharing an unreferenced page — raised with the page id and its
    refcount spelled out instead of silently corrupting the free list.
    Subclasses ``AssertionError`` so callers treating allocator misuse as an
    assertion failure keep working."""


class EngineStalledError(RuntimeError):
    """``run_until_done`` exhausted its step budget with live work remaining.

    The still-live requests have already been marked ``TIMED_OUT`` (pages
    released, error code ``engine_stalled``) by the time this raises, so a
    wedged engine cannot be mistaken for a drained one and never leaks its
    page reservations."""


class PageAllocator:
    """Free-list allocator with refcounts over the global KV page pool.

    A page's refcount is exactly (number of slot block-tables mapping it)
    plus (1 if a prefix-cache entry holds it). ``alloc`` hands out ref=1
    pages, ``share`` adds a reference (prefix reuse / cache registration),
    ``release`` drops one and returns fully-freed pages to the free list.
    ``audit`` asserts the free list and refcounts partition the pool — the
    no-leak / no-double-map invariant the churn tests exercise. Misuse
    (double release, unknown ids, sharing unreferenced pages) raises
    :class:`AllocatorError` with the offending page and refcount."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.free: deque[int] = deque(range(n_pages))
        self.ref = np.zeros(n_pages, np.int32)
        self.peak_used = 0

    @property
    def n_free(self) -> int:
        """Pages currently on the free list."""
        return len(self.free)

    @property
    def n_used(self) -> int:
        """Pages currently mapped or cached (refcount > 0)."""
        return self.n_pages - len(self.free)

    def alloc(self, n: int) -> Optional[list[int]]:
        """Take ``n`` pages off the free list at ref=1; None if the pool
        cannot satisfy the request (admission then waits for evictions)."""
        if n > len(self.free):
            return None
        pages = [self.free.popleft() for _ in range(n)]
        for p in pages:
            assert self.ref[p] == 0, f"free page {p} had ref {self.ref[p]}"
            self.ref[p] = 1
        self.peak_used = max(self.peak_used, self.n_used)
        return pages

    def _known(self, p: int, op: str) -> int:
        p = int(p)
        if not 0 <= p < self.n_pages:
            raise AllocatorError(
                f"{op} of unknown page {p}: valid page ids are "
                f"0..{self.n_pages - 1}"
            )
        return p

    def share(self, pages) -> None:
        """Add one reference to each already-referenced page (prefix-cache
        reuse in a new slot, or cache registration). Sharing an unknown or
        unreferenced page raises :class:`AllocatorError` — an unreferenced
        page may already be recycled into another slot's timeline."""
        for p in pages:
            p = self._known(p, "share")
            if self.ref[p] <= 0:
                raise AllocatorError(
                    f"sharing unreferenced page {p} (refcount "
                    f"{int(self.ref[p])}): only mapped or cached pages can "
                    "take another reference"
                )
            self.ref[p] += 1

    def release(self, pages) -> None:
        """Drop one reference per page; fully-unreferenced pages return to
        the free list. Releasing an unknown page or a page whose refcount is
        already zero raises :class:`AllocatorError` (a double release would
        put the page on the free list twice and hand it to two slots)."""
        for p in pages:
            p = self._known(p, "release")
            if self.ref[p] <= 0:
                raise AllocatorError(
                    f"double release of page {p} (refcount already "
                    f"{int(self.ref[p])}): the page is on the free list and "
                    "releasing it again would corrupt the pool"
                )
            self.ref[p] -= 1
            if self.ref[p] == 0:
                self.free.append(p)

    def audit(self) -> None:
        """Assert the free list and refcounts partition the pool (no leaks,
        no double-maps, no duplicated free entries)."""
        free = set(self.free)
        assert len(free) == len(self.free), "free list contains duplicates"
        for p in range(self.n_pages):
            if p in free:
                assert self.ref[p] == 0, f"free page {p} has ref {self.ref[p]}"
            else:
                assert self.ref[p] > 0, f"page {p} leaked (ref 0 but not free)"


class _PrefixEntry:
    __slots__ = ("key", "page", "eid", "parent", "children", "tick")


class PrefixCache:
    """Prompt-prefix page cache (hash-chained at page granularity).

    Entry j of a prompt's chain is keyed by (parent entry id, the page's
    token tuple), so a key identifies the FULL token prefix up to that page
    boundary without hashing collisions or storing O(S^2) token copies.
    A hit maps already-filled, fully-immutable pages (only whole pages fully
    covered by prompt tokens are ever registered; decode writes land strictly
    after the prompt, so registered pages are never written again) into the
    new slot's block table copy-free. Registered pages carry one cache
    reference; ``evict`` drops least-recently-used leaf entries to refill
    the free list when admission runs out of pages."""

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = page_size
        self.entries: dict[tuple, _PrefixEntry] = {}
        self._by_id: dict[int, _PrefixEntry] = {}
        self._next_id = 1
        self._tick = 0

    def __len__(self) -> int:
        return len(self.entries)

    def _key(self, parent: int, prompt, j: int) -> tuple:
        ps = self.page_size
        return (parent, tuple(int(t) for t in prompt[j * ps : (j + 1) * ps]))

    def match(self, prompt) -> tuple[int, list[int]]:
        """Longest cached prefix of whole pages, capped at len(prompt)-1 so
        at least one suffix token always remains to produce prefill logits.
        Returns (n_tokens_matched, pages)."""
        self._tick += 1
        pages: list[int] = []
        parent = 0
        for j in range((len(prompt) - 1) // self.page_size):
            e = self.entries.get(self._key(parent, prompt, j))
            if e is None:
                break
            e.tick = self._tick
            pages.append(e.page)
            parent = e.eid
        return len(pages) * self.page_size, pages

    def register(self, prompt, pages: list[int]) -> None:
        """Register a freshly admitted prompt's full pages (``pages`` = the
        slot's mapped pages in timeline order, shared prefix included)."""
        self._tick += 1
        parent = 0
        for j in range(min(len(prompt) // self.page_size, len(pages))):
            key = self._key(parent, prompt, j)
            e = self.entries.get(key)
            if e is None:
                e = _PrefixEntry()
                e.key, e.page, e.parent = key, pages[j], parent
                e.eid = self._next_id
                self._next_id += 1
                e.children = 0
                self.entries[key] = e
                self._by_id[e.eid] = e
                if parent:
                    self._by_id[parent].children += 1
                self.allocator.share([e.page])
            e.tick = self._tick
            parent = e.eid

    def evict(self, n_free_needed: int, protect=()) -> int:
        """Drop LRU leaf entries (an inner entry is only evictable once its
        children are gone) until the allocator has ``n_free_needed`` free
        pages or nothing evictable remains. Returns entries evicted.

        ``protect`` is a collection of page ids that must survive: under
        preemption, the pages an admission attempt just MATCHED are a
        preempted request's resume ticket, and evicting them to fund that
        same (possibly failing) allocation would destroy the copy-free
        restore for zero gain."""
        evicted = 0
        while self.allocator.n_free < n_free_needed:
            leaves = [
                e for e in self.entries.values()
                if e.children == 0 and e.page not in protect
            ]
            if not leaves:
                break
            e = min(leaves, key=lambda e: e.tick)
            del self.entries[e.key]
            del self._by_id[e.eid]
            if e.parent:
                self._by_id[e.parent].children -= 1
            self.allocator.release([e.page])
            evicted += 1
        return evicted


# ---------------------------------------------------------------------------
# slot-state splicing
# ---------------------------------------------------------------------------


def _slot_axes(cfg: ModelConfig, model, max_len: int):
    """Pytree of ints: the slot (batch) axis of every decode-state leaf,
    inferred by diffing a batch-2 against a batch-1 ``eval_shape`` — exactly
    one dim differs (2 vs 1), and that dim is the slot axis. Works for any
    family without hand-written per-leaf layout tables."""
    big = jax.eval_shape(lambda: model.init_decode_state(cfg, 2, max_len))
    one = jax.eval_shape(lambda: model.init_decode_state(cfg, 1, max_len))

    def axis(b, o):
        diffs = [i for i, (db, do) in enumerate(zip(b.shape, o.shape)) if db != do]
        if len(diffs) != 1 or b.shape[diffs[0]] != 2 or o.shape[diffs[0]] != 1:
            raise ValueError(f"cannot infer slot axis: {b.shape} vs {o.shape}")
        return diffs[0]

    return jax.tree.map(axis, big, one)


def _make_slot_insert(axes, keys=None) -> Callable:
    """jit-compiled splice of a batch-1 state into slot ``idx`` of the full
    state; one dynamic_update_slice_in_dim per leaf, index traced so every
    slot shares one executable. ``keys`` restricts the splice to a subset of
    (flat dict) state leaves — the paged engine splices only per-slot leaves
    and routes pooled leaves through the page writer instead."""
    if keys is None:
        def insert(state, sub, idx):
            return jax.tree.map(
                lambda leaf, subleaf, ax: jax.lax.dynamic_update_slice_in_dim(
                    leaf, subleaf.astype(leaf.dtype), idx, axis=ax
                ),
                state, sub, axes,
            )
    else:
        keys = tuple(keys)

        def insert(state, sub, idx):
            out = dict(state)
            for k in keys:
                out[k] = jax.lax.dynamic_update_slice_in_dim(
                    state[k], sub[k].astype(state[k].dtype), idx, axis=axes[k]
                )
            return out

    return jax.jit(insert)


def _make_page_writer(pool_keys) -> Callable:
    """jit-compiled scatter of a batch-1 prefill's cache rows into mapped
    pages: sub leaf (L, 1, S, ...) -> pool pages ``page_ids`` (n,). Rows are
    zero-padded / truncated to n*page_size — trailing garbage rows inside a
    reserved page are invisible behind the per-slot pos mask and overwritten
    token-by-token as decode proceeds. Retraces per (n, S) combination, both
    bucketed, so the executable count stays O(log S_max)."""
    pool_keys = tuple(pool_keys)

    def write(state, sub, page_ids):
        n = page_ids.shape[0]
        out = dict(state)
        for k in pool_keys:
            pool = state[k]
            ps = pool.shape[2]
            rows = sub[k][:, 0]  # (L, S, ...)
            need = n * ps
            if rows.shape[1] < need:
                pad = [(0, 0)] * rows.ndim
                pad[1] = (0, need - rows.shape[1])
                rows = jnp.pad(rows, pad)
            else:
                rows = rows[:, :need]
            rows = rows.reshape(rows.shape[0], n, ps, *rows.shape[2:])
            out[k] = pool.at[:, page_ids].set(rows.astype(pool.dtype))
        return out

    return jax.jit(write)


def _make_prefix_gather(pool_keys) -> Callable:
    """jit-compiled gather of shared prefix pages into the dense (L, 1, m,
    ...) context the family prefill's ``prefix`` kwarg consumes."""
    pool_keys = tuple(pool_keys)

    def gather(state, ids):
        out = {}
        for k in pool_keys:
            pool = state[k]
            pages = pool[:, ids]  # (L, m_pages, ps, ...)
            out[k] = pages.reshape(
                pool.shape[0], 1, ids.shape[0] * pool.shape[2], *pool.shape[3:]
            )
        return out

    return jax.jit(gather)


def _ngram_draft(hist: list, k: int) -> list:
    """Self-drafting for speculative decode: propose the ``k`` tokens that
    followed the most recent earlier occurrence of the history's trailing
    n-gram (n = 3, 2, 1, longest context first), falling back to repeating
    the last token. Host-side and deterministic — the draft only has to be
    cheap and often right; verification makes any draft safe."""
    n = len(hist)
    if n == 0:
        return [0] * k
    for m in (3, 2, 1):
        if n <= m:
            continue
        key = hist[n - m:]
        for j in range(n - m - 1, -1, -1):
            if hist[j : j + m] == key:
                cont = hist[j + m : j + m + k]
                if cont:
                    return cont + [cont[-1]] * (k - len(cont))
                break
    return [hist[-1]] * k


# families whose decode state is FULLY page-addressable (caches + pos only),
# so a prompt prefix maps onto shared pages with no residual per-slot state.
# vlm is excluded (patch frontends make token-hashed prefixes unsound),
# encdec has per-request encoder K/V, recurrent families carry O(1) state.
_PREFIX_FAMILIES = ("dense", "moe", "mla_moe")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


# Phases of an engine step (docs/serving.md "Observability"): each is a
# profiler span ``engine.<phase>`` and the counters ``stats["<phase>_s"]``
# (seconds) and ``stats["<phase>_n"]`` (calls).
_PHASES = ("admit", "sample", "schedule", "launch", "device_wait", "download",
           "commit", "upload")
# The newest steps whose records ``engine.step_records`` keeps.
_STEP_RECORDS = 4096


@dataclasses.dataclass
class StepRecord:
    """One engine step as ``ContinuousBatchingEngine.step_records`` keeps it.

    Times are ``time.monotonic()`` seconds, the clock of
    ``Request.token_times``. ``spans`` lists each phase the step entered, in
    order, as (phase, start, end); a phase opened inside another (a prefill
    inside admission) lies inside its parent's interval. A ragged launch also
    keeps the host arrays it uploaded: ``slot`` and ``pos`` per row (pad
    rows carry slot B), ``ctx`` (rows committed per slot before the launch)
    and ``logit_idx`` (the row whose logits each slot gets back), and what
    its ragged attention computes in each layer (``ragged_work``): the
    tile-rounded row spans and the live page steps."""

    step: int
    t0: float
    t1: float = 0.0
    spans: list = dataclasses.field(default_factory=list)
    rows: int = 0  # launched rows that carry a token
    logits_bytes: int = 0
    jit_miss: bool = False  # a jitted step traced, compiled or loaded
    slot: Optional[np.ndarray] = None
    pos: Optional[np.ndarray] = None
    ctx: Optional[np.ndarray] = None
    logit_idx: Optional[np.ndarray] = None
    attn_tile_rows: int = 0
    attn_live_page_steps: int = 0

    @property
    def phases(self) -> dict:
        """Seconds per phase; a phase entered twice sums."""
        out: dict = {}
        for name, a, b in self.spans:
            out[name] = out.get(name, 0.0) + b - a
        return out


class ContinuousBatchingEngine:
    """Continuous-batching server: a static batch of B independent slot
    timelines, per-slot admission/eviction, per-request sampling, lock-step
    decode (the TPU-efficient layout), and throughput accounting.

    ``paged=True`` switches the decode state to the paged layout (global page
    pool + per-slot block tables): cache memory is proportional to pages in
    use instead of slots x max_len, admission gates on free pages, and shared
    prompt prefixes are served from the prefix cache without re-prefilling.
    ``paged=False`` (default) keeps the dense per-slot layout — the A/B lane
    and correctness oracle for the paging invariant tests.

    Prompts are padded to power-of-two buckets by default
    (``bucket_prompts``), so prefill compiles O(log max_len) executables
    instead of one per distinct prompt length; ``compile_stats()`` reports
    the inventory.

    ``ragged=True`` (requires ``paged=True`` and a family exposing
    ``ragged_step``; dense/moe) replaces the bucketed-prefill + lock-step
    split entirely: every ``step()`` concatenates the scheduled tokens of
    ALL live slots — one decode token per decoding slot plus prompt chunks
    for admitting slots, capped at ``token_budget`` — into one flat ragged
    batch and runs ONE launch over it. Long prompts are chunked across
    steps, so decode latency stays flat during admission, and the whole
    engine compiles a single token-budget-shaped executable instead of the
    O(log max_len) prefill bucket inventory (docs/serving.md).
    ``max_chunk_share`` caps the fraction of ``token_budget`` prompt chunks
    may claim per step — the decode-priority knob under long-prompt floods.

    ``speculation=True`` (requires ``paged=True``, non-ragged, dense/moe)
    turns each decode launch into a self-speculative verify step: the
    sampled token plus ``spec_k - 1`` drafted candidates run as one
    multi-row launch through the paged-attention kernel, the longest
    greedy-matching draft prefix commits, and rejected rows roll back by a
    ``pos`` rewind. Greedy output is token-identical to the non-speculative
    engine; ``throughput()`` reports ``acceptance_rate`` and
    ``tokens_per_step`` (docs/serving.md "Speculative decoding").
    """

    def __init__(self, cfg: ModelConfig, params, batch_slots: int = 4, max_len: int = 128,
                 paged: bool = False, page_size: int = 16, n_pages: Optional[int] = None,
                 prefix_caching: bool = True, bucket_prompts: bool = True,
                 on_truncation: str = "warn", ragged: bool = False,
                 token_budget: int = 64, max_chunk_share: float = 1.0,
                 preemption: bool = False, speculation: bool = False,
                 spec_k: int = 4, draft_fn: Optional[Callable] = None):
        if on_truncation not in ("warn", "reject"):
            raise ValueError(f"on_truncation must be 'warn' or 'reject', got {on_truncation!r}")
        if not 0.0 < max_chunk_share <= 1.0:
            raise ValueError(
                f"max_chunk_share must be in (0, 1], got {max_chunk_share}"
            )
        self.cfg = cfg
        self.model = get_model(cfg)
        # serving default: pre-merge sibling quantized packs (q/k/v, gate/up,
        # wq_a/wkv_a) ONCE so fused launches read merged packs directly —
        # trace-time fusion would otherwise re-concatenate the packs inside
        # every jitted step (they are jit arguments, not constants). A no-op
        # for bf16/w4a16/already-merged trees; skipped when the process-wide
        # fusion toggle is off (the benchmarks' --no-fused A/B lane).
        from repro.core.twinquant import fuse_params
        from repro.kernels.dispatch import fusion_enabled

        self.params = fuse_params(params) if fusion_enabled() else params
        self.batch = batch_slots
        self.max_len = max_len
        self.paged = paged
        self.bucket_prompts = bucket_prompts
        self.on_truncation = on_truncation
        # preempt + requeue under page pressure (paged mode only): opt-in so
        # the no-preemption admission behavior stays the A/B baseline
        self.preemption = bool(preemption)
        self._steps = 0  # lifetime engine steps (deadline_steps clock)
        self._next_rid = 0
        # frontend row inflation: vlm prefill prepends n_patches rows to the
        # decoder cache, so capacity/page math must count them with the prompt
        self._extra_rows = cfg.n_patches if cfg.family == "vlm" else 0
        # structural leaf classification (slot axis / optional seq axis)
        self._layout = C.paged_layout(self.model.init_decode_state, cfg, max_len)
        self._pool_keys = tuple(k for k, (_, seq) in self._layout.items() if seq is not None)
        axes = _slot_axes(cfg, self.model, max_len)

        if paged and self._pool_keys:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self.page_size = page_size
            self._max_pages = -(-max_len // page_size)
            self.n_pages = n_pages if n_pages is not None else batch_slots * self._max_pages
            self.state = C.init_paged_state(
                self.model.init_decode_state, cfg, batch_slots, max_len, page_size, self.n_pages
            )
            self.allocator: Optional[PageAllocator] = PageAllocator(self.n_pages)
            self.prefix_cache: Optional[PrefixCache] = (
                PrefixCache(self.allocator, page_size)
                if prefix_caching and cfg.family in _PREFIX_FAMILIES else None
            )
            self._bt = np.full((batch_slots, self._max_pages), -1, np.int32)
            slot_keys = tuple(k for k in self._layout if k not in self._pool_keys)
            self._insert = _make_slot_insert(axes, keys=slot_keys)
            self._page_write = _make_page_writer(self._pool_keys)
            self._prefix_gather = _make_prefix_gather(self._pool_keys)
        else:
            # dense per-slot layout — also the degenerate "paged" layout for
            # purely recurrent families, whose state has nothing to page
            self.page_size = 0
            self.n_pages = 0
            self.state = self.model.init_decode_state(cfg, batch_slots, max_len)
            self.allocator = None
            self.prefix_cache = None
            self._insert = _make_slot_insert(axes)
        # constant zero batch-1 state, built once: the splice source for every
        # admission (prefill never donates/mutates its inputs)
        self._sub_template = self.model.init_decode_state(cfg, 1, max_len)
        self.slots: list[Optional[Request]] = [None] * batch_slots
        self.queue: deque[Request] = deque()
        self._decode = jax.jit(make_decode_step(cfg))
        self._prefill = jax.jit(make_prefill_step(cfg))
        self._prefill_traces: dict[tuple, int] = {}
        # unified ragged step (chunked prefill + decode in one launch)
        self.ragged = False
        self.token_budget = int(token_budget)
        self.max_chunk_share = float(max_chunk_share)
        if ragged:
            ok = (
                self.allocator is not None
                and self._extra_rows == 0
                and getattr(self.model, "ragged_step", None) is not None
            )
            if not ok:
                warnings.warn(
                    "ragged=True needs paged mode and a family with a "
                    "ragged_step (dense/moe); falling back to bucketed "
                    "prefill + lock-step decode",
                    stacklevel=2,
                )
            else:
                if self.token_budget < batch_slots:
                    raise ValueError(
                        f"token_budget ({self.token_budget}) must be >= "
                        f"batch_slots ({batch_slots}) so every decoding slot "
                        f"gets a row each step"
                    )
                self.ragged = True
                # ``_ragged`` is what the step calls (a caller may wrap it);
                # ``_ragged_jit`` is the jit whose cache counts misses
                self._ragged = self._ragged_jit = jax.jit(make_ragged_step(cfg))
                # host mirror of per-slot committed rows: the ragged loop
                # never downloads state["pos"] (no per-step sync for it)
                self._pos_host = np.zeros(batch_slots, np.int32)
        # self-speculative multi-token verification (docs/serving.md
        # "Speculative decoding"): each decode launch stacks the sampled
        # token plus spec_k-1 self-drafted candidates per slot and accepts
        # the longest greedy-matching prefix. Needs the paged layout (the
        # rollback is a pos rewind behind the full up-front page
        # reservation) and a family whose decode_step takes (B, sq) rows
        # through the paged_decode kernel (dense/moe).
        self.speculation = False
        self.spec_k = int(spec_k)
        self._draft_fn = draft_fn
        self._spec_traces: dict[tuple, int] = {}
        if speculation:
            from repro.kernels.autotune import DECODE_M_MAX

            ok = (
                self.allocator is not None
                and not self.ragged
                and self._extra_rows == 0
                and cfg.family in ("dense", "moe")
            )
            if not ok:
                warnings.warn(
                    "speculation=True needs paged (non-ragged) mode and a "
                    "family with multi-row paged decode (dense/moe); falling "
                    "back to one-token decode steps",
                    stacklevel=2,
                )
            elif not 2 <= self.spec_k <= DECODE_M_MAX:
                raise ValueError(
                    f"spec_k must be in [2, {DECODE_M_MAX}] (the kernel's "
                    f"multi-query row cap), got {self.spec_k}"
                )
            else:
                self.speculation = True
        # latency observability (docs/serving.md "SLO metrics"): every request
        # ever submitted (for engine.latency()) and a per-step queue-depth
        # sample — both host bookkeeping, no device traffic
        self._requests: list[Request] = []
        self._queue_depths: list[int] = []
        self.stats = {
            "prefill_tokens": 0, "prefill_s": 0.0,
            "decode_tokens": 0, "decode_steps": 0, "decode_s": 0.0,
            "requests_done": 0, "requests_truncated": 0,
            "requests_failed": 0, "requests_cancelled": 0,
            "requests_timed_out": 0, "requests_preempted": 0,
            "prefix_lookups": 0, "prefix_hits": 0, "prefix_hit_tokens": 0,
            "spec_launches": 0, "spec_slot_steps": 0,
            "spec_drafted": 0, "spec_accepted": 0,
            # step spans and counters (docs/serving.md "Observability")
            "step_s": 0.0, "step_n": 0, "logits_bytes": 0, "jit_misses": 0,
            "attn_tile_rows": 0, "attn_live_page_steps": 0,
            **{f"{p}_{u}": 0.0 if u == "s" else 0 for p in _PHASES for u in "sn"},
        }
        self.step_records: deque[StepRecord] = deque(maxlen=_STEP_RECORDS)
        self._record: Optional[StepRecord] = None  # the step in progress
        self._lap: Optional[float] = None  # where the step's next phase starts
        # dispatch-counter baseline: routing() reports the delta, i.e. the
        # kernel routes this engine's traces took (quantized params only)
        from repro.kernels.dispatch import dispatch_counters

        self._dispatch0 = dispatch_counters()

    # -- admission ----------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Enqueue a request; admit immediately if a slot (and, when paged,
        enough pages) is free. Returns True when the request went straight
        into a slot. Invalid requests are rejected HERE, before touching
        queue or slot state, so one bad request can never strand a batch
        mid-generation. Re-submitting a request that is already queued or
        live is a no-op."""
        if req.status in RequestState.TERMINAL or req.done:
            return True  # already resolved (e.g. admitted+finished inside one step)
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D (S,), got shape {prompt.shape}")
        n = int(prompt.shape[0])
        if n and not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"prompt must hold integer token ids, got dtype {prompt.dtype}"
            )
        if n and (int(prompt.min()) < 0 or int(prompt.max()) >= self.cfg.vocab):
            bad = [int(t) for t in prompt if not 0 <= int(t) < self.cfg.vocab][:8]
            raise ValueError(
                f"prompt contains token ids outside the model vocab "
                f"[0, {self.cfg.vocab}): {bad} — rejected at submit() so "
                "garbage input fails at the API boundary, not as an XLA "
                "gather deep inside prefill"
            )
        rows = n + self._extra_rows  # cache rows the prompt occupies
        if not 1 <= rows < self.max_len:
            raise ValueError(
                f"prompt length {n} (+{self._extra_rows} frontend rows) must "
                f"leave room in max_len={self.max_len}"
            )
        if rows + req.max_new > self.max_len:
            msg = (f"request will truncate: prompt rows {rows} + max_new {req.max_new} "
                   f"> max_len {self.max_len} (the slot runs out of cache rows "
                   f"after {self.max_len - rows} new tokens)")
            if self.on_truncation == "reject":
                raise ValueError(msg)
            warnings.warn(msg, stacklevel=2)
        if self.allocator is not None:
            worst = -(-min(rows + req.max_new, self.max_len) // self.page_size)
            if worst > self.n_pages:
                raise ValueError(
                    f"request needs up to {worst} pages but the pool only has "
                    f"{self.n_pages}; it could never be admitted"
                )
        if any(s is req for s in self.slots) or any(q is req for q in self.queue):
            return any(s is req for s in self.slots)
        if req.request_id is None:
            req.request_id = f"req-{self._next_rid}"
            self._next_rid += 1
        if req.t_submit is None:
            req.t_submit = time.monotonic()
        if all(req is not r for r in self._requests):
            self._requests.append(req)
        req._prompt_host = prompt.astype(np.int32)
        if req.status == RequestState.NEW:
            self._set_status(req, RequestState.QUEUED)
        if req.deadline_steps is not None and req._deadline_step is None:
            req._deadline_step = self._steps + int(req.deadline_steps)
        if req.deadline_s is not None and req._deadline_t is None:
            req._deadline_t = time.monotonic() + float(req.deadline_s)
        self.queue.append(req)
        self._admit()
        return any(s is req for s in self.slots)

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Power-of-two prompt bucket (min 8), capped at the cache capacity."""
        return max(n, min(1 << max(3, (n - 1).bit_length()), cap))

    def _run_prefill(self, req: Request, tokens: np.ndarray, off: int = 0,
                     shared_pages: Optional[list[int]] = None):
        """One batched prefill of ``tokens`` (the prompt, or the suffix after
        ``off`` prefix-cached tokens), bucket-padded. Returns (last_logits
        np (V,), sub_state, bucket_len)."""
        s_real = len(tokens)
        cap = self.max_len - off - self._extra_rows
        bucket = self._bucket(s_real, cap) if self.bucket_prompts else s_real
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :s_real] = tokens
        kwargs = dict(req.frontend)
        if bucket != s_real or off or self.bucket_prompts:
            kwargs["length"] = jnp.full((1,), s_real, jnp.int32)
        if off:
            kwargs["prefix"] = self._prefix_gather(
                {k: self.state[k] for k in self._pool_keys},
                jnp.asarray(shared_pages, jnp.int32),
            )
        key = (bucket, off, tuple(sorted(req.frontend)))
        self._prefill_traces[key] = self._prefill_traces.get(key, 0) + 1
        t0 = time.monotonic()
        with self._launch(self._prefill):
            logits, sub = self._prefill(self.params, jnp.asarray(toks), self._sub_template,
                                        **kwargs)
            logits = logits[0, -1].astype(jnp.float32)
        last = self._fetch_logits(logits)  # sync-point
        last = C.logits_tap(last, "prefill")
        self.stats["prefill_s"] += time.monotonic() - t0
        self.stats["prefill_tokens"] += s_real
        return last, sub, bucket

    def _check_prefill_logits(self, last: np.ndarray) -> None:
        """Finite-logits guard on the freshly-downloaded prefill row: a
        non-finite row fails only THIS request (reason ``nan_logits``), never
        the batch."""
        if C.nonfinite_rows(last[None, :], self.cfg.vocab):
            raise _SlotFault("nan_logits", "non-finite prefill logits")

    # -- lifecycle ----------------------------------------------------------

    def _set_status(self, req: Request, new: str) -> None:
        allowed = _TRANSITIONS.get(req.status, frozenset())
        if new not in allowed:
            raise RuntimeError(
                f"illegal request state transition {req.status} -> {new} "
                f"(request {req.request_id})"
            )
        req.status = new

    def _finish(self, req: Request, status: str,
                code: Optional[str] = None, detail: Optional[str] = None) -> None:
        """Terminal host bookkeeping shared by every exit path: transition to
        ``status``, set ``done``, capture the failure reason, bump the
        matching stats counter."""
        self._set_status(req, status)
        req.done = True
        if req.t_done is None:
            req.t_done = time.monotonic()
        if code is not None:
            req.error = code
            req.error_detail = detail
        req._last_logits = None
        self.stats[_FINISH_COUNTER[status]] += 1

    def _release_slot(self, i: int) -> None:
        """Release slot ``i``'s pages and neutralize its device state — the
        ONE reclaim path every exit (done, failed, cancelled, timed out,
        preempted, stalled) goes through, so no exit can leak pages or leave
        a stale block-table row attending garbage."""
        if self.allocator is not None:
            self.allocator.release([int(p) for p in self._bt[i] if p >= 0])
            self._bt[i, :] = -1
            # block-table upload is a sanctioned exit-path transfer: the
            # transfer-guard sanitizer keeps the rest of the decode loop
            # transfer-free (see analysis/sanitizers.guarded_decode)
            with jax.transfer_guard("allow"):
                self.state["bt"] = jnp.asarray(self._bt)
                # neutralize the freed slot: pos 0 + unmapped block table means
                # its lock-step garbage decode attends nothing and writes nowhere
                self.state["pos"] = self.state["pos"].at[i].set(0)
        if self.ragged:
            self._pos_host[i] = 0

    def _effective_prompt(self, req: Request) -> np.ndarray:
        """The request's prompt as the engine should (re)prefill it now:
        the original prompt, extended by the generated tokens when the
        request was preempted mid-generation (host arrays only — no device
        transfer)."""
        base = req._prompt_host
        if base is None:  # direct _admit_one callers that bypassed submit()
            base = np.asarray(req.prompt, np.int32)
        if not req.out:
            return base
        return np.concatenate([base, np.asarray(req.out, np.int32)])

    def _committed_rows(self, i: int, req: Request) -> int:
        """Cache rows slot ``i`` has actually written (prompt + generated)."""
        if self.ragged:
            return int(self._pos_host[i])
        return len(req._prompt_host) + self._extra_rows + len(req.out)

    def cancel(self, request) -> bool:
        """Cancel a queued or live request by ``request_id`` (or the Request
        object itself). A live request's pages are released and its slot
        neutralized exactly like an eviction; generated-so-far tokens stay on
        ``req.out``. Returns True when the request was cancelled, False when
        it is unknown or already terminal."""
        req = None
        if isinstance(request, Request):
            req = request
        else:
            for r in list(self.queue) + [s for s in self.slots if s is not None]:
                if r.request_id == request:
                    req = r
                    break
        if req is None or req.status in RequestState.TERMINAL:
            return False
        for i, s in enumerate(self.slots):
            if s is req:
                self.slots[i] = None
                self._release_slot(i)
                self._finish(req, RequestState.CANCELLED)
                return True
        try:
            self.queue.remove(req)
        except ValueError:
            return False  # not queued, not live: nothing to cancel
        self._finish(req, RequestState.CANCELLED)
        return True

    def _deadline_code(self, req: Request, now: float) -> Optional[str]:
        if req._deadline_step is not None and self._steps > req._deadline_step:
            return "deadline_steps"
        if req._deadline_t is not None and now >= req._deadline_t:
            return "deadline_s"
        return None

    def _expire_deadlines(self) -> None:
        """TIME_OUT every queued or live request whose step/wall-clock
        deadline has passed; live slots release pages through the common
        exit path. Runs at the top of every engine step."""
        now = time.monotonic()
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            code = self._deadline_code(req, now)
            if code:
                self.slots[i] = None
                self._release_slot(i)
                self._finish(req, RequestState.TIMED_OUT, code,
                             f"deadline expired at engine step {self._steps}")
        if any(self._deadline_code(q, now) for q in self.queue):
            keep: deque[Request] = deque()
            for q in self.queue:
                code = self._deadline_code(q, now)
                if code:
                    self._finish(q, RequestState.TIMED_OUT, code,
                                 f"deadline expired at engine step {self._steps} "
                                 "while queued")
                else:
                    keep.append(q)
            self.queue = keep

    def _preempt(self, i: int, req: Request) -> None:
        """Preempt slot ``i``: register its fully-written pages under
        prompt+generated (so readmission restores them copy-free from the
        prefix cache), release the slot's page reservation, keep the
        generated tokens, and re-enqueue. A resumed greedy request emits
        tokens identical to an uninterrupted run — the effective prompt IS
        the uninterrupted timeline."""
        committed = self._committed_rows(i, req)
        eff = self._effective_prompt(req)
        if self.prefix_cache is not None and not req.frontend:
            row = [int(p) for p in self._bt[i] if p >= 0]
            self.prefix_cache.register(eff[:committed], row)
        self._set_status(req, RequestState.PREEMPTED)
        req._preemptions += 1
        self.stats["requests_preempted"] += 1
        self.slots[i] = None
        self._release_slot(i)
        req._last_logits = None
        req._filled = 0
        req._prompt = None
        self.queue.append(req)

    def _preempt_for(self, head: Request, admitted: list) -> bool:
        """Pick and preempt one victim so the page-starved ``head`` can
        admit: the lowest-priority live slot, ties broken by longest
        remaining quota. Only a STRICTLY lower-priority slot is eligible —
        equal-priority preemption could ping-pong two requests that each
        need the whole pool, whereas strict ordering makes every preemption
        chain finite. Slots admitted during this admission pass and slots
        about to hit capacity anyway are also exempt. Returns True when a
        victim was preempted (the caller retries the head)."""
        if not self.preemption or self.allocator is None:
            return False
        victims = [
            (req.priority, -(req.max_new - len(req.out)), i)
            for i, req in enumerate(self.slots)
            if req is not None
            and all(req is not a for a in admitted)
            and req.priority < head.priority
            and self._committed_rows(i, req) + 1 < self.max_len
        ]
        if not victims:
            return False
        _, _, i = min(victims)
        self._preempt(i, self.slots[i])
        return True

    def _admit(self) -> None:
        admitted: list = []
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return
            head = self.queue[0]
            if self._admit_one(head, free[0]):
                self.queue.popleft()
                admitted.append(head)
                continue
            # page-gated: preempt the cheapest victim and retry the head, or
            # (preemption off / no eligible victim) wait for evictions
            if not self._preempt_for(head, admitted):
                return

    def _admit_one_ragged(self, req: Request, i: int) -> bool:
        """Ragged-mode admission: reserve the request's pages (prefix-cache
        hits included) and park it in slot ``i`` with its chunk cursor at the
        first uncached prompt token. No prefill call happens here — the
        prompt is streamed through subsequent ``_step_ragged`` launches in
        token-budget-sized chunks."""
        prompt = self._effective_prompt(req)  # prompt (+generated, if preempted)
        n = len(prompt)
        remaining = req.max_new - len(req.out)
        need = min(n + remaining, self.max_len)
        n_res = -(-need // self.page_size)
        m_tok, shared = 0, []
        if self.prefix_cache is not None and not req.frontend:
            self.stats["prefix_lookups"] += 1
            # no power-of-two bucketing of the match: chunk scheduling is
            # position-exact, so any matched page count costs zero extra
            # traces (the single token-budget executable covers all offsets)
            m_tok, shared = self.prefix_cache.match(prompt)
        self.allocator.share(shared)
        pages = self.allocator.alloc(n_res - len(shared))
        if pages is None and self.prefix_cache is not None:
            # under preemption, never evict the pages this attempt matched —
            # they are the preempted request's copy-free resume ticket
            protect = frozenset(shared) if self.preemption else frozenset()
            self.prefix_cache.evict(n_res - len(shared), protect=protect)
            pages = self.allocator.alloc(n_res - len(shared))
        if pages is None:
            self.allocator.release(shared)
            return False  # admission gated on free pages
        self._set_status(req, RequestState.PREFILL)
        try:
            if m_tok:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += m_tok
            row = shared + pages
            self._bt[i, :] = -1
            self._bt[i, : len(row)] = row
            with jax.transfer_guard("allow"):
                self.state["bt"] = jnp.asarray(self._bt)
            req._prompt = prompt
            req._filled = m_tok
            self._pos_host[i] = m_tok
            req._last_logits = None
            if req._rng is None:  # survive preemption: don't reset the stream
                req._rng = np.random.default_rng(req.sampling.seed)
            self.slots[i] = req
        except Exception as e:
            # quarantine THIS request, release its whole reservation, and
            # report the head as consumed so the rest of the queue proceeds
            self._bt[i, :] = -1
            with jax.transfer_guard("allow"):
                self.state["bt"] = jnp.asarray(self._bt)
            self.allocator.release(shared + pages)
            self.slots[i] = None
            self._pos_host[i] = 0
            self._finish(req, RequestState.FAILED, *_fault_of(e))
        return True

    def _admit_one(self, req: Request, i: int) -> bool:
        if self.ragged:
            return self._admit_one_ragged(req, i)
        if self.allocator is None:
            self._set_status(req, RequestState.PREFILL)
            try:
                last, sub, _ = self._run_prefill(req, self._effective_prompt(req))
                self._check_prefill_logits(last)
                self.state = self._insert(self.state, sub, i)
            except Exception as e:
                self._finish(req, RequestState.FAILED, *_fault_of(e))
                return True  # consumed (quarantined), not page-gated
        else:
            prompt = self._effective_prompt(req)  # prompt (+generated, if preempted)
            n = len(prompt)
            # reserve the request's full timeline up front (prompt rows incl.
            # frontend inflation + remaining quota) so decode never needs a
            # mid-flight allocation; preemption is the only sanctioned reclaim
            # path and it releases whole reservations
            remaining = req.max_new - len(req.out)
            need = min(n + self._extra_rows + remaining, self.max_len)
            n_res = -(-need // self.page_size)
            m_tok, shared = 0, []
            if self.prefix_cache is not None and not req.frontend:
                self.stats["prefix_lookups"] += 1
                m_tok, shared = self.prefix_cache.match(prompt)
                if shared:
                    # bucket the prefix to a power-of-two page count: the
                    # suffix-prefill executable is shaped by the prefix
                    # length, so raw offsets would compile one trace per
                    # distinct matched length — this keeps the inventory
                    # O(log max_pages), like prompt bucketing itself
                    keep = 1 << (len(shared).bit_length() - 1)
                    shared = shared[:keep]
                    m_tok = keep * self.page_size
            # take our reference on the shared pages BEFORE any eviction:
            # cache eviction under pressure may drop the matched entries, and
            # an unreferenced match could be recycled out from under us
            self.allocator.share(shared)
            n_own = n_res - len(shared)
            pages = self.allocator.alloc(n_own)
            if pages is None and self.prefix_cache is not None:
                # under preemption, never evict the pages this attempt
                # matched — they are the resume ticket of a preempted request
                protect = frozenset(shared) if self.preemption else frozenset()
                self.prefix_cache.evict(n_own, protect=protect)
                pages = self.allocator.alloc(n_own)
            if pages is None:
                self.allocator.release(shared)
                return False  # admission gated on free pages
            self._set_status(req, RequestState.PREFILL)
            try:
                if m_tok:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += m_tok
                last, sub, bucket = self._run_prefill(req, prompt[m_tok:], off=m_tok,
                                                      shared_pages=shared)
                self._check_prefill_logits(last)
                self.state = self._insert(self.state, sub, i)
                n_write = min(-(-(bucket + self._extra_rows) // self.page_size),
                              len(pages))
                self.state = self._page_write(
                    self.state, sub, jnp.asarray(pages[:n_write], jnp.int32)
                )
                row = shared + pages
                self._bt[i, :] = -1
                self._bt[i, : len(row)] = row
                self.state["bt"] = jnp.asarray(self._bt)
                if self.prefix_cache is not None and not req.frontend:
                    self.prefix_cache.register(prompt, row)
            except Exception as e:
                # quarantine THIS request (tampered pack, NaN prefill, ...):
                # hand back the whole reservation, neutralize the row, and
                # consume the queue head so the fault can't wedge admission
                self._bt[i, :] = -1
                with jax.transfer_guard("allow"):
                    self.state["bt"] = jnp.asarray(self._bt)
                self.allocator.release(shared + pages)
                self._finish(req, RequestState.FAILED, *_fault_of(e))
                return True
        req._last_logits = last
        if req._rng is None:  # survive preemption: don't reset the stream
            req._rng = np.random.default_rng(req.sampling.seed)
        self._set_status(req, RequestState.DECODE)
        self.slots[i] = req
        return True

    # -- sampling -----------------------------------------------------------

    def _sample(self, req: Request) -> int:
        logits = req._last_logits[: self.cfg.vocab]
        sp = req.sampling
        if sp.temperature <= 0.0:
            return int(np.argmax(logits))
        scaled = logits / sp.temperature
        if sp.top_k > 0 and sp.top_k < scaled.shape[0]:
            kth = np.partition(scaled, -sp.top_k)[-sp.top_k]
            scaled = np.where(scaled < kth, -np.inf, scaled)
        p = np.exp(scaled - scaled.max())
        p /= p.sum()
        return int(req._rng.choice(p.shape[0], p=p))

    def _emit_token(self, req: Request, tok: int) -> None:
        """The ONE token-emission path every mode (bucketed, ragged,
        speculative) goes through: append to ``req.out``, stamp the
        wall-clock emission time (TTFT on the first), and fire the request's
        ``on_token`` stream callback exactly once for this token. A raising
        callback is detached with a warning — the consumer loses its stream,
        the batch loses nothing."""
        now = time.monotonic()
        req.out.append(tok)
        req.token_times.append(now)
        if req.t_first_token is None:
            req.t_first_token = now
        if req.on_token is not None:
            try:
                req.on_token(req, tok)
            except Exception as e:  # noqa: BLE001 — hostile-consumer guard
                req.on_token = None
                warnings.warn(
                    f"on_token callback for request {req.request_id} raised "
                    f"{type(e).__name__}: {e} — callback detached, request "
                    "continues without streaming",
                    stacklevel=2,
                )

    # -- decode -------------------------------------------------------------

    def _evict(self, i: int, req: Request, truncated: bool) -> None:
        self.slots[i] = None
        self._release_slot(i)
        req.truncated = truncated
        if truncated:
            self.stats["requests_truncated"] += 1
        self._finish(req, RequestState.DONE)

    def _draft_tokens(self, req: Request, k: int) -> list:
        """``k`` draft tokens continuing the request's committed history
        (prompt + generated, the just-sampled token included). An installed
        ``draft_fn(req, k)`` hook (e.g. a small draft model) takes precedence
        over the built-in n-gram self-draft; its proposals are clamped into
        the vocab so a sloppy hook cannot crash the embed gather."""
        if self._draft_fn is not None:
            d = [int(t) for t in self._draft_fn(req, k)][:k]
            d = [min(max(t, 0), self.cfg.vocab - 1) for t in d]
            last = d[-1] if d else (req.out[-1] if req.out else 0)
            return d + [last] * (k - len(d))
        hist = req._prompt_host.tolist() + req.out
        return _ngram_draft(hist, k)

    def _step_spec(self, active: list) -> None:
        """One speculative decode launch (docs/serving.md "Speculative
        decoding"): per live slot, sample the next token from the held
        logits (exactly the non-speculative commit), stack it with
        ``spec_k - 1`` self-drafted candidates, and run ONE multi-row decode
        launch — the paged kernel attends all rows causally and the page
        scatter writes all rows' KV. Greedy slots then accept the longest
        draft prefix matching the launch's own argmaxes (each accepted row's
        logits re-verify the next), capped by quota and cache capacity;
        sampled (temperature > 0) slots commit only the sampled token, so
        their random streams are untouched. Rejected rows are rolled back by
        rewinding ``pos`` — the full up-front page reservation makes the
        stale rows invisible behind the prefix mask until overwritten."""
        k = self.spec_k
        tok = np.zeros((self.batch, k), np.int32)
        pos = self._fetch(self.state["pos"])  # sync-point: next write offset per slot
        live: list[int] = []
        drafts: dict[int, list] = {}
        for i in active:
            req = self.slots[i]
            nxt = self._sample(req)
            self._emit_token(req, nxt)
            if len(req.out) >= req.max_new:
                self._evict(i, req, truncated=False)
            elif int(pos[i]) >= self.max_len:
                self._evict(i, req, truncated=True)
            else:
                drafts[i] = self._draft_tokens(req, k - 1)
                tok[i, 0] = nxt
                tok[i, 1:] = drafts[i]
                live.append(i)
        if not live:
            return
        t0 = time.monotonic()
        with self._launch(self._decode), jax.transfer_guard("allow"):
            logits, self.state = self._decode(self.params, self.state, jnp.asarray(tok))
            logits = logits.astype(jnp.float32)
        last = self._fetch_logits(logits)  # sync-point: (B, k, V) verify download
        last = C.logits_tap(last, "decode")
        dt = time.monotonic() - t0
        self._spec_traces[(self.batch, k)] = self._spec_traces.get((self.batch, k), 0) + 1
        # flat row b*k + j -> slot b (all of a bad slot's rows are suspect)
        bad = {f // k for f in C.nonfinite_rows(last, self.cfg.vocab)}
        # phase 1: acceptance — longest draft prefix whose tokens match the
        # launch's own greedy choices, then rewind pos past the rejects
        committed: dict[int, int] = {}
        delta = np.zeros(self.batch, np.int32)
        for i in live:
            req = self.slots[i]
            n_acc = 0
            if i not in bad and req.sampling.temperature <= 0.0:
                quota_room = req.max_new - len(req.out)
                cap_rows = self.max_len - int(pos[i]) - 1
                while (n_acc < k - 1 and n_acc < quota_room and n_acc < cap_rows
                       and int(drafts[i][n_acc])
                       == int(np.argmax(last[i, n_acc, : self.cfg.vocab]))):
                    self._emit_token(req, int(drafts[i][n_acc]))
                    n_acc += 1
                self.stats["spec_drafted"] += k - 1
                self.stats["spec_accepted"] += n_acc
            committed[i] = 1 + n_acc
            delta[i] = k - committed[i]
        with self._phase("upload"), jax.transfer_guard("allow"):
            # sync-point: upload the per-slot rewind (rejected rows become
            # invisible garbage past pos, overwritten by the next commits)
            self.state["pos"] = self.state["pos"] - jnp.asarray(delta)
        self.stats["decode_s"] += dt
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += sum(committed.values())
        self.stats["spec_launches"] += 1
        self.stats["spec_slot_steps"] += len(live)
        # phase 2: per-slot exits AFTER the rewind (the release path zeroes
        # pos; rewinding later would resurrect the freed slot's offset)
        for i in live:
            req = self.slots[i]
            if i in bad:
                self.slots[i] = None
                self._release_slot(i)
                self._finish(req, RequestState.FAILED, "nan_logits",
                             f"non-finite decode logits at engine step "
                             f"{self._steps}")
                continue
            req._last_logits = last[i, committed[i] - 1]
            if len(req.out) >= req.max_new:
                self._evict(i, req, truncated=False)
            elif int(pos[i]) + committed[i] >= self.max_len:
                # mirror the non-speculative order exactly: the token past
                # the last cache row is still sampled and kept, THEN the
                # slot exits (truncated unless that token filled the quota)
                self._emit_token(req, self._sample(req))
                self._evict(i, req, truncated=len(req.out) < req.max_new)

    def _step_ragged(self) -> int:
        """One unified ragged engine step (docs/serving.md): sample + schedule
        one decode token per decoding slot FIRST (decode rows are never
        displaced by admission), fill the remaining token budget with prompt
        chunks FIFO across admitting slots, then run ONE ``ragged_step``
        launch over the flat batch. Pad rows carry the sentinel slot id B and
        are inert in attention and cache writes. The phases admit, sample,
        schedule, launch, device_wait, download, commit and admit again
        partition the step."""
        with self._phase("admit"):
            self._expire_deadlines()
            self._admit()
            self._queue_depths.append(len(self.queue))
            active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return 0
        # decode tokens first: a slot mid-generation gets its row every step
        decode: list[tuple[int, int]] = []  # (slot, sampled token)
        with self._phase("sample"):
            for i in active:
                req = self.slots[i]
                if req._last_logits is None:
                    continue  # still prefilling — chunks scheduled below
                nxt = self._sample(req)
                self._emit_token(req, nxt)
                # quota filled (or no cache row left for the new token): evict
                # BEFORE the launch — its next logits would be discarded anyway
                if len(req.out) >= req.max_new:
                    self._evict(i, req, truncated=False)
                elif int(self._pos_host[i]) >= self.max_len:
                    self._evict(i, req, truncated=True)
                else:
                    decode.append((i, nxt))
        with self._phase("schedule"):
            budget = self.token_budget
            tokens = np.zeros(budget, np.int32)
            slot = np.full(budget, self.batch, np.int32)  # pad sentinel = B
            pos = np.zeros(budget, np.int32)
            logit_idx = np.zeros(self.batch, np.int32)
            for r, (i, nxt) in enumerate(decode):
                tokens[r] = nxt
                slot[r] = i
                pos[r] = self._pos_host[i]
                logit_idx[i] = r
            row = len(decode)
            # prompt chunks fill whatever budget decode left, FIFO across
            # slots, additionally capped at max_chunk_share of the token
            # budget — the decode-priority knob: a long-prompt flood can never
            # swell the launch beyond the configured share, so steady decoders
            # keep their per-step cadence at a bounded launch size. The floor
            # of one token keeps admission live even at tiny shares.
            chunk_cap = max(1, int(self.token_budget * self.max_chunk_share))
            chunks: list[tuple[int, int]] = []  # (slot, tokens scheduled)
            n_chunk = 0
            for i in active:
                req = self.slots[i]
                if req is None or req._last_logits is not None:
                    continue
                space = min(budget - row, chunk_cap - n_chunk)
                if space <= 0:
                    break
                take = min(space, len(req._prompt) - req._filled)
                tokens[row : row + take] = req._prompt[req._filled : req._filled + take]
                slot[row : row + take] = i
                pos[row : row + take] = self._pos_host[i] + np.arange(take, dtype=np.int32)
                if req._filled + take == len(req._prompt):
                    logit_idx[i] = row + take - 1  # last prompt token's logits
                chunks.append((i, take))
                n_chunk += take
                row += take
            ctx = self._pos_host.copy()
            tile_rows, page_steps = ragged_work(
                slot, ctx, n_slots=self.batch, page=self.page_size
            )
        if row == 0:
            with self._phase("admit"):
                self._admit()
            return len(active)
        t0 = time.monotonic()
        with self._launch(self._ragged_jit), jax.transfer_guard("allow"):
            logits, self.state = self._ragged(
                self.params, self.state, jnp.asarray(tokens), jnp.asarray(slot),
                jnp.asarray(pos), jnp.asarray(ctx), jnp.asarray(logit_idx),
            )
            logits = logits.astype(jnp.float32)
        last = self._fetch_logits(logits)  # sync-point: per-slot logits download
        dt = time.monotonic() - t0
        with self._phase("commit"):
            last = C.logits_tap(last, "ragged")
            rec = self._record
            rec.rows, rec.slot, rec.pos, rec.ctx, rec.logit_idx = row, slot, pos, ctx, logit_idx
            rec.attn_tile_rows, rec.attn_live_page_steps = tile_rows, page_steps
            self.stats["attn_tile_rows"] += tile_rows
            self.stats["attn_live_page_steps"] += page_steps
            # split wall time by scheduled-token share so both tok/s stay honest
            self.stats["decode_s"] += dt * len(decode) / row
            self.stats["prefill_s"] += dt * n_chunk / row
            self.stats["decode_steps"] += 1
            self.stats["decode_tokens"] += len(decode)
            self.stats["prefill_tokens"] += n_chunk
            # finite-logits guard at the step's single sync point: a NaN/Inf row
            # fails only its own slot; every other slot's bytes are untouched
            bad = set(C.nonfinite_rows(last, self.cfg.vocab))
            for i, _ in decode:
                req = self.slots[i]
                if i in bad:
                    self.slots[i] = None
                    self._release_slot(i)
                    self._finish(req, RequestState.FAILED, "nan_logits",
                                 f"non-finite decode logits at engine step {self._steps}")
                    continue
                self._pos_host[i] += 1
                req._last_logits = last[i]
            for i, take in chunks:
                req = self.slots[i]
                self._pos_host[i] += take
                req._filled += take
                if req._filled == len(req._prompt):
                    if i in bad:
                        self.slots[i] = None
                        self._release_slot(i)
                        self._finish(req, RequestState.FAILED, "nan_logits",
                                     f"non-finite prefill logits at engine step "
                                     f"{self._steps}")
                        continue
                    req._last_logits = last[i]
                    self._set_status(req, RequestState.DECODE)
                    # deferred prefix registration: the prompt's pages are only
                    # fully written once its last chunk lands
                    if self.prefix_cache is not None and not req.frontend:
                        self.prefix_cache.register(
                            req._prompt, [int(p) for p in self._bt[i] if p >= 0]
                        )
        with self._phase("admit"):
            self._admit()
        return len(active)

    def _step_bucketed(self) -> int:
        """One bucketed engine step: admission (prompts prefill as they are
        admitted), then one sampled token per active slot and one lock-step
        decode launch for the slots that still need logits."""
        with self._phase("admit"):
            self._expire_deadlines()
            self._admit()
        self._queue_depths.append(len(self.queue))
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return 0
        if self.speculation:
            self._step_spec(active)
            with self._phase("admit"):
                self._admit()
            return len(active)
        tok = np.zeros((self.batch, 1), np.int32)
        pos = self._fetch(self.state["pos"])  # sync-point: next write offset per slot
        live = []
        for i in active:
            req = self.slots[i]
            nxt = self._sample(req)
            self._emit_token(req, nxt)
            tok[i, 0] = nxt
            # a request whose quota is now filled (or whose token has no cache
            # row left) is evicted BEFORE the decode — its final logits would
            # be discarded anyway
            if len(req.out) >= req.max_new:
                self._evict(i, req, truncated=False)
            elif int(pos[i]) >= self.max_len:
                self._evict(i, req, truncated=True)
            else:
                live.append(i)
        if live:
            t0 = time.monotonic()
            with self._launch(self._decode), jax.transfer_guard("allow"):
                logits, self.state = self._decode(self.params, self.state, jnp.asarray(tok))
                logits = logits[:, -1].astype(jnp.float32)
            last = self._fetch_logits(logits)  # sync-point
            last = C.logits_tap(last, "decode")
            self.stats["decode_s"] += time.monotonic() - t0
            self.stats["decode_steps"] += 1
            self.stats["decode_tokens"] += len(live)
            # finite-logits guard at the step's sync point: quarantine only
            # the offending slot, every other slot's logits are untouched
            bad = set(C.nonfinite_rows(last, self.cfg.vocab))
            for i in live:
                req = self.slots[i]
                if i in bad:
                    self.slots[i] = None
                    self._release_slot(i)
                    self._finish(req, RequestState.FAILED, "nan_logits",
                                 f"non-finite decode logits at engine step "
                                 f"{self._steps}")
                else:
                    req._last_logits = last[i]
        with self._phase("admit"):
            self._admit()
        return len(active)

    def step(self) -> int:
        """Admit queued work, sample one token per active slot, then one
        lock-step decode for the slots that still need logits (ragged mode:
        one unified chunked-prefill + decode launch, see ``_step_ragged``).
        Returns the number of slots that were live at entry.

        The step is a profiler step ``engine.step``; its seconds and calls
        go to ``stats["step_s"]`` / ``stats["step_n"]`` and its
        :class:`StepRecord` to ``step_records``."""
        self._steps += 1
        rec = self._record = StepRecord(self._steps, time.monotonic())
        self._lap = rec.t0
        try:
            with jax.profiler.StepTraceAnnotation("engine.step", step_num=self._steps):
                return self._step_ragged() if self.ragged else self._step_bucketed()
        finally:
            rec.t1 = time.monotonic()
            self._record = self._lap = None
            self.stats["step_s"] += rec.t1 - rec.t0
            self.stats["step_n"] += 1
            self.step_records.append(rec)

    # -- step spans ---------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, name: str):
        """Span one phase of the engine step: a profiler annotation
        ``engine.<name>`` on the device trace's clock, its seconds added to
        ``stats["<name>_s"]``, one call to ``stats["<name>_n"]``, and an
        entry in the step's record. A phase at the top of a step starts
        where the step's previous phase ended, so those phases partition the
        step; a phase inside another, or outside any step, times itself."""
        top = self._lap is not None
        t0 = self._lap if top else time.monotonic()
        self._lap = None
        try:
            with jax.profiler.TraceAnnotation(f"engine.{name}"):
                yield
        finally:
            t1 = time.monotonic()
            if top:
                self._lap = t1
            self.stats[f"{name}_s"] += t1 - t0
            self.stats[f"{name}_n"] += 1
            if self._record is not None:
                self._record.spans.append((name, t0, t1))

    @contextlib.contextmanager
    def _launch(self, jitted):
        """The ``engine.launch`` phase around one call of ``jitted``: uploads,
        dispatch and return. A call after which the jit's executable cache
        grew (a trace, a compile, or a load from the persistent cache)
        counts in ``stats["jit_misses"]`` and marks the step's record."""
        n0 = jitted._cache_size()
        with self._phase("launch"):
            yield
            if jitted._cache_size() > n0:
                self.stats["jit_misses"] += 1
                if self._record is not None:
                    self._record.jit_miss = True

    def _fetch(self, x: jax.Array) -> np.ndarray:
        """Bring ``x`` to the host at a sync point: ``engine.device_wait``
        until the device has computed it, then ``engine.download`` for the
        device-to-host copy alone."""
        with self._phase("device_wait"):
            x.block_until_ready()  # sync-point
        with self._phase("download"), jax.transfer_guard("allow"):
            return np.asarray(x)  # sync-point

    def _fetch_logits(self, logits: jax.Array) -> np.ndarray:
        """``_fetch`` for logits: their bytes go to ``stats["logits_bytes"]``
        and to the step's record."""
        last = self._fetch(logits)
        self.stats["logits_bytes"] += last.nbytes
        if self._record is not None:
            self._record.logits_bytes += last.nbytes
        return last

    # -- drivers ------------------------------------------------------------

    def run_until_done(self, max_steps: int = 100_000) -> None:
        """Drive ``step()`` until no slot is live and the queue is empty.

        Exhausting ``max_steps`` SURFACES instead of silently stopping: every
        still-live or still-queued request is marked ``TIMED_OUT`` (error code
        ``engine_stalled``), its pages are released through the common exit
        path, and :class:`EngineStalledError` is raised — a wedged engine can
        never be mistaken for a drained one."""
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                return
        stranded: list[str] = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            stranded.append(str(req.request_id))
            self.slots[i] = None
            self._release_slot(i)
            self._finish(req, RequestState.TIMED_OUT, "engine_stalled",
                         f"run_until_done exhausted {max_steps} steps")
        while self.queue:
            req = self.queue.popleft()
            stranded.append(str(req.request_id))
            self._finish(req, RequestState.TIMED_OUT, "engine_stalled",
                         f"run_until_done exhausted {max_steps} steps while queued")
        raise EngineStalledError(
            f"engine stalled: run_until_done exhausted {max_steps} steps with "
            f"{len(stranded)} request(s) unfinished ({', '.join(stranded)}); "
            "they are marked TIMED_OUT and their pages have been released"
        )

    def serve(self, requests: list[Request], max_steps: int = 100_000) -> list[Request]:
        """Submit all requests and drive the loop to completion. Results ride
        on the Request objects (``out``, ``done``, ``truncated``)."""
        for r in requests:
            self.submit(r)
        self.run_until_done(max_steps)
        return requests

    def stream(self, request: Request, max_steps: int = 100_000):
        """Submit ``request`` and yield its tokens as the engine emits them.

        A synchronous streaming iterator (docs/serving.md "Stream API"):
        each ``next()`` drives ``step()`` until at least one new token lands
        on ``request.out`` (so the time-to-first-yield IS the TTFT, modulo
        the consumer's own latency), then yields the tokens in emission
        order. Other queued/active requests keep being served by the same
        steps — streaming one request does not stall the batch. Returns
        when the request reaches a terminal state; exhausting ``max_steps``
        marks every unfinished request ``TIMED_OUT`` through the common
        exit path and raises :class:`EngineStalledError`, exactly like
        :meth:`run_until_done`. For callback-style consumption (many
        concurrent streams) set ``Request.on_token`` and drive the engine
        yourself."""
        self.submit(request)
        emitted = 0
        for _ in range(max_steps):
            self.step()
            while emitted < len(request.out):
                yield request.out[emitted]
                emitted += 1
            if request.done:
                return
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.slots[i] = None
            self._release_slot(i)
            self._finish(req, RequestState.TIMED_OUT, "engine_stalled",
                         f"stream exhausted {max_steps} steps")
        while self.queue:
            req = self.queue.popleft()
            self._finish(req, RequestState.TIMED_OUT, "engine_stalled",
                         f"stream exhausted {max_steps} steps while queued")
        raise EngineStalledError(
            f"engine stalled: stream exhausted {max_steps} steps with request "
            f"{request.request_id} unfinished; unfinished requests are marked "
            "TIMED_OUT and their pages have been released"
        )

    def reset_stats(self) -> None:
        """Zero the timing counters (e.g. after a warm-up pass).

        The latency surface (request roster + queue-depth samples feeding
        ``latency()``) is cleared too — a warm-up request's compile-inflated
        TTFT would otherwise sit in the percentiles forever. The
        dispatch-routing baseline is NOT reset: routing decisions happen
        at trace time, so a warm executable would otherwise report an empty
        route table. The prefill-trace inventory (compile_stats) persists for
        the same reason. ``step_records`` is emptied with the counters."""
        self.stats = {k: type(v)() for k, v in self.stats.items()}
        self.step_records.clear()
        self._requests.clear()
        self._queue_depths.clear()
        if self.allocator is not None:
            self.allocator.peak_used = self.allocator.n_used

    # -- introspection ------------------------------------------------------

    def compile_stats(self) -> dict:
        """Prefill executable inventory: with prompt bucketing every distinct
        (bucket, prefix-offset, frontend) triple is one trace, so the count
        stays O(log max_len) under arbitrary prompt-length traffic."""
        return {
            "prefill_traces": len(self._prefill_traces),
            "prefill_calls": sum(self._prefill_traces.values()),
            "prefill_buckets": sorted({k[0] for k in self._prefill_traces}),
            # distinct (prefix-offset, frontend) variants: the recompile
            # sanitizer's budget is O(log max_len) buckets PER variant
            "prefill_variants": len({k[1:] for k in self._prefill_traces}),
            "decode_traces": 1 if (self.stats["decode_steps"] and not self.ragged
                                   and not self.speculation) else 0,
            # ragged mode compiles ONE token-budget-shaped executable for
            # everything (chunked prefill + decode); the compile-budget
            # sanitizer asserts ragged_traces + prefill_traces <= 2. The
            # jit's cache grows on every miss, so a recompile shows here.
            "ragged_traces": self._ragged_jit._cache_size() if self.ragged else 0,
            # speculative mode likewise compiles ONE (batch, spec_k)-shaped
            # decode executable; every distinct spec launch shape is a trace
            "spec_traces": len(self._spec_traces),
        }

    def memory(self) -> dict:
        """Cache-memory accounting: the paged pool's bytes and peak pages in
        use vs the dense per-slot footprint the same (batch, max_len) engine
        would allocate — the capacity headroom paging buys."""
        dense_shapes = jax.eval_shape(
            lambda: self.model.init_decode_state(self.cfg, self.batch, self.max_len)
        )
        dense_bytes = sum(
            int(np.prod(dense_shapes[k].shape)) * dense_shapes[k].dtype.itemsize
            for k in self._pool_keys
        )
        out = {
            "mode": "paged" if self.allocator is not None else "dense",
            "dense_cache_bytes": dense_bytes,
        }
        if self.allocator is None:
            out["cache_bytes"] = dense_bytes
            out["peak_cache_bytes"] = dense_bytes
            return out
        page_bytes = 0
        for k in self._pool_keys:
            pool = self.state[k]
            page_bytes += int(np.prod(pool.shape[:1] + pool.shape[2:])) * pool.dtype.itemsize
        out.update(
            page_size=self.page_size,
            n_pages=self.n_pages,
            page_bytes=page_bytes,
            cache_bytes=page_bytes * self.n_pages,
            pages_in_use=self.allocator.n_used,
            pages_peak=self.allocator.peak_used,
            peak_cache_bytes=page_bytes * self.allocator.peak_used,
            prefix_entries=0 if self.prefix_cache is None else len(self.prefix_cache),
        )
        return out

    def check_page_invariants(self) -> None:
        """Debug/test hook: allocator audit plus exact refcount accounting —
        every pool page's refcount equals the number of slot block-tables
        mapping it plus its prefix-cache registrations, no slot maps a page
        twice, and free/used pages partition the pool."""
        if self.allocator is None:
            return
        self.allocator.audit()
        refs = np.zeros(self.n_pages, np.int32)
        for i in range(self.batch):
            row = [int(p) for p in self._bt[i] if p >= 0]
            assert len(set(row)) == len(row), f"slot {i} maps a page twice: {row}"
            assert self.slots[i] is not None or not row, \
                f"empty slot {i} still maps pages {row}"
            assert (self.slots[i] is None
                    or self.slots[i].status not in RequestState.TERMINAL), \
                f"slot {i} holds terminal request {self.slots[i].request_id}"
            for p in row:
                refs[p] += 1
        if self.prefix_cache is not None:
            for e in self.prefix_cache.entries.values():
                refs[e.page] += 1
        assert np.array_equal(refs, self.allocator.ref), (
            f"refcount drift: mapped+cached {refs.tolist()} "
            f"vs allocator {self.allocator.ref.tolist()}"
        )

    def routing(self) -> dict:
        """Kernel routes taken by this engine's traces: {kind/path: count}.

        Counts compiled routes (trace-time dispatch decisions) for the
        quantized linears in this engine's prefill/decode executables —
        the end-to-end evidence that decode steps hit the decode-shaped
        kernel schedule and prefill steps hit the prefill one, and (kind
        ``dual_fused``) that sibling projections (q/k/v, gate/up) ran as
        one fused launch rather than one per sibling. The per-kind sums
        are the launches-per-traced-step number the bench gate ratchets.

        Attribution caveat: the underlying counters are process-global, so
        the delta also includes routes traced by OTHER engines (or eager
        quant_linear calls) between this engine's construction and now.
        Reliable per-engine attribution requires constructing and driving
        engines sequentially, as the benchmarks do."""
        from repro.kernels.dispatch import dispatch_counters

        now = dispatch_counters()
        return {
            k: v - self._dispatch0.get(k, 0)
            for k, v in now.items()
            if v - self._dispatch0.get(k, 0) > 0
        }

    def throughput(self) -> dict:
        """Tokens/s summary from the accounting counters."""
        st = self.stats
        return {
            "decode_tok_s": st["decode_tokens"] / max(st["decode_s"], 1e-9),
            "prefill_tok_s": st["prefill_tokens"] / max(st["prefill_s"], 1e-9),
            "mean_batch_occupancy": st["decode_tokens"] / max(st["decode_steps"], 1),
            # speculative decode quality: drafts accepted / drafts verified,
            # and committed tokens per slot per decode launch (>= 1.0; the
            # speculation speedup lever, 1.0 exactly when speculation is off)
            "acceptance_rate": st["spec_accepted"] / max(st["spec_drafted"], 1),
            "tokens_per_step": (
                st["decode_tokens"] / max(st["spec_slot_steps"], 1)
                if self.speculation
                else (1.0 if st["decode_tokens"] else 0.0)
            ),
            "routing": self.routing(),
            **st,
        }

    def latency(self, slo=None) -> dict:
        """SLO-facing latency summary over every request this engine has seen
        (docs/serving.md "SLO metrics & traffic harness"): TTFT / per-token
        / end-to-end percentiles, goodput under ``slo`` (an
        :class:`repro.launch.metrics.SLO` or None for raw throughput),
        queue-depth profile, preemption and prefix-hit rates. Timing comes
        from the wall-clock stamps ``submit()``/``_emit_token``/``_finish``
        record on each Request; the counters ride on ``self.stats``. Sits
        beside :meth:`routing` and :meth:`throughput` as the third
        introspection surface — this one is about tails, not means."""
        from repro.launch.metrics import summarize

        return summarize(self._requests, slo=slo,
                         queue_depths=self._queue_depths, stats=self.stats)


# Backwards-compatible name: the engine replaced the original demo Server.
Server = ContinuousBatchingEngine
