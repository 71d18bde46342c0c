"""Batched decode serving loop (continuous-batching-lite, multi-tenant).

A request queue feeds fixed-size decode batches; finished sequences are
swapped out slot-wise while the rest keep decoding — the slot-batching
scheme of production LLM servers reduced to its JAX essentials:

- one jitted decode step with **per-slot positions** (slots are at
  different sequence offsets),
- an **active-slot mask**: the cache of inactive slots is frozen by a
  jitted blend (recurrent states would otherwise advance on pad tokens),
- **chunked batched prefill** (FastDecode): a whole admitted group's
  prompts run through ``model.prefill_into_slots`` in prompt chunks —
  one full-sequence dispatch per chunk scatters the K/V rows straight
  into the slot-batched cache and the final chunk's logits emit each
  request's first token.  A P-token prompt costs ``ceil(P /
  prefill_chunk)`` dispatches per group instead of P whole-model decode
  dispatches per request; chunk lengths are bucketed to powers of two so
  ragged prompts hit a handful of compiled shapes.  Non-attention
  families (recurrent/SSM state would advance on padding) and
  ``prefill_chunk=0`` fall back to the legacy per-token priming, which
  decodes the prompt through the same step as generation.

Multi-tenant (BlockDelta) serving: requests may carry an ``adapter_id``
resolved against an adapter registry (``repro.adapters``).  One base
model stays resident; the scheduler groups slots by adapter and runs
each group for a micro-batch of decode steps, hot-swapping the delta
rows between turns (row scatter-swap — O(delta) bytes, not O(params)).
Because inactive slots are masked out of both the cache blend and token
emission, a slot only ever decodes under its own adapter's weights:
per-request outputs are identical to a single-tenant server running
that adapter alone — regardless of scheduling policy or caching tier.

**Adapter-aware scheduling** (default).  Rotating round-robin pays a
swap pair at every turn boundary even when the resident adapter still
has queued work.  The aware scheduler instead:

- prefers filling free slots with queued requests of the *resident*
  adapter (zero-swap turn renewal) before rotating;
- sizes each turn per adapter — ``steps_per_turn`` scaled by the
  group's share of pending requests (deep queues amortize their swap
  over a longer micro-batch), clamped to ``[1, 4*steps_per_turn]`` and
  truncated when another group's SLO deadline would expire inside it;
- honors per-request deadlines: ``Request.slo_ms`` (converted to decode
  steps via ``ms_per_step``; pass ``"auto"`` to calibrate it from a
  wall-clock EMA of the measured step time) pulls a group to the front
  of rotation when its slack runs low;
- bounds starvation with an aging rule: any runnable group that has
  waited ``aging_steps`` decode steps preempts residency at the next
  turn boundary, so the worst-case wait is
  ``aging_steps + 4*steps_per_turn`` regardless of skew.

**AdapterCache** (``adapters/device_cache.py``): pass ``cache_bytes >
0`` and hot adapters' delta rows stay resident in HBM — a tenant flip
whose delta is cached is a device-to-device scatter-swap with zero
host->device transfer (the registry's host LRU is the second tier,
disk the third).  Reverted adapters are captured into the cache from
the revert's displaced rows, so a tenant's delta crosses the host
boundary at most once while it stays hot.

**PagedKV** (``runtime/paged_kv.py``): pass ``kv_layout="paged"`` and
the dense ``[slots, max_seq]`` KV cache becomes a pool of fixed-size
pages addressed through per-slot page tables — HBM is paid per live
token, not per worst-case slot, so the same bytes admit far more
concurrent requests on mixed-length workloads.  Admission turns
*continuous*: every decode step retires finished requests (their
pages free immediately) and admits queued ones against a worst-case
page reservation, so a mid-flight allocation can never fail and the
wedge guard in ``run_until_drained`` stays an invariant.  With
``prefix_share`` (and an all-global-attention config) tenants with a
common prompt prefix map the *same* physical pages copy-on-write:
pages split lazily on the first diverging write.  Token streams are
bit-identical to the dense layout — the paged decode path gathers the
exact dense-shaped view through the page table (or runs the fused
write+attend Pallas kernel) and chunked prefill mirrors the dense
concat.  Per-request streaming is available on both layouts via
``Request.on_token``.
"""
from __future__ import annotations

import functools
import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as model_lib
from repro.obs import MetricsRegistry
from repro.runtime import paged_kv
from repro.runtime.serve_config import ServeConfig

STATS_VERSION = 2  # nested sections only; flat aliases removed in PR 9

BASE = None  # adapter id of the un-adapted base model


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [P] int32
    max_new_tokens: int = 16
    adapter_id: Optional[str] = BASE   # None => base model
    slo_ms: Optional[float] = None     # per-request deadline budget
    on_token: Optional[Callable[[int], None]] = None  # streaming callback
    out: List[int] = field(default_factory=list)
    done: bool = False
    submit_step: int = -1       # decode-step clock at submit()
    first_token_step: int = -1  # decode-step clock at first output token
    finish_step: int = -1       # decode-step clock at completion
    submit_ns: int = -1         # monotonic clock at submit() (tracing)

    def replay_clone(self, rid: int) -> "Request":
        """Failover replay of this (in-flight) request on a peer
        replica: the clone's prompt is the retained prompt plus every
        token already streamed, its budget the remaining tokens.
        Greedy decode is a deterministic function of the prefix, so the
        clone's continuation is bit-identical to what an uninterrupted
        run would have emitted next.

        Stream splice: the clone's ``on_token`` forwards each token
        into THIS request's ``out``/``on_token``, **deduplicated at the
        emitted-token watermark** — the clone's k-th token occupies
        stream position ``watermark + k`` and is dropped if the
        original already holds it (e.g. a fenced-but-not-dead replica
        raced one more step in) — so downstream consumers observe every
        stream position exactly once, in order, fault or no fault.
        When the clone finishes, completion is propagated back by the
        failover driver (``Router.step``), not here."""
        watermark = len(self.out)
        remaining = self.max_new_tokens - watermark
        assert remaining > 0, \
            f"request {self.rid} already emitted its full budget"
        prompt = np.asarray(self.prompt).ravel()
        if watermark:
            prompt = np.concatenate(
                [prompt, np.asarray(self.out, prompt.dtype)])
        clone = Request(rid=rid, prompt=prompt,
                        max_new_tokens=remaining,
                        adapter_id=self.adapter_id, slo_ms=self.slo_ms)

        def _forward(tok: int, _orig=self, _clone=clone,
                     _base=watermark) -> None:
            pos = _base + len(_clone.out) - 1   # out appended pre-callback
            if len(_orig.out) == pos:           # watermark dedup
                _orig.out.append(tok)
                if _orig.on_token is not None:
                    _orig.on_token(tok)

        clone.on_token = _forward
        return clone


def _lane(adapter_id: Optional[str]) -> str:
    """One trace lane per tenant; the base model gets its own."""
    return f"tenant:{adapter_id}" if adapter_id is not BASE else "tenant:base"


def _jit_cache_size(fn) -> int:
    """Compiled-entry count of a jitted fn.  Growth across a call ==
    that call compiled."""
    return fn._cache_size()


@functools.lru_cache(maxsize=None)
def _decode_fn(cfg, attn_impl):
    """Shared jitted decode step per (cfg, attn_impl) — every server on
    the same architecture reuses one compilation (``ModelConfig`` is
    frozen/hashable)."""

    def _decode(params, cache, token, pos_vec, active_mask):
        logits, new_cache = model_lib.decode_step(
            params, cfg, cache, token, pos_vec, attn_impl=attn_impl)

        def blend(n, o):
            m = active_mask.reshape((1, -1) + (1,) * (n.ndim - 2)) \
                if n.ndim >= 2 else active_mask
            return jnp.where(m, n, o)

        return logits, jax.tree.map(blend, new_cache, cache)

    return jax.jit(_decode, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _paged_decode_fn(cfg, attn_impl):
    """Paged decode step: the page table rides along and the model masks
    inactive slots itself (pooled caches write through the table, dense
    ring blocks drop the write) — no server-side cache blend needed."""

    def _decode(params, cache, token, pos_vec, active_mask, page_table):
        return model_lib.decode_step(params, cfg, cache, token, pos_vec,
                                     attn_impl=attn_impl,
                                     page_table=page_table,
                                     active=active_mask)

    return jax.jit(_decode, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg, chunk_len, chunk_start):
    """Shared jitted chunk-prefill per (cfg, chunk shape) — chunk lengths
    are bucketed by the server, so the compile count stays at a handful
    of static shapes per architecture."""

    def _pf(params, cache, tokens, lengths):
        return model_lib.prefill_into_slots(params, cfg, cache, tokens,
                                            lengths,
                                            chunk_start=chunk_start)

    return jax.jit(_pf, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _paged_prefill_fn(cfg, chunk_len, chunk_start):
    """Paged chunk-prefill: rows scatter into physical pages through the
    page table; ``begin`` [B] skips rows below each slot's shared-prefix
    match (those pages are mapped, not recomputed)."""

    def _pf(params, cache, tokens, lengths, page_table, begin):
        return model_lib.prefill_into_slots(params, cfg, cache, tokens,
                                            lengths,
                                            chunk_start=chunk_start,
                                            page_table=page_table,
                                            begin=begin)

    return jax.jit(_pf, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _verify_fn(cfg):
    """Jitted speculative verifier (dense KV): scores K candidate
    positions per slot in one dispatch.  Per-slot chunk starts are
    TRACED (unlike ``_prefill_fn``'s static chunk_start) — one compile
    per (cfg, K) regardless of where each slot's frontier sits."""

    def _vf(params, cache, tokens, starts, active):
        return model_lib.verify_into_slots(params, cfg, cache, tokens,
                                           starts, active)

    return jax.jit(_vf, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _paged_verify_fn(cfg):
    """Paged speculative verifier: the chunk scatters through the page
    table (pages pre-allocated by ``ensure_range``); rejected rows are
    returned to the pool host-side via ``PageAllocator.rollback_to``."""

    def _vf(params, cache, tokens, starts, active, page_table):
        return model_lib.verify_into_slots(params, cfg, cache, tokens,
                                           starts, active,
                                           page_table=page_table)

    return jax.jit(_vf, donate_argnums=(1,))


def spec_accept(draft: Sequence[int], verify: Sequence[int]
                ) -> Tuple[int, List[int]]:
    """The speculative acceptance rule (greedy / longest-prefix).

    ``draft`` — the N tokens the base model proposed; ``verify`` — the
    N + 1 greedy argmaxes of the adapter model at positions
    ``pos .. pos + N`` (``verify[j]`` is what the adapter would emit
    after the last emitted token followed by ``draft[:j]``).  Returns
    ``(accepted, emitted)`` where ``accepted`` is the length of the
    longest prefix with ``draft[j] == verify[j]`` and ``emitted =
    verify[:accepted + 1]`` — the accepted drafts plus the adapter's
    own next token (a correction on mismatch, a bonus on full accept).
    Every emitted token is an adapter argmax, so the stream is
    bit-identical to non-speculative greedy decoding by construction.
    """
    n = len(draft)
    if len(verify) != n + 1:
        raise ValueError(f"verify must score n+1 positions "
                         f"(n={n}, got {len(verify)})")
    a = 0
    while a < n and draft[a] == verify[a]:
        a += 1
    return a, [int(t) for t in verify[:a + 1]]


@functools.lru_cache(maxsize=None)
def _copy_pages_fn():
    """Jitted device half of a COW split (src -> dst page copies in every
    pooled leaf).  jit's shape cache handles the pair-count bucketing."""
    return jax.jit(model_lib.copy_cache_pages, donate_argnums=(0,))


def _chunk_bucket(k: int, cap: int) -> int:
    """Round a ragged tail-chunk length up to the next power of two
    (capped at the configured chunk) — bounds recompiles without padding
    every prompt to the full chunk."""
    b = 1
    while b < k:
        b <<= 1
    return min(b, cap)


class DecodeServer:
    def __init__(self, cfg, params, config: Optional[ServeConfig] = None,
                 *, registry=None, cache=None, tracer=None, metrics=None,
                 **legacy):
        # one-release deprecation shim: the pre-PR-9 flat kwargs
        # (batch_slots=..., kv_layout=..., speculate=..., ...) still
        # construct, mapped onto a ServeConfig, but warn.  New code
        # passes `config=ServeConfig(...)`; runtime objects (registry,
        # cache, tracer, metrics) stay explicit kwargs — they are not
        # part of what the config describes.
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=ServeConfig(...) or legacy flat "
                    f"kwargs, not both (got {sorted(legacy)})")
            config = ServeConfig.from_legacy_kwargs(**legacy)
            warnings.warn(
                "DecodeServer(**flat_kwargs) is deprecated; pass "
                "config=ServeConfig(...) — e.g. "
                f"ServeConfig.from_legacy_kwargs({', '.join(sorted(legacy))}"
                ") builds the equivalent config",
                DeprecationWarning, stacklevel=2)
        if config is None:
            config = ServeConfig()
        self.config = config
        batch_slots = config.batch_slots
        max_seq = config.max_seq
        attn_impl = config.attn_impl
        prefill_chunk = config.prefill_chunk
        steps_per_turn = config.sched.steps_per_turn
        adapter_aware = config.sched.adapter_aware
        aging_steps = config.sched.aging_steps or None   # 0 = auto
        ms_per_step = config.sched.ms_per_step
        swap_mode = config.sched.swap_mode
        cache_bytes = config.sched.cache_bytes
        kv_layout = config.kv.layout
        kv_page_size = config.kv.page_size
        kv_pages = config.kv.pages
        prefix_share = config.kv.prefix_share
        speculate = config.spec.draft
        spec_adaptive = config.spec.adaptive
        self.cfg = cfg
        # TraceKit: tracer=None disables tracing (hot paths guard with a
        # single `is None` check — no NullTracer dispatch).  The metrics
        # registry is always live: it is the source of the stats()
        # sections, and its per-step cost (a few uncontended lock
        # acquires) is noise next to a jitted decode dispatch.
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if registry is not None:
            # the server owns its resident weights: hot swaps donate the
            # edited leaves in place, so they must not alias caller arrays
            from repro.adapters import copy_tree
            params = copy_tree(params)
        self.params = params            # live tree (current adapter applied)
        self.slots = batch_slots
        self.max_seq = max_seq
        self.registry = registry
        self.steps_per_turn = max(1, steps_per_turn)
        self.swap_mode = swap_mode
        self.adapter_aware = adapter_aware
        self.aging_steps = (3 * self.steps_per_turn if aging_steps is None
                            else max(1, aging_steps))
        # "auto": calibrate ms_per_step from a wall-clock EMA of measured
        # decode-step time (closes the ROADMAP AdapterCache follow-up) —
        # SLO slack then tracks the actual hardware instead of the 1.0
        # placeholder.  A float pins it (deterministic tests/benches).
        self._ms_auto = ms_per_step == "auto"
        self._ms_samples = 0
        self.ms_per_step = 1.0 if self._ms_auto else float(ms_per_step)
        self.cache = cache
        if self.cache is None and cache_bytes > 0:
            if registry is None:
                raise ValueError("cache_bytes needs an adapter registry")
            from repro.adapters.device_cache import AdapterCache
            self.cache = AdapterCache(registry, cache_bytes=cache_bytes,
                                      tracer=tracer)
        elif self.cache is not None and tracer is not None \
                and getattr(self.cache, "tracer", None) is None:
            self.cache.tracer = tracer
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.pos = np.zeros(batch_slots, np.int32)  # next write index
        # KV layout: dense [slots, max_seq] rows, or PagedKV — a page
        # pool + per-slot page tables + the host-side allocator
        # (runtime/paged_kv.py).  Page tables ride into the jitted step
        # as a traced [slots, pages] int32, so admissions / COW splits
        # never recompile.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        self.kv_layout = kv_layout
        self.alloc: Optional[paged_kv.PageAllocator] = None
        self._plans: Dict[int, paged_kv.AdmitPlan] = {}
        if kv_layout == "paged":
            if not model_lib.supports_paged_kv(cfg):
                raise ValueError(
                    "kv_layout='paged' needs an all-attention, token-only "
                    "architecture (recurrent/SSM state is not paged)")
            ps = int(kv_page_size)
            # 0 = auto: the dense-equivalent page count (every slot can
            # hold max_seq tokens) + the null page.  Pass a smaller
            # kv_pages to oversubscribe slots against aggregate tokens.
            npages = int(kv_pages) or batch_slots * (max_seq // ps) + 1
            self.alloc = paged_kv.PageAllocator(
                npages, ps, batch_slots, max_seq,
                share_prefix=(prefix_share
                              and model_lib.supports_prefix_share(cfg)),
                metrics=self.metrics, tracer=tracer)
            self.cache_state = model_lib.init_paged_cache(
                cfg, batch_slots, npages, ps, max_seq)
        else:
            self.cache_state = model_lib.init_cache(cfg, batch_slots,
                                                    max_seq)
        self.tokens = np.zeros((batch_slots, 1), np.int32)
        self.steps = 0
        # adapter swap state
        self._applied: Optional[str] = BASE
        self._displaced = None          # SparseDelta restoring the base
        self._turn_group: Optional[str] = BASE
        self._turn_left = 0
        self._last_served: Dict[Optional[str], int] = {}
        self.swaps = 0
        self.swap_bytes = 0
        self.attn_impl = attn_impl
        self._decode = (_paged_decode_fn(cfg, attn_impl)
                        if self.alloc is not None
                        else _decode_fn(cfg, attn_impl))
        # SpecServe: self-speculative decoding.  The base model — always
        # resident under BlockDelta (a tenant differs by <5% of rows) —
        # drafts ``speculate`` tokens via the plain decode path, then the
        # adapter-applied model scores all N+1 positions in ONE verify
        # dispatch; the longest greedy-agreeing prefix is accepted
        # (see ``spec_accept``) so streams stay bit-identical to
        # non-speculative serving.  ``spec_adaptive`` backs the per-group
        # draft length off when the acceptance EMA drops (a divergent
        # tenant wastes draft steps) and grows it back toward
        # ``speculate`` when acceptance recovers.
        self.speculate = max(0, int(speculate))
        self.spec_adaptive = bool(spec_adaptive)
        if self.speculate and not model_lib.supports_spec_decode(cfg):
            raise ValueError(
                "speculate > 0 needs an all-global-attention, token-only "
                "architecture: rejected draft rows roll back by position "
                "masking, which ring-buffer local-attention rows do not "
                "support (see model.supports_spec_decode)")
        self._verify = None
        if self.speculate:
            self._verify = (_paged_verify_fn(cfg) if self.alloc is not None
                            else _verify_fn(cfg))
        self._spec_len: Dict[Optional[str], int] = {}
        self._spec_ema: Dict[Optional[str], float] = {}
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        # chunked batched prefill (FastDecode); 0 or an unsupported
        # family (recurrent/SSM) falls back to per-token priming
        self.prefill_chunk = max(0, prefill_chunk)
        self._slot_prefill = (self.prefill_chunk > 0
                              and model_lib.supports_slot_prefill(cfg))
        self.prefill_dispatches = 0      # model dispatches spent priming
        self.prefill_prompt_tokens = 0   # prompt tokens primed
        # pre-register the registry instruments so the stats() sections
        # exist from step zero (gates diff fixed key sets)
        m = self.metrics
        for c in ("decode/steps", "decode/tokens", "prefill/dispatches",
                  "prefill/prompt_tokens", "sched/swaps",
                  "sched/swap_bytes", "sched/compiles", "sched/submitted",
                  "sched/finished"):
            m.counter(c)
        if self.speculate:
            for c in ("spec/rounds", "spec/drafted", "spec/accepted",
                      "spec/rollbacks", "spec/flips"):
                m.counter(c)
            m.gauge("spec/draft_len")
            m.gauge("spec/acceptance_rate")
        for g in ("decode/ms_per_step", "sched/queue_depth",
                  "sched/swap_rate"):
            m.gauge(g)
        for h in ("decode/step_ms", "sched/request_ms",
                  "sched/queue_wait_ms"):
            m.histogram(h)

    def submit(self, req: Request):
        if req.adapter_id is not BASE:
            # reject up front: an unknown adapter discovered at schedule
            # time would wedge the queue (the request can never decode)
            if self.registry is None:
                raise ValueError(f"request {req.rid} wants adapter "
                                 f"{req.adapter_id!r} but no registry is "
                                 f"set")
            if not self.registry.exists(req.adapter_id):
                raise ValueError(f"request {req.rid}: adapter "
                                 f"{req.adapter_id!r} not in registry")
        if self.alloc is not None:
            # reject up front: a request whose worst case exceeds the
            # whole page pool could never be admitted (it would wedge
            # the queue behind an admission check that never passes)
            total = min(len(req.prompt) + req.max_new_tokens, self.max_seq)
            if not self.alloc.fits_ever(total):
                raise ValueError(
                    f"request {req.rid}: worst case {total} tokens needs "
                    f"more KV pages than the pool holds "
                    f"({self.alloc.usable_pages} x "
                    f"{self.alloc.page_size} rows)")
        req.submit_step = self.steps
        req.submit_ns = time.monotonic_ns()
        self.queue.append(req)
        self.metrics.counter("sched/submitted").inc()
        if self.tracer is not None:
            self.tracer.instant("submit", lane=_lane(req.adapter_id),
                                rid=req.rid, adapter=str(req.adapter_id),
                                prompt_len=len(req.prompt))

    # ------------------------------------------------------------------ #
    # adapter swapping
    # ------------------------------------------------------------------ #

    def _ensure_adapter(self, adapter_id: Optional[str]):
        """Make ``self.params`` carry ``adapter_id`` (lazy: no-op when it
        already does).  Swap = revert current delta rows, apply new ones;
        both are exact row swaps so the base is never corrupted.  With an
        AdapterCache the delta rows come from (and return to) HBM."""
        if adapter_id == self._applied:
            return
        from repro.adapters import delta as delta_lib
        tr = self.tracer
        if self._applied is not BASE:
            t0 = time.monotonic_ns() if tr is not None else 0
            disp, self._displaced = self._displaced, None
            # the revert's displaced rows are the leaving adapter's exact
            # resident values — capture them into the device cache so the
            # next flip to it pays no host->device transfer
            self.params, back = delta_lib.apply_delta(
                self.params, disp, mode=self.swap_mode, donate=True,
                check_fingerprint=False)
            if self.cache is not None:
                self.cache.put_back(self._applied, back)
            else:
                self.registry.release(self._applied)
            # state committed per half-swap: if the apply below fails the
            # server is consistently back on the base model
            if tr is not None:
                tr.add_span("swap_revert", t0, time.monotonic_ns(),
                            lane="sched", adapter=str(self._applied),
                            bytes=disp.nbytes)
            self._applied = BASE
            self.swap_bytes += disp.nbytes
            self.swaps += 1
            self.metrics.counter("sched/swaps").inc()
            self.metrics.counter("sched/swap_bytes").inc(disp.nbytes)
        if adapter_id is not BASE:
            t0 = time.monotonic_ns() if tr is not None else 0
            if self.cache is not None:
                d = self.cache.get(adapter_id)
            else:
                d = self.registry.acquire(adapter_id)
            try:
                self.params, self._displaced = delta_lib.apply_delta(
                    self.params, d, mode=self.swap_mode, donate=True)
            except Exception:
                if self.cache is None:
                    self.registry.release(adapter_id)
                raise
            if tr is not None:
                tr.add_span("swap_apply", t0, time.monotonic_ns(),
                            lane="sched", adapter=str(adapter_id),
                            bytes=d.nbytes)
            self._applied = adapter_id
            self.swap_bytes += d.nbytes
            self.swaps += 1
            self.metrics.counter("sched/swaps").inc()
            self.metrics.counter("sched/swap_bytes").inc(d.nbytes)

    def restore_base(self):
        """Revert any applied adapter — ``self.params`` is the pristine
        base again (bit-exact; see adapters/delta.py)."""
        self._ensure_adapter(BASE)

    # ------------------------------------------------------------------ #
    # scheduling
    # ------------------------------------------------------------------ #

    def _present_groups(self) -> List[Optional[str]]:
        """Adapter ids that can make progress RIGHT NOW, in deterministic
        order: a group with an active slot can decode; a queue-only group
        needs a free slot to admit into.  Queue-only groups with every
        slot occupied are excluded — rotating to them would pay a swap
        pair for zero decode work (they re-qualify once a slot frees)."""
        free = any(r is None for r in self.active)
        active_groups = {r.adapter_id for r in self.active if r is not None}
        seen, out = set(), []
        for r in list(self.active) + self.queue:
            if r is None or r.adapter_id in seen:
                continue
            seen.add(r.adapter_id)
            if r.adapter_id in active_groups or free:
                out.append(r.adapter_id)
        return out

    def _group_reqs(self, g) -> List[Request]:
        return [r for r in list(self.active) + self.queue
                if r is not None and r.adapter_id == g]

    def _group_has_work(self, g) -> bool:
        return bool(self._group_reqs(g))

    def _waited(self, g) -> int:
        """Decode steps since ``g`` last made progress WHILE having
        work: anchored at the later of its last served step and its
        earliest pending submit, so a tenant that drained and returned
        much later does not count the idle gap as starvation (and
        trigger a spurious preemption for a request that just
        arrived)."""
        reqs = self._group_reqs(g)
        if not reqs:
            return 0
        earliest = min(r.submit_step for r in reqs)
        last = self._last_served.get(g)
        return self.steps - (earliest if last is None
                             else max(last, earliest))

    def _min_slack(self, g) -> Optional[float]:
        """Tightest remaining deadline (in decode steps) among ``g``'s
        pending SLO-carrying requests; None when no request has one."""
        slacks = [r.submit_step + r.slo_ms / self.ms_per_step - self.steps
                  for r in self._group_reqs(g) if r.slo_ms is not None]
        return min(slacks, default=None)

    def _turn_budget(self, g, groups) -> int:
        """Per-adapter SLO-aware turn length.  ``steps_per_turn`` scaled
        up by the group's share of pending requests (deep queues
        amortize their swap over more decode steps, capped at
        ``4*steps_per_turn``), never below the base turn (a short visit
        still pays a full swap pair), extended to drain a group that
        fits entirely in the slots (finishing a small tenant in one
        visit beats paying a second flip for its tail), and truncated
        so no other runnable group's deadline expires inside the turn."""
        if not self.adapter_aware:
            return self.steps_per_turn
        cap = 4 * self.steps_per_turn
        depths = {h: max(1, len(self._group_reqs(h))) for h in groups}
        mean = sum(depths.values()) / len(depths)
        b = math.ceil(self.steps_per_turn * depths.get(g, 1) / mean)
        b = max(self.steps_per_turn, min(b, cap))
        reqs = self._group_reqs(g)
        if 0 < len(reqs) <= self.slots:
            need = max(r.max_new_tokens - len(r.out) for r in reqs)
            b = max(b, min(need, cap))
        for h in groups:
            if h == g:
                continue
            slack = self._min_slack(h)
            if slack is not None:
                b = max(1, min(b, int(slack)))
        return b

    def _pick_next(self, groups) -> Optional[str]:
        """Choose the group for a fresh turn.  Priority order: starved
        groups past the aging bound, then tight SLO deadlines, then the
        resident adapter (zero-swap), then round-robin."""
        if not self.adapter_aware:
            try:
                i = groups.index(self._turn_group)
                return groups[(i + 1) % len(groups)]
            except ValueError:
                return groups[0]
        # 1. anti-starvation: longest wait past the aging bound wins
        starved = [g for g in groups if self._waited(g) >= self.aging_steps]
        if starved:
            return min(starved,
                       key=lambda g: (-self._waited(g), groups.index(g)))
        # 2. deadline pressure: a group whose slack is about to run out
        slacks = {g: self._min_slack(g) for g in groups}
        urgent = [(slacks[g], i, g) for i, g in enumerate(groups)
                  if slacks[g] is not None
                  and slacks[g] <= self.steps_per_turn]
        if urgent:
            return min(urgent)[2]
        # 3. stay resident: renewing the applied adapter costs no swap
        if self._applied in groups:
            return self._applied
        # 4. round-robin fallback over the remaining groups
        try:
            i = groups.index(self._turn_group)
            return groups[(i + 1) % len(groups)]
        except ValueError:
            return groups[0]

    def _schedule(self) -> Optional[str]:
        """Pick the adapter group for this decode micro-step: stay on the
        current group while its turn budget lasts, then hand the choice
        to ``_pick_next``.  The budget is recomputed at EVERY turn
        boundary — including renewals of the same group — so a group
        that drained mid-turn can never leak a stale ``_turn_left`` into
        the next group's turn."""
        groups = self._present_groups()
        if not groups:
            return self._turn_group
        if self._turn_left > 0 and self._turn_group in groups:
            return self._turn_group
        nxt = self._pick_next(groups)
        self._turn_group = nxt
        self._turn_left = self._turn_budget(nxt, groups)
        return nxt

    def _mask(self, only: Optional[int] = None,
              group: Optional[str] = BASE, any_group: bool = False
              ) -> np.ndarray:
        if only is not None:
            m = np.zeros(self.slots, bool)
            m[only] = True
            return m
        return np.asarray([r is not None and
                           (any_group or r.adapter_id == group)
                           for r in self.active])

    def _emit(self, req: Request, slot: int, tok: int):
        """Record one generated token (output list + streaming callback
        + slot feedback for the next decode step)."""
        req.out.append(tok)
        self.tokens[slot, 0] = tok
        if req.on_token is not None:
            req.on_token(tok)

    def _retire(self, req: Request, slot: int):
        """Free a finished request's slot (and, paged, its KV pages —
        continuous batching re-admits against them the same step)."""
        req.done = True
        req.finish_step = self.steps
        self.active[slot] = None
        if self.alloc is not None:
            self.alloc.release_slot(slot)
            self._plans.pop(slot, None)
        self._finish(req)

    def _apply_copies(self, copies):
        """Run the device half of COW splits: pad the (src, dst) pairs
        to a power of two (null-page self-copies are no-ops) so the
        jitted copy hits a handful of compiled shapes."""
        if not copies:
            return
        n = 1
        while n < len(copies):
            n <<= 1
        src = np.zeros(n, np.int32)
        dst = np.zeros(n, np.int32)
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        self.cache_state = _copy_pages_fn()(
            self.cache_state, jnp.asarray(src), jnp.asarray(dst))

    def _admit(self, group: Optional[str] = BASE):
        """Fill free slots with queued requests of ``group`` and prime
        their prompts (the delta for ``group`` is already applied).
        Admitted requests are primed TOGETHER through the chunked
        batched prefill when the family supports it — ceil(P/chunk)
        dispatches for the whole group — else per token.

        Paged KV: admission is additionally gated on page capacity —
        each request reserves its worst case (prompt + max new tokens,
        minus shared prefix pages) and FIFO order is preserved per
        group (a request that does not fit blocks later ones, so big
        requests cannot be starved by a stream of small ones)."""
        admitted = []
        for slot in range(self.slots):
            if self.active[slot] is not None:
                continue
            qi = next((i for i, r in enumerate(self.queue)
                       if r.adapter_id == group), None)
            if qi is None:
                break
            req = self.queue[qi]
            if self.alloc is not None:
                total = min(len(req.prompt) + req.max_new_tokens,
                            self.max_seq)
                plan = self.alloc.plan(group, req.prompt, total)
                if not self.alloc.can_admit(plan.need_pages):
                    break           # pages free as active requests retire
                self.alloc.admit(slot, plan)
                self._plans[slot] = plan
            self.queue.pop(qi)
            self.active[slot] = req
            admitted.append((slot, req))
        if not admitted:
            return
        tr = self.tracer
        if tr is not None:
            now = time.monotonic_ns()
            for _, req in admitted:
                # retroactive: the wait ends at this admission
                if req.submit_ns >= 0:
                    tr.add_span("queue_wait", req.submit_ns, now,
                                lane=_lane(req.adapter_id), rid=req.rid)
        for _, req in admitted:
            if req.submit_ns >= 0:
                self.metrics.histogram("sched/queue_wait_ms").observe(
                    (time.monotonic_ns() - req.submit_ns) / 1e6)
        admit_t0 = time.monotonic_ns() if tr is not None else 0
        firsts = (self._prime_chunked(admitted) if self._slot_prefill
                  else self._prime_tokenwise(admitted))
        if tr is not None:
            tr.add_span("admit", admit_t0, time.monotonic_ns(),
                        lane="sched", group=str(group), count=len(admitted))
        for (slot, req), first in zip(admitted, firsts):
            if self.alloc is not None:
                # pin the freshly-prefilled prompt pages BEFORE the
                # first decode write: the registry pin keeps them
                # immutable (the write COW-splits), so later requests
                # with the same prefix map them instead of prefilling
                self.alloc.register(slot, group, req.prompt)
            req.first_token_step = self.steps
            self._emit(req, slot, first)
            self.pos[slot] = len(req.prompt)
            self.prefill_prompt_tokens += len(req.prompt)
            self.metrics.counter("prefill/prompt_tokens").inc(
                len(req.prompt))
            if len(req.out) >= req.max_new_tokens:
                self._retire(req, slot)

    def _prime_begins(self, admitted) -> np.ndarray:
        """Paged prime prep: make every slot's fresh prompt rows
        writable (allocating pages, COW-splitting shared ones) and
        return each slot's first self-computed position — the
        shared-prefix match length (0 for the whole batch when prefix
        sharing is off or nothing matched)."""
        begins = np.zeros(self.slots, np.int32)
        copies = []
        for slot, req in admitted:
            b = self._plans[slot].matched_len
            begins[slot] = b
            copies.extend(self.alloc.ensure_range(slot, b,
                                                  len(req.prompt)))
        self._apply_copies(copies)
        return begins

    def _prime_tokenwise(self, admitted) -> List[int]:
        """Legacy priming: teacher-force each prompt through the decode
        step, one token (= one whole-model dispatch) at a time, one
        request at a time.  Returns each request's first new token.
        Paged slots skip their shared-prefix rows — the history is
        already mapped, so teacher-forcing resumes mid-prompt."""
        tr = self.tracer
        paged = self.alloc is not None
        begins = self._prime_begins(admitted) if paged \
            else np.zeros(self.slots, np.int32)
        table = (jnp.asarray(self.alloc.table()) if paged else None)
        firsts = []
        for slot, req in admitted:
            logits = None
            toks = self.tokens.copy()
            t0 = time.monotonic_ns() if tr is not None else 0
            b0 = int(begins[slot])
            for t in range(b0, len(req.prompt)):
                toks[slot, 0] = int(req.prompt[t])
                pos = self.pos.copy()
                pos[slot] = t
                if paged:
                    logits, self.cache_state = self._decode(
                        self.params, self.cache_state, jnp.asarray(toks),
                        jnp.asarray(pos), jnp.asarray(self._mask(slot)),
                        table)
                else:
                    logits, self.cache_state = self._decode(
                        self.params, self.cache_state, jnp.asarray(toks),
                        jnp.asarray(pos), jnp.asarray(self._mask(slot)))
                self.prefill_dispatches += 1
            self.metrics.counter("prefill/dispatches").inc(
                len(req.prompt) - b0)
            if tr is not None:
                tr.add_span("prefill", t0, time.monotonic_ns(),
                            lane="sched", kind="tokenwise", rid=req.rid,
                            tokens=len(req.prompt) - b0)
            # final prime logits predict the first new token
            firsts.append(int(jnp.argmax(logits[slot])))
        return firsts

    def _prime_chunked(self, admitted) -> List[int]:
        """Chunked batched prefill: every admitted request's prompt runs
        through ``model.prefill_into_slots`` together, ``prefill_chunk``
        positions per dispatch (tail chunks bucketed to powers of two).
        K/V rows land directly in the slot-batched cache; the chunk
        covering each prompt's last token yields its first new token.

        Paged + prefix sharing uses a FIXED chunk grid (full-size
        chunks at aligned starts, no tail bucketing): a K/V row's bits
        then depend only on the token prefix, never on this batch's
        chunk layout, so rows written by one request can be mapped by
        another bit-for-bit.  Chunks fully below every slot's match
        point are skipped outright."""
        tr = self.tracer
        paged = self.alloc is not None
        begins = self._prime_begins(admitted) if paged \
            else np.zeros(self.slots, np.int32)
        table = (jnp.asarray(self.alloc.table()) if paged else None)
        begin_j = jnp.asarray(begins) if paged else None
        fixed_grid = paged and self.alloc.share_prefix
        lengths = np.zeros(self.slots, np.int32)
        for slot, req in admitted:
            lengths[slot] = len(req.prompt)
        longest = int(lengths.max())
        firsts: Dict[int, int] = {}
        start = 0
        if fixed_grid:
            start = (int(min(begins[s] for s, _ in admitted))
                     // self.prefill_chunk) * self.prefill_chunk
        while start < longest:
            k = (self.prefill_chunk if fixed_grid else
                 _chunk_bucket(min(self.prefill_chunk, longest - start),
                               self.prefill_chunk))
            toks = np.zeros((self.slots, k), np.int32)
            for slot, req in admitted:
                hi = min(len(req.prompt), start + k)
                if hi > start:
                    toks[slot, :hi - start] = np.asarray(
                        req.prompt[start:hi], np.int32)
            pf = (_paged_prefill_fn(self.cfg, k, start) if paged
                  else _prefill_fn(self.cfg, k, start))
            before = _jit_cache_size(pf)
            t0 = time.monotonic_ns() if tr is not None else 0
            if paged:
                logits, self.cache_state = pf(
                    self.params, self.cache_state, jnp.asarray(toks),
                    jnp.asarray(lengths), table, begin_j)
            else:
                logits, self.cache_state = pf(
                    self.params, self.cache_state, jnp.asarray(toks),
                    jnp.asarray(lengths))
            if tr is not None:
                t1 = time.monotonic_ns()
                compiled = _jit_cache_size(pf) > before
                tr.add_span("prefill", t0, t1, lane="sched", kind="chunk",
                            start=start, chunk=k, compiled=compiled)
                if compiled:
                    tr.instant("jit_compile", lane="sched", fn="prefill",
                               chunk=k, chunk_start=start)
            self.metrics.counter("prefill/dispatches").inc()
            self.prefill_dispatches += 1
            lg = None
            for slot, req in admitted:
                if start < len(req.prompt) <= start + k:
                    if lg is None:
                        lg = np.asarray(logits)
                    firsts[slot] = int(np.argmax(lg[slot]))
            start += k
        return [firsts[slot] for slot, _ in admitted]

    def _finish(self, req: Request):
        """Bookkeeping for a completed request (trace span + metrics)."""
        self.metrics.counter("sched/finished").inc()
        if req.submit_ns >= 0:
            now = time.monotonic_ns()
            self.metrics.histogram("sched/request_ms").observe(
                (now - req.submit_ns) / 1e6)
            if self.tracer is not None:
                self.tracer.add_span(
                    "request", req.submit_ns, now,
                    lane=_lane(req.adapter_id), rid=req.rid,
                    adapter=str(req.adapter_id), tokens=len(req.out))

    def step(self) -> int:
        """One decode micro-step for the scheduled adapter group;
        returns #finished requests."""
        group = self._schedule()
        self._ensure_adapter(group)
        self._admit(group)
        self.metrics.gauge("sched/queue_depth").set(len(self.queue))
        mask = self._mask(group=group)
        if not mask.any():
            self._turn_left = 0  # group drained during admission: rotate
            return 0
        if self.speculate:
            n = self._spec_round_len(group, mask)
            if n >= 1:
                return self._spec_step(group, mask, n)
        # compile detection: the shared jitted fn's cache growing across
        # this call means THIS step paid a fresh compile — exclude it
        # from the ms_per_step EMA (a compile-laden sample would poison
        # the SLO clock for ~5 samples) and record it as an event
        if self.alloc is not None:
            # make this step's write rows writable BEFORE dispatch:
            # allocates a fresh page at page boundaries, COW-splits a
            # shared one at the first diverging write.  Reservations
            # guarantee the allocs succeed (see paged_kv.py).
            copies = []
            for slot in range(self.slots):
                if mask[slot]:
                    p = int(self.pos[slot])
                    copies.extend(self.alloc.ensure_range(slot, p, p + 1))
            self._apply_copies(copies)
        before = _jit_cache_size(self._decode)
        t0_ns = time.monotonic_ns()
        if self.alloc is not None:
            logits, self.cache_state = self._decode(
                self.params, self.cache_state, jnp.asarray(self.tokens),
                jnp.asarray(self.pos), jnp.asarray(mask),
                jnp.asarray(self.alloc.table()))
        else:
            logits, self.cache_state = self._decode(
                self.params, self.cache_state, jnp.asarray(self.tokens),
                jnp.asarray(self.pos), jnp.asarray(mask))
        nxt = np.asarray(jnp.argmax(logits, -1))  # host sync point
        t1_ns = time.monotonic_ns()
        after = _jit_cache_size(self._decode)
        compiled = after > before
        dt = (t1_ns - t0_ns) / 1e6
        if compiled:
            self.metrics.counter("sched/compiles").inc()
        if self.tracer is not None:
            self.tracer.add_span("decode_step", t0_ns, t1_ns,
                                 lane=_lane(group), step=self.steps,
                                 batch=int(mask.sum()), compiled=compiled)
            if compiled:
                self.tracer.instant("jit_compile", lane="sched",
                                    fn="decode", step=self.steps)
        if not compiled:
            self.metrics.histogram("decode/step_ms").observe(dt)
        if self._ms_auto and not compiled:
            # EMA over compile-free samples only; first one seeds it
            self._ms_samples += 1
            if self._ms_samples == 1:
                self.ms_per_step = dt
            else:
                self.ms_per_step = 0.2 * dt + 0.8 * self.ms_per_step
        finished = 0
        self.steps += 1
        self.metrics.counter("decode/steps").inc()
        self.metrics.counter("decode/tokens").inc(int(mask.sum()))
        self._turn_left -= 1
        self._last_served[group] = self.steps
        for slot, req in enumerate(self.active):
            if req is None or not mask[slot]:
                continue
            self._emit(req, slot, int(nxt[slot]))
            self.pos[slot] += 1
            if (len(req.out) >= req.max_new_tokens
                    or self.pos[slot] >= self.max_seq - 1):
                self._retire(req, slot)
                finished += 1
        if not self._group_has_work(group):
            self._turn_left = 0
        return finished

    def _spec_round_len(self, group, mask) -> int:
        """Draft length for this round: the group's adaptive length,
        clamped so no active slot writes past its budget — rows up to
        ``pos + n`` are written by the verify chunk, and paged slots
        reserved exactly ``prompt + max_new_tokens`` rows, so ``n`` may
        not exceed any slot's remaining tokens (nor its max_seq
        headroom)."""
        n = self._spec_len.get(group, self.speculate)
        for slot in range(self.slots):
            if not mask[slot]:
                continue
            req = self.active[slot]
            n = min(n, req.max_new_tokens - len(req.out),
                    self.max_seq - 1 - int(self.pos[slot]))
        return max(0, n)

    def _flip_to_base(self):
        """Drop to the base model for drafting: re-apply the displaced
        base rows (a pure device scatter-swap — no registry or cache
        traffic, ``_applied`` unchanged).  Returns the adapter's rows
        for ``_flip_back``; None when the base group is already live
        (drafter == verifier: every draft is accepted by parity)."""
        if self._displaced is None:
            return None
        from repro.adapters import flip_delta
        disp, self._displaced = self._displaced, None
        self.params, adapter_rows = flip_delta(self.params, disp,
                                               mode=self.swap_mode)
        return adapter_rows

    def _flip_back(self, adapter_rows):
        if adapter_rows is None:
            return
        from repro.adapters import flip_delta
        self.params, self._displaced = flip_delta(self.params, adapter_rows,
                                                  mode=self.swap_mode)
        self.metrics.counter("spec/flips").inc(2)

    def _spec_step(self, group, mask, n: int) -> int:
        """One speculative scheduler step: the base model drafts ``n``
        tokens per active slot through the plain decode path, the
        adapter model scores all n+1 positions in one verify dispatch
        (overwriting the draft K/V rows with adapter-correct values),
        and the longest greedy-agreeing prefix is accepted.  Emits
        between 1 and n+1 tokens per slot; returns #finished."""
        tr = self.tracer
        m = self.metrics
        paged = self.alloc is not None
        pos0 = self.pos.copy()
        slots_idx = [s for s in range(self.slots) if mask[s]]
        if paged:
            # every row this round touches — n draft writes + the verify
            # chunk's n+1 rows — made writable up front; reservations
            # guarantee the allocs succeed (n is clamped to each slot's
            # remaining-token budget)
            copies = []
            for s in slots_idx:
                p = int(pos0[s])
                copies.extend(self.alloc.ensure_range(s, p, p + n + 1))
            self._apply_copies(copies)
            table = jnp.asarray(self.alloc.table())
        mask_j = jnp.asarray(mask)
        before = _jit_cache_size(self._decode)
        vbefore = _jit_cache_size(self._verify)
        t0_ns = time.monotonic_ns()
        # ---- draft: n plain decode steps under the base model --------- #
        saved = self._flip_to_base()
        toks = self.tokens.copy()
        dpos = pos0.copy()
        drafts = np.zeros((n, self.slots), np.int64)
        for i in range(n):
            d0 = time.monotonic_ns()
            if paged:
                logits, self.cache_state = self._decode(
                    self.params, self.cache_state, jnp.asarray(toks),
                    jnp.asarray(dpos), mask_j, table)
            else:
                logits, self.cache_state = self._decode(
                    self.params, self.cache_state, jnp.asarray(toks),
                    jnp.asarray(dpos), mask_j)
            drafts[i] = np.asarray(jnp.argmax(logits, -1))
            d1 = time.monotonic_ns()
            if tr is not None:
                tr.add_span("decode_step", d0, d1, lane=_lane(group),
                            step=self.steps, batch=int(mask.sum()),
                            draft=True)
            for s in slots_idx:
                toks[s, 0] = drafts[i, s]
            dpos[mask] += 1
        self._flip_back(saved)
        t1_ns = time.monotonic_ns()
        if tr is not None:
            tr.add_span("spec_draft", t0_ns, t1_ns, lane=_lane(group),
                        step=self.steps, n=n, batch=int(mask.sum()))
        # ---- verify: one chunked dispatch under the adapter ----------- #
        vt = np.zeros((self.slots, n + 1), np.int32)
        for s in slots_idx:
            vt[s, 0] = self.tokens[s, 0]   # last emitted token
            vt[s, 1:] = drafts[:, s]
        if paged:
            vlogits, self.cache_state = self._verify(
                self.params, self.cache_state, jnp.asarray(vt),
                jnp.asarray(pos0), mask_j, table)
        else:
            vlogits, self.cache_state = self._verify(
                self.params, self.cache_state, jnp.asarray(vt),
                jnp.asarray(pos0), mask_j)
        greedy = np.asarray(jnp.argmax(vlogits, -1))   # [slots, n+1]
        t2_ns = time.monotonic_ns()
        if tr is not None:
            tr.add_span("spec_verify", t1_ns, t2_ns, lane=_lane(group),
                        step=self.steps, n=n + 1, batch=int(mask.sum()))
        after = _jit_cache_size(self._decode)
        vafter = _jit_cache_size(self._verify)
        compiled = after > before or vafter > vbefore
        if compiled:
            m.counter("sched/compiles").inc()
            if tr is not None:
                tr.instant("jit_compile", lane="sched", fn="spec",
                           step=self.steps)
        dt = (t2_ns - t0_ns) / 1e6
        if not compiled:
            m.histogram("decode/step_ms").observe(dt)
        if self._ms_auto and not compiled:
            self._ms_samples += 1
            self.ms_per_step = (dt if self._ms_samples == 1
                                else 0.2 * dt + 0.8 * self.ms_per_step)
        # ---- accept / emit / roll back -------------------------------- #
        finished = 0
        emitted_total = 0
        accepted_total = 0
        rollbacks = 0
        self.steps += 1
        m.counter("decode/steps").inc()
        self._turn_left -= 1
        self._last_served[group] = self.steps
        for s in slots_idx:
            req = self.active[s]
            a, emit = spec_accept(drafts[:, s], greedy[s])
            accepted_total += a
            if a < n:
                rollbacks += 1
            for t in emit:
                self._emit(req, s, t)
                self.pos[s] += 1
                emitted_total += 1
                if (len(req.out) >= req.max_new_tokens
                        or self.pos[s] >= self.max_seq - 1):
                    break
            if (len(req.out) >= req.max_new_tokens
                    or self.pos[s] >= self.max_seq - 1):
                self._retire(req, s)
                finished += 1
            elif paged:
                # return pages the rejected suffix no longer needs
                self.alloc.rollback_to(s, int(self.pos[s]))
        self.spec_rounds += 1
        self.spec_drafted += n * len(slots_idx)
        self.spec_accepted += accepted_total
        self.spec_emitted += emitted_total
        m.counter("spec/rounds").inc()
        m.counter("spec/drafted").inc(n * len(slots_idx))
        m.counter("spec/accepted").inc(accepted_total)
        m.counter("spec/rollbacks").inc(rollbacks)
        m.counter("decode/tokens").inc(emitted_total)
        # ---- adaptive draft length ------------------------------------ #
        rate = accepted_total / (n * len(slots_idx))
        prev = self._spec_ema.get(group)
        ema = rate if prev is None else 0.5 * rate + 0.5 * prev
        self._spec_ema[group] = ema
        if self.spec_adaptive:
            cur = self._spec_len.get(group, self.speculate)
            if ema < 0.4 and cur > 1:
                cur = max(1, cur // 2)
            elif ema > 0.8 and cur < self.speculate:
                cur += 1
            self._spec_len[group] = cur
            m.gauge("spec/draft_len").set(cur)
        if not self._group_has_work(group):
            self._turn_left = 0
        return finished

    def _progress_key(self):
        return (self.steps, len(self.queue),
                sum(r is not None for r in self.active),
                sum(len(r.out) for r in self.active if r is not None))

    def run_until_drained(self, max_steps=10_000,
                          on_step=None) -> List[Request]:
        """Step until queue and slots are empty.  A wedged queue — a
        step that changes NOTHING (no decode, no admission, no
        completion) would repeat identically forever — raises instead of
        silently burning ``max_steps`` and returning undone requests;
        so does running out of ``max_steps`` with work left.
        ``on_step(server)`` (if given) runs after every scheduler step —
        the launchers hook periodic metrics dumps here."""
        all_reqs = list(self.queue)
        for _ in range(max_steps):
            before = self._progress_key()
            self.step()
            if on_step is not None:
                on_step(self)
            if not self.queue and all(r is None for r in self.active):
                return all_reqs
            if self._progress_key() == before:
                raise RuntimeError(
                    f"DecodeServer wedged at step {self.steps}: "
                    f"{len(self.queue)} queued / "
                    f"{sum(r is not None for r in self.active)} active "
                    f"requests but a scheduler step made no progress")
        if not self.queue and all(r is None for r in self.active):
            return all_reqs
        undone = [r.rid for r in all_reqs if not r.done]
        raise RuntimeError(
            f"run_until_drained: {len(undone)} request(s) undone after "
            f"max_steps={max_steps} (rids {undone[:8]}...)")

    def stats(self) -> Dict[str, object]:
        """Nested ``prefill`` / ``decode`` / ``cache`` / ``sched`` (and
        ``kv`` / ``spec`` when enabled) sections sourced from the
        metrics registry.  The schema is stamped with ``stats_version``
        (v2: the pre-TraceKit flat key aliases from PR 6 are gone —
        read ``s["sched"]["swaps"]``, not ``s["swaps"]``)."""
        swap_rate = self.swaps / self.steps if self.steps else 0.0
        self.metrics.gauge("decode/ms_per_step").set(self.ms_per_step)
        self.metrics.gauge("sched/swap_rate").set(swap_rate)
        if self.speculate:
            self.metrics.gauge("spec/acceptance_rate").set(
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)
        nested = self.metrics.nested()
        sched = dict(nested.get("sched", {}))
        sched["applied"] = self._applied
        out: Dict[str, object] = {
            "stats_version": STATS_VERSION,
            "decode": dict(nested.get("decode", {})),
            "prefill": dict(nested.get("prefill", {})),
            "sched": sched,
        }
        if self.speculate:
            spec = dict(nested.get("spec", {}))
            spec["tokens_per_step"] = (
                self.spec_emitted / spec["rounds"] if spec.get("rounds")
                else 0.0)
            out["spec"] = spec
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        if self.alloc is not None:
            kv = dict(nested.get("kv", {}))
            kv["page_size"] = self.alloc.page_size
            kv["num_pages"] = self.alloc.num_pages
            out["kv"] = kv
        return out
