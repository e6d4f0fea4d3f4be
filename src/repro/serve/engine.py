"""Continuous-batching serving engine with block decode dispatch.

Replaces the lock-step serve loop: a request queue feeds a fixed pool
of decode *slots*.  Each engine step (1) admits queued requests into
free slots — one fused ``Model.prefill`` call per request populates
that slot's stripe of the shared KV/state cache — and (2) runs ONE
jitted *block* of ``steps_per_dispatch`` decode+sample iterations over
all slots, so sequences of different lengths and arrival times decode
together and a finished request's slot is refilled on the next step
instead of stalling the batch until its slowest member drains.

Why blocks: decode is bandwidth-bound, so per-iteration host control
(readback, argmax, re-dispatch) is a first-order cost — the software
analogue of the per-iteration loop overhead the paper's zero-overhead
loop nests eliminate.  The block path hoists that control out of the
hot loop: sampling runs on device (:mod:`repro.serve.sampling`), K
decode+sample iterations run inside a single ``lax.scan`` dispatch,
and the host syncs ONCE per block to read the ``(num_slots, K)``
token tile.  Per-slot done masks (eos hit or ``max_new_tokens``
reached) freeze finished rows inside the block — the frozen row
re-emits its last token, stops advancing its PRNG key, and the host
discards everything past the done point — so emitted tokens are
identical for every ``steps_per_dispatch``.

Why this is family-agnostic: every family's cache is a pytree whose
leaves carry the batch dimension *somewhere* (axis 1 for stacked-layer
KV, axis 2 for the hybrid's grouped SSM states, axis 0 for ``pos``).
The engine probes ``init_cache`` at two batch sizes once and records
each leaf's batch axis, so slot insertion is a per-leaf
``dynamic_update_slice_in_dim`` with no per-family code.  Per-slot
decode depth rides the (B,) ``pos`` vector that ``Model.prefill``
returns (rope offsets, causal masks and cache scatters are all
per-row — see ``layers._scatter_at``).

Determinism contract: greedy decode (``temperature=0``) through the
engine is token-for-token identical to :func:`lockstep_generate` for
the row-independent families (dense/vlm, ssm, hybrid, encdec) at
every ``steps_per_dispatch`` — padding is masked to exact zeros, so
bucket size and batch composition cannot leak into a request's
logits.  Stochastic decode is deterministic per request (seeded by
``Request.seed``, defaulting to a fold-in of the engine seed and the
rid) and independent of batch composition and block size: a request's
sample chain advances exactly once per emitted token.  MoE routing is
batch-global (capacity competition), so MoE serves correctly but is
not bit-matched to a differently-composed batch.

Paged mode (``page_size=...``) replaces the contiguous per-slot cache
stripes with a device-resident page pool plus per-slot page tables
(:mod:`repro.serve.paging`): admission allocates just the pages a
request needs, identical prompt prefixes share pages copy-on-write via
a refcounted prefix cache, and decode attention walks the table either
through a jnp gather or the dedicated Pallas kernel
(:func:`repro.kernels.paged_attention.paged_attention`).  The
determinism contract carries over unchanged — on the jnp backend the
paged gather reproduces the contiguous math bit-for-bit, so paged
serving matches :func:`lockstep_generate` token-for-token too.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve import sampling
from repro.serve.paging import (TRASH_PAGE, OutOfPages, PageAllocator,
                                PageGeometry, PrefixCache)
from repro.serve.request import GenerationResult, Request, SlotState
from repro.serve.stats import EngineStats

__all__ = ["ServeEngine", "lockstep_generate", "paged_view"]

# families whose prefill K/V at position i depends only on tokens <= i
# AND is batch-composition independent — the prefix-sharing soundness
# bar.  MoE is out (routing competes batch-globally), encdec is out
# (every position also depends on the source frames).
_SHARE_FAMILIES = ("dense", "vlm", "hybrid")


def _host(x) -> np.ndarray:
    """THE device->host boundary.  Every readback the engine performs
    funnels through here, so tests can monkeypatch it and count the
    syncs per dispatch (the quantity block dispatch exists to cut)."""
    return np.asarray(x)


# THE engine clock.  Every latency the engine records (TTFT, queue
# wait, per-token latency, prefill/decode budgets) reads this one
# module-level callable, so tests can monkeypatch ``engine._now`` with
# a fake clock and get bit-deterministic latency metrics.
_now = time.perf_counter


def _vector_pos(cache: dict, batch: int) -> dict:
    """Promote the scalar lock-step ``pos`` to the per-slot (B,) form."""
    c = dict(cache)
    c["pos"] = jnp.zeros((batch,), jnp.int32) + jnp.asarray(c["pos"],
                                                            jnp.int32)
    return c


def _batch_axes(c1: Any, c2: Any) -> Any:
    """Tree of per-leaf batch-axis indices, probed from two batch sizes."""
    def axis_of(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"cannot locate batch axis: shapes {a.shape} vs {b.shape}")
        return diffs[0]
    return jax.tree.map(axis_of, c1, c2)


class ServeEngine:
    """Continuous-batching engine over a ``Model`` bundle.

    Parameters
    ----------
    model, params, ctx : the ``build_model`` bundle, its params, and the
        execution context (``ctx.plan`` selects the backend and the
        kernel configs exactly as everywhere else).  Quantized params
        (``model.quantize_weights(params)`` + a ``quant="int8"`` plan)
        serve unchanged: the engine only ever slices/updates the
        *cache*, never the params, so QTensor weights flow straight
        through to the int8 kernels.
    num_slots : decode batch width (the compiled decode shape).
    max_len : per-slot cache capacity; every request must satisfy
        ``len(prompt) [+ frontend] + max_new_tokens <= max_len``.
    steps_per_dispatch : decode iterations fused into one jitted
        dispatch (K).  The host syncs once per dispatch instead of
        once per token; emitted tokens are identical for every K (the
        in-block done mask freezes retired rows).  A slot freed
        mid-block is refilled at the next block boundary, so very
        large K trades a little occupancy for K-fold lower dispatch
        overhead.
    bucket_sizes : prompt pad lengths (one prefill compilation each);
        defaults to powers of two from 8 up to ``max_len``.
    eos_id : optional early-stop token id.
    seed : engine-level sampling seed; a request without an explicit
        ``Request.seed`` samples from ``fold_in(PRNGKey(seed), rid)``.
    cache_kwargs : forwarded to ``model.init_cache`` (e.g. ``enc_len``
        for the encdec family, which must be shared by all requests).
    plan : optional :class:`repro.plan.Plan` the engine executes under
        (replaces ``ctx``'s plan), or the string ``"trace"`` to resolve
        one ahead of time via :func:`repro.plan.trace_model` over this
        engine's exact prefill buckets and decode shape — the serving
        analogue of the paper's ahead-of-the-loop CSR writes: with a
        traced (or otherwise complete) plan, admission and the decode
        loop never touch the tuner.  The active plan is ``self.plan``
        (``Plan.save`` makes it a shippable artifact).
    validate : run :func:`repro.analyze.lint_plan` over the active
        plan at load time — error-level diagnostics (slot-reuse
        hazards, int8-in-int8 accumulation, over-budget tiles) raise
        ``ValueError`` before any request is admitted; warnings are
        reported as a ``RuntimeWarning``.  With ``page_size`` set, the
        page geometry is linted too (:func:`repro.analyze.
        lint_page_geometry`, rules ZS-L008/ZS-S008).
    page_size : tokens per KV page.  ``None`` (default) keeps the
        contiguous per-slot cache bit-for-bit; an int switches the
        sequence-extent cache leaves to a device-resident page pool
        with per-slot page tables, refcounted prefix sharing and
        copy-on-write semantics (see :mod:`repro.serve.paging`).  Must
        divide ``max_len``.
    num_pages : physical pool size including the reserved trash page 0;
        defaults to ``num_slots * (max_len // page_size) + 1`` (zero
        memory saving, full correctness).  Smaller pools oversubscribe:
        admission falls back to LRU prefix-cache eviction, then to
        requeueing the request until a decode retires.
    prefill_chunk : when set, prompts longer than this are ingested
        ``prefill_chunk`` tokens per engine step (one chunk between
        decode dispatches) instead of one monolithic prefill, bounding
        the head-of-line TTFT penalty a long prompt imposes on queued
        short requests.  Requires ``Model.prefill_chunk`` (dense/vlm).
    device : optional ``jax.Device`` this engine lives on.  Params
        (copied there unless already resident), cache and sampling
        keys are placed on it, and every jitted dispatch follows its
        committed operands — so N replicas built with N devices serve
        from N chips.  ``None`` keeps JAX's default placement.
    """

    def __init__(self, model, params, ctx, *, num_slots: int = 4,
                 max_len: int = 128, cache_dtype=jnp.float32,
                 steps_per_dispatch: int = 1,
                 bucket_sizes: Sequence[int] | None = None,
                 eos_id: int | None = None, seed: int = 0,
                 cache_kwargs: dict | None = None,
                 plan=None, validate: bool = False,
                 page_size: int | None = None,
                 num_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 device: jax.Device | None = None):
        self.model = model
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.device = device
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.steps_per_dispatch = int(steps_per_dispatch)
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        self.eos_id = eos_id
        self.seed = int(seed)
        self.page_size = None if page_size is None else int(page_size)
        self._chunk = None if prefill_chunk is None else int(prefill_chunk)
        if self._chunk is not None and self._chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        kw = dict(cache_kwargs or {})

        if bucket_sizes is None:
            bucket_sizes, b = [], 8
            while b < max_len:
                bucket_sizes.append(b)
                b *= 2
            bucket_sizes.append(max_len)
        self.bucket_sizes = tuple(sorted(set(int(b) for b in bucket_sizes)))

        if plan is not None:
            if isinstance(plan, str) and plan == "trace":
                plan = self._trace_plan(model, ctx, kw, cache_dtype)
            ctx = ctx.with_plan(plan)
        self.ctx = ctx
        self.plan = ctx.plan
        if validate:
            self._validate_plan()

        # probe each cache leaf's batch axis once (family-agnostic
        # slots); eval_shape gets the shapes without allocating two
        # throwaway cache-sized pytrees
        def probe(b):
            return _vector_pos(
                model.init_cache(b, max_len, cache_dtype, **kw), b)
        c1 = jax.eval_shape(lambda: probe(1))
        c2 = jax.eval_shape(lambda: probe(2))
        self._axes = _batch_axes(c1, c2)

        self._geom: PageGeometry | None = None
        self._pages_active = False
        if self.page_size is not None:
            self._init_paging(model, kw, cache_dtype, num_pages, probe)
        else:
            self.cache = _vector_pos(
                model.init_cache(self.num_slots, max_len, cache_dtype, **kw),
                self.num_slots)
        if validate and self._geom is not None:
            self._validate_pages()

        # chunked prefill: long prompts admitted one fixed-size chunk
        # per engine step instead of one monolithic prefill, so queued
        # short requests and active decodes are never head-of-line
        # blocked behind a long prompt
        self._chunking: dict[int, dict] = {}
        if self._chunk is not None:
            if model.prefill_chunk is None:
                raise ValueError(
                    f"family {model.cfg.family!r} does not support chunked "
                    "prefill (Model.prefill_chunk is None: its prompt state "
                    "is not chunk-invariant)")
            self._prefill_chunk_fn: Callable = jax.jit(
                lambda p, toks, cache, off, lens: model.prefill_chunk(
                    p, toks, ctx, cache=cache, offset=off, lengths=lens),
                donate_argnums=(2,))
            self._chunk_cache_init = lambda: _vector_pos(
                model.init_cache(1, max_len, cache_dtype, **kw), 1)

        # two static block specializations: an all-greedy slot pool
        # (the default, and the determinism-contract path) never pays
        # for the stochastic sampler's sorts/PRNG draws — the host
        # knows every active row's temperature, so it picks per
        # dispatch; at most both compile once
        self._decode_block: Callable = jax.jit(
            self._build_block(model, ctx, self.steps_per_dispatch,
                              greedy_only=False),
            donate_argnums=(1,))
        self._decode_block_greedy: Callable = jax.jit(
            self._build_block(model, ctx, self.steps_per_dispatch,
                              greedy_only=True),
            donate_argnums=(1,))
        self._prefill: Callable = jax.jit(
            lambda p, batch: model.prefill(p, batch, ctx, max_len))
        self._sample1: Callable = jax.jit(sampling.sample)

        # cache-adjacent sampling state: per-slot PRNG keys live on
        # device next to the cache (overwritten at admission, carried
        # through the jitted block); the tiny per-slot knob vectors are
        # host mirrors shipped with each dispatch (traced operands, so
        # heterogeneous requests share one compiled program)
        self._keys = sampling.make_keys(self.num_slots)
        if device is not None:
            self.cache, self._keys = jax.device_put(
                (self.cache, self._keys), device)
        self._temp = np.zeros((self.num_slots,), np.float32)
        self._topk = np.zeros((self.num_slots,), np.int32)
        self._topp = np.ones((self.num_slots,), np.float32)

        self._pending: collections.deque[Request] = collections.deque()
        self._slots: list[SlotState | None] = [None] * self.num_slots
        self._results: dict[int, GenerationResult] = {}
        self._step = 0
        self.stats = EngineStats(num_slots=self.num_slots)
        self._submit_t: dict[int, float] = {}     # rid -> submit clock
        self._last_prefill_s = 0.0   # slowest single admission, last step
        self._last_dispatch_s = 0.0  # decode block wall-clock, last step

    # ------------------------------------------------------------------
    def _build_block(self, model, ctx, K: int, *, greedy_only: bool):
        """The fused decode block: K decode+sample iterations in one
        ``lax.scan`` under one jit.  Carries (cache, fed token, keys,
        done, budget); finished rows are frozen — they re-feed their
        last token, keep their key, and stop consuming budget — so the
        emitted ``(num_slots, K)`` tile is bit-identical to running K
        single-step dispatches.  Cache rows of frozen slots still see
        writes (masking them per-leaf would need per-family code), but
        a retired slot's stripe is fully overwritten at admission and
        ``_scatter_at``'s dynamic-update-slice clamps in-bounds, so the
        garbage is never observable.

        ``greedy_only=True`` compiles the pure-argmax variant (no
        sorts, no PRNG): keys pass through untouched, which is sound
        because greedy rows never consume their key and a stochastic
        row is never dispatched through this block."""
        eos_id = self.eos_id

        def block(params, cache, tok, keys, temp, topk, topp, done, budget):
            def one(carry, _):
                cache, tok, keys, done, budget = carry
                logits, cache = model.decode(params, cache, tok[:, None], ctx)
                if greedy_only:
                    nxt = sampling.greedy(logits[:, -1])
                else:
                    keys2, nxt = sampling.sample(logits[:, -1], keys,
                                                 temp, topk, topp)
                    keys = jnp.where(done[:, None], keys, keys2)
                nxt = jnp.where(done, tok, nxt)
                budget = budget - jnp.where(done, 0, 1)
                newly_done = budget <= 0
                if eos_id is not None:
                    newly_done = newly_done | (nxt == eos_id)
                done = done | newly_done
                return (cache, nxt, keys, done, budget), nxt

            carry = (cache, tok, keys, done, budget)
            (cache, tok, keys, done, budget), toks = jax.lax.scan(
                one, carry, None, length=K)
            return cache, toks.T, keys   # toks: (K, B) -> (B, K)

        return block

    # ------------------------------------------------------------------
    def _trace_plan(self, model, ctx, cache_kwargs: dict, cache_dtype):
        """Resolve every kernel config this engine will need, ahead of
        time: one abstract prefill per bucket size (batch 1, exactly
        the admission shape) plus one abstract decode at the slot
        width.  Costs shapes only (``jax.eval_shape``)."""
        from repro.plan import trace_model
        cfg = model.cfg
        n_front = 0
        if cfg.family == "encdec":
            front = ("frontend_embeds",
                     (1, int(cache_kwargs.get("enc_len", 8)), cfg.d_model))
        elif getattr(cfg, "frontend", None):
            front = ("frontend_embeds", (1, cfg.frontend_tokens, cfg.d_model))
            n_front = cfg.frontend_tokens
        else:
            front = None
        shapes, seen = [], set()
        for b in self.bucket_sizes:
            sb = min(b, self.max_len - n_front)
            if sb < 1 or sb in seen:
                continue
            seen.add(sb)
            bs = {"tokens": ((1, sb), jnp.int32),
                  "lengths": ((1,), jnp.int32)}
            if front is not None:
                bs[front[0]] = (front[1], jnp.float32)
            shapes.append(bs)
        # trace with the engine's REAL params: param dtypes feed type
        # promotion, so a float32-init trace of a bf16 model would
        # memoize wrong-dtype OpKeys and the serving loop would still
        # hit the tuner on the mismatched buckets
        return trace_model(model, shapes, ctx, max_len=self.max_len,
                           modes=("prefill", "decode"),
                           decode_batch=self.num_slots,
                           cache_dtype=cache_dtype,
                           cache_kwargs=cache_kwargs,
                           params=self.params)

    # ------------------------------------------------------------------
    def _validate_plan(self) -> None:
        """Load-time plan verification (``validate=True``): run the
        static analyzer (:func:`repro.analyze.lint_plan`) over the
        active plan — a shipped plan with a slot-reuse hazard, an
        int8-in-int8 entry or an over-budget tile is rejected before
        the first request is admitted; warnings are surfaced but do
        not block."""
        from repro.analyze import lint_plan
        from repro.plan import Plan
        if not isinstance(self.plan, Plan):
            return
        report = lint_plan(self.plan)
        if report.errors:
            raise ValueError(
                "ServeEngine(validate=True): the plan failed static "
                "analysis:\n" + "\n".join(d.format() for d in report.errors))
        if report.warnings:
            import warnings as _warnings
            _warnings.warn(
                "ServeEngine: plan analysis warnings:\n"
                + "\n".join(d.format() for d in report.warnings),
                RuntimeWarning, stacklevel=3)

    # -- paged KV cache ------------------------------------------------
    def _init_paging(self, model, kw: dict, cache_dtype, num_pages,
                     probe) -> None:
        """Replace the contiguous per-slot cache with a page pool.

        Which leaves page is *probed*, not hard-coded: grow ``max_len``
        by one page and see which leaf shapes move — a leaf that grows
        by exactly ``page_size`` on the axis right of its batch axis is
        sequence-extent KV and becomes a ``(num_pages, page_size, ...)``
        pool; everything else (SSM/conv state, cross-attention K/V,
        ``pos``) keeps its per-slot form.  A family with no pageable
        leaves (pure SSM) degrades to the contiguous engine with zero
        page gauges.
        """
        ps = self.page_size
        if self.max_len % ps:
            raise ValueError(
                f"page_size {ps} must divide max_len {self.max_len}")
        if kw.get("quantize_kv"):
            raise ValueError("paged serving does not support quantize_kv "
                             "(int8 page pools are not implemented)")
        if model.cfg.family == "encdec" and "enc_len" not in kw:
            raise ValueError(
                "paged encdec serving requires an explicit enc_len in "
                "cache_kwargs: with enc_len defaulting to max_len the "
                "fixed cross-attention extent would probe as a pageable "
                "sequence axis")
        T = self.max_len // ps
        if num_pages is None:
            num_pages = self.num_slots * T + 1   # +1: reserved trash page

        def probe_len(ml):
            return _vector_pos(
                model.init_cache(1, ml, cache_dtype, **kw), 1)
        cA = jax.eval_shape(lambda: probe_len(self.max_len))
        cB = jax.eval_shape(lambda: probe_len(self.max_len + ps))

        def page_axis(a, b, bax):
            diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y]
            if not diffs:
                return -1
            if diffs != [bax + 1] or b.shape[bax + 1] - a.shape[bax + 1] != ps:
                raise ValueError(
                    f"cannot page cache leaf: shapes {a.shape} vs {b.shape} "
                    f"(expected growth of {ps} on axis {bax + 1})")
            return bax + 1
        self._paged = jax.tree.map(page_axis, cA, cB, self._axes)
        self._pages_active = any(
            p >= 0 for p in jax.tree.leaves(self._paged))
        self._geom = PageGeometry(ps, int(num_pages), T)
        self._alloc = PageAllocator(self._geom)
        self._prefix = PrefixCache(self._alloc)
        self._slot_pages: list[list[int]] = [[] for _ in range(self.num_slots)]

        cS = jax.eval_shape(lambda: probe(self.num_slots))

        def build(leaf, bax, pax):
            if pax < 0:
                return jnp.zeros(leaf.shape, leaf.dtype)
            shape = list(leaf.shape)
            shape[bax] = self._geom.num_pages
            shape[pax] = ps
            return jnp.zeros(shape, leaf.dtype)
        cache = jax.tree.map(build, cS, self._axes, self._paged)
        if self._pages_active:
            # all-zeros table: every slot starts parked on the trash page
            cache["page_table"] = jnp.zeros((self.num_slots, T), jnp.int32)
            self._insert_paged: Callable = jax.jit(
                self._build_paged_insert(), donate_argnums=(0,))
        self.cache = cache

    def _build_paged_insert(self):
        """One jitted slot insertion for the paged cache: paged leaves
        of the contiguous prefill stripe are split into pages and
        scattered into the pool at ``write_ids`` (physical page per
        logical page; ``TRASH_PAGE`` for shared prefix hits — their
        pages already hold the values and must not be rewritten — and
        for unallocated tail positions), the slot's device table row
        becomes ``table_ids``, and non-paged leaves take the usual
        per-leaf dynamic-update-slice."""
        axes, paged = self._axes, self._paged
        T, ps = self._geom.table_len, self._geom.page_size

        def insert(cache, cache1, slot, write_ids, table_ids):
            def ins(dst, src, bax, pax):
                if pax < 0:
                    return jax.lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), slot, axis=bax)
                s = jnp.squeeze(src, axis=bax)
                s = s.reshape(s.shape[:bax] + (T, ps) + s.shape[bax + 1:])
                s = jnp.moveaxis(s, bax, 0)       # (T, ..., ps, ...)
                d = jnp.moveaxis(dst, bax, 0)     # (P, ..., ps, ...)
                d = d.at[write_ids].set(s.astype(d.dtype))
                return jnp.moveaxis(d, 0, bax)

            body = {k: v for k, v in cache.items() if k != "page_table"}
            out = jax.tree.map(ins, body, cache1, axes, paged)
            out["page_table"] = jax.lax.dynamic_update_slice_in_dim(
                cache["page_table"], table_ids[None], slot, axis=0)
            return out
        return insert

    def _validate_pages(self) -> None:
        """Load-time page-geometry verification (``validate=True``):
        :func:`repro.analyze.lint_page_geometry` rejects a page size
        that does not tile the plan's attention KV blocks (ZS-L008) or
        a table too short for ``max_len`` (ZS-S008)."""
        from repro.analyze import lint_page_geometry
        from repro.plan import Plan
        plan = self.plan if isinstance(self.plan, Plan) else None
        report = lint_page_geometry(self._geom.page_size,
                                    self._geom.table_len,
                                    max_len=self.max_len, plan=plan)
        if report.errors:
            raise ValueError(
                "ServeEngine(validate=True): page geometry failed static "
                "analysis:\n" + "\n".join(d.format() for d in report.errors))

    # ------------------------------------------------------------------
    def submit(self, request: Request) -> None:
        n_prompt = len(request.prompt)
        if request.frontend_embeds is not None \
                and self.model.cfg.family != "encdec":
            n_prompt += np.asarray(request.frontend_embeds).shape[0]
        budget = n_prompt + request.max_new_tokens
        if self._geom is not None:
            # checked before the budget: a prompt that cannot even be
            # *stored* gets the structural error, not the generic one
            cap = self._geom.table_len * self._geom.page_size
            if n_prompt > cap:
                raise ValueError(
                    f"request {request.rid}: prompt ({n_prompt} tokens) "
                    f"alone exceeds the page-table capacity {cap} "
                    f"({self._geom.table_len} pages x "
                    f"{self._geom.page_size} tokens/page)")
        if budget > self.max_len:
            raise ValueError(f"request {request.rid}: prompt + generation "
                             f"({budget}) exceeds max_len {self.max_len}")
        # a rid is live from submission to result pickup: results,
        # occupied slots AND the pending queue (a pending duplicate used
        # to be accepted and its result silently overwrote the first)
        if request.rid in self._results or any(
                s is not None and s.request.rid == request.rid
                for s in self._slots) or any(
                r.rid == request.rid for r in self._pending):
            raise ValueError(f"duplicate request id {request.rid}")
        self._submit_t[request.rid] = _now()
        self._pending.append(request)

    @property
    def idle(self) -> bool:
        return (not self._pending and not self._chunking
                and all(s is None for s in self._slots))

    # -- router-facing load/result hooks -------------------------------
    # (consumed by repro.serve.cluster; trivially true standalone too)
    @property
    def free_slots(self) -> int:
        """Slots neither occupied nor parked on a chunked admission."""
        return sum(1 for i, s in enumerate(self._slots)
                   if s is None and i not in self._chunking)

    @property
    def queue_depth(self) -> int:
        """Requests admitted but not yet placed into a slot."""
        return len(self._pending)

    @property
    def pages_in_use_now(self) -> int:
        """Current page-pool occupancy (0 for the contiguous cache) —
        an instantaneous gauge, unlike ``stats.pages_in_use`` (peak)."""
        return self._alloc.in_use if self._geom is not None else 0

    def pop_results(self) -> dict[int, GenerationResult]:
        """Hand over (and clear) finished results.  The router drains
        results after every replica step; a rid stays live against
        duplicate submission only until its result is popped."""
        out = self._results
        self._results = {}
        return out

    # ------------------------------------------------------------------
    def _bucket(self, n: int, limit: int) -> int:
        """Smallest bucket >= n, clamped to ``limit`` (submit() already
        guarantees n <= limit, so the clamp stays a valid pad length —
        frontend prefixes eat into the bucket budget, not the prompt)."""
        for b in self.bucket_sizes:
            if b >= n:
                return min(b, limit)
        raise ValueError(f"prompt length {n} exceeds the largest bucket "
                         f"{self.bucket_sizes[-1]}")

    def _request_key(self, req: Request) -> jax.Array:
        """(2,) uint32 sample-chain seed for one request."""
        if req.seed is not None:
            return sampling.request_key(req.seed)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), req.rid)
        return jax.random.key_data(key).astype(jnp.uint32)

    def _admit(self, req: Request, slot: int) -> int:
        """Fused prefill into ``slot``; returns the first sampled token.

        Raises :class:`OutOfPages` (paged mode, pool exhausted even
        after prefix-cache eviction) *before* any engine state mutates,
        so the caller can requeue the request cleanly."""
        n = len(req.prompt)
        n_front = 0
        if req.frontend_embeds is not None \
                and self.model.cfg.family != "encdec":
            n_front = np.asarray(req.frontend_embeds).shape[0]
        sb = self._bucket(n, self.max_len - n_front)
        toks = np.zeros((1, sb), np.int32)
        toks[0, :n] = req.prompt
        batch = {"tokens": jnp.asarray(toks),
                 "lengths": jnp.asarray([n], jnp.int32)}
        if req.frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(req.frontend_embeds)[None]
        logits, cache1 = self._prefill(self.params, batch)
        self._install(req, slot, cache1, n + n_front)
        return self._first_token(req, slot, logits)

    def _first_token(self, req: Request, slot: int, logits) -> int:
        """Sample the request's first token from its prefill logits
        with its own knobs/seed — one sync per admission (prefill is
        per-request anyway); the advanced key parks in the slot row."""
        with obs.span("serve.first_token", rid=req.rid, slot=slot):
            key = self._request_key(req)
            new_key, tok_arr = self._sample1(
                logits[:, -1], key[None],
                jnp.full((1,), req.temperature, jnp.float32),
                jnp.full((1,), req.top_k, jnp.int32),
                jnp.full((1,), req.top_p, jnp.float32))
            with obs.span("serve.sync", what="first_token"):
                tok = int(_host(tok_arr)[0])
            self._keys = self._keys.at[slot].set(new_key[0])
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
        return tok

    def _install(self, req: Request, slot: int, cache1, n_prompt: int
                 ) -> None:
        """Insert a prefilled (batch-1, contiguous) cache into ``slot``.

        Contiguous mode: per-leaf dynamic-update-slice.  Paged mode:
        retain any published prefix pages, allocate the rest (evicting
        cold prefixes under pressure), scatter the stripe's pages into
        the pool, write the slot's table row, and publish this prompt's
        full pages for future sharing.  Prefix-cache hits count into
        ``stats.prefix_hit_tokens`` once the allocation has succeeded,
        so an :class:`OutOfPages` requeue counts nothing."""
        with obs.span("serve.install", rid=req.rid, slot=slot):
            if self._geom is None or not self._pages_active:
                def insert(dst, src, ax):
                    return jax.lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), slot, axis=ax)
                self.cache = jax.tree.map(insert, self.cache, cache1,
                                          self._axes)
                return

            geom = self._geom
            n_reserve = min(n_prompt + req.max_new_tokens, self.max_len)
            t_alloc = geom.pages_for(n_reserve)
            share = (self.model.cfg.family in _SHARE_FAMILIES
                     and req.frontend_embeds is None)
            shared: list[int] = []
            if share:
                _, shared = self._prefix.lookup(req.prompt)
                shared = shared[:t_alloc]
                # hold the hits before allocating: eviction under
                # pressure must not recycle the very pages this
                # admission is reusing
                for p in shared:
                    self._alloc.retain(p)
            try:
                own = self._alloc_pages(t_alloc - len(shared))
            except OutOfPages:
                self._alloc.release_all(shared)
                raise
            self.stats.prefix_hit_tokens += len(shared) * geom.page_size
            pages = shared + own
            write_ids = np.full((geom.table_len,), TRASH_PAGE, np.int32)
            table_ids = np.full((geom.table_len,), TRASH_PAGE, np.int32)
            table_ids[:t_alloc] = pages
            write_ids[len(shared):t_alloc] = pages[len(shared):]
            self.cache = self._insert_paged(
                self.cache, cache1, slot,
                jnp.asarray(write_ids), jnp.asarray(table_ids))
            self._slot_pages[slot] = pages
            if share:
                self._prefix.publish(req.prompt, pages)
            self._page_gauges()

    def _alloc_pages(self, n: int) -> list[int]:
        """Atomic n-page allocation, evicting LRU prefix entries on
        pressure; raises :class:`OutOfPages` only once the prefix cache
        is empty too."""
        while True:
            try:
                return self._alloc.alloc(n)
            except OutOfPages:
                if not self._prefix.evict_lru():
                    raise

    def _page_gauges(self) -> None:
        s = self.stats
        s.pages_in_use = max(s.pages_in_use, self._alloc.in_use)
        counts = collections.Counter(
            p for pages in self._slot_pages for p in pages)
        s.pages_shared = max(
            s.pages_shared, sum(1 for c in counts.values() if c >= 2))

    def _retire(self, slot: int) -> None:
        st = self._slots[slot]
        self._results[st.request.rid] = GenerationResult(
            rid=st.request.rid, prompt_len=len(st.request.prompt),
            tokens=st.tokens, admitted_step=st.admitted_step,
            finished_step=self._step, queue_wait_s=st.queue_wait_s,
            ttft_s=st.ttft_s)
        self._slots[slot] = None
        self.stats.retired += 1
        if self._pages_active and self._slot_pages[slot]:
            # order matters: point the device table row at the trash
            # page FIRST, then release the host refs — a freed page can
            # be re-allocated immediately, and the retired row's frozen
            # decode writes in the next block must land in trash, never
            # in a page that now belongs to another request
            self.cache["page_table"] = \
                self.cache["page_table"].at[slot].set(TRASH_PAGE)
            self._alloc.release_all(self._slot_pages[slot])
            self._slot_pages[slot] = []
        obs.event("serve.retire", rid=st.request.rid, slot=slot,
                  tokens=len(st.tokens), steps=self._step - st.admitted_step)

    def _done(self, st: SlotState, tok: int) -> bool:
        return (len(st.tokens) >= st.request.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id))

    # -- chunked prefill -----------------------------------------------
    def _chunkable(self, req: Request) -> bool:
        return (self._chunk is not None
                and req.frontend_embeds is None
                and len(req.prompt) > self._chunk)

    def _start_chunking(self, req: Request, slot: int, queue_wait: float,
                        t_submit: float) -> None:
        """Park ``req`` in ``slot`` as an in-flight chunked admission:
        the prompt is ingested ``prefill_chunk`` tokens per engine step
        against a private contiguous stripe, which is installed into
        the shared cache only when the last chunk lands."""
        self._chunking[slot] = {
            "req": req, "off": 0, "n": len(req.prompt),
            "cache": self._chunk_cache_init(),
            "queue_wait": queue_wait, "t_submit": t_submit,
        }

    def _advance_chunk(self, slot: int) -> list[tuple[int, int]]:
        """Ingest one more chunk for the admission parked in ``slot``;
        on the final chunk, install the stripe, sample the first token
        and activate the slot.  Returns the streamed events (empty
        until the first token)."""
        st = self._chunking[slot]
        req: Request = st["req"]
        if "logits" not in st:
            off, n = st["off"], st["n"]
            end = min(off + self._chunk, n)
            toks = np.zeros((1, self._chunk), np.int32)
            toks[0, :end - off] = req.prompt[off:end]
            t0 = _now()
            with obs.span("serve.prefill_chunk", rid=req.rid, slot=slot,
                          step=self._step, offset=off, end=end):
                logits, st["cache"] = self._prefill_chunk_fn(
                    self.params, jnp.asarray(toks), st["cache"],
                    jnp.asarray(off, jnp.int32),
                    jnp.asarray([end], jnp.int32))
            dt = _now() - t0
            self.stats.prefill_s += dt
            self.stats.prefill_chunks += 1
            self._last_prefill_s = max(self._last_prefill_s, dt)
            st["off"] = end
            if end < n:
                return []
            st["logits"] = logits
        try:
            self._install(req, slot, st["cache"], st["n"])
        except OutOfPages:
            # the stripe is complete but the pool is full: keep the
            # parked state and retry next step once decodes retire —
            # unless nothing is active to ever free a page
            if not any(s is not None for s in self._slots):
                raise
            return []
        tok = self._first_token(req, slot, st["logits"])
        del self._chunking[slot]
        self.stats.prefill_tokens += st["n"]
        self.stats.admitted += 1
        ttft = _now() - st["t_submit"]
        self.stats.queue_wait_s.append(st["queue_wait"])
        self.stats.ttft_s.append(ttft)
        slot_st = SlotState(request=req, tokens=[tok], next_token=tok,
                            admitted_step=self._step,
                            queue_wait_s=st["queue_wait"], ttft_s=ttft)
        self._slots[slot] = slot_st
        if self._done(slot_st, tok):
            self._retire(slot)
        return [(req.rid, tok)]

    # ------------------------------------------------------------------
    def step(self) -> list[tuple[int, int]]:
        """Admissions + one fused decode block (``steps_per_dispatch``
        decode iterations, one host sync).  Returns streamed
        (rid, token) events in emission order.

        Traced, the step is one ``serve.step`` span whose children
        cover its host phases: ``serve.prefill_chunk`` / ``serve.admit``
        (with ``serve.install``, ``serve.first_token`` and its
        ``serve.sync``), ``serve.dispatch`` (``serve.launch`` of the
        block, then ``serve.sync`` of its tokens) and ``serve.emit``
        (the drain, retirements included)."""
        self._step += 1
        with obs.span("serve.step", step=self._step,
                      active=self.num_slots - self._slots.count(None),
                      pending=len(self._pending),
                      chunking=len(self._chunking)):
            events: list[tuple[int, int]] = []
            self._last_prefill_s = 0.0
            self._last_dispatch_s = 0.0

            # in-flight chunked admissions first: one chunk each per step,
            # interleaved between decode dispatches, so a long prompt never
            # head-of-line blocks the slots that are already decoding
            for slot in sorted(self._chunking):
                events.extend(self._advance_chunk(slot))

            blocked = False
            for slot in range(self.num_slots):
                if (self._slots[slot] is not None or slot in self._chunking
                        or not self._pending):
                    continue
                req = self._pending.popleft()
                t0 = _now()
                queue_wait = t0 - self._submit_t.pop(req.rid, t0)
                if self._chunkable(req):
                    self._start_chunking(req, slot, queue_wait,
                                         t0 - queue_wait)
                    events.extend(self._advance_chunk(slot))
                    continue
                try:
                    with obs.span("serve.admit", rid=req.rid, slot=slot,
                                  step=self._step,
                                  prompt_len=len(req.prompt)):
                        tok = self._admit(req, slot)
                except OutOfPages:
                    # pool exhausted: requeue at the front and stop
                    # admitting — active slots will retire and free pages
                    self._submit_t[req.rid] = t0 - queue_wait
                    self._pending.appendleft(req)
                    blocked = True
                    break
                t1 = _now()
                dt = t1 - t0
                self.stats.prefill_s += dt
                self.stats.prefill_tokens += len(req.prompt)
                self.stats.admitted += 1
                self._last_prefill_s = max(self._last_prefill_s, dt)
                # TTFT: submit -> first token on the host (the prefill
                # logits' sample); queue wait is the pre-admission share
                ttft = queue_wait + dt
                self.stats.queue_wait_s.append(queue_wait)
                self.stats.ttft_s.append(ttft)
                st = SlotState(request=req, tokens=[tok], next_token=tok,
                               admitted_step=self._step,
                               queue_wait_s=queue_wait, ttft_s=ttft)
                self._slots[slot] = st
                events.append((req.rid, tok))
                if self._done(st, tok):
                    self._retire(slot)

            active = [i for i, s in enumerate(self._slots) if s is not None]
            if blocked and not active and not self._chunking:
                raise OutOfPages(
                    f"page pool exhausted: request {self._pending[0].rid} "
                    f"cannot be admitted and no active request remains to "
                    f"free pages (pool: {self._geom.usable_pages} usable "
                    f"pages of {self._geom.page_size} tokens)")
            self.stats.max_concurrent = max(self.stats.max_concurrent,
                                            len(active))
            if not active:
                return events

            K = self.steps_per_dispatch
            toks = np.zeros((self.num_slots,), np.int32)
            done = np.ones((self.num_slots,), bool)
            budget = np.zeros((self.num_slots,), np.int32)
            for i in active:
                st = self._slots[i]
                toks[i] = st.next_token
                done[i] = False
                budget[i] = st.request.max_new_tokens - len(st.tokens)

            # all-greedy pools (the default) take the argmax-specialized
            # block — no sampler sorts/draws in the hot loop
            fn = (self._decode_block_greedy
                  if all(self._temp[i] == 0.0 for i in active)
                  else self._decode_block)
            t0 = _now()
            with obs.span("serve.dispatch", step=self._step, k=K,
                          active=len(active)):
                with obs.span("serve.launch"):
                    self.cache, block, self._keys = fn(
                        self.params, self.cache, jnp.asarray(toks),
                        self._keys, jnp.asarray(self._temp),
                        jnp.asarray(self._topk), jnp.asarray(self._topp),
                        jnp.asarray(done), jnp.asarray(budget))
                with obs.span("serve.sync", what="decode_block"):
                    block = _host(block)  # THE one sync of this dispatch
            dt = _now() - t0
            self._last_dispatch_s = dt
            self.stats.decode_s += dt
            self.stats.decode_steps += K
            self.stats.dispatches += 1
            self.stats.dispatch_occupancy.append(len(active) / self.num_slots)

            # drain the (num_slots, K) tile in step-major order so the
            # event stream is ordered exactly like K single-step
            # dispatches; the block's tokens share one emission time
            with obs.span("serve.emit", step=self._step, active=len(active)):
                t_emit = _now()
                for k in range(K):
                    for i in active:
                        st = self._slots[i]
                        if st is None:       # retired at an earlier k
                            continue
                        tok = int(block[i, k])
                        st.tokens.append(tok)
                        st.next_token = tok
                        self.stats.decode_tokens += 1
                        if st.last_emit_s is not None:
                            self.stats.token_latency_s.append(
                                t_emit - st.last_emit_s)
                        st.last_emit_s = t_emit
                        events.append((st.request.rid, tok))
                        if self._done(st, tok):
                            self._retire(i)
            return events

    # ------------------------------------------------------------------
    def run(self, requests: Sequence[Request] = (), *,
            step_timeout_s: float | None = None,
            prefill_timeout_s: float | None = None,
            decode_timeout_s: float | None = None,
            on_token: Callable[[int, int], None] | None = None
            ) -> dict[int, GenerationResult]:
        """Drive until every submitted request has finished.

        Timeouts turn a hung backend into a failure instead of a stall
        (CI's use).  Prefill and decode are timed against **separate**
        budgets: ``prefill_timeout_s`` bounds the slowest single
        admission prefill of a step and ``decode_timeout_s`` bounds the
        fused decode dispatch — a step that admits long prompts into
        several slots no longer trips the decode budget with prefill
        time.  ``step_timeout_s`` is shorthand for setting both.
        ``on_token``: streaming callback, called as tokens are emitted
        (drained once per block dispatch).
        """
        if prefill_timeout_s is None:
            prefill_timeout_s = step_timeout_s
        if decode_timeout_s is None:
            decode_timeout_s = step_timeout_s
        for r in requests:
            self.submit(r)
        while not self.idle:
            for rid, tok in self.step():
                if on_token is not None:
                    on_token(rid, tok)
            if prefill_timeout_s is not None \
                    and self._last_prefill_s > prefill_timeout_s:
                raise RuntimeError(
                    f"engine step {self._step}: an admission prefill took "
                    f"{self._last_prefill_s:.1f}s "
                    f"(> prefill_timeout_s={prefill_timeout_s})")
            if decode_timeout_s is not None \
                    and self._last_dispatch_s > decode_timeout_s:
                raise RuntimeError(
                    f"engine step {self._step}: decode dispatch took "
                    f"{self._last_dispatch_s:.1f}s "
                    f"(> decode_timeout_s={decode_timeout_s})")
        return dict(self._results)

    # ------------------------------------------------------------------
    def throughput(self) -> dict[str, float]:
        """Prefill and decode throughput, reported separately — decode
        is bandwidth-bound and prefill compute-bound (the roofline
        framing), so a single blended tokens/s hides both."""
        s = self.stats
        return {
            "prefill_tok_s": s.prefill_tok_s,
            "decode_tok_s": s.decode_tok_s,
            "prefill_s": s.prefill_s,
            "decode_s": s.decode_s,
        }


# ----------------------------------------------------------------------
def paged_view(cache: dict, page_size: int) -> dict:
    """A contiguous dense-family cache laid out as a page pool.

    ``k``/``v`` ``(L, B, max_len, KV, D)`` become ``(L, B*T, ps, KV,
    D)`` pools (``T = max_len // page_size``), and row ``b``'s table
    maps its logical pages to ``b*T .. b*T + T-1`` — the same values a
    paged engine holds, read through the same paged decode path."""
    k = cache["k"]
    L_, B, S = k.shape[:3]
    if S % page_size:
        raise ValueError(f"page_size {page_size} must divide {S}")
    T = S // page_size

    def pool(x):
        return x.reshape(L_, B * T, page_size, *x.shape[3:])
    table = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return {**cache, "k": pool(k), "v": pool(cache["v"]),
            "page_table": table}


def lockstep_generate(model, params, ctx, prompts: Sequence[Sequence[int]],
                      max_new_tokens: int | Sequence[int], *,
                      max_len: int, frontend_embeds=None,
                      page_size: int | None = None,
                      return_logits: bool = False,
                      forced: Sequence[Sequence[int]] | None = None):
    """Greedy lock-step oracle: one ragged batch, fused prefill, then
    synchronized decode.  The continuous-batching engine must match
    this token-for-token per request (row-independent families) at
    every ``steps_per_dispatch``.  ``page_size`` (dense family) decodes
    through the paged attention path over :func:`paged_view` of the
    prefilled cache — the oracle for a paged engine on a backend whose
    paged kernel is not bitwise equal to the contiguous decode math.
    ``return_logits`` also returns each request's float32 logits at
    every emitted step, ``(B, max(max_new_tokens), V)``, for checks
    that must tell a rounding-level near-tie from a real divergence.
    ``forced`` (one token list per prompt, e.g. an engine's output)
    teacher-forces the decode: each step feeds the forced token instead
    of the oracle's own pick, so the returned picks (and logits) are
    the oracle's verdict on every step of that continuation given the
    same history.  Equal to the free-running oracle up to the first
    step where the forced token differs from its pick."""
    B = len(prompts)
    if isinstance(max_new_tokens, int):
        max_new = [max_new_tokens] * B
    else:
        max_new = [int(m) for m in max_new_tokens]
    lens = [len(p) for p in prompts]
    S = max(lens)
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :lens[i]] = list(p)
    batch = {"tokens": jnp.asarray(toks),
             "lengths": jnp.asarray(lens, jnp.int32)}
    if frontend_embeds is not None:
        batch["frontend_embeds"] = jnp.asarray(frontend_embeds)
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, ctx, max_len))(params, batch)
    if page_size is not None:
        cache = paged_view(cache, page_size)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    outs = [[int(t)] for t in np.asarray(tok)]
    steps = [np.asarray(logits[:, -1], np.float32)] if return_logits else []
    decode = jax.jit(lambda p, c, t: model.decode(p, c, t, ctx),
                     donate_argnums=(1,))
    if forced is not None:
        feed = np.zeros((B, max(max_new)), np.int32)
        for i, f in enumerate(forced):
            feed[i, :len(f)] = list(f)[:max(max_new)]
    for step in range(max(max_new) - 1):
        if forced is not None:
            tok = jnp.asarray(feed[:, step])
        logits, cache = decode(params, cache, tok[:, None])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if return_logits:
            steps.append(np.asarray(logits[:, -1], np.float32))
        for i, t in enumerate(np.asarray(tok)):
            if len(outs[i]) < max_new[i]:
                outs[i].append(int(t))
    if return_logits:
        return outs, np.stack(steps, axis=1)
    return outs
