"""Serving engine: continuous-batching scheduler over a paged KV pool.

The engine is the model-side half of the serving subsystem:

- :class:`~repro.serving.scheduler.ContinuousBatchingScheduler` owns
  every *policy* decision — FIFO admission by token budget, page-pool
  growth, prefix aliasing, preemption/eviction (see its docstring for
  the admit → prefill → decode → evict loop);
- this class owns params, compiled steps and device state: chunked
  prefill (jitted once per (format, chunk index), memoized), ONE batched
  decode over the fixed slot capacity (static shapes — request churn
  never recompiles), and the paged KV cache
  (``models.init_paged_cache``) both read through the scheduler's page
  table.

**Async pipelined stepping** (``async_steps=True``, the default): the
decode step and its sampling run as ONE jitted program
(``models.decode_and_sample``) whose results stay on device — the
sampled token feeds the *next* step's inputs directly (the carried
``batch["tokens"]`` array), so the host never blocks on logits to
schedule more work.  Launched steps queue in a bounded in-flight deque
(pipeline depth 2: step N+1's host work — admit, prefill chunks, evict
checks — overlaps step N's device compute) and the host syncs exactly
once per delivered step, on the sampled token + finite flag.  Token
*delivery* (``req.output``, finish checks, latency notes) therefore lags
the launch frontier by up to one step; the pipeline flushes — every
in-flight step delivered, host state exact — at eviction, speculation,
snapshot, deadline-cancellation, sequence-horizon and fault boundaries,
so the resilience and rewind invariants below are unchanged.  The cache
argument of the decode and prefill-chunk programs is donated
(``donate_argnums``): back-to-back steps update the paged pool and the
per-slot state in place instead of copying them.  Greedy outputs are bit-identical with
``async_steps`` on or off (same program, same inputs — only delivery
timing differs); ``--no-async`` in ``launch/serve.py`` is the escape
hatch.

**Chunked prefill**: a prompt is prefilled in fixed-size
``prefill_chunk`` chunks (default: the whole ``prefill_len`` window)
that write their KV *directly* into the request's pool pages
(``models.prefill_chunk``) and are interleaved with the batched decode
step — each engine step runs up to
``scheduler.prefill_chunk_quota(n_decoding)`` chunks, then the decode
batch, so a long prompt never stalls in-flight decodes and every prefill
GEMM arrives at the plan cache as the one (chunk, d_model) signature
instead of a per-prompt-length zoo.

**Prefix caching**: with ``prefix_cache=True`` (the default) each
admission hashes its prefill window page-by-page
(:func:`repro.serving.kv_cache.page_prefix_hashes` — chained over the
whole prefix plus a precision salt, so a hit implies identical tokens at
identical positions under identical formats) and aliases the longest
cached chunk-aligned prefix out of the pool instead of recomputing it:
only the uncached suffix chunks run.  The hit path re-reads cached KV
through the page table — it never approximates it, so fp32 outputs are
bit-identical with the cache on or off.  Pages are refcounted; eviction
decrements, never frees, shared pages, and an evicted request re-attaches
to its own published pages on resume.  Prefix caching engages only when
every mixer layer is global attention (ring/recurrent prefix state is
not pageable) and ``prefill_chunk`` divides the window into ≥ 2
page-aligned chunks (the final chunk always recomputes — its logits seed
sampling).

KV storage: global-attention layers hold fixed-size pages from a shared
pool, quantized under ``kv_format`` (a
:class:`repro.core.formats.FormatPolicy` name; ``int8pt`` per-tensor-scale
int8 is the default whenever the config asks for a quantized cache,
``None`` stores raw compute-dtype pages).  Sequences grow page-by-page
with no recompaction; when the pool runs dry the scheduler evicts the
youngest-arrival request (its private pages return to the pool, shared
pages are decremented, the request re-enters the queue with its original
arrival stamp and resumes later by re-prefilling the last
``prefill_len`` tokens of its prompt + generated prefix — the same
static truncation window every admission applies, so under pool pressure
a long resumed request continues from a truncated context, exactly as an
equally long fresh prompt would).

Decode GEMVs: with ``grouped_qkv`` (default on the pallas backend) the
q/k/v projections of a decode step run as ONE grouped GEMM, so the plan
cache sees a single grouped signature per step instead of three GEMV
launches — the shape-adaptive batching the paper's small-GEMM claim is
about.

Precision: as before, ``format_policy=`` overrides the model config's
policy; a request may name its own prefill policy
(``Request(format_policy="int8")``).  The GEMM plan cache keys plans per
format, so the JSON warm start (``plan_cache_path=``) restores
format-keyed plans — including the grouped decode signature.  Every
policy computes with the parameters as given; a caller that narrows
them (:func:`serving_params`) names every policy that will read them.

**Failure model** (see :mod:`repro.serving.resilience`): requests fail
*individually*, the batch keeps decoding.  ``run()`` returns
``Dict[int, Response]`` — a list subclass carrying tokens plus a
structured status.  Per-request deadlines (``deadline_ms``, engine
default or per ``Request``) cancel late requests in ``step()``, freeing
their slot/pages and returning partial output with status
``"deadline"``.  Load shedding (``shed_queue_depth`` /
``shed_token_watermark``) rejects at ``submit`` with :class:`Shed`
instead of letting the queue grow without bound.  NaN/inf logits
quarantine only the poisoned slot (status ``"poisoned"``) — in fp32 the
batched decode is row-independent, so every other slot's tokens are
bit-identical to a fault-free run.  A head request that can never fit is
cancelled with :class:`CapacityExceeded` instead of wedging the engine.
``snapshot()``/``restore()`` capture the host-side request + page-index
state so a supervised restart re-admits in-flight requests through the
prefix-cache re-attachment path; ``watchdog_s`` arms a
:class:`~repro.distributed.fault.StepWatchdog` around every step so
hangs become supervised restarts.  ``fault=`` threads a deterministic
:class:`~repro.serving.resilience.FaultInjector` through the step/chunk/
logit hooks; ``debug_audit=True`` runs :meth:`KVPagePool.audit` after
every step.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import model as model_lib
from repro.serving.kv_cache import page_prefix_hashes
from repro.serving.resilience import (CapacityExceeded, DeadlineExceeded,
                                      FaultInjector, PoisonedOutput,
                                      RequestError, Response, Shed)
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.telemetry import tracing
from repro.telemetry.registry import registry as metrics_registry

__all__ = ["Request", "ServingEngine", "init_serving_params",
           "serving_params"]


def serving_params(params, *readers: ArchConfig):
    """``params`` with the GEMM weights (``w`` leaves), the expert stacks
    (``gate``, ``up``, ``down`` leaves of shape ``(E, ., .)``), the expert
    router and the embedding table / LM head narrowed to the operand width
    every reader rounds them to.

    ``readers`` are the configs whose format policies will compute with
    these parameters: the engine's, a weight-shared draft's, and any
    per-request prefill policy.  The cast applies only when it is exact
    for all of them: each is unquantized with an operand dtype equal to
    its compute dtype, and they agree on it.  Each consumer (the GEMM's
    operand cast, the grouped expert products' and the router's operand
    casts, ``embed``'s cast to the compute dtype, ``unembed``'s cast to
    the operand dtype) then rounds these leaves to that dtype first, so
    holding them narrow changes no result.  If any reader
    would see more bits (fp32) or quantizes from the weights (int8), the
    tree is returned unchanged.  Norms, biases and every other leaf keep
    their dtype.
    """
    from repro.models.layers import model_format
    widths = set()
    for cfg in readers:
        fmt = model_format(cfg)
        width = jnp.dtype(fmt.operand_jnp)
        if fmt.quantized or width != jnp.dtype(cfg.compute_dtype):
            return params
        widths.add(width)
    if len(widths) != 1:
        return params
    (narrow,) = widths

    def hold(path, x):
        name = getattr(path[-1], "key", None)
        stack = name in ("gate", "up", "down") and x.ndim >= 3
        if ((name in ("w", "table", "head", "router") or stack)
                and jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != narrow):
            return x.astype(narrow)
        return x

    return jax.tree_util.tree_map_with_path(hold, params)


def init_serving_params(key, cfg: ArchConfig, *readers: ArchConfig):
    """Random parameters of ``cfg`` from ``key``, initialised straight
    into :func:`serving_params` form for ``cfg`` and ``readers``.

    One program does both, so the fp32 tree never exists on the device:
    gemma-2b's takes 10 GB of a v5e's 16 GB, its bf16 serving form 5 GB.
    """
    def serve_init_params(k):
        return serving_params(model_lib.init_params(k, cfg), cfg, *readers)

    return jax.jit(serve_init_params)(key)


def _stack_decode_qkv(params):
    """Precompute the grouped decode-projection layout.

    Every attention mixer gains a stacked (…, 3, D, Nmax) ``qkv`` weight
    (``repro.graph.stack_group_weights`` — the same stacking the
    GroupNode path executes) so the jitted decode-step program reads the
    grouped operand directly instead of re-padding q/k/v on every step;
    prefill/forward ignore the extra leaf.  Returns a shallow-copied
    params tree — the caller's params are untouched.
    """
    from repro.graph import stack_group_weights

    def aug_layer(lp):
        m = lp.get("mixer")
        if not (isinstance(m, dict) and {"q", "k", "v"} <= m.keys()):
            return lp
        m = dict(m)
        m["qkv"] = stack_group_weights([m["q"]["w"], m["k"]["w"],
                                        m["v"]["w"]])
        lp = dict(lp)
        lp["mixer"] = m
        return lp

    out = dict(params)
    if params.get("groups") is not None:
        out["groups"] = [aug_layer(lp) for lp in params["groups"]]
    out["tail"] = [aug_layer(lp) for lp in params["tail"]]
    return out


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    format_policy: Optional[str] = None  # per-request prefill precision
    deadline: Optional[float] = None     # consumed by DeadlineScheduler
    #                                      (ignored by the FIFO default)
    deadline_ms: Optional[float] = None  # wall-clock completion deadline,
    #                                      measured from submit; overrides
    #                                      the engine-level default
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, params, cfg: ArchConfig, *, slots: int = 4,
                 cache_len: int = 512, prefill_len: int = 128,
                 seed: int = 0, plan_cache_path: Optional[str] = None,
                 format_policy: Optional[str] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 kv_format: Optional[str] = None,
                 token_budget: Optional[int] = None,
                 grouped_qkv: Optional[bool] = None,
                 scheduler_cls=None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 deadline_ms: Optional[float] = None,
                 shed_queue_depth: Optional[int] = None,
                 shed_token_watermark: Optional[int] = None,
                 fault: Optional[FaultInjector] = None,
                 debug_audit: bool = False,
                 watchdog_s: Optional[float] = None,
                 quarantine: bool = True,
                 clock=None,
                 spec_k: int = 0,
                 draft_params=None,
                 draft_config: Optional[ArchConfig] = None,
                 draft_groups: int = 1,
                 draft_format_policy: Optional[str] = None,
                 prefix_index_path: Optional[str] = None,
                 slo_monitor=None,
                 async_steps: bool = True,
                 pipeline_depth: int = 2):
        if format_policy is not None:
            cfg = dataclasses.replace(cfg, format_policy=format_policy)
        if kv_format is None and cfg.cache_quant:
            kv_format = "int8pt"  # the quantized-KV default (per-tensor)
        if kv_format is not None:
            from repro.core.formats import resolve_format
            resolve_format(kv_format)
        if grouped_qkv is None:
            grouped_qkv = (cfg.gemm_backend == "pallas"
                           or cfg.decode_qkv_grouped)
        # Paged storage replaces the legacy contiguous cache_quant slots;
        # prefill is chunked and quantizes at page-write time.
        from repro.core.geometry import cdiv
        cache_len = cdiv(cache_len, page_size) * page_size
        cfg = dataclasses.replace(cfg, cache_quant=False,
                                  kv_cache_format=kv_format,
                                  decode_qkv_grouped=bool(grouped_qkv))
        if grouped_qkv:
            params = _stack_decode_qkv(params)
        self.params = params
        self.cfg = cfg
        # Warm-start the GEMM plan cache so the decode hot path starts
        # with pre-tuned plans instead of re-solving them on first token.
        # Purely an optimization: a stale/corrupt file must not prevent
        # the engine from starting cold.
        self.plan_cache_path = plan_cache_path
        if plan_cache_path and os.path.exists(plan_cache_path):
            from repro.core import autotune
            try:
                autotune.load_plans(plan_cache_path)
            except (ValueError, KeyError, TypeError, OSError,
                    json.JSONDecodeError) as e:
                print(f"plan-cache warm start skipped "
                      f"({plan_cache_path}: {e})")
        self.slots = slots
        self.cache_len = cache_len
        self.prefill_len = prefill_len
        self.page_size = page_size
        if prefill_chunk is None:
            prefill_chunk = prefill_len
        if prefill_len % prefill_chunk != 0:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must divide "
                f"prefill_len ({prefill_len}): chunks are the static "
                f"prefill shape")
        self.prefill_chunk = int(prefill_chunk)
        self.n_chunks = prefill_len // self.prefill_chunk
        # Prefix caching needs page-aligned chunks, at least one chunk of
        # aliasable prefix ahead of the always-recomputed final chunk,
        # and a fully paged prefix (every mixer a global-attention layer:
        # ring/recurrent prefix state cannot be aliased out of the pool).
        self.prefix_cache = bool(prefix_cache)
        self._prefix_active = (
            self.prefix_cache
            and self.prefill_chunk % page_size == 0
            and prefill_len >= 2 * self.prefill_chunk
            and all(kind[0] == "attn" for kind in cfg.layer_kinds))
        self._key = jax.random.PRNGKey(seed)

        # A scheduling policy drops in by class (see ROADMAP "Serving
        # subsystem"): e.g. scheduler_cls=DeadlineScheduler for
        # earliest-deadline-first admission over Request.deadline.
        scheduler_cls = scheduler_cls or ContinuousBatchingScheduler
        self.sched = scheduler_cls(
            slots=slots, max_seq_len=cache_len, page_size=page_size,
            num_pages=num_pages, token_budget=token_budget,
            prefill_chunk=self.prefill_chunk)
        self.cache = model_lib.init_paged_cache(
            cfg, slots, cache_len, num_pages=self.sched.pool.num_pages,
            page_size=page_size)
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.slot_pos = np.zeros(slots, np.int32)
        self.completed: List[Request] = []
        # Ring/recurrent layers keep per-slot rows the batched decode
        # rewrites for EVERY row — a still-prefilling slot's carried
        # chunk state must be restored after each decode step.
        self._stateful_rows = any(kind[0] != "attn"
                                  for kind in cfg.layer_kinds)
        # Held-share expert layers count their work in every decode and
        # chunk program (``moe_assignments``, ``moe_experts_read``): each
        # program's counts ride in the next delivered entry and are added
        # up at that entry's one host sync.
        self._count_experts = (
            cfg.moe is not None and cfg.moe.held is not None
            and any(kind[1] == "moe" for kind in cfg.layer_kinds))
        self._expert_counts = np.zeros(2, np.int64)
        self._counts_pending: List[jax.Array] = []
        # slot -> in-flight chunked-prefill state
        # {"tokens": (prefill_len,) window, "chunk": next chunk index,
        #  "hashes": the window's page-prefix hashes (None: prefix off)}
        self._prefilling: Dict[int, dict] = {}

        # One jitted prefill-chunk program per (format, chunk index) —
        # outer dict keyed by format policy (None = engine default), so
        # a request-supplied format compiles its own chunk pipeline once.
        self._prefill_fns: Dict[Optional[str], Dict[int, object]] = {}

        # -- async pipelined stepping (see the module docstring) ---------------
        # Decode + sampling compile as ONE program whose cache argument
        # is donated (argnums: params=0, batch=1, cache=2) — back-to-back
        # steps update the paged slabs in place.  The in-flight deque is
        # the lagging delivery queue; ``pipeline_depth`` bounds how many
        # *steps* may be launched-but-undelivered at once (2 = step N+1's
        # host scheduling overlaps step N's device compute; faults force
        # an effective depth of 1, i.e. fully synchronous).
        self.async_steps = bool(async_steps)
        self.pipeline_depth = (max(1, int(pipeline_depth))
                               if self.async_steps else 1)
        self._inflight: Deque[dict] = collections.deque()
        self._flushing = False
        self.steps_in_flight_max = 0   # deepest pipeline ever (bench row)
        self._last_tok = jnp.zeros((slots, 1), jnp.int32)
        self._zero_key = jax.random.PRNGKey(0)  # greedy rows: no stream use

        # Every jitted serving program is a named function: the profiler
        # shows it as ``jit_<name>`` on the device's XLA Modules line.
        def serve_decode(p, b, c, key, temps, active):
            return model_lib.decode_and_sample(
                p, b, c, self.cfg, key=key, temperatures=temps,
                active_rows=active, with_counts=self._count_experts)

        def serve_scatter_token(lt, tok, slot):
            return lt.at[slot, 0].set(tok)

        self._decode_step = jax.jit(serve_decode, donate_argnums=(2,))
        self._seed_sample = jax.jit(model_lib.sample_token)
        self._scatter_tok = jax.jit(serve_scatter_token)

        # -- prefix-index persistence (cross-engine prefix cache) --------------
        # JSON of the pool's published (page, hash) pairs, saved next to
        # the plan cache at the end of run(): a restarted (or
        # disaggregated-decode) engine that kept/received the device
        # pages reloads the index so admissions alias the surviving KV.
        # Like the plan-cache warm start, a stale/corrupt/mismatched file
        # must never prevent a cold start.
        self.prefix_index_path = prefix_index_path
        if prefix_index_path and os.path.exists(prefix_index_path):
            try:
                self.sched.pool.load_index(prefix_index_path)
            except (ValueError, KeyError, TypeError, OSError,
                    json.JSONDecodeError) as e:
                print(f"prefix-index warm start skipped "
                      f"({prefix_index_path}: {e})")

        # -- speculative decoding (draft-and-verify) ---------------------------
        # spec_k >= 2 turns each decode step into: draft proposes k-1
        # tokens (a truncated weight-shared stack by default), the target
        # scores the whole window in ONE verify_chunk whose GEMMs carry
        # M = slots*k rows, accepted tokens commit, the first rejection
        # resamples from the target and rolls the state back.  k < 2 (or
        # the per-step clamp in _spec_depth) is exactly the vanilla path.
        self.spec_k = int(spec_k or 0)
        self._spec_on = self.spec_k >= 2
        self.draft_cfg: Optional[ArchConfig] = None
        self.draft_params = None
        self.spec_k_hist: Dict[int, int] = {}   # verify window k -> steps
        self._slot_window: Dict[int, np.ndarray] = {}
        self._draft_pos = np.zeros(slots, np.int32)
        if self._spec_on:
            if draft_config is not None:
                dcfg = draft_config
            else:
                dfmt = (draft_format_policy if draft_format_policy
                        is not None else cfg.format_policy)
                dcfg = cfg.draft(draft_groups, format_policy=dfmt)
            # Same serving overrides as the target: paged quantized KV,
            # grouped decode projections, chunk-time quantization.
            dcfg = dataclasses.replace(
                dcfg, cache_quant=False, kv_cache_format=kv_format,
                decode_qkv_grouped=bool(grouped_qkv))
            if draft_params is None:
                # Weight-shared truncation of the (already qkv-stacked)
                # target params — zero extra parameter memory.
                draft_params = model_lib.draft_from(
                    self.params, self.cfg,
                    groups=dcfg.n_layers // dcfg.period)
            elif grouped_qkv:
                draft_params = _stack_decode_qkv(draft_params)
            self.draft_cfg = dcfg
            self.draft_params = draft_params
            self._draft_stateful = any(kind[0] != "attn"
                                       for kind in dcfg.layer_kinds)
            # The draft keeps slot-private page stripes (no pool, no
            # sharing): slot i owns pages [1 + i*maxp, 1 + (i+1)*maxp).
            maxp = self.sched.max_pages_per_seq
            tbl = np.empty((slots, maxp), np.int32)
            for s in range(slots):
                tbl[s] = 1 + s * maxp + np.arange(maxp, dtype=np.int32)
            self._draft_table = tbl
            self.draft_cache = model_lib.init_paged_cache(
                dcfg, slots, cache_len, num_pages=slots * maxp + 1,
                page_size=page_size)

            def serve_draft_decode(p, b, c):
                return model_lib.decode(p, b, c, self.draft_cfg)

            def serve_draft_verify(p, b, c):
                return model_lib.verify_chunk(p, b, c, self.draft_cfg)

            def serve_verify(p, b, c):
                return model_lib.verify_chunk(p, b, c, self.cfg)

            self._draft_decode = jax.jit(serve_draft_decode)
            self._draft_verify = jax.jit(serve_draft_verify)
            self._verify = jax.jit(serve_verify)
            self._draft_chunk_fns: Dict[int, object] = {}
            self._spec_program = None
            if self.cfg.use_graph:
                self._warm_spec_program()

        # -- resilience (see repro.serving.resilience) ------------------------
        self.deadline_ms = deadline_ms
        self.shed_queue_depth = shed_queue_depth
        self.shed_token_watermark = shed_token_watermark
        self.fault = fault
        self.debug_audit = bool(debug_audit)
        self.quarantine = bool(quarantine)
        # Optional repro.telemetry.slo.SloMonitor evaluated after every
        # step (pure host-side registry reads — no device interaction,
        # so greedy outputs are bit-identical with or without it).
        self.slo_monitor = slo_monitor
        self._clock = clock or time.monotonic
        self.step_idx = 0
        self._deadline_at: Dict[int, float] = {}   # rid -> absolute deadline
        self._responses: Dict[int, Response] = {}  # rid -> finished Response

        # -- telemetry (repro.telemetry): per-request latency bookkeeping.
        # Timestamps are host-clock reads at sync points only (the token
        # is already host-visible when they fire); they feed the global
        # serving.{ttft,inter_token,queue_wait,e2e}_s histograms and the
        # per-request summary attached to Response.metrics.
        self._ts_submit: Dict[int, float] = {}
        self._ts_first: Dict[int, float] = {}
        self._ts_last: Dict[int, float] = {}
        self._queue_wait: Dict[int, float] = {}
        self._itl: Dict[int, List[float]] = {}
        self.watchdog_s = watchdog_s
        self._watchdog = None
        if watchdog_s:
            from repro.distributed.fault import StepWatchdog
            self._watchdog = StepWatchdog(watchdog_s)

    @property
    def queue(self) -> List[Request]:
        """Waiting requests in arrival order (FIFO line)."""
        return [e.req for e in
                sorted(self.sched.waiting, key=lambda e: e.arrival)]

    @property
    def steps_in_flight(self) -> int:
        """Distinct engine steps launched but not yet delivered (the
        lagging queue depth; 0 == host state is exact)."""
        return len({e["step"] for e in self._inflight})

    def _make_batch(self, tokens, *, pos=None, table=None, slot=None,
                    row_valid=None):
        """Assemble the device batch dict every model entry point reads:
        ``tokens`` plus optional per-row positions, page-table rows, the
        prefill ``slot`` scalar and the stateful-arch ``row_valid`` mask
        — one choke point instead of a hand-built dict per call site."""
        batch = {"tokens": jnp.asarray(tokens)}
        if pos is not None:
            batch["pos"] = jnp.asarray(pos)
        if table is not None:
            batch["page_table"] = jnp.asarray(table)
        if slot is not None:
            batch["slot"] = jnp.int32(slot)
        if row_valid is not None:
            batch["row_valid"] = jnp.asarray(row_valid)
        return batch

    def _chunk_fn(self, format_policy: Optional[str], chunk_idx: int):
        """The jitted prefill-chunk program for one (format, chunk
        index).  Compiled once per pair, then reused — all chunk indices
        share the same GEMM shapes, so the plan cache solves them once.
        Its cache argument is donated, as the decode program's is: the
        chunk writes the pool in place instead of into a second pool."""
        if format_policy == self.cfg.format_policy:
            format_policy = None  # engine default: share its compilation
        per_fmt = self._prefill_fns.setdefault(format_policy, {})
        fn = per_fmt.get(chunk_idx)
        if fn is None:
            cfg = (dataclasses.replace(self.cfg,
                                       format_policy=format_policy)
                   if format_policy is not None else self.cfg)
            pos0 = chunk_idx * self.prefill_chunk

            def serve_prefill_chunk(p, b, c):
                return model_lib.prefill_chunk(
                    p, b, c, cfg, pos0=pos0,
                    with_counts=self._count_experts)

            fn = jax.jit(serve_prefill_chunk, donate_argnums=(2,))
            per_fmt[chunk_idx] = fn
        return fn

    # -- client API -----------------------------------------------------------
    def submit(self, req: Request):
        if req.format_policy is not None:
            # Reject bad names at the door: a typo'd per-request policy
            # must fail this submit, not crash the batched loop (and
            # every other in-flight request) inside run().
            from repro.core.formats import resolve_format
            resolve_format(req.format_policy)
        err = self._shed_reason(req)
        if err is not None:
            self.sched.shed_requests += 1
            self._responses[req.rid] = Response(
                (), rid=req.rid, status=err.code, error=err)
            tr = tracing.active()
            if tr is not None:
                tr.instant("request.shed", args={"rid": req.rid})
            raise err
        self._ts_submit[req.rid] = self._clock()
        self.sched.submit(req)
        dl = req.deadline_ms if req.deadline_ms is not None \
            else self.deadline_ms
        if dl is not None:
            self._deadline_at[req.rid] = self._clock() + dl / 1000.0

    def _shed_reason(self, req: Request) -> Optional[Shed]:
        """Load-shedding admission: reject at the door when the queue is
        already deep or the committed-token demand (active + waiting +
        this request, each booked at ``prefill_len + max_tokens``) is
        over the watermark — bounded backpressure instead of unbounded
        queue growth."""
        if (self.shed_queue_depth is not None
                and len(self.sched.waiting) >= self.shed_queue_depth):
            return Shed(f"queue depth {len(self.sched.waiting)} >= "
                        f"{self.shed_queue_depth} (rid={req.rid})",
                        rid=req.rid)
        if self.shed_token_watermark is not None:
            demand = (self.sched._committed_tokens(self.prefill_len)
                      + sum(self.prefill_len
                            + int(getattr(e.req, "max_tokens", 0))
                            for e in self.sched.waiting)
                      + self.prefill_len + int(req.max_tokens))
            if demand > self.shed_token_watermark:
                return Shed(f"committed-token demand {demand} > watermark "
                            f"{self.shed_token_watermark} (rid={req.rid})",
                            rid=req.rid)
        return None

    def save_plan_cache(self, path: Optional[str] = None):
        """Persist tuned GEMM plans for the next process's warm start."""
        from repro.core import autotune
        target = path or self.plan_cache_path
        if target:
            autotune.save_plans(target)

    def run(self, max_steps: int = 1000) -> Dict[int, Response]:
        """Run until all submitted requests finish (or step budget).

        Returns ``rid -> Response`` — tokens plus structured status
        (``"ok"``; ``"deadline"``/``"shed"``/``"poisoned"``/
        ``"capacity"``/``"error"`` for contained failures;
        ``"incomplete"`` for requests still live at the step budget)."""
        for _ in range(max_steps):
            self._enforce_deadlines()
            with tracing.current().span("admit"):
                self._admit()
            if not any(r is not None for r in self.slot_req):
                if not self.sched.waiting:
                    break
                if self.sched.admission_stuck(self.prefill_len):
                    # The head alone exceeds the pool/budget: cancel it
                    # with a structured status instead of wedging the
                    # queue behind it (the old behaviour raised here).
                    head = self.sched._pick_admit()
                    self._cancel_waiting(head, CapacityExceeded(
                        f"request rid={head.rid} can never be admitted: "
                        f"pool={self.sched.pool.describe_str()}, "
                        f"token_budget={self.sched.token_budget}",
                        rid=head.rid))
                continue
            if self._watchdog is not None:
                self._watchdog.arm()
            self.step()
            if self._watchdog is not None:
                self._watchdog.disarm()
                self._watchdog.check()  # straggler -> StragglerError
        # Deliver every launched step before reporting: run() is the API
        # boundary, so Responses (including "incomplete" partials) always
        # carry the tokens of every step that ran.
        self._flush_pipeline()
        out = dict(self._responses)
        for r in self.queue + [r for r in self.slot_req if r is not None]:
            out[r.rid] = Response(r.output, rid=r.rid, status="incomplete")
        if self.prefix_index_path:
            try:
                self.sched.pool.save_index(self.prefix_index_path)
            except OSError as e:  # persistence is best-effort, like plans
                print(f"prefix-index save skipped "
                      f"({self.prefix_index_path}: {e})")
        return out

    def metrics(self) -> Dict[str, float]:
        """Scheduler counters (occupancy, token split, preemptions,
        prefix hit rate) plus pool sharing state and engine-level shape
        facts — the serving-throughput / serving-prefix inputs."""
        m = dict(self.sched.metrics())
        pool = self.sched.pool
        m.update(slots=self.slots, page_size=self.page_size,
                 num_pages=pool.num_pages,
                 free_pages=pool.free_pages,
                 kv_format=self.cfg.kv_cache_format or "none",
                 prefix_cache=int(self._prefix_active),
                 prefill_chunk=self.prefill_chunk,
                 prefix_queries=pool.prefix_queries,
                 prefix_hit_pages=pool.prefix_hit_pages,
                 shared_pages=pool.shared_pages,
                 cached_pages=pool.cached_pages,
                 cow_copies=pool.cow_copies,
                 spec_on=int(self._spec_on),
                 spec_k=self.spec_k)
        if self._count_experts:
            m.update(moe_assignments=int(self._expert_counts[0]),
                     moe_experts_read=int(self._expert_counts[1]))
        if self.spec_k_hist:
            steps = sum(self.spec_k_hist.values())
            m["spec_k_mean"] = (sum(k * n for k, n
                                    in self.spec_k_hist.items()) / steps)
        # Planner/compiler caches: hidden hit rates that explain whether
        # the serving hot path ever re-enters the solver.
        from repro.core import autotune
        from repro.graph import schedule as graph_schedule
        cs = autotune.cache_stats()
        m.update(plan_cache_hits=cs.hits, plan_cache_misses=cs.misses,
                 plan_solver_calls=cs.solver_calls)
        ps = graph_schedule.program_stats()
        m.update(graph_programs_compiled=ps.get("compiles", 0),
                 graph_program_hits=ps.get("hits", 0))
        from repro.telemetry.registry import publish
        publish("serving", m)
        return m

    # -- scheduler ------------------------------------------------------------
    def _window_tokens(self, req: Request) -> np.ndarray:
        """The request's static prefill window: the last ``prefill_len``
        tokens of prompt + generated output (resumption is position-
        rebased), left-padded to the fixed shape."""
        context = np.asarray(req.prompt, np.int32).ravel()
        if req.output:  # resuming a preempted request
            context = np.concatenate(
                [context, np.asarray(req.output, np.int32)])
        prompt = context[-self.prefill_len:]
        pad = self.prefill_len - len(prompt)
        return np.pad(prompt, (pad, 0))  # left-pad to static shape

    def _hasher(self, entry) -> List[str]:
        """Content hashes of an entry's prefill window.  The salt folds
        in every knob that changes the *stored bytes* a window produces:
        the prefill compute format and the KV storage format — two
        requests may only share pages when both match.  The window is
        stashed on the entry so admission reuses it (the scheduler
        memoizes the result until a preemption changes the window)."""
        req = entry.req
        fmt = req.format_policy or self.cfg.format_policy
        salt = f"{self.cfg.name}|{fmt}|{self.cfg.kv_cache_format}"
        entry.window = self._window_tokens(req)
        return page_prefix_hashes(entry.window, self.page_size, salt)

    def _admit(self):
        """Admit the longest-waiting requests while capacity allows.

        FIFO fairness: the scheduler considers only the minimum-arrival
        waiting request (a preempted request keeps its original stamp, so
        it re-enters at the *front* of the line, not behind requests
        submitted after it).  Admission allocates pages — aliasing the
        longest cached prefix when prefix caching is on — and queues the
        uncached suffix for chunked prefill; the chunks themselves run
        inside :meth:`step`, interleaved with decodes.
        """
        hasher = self._hasher if self._prefix_active else None
        while True:
            got = self.sched.pop_admit(self.prefill_len, hasher)
            if got is None:
                return
            slot, entry, cached_tok = got
            req = entry.req
            sub = self._ts_submit.get(req.rid)
            if sub is not None and req.rid not in self._queue_wait:
                # First admission only: a preempted request re-admits,
                # but its queue wait is the original submit -> admit gap.
                wait = self._clock() - sub
                self._queue_wait[req.rid] = wait
                metrics_registry().histogram(
                    "serving.queue_wait_s").observe(wait)
            self.slot_req[slot] = req
            self.slot_pos[slot] = 0
            window = (entry.window if entry.window is not None
                      else self._window_tokens(req))
            self._prefilling[slot] = {
                "tokens": window,
                "chunk": cached_tok // self.prefill_chunk,
                "hashes": entry.hashes,
            }
            if self._spec_on:
                # The draft re-derives the slot's whole context from this
                # window + the outputs; a fresh occupant starts from zero.
                self._slot_window[slot] = window
                self._draft_pos[slot] = 0

    def step(self):
        """One engine step (see :meth:`_step_impl`), followed by the
        per-step observability hook: KV-pool occupancy and scheduler
        depth land in the metrics registry as ``kv.*`` / ``serving.*``
        gauges and the optional :class:`SloMonitor` evaluates its
        objectives — all pure host-side bookkeeping, after the step's
        device work is already submitted.

        Host spans, strictly nested under ``step``: ``admit``,
        ``prefill_chunk``, ``device_wait`` (the blocking fetch of a
        delivered step), ``sample`` (its host bookkeeping), ``evict``,
        ``decode_launch`` and ``observe``; ``draft`` and ``verify`` on
        the speculative path."""
        tr = tracing.current()
        with tr.span("step"):
            self._step_impl()
            with tr.span("observe"):
                self._observe_step()

    def _observe_step(self):
        """Publish per-step pool/scheduler state and evaluate SLOs.
        Registry writes only — never touches device state or RNG, so
        enabling it cannot perturb decode outputs."""
        from repro.telemetry.registry import publish, registry
        publish("kv", self.sched.pool.describe())
        reg = registry()
        reg.gauge("serving.queue_depth").set(len(self.sched.waiting))
        reg.gauge("serving.active_slots").set(
            sum(1 for r in self.slot_req if r is not None))
        reg.gauge("serving.completed_requests").set(
            self.sched.completed_requests)
        reg.gauge("serving.cancelled_requests").set(
            self.sched.cancelled_requests)
        reg.gauge("serving.finished_requests").set(
            self.sched.completed_requests + self.sched.cancelled_requests)
        if self.slo_monitor is not None:
            self.slo_monitor.observe(step=self.step_idx)

    def _step_impl(self):
        """One engine step: up to ``prefill_chunk_quota`` prefill chunks,
        then ONE batched decode+sample launch over the decoding slots.

        Chunks run first so a slot finishing its prefill joins the same
        step's decode batch (single-chunk prefills behave exactly like
        the old monolithic admission).  Before the decode, every decoding
        sequence's page coverage for its next token is guaranteed
        (growing into the shared pool, evicting the youngest request when
        the pool runs dry — shared pages are only decremented).  Per-slot
        positions ride in ``pos`` (B,) and the page table in
        ``batch["page_table"]`` — slots at different depths decode
        together with static shapes, so no recompiles; still-prefilling
        slots ride along masked (all-(−1) table rows scribble their
        garbage token into the reserved null page, and on architectures
        with ring/recurrent per-slot state ``row_valid`` masks their
        batch rows so the carried chunk state survives the decode).

        The launch does not block: sampling happens inside the decode
        program, its token feeds the next step on device, and host
        delivery (:meth:`_deliver_decode`) lags by up to
        ``pipeline_depth - 1`` steps.  The pipeline flushes first at
        every boundary that reads or rewrites host-visible output state:
        sequence horizon, speculation, imminent eviction (and, via their
        own call sites, deadlines / snapshots / faults).

        Containment: the injected :class:`FaultInjector` hooks fire at
        the step boundary (crash/straggle/alloc-failure) and per decode
        row (logit poison); non-finite logits quarantine only their slot.
        """
        self.step_idx += 1
        if self.fault is not None:
            # May raise EngineCrash (supervised restart path) or arm a
            # pool allocation failure / sleep through a straggle.
            self.fault.step_begin(self.step_idx, pool=self.sched.pool)
        self._enforce_deadlines()
        self._run_prefill_chunks()
        # Retire the previous step HERE — after this step's admit/prefill
        # host work (which the in-flight decode span therefore overlaps)
        # and before the decode-launch decisions below (which therefore
        # see every delivered finish and never schedule a dead slot).
        self._drain_to_depth()
        # The retire may have freed slots that this step's run()-level
        # admission could not see (delivery lags launch by one step).
        # Re-admit into them now — work conservation: a finish never
        # costs an idle slot-step relative to the synchronous loop.
        if self.sched.waiting and any(r is None for r in self.slot_req):
            with tracing.current().span("admit"):
                self._admit()
            self._run_prefill_chunks()
        decoding = [s for s, r in enumerate(self.slot_req)
                    if r is not None and s not in self._prefilling]
        # Horizon boundary: a slot whose launched position reached
        # cache_len finishes at delivery — flush so that lands before
        # anything more is scheduled for it.
        if self._inflight and any(int(self.slot_pos[s]) >= self.cache_len
                                  for s in decoding):
            self._flush_pipeline()
            decoding = [s for s in decoding if self.slot_req[s] is not None
                        and s not in self._prefilling]
        # Speculation depth for this step: the configured k clamped by
        # the scheduler's load policy, every slot's horizon room, and the
        # pages obtainable WITHOUT eviction — a full pool degrades the
        # step to k=1 (vanilla decode) instead of preempting anyone.
        k_step = self._spec_depth(decoding) if decoding else 1
        if k_step >= 2 and self._inflight:
            # Spec boundary: draft windows and accept/reject read
            # req.output on the host every step — drain first.
            self._flush_pipeline()
            decoding = [s for s in decoding if self.slot_req[s] is not None
                        and s not in self._prefilling]
            k_step = self._spec_depth(decoding) if decoding else 1
        # Eviction boundary: preemption requeues the victim with its
        # host-visible output, so in-flight tokens must land first.
        if self._inflight and decoding and self._needs_eviction(decoding,
                                                                k_step):
            self._flush_pipeline()
            decoding = [s for s in decoding if self.slot_req[s] is not None
                        and s not in self._prefilling]
            k_step = self._spec_depth(decoding) if decoding else 1
        with tracing.current().span("evict"):
            for slot in decoding:
                if self.slot_req[slot] is None or slot in self._prefilling:
                    continue
                evicted = self.sched.ensure_decode(
                    slot, int(self.slot_pos[slot]) + k_step)
                for vslot, _ventry in evicted:
                    self._clear_slot(vslot)
        decoding = [s for s in decoding if self.slot_req[s] is not None
                    and s not in self._prefilling]
        if not decoding:
            self._drain_to_depth()
            if self.debug_audit:
                self.sched.pool.audit()
            return
        for slot in decoding:
            self._cow_guard(slot, k_step)
        if k_step >= 2:
            self._spec_step(decoding, k_step)
            if self.debug_audit:
                self.sched.pool.audit()
            return
        self._launch_decode(decoding)
        self._drain_to_depth()
        if self.debug_audit:
            self.sched.pool.audit()

    # -- async pipeline --------------------------------------------------------
    def _needs_eviction(self, decoding, k_step: int) -> bool:
        """Host-side dry run of this step's pool demand (the same
        arithmetic as :meth:`_spec_depth`'s no-evict clamp): True when
        ``ensure_decode`` would have to preempt someone, i.e. the pages
        wanted beyond what the decoding slots already own exceed the
        allocatable (free + reclaimable cached-free) list."""
        pool = self.sched.pool
        need = 0
        for slot in decoding:
            entry = self.sched.active.get(slot)
            if entry is None:
                continue
            owned = len(pool.pages_of(entry.arrival))
            want = -(-(int(self.slot_pos[slot]) + k_step) // self.page_size)
            need += max(0, want - owned)
        return need > pool.free_pages

    def _launch_decode(self, decoding):
        """Submit one batched decode+sample program and queue its
        delivery.  Nothing here blocks on the previous step: the token
        inputs are the carried device-side last-token array (updated
        *inside* the previous launch), and pos/table/temps are host
        scheduler state."""
        with tracing.current().span("decode_launch"):
            table = np.full((self.slots, self.sched.max_pages_per_seq), -1,
                            np.int32)
            temps = np.zeros(self.slots, np.float32)
            active = np.zeros(self.slots, bool)
            for slot in decoding:
                table[slot] = self.sched.table_row(slot)
                temps[slot] = max(0.0, float(self.slot_req[slot].temperature))
                active[slot] = True
            if temps.any():
                self._key, key = jax.random.split(self._key)
            else:
                key = self._zero_key   # all-greedy: key stream untouched
            # Row-valid mask: ring/recurrent cache rows of slots that are
            # not decoding this step keep their prior state inside the
            # decode program itself, and held-share expert layers count
            # only these rows.  Always passed for such archs so the jit
            # signature is stable.  ``slot_pos`` is copied: the
            # device array may alias the host buffer until the launch has
            # read it, and the positions advance in place just below.
            batch = self._make_batch(
                self._last_tok, pos=self.slot_pos.copy(), table=table,
                row_valid=(active if self._stateful_rows
                           or self._count_experts else None))
            # The decode span stays open until delivery: it covers the
            # device-resident window, so async traces show decode visibly
            # overlapping the NEXT step's admit/prefill/sample host spans.
            # Not host work, so not a profiler annotation: the device's own
            # module events show that interval in a profile.
            tr = tracing.active()
            span = None
            if tr is not None:
                span = tr.span("decode", annotate=False, args={
                    "step": self.step_idx, "rows": len(decoding)})
                span.__enter__()
            out = self._decode_step(
                self.params, batch, self.cache, key, jnp.asarray(temps),
                jnp.asarray(active))
            tok, finite, logits, self._last_tok, self.cache = out[:5]
            self._inflight.append({
                "kind": "decode", "step": self.step_idx, "span": span,
                "slots": list(decoding),
                "reqs": {s: self.slot_req[s] for s in decoding},
                "pos_after": {s: int(self.slot_pos[s]) + 1
                              for s in decoding},
                "tok": tok, "finite": finite, "logits": logits,
                "counts": self._take_counts(out[5:]),
            })
            for slot in decoding:
                self.slot_pos[slot] += 1
            self.steps_in_flight_max = max(self.steps_in_flight_max,
                                           self.steps_in_flight)

    def _drain_to_depth(self):
        """Deliver in-flight results down to the pipeline's depth bound.

        Synchronous mode (``pipeline_depth`` 1, or any step while a
        :class:`FaultInjector` is armed — its poison/sample semantics are
        host-side and must fire in the same step the decode ran) flushes
        everything.  Async mode retires every entry from *older* steps,
        plus this step's own prefill *seeds* (a request's first token is
        its TTFT — it never lags): the current step's decode launches
        stay on device across the next
        step's host scheduling window, which is the depth-2 pipeline —
        step N's decode is still in flight while step N+1 admits and
        prefills.  Crucially this runs *before* the next decode launch,
        so launch decisions always see delivered finishes and never burn
        a step decoding a request whose final token is merely undelivered
        (the single-core "bubble" tax that would otherwise make async
        strictly worse than sync when compute cannot overlap the host).
        """
        depth = 1 if self.fault is not None else self.pipeline_depth
        if depth <= 1:
            self._flush_pipeline()
            return
        while self._inflight and self._inflight[0]["step"] < self.step_idx:
            self._retire_one()
        # Seed tokens deliver in their own step: the first token is the
        # TTFT-critical path, and lagging it would charge the *next*
        # step's host window (admission, chunk compiles) to this
        # request's time-to-first-token.  Only decode entries lag.
        while self._inflight and self._inflight[0]["kind"] == "seed":
            self._retire_one()

    def _flush_pipeline(self):
        """Deliver every launched step now — the synchronization barrier
        at eviction / speculation / snapshot / horizon / deadline / fault
        boundaries and at the end of :meth:`run`.  After a flush the
        host-side state (outputs, finishes, releases) is exact."""
        if self._flushing:
            return
        self._flushing = True
        try:
            while self._inflight:
                self._retire_one()
        finally:
            self._flushing = False

    def _retire_one(self):
        """Deliver the oldest in-flight entry — the ONE intentional host
        sync per step (sampled token + finite flag together)."""
        entry = self._inflight.popleft()
        try:
            if entry["kind"] == "seed":
                self._deliver_seed(entry)
            else:
                self._deliver_decode(entry)
        finally:
            span = entry.get("span")
            if span is not None:
                span.__exit__(None, None, None)

    def _take_counts(self, counts) -> List[jax.Array]:
        """The expert counts of the programs launched since the last
        entry, ``counts`` (this program's, if any) last."""
        taken = self._counts_pending + list(counts)
        self._counts_pending = []
        return taken

    def _fetch(self, entry):
        """The entry's sampled tokens and finite flags, fetched with the
        expert counts it carries in one host sync."""
        with tracing.current().span("device_wait"):
            tok, finite, counts = jax.device_get(
                (entry["tok"], entry["finite"], entry["counts"]))
        for c in counts:
            self._expert_counts += np.asarray(c, np.int64)
        return tok, finite

    def _deliver_decode(self, entry):
        """Host bookkeeping for one delivered decode step: append the
        token, latency notes, finish / horizon checks, quarantine —
        exactly what the synchronous engine did inline.  Slots whose
        request finished, was evicted or cancelled after the launch are
        discarded: their device-side write went to pages that are either
        still owned or fully rewritten by a later owner's prefill
        (launch order == device execution order)."""
        tok, finite = self._fetch(entry)
        tok = np.asarray(tok).copy()
        finite = np.asarray(finite).copy()
        if self.fault is not None:
            # Poison fires on host logits exactly as the synchronous
            # engine did: fetch the fp32 row, override the token with
            # the legacy host-side sample, re-derive quarantine from the
            # poisoned values.  (Faults force depth 1, so this runs in
            # the same step the decode did.)
            logits = None
            for slot in entry["slots"]:
                req = entry["reqs"][slot]
                val = self.fault.poison_value(entry["step"], req.rid)
                if val is None:
                    continue
                if logits is None:
                    logits = np.array(jnp.asarray(entry["logits"],
                                                  jnp.float32))
                logits[slot] = val
                finite[slot] = bool(np.isfinite(logits[slot]).all())
                t = int(self._sample(logits[slot:slot + 1], req)[0])
                tok[slot] = t
                if self.slot_req[slot] is req:
                    self._last_tok = self._scatter_tok(
                        self._last_tok, jnp.int32(t), slot)
        n_live = 0
        with tracing.current().span("sample"):
            for slot in entry["slots"]:
                req = entry["reqs"][slot]
                if req.done or self.slot_req[slot] is not req:
                    continue   # finished/evicted after launch: discard
                n_live += 1
                if self.quarantine and not finite[slot]:
                    self._cancel_active(slot, PoisonedOutput(
                        f"non-finite logits for rid={req.rid} at step "
                        f"{entry['step']}", rid=req.rid))
                    continue
                req.output.append(int(tok[slot]))
                self._note_emitted(req, 1)
                done = self._finished(slot)
                # Capacity guard: a sequence at the page-table horizon
                # must finish now — there is no logical page for the
                # next token.
                if not done and entry["pos_after"][slot] >= self.cache_len:
                    self._record_done(req)
                    self.slot_req[slot] = None
                    self.slot_pos[slot] = 0
                    self.sched.release(slot, finished=True)
        if n_live:
            self.sched.note_step(n_live,
                                 lag=self.step_idx - entry["step"])

    def _deliver_seed(self, entry):
        """Deliver a prefill seed token (the final chunk's on-device
        sample): the first token of a freshly prefilled request."""
        slot = entry["slots"][0]
        req = entry["reqs"][slot]
        tok, finite = self._fetch(entry)
        if req.done or self.slot_req[slot] is not req:
            return
        if self.quarantine and not bool(np.asarray(finite).reshape(-1)[0]):
            self._cancel_active(slot, PoisonedOutput(
                f"non-finite prefill logits for rid={req.rid} at step "
                f"{entry['step']}", rid=req.rid))
            return
        req.output.append(int(np.asarray(tok).reshape(-1)[0]))
        self._note_emitted(req, 1)
        self._finished(slot)

    # -- chunked prefill -------------------------------------------------------
    def _run_prefill_chunks(self):
        """Advance in-flight prefills by up to the scheduler's chunk
        quota, oldest arrival first (chunks are budgeted like decode
        tokens — the policy hook rides next to ``_pick_admit``)."""
        if not self._prefilling:
            return
        n_decoding = sum(1 for s, r in enumerate(self.slot_req)
                         if r is not None and s not in self._prefilling)
        quota = max(1, int(self.sched.prefill_chunk_quota(n_decoding)))
        for _ in range(quota):
            if not self._prefilling:
                return
            slot = min(self._prefilling,
                       key=lambda s: self.sched.active[s].arrival)
            try:
                with tracing.current().span("prefill_chunk"):
                    self._advance_prefill(slot)
            except RequestError as e:
                # Chunk-compute failure: contained to this request — its
                # slot and pages free, every other request unaffected.
                self._cancel_active(slot, e)

    def _advance_prefill(self, slot: int):
        """Run ONE prompt chunk for ``slot`` straight into its pool
        pages; the final chunk's logits seed the first sampled token."""
        st = self._prefilling[slot]
        req = self.slot_req[slot]
        c = st["chunk"]
        size = self.prefill_chunk
        toks = st["tokens"][c * size:(c + 1) * size]
        batch = self._make_batch(toks[None],
                                 table=self.sched.table_row(slot)[None],
                                 slot=slot)
        if self.fault is not None:
            self.fault.chunk_fault(self.step_idx, req.rid, c)
        # A compile or lowering error here is a defect of the program,
        # not of this request: it propagates instead of being contained.
        out = self._chunk_fn(req.format_policy, c)(
            self.params, batch, self.cache)
        logits, self.cache = out[:2]
        self._counts_pending.extend(out[2:])
        # Publish the chunk's fully-written pages to the prefix cache —
        # only now: an eviction mid-prefill must never leave a
        # half-written page findable.
        if st["hashes"] is not None and size % self.page_size == 0:
            per_chunk = size // self.page_size
            for j in range(c * per_chunk, (c + 1) * per_chunk):
                self.sched.register_prefix(slot, j, st["hashes"][j])
        st["chunk"] = c + 1
        if st["chunk"] >= self.n_chunks:
            del self._prefilling[slot]
            self.slot_pos[slot] = self.prefill_len
            if self.fault is not None:
                # Fault-injection path stays fully synchronous (depth 1):
                # poison overrides and quarantine need the host logits in
                # the same step the chunk ran.
                logits = np.array(jnp.asarray(logits, jnp.float32))
                val = self.fault.poison_value(self.step_idx, req.rid)
                if val is not None:
                    logits[:] = val
                if self.quarantine and not np.isfinite(logits).all():
                    raise PoisonedOutput(
                        f"non-finite prefill logits for rid={req.rid} at "
                        f"step {self.step_idx}", rid=req.rid)
                tok = int(self._sample(logits, req)[0])
                req.output.append(tok)
                self._note_emitted(req, 1)
                self._finished(slot)
                if self.slot_req[slot] is req:
                    self._last_tok = self._scatter_tok(
                        self._last_tok, jnp.int32(tok), slot)
                return
            # Seed the first token on device: sample from the final
            # chunk's logits without a host round-trip, scatter it into
            # the carried last-token array (so the next decode launch
            # reads it), and queue the host-side delivery.
            temp = max(0.0, float(req.temperature))
            if temp > 0.0:
                self._key, key = jax.random.split(self._key)
            else:
                key = self._zero_key
            tok, finite = self._seed_sample(
                logits, key, jnp.full((1,), temp, jnp.float32))
            self._last_tok = self._scatter_tok(self._last_tok, tok[0], slot)
            self._inflight.append({
                "kind": "seed", "step": self.step_idx, "span": None,
                "slots": [slot], "reqs": {slot: req},
                "tok": tok, "finite": finite,
                "counts": self._take_counts(()),
            })
            self.steps_in_flight_max = max(self.steps_in_flight_max,
                                           self.steps_in_flight)

    # -- speculative decoding --------------------------------------------------
    #
    # Step anatomy (spec_k = k, decoding slots ride batched, inactive
    # rows masked):
    #   1. draft catch-up: feed the draft every *known* token it has not
    #      seen (window prefill via the draft's chunk programs, then
    #      batched multi-token windows through the draft's verify_chunk —
    #      all fed tokens are real history, so catch-up always commits);
    #      the final logits propose draft token d_1.
    #   2. snapshot the draft cache, then k-2 batched draft decode steps
    #      feed d_1..d_{k-2} and propose d_2..d_{k-1}.
    #   3. ONE target verify_chunk scores the window [e, d_1..d_{k-1}]
    #      (e = the last emitted token, position slot_pos): its GEMMs
    #      carry M = slots*k rows — the M=1 decode GEMV turned into the
    #      GEMM shape family the paper's flexible tiles are built for.
    #   4. accept/reject: greedy keeps drafts while argmax agrees and
    #      emits the target argmax at the first mismatch (bit-identical
    #      to vanilla decode); sampled requests run rejection sampling
    #      (accept d_i w.p. min(1, p_t/p_d); resample the residual on
    #      reject), which preserves the target distribution exactly.
    #   5. rollback: rejected positions are *rewound*, never freed —
    #      paged KV past the accepted point is garbage the next window
    #      overwrites (pages are position-addressed, CoW-guarded);
    #      ring/recurrent rows restore their pre-verify state and replay
    #      the accepted prefix through the same verify program.
    def _spec_depth(self, decoding) -> int:
        """This step's window length k: configured ``spec_k``, clamped by
        the scheduler's ``spec_k`` load policy, each slot's sequence
        horizon, and the largest window whose extra pages every decoding
        slot can take from the *free* list — speculation never evicts."""
        if not self._spec_on or not decoding:
            return 1
        k = self.spec_k
        cap = self.sched.spec_k(len(decoding))
        if cap is not None:
            k = min(k, int(cap))
        for slot in decoding:
            k = min(k, self.cache_len - int(self.slot_pos[slot]))
        pool = self.sched.pool
        while k >= 2:
            need = 0
            for slot in decoding:
                entry = self.sched.active[slot]
                owned = len(pool.pages_of(entry.arrival))
                want = -(-(int(self.slot_pos[slot]) + k) // self.page_size)
                need += max(0, want - owned)
            if need <= pool.free_pages:
                break
            k -= 1
        return max(1, k)

    def _known_tokens(self, slot: int) -> np.ndarray:
        """Every token whose position is settled for ``slot``: the
        admission window (positions [0, prefill_len)) + the emitted
        output.  Position p holds known[p]; the last emitted token sits
        at position ``len(known) - 1 == slot_pos`` (not yet in the
        target cache)."""
        return np.concatenate([self._slot_window[slot],
                               np.asarray(self.slot_req[slot].output,
                                          np.int32)])

    def _draft_chunk_fn(self, chunk_idx: int):
        fn = self._draft_chunk_fns.get(chunk_idx)
        if fn is None:
            pos0 = chunk_idx * self.prefill_chunk

            def serve_draft_prefill_chunk(p, b, c):
                return model_lib.prefill_chunk(p, b, c, self.draft_cfg,
                                               pos0=pos0)

            fn = jax.jit(serve_draft_prefill_chunk)
            self._draft_chunk_fns[chunk_idx] = fn
        return fn

    def _draft_catchup(self, decoding, k) -> Dict[int, np.ndarray]:
        """Advance the draft to every known token.  Returns per-slot
        final logits (the proposal distribution for d_1).  Fresh slots
        prefill their window through the draft's chunk programs (same
        static shapes as the target's); the remaining tokens feed as
        batched multi-token windows (grouped by distinct length, ≤ k)
        through the draft's verify_chunk — real history only, so every
        window commits and ``_draft_pos`` advances unconditionally."""
        for slot in decoding:
            if int(self._draft_pos[slot]) == 0:
                window = self._slot_window[slot]
                for c in range(self.n_chunks):
                    toks = window[c * self.prefill_chunk:
                                  (c + 1) * self.prefill_chunk]
                    batch = self._make_batch(
                        toks[None], table=self._draft_table[slot][None],
                        slot=slot)
                    _, self.draft_cache = self._draft_chunk_fn(c)(
                        self.draft_params, batch, self.draft_cache)
                self._draft_pos[slot] = self.prefill_len
        last: Dict[int, np.ndarray] = {}
        known = {s: self._known_tokens(s) for s in decoding}
        while True:
            rem = {s: len(known[s]) - int(self._draft_pos[s])
                   for s in decoding if len(known[s]) > self._draft_pos[s]}
            if not rem:
                return last
            length = min(min(rem.values()), k)
            rows = sorted(rem)
            logits = self._draft_window(rows, length, known)
            for s in rows:
                self._draft_pos[s] += length
                if int(self._draft_pos[s]) == len(known[s]):
                    last[s] = logits[s, length - 1]

    def _draft_window(self, rows, length, known) -> np.ndarray:
        """One batched draft verify_chunk feeding ``length`` known tokens
        for ``rows`` (other rows masked).  Returns (slots, length, V)."""
        tokens = np.zeros((self.slots, length), np.int32)
        pos = np.zeros(self.slots, np.int32)
        table = np.full_like(self._draft_table, -1)
        rv = np.zeros(self.slots, bool)
        for s in rows:
            dp = int(self._draft_pos[s])
            tokens[s] = known[s][dp:dp + length]
            pos[s] = dp
            table[s] = self._draft_table[s]
            rv[s] = True
        batch = self._make_batch(
            tokens, pos=pos, table=table,
            row_valid=rv if self._draft_stateful else None)
        logits, self.draft_cache = self._draft_verify(
            self.draft_params, batch, self.draft_cache)
        return np.asarray(logits, np.float32)

    def _propose(self, logits: np.ndarray, req: Request) -> int:
        """Sample one draft proposal from the draft's distribution
        (argmax for greedy requests — rejection sampling needs the
        proposal drawn from the same p_d it divides by)."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        self._key, sub = jax.random.split(self._key)
        return int(jax.random.categorical(
            sub, jnp.asarray(logits) / req.temperature))

    def _draft_propose(self, decoding, k):
        """Draft k-1 proposals per decoding slot.  Returns (proposals,
        draft_logits, snapshot): per-slot proposal token lists, the draft
        logits each was drawn from (rejection sampling divides by them),
        and the post-catch-up draft cache (the rollback point —
        ``_draft_pos`` stays at the catch-up position until acceptance
        is known)."""
        last = self._draft_catchup(decoding, k)
        snapshot = self.draft_cache
        proposals = {s: [] for s in decoding}
        dlogits = {s: [] for s in decoding}
        cur = last
        for i in range(k - 1):
            for s in decoding:
                proposals[s].append(self._propose(cur[s], self.slot_req[s]))
                dlogits[s].append(cur[s])
            if i == k - 2:
                break
            tokens = np.zeros((self.slots, 1), np.int32)
            pos = np.zeros(self.slots, np.int32)
            table = np.full_like(self._draft_table, -1)
            rv = np.zeros(self.slots, bool)
            for s in decoding:
                tokens[s, 0] = proposals[s][-1]
                pos[s] = int(self._draft_pos[s]) + i
                table[s] = self._draft_table[s]
                rv[s] = True
            batch = self._make_batch(
                tokens, pos=pos, table=table,
                row_valid=rv if self._draft_stateful else None)
            logits, self.draft_cache = self._draft_decode(
                self.draft_params, batch, self.draft_cache)
            logits = np.asarray(logits, np.float32)
            cur = {s: logits[s] for s in decoding}
        return proposals, dlogits, snapshot

    @staticmethod
    def _softmax(x: np.ndarray) -> np.ndarray:
        x = x - x.max()
        e = np.exp(x)
        return e / e.sum()

    def _accept(self, logits: np.ndarray, proposals, dlogits, req: Request):
        """Decide the emitted tokens for one slot from its (k, V) target
        logits.  Returns (emit, j): ``j`` accepted drafts followed by one
        resampled/bonus token — a speculative step always emits j+1 ≥ 1.

        Greedy: accept while the target argmax agrees; the first
        disagreement emits the target argmax — the exact token vanilla
        decode would have produced (logits row i-1 is bit-identical to a
        vanilla step at that position).  Sampled: canonical rejection
        sampling — accept d w.p. min(1, p_t(d)/p_d(d)); on reject draw
        from the normalized residual max(0, p_t − p_d), which makes the
        emitted marginal exactly p_t regardless of the draft."""
        k = len(proposals) + 1
        emit: List[int] = []
        if req.temperature <= 0.0:
            for i in range(k - 1):
                t = int(np.argmax(logits[i]))
                emit.append(t)
                if t != proposals[i]:
                    return emit, i
            emit.append(int(np.argmax(logits[k - 1])))
            return emit, k - 1
        temp = req.temperature
        for i in range(k - 1):
            pt = self._softmax(logits[i] / temp)
            pd = self._softmax(dlogits[i] / temp)
            d = proposals[i]
            self._key, sub = jax.random.split(self._key)
            if float(jax.random.uniform(sub)) < min(
                    1.0, float(pt[d]) / max(float(pd[d]), 1e-30)):
                emit.append(d)
                continue
            res = np.maximum(pt - pd, 0.0)
            if res.sum() <= 0.0:
                res = pt
            self._key, sub = jax.random.split(self._key)
            emit.append(int(jax.random.categorical(
                sub, jnp.log(jnp.asarray(res / res.sum()) + 1e-30))))
            return emit, i
        self._key, sub = jax.random.split(self._key)
        emit.append(int(jax.random.categorical(
            sub, jnp.asarray(logits[k - 1]) / temp)))
        return emit, k - 1

    def _spec_step(self, decoding, k):
        """One draft-and-verify decode step over the decoding slots."""
        with tracing.current().span("draft"):
            proposals, dlogits, draft_snap = self._draft_propose(decoding, k)
        target_snap = self.cache
        tokens = np.zeros((self.slots, k), np.int32)
        pos = np.zeros(self.slots, np.int32)
        table = np.full((self.slots, self.sched.max_pages_per_seq), -1,
                        np.int32)
        rv = np.zeros(self.slots, bool)
        for s in decoding:
            req = self.slot_req[s]
            tokens[s, 0] = req.output[-1]   # last emitted, not yet cached
            tokens[s, 1:] = proposals[s]
            pos[s] = self.slot_pos[s]
            table[s] = self.sched.table_row(s)
            rv[s] = True
        batch = self._make_batch(
            tokens, pos=pos, table=table,
            row_valid=rv if self._stateful_rows else None)
        with tracing.current().span("verify"):
            logits, self.cache = self._verify(self.params, batch, self.cache)
            # ONE device->host transfer; copy only when poison may write.
            logits = np.asarray(jnp.asarray(logits, jnp.float32))  # (slots,k,V)
            if self.fault is not None:
                logits = np.array(logits)
        self.spec_k_hist[k] = self.spec_k_hist.get(k, 0) + 1
        if self.fault is not None:
            for s in decoding:
                val = self.fault.poison_value(self.step_idx,
                                              self.slot_req[s].rid)
                if val is not None:
                    logits[s] = val
        if self.quarantine:
            healthy = []
            for s in decoding:
                if np.isfinite(logits[s]).all():
                    healthy.append(s)
                else:
                    req = self.slot_req[s]
                    self._cancel_active(s, PoisonedOutput(
                        f"non-finite logits for rid={req.rid} at step "
                        f"{self.step_idx}", rid=req.rid))
            decoding = healthy
        drafted = accepted = emitted = 0
        partial: Dict[int, int] = {}      # slot -> accepted-prefix length
        draft_rollback: List[int] = []
        sample_span = tracing.current().span("sample")
        sample_span.__enter__()
        for s in decoding:
            req = self.slot_req[s]
            if req is None:
                continue
            emit, j = self._accept(logits[s], proposals[s], dlogits[s], req)
            drafted += k - 1
            accepted += j
            n_emit = 0
            for t in emit:
                req.output.append(int(t))
                self.slot_pos[s] += 1
                emitted += 1
                n_emit += 1
                # Same predicate _finished() applies below — checked
                # inline so the latency note lands BEFORE _record_done
                # pops this request's timing state.
                if (len(req.output) >= req.max_tokens
                        or (req.eos_id is not None
                            and int(t) == req.eos_id)):
                    break
            if n_emit:
                self._note_emitted(req, n_emit)
            done = self._finished(s)
            if not done and int(self.slot_pos[s]) >= self.cache_len:
                self._record_done(req)
                self.slot_req[s] = None
                self.slot_pos[s] = 0
                self.sched.release(s, finished=True)
                done = True
            if not done:
                # Spec emits host-side: refresh the device-carried
                # last-token array so a later k=1 async launch chains
                # from the token speculation actually emitted.
                self._last_tok = self._scatter_tok(
                    self._last_tok, jnp.int32(req.output[-1]), s)
            if done:
                self._draft_pos[s] = 0
                self._slot_window.pop(s, None)
            elif j == k - 1:
                # Full acceptance: every verified token was real, both
                # caches are exact.  The draft saw d_1..d_{k-2}, so it
                # sits k-2 past its catch-up point.
                self._draft_pos[s] += k - 2
            else:
                # Rejection at draft j+1: target pages past the accepted
                # point hold garbage the next window overwrites; only the
                # sequential (ring/recurrent) rows need the snapshot +
                # replay of the j+1 real tokens [e, d_1..d_j].
                partial[s] = j + 1
                draft_rollback.append(s)
        sample_span.__exit__(None, None, None)
        if draft_rollback and self._draft_stateful:
            self.draft_cache = self._merge_rows(self.draft_cache,
                                                draft_snap, draft_rollback)
        if partial and self._stateful_rows:
            self.cache = self._merge_rows(self.cache, target_snap,
                                          list(partial))
            self._replay(partial)
        self.sched.note_spec_step(len(decoding), drafted, accepted, emitted)

    def _merge_rows(self, cur, snap, rows):
        """Restore batch rows ``rows`` of every *batch-axis* cache leaf
        (ring/RG-LRU/SSD state) from ``snap``; paged slabs pass through
        untouched — their rollback is positional, not row-wise.  Grouped
        slabs carry the batch axis after the scan axis."""
        sel = np.zeros(self.slots, bool)
        sel[rows] = True
        sel = jnp.asarray(sel)

        def merge_layer(c_layer, s_layer, axis):
            if isinstance(c_layer, dict) and "k_pages" in c_layer:
                return c_layer

            def m(c, s):
                mask = sel.reshape((1,) * axis + (-1,)
                                   + (1,) * (c.ndim - axis - 1))
                return jnp.where(mask, s.astype(c.dtype), c)
            return jax.tree.map(m, c_layer, s_layer)

        groups = cur["groups"]
        if groups is not None:
            groups = tuple(merge_layer(c, s, 1)
                           for c, s in zip(cur["groups"], snap["groups"]))
        tail = [merge_layer(c, s, 0)
                for c, s in zip(cur["tail"], snap["tail"])]
        return {"groups": groups, "tail": tail}

    def _replay(self, partial: Dict[int, int]):
        """Re-run the accepted prefix of partially-accepted rows through
        the verify program (grouped by distinct prefix length, other rows
        masked) so ring/recurrent state lands exactly where sequential
        decode would have left it.  Paged rewrites are idempotent — same
        tokens, same positions, same quantization — so replay is safe to
        run over the shared pool."""
        for length in sorted(set(partial.values())):
            rows = [s for s, n_real in partial.items() if n_real == length]
            tokens = np.zeros((self.slots, length), np.int32)
            pos = np.zeros(self.slots, np.int32)
            table = np.full((self.slots, self.sched.max_pages_per_seq), -1,
                            np.int32)
            rv = np.zeros(self.slots, bool)
            for s in rows:
                out = self.slot_req[s].output
                tokens[s] = out[-(length + 1):-1]   # [e, d_1..d_j]
                pos[s] = int(self.slot_pos[s]) - length
                table[s] = self.sched.table_row(s)
                rv[s] = True
            batch = self._make_batch(tokens, pos=pos, table=table,
                                     row_valid=rv)
            _, self.cache = self._verify(self.params, batch, self.cache)

    def _warm_spec_program(self):
        """Compile the speculative step's GEMM pipeline — the draft's
        grouped q/k/v decode projection, the target's grouped verify
        projection (M = slots*k), and the verify unembedding — as ONE
        merged ``repro.graph`` program.  The scheduler sees the whole
        draft+verify pipeline in one graph (grouping and tile
        stabilization score across both models), and on the kernel
        backend the compile grants every node's plan up front, so the
        first real verify chunk lands on warm plans instead of solving
        them on the hot path."""
        from repro.graph import schedule as graph_schedule
        from repro.graph.trace import GraphBuilder, merge_graphs
        from repro.models.layers import model_format

        cfg, dcfg = self.cfg, self.draft_cfg
        cdt = str(jnp.dtype(cfg.compute_dtype))
        wdt = str(jnp.dtype(cfg.param_dtype))
        mv = self.slots * self.spec_k

        def build():
            graphs = []
            for m, c, tag in ((self.slots, dcfg, "draft"),
                              (mv, cfg, "verify")):
                fmt = model_format(c)
                nq = c.n_heads * c.hd
                nkv = c.n_kv_heads * c.hd
                b = GraphBuilder()
                xv = b.input((m, c.d_model), cdt, f"{tag}_x")
                wv = b.input((3, c.d_model, nq), wdt, f"{tag}_qkv")
                outs = b.group(xv, stacked=wv, widths=(nq, nkv, nkv),
                               fmt=fmt.name, out_dtype=cdt,
                               policy=c.gemm_policy)
                b.output(*outs)
                graphs.append(b.build())
            b = GraphBuilder()
            xv = b.input((mv, cfg.d_model), cdt, "verify_h")
            wv = b.input((cfg.d_model, cfg.vocab), wdt, "unembed")
            b.output(b.gemm(xv, wv, fmt=model_format(cfg).name,
                            out_dtype="float32", policy=cfg.gemm_policy))
            graphs.append(b.build())
            return merge_graphs(*graphs)

        key = ("spec_step", cfg.name, dcfg.name, self.slots, self.spec_k,
               cfg.format_policy, dcfg.format_policy, cdt, wdt,
               cfg.gemm_policy)
        self._spec_program = graph_schedule.compile_cached(
            key, build, backend=cfg.gemm_backend)

    # -- telemetry: per-request latency ----------------------------------------
    def _note_emitted(self, req: Request, n_new: int):
        """Latency bookkeeping at a host sync point: ``n_new`` tokens of
        ``req`` just became host-visible.  The first emission closes the
        TTFT window; later ones feed the inter-token histogram (a
        speculative step emitting n tokens contributes n samples of the
        per-token share of its step gap)."""
        if n_new <= 0:
            return
        rid = req.rid
        now = self._clock()
        reg = metrics_registry()
        if rid not in self._ts_first:
            self._ts_first[rid] = now
            sub = self._ts_submit.get(rid)
            if sub is not None:
                reg.histogram("serving.ttft_s").observe(now - sub)
            tr = tracing.active()
            if tr is not None:
                tr.instant("request.first_token", args={"rid": rid})
            n_new -= 1   # the first token closes TTFT, not an ITL gap
        last = self._ts_last.get(rid)
        if last is not None and n_new > 0:
            gap = (now - last) / n_new
            hist = reg.histogram("serving.inter_token_s")
            samples = self._itl.setdefault(rid, [])
            for _ in range(n_new):
                hist.observe(gap)
                samples.append(gap)
        self._ts_last[rid] = now

    def _request_metrics(self, rid: int, n_tokens: int) -> Dict[str, float]:
        """The latency summary attached to ``Response.metrics`` when a
        request ends (finish or cancel); pops the per-rid state."""
        m: Dict[str, float] = {"tokens": n_tokens}
        now = self._clock()
        sub = self._ts_submit.pop(rid, None)
        first = self._ts_first.pop(rid, None)
        self._ts_last.pop(rid, None)
        wait = self._queue_wait.pop(rid, None)
        itl = self._itl.pop(rid, None)
        if sub is not None:
            m["e2e_s"] = now - sub
            metrics_registry().histogram("serving.e2e_s").observe(
                m["e2e_s"])
        if wait is not None:
            m["queue_wait_s"] = wait
        if sub is not None and first is not None:
            m["ttft_s"] = first - sub
        if itl:
            itl = sorted(itl)
            m["itl_mean_s"] = sum(itl) / len(itl)
            m["itl_p50_s"] = itl[len(itl) // 2]
            m["itl_p99_s"] = itl[min(len(itl) - 1,
                                     int(round(0.99 * (len(itl) - 1))))]
        return m

    # -- request-level containment ---------------------------------------------
    def _record_done(self, req: Request, status: str = "ok",
                     error: Optional[RequestError] = None):
        req.done = True
        self.completed.append(req)
        self._responses[req.rid] = Response(
            req.output, rid=req.rid, status=status, error=error,
            metrics=self._request_metrics(req.rid, len(req.output)))

    def _cancel_active(self, slot: int, err: RequestError):
        """Cancel the request in ``slot``: free the slot and its pages
        (shared pages only decremented) and record the structured
        failure with whatever partial output exists.  The rest of the
        batch is untouched."""
        req = self.slot_req[slot]
        if req is None:
            return
        self.sched.cancel(slot)
        self._clear_slot(slot)
        req.done = True
        self._deadline_at.pop(req.rid, None)
        self._responses[req.rid] = Response(
            req.output, rid=req.rid, status=err.code, error=err,
            metrics=self._request_metrics(req.rid, len(req.output)))

    def _cancel_waiting(self, entry, err: RequestError):
        """Cancel a request still in the queue (never admitted)."""
        self.sched.cancel_waiting(entry)
        req = entry.req
        req.done = True
        self._deadline_at.pop(req.rid, None)
        self._responses[req.rid] = Response(
            req.output, rid=req.rid, status=err.code, error=err,
            metrics=self._request_metrics(req.rid, len(req.output)))

    def _enforce_deadlines(self):
        """Cancel every request (active or waiting) whose absolute
        deadline has passed — partial output is returned with status
        ``"deadline"`` and the freed capacity goes to the live batch."""
        if not self._deadline_at:
            return
        now = self._clock()
        if self._inflight and any(dl <= now
                                  for dl in self._deadline_at.values()):
            # Deadline boundary: the cancelled Response snapshots
            # req.output — deliver in-flight tokens first so the partial
            # output is complete up to the cancel point.
            self._flush_pipeline()
        for slot, req in enumerate(self.slot_req):
            if (req is not None
                    and self._deadline_at.get(req.rid, now + 1) <= now):
                self._cancel_active(slot, DeadlineExceeded(
                    f"rid={req.rid} missed its deadline after "
                    f"{len(req.output)} tokens", rid=req.rid))
        for entry in list(self.sched.waiting):
            if self._deadline_at.get(entry.rid, now + 1) <= now:
                self._cancel_waiting(entry, DeadlineExceeded(
                    f"rid={entry.rid} missed its deadline in queue",
                    rid=entry.rid))

    # -- crash recovery --------------------------------------------------------
    def _geometry(self) -> Dict[str, object]:
        return {"arch": self.cfg.name, "slots": self.slots,
                "cache_len": self.cache_len,
                "prefill_len": self.prefill_len,
                "page_size": self.page_size,
                "num_pages": self.sched.pool.num_pages,
                "kv_format": self.cfg.kv_cache_format}

    def snapshot(self) -> Dict[str, object]:
        """Host-side state for crash recovery: every live request (in
        arrival order, with its partial output), the published page
        registrations, finished responses, and the engine geometry.
        Pure metadata — no device arrays; pair it with ``self.cache`` if
        the restore should re-attach the surviving KV."""
        with tracing.current().span("snapshot"):
            # Snapshot boundary: the snapshot must capture every token
            # the device already produced (PR-6 invariant — restore
            # replays from host state only).
            self._flush_pipeline()
            return self._snapshot()

    def _snapshot(self) -> Dict[str, object]:
        now = self._clock()
        entries = sorted(
            list(self.sched.active.values()) + list(self.sched.waiting),
            key=lambda e: e.arrival)
        reqs = []
        for entry in entries:
            req = entry.req
            dl = self._deadline_at.get(req.rid)
            reqs.append({
                "rid": req.rid,
                "prompt": np.asarray(req.prompt, np.int32).tolist(),
                "output": list(req.output),
                "max_tokens": req.max_tokens,
                "temperature": req.temperature,
                "eos_id": req.eos_id,
                "format_policy": req.format_policy,
                "deadline": req.deadline,
                "deadline_remaining_ms": (
                    None if dl is None
                    else max(0.0, (dl - now) * 1000.0)),
            })
        return {
            "version": 1,
            "geometry": self._geometry(),
            "requests": reqs,
            "published": self.sched.pool.registrations(),
            "responses": {int(rid): {"tokens": list(r), "status": r.status}
                          for rid, r in self._responses.items()},
        }

    def restore(self, snap: Dict[str, object], *, cache=None):
        """Rebuild a freshly-constructed engine from a :meth:`snapshot`.

        Finished responses are carried over; live requests are
        re-submitted in arrival order (bypassing load shedding — they
        were already admitted once) and re-enter through normal
        admission, which re-prefills each request's prompt + generated
        prefix window.  With ``cache`` (the dying engine's device cache),
        the snapshot's page registrations are restored into the fresh
        pool first, so re-admission aliases the published KV through the
        prefix cache instead of recomputing it.
        """
        with tracing.current().span("restore"):
            return self._restore(snap, cache=cache)

    def _restore(self, snap: Dict[str, object], *, cache=None):
        geo = snap.get("geometry")
        if geo != self._geometry():
            raise ValueError(f"snapshot geometry {geo} does not match "
                             f"this engine {self._geometry()}")
        if cache is not None:
            self.cache = cache
            self.sched.pool.restore_registrations(
                snap.get("published", ()))
        for rid, rd in snap.get("responses", {}).items():
            self._responses[int(rid)] = Response(
                rd["tokens"], rid=int(rid), status=rd["status"])
        for rd in snap.get("requests", ()):
            req = Request(
                rid=rd["rid"],
                prompt=np.asarray(rd["prompt"], np.int32),
                max_tokens=rd["max_tokens"],
                temperature=rd["temperature"],
                eos_id=rd["eos_id"],
                format_policy=rd["format_policy"],
                deadline=rd["deadline"],
                output=list(rd["output"]))
            self.sched.submit(req)  # direct: re-admission is never shed
            rem = rd.get("deadline_remaining_ms")
            if rem is not None:
                self._deadline_at[req.rid] = self._clock() + rem / 1000.0
        return self

    # -- helpers ---------------------------------------------------------------
    def _clear_slot(self, slot: int):
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        self._prefilling.pop(slot, None)
        self._draft_pos[slot] = 0
        self._slot_window.pop(slot, None)

    def _cow_guard(self, slot: int, n_tokens: int = 1):
        """Copy-on-write: decode is about to write ``slot``'s next
        ``n_tokens`` tokens into the logical pages covering
        [pos, pos + n_tokens) — any shared (refcount > 1) physical page
        in that range is re-owned onto a fresh page with its device-side
        content copied first.  Structurally unreachable under the
        chunk-aligned aliasing cap (shared pages always precede the
        recompute window, decode writes always follow it), but enforced
        rather than assumed."""
        entry = self.sched.active.get(slot)
        if entry is None:
            return
        pos = int(self.slot_pos[slot])
        first = pos // self.page_size
        last = (pos + n_tokens - 1) // self.page_size
        for idx in range(first, last + 1):
            pages = self.sched.pool.pages_of(entry.arrival)
            if idx >= len(pages) or self.sched.pool.ref_of(pages[idx]) <= 1:
                continue
            old, new = self.sched.pool.make_private(entry.arrival, idx)
            self._copy_page(old, new)

    def _copy_page(self, old: int, new: int):
        """Duplicate one physical page's content across every paged
        layer slab (grouped slabs carry the page axis after the group
        axis)."""
        def cp(layer, grouped):
            if not (isinstance(layer, dict) and "k_pages" in layer):
                return layer
            out = dict(layer)
            for name, leaf in layer.items():
                out[name] = (leaf.at[:, new].set(leaf[:, old]) if grouped
                             else leaf.at[new].set(leaf[old]))
            return out

        groups = self.cache["groups"]
        if groups is not None:
            groups = tuple(cp(layer, True) for layer in groups)
        tail = [cp(layer, False) for layer in self.cache["tail"]]
        self.cache = {"groups": groups, "tail": tail}

    def _sample(self, logits, req: Request):
        if req.temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, axis=-1))
        self._key, sub = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(
            sub, logits / req.temperature, axis=-1))

    def _finished(self, slot: int) -> bool:
        req = self.slot_req[slot]
        if req is None:
            return True
        hit_eos = req.eos_id is not None and req.output[-1] == req.eos_id
        if len(req.output) >= req.max_tokens or hit_eos:
            self._record_done(req)
            self._deadline_at.pop(req.rid, None)
            self.slot_req[slot] = None
            self.slot_pos[slot] = 0
            self.sched.release(slot, finished=True)
            return True
        return False
