"""repro.serving — the continuous-batching serving subsystem.

Three layers, policy separated from mechanism:

- :mod:`repro.serving.kv_cache` — :class:`KVPagePool`, the paged KV-cache
  allocator: fixed-size pages from a shared free list, per-request growth
  with no recompaction, physical page 0 reserved as the null page.
  Pages are *refcounted* and *content-addressed*
  (:func:`~repro.serving.kv_cache.page_prefix_hashes`): requests sharing
  a page-aligned prompt prefix alias the same physical pages, eviction
  decrements shared pages instead of freeing them, ref-0 pages keep
  their content on an LRU cached-free list until reclaimed, and
  ``make_private`` is the copy-on-write escape hatch.  Pure host-side
  bookkeeping; the device-side page arrays live in the model cache
  (``models.model.init_paged_cache``) and are quantized under a
  ``FormatPolicy`` (``int8pt`` per-tensor-scale int8 is the quantized
  default).
- :mod:`repro.serving.scheduler` — :class:`ContinuousBatchingScheduler`,
  the admit → prefill → decode → evict policy loop: strict-FIFO admission
  by arrival stamp (starvation-free; preempted requests keep their
  stamp), prefix-cached admission (alias the longest cached chunk-aligned
  prefix, recompute only the suffix), token-budget admission control,
  youngest-first eviction when the pool runs dry, occupancy/throughput/
  hit-rate metrics.  Subclass its ``_pick_admit`` / ``_pick_victim`` /
  ``prefill_chunk_quota`` hooks to add a scheduling policy.
- :mod:`repro.serving.engine` — :class:`ServingEngine`, the model-side
  executor: chunked prefill (fixed-size prompt chunks written straight
  into pool pages, jitted once per (format, chunk index), interleaved
  with decode steps so long prompts never stall in-flight decodes), one
  batched decode over fixed slots reading KV through the page table (the
  page-table-indexed flash-decode kernel on the pallas backend), grouped
  decode-GEMV projections (one plan-cache signature per step), GEMM
  plan-cache warm start/save.

Client API: ``engine.submit(Request(...)); engine.run()`` — see
``examples/serving_continuous.py``.

Async pipelined step
--------------------

The run loop is asynchronous by default (``async_steps=True``,
``pipeline_depth=2``; ``--no-async`` from the launcher).  Sampling
happens *inside* the jitted decode program (``models.decode_and_sample``
— greedy argmax + keyed categorical per row), the KV cache argument is
donated so steps chain without copies, and the sampled token feeds the
next launch as a carried device array.  Each step runs its host
scheduling work (deadlines, admission, prefill chunks) while the
previous step's decode is still on device, then retires that step — the
ONE intentional blocking ``device_get`` per step — re-admits into any
slot the delivery freed, and launches its own decode.  Delivery
therefore lags launch by one step (``scheduler.delivery_lag_mean``
and a staleness note in ``telemetry.export.health()`` make the lag
observable).  The pipeline flushes wherever host-visible output state
is read or rewritten: sequence horizon, speculation, imminent eviction,
due deadlines, ``snapshot()``, ``run()`` exit — and an armed
``FaultInjector`` pins the effective depth to 1.  Greedy outputs are
bit-identical with async on or off (both modes execute the same jitted
program; only delivery timing differs — test-asserted across archs,
speculation and mid-run eviction).

Speculative decoding
--------------------

``ServingEngine(spec_k=k)`` (k >= 2) replaces the M=1 decode GEMV with a
draft-and-verify step — the tall/skinny regime the source paper's
flexible tiles are built for.  Anatomy of one step:

- **draft k-1**: a small draft model — by default the target's first
  scan group(s), weight-shared via ``models.draft_from`` (zero extra
  parameter memory), optionally a separate ``draft_config`` under its
  own ``FormatPolicy`` (e.g. an int8 draft under a bf16 target) —
  catches up on the slot's known tokens and proposes ``k-1`` tokens
  autoregressively against its own slot-private paged KV.
- **verify chunk**: the target scores the whole window
  ``[last_emitted, d_1..d_{k-1}]`` in ONE ``models.verify_chunk`` call
  over the shared paged pool — the same arbitrary-window machinery as a
  prefill chunk, so its GEMMs carry ``M = slots*k`` rows and land on
  the plan-cache signature family prefill already warmed.  The merged
  draft+verify GEMM pipeline is compiled as one ``repro.graph`` program
  at engine construction.
- **accept / rewind**: greedy acceptance keeps proposals while the
  target argmax agrees (output **bit-identical** to vanilla decode);
  sampled requests run canonical rejection sampling (accept w.p.
  ``min(1, p_t/p_d)``, resample the residual on reject), preserving the
  target distribution exactly.  Rejected tokens *rewind*: page-table
  positions move back, no pages are freed — garbage KV past the
  accepted point is overwritten by the next window (ring/recurrent rows
  restore their pre-verify state and replay the accepted prefix).
- **budget accounting**: a speculative step commits up to ``k-1`` extra
  page slots per sequence before acceptance is known, so depth is load
  traffic: ``scheduler.spec_k(n_decoding)`` (a policy hook) plus
  per-slot page/horizon clamps shrink k under pressure — a full pool
  degrades to k=1 (exactly vanilla decode) instead of evicting anyone.
  ``note_spec_step`` feeds ``accepted_per_step`` / ``acceptance_rate``
  into ``metrics()``.

Failure model
-------------

Requests fail *individually*; the engine fails *recoverably* — the
contract :mod:`repro.serving.resilience` implements:

- every way a request can end abnormally has a name in the error
  taxonomy (:class:`~repro.serving.resilience.RequestError` subclasses:
  ``DeadlineExceeded``, ``Shed``, ``PoisonedOutput``,
  ``CapacityExceeded``), and ``run()`` returns a
  :class:`~repro.serving.resilience.Response` per request — the token
  list (a ``list`` subclass, so legacy consumers are unchanged) plus a
  structured ``status``/``error``.
- **containment**: NaN/inf logits quarantine only the poisoned slot;
  per-request deadlines cancel only the expired request (slot + pages
  freed, partial output returned); load shedding rejects at ``submit``
  (queue-depth / committed-token watermark) instead of growing the
  queue without bound.  Because fp32 decode rows are independent, every
  unaffected request completes bit-identical to a fault-free run.
- **recovery**: ``ServingEngine.snapshot()/restore()`` capture the
  host-side state (requests, outputs, deadlines, published page
  hashes); :func:`~repro.serving.resilience.serve_with_recovery` wraps
  a crash or watchdog-detected straggler in
  ``repro.distributed.fault.supervise`` and re-admits in-flight work
  through the prefix-cache re-attachment path.
- **verification**: :meth:`KVPagePool.audit` checks the pool's
  conservation invariants (free/cached-free/owned partition, refcount
  conservation, hash-index bijection); the engine's ``debug_audit``
  flag runs it after every step, and the seeded
  :class:`~repro.serving.resilience.FaultInjector` makes chaos tests
  deterministic (same plan → same firings → same outputs).

Telemetry
---------

The stack is instrumented through :mod:`repro.telemetry`; observation
never changes behavior (greedy outputs are bit-identical with telemetry
on or off, test-asserted):

- **metrics** land in the process-global registry under dotted
  ``subsystem.metric[_unit]`` names: ``serving.ttft_s`` /
  ``serving.inter_token_s`` / ``serving.queue_wait_s`` /
  ``serving.e2e_s`` histograms observed at host sync points only (a
  clock read never sits inside jitted code), every ``metrics()`` number
  mirrored as a ``serving.*`` gauge via ``telemetry.registry.publish``,
  and the planner/compiler hit rates (``plan_cache_hits``,
  ``graph_program_hits``, …) surfaced alongside.  Each finished or
  cancelled request carries its own latency summary in
  ``Response.metrics`` (``ttft_s``, ``itl_p50_s``, ``queue_wait_s``,
  ``e2e_s``, …).
- **spans**: wrap a new engine-loop phase with
  ``with tracing.current().span("phase"):`` — when no tracer is
  installed this is the allocation-free no-op singleton, so
  instrumentation costs nothing; never place a span inside a jitted
  function (it would time jax tracing, not execution).  An installed
  tracer's spans are also ``jax.profiler`` annotations, so a profile
  shows the step's host phases (``step``, ``admit``, ``prefill_chunk``,
  ``device_wait``, ``sample``, ``evict``, ``decode_launch``,
  ``observe``) on the device's clock beside the ``jit_serve_*``
  programs.  Request
  lifecycle instants flow through the scheduler's ``_note_event`` choke
  point; fault firings emit ``fault.*`` instants.
  ``launch/serve.py --trace PATH`` exports Chrome/Perfetto
  ``trace_event`` JSON (open in ``ui.perfetto.dev``); the trace file is
  ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` with phase-``X``
  complete events (integer-µs ``ts``/``dur``) and phase-``i`` instants.
- **per-GEMM accounting**: ``telemetry.gemm_account.account_gemms()``
  (or ``serve.py --gemm-table``) records every distinct compiled GEMM
  dispatch with its shape class, format and plan provenance — the
  paper's Fig. 7 traffic axis, live.
"""
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import AuditError, KVPagePool
from repro.serving.resilience import (CapacityExceeded, DeadlineExceeded,
                                      EngineCrash, Fault, FaultInjector,
                                      PoisonedOutput, RequestError, Response,
                                      Shed, serve_with_recovery)
from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                     DeadlineScheduler)

__all__ = ["Request", "ServingEngine", "KVPagePool", "AuditError",
           "ContinuousBatchingScheduler", "DeadlineScheduler",
           "RequestError", "DeadlineExceeded", "Shed", "PoisonedOutput",
           "CapacityExceeded", "EngineCrash", "Response", "Fault",
           "FaultInjector", "serve_with_recovery"]
