"""``repro.serving`` — Serving API v1: the stable request/response contract.

Like ``repro.artifacts`` freezes the artifact manifest schema, this package
docstring freezes the serving surface every later layer (HTTP frontend,
sharded serving, TPU deployment) builds against. The contract, v1:

Submission
----------
``engine.submit(prompt: list[int], params: SamplingParams = SamplingParams())
-> RequestHandle``. ``SamplingParams`` is frozen: ``max_new_tokens``,
``temperature`` (0 → greedy), ``top_k`` (0 → off), ``top_p`` (1.0 → off),
``seed`` (the request's private RNG stream), ``stop`` (a set of token ids
that terminate generation, honored in addition to the engine-wide
``EngineConfig.eos_id``; the stop token is the last token of the output),
``deadline_s`` / ``ttft_deadline_s`` (wall budgets measured from submit;
None → none).

Deadlines (v1.1)
----------------
The engine sweeps expirations at the start of every ``step()``: a request
past ``deadline_s`` — or past ``ttft_deadline_s`` with no first token yet —
retires with frozen ``finish_reason`` ``"timeout"``, wherever it is
(queued, mid-prefill, or mid-decode), keeping the tokens it already
produced. The freed slot is reusable at that same step's admission, and
co-batched survivors are bit-unperturbed (the cancellation guarantee,
extended to every retirement path).

Admission control (v1.1)
------------------------
``EngineConfig.max_queue`` caps waiting requests and
``EngineConfig.max_resident_tokens`` caps the committed token footprint
(clipped prompt + generation budget) over queued + resident work. A submit
that would exceed a cap is **shed** under ``admission_policy="reject"`` —
the handle returns already finished with reason ``"rejected"`` and a
human-readable ``error`` — or, under ``"block"``, drives ``step()`` until
the fleet drains enough to accept (a request too large to *ever* fit is
rejected regardless). Overload therefore degrades to fast rejections or
progress-coupled blocking, never unbounded queue growth.

Fault containment (v1.1)
------------------------
Non-finite logits detected for a row (checked on device every decode step
and at prefill completion) and device dispatch exceptions retire the
offending request with frozen reason ``"error"`` (detail in ``.error``) and
quarantine its slot out of the admission pool (``engine.rehabilitate()``
row-resets and restores quarantined slots); the engine keeps stepping and
co-batched survivors are bit-unperturbed. ``engine.health()`` returns a
``repro.runtime.monitor.HealthSnapshot`` (queue depth, occupancy,
quarantined slots, shed/timeout/error counters). The deterministic
fault-injection harness in ``repro.serving.faults`` (``FaultPlan`` /
``FaultInjector`` / ``VirtualClock``) schedules all of the above
repeatably; ``ServingEngine(..., injector=None)`` — the production default
— compiles every injection input out.

The full frozen ``finish_reason`` set (``api.FINISH_REASONS``):
``"stop" | "length" | "cancelled" | "timeout" | "rejected" | "error"``.

Paged KV cache (v1.2)
---------------------
``EngineConfig.kv_layout="paged"`` virtualizes every slot's KV ring into
``page_size``-token physical pages drawn from one pool of ``max_pages``
pages shared by the whole fleet (default: exactly the ring footprint,
``max_slots · capacity/page_size``; set lower to overcommit). Semantics:

* **Paged semantics.** A slot's logical ring is unchanged — same
  capacity, same sliding-window/wrap masking, same int8 quantization —
  only its storage is indirected through a per-slot page table
  (``repro.kernels.chunk_attention.chunk_attention_paged``). ``"ring"``
  remains the default layout and the bit-identity oracle.
* **COW prefix sharing** (``EngineConfig.prefix_cache``, default on).
  Fully prompt-filled pages are published under their *exact* prompt-
  prefix token tuple (never a hash — a collision would splice one
  request's KV into another). A later request adopts the longest cached
  run read-only and those tokens skip prefill entirely (lower TTFT); any
  write to a shared page forks it first, so readers keep bit-identical
  history. Reuse auto-disables for models with recurrent mixers (their
  state cannot skip tokens) and for truncated prompts.
* **Determinism guarantee.** A request's output remains a pure function
  of (params, prompt, ``SamplingParams``) — bit-identical whether its
  prefix was shared or recomputed, and identical to the ``"ring"``
  layout. (The skipped-prefix length is trimmed to a ``prefill_chunk``
  multiple so warm runs replay the cold run's dispatch sequence.)
* **Page-budget admission rule.** Admission reserves a request's
  worst-case page need up front — ``min(ceil((clipped_prompt +
  max_new_tokens)/page_size), capacity/page_size)`` pages, counting COW
  fork targets for wrap-bound requests — composing with ``max_queue`` /
  ``max_resident_tokens``: the queue head waits (strict FIFO) until the
  pool can cover it, a request whose worst case exceeds the whole pool
  sheds at submit, and every retirement path (finish, cancel, timeout,
  error) returns its pages. Under pool pressure, unreferenced prefix-
  cache pages evict LRU-first.

``engine.health()`` gains page-pool gauges (``pages_free/used/shared``,
``prefix_hits/misses/evictions``) and ``engine.memory_stats()`` reports
``kv_resident_bytes`` — bytes of *used* pages, the requests-per-GB number
— under paging.

Observability (v1.3)
--------------------
Every engine carries an ``Observability`` bundle (``engine.obs``; pass
``observability=`` to share one across boot + engine, or leave it unset —
a default bundle with tracing off is always attached). Its parts:

* **Metrics registry** (``engine.obs.registry``, a ``MetricsRegistry``).
  The metric *names, kinds, and units* in
  ``observability.SERVING_METRICS`` are frozen exactly like
  ``FINISH_REASONS`` — scrape pipelines and dashboards may depend on
  them. Counters are monotone for the engine's lifetime; gauges describe
  the instant of the read; histograms expose Prometheus cumulative
  buckets plus exact windowed percentiles (``percentile(q)`` over the
  last 4096 observations). Export as Prometheus text
  (``registry.render_prometheus()``), a JSONL snapshot line
  (``registry.jsonl_line()``), or an aligned summary table. The page-pool
  metrics register only under ``kv_layout="paged"``.
  ``engine.health()`` is now *derived from* the registry — a snapshot
  and a scrape can never disagree.
* **Lifecycle + step tracing** (``engine.obs.trace``, a bounded-ring
  ``TraceRecorder``; ``Observability(trace=True)`` enables it, default
  off). Each request emits spans submitted → queued → admitted →
  prefill chunks → first token → decode → retired on its own track
  (annotated with slot, pages, and ``finish_reason``); each engine step
  emits phase spans (sweep, admit, prefill dispatch/sync, sample
  collect, decode dispatch/sync, collect, page maintenance); artifact
  boot phases land on a "boot" track. ``trace.write(path)`` emits
  Chrome/Perfetto ``trace.json``. When the ring overflows, the *oldest*
  events drop and ``serving_trace_dropped_total`` counts them.
* **Clock injection.** All engine timestamps flow through one injectable
  clock (``repro.runtime.clock``; ``faults.VirtualClock`` duck-types
  it), so a seeded ``FaultPlan`` run produces a fully deterministic
  trace whose span durations reconcile *exactly* with
  ``RequestResult.t_submit/t_first/t_done`` and the histogram
  percentiles. Direct wall-clock calls are banned from the serving and
  model layers by a static guard test.
* **Zero perturbation** (the testable guarantee, like determinism): a
  request's tokens are bit-identical with tracing on, off, or the
  bundle left unconfigured. Instrumentation is host-side only and never
  adds a compile-cache axis; what tracing on costs end to end is read
  on the chip (PERF.md, "Findings").

``RequestResult`` additionally carries ``t_admit`` and the derived
``queue_wait`` (0.0 for never-admitted requests); heartbeat payloads are
now versioned (``runtime.monitor.HEARTBEAT_SCHEMA``) and
``HealthSnapshot.beat(..., metrics=engine.obs.digest())`` folds a metrics
digest into the heartbeat file a ``StragglerDetector`` reads.

Concurrent frontend (v1.4)
--------------------------
``repro.serving.frontend`` is the concurrent serving surface; the
engines themselves stay single-threaded and the cooperative style below
remains the in-process baseline (and the bit-identity oracle).

* **Driver threading rules.** ``EngineDriver(engine).start()`` spawns
  the one thread that owns the device: after ``start()``, no other
  thread may call any engine method. Clients use the driver's
  thread-safe ``submit(prompt, params, tenant=...)`` / ``cancel`` and
  the returned ``DriverHandle`` — same reading surface as
  ``RequestHandle`` but passive: ``tokens()`` reads a per-request queue
  fed in the engine step that produced each token (stream TTFT is
  engine TTFT), ``result()`` waits instead of stepping, and
  ``subscribe(fn)`` replays history then attaches (no token can be
  lost to the submit/attach race). Engine reads while the driver runs
  go through ``driver.call(fn)``, which executes ``fn(engine)`` on the
  driver thread between steps. ``drain()`` stops intake (waiting
  requests shed ``"rejected"``; offered work finishes or deadlines
  out); ``close()`` cancels the rest and joins. Determinism is
  unchanged — outputs through the driver are bit-identical to
  cooperative ``submit()``, any thread interleaving.
* **The tenant field.** ``SamplingParams.tenant`` (default ``""``) is a
  scheduling identity, not a sampling input: the determinism contract
  is over (params, prompt, the sampling fields) and ignores it. The
  driver's ``FairScheduler`` holds accepted requests in per-tenant
  queues under deficit-weighted round-robin (quantum/weights in
  committed tokens — the v1.1 unit) and offers the engine at most its
  free admissible slots, so DRR order *is* admission order while the
  engine's internal FIFO (and the v1.1/v1.2 caps and page budgets,
  which still apply to every offer) stays shallow. Per-tenant
  ``tenant_max_resident_tokens`` caps a tenant's committed tokens in
  the engine; a capped tenant skips its turn without banking deficit,
  so a flooding tenant bounds no one's admission latency but its own.
* **HTTP status mapping.** The asyncio frontend (``HttpServer``;
  ``serve.py --http HOST:PORT``) maps terminal outcomes known before
  the response body starts: ``"rejected"`` → 429 with ``Retry-After``,
  ``"timeout"`` → 504, ``"error"`` → 500; malformed input → 400. Every
  ``/v1/completions`` response carries ``X-Request-Id: <uid>`` (the id
  trace spans are annotated with). ``POST /v1/completions`` with
  ``"stream": true`` is SSE — one ``data:`` event per token, a
  terminal result event, ``data: [DONE]``; client disconnect cancels
  the request. ``GET /healthz`` is the ``HealthSnapshot`` as JSON;
  ``GET /metrics`` is ``render_prometheus()`` (plus frontend-only
  additions ``serving_frontend_shed_total`` /
  ``serving_frontend_queue_depth``, registered when a driver starts).
  Once streaming has begun the status is committed; late outcomes
  arrive in the terminal SSE event instead.

Supervised recovery (v1.5)
--------------------------
``EngineSupervisor`` (``repro.serving.frontend.supervisor``; ``serve.py
--supervise``) wraps the driver lifecycle so engine *death* — an
exception escaping ``engine.step()`` (``EngineCrash`` from the fault
plan's ``engine_crash``, or any real crash) or a hung step flagged by
the watchdog (``step_age() > watchdog_step_timeout_s``, read off the
injectable clock) — becomes a recovery, not a fleet-wide ``"error"``:

* **Engine generations.** The supervisor owns an engine *factory*
  (rebuild from the memmap artifact or in-process quantization). Each
  rebuild gets a fresh engine, driver, and registry under a new integer
  generation id (gauge ``serving_engine_generation``; heartbeats carry
  ``engine_generation`` / ``engine_restarts`` under HEARTBEAT_SCHEMA 3).
* **Replay guarantee.** Every non-retired request is re-queued on the
  new generation, keeping its uid, handle, subscribers, and original
  timestamps. The determinism contract (output is a pure function of
  (params, prompt, SamplingParams)) means replay regenerates the same
  stream from token 0; the handle's delivered-token cursor skips the
  already-streamed prefix, so an SSE client sees its stream continue
  with **no duplicated and no dropped token** and a final result
  bit-identical to a crash-free run.
* **Suspects and the blacklist.** The request mid-dispatch at the crash
  is the suspect. A single-attributed suspect retires ``"error"``
  exactly once (crash detail in ``.error`` and the HTTP 500 body) and
  never replays; an ambiguous multi-row crash replays everyone but
  counts strikes, and a repeat offender is blacklisted — a poison
  request cannot crash-loop the fleet.
* **Degraded mode.** Exponential backoff between restarts; ≥
  ``max_restarts`` crashes inside ``crash_window_s`` open the circuit
  breaker: new submits shed with HTTP **503 + Retry-After**
  (``DegradedError``) while replayable work finishes, and a crash-free
  window closes the breaker. ``GET /healthz`` carries the supervisor
  block (generation, restarts, degraded, blacklist).
* **Unchanged surface.** ``FINISH_REASONS`` is untouched — recovery
  introduces no new terminal state (crash victims that cannot replay
  retire with the existing ``"error"``), and the supervisor duck-types
  the driver's client surface, so every v1.4 rule above applies
  verbatim under supervision.

Observability on the profiler's clock (v1.6)
--------------------------------------------
* **The engine thread is tiled by spans.** Under ``EngineDriver`` the
  driver loop's phases (``driver_loop`` around one pass, the step
  included; ``driver_lock``, ``driver_calls``, ``driver_offer``,
  ``driver_pump``, ``driver_idle`` inside it) and the step's
  (``decode_prepare`` and ``prefill_prepare`` besides the v1.3 phases)
  leave no part of the thread without a span; each has its
  ``serving_phase_<name>_seconds_total`` counter.
* **Profiling a live server.** While tracing is on, every span is also
  a ``jax.profiler.TraceAnnotation`` of the same name and each step a
  ``StepTraceAnnotation("step", step_num=...)``. Serve with tracing on
  (``Observability(trace=True)``; ``serve.py --trace-out``) and capture
  a profile — ``jax.profiler.start_trace``/``stop_trace``, or
  ``jax.profiler.start_server(port)`` and TensorBoard's profiler — and
  the engine's phases appear on the host thread beside the device's
  ops. With tracing off no annotation object is made.
* **The frontend's wait.** ``DriverHandle.t_offer`` stamps the moment
  the fair queue hands a request to the engine; the histogram
  ``serving_frontend_queue_wait_seconds`` observes ``t_offer −
  t_submit``, and while tracing a ``frontend_queued`` span covers it on
  the request's track (ending at retirement, with ``finish_reason``, for
  a request shed or deadlined in the frontend). With the engine's
  ``queued`` (engine submit → admit) and ``prefill`` (admit → first
  token) spans, a request's time to first token splits into three
  measured parts.
* **Compiles.** ``serving_compiles_total``,
  ``serving_compile_seconds_total`` and
  ``serving_compile_cache_hits_total`` count the process's XLA backend
  compiles, their seconds and persistent-cache hits (one process-wide
  ``jax.monitoring`` listener; a cache hit counts as a compile with its
  read's duration); a compile that finishes on the driver thread is a
  ``compile`` span on the engine track while tracing.

Consumption
-----------
``RequestHandle.tokens()`` — a generator yielding each generated token in
the engine step that produced it (it drives ``engine.step()`` on demand, so
the first yield lands in the same step the prompt's prefill completes:
stream TTFT **is** engine TTFT). ``RequestHandle.result()`` — block until
finished, returning an immutable ``RequestResult`` (tokens, a
``finish_reason`` from ``FINISH_REASONS``, ``truncated``, ``error`` detail
for contained faults/sheds, and the timing triplet
``t_submit / t_first / t_done``). ``RequestHandle.cancel()`` — a queued
request never admits; a resident one frees its slot immediately
(mid-prefill or mid-decode) without perturbing co-resident requests.
Batch callers may instead drive ``engine.step()`` / ``engine.run()``
themselves and read the same handles afterwards — both styles compose.

Determinism (the testable guarantee)
------------------------------------
A request's output is a pure function of (model params, prompt,
``SamplingParams``). Every random draw comes from the request's own stream
— token i uses ``fold_in(PRNGKey(params.seed), i)``, evaluated on device
inside the fused decode scan — never from engine-global state. Output is
therefore bit-identical whether the request runs alone, co-batched with
arbitrary traffic, on ``ServingEngine`` or ``SerialAdmitEngine``, or across
any decode/prefill chunking. Temperature 0 is pure argmax (no RNG at all)
and matches the teacher-forced ``forward`` argmax path.

Engines
-------
``ServingEngine`` — bucketed batched admission + chunked prefill
interleaved with the fused multi-step decode loop (the production
scheduler). ``SerialAdmitEngine`` — the PR-1 one-prompt-at-a-time
admission baseline. Both implement the identical v1 contract, which is
what makes the determinism guarantee scheduler-independent.
"""

from repro.runtime.monitor import HealthSnapshot
from repro.serving.api import (FINISH_REASONS, RequestHandle, RequestResult,
                               SamplingParams)
from repro.serving.engine import (EngineConfig, EngineCrash, EngineFault,
                                  SerialAdmitEngine, ServingEngine)
from repro.serving.faults import FaultInjector, FaultPlan, VirtualClock
from repro.serving.frontend import (DegradedError, DriverHandle, EngineDriver,
                                    EngineSupervisor, FairScheduler,
                                    HttpServer, ThreadedHttpServer)
from repro.serving.observability import (SERVING_METRICS, MetricsRegistry,
                                         Observability, TraceRecorder)
from repro.serving.paging import PageAllocator
from repro.serving.sampling import (request_keys, sample_token, sample_tokens,
                                    sample_tokens_per_request,
                                    top_k_top_p_mask)

__all__ = [
    "SamplingParams", "RequestHandle", "RequestResult", "FINISH_REASONS",
    "ServingEngine", "SerialAdmitEngine", "EngineConfig", "EngineFault",
    "EngineCrash",
    "FaultPlan", "FaultInjector", "VirtualClock", "HealthSnapshot",
    "PageAllocator",
    "EngineDriver", "DriverHandle", "FairScheduler", "HttpServer",
    "ThreadedHttpServer", "EngineSupervisor", "DegradedError",
    "Observability", "MetricsRegistry", "TraceRecorder", "SERVING_METRICS",
    "sample_token", "sample_tokens", "sample_tokens_per_request",
    "request_keys", "top_k_top_p_mask",
]
