"""Serving launcher: quantize with PTQTP (or boot a prebuilt artifact), then
serve batched requests through the v1 request API.

``python -m repro.launch.serve --arch qwen2-1.5b --requests 8``
``python -m repro.launch.serve --artifact artifacts/qwen --temperature 0.8``

Pipeline: init (or load) weights → PTQTP-quantize every linear (the paper's
single-pass, calibration-free recipe) → continuous-batching engine drives
bucketed/chunked prefill + fused decode with the multiplication-free ternary
representation. Requests go through ``submit(prompt, SamplingParams(...))``
→ ``RequestHandle`` (the Serving API v1 surface — per-request seed, top-k/
top-p, stop ids, streaming, cancellation); ``--stream`` consumes the first
request token by token through ``handle.tokens()`` to demonstrate the
streaming path. Prompts longer than ``--capacity`` are clipped at admission
— the handle's ``truncated`` flag surfaces it and this launcher warns
instead of dropping tokens invisibly. ``--artifact PATH`` replaces the
first two stages with a memory-mapped load of a ``repro.launch.quantize``
artifact — the server never touches FP weights and pays no quantization at
boot (the "quantize once, serve many" deployment path; the startup summary
breaks the boot down per phase so the win is visible); ``--verify-artifact
sizes`` stat-checks shard lengths at boot and ``--verify-artifact`` (or
``=full``) re-checksums every buffer. ``--scheduler serial`` selects the
PR-1 serial-admit baseline (one jit per prompt length) for A/B comparison.

Robustness knobs (v1.1): ``--deadline`` / ``--ttft-deadline`` give every
request a wall budget (expired requests retire with finish_reason
``"timeout"``); ``--max-queue`` / ``--max-resident-tokens`` bound admission
with ``--admission-policy`` choosing shed-on-submit (``reject``, the
default) vs progress-coupled blocking (``block``). The final line prints
``engine.health().summary()`` — the same one-line snapshot a monitor
scrapes. The batch path exits nonzero, printing each failure's detail,
when any request retires ``"error"`` or no request produced a token —
fault containment keeps the engine serving, but a run in which a kernel
failed to compile must not report success.

Paged KV (v1.2): ``--kv-layout paged`` serves from fixed-size physical KV
pages (``--page-size``, pool ``--max-pages``) with copy-on-write prefix
reuse across requests (``--prefix-cache`` / ``--no-prefix-cache``); the
boot breakdown prints the page pool and the health line gains page-pool
gauges. Outputs are bit-identical to ``--kv-layout ring``.

Observability (v1.3): ``--trace-out trace.json`` records the per-request
lifecycle + per-step engine-phase trace (load it in ui.perfetto.dev or
chrome://tracing; boot phases appear on their own track);
``--metrics-out metrics.prom`` writes the Prometheus text exposition at
shutdown plus a ``.jsonl`` snapshot stream next to it;
``--metrics-interval N`` prints a one-line stats digest (req/s, resident
slots, pages free, p99 TTFT so far) every N engine steps and appends a
registry snapshot to the JSONL stream. The shutdown summary prints a
per-request latency table (queue wait, TTFT, total) and the non-zero
registry metrics. All of it is zero-perturbation: tokens are
bit-identical with tracing on, off, or unconfigured.

HTTP frontend (v1.4): ``--http HOST:PORT`` serves network traffic
instead of the built-in prompt list — one ``EngineDriver`` thread owns
the engine, the asyncio frontend exposes ``POST /v1/completions`` (SSE
streaming, cancel-on-disconnect), ``GET /healthz``, and ``GET
/metrics``, and admission runs deficit-weighted round-robin across
tenants (``--tenant-quantum`` / ``--tenant-weights`` /
``--max-pending`` / ``--tenant-max-resident-tokens``). Either mode
shuts down gracefully on SIGINT/SIGTERM: stop admitting, finish (or
deadline-out) residents, flush ``--trace-out`` / ``--metrics-out``, and
print the drain tables; a second signal force-quits.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal
import threading
import time
from pathlib import Path

import jax

from repro import configs
from repro.artifacts import load_artifact, load_model_config
from repro.core.ptqtp import PTQTPConfig
from repro.core.quantize_model import quantize_tree
from repro.data.tokenizer import ByteTokenizer
from repro.models import init_params
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (EngineConfig, SamplingParams, SerialAdmitEngine,
                           ServingEngine)
from repro.serving.observability import TRACK_BOOT, Observability

PROMPTS = [
    "the model computes two trit planes",
    "count 5 6 7",
    "slot 42 holds 7 ;",
    "12 plus 30 equals",
]


@contextlib.contextmanager
def _boot_phase(obs, boot, name, **span_args):
    """Time one boot phase into the printed breakdown dict *and* record it
    as a span on the trace's boot track (when tracing is on)."""
    t0 = time.time()
    with obs.span(name, track=TRACK_BOOT, cat="boot", args=span_args or None):
        yield
    boot[name] = time.time() - t0


def _install_drain_signals(on_signal):
    """SIGINT/SIGTERM → graceful drain (``on_signal()``); a second signal
    force-quits with rc ``128+signum`` — distinct from the graceful
    drain's 0, so a process manager can tell a forced kill from a clean
    shutdown. Returns the previous handlers."""
    fired = {"n": 0}

    def _handler(signum, _frame):
        fired["n"] += 1
        if fired["n"] > 1:
            # operator really means it: exit immediately with a nonzero
            # rc wherever the main thread is blocked (drain join, step
            # loop, Event.wait). os._exit skips flushes by design — this
            # is the no-more-waiting path, not a shutdown.
            print(f"[serve] force quit (rc {128 + signum})", flush=True)
            os._exit(128 + signum)
        print(f"[serve] {signal.Signals(signum).name}: draining "
              "(signal again to force quit)", flush=True)
        on_signal()

    return [(s, signal.signal(s, _handler))
            for s in (signal.SIGINT, signal.SIGTERM)]


def _drain_report(results, engine, tok, args, dt, jsonl_f, jsonl_path):
    """The shutdown tables + file flushes, shared by the cooperative and
    HTTP paths (and by signal-triggered drains): per-request latency,
    registry summary, health line, then --metrics-out/--trace-out."""
    reg = engine.obs.registry
    n_tok = sum(len(r.tokens) for r in results)
    stats = engine.compile_stats()
    print(f"[serve] {len(results)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s, {engine.steps} decode steps, "
          f"{engine.prefill_steps} prefill steps)")
    ttft = sorted(1e3 * r.ttft for r in results if r.t_first)
    if ttft:
        print(f"[serve] ttft ms: median {ttft[len(ttft) // 2]:.1f} "
              f"max {ttft[-1]:.1f}; compiles: {stats['n_prefill_compiles']} "
              f"prefill {sorted(stats['prefill_bucket_lengths'])} "
              f"+ {stats['n_decode_compiles']} decode "
              f"{stats['decode_chunk_lengths']}")
    for r in sorted(results, key=lambda r: r.uid)[:4]:
        print(f"  [{r.uid}] ({r.finish_reason}) -> "
              f"{tok.decode(list(r.tokens))!r}")

    # per-request latency table from the handles' own timestamps (the same
    # numbers the trace spans are built from, so the two always reconcile)
    print("[serve] request latency (ms):")
    print(f"  {'uid':>4} {'reason':>9} {'tok':>4} {'queue':>8} "
          f"{'ttft':>8} {'total':>8}")
    for r in sorted(results, key=lambda r: r.uid):
        total = (r.t_done - r.t_submit) if r.t_done else 0.0
        print(f"  {r.uid:>4} {r.finish_reason:>9} {len(r.tokens):>4} "
              f"{1e3 * r.queue_wait:>8.1f} {1e3 * r.ttft:>8.1f} "
              f"{1e3 * total:>8.1f}")
    print("[serve] metrics summary:")
    for line in reg.summary_table().splitlines():
        print(f"  {line}")
    print(f"[serve] health: {engine.health().summary()}")

    if jsonl_f is not None:
        jsonl_f.write(reg.jsonl_line() + "\n")  # final snapshot
        jsonl_f.close()
        print(f"[serve] metrics snapshots -> {jsonl_path}")
    if args.metrics_out:
        Path(args.metrics_out).write_text(reg.render_prometheus())
        print(f"[serve] metrics -> {args.metrics_out}")
    if args.trace_out:
        engine.obs.trace.write(args.trace_out)
        print(f"[serve] trace ({len(engine.obs.trace)} events) -> "
              f"{args.trace_out}")


def _serve_http(engine, tok, args, stop, factory=None):
    """``--http`` mode: hand the engine to an ``EngineDriver`` (the only
    thread that touches it from here on), serve the v1.4 endpoints, block
    until SIGINT/SIGTERM, then drain gracefully and print the same
    shutdown report as the cooperative path. With ``--supervise`` the
    driver lifecycle is wrapped in an ``EngineSupervisor``: engine death
    rebuilds from ``factory`` and replays in-flight requests (v1.5)."""
    from repro.serving.frontend import (EngineDriver, EngineSupervisor,
                                        FairScheduler, ThreadedHttpServer)

    host, _, port = args.http.rpartition(":")
    host = host or "127.0.0.1"
    weights = {}
    for pair in (args.tenant_weights or "").split(","):
        if pair.strip():
            name, _, w = pair.partition("=")
            weights[name.strip()] = float(w or 1.0)

    def make_fair():
        return FairScheduler(
            quantum=args.tenant_quantum, weights=weights,
            max_pending=args.max_pending,
            tenant_max_resident_tokens=args.tenant_max_resident_tokens)

    if args.supervise:
        driver = EngineSupervisor(
            factory, engine=engine, fairness_factory=make_fair,
            max_restarts=args.max_restarts,
            restart_backoff_s=args.restart_backoff,
            watchdog_step_timeout_s=args.watchdog_step_timeout).start()
    else:
        driver = EngineDriver(engine, fairness=make_fair()).start()
    srv = ThreadedHttpServer(driver, host, int(port)).start()
    print(f"[serve] http: listening on http://{srv.host}:{srv.port} "
          "(POST /v1/completions, GET /healthz, GET /metrics"
          f"{'; supervised' if args.supervise else ''})", flush=True)

    t0 = time.time()
    interval = max(args.metrics_interval, 0)
    jsonl_path = (Path(args.metrics_out).with_suffix(".jsonl")
                  if args.metrics_out and interval else None)
    jsonl_f = open(jsonl_path, "w") if jsonl_path else None
    # in HTTP mode --metrics-interval is seconds between digests (there is
    # no cooperative step loop to count); engine reads go through the
    # driver so they can never race a step
    while not stop.wait(interval if interval else None):
        try:
            print(driver.call(lambda eng: _stats_line(eng, t0)), flush=True)
            if jsonl_f is not None:
                jsonl_f.write(driver.call(
                    lambda eng: eng.obs.registry.jsonl_line()) + "\n")
        except (RuntimeError, TimeoutError) as e:
            # supervised mode: the engine may be mid-rebuild (or dead)
            # when the digest tick fires — report, don't crash the loop
            print(f"[serve] stats unavailable: {e}", flush=True)

    srv.stop()                      # stop accepting connections first,
    driver.drain(timeout=300.0)     # then let offered work finish
    driver.close()
    dt = time.time() - t0
    results = driver.results()
    front = driver.stats()
    print(f"[serve] drained: {front['retired']} retired "
          f"({front['frontend_sheds']} frontend sheds, "
          f"{front['frontend_cancelled']} cancelled pre-admission)")
    if args.supervise:
        sup = driver.supervisor_status()
        print(f"[serve] supervisor: generation {sup['generation']}, "
              f"{sup['restarts']} restarts, {sup['replayed']} replayed, "
              f"degraded={sup['degraded']}, "
              f"blacklisted={sup['blacklisted']}")
        engine = driver.engine  # report against the surviving generation
    _drain_report(results, engine, tok, args, dt, jsonl_f, jsonl_path)
    return results


def _stats_line(engine, t_serve0):
    """The periodic one-line digest: everything read off the registry, so
    what the operator watches and what a scraper collects can't diverge."""
    reg = engine.obs.registry
    elapsed = max(time.time() - t_serve0, 1e-9)
    done = reg.value("serving_requests_completed_total")
    line = (f"[serve] step {engine.engine_steps}: "
            f"{done / elapsed:.2f} req/s "
            f"resident={reg.value('serving_resident_slots')} "
            f"queue={reg.value('serving_queue_depth')} "
            f"tokens={reg.value('serving_tokens_generated_total')}")
    if "serving_pages_free" in reg:
        line += f" pages_free={reg.value('serving_pages_free')}"
    ttft = reg.get_histogram("serving_ttft_seconds")
    if ttft.count:
        line += f" p99_ttft={1e3 * ttft.percentile(99):.1f}ms"
    return line


def _check_batch(results, drained: bool):
    """Exit nonzero when the batch run lost a request to a contained fault
    or, unless a signal drained it, served no token at all (the engine
    keeps stepping past both, so a clean return alone would hide a kernel
    that never compiled)."""
    errors = [r for r in results if r.finish_reason == "error"]
    for r in errors:
        print(f"[serve] ERROR request {r.uid}: {r.error}", flush=True)
    if errors or not (drained or any(r.tokens for r in results)):
        raise SystemExit(
            f"[serve] FAILED: {len(errors)} of {len(results)} requests "
            f"errored, {sum(len(r.tokens) for r in results)} tokens served")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--artifact", default=None, metavar="PATH",
                    help="boot from a prebuilt trit-plane artifact "
                         "(repro.launch.quantize) instead of init+quantize; "
                         "--arch and the quantize flags are ignored")
    ap.add_argument("--verify-artifact", nargs="?", const="full",
                    choices=("off", "sizes", "full"), default="off",
                    help="artifact integrity check at boot: 'sizes' "
                         "stat-checks shard lengths without reading tensor "
                         "bytes; 'full' (also the value when the flag is "
                         "given bare) re-checksums every buffer")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k truncation (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus sampling mass (1.0 = off)")
    ap.add_argument("--stream", action="store_true",
                    help="consume the first request token-by-token through "
                         "RequestHandle.tokens()")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request end-to-end wall budget in seconds; an "
                         "expired request retires with finish_reason "
                         "'timeout', keeping the tokens it already produced")
    ap.add_argument("--ttft-deadline", type=float, default=None, metavar="S",
                    help="per-request budget for the first token, seconds")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="admission cap on waiting requests (load shedding)")
    ap.add_argument("--max-resident-tokens", type=int, default=None,
                    metavar="N",
                    help="admission cap on the committed token footprint "
                         "(clipped prompt + generation budget) over queued "
                         "plus resident work")
    ap.add_argument("--admission-policy", choices=("reject", "block"),
                    default="reject",
                    help="what submit() does past a cap: 'reject' sheds the "
                         "request (finish_reason 'rejected'), 'block' drives "
                         "engine steps until it fits")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens consumed per slot per engine step")
    ap.add_argument("--scheduler", choices=("bucketed", "serial"),
                    default="bucketed",
                    help="bucketed/chunked admission (default) or the "
                         "serial per-length-jit baseline")
    ap.add_argument("--attn-backend",
                    choices=("auto", "pallas", "stream", "materialized"),
                    default="auto",
                    help="ring-cache attention backend (repro.kernels."
                         "chunk_attention): auto = Pallas on TPU, the "
                         "streaming online-softmax fallback elsewhere; "
                         "materialized = the full-score-block baseline")
    ap.add_argument("--kv-layout", choices=("ring", "paged"), default="ring",
                    help="KV-cache storage: 'ring' = contiguous per-slot "
                         "(baseline + bit-identity oracle); 'paged' = "
                         "fixed-size pages from a shared pool with COW "
                         "prefix reuse (serving contract v1.2)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical KV page (paged layout); must "
                         "divide --capacity and align with the attention "
                         "tile selection")
    ap.add_argument("--max-pages", type=int, default=None, metavar="N",
                    help="physical page pool size (paged layout; default "
                         "slots*capacity/page_size = the ring footprint; "
                         "lower overcommits against prefix sharing)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="COW prefix-page reuse across requests (paged "
                         "layout; cache-hit prompt pages skip prefill)")
    ap.add_argument("--warmup", action="store_true",
                    help="precompile every dispatch bucket before serving")
    ap.add_argument("--no-quantize", action="store_true",
                    help="serve FP weights (baseline)")
    ap.add_argument("--t-max", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed; request i samples from its own "
                         "stream seeded seed+i (reproducible regardless "
                         "of co-batched traffic)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace.json of boot "
                         "phases, engine step phases, and per-request "
                         "lifecycle spans at shutdown (zero-perturbation: "
                         "tokens are bit-identical with tracing off)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition of the "
                         "metrics registry at shutdown; a .jsonl snapshot "
                         "stream is written next to it when "
                         "--metrics-interval is set")
    ap.add_argument("--metrics-interval", type=int, default=0, metavar="N",
                    help="print a one-line stats digest (and append a "
                         "registry snapshot to the JSONL stream) every N "
                         "engine steps while draining (0 = off); in --http "
                         "mode, every N seconds")
    ap.add_argument("--http", default=None, metavar="HOST:PORT",
                    help="serve over HTTP instead of the built-in prompt "
                         "list: a single EngineDriver thread owns the "
                         "engine and an asyncio frontend exposes POST "
                         "/v1/completions (SSE streaming), GET /healthz, "
                         "GET /metrics; SIGINT/SIGTERM drains gracefully. "
                         "':0' picks a free port")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the driver in an EngineSupervisor (--http "
                         "mode): engine death or a hung step rebuilds the "
                         "engine (from --artifact when given, else "
                         "re-quantizing in-process) under a new generation "
                         "id and replays in-flight requests bit-identically "
                         "(serving contract v1.5)")
    ap.add_argument("--max-restarts", type=int, default=3, metavar="N",
                    help="crash-loop circuit breaker: N crashes within the "
                         "crash window open the breaker (degraded mode: new "
                         "submits shed with HTTP 503 + Retry-After while "
                         "replayable work finishes)")
    ap.add_argument("--restart-backoff", type=float, default=0.5,
                    metavar="S",
                    help="base seconds between engine death and rebuild; "
                         "doubles per crash in the window")
    ap.add_argument("--watchdog-step-timeout", type=float, default=None,
                    metavar="S",
                    help="flag an engine step running longer than S seconds "
                         "(on the injectable clock) as hung and recover as "
                         "if it crashed (default: watchdog off)")
    ap.add_argument("--tenant-quantum", type=int, default=256, metavar="TOK",
                    help="DRR deficit replenished per tenant per round, in "
                         "committed tokens (--http mode fairness)")
    ap.add_argument("--tenant-weights", default=None, metavar="T=W,...",
                    help="per-tenant DRR weight overrides, e.g. "
                         "'paid=4,free=1' (default weight 1.0)")
    ap.add_argument("--max-pending", type=int, default=None, metavar="N",
                    help="frontend cap on requests waiting in the fair "
                         "queue across all tenants; past it submits shed "
                         "with HTTP 429 (--http mode)")
    ap.add_argument("--tenant-max-resident-tokens", type=int, default=None,
                    metavar="N",
                    help="per-tenant cap on committed tokens concurrently "
                         "inside the engine (--http mode fairness)")
    args = ap.parse_args(argv)

    if args.supervise and args.http is None:
        ap.error("--supervise requires --http (the batch path has no "
                 "driver to supervise)")
    if args.kv_layout == "paged":
        if args.scheduler == "serial":
            ap.error("--kv-layout paged requires the bucketed scheduler "
                     "(the serial baseline prefills into a private ring)")
        if args.capacity % args.page_size:
            ap.error(f"--capacity {args.capacity} must be a whole number "
                     f"of pages (--page-size {args.page_size})")
        # page boundaries must align with the attention tile walk: the
        # paged kernels tile at paged_tile(page_size, L) which divides the
        # page by construction, and bit-identity with the ring baseline
        # additionally wants the ring tile to land on page boundaries
        from repro.kernels.chunk_attention import paged_tile
        from repro.kernels.chunk_attention.ops import _select_tile
        for L in (1, args.prefill_chunk):
            t_ring = _select_tile(args.capacity, L)
            t_paged = paged_tile(args.page_size, L)
            if args.page_size % t_paged:
                ap.error(f"--page-size {args.page_size} admits no clean "
                         f"attention tile at chunk length {L}")
            if t_ring % args.page_size and args.page_size % t_ring:
                ap.error(f"--page-size {args.page_size} does not divide "
                         f"the attention tile selection cleanly (ring "
                         f"tile {t_ring} at chunk length {L}); pick a "
                         "power-of-two page size dividing --capacity")

    # one observability bundle for the whole process: boot spans land on
    # its trace before the engine exists, then bind_engine() (inside the
    # constructor) attaches the registry to the engine's counters
    obs = Observability(trace=args.trace_out is not None)

    boot = {}  # phase -> seconds (startup breakdown)
    t_boot = time.time()
    if args.artifact:
        with _boot_phase(obs, boot, "artifact_load",
                         verify=args.verify_artifact):
            params, manifest = load_artifact(args.artifact,
                                             verify=args.verify_artifact,
                                             obs=obs)
            cfg = load_model_config(manifest)
        if not cfg.embed_inputs:
            ap.error(f"artifact model {cfg.name} has a stub modality "
                     "frontend; token serving applies to LM archs")
        stats = manifest.get("stats", {})
        print(f"[serve] artifact: {manifest['arch']} "
              f"({stats.get('n_quantized', '?')} quantized kernels, "
              f"{stats.get('total_bytes', 0) / 1e6:.2f} MB memory-mapped, "
              f"{boot['artifact_load'] * 1e3:.0f}ms)")
    else:
        cfg = configs.get_smoke_config(args.arch)
        if not cfg.embed_inputs:  # reject stub archs before any boot work
            ap.error(f"{args.arch} has a stub modality frontend; token "
                     "serving applies to LM archs (see launch/dryrun.py "
                     "for its cells)")
        with _boot_phase(obs, boot, "weight_init"):
            params = init_params(cfg, jax.random.PRNGKey(args.seed))
        if not args.no_quantize:
            with _boot_phase(obs, boot, "quantize", t_max=args.t_max):
                gs = min(128, cfg.d_model)
                params, report = quantize_tree(
                    params, PTQTPConfig(group_size=gs, t_max=args.t_max))
            tot = report["__total__"]
            print(f"[serve] PTQTP: {tot['n_quantized']} kernels, "
                  f"{tot['compression']:.2f}x compression, "
                  f"{boot['quantize']:.1f}s")

    tok = ByteTokenizer()
    cls = ServingEngine if args.scheduler == "bucketed" else SerialAdmitEngine
    ecfg = EngineConfig(
        max_slots=args.slots, capacity=args.capacity,
        prefill_chunk=args.prefill_chunk, attn_backend=args.attn_backend,
        max_queue=args.max_queue,
        max_resident_tokens=args.max_resident_tokens,
        admission_policy=args.admission_policy,
        kv_layout=args.kv_layout, page_size=args.page_size,
        max_pages=args.max_pages, prefix_cache=args.prefix_cache)
    with _boot_phase(obs, boot, "engine_init", scheduler=args.scheduler):
        engine = cls(params, cfg, ecfg, observability=obs)

    def engine_factory():
        # supervised recovery rebuild: reload params from the artifact when
        # one was given (the mmap re-open is cheap and sheds any state the
        # dying generation may have corrupted), else reuse the in-memory
        # quantized tree; each generation gets a fresh Observability so
        # bind_engine's single-bind invariant holds
        p = params
        if args.artifact:
            p, _ = load_artifact(args.artifact, verify="off")
        return cls(p, cfg, ecfg, observability=Observability(
            trace=args.trace_out is not None))

    mem = engine.memory_stats()
    if args.kv_layout == "paged":
        print(f"[serve] paged KV: pool {engine.alloc.n_pages} pages x "
              f"{args.page_size} tokens ({mem['kv_pool_bytes'] / 1e6:.2f} MB"
              f", {mem['kv_page_bytes'] / 1e3:.1f} KB/page across layers), "
              f"prefix cache {'on' if engine._prefix_reuse else 'off'}; "
              f"resident KV {mem['kv_resident_bytes'] / 1e6:.2f} MB")
    if mem["preunpack_decode"]:
        # honest resident-state accounting: pre-unpacked decode planes are
        # int8 trits, 4x the packed bytes a weight-only count would suggest
        print(f"[serve] resident planes "
              f"{mem['resident_plane_bytes'] / 1e6:.2f} MB "
              f"({mem['preunpack_ratio']:.1f}x packed "
              f"{mem['packed_plane_bytes'] / 1e6:.2f} MB, preunpack_decode); "
              f"decode state {mem['decode_state_bytes'] / 1e6:.2f} MB; "
              f"total resident {mem['resident_total_bytes'] / 1e6:.2f} MB")
    if args.warmup:
        with _boot_phase(obs, boot, "warmup"):
            engine.warmup()
        print(f"[serve] warmup: {engine.compile_stats()['n_prefill_compiles']}"
              f" prefill programs in {boot['warmup']:.1f}s")
    breakdown = " ".join(f"{k}={v:.2f}s" for k, v in boot.items())
    # graceful drain on SIGINT/SIGTERM, armed before the boot line prints
    # so an operator (or a supervisor) can signal the moment boot is
    # announced: stop admitting (cancel what is still queued), finish or
    # deadline-out residents, then fall through to the normal report +
    # file flushes instead of dying mid-step
    draining = threading.Event()
    _install_drain_signals(draining.set)
    print(f"[serve] boot {time.time() - t_boot:.2f}s ({breakdown})",
          flush=True)

    if args.http is not None:
        return _serve_http(engine, tok, args, stop=draining,
                           factory=engine_factory)

    handles = []
    for i in range(args.requests):
        prompt = tok.encode(PROMPTS[i % len(PROMPTS)], eos=False)
        h = engine.submit(prompt, SamplingParams(
            max_new_tokens=args.max_new, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=args.seed + i,
            deadline_s=args.deadline, ttft_deadline_s=args.ttft_deadline))
        if h.done:  # shed at submit (admission-policy reject past a cap)
            print(f"[serve] WARNING: request {h.uid} {h.finish_reason}: "
                  f"{h.error}")
            handles.append(h)
            continue
        if h.truncated:
            print(f"[serve] WARNING: request {h.uid} prompt "
                  f"({len(prompt)} tokens) exceeds --capacity "
                  f"{args.capacity}; only the last {args.capacity} tokens "
                  "will be served (result carries truncated=True)")
        handles.append(h)

    t0 = time.time()
    if args.stream and handles and not handles[0].done:
        # the streaming path: tokens arrive in the engine step that produced
        # them (first one in the step its prefill completed); the rest of
        # the fleet advances through the same steps
        pieces = []
        for t in handles[0].tokens():
            pieces.append(tok.decode([t]))
        print(f"[serve] streamed [{handles[0].uid}] -> {''.join(pieces)!r} "
              f"(ttft {1e3 * (handles[0].t_first - handles[0].t_submit):.1f}"
              "ms)")

    # explicit drive loop (rather than letting result() drive implicitly)
    # so the periodic stats digest and JSONL snapshots can interleave with
    # engine steps at a known cadence
    interval = max(args.metrics_interval, 0)
    jsonl_path = (Path(args.metrics_out).with_suffix(".jsonl")
                  if args.metrics_out and interval else None)
    jsonl_f = open(jsonl_path, "w") if jsonl_path else None
    reg = engine.obs.registry
    while engine.queue or any(s is not None for s in engine.slots):
        if draining.is_set():
            for h in list(engine.queue):  # stop admitting: queued work
                engine.cancel(h)          # never reaches a slot
        engine.step()
        if interval and engine.engine_steps % interval == 0:
            print(_stats_line(engine, t0))
            if jsonl_f is not None:
                jsonl_f.write(reg.jsonl_line() + "\n")
    results = [h.result() for h in handles]  # all retired; just collects
    dt = time.time() - t0
    if draining.is_set():
        n_cancelled = sum(r.finish_reason == "cancelled" for r in results)
        print(f"[serve] drained: {len(results) - n_cancelled} finished, "
              f"{n_cancelled} cancelled in queue")
    _drain_report(results, engine, tok, args, dt, jsonl_f, jsonl_path)
    _check_batch(results, drained=draining.is_set())
    return results


if __name__ == "__main__":
    main()
