"""Continuous-batching serving engine behind the v1 request API: bucketed
batched prefill, chunked prefill interleaved with a fused multi-step decode
loop, per-request RNG, streaming handles, cancellation.

Request lifecycle (Serving API v1 — see ``repro.serving.api``):

  * ``submit(prompt, SamplingParams(...)) -> RequestHandle`` enqueues; the
    handle exposes ``tokens()`` (a generator that drives ``step()`` on
    demand and yields each token in the engine step that produced it),
    ``result()`` (block until finished), ``cancel()`` (frees the slot
    immediately, mid-prefill or mid-decode), plus ``t_submit/t_first/
    t_done`` and a ``truncated`` flag when the prompt was clipped to
    ``capacity``;
  * ``step()`` advances the whole fleet one engine step (admission +
    prefill chunk + decode chunk) and returns the handles that finished;
  * ``run()`` drives until drained (the batch-caller style; the pre-v1
    ``Request`` record shim is gone after its one PR of grace).

Scheduling (unchanged from PR 2): the batch has ``max_slots`` fixed slots →
one jit'd decode loop for the whole fleet; **bucketed admission** drains the
wait queue into all free slots per step and advances every mid-prompt row by
one power-of-two prefill-chunk bucket in a single fixed-shape dispatch
(prefill compile cache O(log prefill_chunk)); **chunked prefill** interleaves
long prompts with (shortened) decode chunks; finished or cancelled slots free
immediately and refill next step.

Per-request RNG (the v1 determinism contract): each slot carries its
request's ``SamplingParams.seed``; the i-th generated token is drawn with
``fold_in(PRNGKey(seed), i)`` *on device inside the decode scan* (and for
i = 0 by the prefill finisher / serial admitter). No draw touches
engine-global state, so a request's output is a pure function of (params,
prompt, SamplingParams) — invariant to fleet composition, scheduler
(`ServingEngine` vs `SerialAdmitEngine`), and chunk boundaries. Stop-token
ids (``SamplingParams.stop`` ∪ ``EngineConfig.eos_id``) freeze the row
on device and truncate the host-side stream at the first hit, wherever in a
chunk (or in the prefill-finisher sample) it lands.

Paged KV: ``EngineConfig.kv_layout="paged"`` virtualizes every slot's KV
ring into ``page_size``-token physical pages drawn from one shared,
refcounted pool (``repro.serving.paging``), with copy-on-write prefix
sharing keyed by *exact* prompt-prefix token tuples — cache-hit pages are
adopted read-only and their tokens skip prefill entirely. Admission
reserves each request's worst-case page budget up front (including COW
fork targets for wrap-bound requests), so a resident request can never
run out of pages; the v1.2 contract section in ``repro.serving`` states
the determinism guarantee.

Works identically for dense and PTQTP-quantized params (`dense` dispatches
on the kernel leaf type), which is the paper's deployment story.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import unpack_trits
from repro.core.quantize_model import QuantizedKernel
from repro.kernels.ternary_matmul.ops import resolve_backend
from repro.models import (decode_step, init_decode_state, prefill,
                          prefill_chunk)
from repro.models.attention import row_axis
from repro.models.common import matmul_backend
from repro.runtime import clock as rtclock
from repro.runtime.monitor import HealthSnapshot
from repro.serving.api import (FINISH_CANCELLED, FINISH_ERROR, FINISH_LENGTH,
                               FINISH_REJECTED, FINISH_STOP, FINISH_TIMEOUT,
                               RequestHandle, SamplingParams, make_handle)
from repro.serving.observability import TRACK_ENGINE, Observability
from repro.serving.paging import PageAllocator
from repro.serving.sampling import request_keys, sample_tokens_per_request

__all__ = ["EngineConfig", "ServingEngine", "SerialAdmitEngine",
           "SamplingParams", "RequestHandle", "EngineFault", "EngineCrash"]


class EngineCrash(RuntimeError):
    """The engine itself died — not a containable per-dispatch fault.

    Unlike :class:`EngineFault`, which ``_contain`` absorbs (retire the
    attributed slot, quarantine, keep stepping), an ``EngineCrash``
    deliberately escapes ``step()``: device state after a crash cannot be
    trusted, so whoever drives the engine (the ``EngineDriver``'s
    ``_fatal`` path) must tear it down and — under an
    ``EngineSupervisor`` — rebuild and replay. ``uid`` blames one request
    when the crasher is known; the engine fills ``suspects`` with the
    uids participating in the dispatch that died (just the blamed uid
    when it was resident), which is what the supervisor's replay
    blacklist keys on."""

    def __init__(self, msg: str, uid: Optional[int] = None):
        super().__init__(msg)
        self.uid = uid
        self.suspects: Tuple[int, ...] = ()


class EngineFault(RuntimeError):
    """A device-dispatch failure attributed (when possible) to one slot.

    Raised by fault injectors and used internally as the containment
    envelope for real dispatch exceptions. ``slot`` is the offending batch
    row, or None when the failure cannot be attributed — in that case every
    request participating in the dispatch is retired (the containment unit
    is the dispatch, never the engine)."""

    def __init__(self, msg: str, slot: Optional[int] = None):
        super().__init__(msg)
        self.slot = slot


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-wide knobs. Per-request generation behavior (budget,
    temperature, top-k/top-p, seed, stop ids) lives in ``SamplingParams``;
    what remains here is fleet shape and scheduling.

    ``eos_id`` is the engine-wide stop token (tokenizer property, honored
    for every request in addition to its ``SamplingParams.stop``).
    ``attn_backend`` overrides the model's ring-cache attention backend
    (``repro.kernels.chunk_attention``: auto | pallas | stream |
    materialized) for every dispatch this engine compiles — the serving-
    level knob the launcher's ``--attn-backend`` flag sets.
    """

    max_slots: int = 4
    capacity: int = 256          # KV-cache length per slot
    eos_id: Optional[int] = None
    attn_backend: Optional[str] = None
    decode_chunk: int = 8        # tokens per jitted decode dispatch (K)
    prefill_chunk: int = 64      # max prompt tokens consumed per slot per step
    # ---- admission control (None → unbounded, the pre-containment behavior)
    # max_queue caps how many requests may *wait* for a slot; a submit that
    # would exceed it is shed ("reject": the handle comes back already
    # finished with reason "rejected") or blocks ("block": submit drives
    # step() until space frees) — overload degrades to fast rejections or
    # bounded blocking instead of unbounded queue growth.
    max_queue: Optional[int] = None
    # max_resident_tokens caps the committed token footprint (clipped prompt
    # + max_new_tokens budget) summed over queued + resident requests.
    max_resident_tokens: Optional[int] = None
    admission_policy: str = "reject"   # "reject" | "block"
    # how many engine steps a suspect slot sits out before it is row-reset
    # and returned to the admission pool (observable cool-down; None →
    # never automatically, only an explicit engine.rehabilitate())
    quarantine_steps: Optional[int] = 2
    # decode chunk cap while any slot is mid-prefill: a long prompt reaches
    # its first token in ~L/prefill_chunk short engine steps instead of
    # waiting a full decode chunk between each of its prefill chunks
    # (TTFT-vs-TPOT balance, the chunked-prefill token-budget idea)
    decode_chunk_prefilling: int = 2
    # Pre-unpack trit-planes for the decode loop (None → auto: only when the
    # grouped XLA backend serves the quantized matmuls; the Pallas TPU kernel
    # unpacks in-kernel, where streaming packed planes IS the win). Trades
    # 4x plane bytes (int8 trits vs 2-bit fields, still 2x under fp16) for
    # not re-unpacking every weight at every decode step.
    preunpack_decode: Optional[bool] = None
    # ---- paged KV cache ("paged" virtualizes every slot's ring into
    # page_size-token physical pages drawn from one shared pool; "ring" is
    # the contiguous per-slot layout, kept as the baseline and the
    # bit-identity oracle)
    kv_layout: str = "ring"            # "ring" | "paged"
    page_size: int = 16                # tokens per physical page
    # pool size in pages (None → max_slots · capacity/page_size: exactly the
    # ring footprint, so paging alone never reduces admissible load — set it
    # lower to overcommit against prefix sharing)
    max_pages: Optional[int] = None
    prefix_cache: bool = True          # COW prefix reuse across requests

    def __post_init__(self):
        assert self.max_slots >= 1 and self.capacity >= 1
        assert self.decode_chunk >= 1, "decode_chunk=0 would never emit"
        assert self.prefill_chunk >= 1, "prefill_chunk=0 would never admit"
        assert self.decode_chunk_prefilling >= 1
        assert self.admission_policy in ("reject", "block"), \
            self.admission_policy
        assert self.max_queue is None or self.max_queue >= 1
        assert self.max_resident_tokens is None \
            or self.max_resident_tokens >= 1
        assert self.quarantine_steps is None or self.quarantine_steps >= 0
        assert self.kv_layout in ("ring", "paged"), self.kv_layout
        if self.kv_layout == "paged":
            assert self.page_size >= 1
            assert self.capacity % self.page_size == 0, \
                (f"capacity {self.capacity} must be a whole number of "
                 f"pages (page_size {self.page_size})")
            # max_pages below one slot's worth is allowed: requests whose
            # worst case can't fit the pool shed at submit; shorter ones
            # still serve (deliberate overcommit against prefix sharing)
            assert self.max_pages is None or self.max_pages >= 1


def _pow2ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _preunpack_params(params):
    """Replace packed QuantizedKernel planes with raw int8 trit-planes.

    The unpack is exact and the grouped einsum consumes either form with the
    identical contraction order, so decode outputs are bit-identical — the
    unpack work just moves from every decode step to engine init.
    """

    def unpack(leaf):
        if isinstance(leaf, QuantizedKernel):
            return dataclasses.replace(
                leaf, t1p=unpack_trits(leaf.t1p), t2p=unpack_trits(leaf.t2p))
        return leaf

    return jax.tree.map(unpack, params,
                        is_leaf=lambda x: isinstance(x, QuantizedKernel))


def _row_axis(path: str) -> int:
    """The batch-row (or physical-page) axis of the decode-state leaf at
    ``path``: the leaf's own (``models.attention.row_axis``), one further
    in for a scan's stacked caches (``/blocks/``: (L, ...))."""
    return row_axis(path.rsplit("/", 1)[-1]) + ("/blocks/" in path)


def _merge_slot_impl(batch_state, one_state, slot):
    """Write a batch=1 decode state into slot `slot` of the batch state.

    Jitted (slot is a traced scalar): one dispatch per admit instead of one
    per state leaf — the leaf-by-leaf eager version dominated admit latency.
    The batch state is donated on accelerators so the one-slot write never
    copies the other slots' KV caches. (Serial-admit path only; the bucketed
    scheduler prefills straight into the batch state and never merges.)
    """

    def walk(dst, src, path):
        if isinstance(dst, dict):
            return {k: walk(dst[k], src[k], f"{path}/{k}") for k in dst}
        axis = _row_axis(path)
        idx = [slice(None)] * dst.ndim
        idx[axis] = slot
        return dst.at[tuple(idx)].set(
            jnp.take(src, 0, axis=axis).astype(dst.dtype))

    return walk(batch_state, one_state, "")


_merge_jit = None


def _merge_slot(batch_state, one_state, slot):
    """Jitted merge, donation decided lazily (first call, not import time —
    importing this module must not initialize the JAX platform)."""
    global _merge_jit
    if _merge_jit is None:
        donate = (0,) if jax.default_backend() != "cpu" else ()
        _merge_jit = jax.jit(_merge_slot_impl, donate_argnums=donate)
    return _merge_jit(batch_state, one_state, slot)


def _reset_rows_impl(state, mask, pos0):
    """Clear the per-row decode state for rows in `mask` (new admissions).

    Ring-cache position leaves reset to -1 (nothing valid), everything else
    (KV, recurrent states, page tables) to zero, and the absolute position
    to ``pos0`` (nonzero when a paged admission skips prefix-cached prompt
    pages — the row resumes mid-prompt) — one fused dispatch no matter how
    many rows reset, so a burst of admits costs one round-trip.

    Paged pool leaves (``pages_*``) have no batch axis — they are shared
    physical storage, owned by the host-side :class:`PageAllocator` — so
    they pass through untouched; the engine's page maintenance op clears
    freshly allocated pages instead.
    """

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if path.rsplit("/", 1)[-1].startswith("pages_"):
            return node
        axis = _row_axis(path)
        shape = [1] * node.ndim
        shape[axis] = node.shape[axis]
        if path == "/pos":
            return jnp.where(mask, pos0.astype(node.dtype), node)
        reset = -1 if path.endswith("/pos") else 0
        return jnp.where(mask.reshape(shape),
                         jnp.asarray(reset, node.dtype), node)

    return walk(state, "")


def _page_maint_impl(state, src, dst, clear, tables):
    """One fused dispatch for all device-side page bookkeeping of a step:
    COW copies (``pool[dst] = pool[src]`` on every ``pages_*`` leaf, every
    layer), invalidation of freshly allocated pages (``pages_pos[clear] =
    -1`` — a recycled page's stale positions would otherwise satisfy the
    gather mask), and the authoritative host page-table push. Index args
    are power-of-two padded with 0 by the caller: page 0 is the reserved
    null page, so ``copy 0→0`` and ``clear 0`` are identities.
    """

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        name = path.rsplit("/", 1)[-1]
        if name == "table":
            t = tables.astype(node.dtype)
            return jnp.broadcast_to(t[None], node.shape) if node.ndim == 3 \
                else t
        if not name.startswith("pages_"):
            return node
        axis = _row_axis(path)                 # the pool's physical pages
        idx = [slice(None)] * node.ndim
        idx[axis] = dst
        node = node.at[tuple(idx)].set(jnp.take(node, src, axis=axis))
        if name == "pages_pos":
            idx[axis] = clear
            node = node.at[tuple(idx)].set(-1)
        return node

    return walk(state, "")


def _decode_loop(params, state, tokens, temps, active, seeds, gen_idx,
                 top_k, top_p, stops, poison, *, cfg, n_steps, use_mask,
                 use_poison=False):
    """K fused decode steps with on-device per-request sampling.

    Args:
      tokens:  (B,) int32 last token per slot.
      temps:   (B,) f32 per-slot temperature (0 → greedy for that row).
      active:  (B,) bool — decoding slots; inactive slots (free, mid-prefill,
        or stop-frozen) repeat their token and their state is left untouched.
      seeds:   (B,) uint32 per-request RNG seed (``SamplingParams.seed``).
      gen_idx: (B,) int32 tokens already generated per request — the i-th
        token draws ``fold_in(PRNGKey(seed), i)``, so resuming a request at
        any chunk boundary continues the identical stream.
      top_k:   (B,) int32, 0 disables per row (traced iff ``use_mask``).
      top_p:   (B,) f32, 1.0 disables per row (traced iff ``use_mask``).
      stops:   (B, W) int32 stop-token ids, -1-padded (W static; a hit
        freezes the row exactly like the pre-v1 EOS check).
      poison:  (B,) int32 fault-injection gen-index per row, -1 = never
        (traced iff ``use_poison``, i.e. only for engines built with a
        fault injector — the production loop compiles it out). When row b's
        gen counter equals ``poison[b]`` its logits are overwritten with
        NaN *on device*, exercising the real non-finite containment path.
    Returns:
      (new_state, (toks, bad)): toks (n_steps, B) — the sampled token per
      step; bad (n_steps, B) bool — True where the row's logits for that
      step were non-finite (the host retires such rows with reason
      ``"error"`` and discards the garbage token). The reduction is a
      per-row ``isfinite`` all — numerics of surviving rows are untouched,
      so adding the health output preserves bit-identity.
    """

    def body(carry, _):
        state, tok, active, gen = carry
        logits, state = decode_step(params, cfg, state, tok, active)
        if use_poison:
            logits = jnp.where((gen == poison)[:, None] & active[:, None],
                               jnp.nan, logits)
        bad = jnp.logical_and(
            active, jnp.logical_not(jnp.all(jnp.isfinite(logits), axis=-1)))
        keys = request_keys(seeds, gen)
        nxt = sample_tokens_per_request(
            logits, keys, temps,
            top_k=top_k if use_mask else None,
            top_p=top_p if use_mask else None)
        nxt = jnp.where(active, nxt, tok)  # frozen slots repeat (host drops)
        gen = gen + active.astype(gen.dtype)
        hit = jnp.any(nxt[:, None] == stops, axis=-1)
        # a poisoned/non-finite row freezes too: its state is garbage from
        # here on and the host is about to retire it anyway
        active = jnp.logical_and(active,
                                 jnp.logical_not(jnp.logical_or(hit, bad)))
        return (state, nxt, active, gen), (nxt, bad)

    # Full unroll: the scan body is op-overhead-bound at decode shapes, and
    # unrolling lets XLA fuse across steps (measured ~40% per-token on CPU).
    (state, _, _, _), (toks, bad) = jax.lax.scan(
        body, (state, tokens, active, gen_idx), None, length=n_steps,
        unroll=min(n_steps, 16))
    return state, (toks, bad)


class ServingEngine:
    """Bucketed/chunked-prefill scheduler behind the v1 handle API (see
    module docstring).

    ``injector`` (optional) is a fault-injection hook implementing the
    :class:`repro.serving.faults.FaultInjector` protocol: it may substitute
    the engine's clock (deterministic deadline tests), raise from a chosen
    dispatch, and poison chosen rows' logits with NaN on device. Production
    engines pass None and compile the poison input out entirely.

    ``observability`` (optional) is a :class:`repro.serving.observability.
    Observability` bundle; the engine always carries one (constructing a
    registry-only default when unconfigured), adopts it onto its own clock,
    and registers the frozen serving metric set against its bookkeeping
    counters. Pass ``Observability(trace=True)`` to also record the
    lifecycle/phase trace. All instrumentation is host-side around (never
    inside) the compiled dispatches: tokens are bit-identical with tracing
    on, off, or unconfigured, and no new compile-cache axis exists.
    """

    def __init__(self, params, model_cfg, engine_cfg: EngineConfig, *,
                 injector=None, observability: Optional[Observability] = None):
        self.params = params
        if engine_cfg.attn_backend is not None:
            model_cfg = dataclasses.replace(
                model_cfg, attn_backend=engine_cfg.attn_backend)
        self.cfg = model_cfg
        self.ecfg = engine_cfg
        self.queue: deque[RequestHandle] = deque()
        self.slots: List[Optional[RequestHandle]] = [None] * engine_cfg.max_slots
        # ---- paged KV layout (see _plan_pages for the admission story)
        self.paged = engine_cfg.kv_layout == "paged"
        kv_spec = None
        if self.paged:
            ps = engine_cfg.page_size
            self._per_slot = engine_cfg.capacity // ps
            total = engine_cfg.max_pages
            if total is None:
                total = engine_cfg.max_slots * self._per_slot
            kinds = (tuple(model_cfg.prefix_pattern)
                     + tuple(model_cfg.block_pattern)
                     + tuple(model_cfg.remainder_pattern))
            # prefix reuse splices cached KV pages under a later request —
            # sound only when attention is the *only* stateful mixer (a
            # recurrent rwkv/rglru state summarizes every prior token and
            # cannot skip the shared prefix), so it auto-disables otherwise
            attn_only = all(k != "rwkv" and not k.startswith("rglru")
                            for k in kinds)
            self._prefix_reuse = engine_cfg.prefix_cache and attn_only
            self.alloc = PageAllocator(total, ps,
                                       prefix_cache=self._prefix_reuse)
            # host-authoritative logical→physical page map per slot; pushed
            # to the device "table" leaves by _page_maintenance
            self._tables = np.zeros((engine_cfg.max_slots, self._per_slot),
                                    np.int32)
            self._tables_dirty = False
            self._registered = [0] * engine_cfg.max_slots
            self._cacheable = [False] * engine_cfg.max_slots
            # COW fork targets pre-reserved at admission (so a wrap-time
            # fork can never fail mid-request)
            self._reserve: List[List[int]] = \
                [[] for _ in range(engine_cfg.max_slots)]
            self._maint_jit = None
            kv_spec = {"page_size": ps, "max_pages": total}
        else:
            self.alloc = None
            self._prefix_reuse = False
        self.state = init_decode_state(model_cfg, engine_cfg.max_slots,
                                       engine_cfg.capacity, kv_spec=kv_spec)
        self.last_tokens = np.zeros((engine_cfg.max_slots,), np.int32)
        pre = engine_cfg.preunpack_decode
        if pre is None:
            pre = resolve_backend(matmul_backend()) == "grouped"
        # serve-side params: prefill and decode both read these, so the
        # unpack is paid once per engine, not once per dispatch; placed on
        # the device once (an artifact's memmapped host leaves would
        # otherwise be copied to the accelerator at every dispatch)
        self._serve_params = jax.device_put(
            _preunpack_params(params) if pre else params)
        self.preunpack_decode = pre
        self._loop_cache: Dict[Tuple[int, bool, int, bool], Any] = {}
        self._prefill_cache: Dict[int, Any] = {}
        self._reset_jit = None
        # per-slot prompt progress: clipped prompt + tokens already consumed
        self._prompts: List[Optional[List[int]]] = [None] * engine_cfg.max_slots
        self._cursor: List[int] = [0] * engine_cfg.max_slots
        self._admit_finished: List[RequestHandle] = []
        self._slot_arrays = None  # fleet array cache; None → slots dirty
        self._next_uid = 0
        self.steps = 0           # decode steps dispatched (tokens per slot)
        self.prefill_steps = 0   # prefill_chunk dispatches
        self.admits = 0
        # ---- fault containment / admission control state
        self._injector = injector
        clock = getattr(injector, "clock", None) if injector else None
        self._clock = clock if clock is not None else rtclock.MONOTONIC
        # suspect slots → engine step at which they may auto-rehabilitate
        self.quarantined: Dict[int, int] = {}
        self.engine_steps = 0    # step() calls (injector schedule index)
        self._dispatch_counts = {"prefill": 0, "decode": 0}
        self.completed = 0       # finished stop/length
        self.cancelled = 0
        self.sheds = 0           # rejected at submit
        self.timeouts = 0        # retired by the deadline sweep
        self.errors = 0          # retired by fault containment
        # ---- observability (registry always on; tracing only when asked)
        self.submitted = 0           # submit() calls accepted
        self.tokens_generated = 0    # tokens delivered to outputs
        self.prefill_tokens = 0      # prompt tokens consumed by prefill
        self.obs = observability if observability is not None \
            else Observability()
        # the engine's clock (a VirtualClock under an injector) owns every
        # timestamp, including the bundle's spans and histogram observations
        self.obs.clock = self._clock
        self.obs.bind_engine(self)

    # ------------------------------------------------------------------ API
    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               uid: Optional[int] = None) -> RequestHandle:
        """Enqueue a request; returns its :class:`RequestHandle`.

        ``prompt`` is a token-id list; ``params`` is its
        ``SamplingParams`` (default greedy).

        Admission control: when ``EngineConfig.max_queue`` or
        ``max_resident_tokens`` is set and accepting this request would
        exceed it, the request is **shed** — under policy ``"reject"`` the
        handle returns already finished with reason ``"rejected"`` (a fast,
        bounded failure the caller can retry elsewhere); under ``"block"``
        submit drives ``step()`` until the fleet drains enough to accept.
        """
        if uid is None:
            uid, self._next_uid = self._next_uid, self._next_uid + 1
        h = make_handle(self, prompt, params, uid)
        self._next_uid = max(self._next_uid, h.uid + 1)  # explicit uids must
        # not collide with auto-assigned ones
        h.t_submit = self._clock()  # the engine clock owns all timestamps
        self.submitted += 1
        stop = frozenset(h.params.stop)
        if self.ecfg.eos_id is not None:
            stop |= {self.ecfg.eos_id}
        h._stop_ids = stop
        # the truncation that _admit will apply, surfaced at submit time
        h.truncated = len(h.prompt) > self.ecfg.capacity
        self.obs.request_submitted(h)
        never_fits = (self.ecfg.max_resident_tokens is not None
                      and self._committed_tokens(h)
                      > self.ecfg.max_resident_tokens)
        if self.paged and self._worst_pages(h) > self.alloc.n_pages:
            # an empty pool could not hold its worst case: shed now rather
            # than let the queue head wait for pages that can never free
            h.error = (f"page budget ({self._worst_pages(h)} worst-case "
                       f"pages > pool of {self.alloc.n_pages})")
            self._finish(h, FINISH_REJECTED, self._clock())
            return h
        if not self._admissible(h):
            if self.ecfg.admission_policy == "reject" or never_fits:
                # never_fits: blocking would spin forever — an empty engine
                # still could not hold it, so shed regardless of policy
                h.error = self._overload_reason(h)
                self._finish(h, FINISH_REJECTED, self._clock())
                return h
            while not self._admissible(h):  # "block": bounded latency is
                if not self.queue and all(s is None for s in self.slots):
                    # fully drained and still over cap: blocking could never
                    # succeed (e.g. every slot quarantined), so shed instead
                    h.error = self._overload_reason(h)
                    self._finish(h, FINISH_REJECTED, self._clock())
                    return h
                self.step()                 # traded for progress-coupled wait
        self.queue.append(h)
        return h

    def _committed_tokens(self, h: RequestHandle) -> int:
        """Token footprint a request commits the engine to: its clipped
        prompt plus its full generation budget."""
        return min(len(h.prompt), self.ecfg.capacity) + h.params.max_new_tokens

    def resident_tokens(self) -> int:
        """Committed tokens across queued + resident requests (the load
        number ``max_resident_tokens`` caps)."""
        live = list(self.queue) + [s for s in self.slots if s is not None]
        return sum(self._committed_tokens(h) for h in live)

    @property
    def clock(self):
        """The engine's injectable clock (``repro.runtime.clock`` duck type;
        a ``VirtualClock`` under a fault injector). Frontend layers stamp
        their timestamps through this so every layer shares one time base."""
        return self._clock

    def free_admissible_slots(self) -> int:
        """Slots a new admission could take right now (free and not
        quarantined) — what the frontend scheduler meters offers against."""
        return sum(1 for i, s in enumerate(self.slots)
                   if s is None and i not in self.quarantined)

    def _admissible(self, h: RequestHandle) -> bool:
        if self.ecfg.max_queue is not None \
                and len(self.queue) >= self.ecfg.max_queue:
            return False
        if self.ecfg.max_resident_tokens is not None \
                and self.resident_tokens() + self._committed_tokens(h) \
                > self.ecfg.max_resident_tokens:
            return False
        return True

    def _overload_reason(self, h: RequestHandle) -> str:
        if self.ecfg.max_queue is not None \
                and len(self.queue) >= self.ecfg.max_queue:
            return (f"queue full ({len(self.queue)}/{self.ecfg.max_queue} "
                    "waiting)")
        return (f"resident-token cap ({self.resident_tokens()} committed + "
                f"{self._committed_tokens(h)} requested > "
                f"{self.ecfg.max_resident_tokens})")

    # -------------------------------------------------- paged KV internals
    def _worst_pages(self, h: RequestHandle) -> int:
        """Worst-case physical pages a request can ever hold at once: its
        committed tokens in pages, clipped to the slot's logical ring (a
        wrapping request reuses its own pages). This is exactly what
        admission reserves — shared prefix pages reduce *fresh* demand but
        wrap-bound requests pre-reserve matching COW fork targets, so the
        pool draw is this number regardless of cache luck."""
        ps = self.ecfg.page_size
        return min(-(-self._committed_tokens(h) // ps), self._per_slot)

    def _plan_pages(self, h: RequestHandle):
        """Reserve the whole worst-case page budget for ``h`` up front, or
        return None if the pool can't cover it yet (the queue head then
        waits — FIFO, nothing jumps it).

        Returns (prompt, shared, fresh, reserve, cacheable):
          shared   — prefix-cache pages adopted read-only (logical pages
                     0..len(shared)-1; their tokens skip prefill entirely);
          fresh    — private pages for the rest of the logical ring;
          reserve  — unmapped COW fork targets, one per shared page, taken
                     only when generation will wrap the ring (every shared
                     page is then eventually overwritten and must fork —
                     reserving at admission makes the fork infallible);
          cacheable — whether this row's own prompt pages may be published
                     (truncated prompts never: their page keys would claim
                     tokens the row didn't see; wrap-bound rows never:
                     their prompt pages get overwritten by generation).

        The skipped-prefix length is trimmed to a multiple of
        ``prefill_chunk`` so a warm run replays the cold run's exact
        prefill dispatch sequence from the skip point — chunk boundaries,
        and therefore logits, stay deterministic under cache hits.
        """
        ps, cap = self.ecfg.page_size, self.ecfg.capacity
        prompt = list(h.prompt[-cap:])
        plen = len(prompt)
        will_wrap = plen + h.params.max_new_tokens > cap
        n_req = self._worst_pages(h)
        shared: List[int] = []
        n_keys = 0
        if self._prefix_reuse and not h.truncated:
            # page j is lookup-able iff fully prompt-filled; at least one
            # token always prefills (the finisher samples from the last
            # prompt position's logits)
            n_keys = (plen - 1) // ps
            shared = self.alloc.cache_lookup(
                [tuple(prompt[:(j + 1) * ps]) for j in range(n_keys)])
            chunk = self.ecfg.prefill_chunk
            while shared and (len(shared) * ps) % chunk:
                self.alloc.release(shared.pop())  # determinism trim
        need = n_req - len(shared) + (len(shared) if will_wrap else 0)
        if self.alloc.available() < need:
            for pid in shared:
                self.alloc.release(pid)
            return None
        fresh = self.alloc.alloc(need)
        reserve = fresh[n_req - len(shared):]
        fresh = fresh[:n_req - len(shared)]
        self.alloc.hits += len(shared)
        self.alloc.misses += 1 if n_keys > len(shared) else 0
        cacheable = (self._prefix_reuse and not h.truncated
                     and not will_wrap)
        return prompt, shared, fresh, reserve, cacheable

    def _page_maintenance(self, copies=(), clear=()):
        """Apply COW copies + fresh-page invalidation on device and push
        the host page tables (one fused jitted dispatch; index operands are
        power-of-two padded with the null page so compile count stays
        O(log pool))."""
        def pad(ids):
            out = list(ids)
            out += [0] * (_pow2ceil(max(len(out), 1)) - len(out))
            return jnp.asarray(out, jnp.int32)

        if self._maint_jit is None:
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._maint_jit = jax.jit(_page_maint_impl,
                                      donate_argnums=donate)
        with self.obs.span("page_maint",
                           args={"copies": len(copies), "clear": len(clear)}):
            self.state = self._maint_jit(
                self.state, pad([s for s, _ in copies]),
                pad([d for _, d in copies]), pad(clear),
                jnp.asarray(self._tables))
        self._tables_dirty = False

    def _fork_writes(self, spans):
        """Copy-on-write, before the dispatch that writes: for each
        upcoming write span (slot, first position, token count), any
        touched logical page whose physical page is shared (ref > 1 — held
        by the prefix cache and/or another slot) forks to this row's
        pre-reserved target; readers keep the original bit-for-bit.
        Spans are worst case (a row may freeze mid-chunk): a wasted fork
        costs one page copy, never correctness."""
        ps = self.ecfg.page_size
        copies = []
        for slot, start, n in spans:
            if n <= 0:
                continue
            for p in range(start // ps, (start + n - 1) // ps + 1):
                j = p % self._per_slot
                pid = int(self._tables[slot, j])
                if pid == 0 or self.alloc.ref[pid] <= 1:
                    continue
                new = self._reserve[slot].pop()
                self._tables[slot, j] = new
                self._tables_dirty = True
                copies.append((pid, new))
                self.alloc.release(pid)
                self.alloc.forks += 1
        if copies:
            self._page_maintenance(copies=copies)

    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel a request (``RequestHandle.cancel`` delegates here).

        Queued → removed before it ever admits; resident → its slot frees
        *immediately*, mid-prefill or mid-decode, and the next admission
        reuses it (the admission row-reset clears whatever the cancelled
        request left in the KV cache, so neighbors never see it). Already
        finished → no-op, returns False.
        """
        if handle.done:
            return False
        try:
            self.queue.remove(handle)
        except ValueError:
            slot = next((i for i, h in enumerate(self.slots) if h is handle),
                        None)
            if slot is None:
                return False  # not ours
            self._free_slot(slot)
        self._finish(handle, FINISH_CANCELLED, self._clock())
        return True

    def run(self, max_steps: int = 10_000) -> List[RequestHandle]:
        """Drive until queue + slots drain; returns the finished handles.
        Cancelled requests are not returned."""
        finished: List[RequestHandle] = []
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                break
            finished.extend(self.step())
        return finished

    def warmup(self):
        """Precompile every dispatch the engine can ever need.

        Feasible *because* the dispatch set is bounded: prefill buckets are
        the powers of two up to prefill_chunk and decode chunks the powers
        of two up to decode_chunk, each in a masked (top-k/top-p fleet) and
        unmasked sampling variant — a few dozen programs, not one per
        prompt length. (The only lazily compiled stragglers are stop-set
        width buckets > 1, for fleets using multi-token ``stop`` sets.)
        Every warm call is a semantic no-op on the live state (lengths=0
        rows / active=False rows / empty reset mask), so warmup can run at
        any point in the engine's life.
        """
        self._warm_prefill()
        nb = len(self.slots)
        chunks = {min(self.ecfg.decode_chunk, n)
                  for n in self._bucket_lengths(self.ecfg.decode_chunk)}
        chunks.add(min(self.ecfg.decode_chunk,
                       self.ecfg.decode_chunk_prefilling))
        idle = jnp.zeros((nb,), bool)
        z32 = jnp.zeros((nb,), jnp.int32)
        use_poison = self._injector is not None
        for n in sorted(chunks):
            for masked in (False, True):
                self.state, _ = self._loop_fn(n, masked, 1, use_poison)(
                    self._serve_params, self.state,
                    jnp.asarray(self.last_tokens),
                    jnp.zeros((nb,), jnp.float32), idle,
                    jnp.zeros((nb,), jnp.uint32), z32, z32,
                    jnp.ones((nb,), jnp.float32),
                    jnp.full((nb, 1), -1, jnp.int32),
                    jnp.full((nb,), -1, jnp.int32))
        self._reset_rows(np.zeros((nb,), bool))

    def _warm_prefill(self):
        nb = len(self.slots)
        for length in self._bucket_lengths(self.ecfg.prefill_chunk):
            _, self.state = self._prefill_fn(length)(
                self._serve_params, self.state,
                jnp.zeros((nb, length), jnp.int32),
                jnp.zeros((nb,), jnp.int32))

    @staticmethod
    def _bucket_lengths(top: int) -> List[int]:
        out = [1]
        while out[-1] < _pow2ceil(top):
            out.append(out[-1] * 2)
        return out

    def compile_stats(self) -> Dict[str, Any]:
        """Jit-cache occupancy — the compile-bound story, made observable.

        The bucketed scheduler's prefill entries are power-of-two chunk
        lengths ≤ prefill_chunk, so ``n_prefill_compiles`` is bounded by
        ``prefill_bucket_bound`` = log2(next_pow2(prefill_chunk)) + 1; the
        decode entries are (power-of-two chunk length ≤ decode_chunk,
        masked-sampling?, stop-width bucket, poison-injection?) quadruples
        — the last axis only ever True under a fault injector, so the
        production cache stays the PR-5 triple set. The serial-admit
        baseline instead caches one prefill entry per distinct prompt
        length (up to `capacity` of them).
        """
        return {
            "prefill_bucket_lengths": sorted(self._prefill_cache),
            "n_prefill_compiles": len(self._prefill_cache),
            "prefill_bucket_bound":
                _pow2ceil(self.ecfg.prefill_chunk).bit_length(),
            "decode_chunk_lengths": sorted({k[0] for k in self._loop_cache}),
            "n_decode_compiles": len(self._loop_cache),
            "admits": self.admits,
            "prefill_steps": self.prefill_steps,
        }

    def memory_stats(self) -> Dict[str, Any]:
        """Resident serving-state byte accounting (the boot-breakdown /
        attention-memory-bench numbers, computed not estimated).

        ``preunpack_decode`` trades plane bytes for per-step unpack work:
        the resident planes are raw int8 trits (1 byte/trit) instead of the
        packed 2-bit fields (0.25 byte/trit), so ``resident_plane_bytes``
        is 4x ``packed_plane_bytes`` while it is on — and a bench that only
        counted the packed artifact would understate resident state by
        exactly that ratio. ``decode_state_bytes`` is the live batch state
        (KV rings + recurrent states + positions) at this engine's
        (max_slots, capacity).
        """
        def plane_bytes(tree) -> int:
            return sum(
                int(leaf.t1p.nbytes) + int(leaf.t2p.nbytes)
                for leaf in jax.tree.leaves(
                    tree, is_leaf=lambda x: isinstance(x, QuantizedKernel))
                if isinstance(leaf, QuantizedKernel))

        packed = plane_bytes(self.params)
        resident = plane_bytes(self._serve_params)
        param_bytes = sum(int(x.nbytes)
                          for x in jax.tree.leaves(self._serve_params))
        state_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(self.state))
        out = {
            "preunpack_decode": self.preunpack_decode,
            "packed_plane_bytes": packed,
            "resident_plane_bytes": resident,
            "preunpack_ratio": (resident / packed) if packed else 1.0,
            "param_bytes": param_bytes,
            "decode_state_bytes": state_bytes,
            "resident_total_bytes": param_bytes + state_bytes,
            "kv_layout": self.ecfg.kv_layout,
        }
        out.update(self._kv_bytes())
        return out

    def _kv_bytes(self) -> Dict[str, Any]:
        """KV-cache byte accounting by leaf name. Under the ring layout the
        whole allocation is resident per slot; under paging only *used*
        pages hold live KV — ``kv_resident_bytes`` is what a request
        actually costs, the number the paged-KV bench turns into
        requests/GB."""
        pool_bytes = table_bytes = kv_bytes = 0
        n_phys = 1

        def walk(node, path):
            nonlocal pool_bytes, table_bytes, kv_bytes, n_phys
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, f"{path}/{k}")
                return
            name = path.rsplit("/", 1)[-1]
            if name.startswith("pages_"):
                pool_bytes += int(node.nbytes)
                n_phys = node.shape[_row_axis(path)]
            elif name == "table":
                table_bytes += int(node.nbytes)
            elif name in ("k", "v", "k_scale", "v_scale") \
                    or (name == "pos" and path != "/pos"):
                kv_bytes += int(node.nbytes)

        walk(self.state, "")
        if not self.paged:
            return {"kv_pool_bytes": kv_bytes, "kv_resident_bytes": kv_bytes}
        per_page = pool_bytes // n_phys  # one physical page, all layers
        return {"kv_pool_bytes": pool_bytes + table_bytes,
                "kv_page_bytes": per_page,
                # used pages + the always-resident null page + the tables
                "kv_resident_bytes":
                    per_page * (self.alloc.used_pages() + 1) + table_bytes}

    # ----------------------------------------------------------------- step
    def step(self) -> List[RequestHandle]:
        """Sweep deadlines, admit into all free slots, advance prefill one
        chunk, decode one chunk; returns the requests that finished this
        step (including ones retired by the sweep or fault containment).

        The decode chunk length adapts to the largest remaining token budget
        among decoding slots, rounded up to a power of two (compile count
        stays O(log K)) — a fleet that only needs 3 more tokens never pays
        for a 16-step dispatch.
        """
        with self.obs.step_annotation(self.engine_steps + 1):
            return self._step()

    def _step(self) -> List[RequestHandle]:
        obs = self.obs
        t_step0, tok0, churn0 = self._step_begin()
        self.engine_steps += 1
        if self._injector is not None:
            self._injector.on_step(self)
        with obs.span("sweep"):
            done_now = self._sweep_deadlines()
            self._auto_rehabilitate()
        with obs.span("admit"):
            self._admit()
        done_now += self._admit_finished
        self._admit_finished = []
        done_now = done_now + self._prefill_step()
        dec = [i for i in range(len(self.slots)) if self._decoding(i)]
        if not dec:
            self._step_end(t_step0, tok0, churn0)
            return done_now
        remaining = max(self.slots[i].params.max_new_tokens
                        - len(self.slots[i].output) for i in dec)
        chunk = self.ecfg.decode_chunk
        if any(self._prefilling(i) for i in range(len(self.slots))):
            chunk = min(chunk, self.ecfg.decode_chunk_prefilling)
        n_steps = min(chunk, _pow2ceil(remaining))
        if self.paged:
            # decode writes positions pos..pos+n_steps-1 (worst case); a
            # wrapping row is about to overwrite its oldest pages, which
            # may be cache-shared prefix — fork them first (COW)
            self._fork_writes(
                [(i, len(self._prompts[i]) + len(self.slots[i].output) - 1,
                  n_steps) for i in dec])
            if self._tables_dirty:
                self._page_maintenance()
        use_poison = self._injector is not None
        with obs.span("decode_prepare"):
            (temps, active, seeds, top_k, top_p, stops), use_mask, stop_w = \
                self._fleet_arrays()
            # tokens generated so far per row: the on-device draw for a
            # row's i-th token always uses fold_in(PRNGKey(seed), i),
            # independent of where the chunk boundaries fell
            gen0 = jnp.asarray([len(self.slots[i].output)
                                if self._decoding(i) else 0
                                for i in range(len(self.slots))], jnp.int32)
            poison = self._poison_array(gen0, n_steps) if use_poison \
                else jnp.full((len(self.slots),), -1, jnp.int32)
        try:
            self._guard_dispatch("decode", dec)
            with obs.span("decode_dispatch",
                          args={"n_steps": n_steps, "rows": len(dec)}):
                self.state, (toks, bad) = self._loop_fn(
                    n_steps, use_mask, stop_w, use_poison)(
                    self._serve_params, self.state,
                    jnp.asarray(self.last_tokens),
                    temps, active, seeds, gen0, top_k, top_p, stops, poison)
        except EngineCrash as exc:  # engine death escapes containment
            self._attribute_crash(exc, dec)
            raise
        except Exception as exc:  # containment unit: this dispatch only
            done_now = done_now + self._contain("decode", dec, exc)
            self._step_end(t_step0, tok0, churn0)
            return done_now
        self.steps += n_steps
        with obs.span("decode_sync"):
            toks_np, bad_np = np.asarray(toks), np.asarray(bad)
        with obs.span("collect"):
            done_now = done_now + self._collect(toks_np, bad_np)
        self._step_end(t_step0, tok0, churn0)
        return done_now

    def _step_begin(self) -> Tuple[float, int, int]:
        churn = (self.alloc.allocs + self.alloc.releases) if self.paged else 0
        return self._clock(), self.tokens_generated, churn

    def _step_end(self, t0: float, tok0: int, churn0: int):
        """Per-step observations (always on — host-side arithmetic only):
        step duration, tokens delivered this step, page churn this step,
        plus the enclosing "step" trace span when tracing."""
        obs = self.obs
        now = self._clock()
        obs.h_step.observe(now - t0)
        obs.h_tokens_step.observe(self.tokens_generated - tok0)
        if self.paged:
            obs.h_page_churn.observe(
                self.alloc.allocs + self.alloc.releases - churn0)
        if obs.trace is not None:
            obs.trace.complete("step", TRACK_ENGINE, t0, now, cat="engine",
                               args={"engine_step": self.engine_steps})

    # ------------------------------------------------- deadlines / containment
    def _expired(self, h: RequestHandle, now: float) -> Optional[str]:
        p = h.params
        if p.deadline_s is not None and now - h.t_submit > p.deadline_s:
            return f"deadline_s={p.deadline_s} exceeded"
        if p.ttft_deadline_s is not None and not h.t_first \
                and now - h.t_submit > p.ttft_deadline_s:
            return f"ttft_deadline_s={p.ttft_deadline_s} exceeded"
        return None

    def _sweep_deadlines(self) -> List[RequestHandle]:
        """Retire every queued or resident request past its deadline with
        frozen reason ``"timeout"``. Freed slots are reusable at this very
        step's admission; neighbors are bit-unperturbed (the same guarantee
        cancellation gives — retirement only ever *removes* a row)."""
        now = self._clock()
        out: List[RequestHandle] = []
        for h in list(self.queue):
            why = self._expired(h, now)
            if why is not None:
                self.queue.remove(h)
                h.error = why
                self._finish(h, FINISH_TIMEOUT, now)
                out.append(h)
        for slot, h in enumerate(self.slots):
            if h is None:
                continue
            why = self._expired(h, now)
            if why is not None:
                self._free_slot(slot)
                h.error = why
                self._finish(h, FINISH_TIMEOUT, now)
                out.append(h)
        return out

    def _poison_array(self, gen0, n_steps: int):
        """(B,) int32 gen-index at which to NaN each row's logits, -1 =
        never (asked of the injector per decode dispatch)."""
        nb = len(self.slots)
        poison = np.full((nb,), -1, np.int32)
        g = np.asarray(gen0)
        for i in range(nb):
            if not self._decoding(i):
                continue
            k = self._injector.poison_index(self.slots[i].uid, int(g[i]),
                                            n_steps)
            if k is not None:
                poison[i] = k
        return jnp.asarray(poison)

    def _guard_dispatch(self, kind: str, slots: List[int]):
        """Count the dispatch and let the injector veto it (raising
        :class:`EngineFault`) — injected faults fire *before* the device
        call so the batch state is never half-written."""
        idx = self._dispatch_counts[kind]
        self._dispatch_counts[kind] = idx + 1
        if self._injector is not None:
            self._injector.before_dispatch(self, kind, idx, slots)

    def _attribute_crash(self, exc: "EngineCrash", slots: List[int]) -> None:
        """Stamp an escaping :class:`EngineCrash` with its suspects: the
        blamed uid when it is resident in the dying dispatch, else every
        participating row — the supervisor retires/blacklists from this."""
        if exc.suspects:
            return
        uids = [self.slots[i].uid for i in slots if self.slots[i] is not None]
        if exc.uid is not None and exc.uid in uids:
            exc.suspects = (exc.uid,)
        else:
            exc.suspects = tuple(uids)

    def _contain(self, kind: str, slots: List[int],
                 exc: Exception) -> List[RequestHandle]:
        """Quarantine a failed dispatch to the offending request/slot.

        An :class:`EngineFault` carrying a slot retires exactly that
        request; an unattributed exception retires every request that
        participated in the dispatch (the honest containment unit — their
        rows' states cannot be trusted). Either way the slot(s) are marked
        suspect and leave the admission pool until :meth:`rehabilitate`,
        and the engine keeps stepping: the dispatch that failed was never
        applied, so surviving rows retry it untouched next step.
        """
        hit = getattr(exc, "slot", None)
        bad_slots = [hit] if hit is not None and hit in slots else list(slots)
        now = self._clock()
        out: List[RequestHandle] = []
        for slot in bad_slots:
            h = self.slots[slot]
            if h is None:
                continue
            self._free_slot(slot)
            self._quarantine(slot)
            h.error = f"{kind} dispatch failed: {exc!r}"
            self._finish(h, FINISH_ERROR, now)
            out.append(h)
        return out

    def _quarantine(self, slot: int):
        cool = self.ecfg.quarantine_steps
        until = (self.engine_steps + cool) if cool is not None else -1
        self.quarantined[slot] = until

    def _restore(self, slots: List[int]):
        mask = np.zeros((len(self.slots),), bool)
        mask[slots] = True
        self._reset_rows(mask)
        for s in slots:
            self.quarantined.pop(s, None)
        self._slot_arrays = None

    def _auto_rehabilitate(self):
        """Return suspect slots whose cool-down elapsed to the pool (after
        a row reset). ``quarantine_steps=None`` disables — only an explicit
        :meth:`rehabilitate` restores them."""
        if self.ecfg.quarantine_steps is None:
            return
        due = [s for s, until in self.quarantined.items()
               if self.engine_steps >= until]
        if due:
            self._restore(due)

    def rehabilitate(self) -> List[int]:
        """Row-reset every quarantined slot and return it to the admission
        pool immediately; returns the slots restored. (The operator
        override of the ``quarantine_steps`` cool-down.)"""
        back = sorted(self.quarantined)
        if back:
            self._restore(back)
        return back

    def health(self) -> HealthSnapshot:
        """Current engine health (see :class:`repro.runtime.monitor.
        HealthSnapshot`); cheap — every field is a read of the same
        registry counters/gauges the observability bundle exports, so a
        snapshot and a metrics scrape can never disagree."""
        reg = self.obs.registry
        pages = {}
        if self.paged:
            pages = dict(
                pages_free=reg.value("serving_pages_free"),
                pages_used=reg.value("serving_pages_used"),
                pages_shared=reg.value("serving_pages_shared"),
                prefix_hits=reg.value("serving_prefix_hits_total"),
                prefix_misses=reg.value("serving_prefix_misses_total"),
                prefix_evictions=reg.value("serving_prefix_evictions_total"))
        return HealthSnapshot(
            t=self._clock(), steps=self.steps,
            queue_depth=reg.value("serving_queue_depth"),
            resident=reg.value("serving_resident_slots"),
            free_slots=reg.value("serving_free_slots"),
            quarantined_slots=tuple(sorted(self.quarantined)),
            resident_tokens=reg.value("serving_resident_tokens"),
            completed=reg.value("serving_requests_completed_total"),
            cancelled=reg.value("serving_requests_cancelled_total"),
            sheds=reg.value("serving_requests_shed_total"),
            timeouts=reg.value("serving_requests_timeout_total"),
            errors=reg.value("serving_requests_error_total"),
            **pages)

    # ------------------------------------------------------------- internals
    def _prefilling(self, slot: int) -> bool:
        return (self.slots[slot] is not None
                and self._cursor[slot] < len(self._prompts[slot]))

    def _decoding(self, slot: int) -> bool:
        return (self.slots[slot] is not None
                and self._cursor[slot] >= len(self._prompts[slot]))

    def _free_slot(self, slot: int):
        if self.paged and self.slots[slot] is not None:
            # retirement — every retirement path (finish, cancel, timeout,
            # error containment) funnels through here, so pages always
            # return: table refs drop (cache-held pages survive at ref 1,
            # evictable; private pages free instantly), unused COW
            # reserves free, and the device table row goes stale-but-
            # harmless (lengths-0/inactive rows are fully masked) until
            # the next maintenance push
            for pid in self._tables[slot]:
                if pid:
                    self.alloc.release(int(pid))
            for pid in self._reserve[slot]:
                self.alloc.release(pid)
            self._reserve[slot] = []
            self._tables[slot, :] = 0
            self._registered[slot] = 0
            self._cacheable[slot] = False
            self._tables_dirty = True
        self.slots[slot] = None
        self._prompts[slot] = None
        self._cursor[slot] = 0
        self._slot_arrays = None

    def _mark_first(self, h: RequestHandle, now: float):
        if not h.t_first:
            h.t_first = now
            self.obs.request_first_token(h)

    def _finish(self, h: RequestHandle, reason: str, now: float):
        h.finish_reason = reason
        h.t_done = now
        if reason in (FINISH_STOP, FINISH_LENGTH):
            self.completed += 1
        elif reason == FINISH_CANCELLED:
            self.cancelled += 1
        elif reason == FINISH_TIMEOUT:
            self.timeouts += 1
        elif reason == FINISH_REJECTED:
            self.sheds += 1
        elif reason == FINISH_ERROR:
            self.errors += 1
        # every retirement path funnels through here — the single place
        # the lifecycle spans and completion histograms are emitted
        self.obs.request_retired(h, h._slot)

    def _fleet_arrays(self):
        """Per-slot device arrays for the decode dispatch, cached until the
        fleet changes: (temps, active, seeds, top_k, top_p, stops) plus the
        static (use_mask, stop_width) pair that keys the loop variant."""
        if self._slot_arrays is None:
            nb = len(self.slots)
            temps = np.zeros((nb,), np.float32)
            seeds = np.zeros((nb,), np.uint32)
            top_k = np.zeros((nb,), np.int32)
            top_p = np.ones((nb,), np.float32)
            stop_sets: List[List[int]] = [[] for _ in range(nb)]
            use_mask = False
            for i in range(nb):
                if not self._decoding(i):
                    continue
                p = self.slots[i].params
                temps[i] = p.temperature
                seeds[i] = p.seed & 0xFFFFFFFF
                top_k[i] = p.top_k
                top_p[i] = p.top_p
                stop_sets[i] = sorted(self.slots[i]._stop_ids)
                use_mask |= p.needs_mask
            stop_w = _pow2ceil(max(1, max(len(s) for s in stop_sets)))
            stops = np.full((nb, stop_w), -1, np.int32)
            for i, s in enumerate(stop_sets):
                stops[i, :len(s)] = s
            active = np.asarray([self._decoding(i) for i in range(nb)])
            self._slot_arrays = (
                tuple(jnp.asarray(a) for a in
                      (temps, active, seeds, top_k, top_p, stops)),
                use_mask, stop_w)
        return self._slot_arrays

    def _loop_fn(self, n_steps: int, use_mask: bool, stop_w: int,
                 use_poison: bool = False):
        key = (n_steps, use_mask, stop_w, use_poison)
        if key not in self._loop_cache:
            # Donating the decode state lets XLA update the KV caches in
            # place; CPU has no donation support and would warn per dispatch.
            donate = (1,) if jax.default_backend() != "cpu" else ()
            self._loop_cache[key] = jax.jit(
                functools.partial(_decode_loop, cfg=self.cfg,
                                  n_steps=n_steps, use_mask=use_mask,
                                  use_poison=use_poison),
                donate_argnums=donate)
        return self._loop_cache[key]

    def _prefill_fn(self, length: int):
        """One jit per power-of-two chunk bucket (O(log prefill_chunk))."""
        if length not in self._prefill_cache:
            cfg = self.cfg
            donate = (1,) if jax.default_backend() != "cpu" else ()

            def impl(params, state, tokens, lengths):
                return prefill_chunk(params, cfg, state, {"tokens": tokens},
                                     lengths)

            self._prefill_cache[length] = jax.jit(impl, donate_argnums=donate)
        return self._prefill_cache[length]

    def _reset_rows(self, mask: np.ndarray, pos0=None):
        if self._reset_jit is None:
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._reset_jit = jax.jit(_reset_rows_impl, donate_argnums=donate)
        if pos0 is None:
            pos0 = np.zeros((len(self.slots),), np.int32)
        self.state = self._reset_jit(self.state, jnp.asarray(mask),
                                     jnp.asarray(pos0))

    def _admit(self):
        """Drain the wait queue into *all* free, non-quarantined slots in
        one go. Under the paged layout a slot admits only when the queue
        head's worst-case page budget is reservable right now; otherwise
        the head waits (strict FIFO — a shorter request behind it never
        jumps the line) until retirements return pages to the pool."""
        fresh_rows = []
        pos0 = np.zeros((len(self.slots),), np.int32)
        clear: List[int] = []
        for slot in range(len(self.slots)):
            if self.slots[slot] is not None or not self.queue \
                    or slot in self.quarantined:
                continue
            page_args = None
            if self.paged:
                plan = self._plan_pages(self.queue[0])
                if plan is None:
                    break  # head waits for pages; FIFO holds
                prompt, shared, fresh, reserve, cacheable = plan
                h = self.queue.popleft()
                self.slots[slot] = h
                self._prompts[slot] = prompt
                skip = len(shared) * self.ecfg.page_size
                self._cursor[slot] = skip   # cache-hit tokens never prefill
                pos0[slot] = skip
                ids = shared + fresh
                self._tables[slot, :] = 0
                self._tables[slot, :len(ids)] = ids
                self._tables_dirty = True
                self._registered[slot] = len(shared)
                self._cacheable[slot] = cacheable
                self._reserve[slot] = reserve
                clear.extend(fresh)
                page_args = {"pages_shared": len(shared),
                             "pages_fresh": len(fresh),
                             "pages_reserved": len(reserve)}
            else:
                h = self.queue.popleft()
                self.slots[slot] = h
                self._prompts[slot] = list(h.prompt[-self.ecfg.capacity:])
                self._cursor[slot] = 0
            h.t_admit = self._clock()
            h._slot = slot
            self.obs.request_admitted(h, slot, pages=page_args)
            fresh_rows.append(slot)
            self.admits += 1
        if fresh_rows:
            mask = np.zeros((len(self.slots),), bool)
            mask[fresh_rows] = True
            self._reset_rows(mask, pos0)
            if self.paged:
                self._page_maintenance(clear=clear)
            self._slot_arrays = None

    def _sample_first(self, logits, rows: List[int]) -> np.ndarray:
        """Token 0 for every row in ``rows`` (whose prompt just completed),
        drawn from each request's own stream — ``fold_in(PRNGKey(seed), 0)``
        — with its top-k/top-p support; other rows ride along as greedy and
        are ignored by the caller."""
        nb = logits.shape[0]
        rs = set(rows)
        p = {i: self.slots[i].params for i in rows}
        temps = jnp.asarray([p[i].temperature if i in rs else 0.0
                             for i in range(nb)], jnp.float32)
        seeds = jnp.asarray([p[i].seed & 0xFFFFFFFF if i in rs else 0
                             for i in range(nb)], jnp.uint32)
        keys = request_keys(seeds, jnp.zeros((nb,), jnp.int32))
        tk = tp = None
        if any(p[i].needs_mask for i in rows):
            tk = jnp.asarray([p[i].top_k if i in rs else 0
                              for i in range(nb)], jnp.int32)
            tp = jnp.asarray([p[i].top_p if i in rs else 1.0
                              for i in range(nb)], jnp.float32)
        return np.asarray(sample_tokens_per_request(
            logits, keys, temps, top_k=tk, top_p=tp))

    def _prefill_step(self) -> List[RequestHandle]:
        """Advance every mid-prompt slot by one bucketed chunk.

        All prefilling rows share one fixed-(B, L) dispatch: L is the
        power-of-two bucket of the longest remaining need this step (capped
        at prefill_chunk); rows with shorter remainders right-pad, rows not
        prefilling ride along with length 0 (no-op). Rows whose prompt
        completes sample their first token here — so a streamed first token
        lands in the same engine step that finishes its prefill — and join
        the decode fleet the same step.
        """
        pf = [i for i in range(len(self.slots)) if self._prefilling(i)]
        if not pf:
            return []
        obs = self.obs
        with obs.span("prefill_prepare"):
            nb = len(self.slots)
            need = max(min(len(self._prompts[i]) - self._cursor[i],
                           self.ecfg.prefill_chunk) for i in pf)
            length = _pow2ceil(need)
            tokens = np.zeros((nb, length), np.int32)
            lengths = np.zeros((nb,), np.int32)
            for i in pf:
                # never consume more than prefill_chunk per step, even when
                # the pow2 bucket rounds past it (non-pow2 prefill_chunk)
                take = min(len(self._prompts[i]) - self._cursor[i],
                           self.ecfg.prefill_chunk)
                tokens[i, :take] = self._prompts[i][
                    self._cursor[i]:self._cursor[i] + take]
                lengths[i] = take
        if self.paged:
            # prefill only ever writes this row's private unregistered
            # pages (skip starts past the shared prefix and registration
            # trails the cursor), so these are no-ops — kept as the single
            # COW choke point guarding *every* write dispatch
            self._fork_writes([(i, self._cursor[i], int(lengths[i]))
                               for i in pf])
            if self._tables_dirty:
                self._page_maintenance()
        t_pf0 = self._clock()
        try:
            self._guard_dispatch("prefill", pf)
            with obs.span("prefill_dispatch",
                          args={"bucket": length, "rows": len(pf)}):
                logits, self.state = self._prefill_fn(length)(
                    self._serve_params, self.state, jnp.asarray(tokens),
                    jnp.asarray(lengths))
        except EngineCrash as exc:  # engine death escapes containment
            self._attribute_crash(exc, pf)
            raise
        except Exception as exc:  # cursors untouched: survivors retry as-is
            return self._contain("prefill", pf, exc)
        t_pf1 = self._clock()
        obs.h_prefill_chunk.observe(t_pf1 - t_pf0)
        self.prefill_steps += 1
        self.prefill_tokens += int(lengths.sum())
        finishers = [i for i in pf
                     if self._cursor[i] + int(lengths[i])
                     >= len(self._prompts[i])]
        for i in pf:
            self._cursor[i] += int(lengths[i])
            obs.prefill_chunk(self.slots[i], i, t_pf0, t_pf1,
                              int(lengths[i]), self._cursor[i])
        if not finishers:
            return []
        if self._injector is not None:
            # token 0's logits can be poisoned too (gen index 0 lives in the
            # prefill finisher, not the decode loop); row-local, so
            # co-batched rows keep their exact logits
            for i in finishers:
                if self._injector.poison_index(self.slots[i].uid, 0, 1) == 0:
                    logits = logits.at[i].set(jnp.nan)
        # non-finite logits are contained *before* sampling: the offending
        # row retires with "error", finite rows sample from untouched logits
        with obs.span("prefill_sync"):
            row_ok = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
        if self.paged:
            # registration rides the finisher sync that happens anyway — a
            # per-chunk publish would cost a blocking device round-trip on
            # every prefill step
            self._register_pages(finishers, row_ok)
        now = self._clock()
        finished: List[RequestHandle] = []
        bad_rows = [i for i in finishers if not row_ok[i]]
        for i in bad_rows:
            h = self.slots[i]
            self._free_slot(i)
            self._quarantine(i)
            h.error = "non-finite logits at prefill completion"
            self._finish(h, FINISH_ERROR, now)
            finished.append(h)
        finishers = [i for i in finishers if row_ok[i]]
        if not finishers:
            return finished
        # the prompt's last logits yield the first generated token; one
        # vectorized sample covers every finishing row
        with obs.span("sample_collect", args={"rows": len(finishers)}):
            toks = self._sample_first(logits, finishers)
            for i in finishers:
                h = self.slots[i]
                tok = int(toks[i])
                h.output.append(tok)
                self.tokens_generated += 1
                self._mark_first(h, now)
                # the prefill-sampled token may already terminate the
                # request — on eos_id *or* any SamplingParams.stop id
                if tok in h._stop_ids:
                    self._finish(h, FINISH_STOP, now)
                elif len(h.output) >= h.params.max_new_tokens:
                    self._finish(h, FINISH_LENGTH, now)
                else:
                    self.last_tokens[i] = tok
                    self._slot_arrays = None
                    continue
                finished.append(h)
                self._free_slot(i)
        return finished

    def _register_pages(self, finishers: List[int], row_ok):
        """Publish a finished prompt's fully-filled pages to the prefix
        cache, at prefill completion (the step that already syncs logits
        for the first token — containment granularity, PR 6). A row whose
        completion logits are non-finite never publishes — its KV pages
        can't be trusted and must never splice into other requests.
        """
        ps = self.ecfg.page_size
        for i in finishers:
            if not self._cacheable[i]:
                continue
            if not row_ok[i]:
                self._cacheable[i] = False
                continue
            prompt = self._prompts[i]
            upto = min(self._cursor[i], len(prompt)) // ps
            for j in range(self._registered[i], upto):
                self.alloc.cache_insert(tuple(prompt[:(j + 1) * ps]),
                                        int(self._tables[i, j]))
            self._registered[i] = upto

    def _collect(self, toks: np.ndarray,
                 bad: Optional[np.ndarray] = None) -> List[RequestHandle]:
        """Fold a (K, B) chunk of tokens into the per-slot requests.

        A slot stops at its first stop-token hit (any id in the request's
        ``stop`` set ∪ ``eos_id``) or at its token budget; anything the
        device generated past that point within the chunk is discarded (the
        slot's state is reset by the next admission). Slots still mid-prefill
        took no decode step — their repeated tokens are skipped entirely.

        ``bad`` (K, B) flags steps whose logits were non-finite for that
        row: the garbage token is *not* appended — the request retires with
        frozen reason ``"error"`` and the slot is quarantined, before the
        poisoned value can reach the stream.
        """
        finished = []
        now = self._clock()
        for slot, h in enumerate(self.slots):
            if h is None or not self._decoding(slot):
                continue
            for k in range(toks.shape[0]):
                if bad is not None and bad[k, slot]:
                    self._free_slot(slot)
                    self._quarantine(slot)
                    h.error = (f"non-finite logits at generated token "
                               f"{len(h.output)}")
                    self._finish(h, FINISH_ERROR, now)
                    finished.append(h)
                    break
                tok = int(toks[k, slot])
                h.output.append(tok)
                self.tokens_generated += 1
                self._mark_first(h, now)
                self.last_tokens[slot] = tok
                if tok in h._stop_ids:
                    self._finish(h, FINISH_STOP, now)
                elif len(h.output) >= h.params.max_new_tokens:
                    self._finish(h, FINISH_LENGTH, now)
                else:
                    continue
                finished.append(h)
                self._free_slot(slot)
                break
        return finished


class SerialAdmitEngine(ServingEngine):
    """The PR-1 admission path, kept as the measured baseline: each arriving
    request is prefilled *alone* through a jit cached per distinct prompt
    length (up to `capacity` compilations) and merged into its slot — the
    whole decode fleet stalls while the queue's prompts are consumed one by
    one. Decode (and the v1 handle/cancellation/per-request-RNG surface) is
    identical to `ServingEngine`, so a request's output is bit-identical
    across the two schedulers.
    """

    def __init__(self, params, model_cfg, engine_cfg: EngineConfig, *,
                 injector=None, observability: Optional[Observability] = None):
        if engine_cfg.kv_layout != "ring":
            raise ValueError(
                "SerialAdmitEngine prefills through prefill() into a "
                "private ring state and merges it by slot — the paged "
                "layout is a bucketed-scheduler feature; use "
                "kv_layout='ring' here")
        super().__init__(params, model_cfg, engine_cfg, injector=injector,
                         observability=observability)

    def _warm_prefill(self):
        # Best effort only: compiles the power-of-two prompt lengths, but
        # this engine's jit cache is keyed by *exact* prompt length — any
        # other arriving length still compiles at admission time, which is
        # exactly the TTFT pathology the bucketed scheduler removes.
        for length in self._bucket_lengths(self.ecfg.capacity):
            if length > self.ecfg.capacity:
                break
            self._prefill_len_fn(length)(
                self._serve_params, jnp.zeros((1, length), jnp.int32))

    def _merge(self, batch_state, one_state, slot):
        # hook: the decode benchmark's seed baseline overrides this with the
        # eager leaf-by-leaf merge it measures against
        return _merge_slot(batch_state, one_state, slot)

    @staticmethod
    def _sample_first_row(logits, keys, p: SamplingParams):
        """Token 0 for one batch-1 logits row — row-wise sampling is
        batch-size-invariant, so this matches the bucketed engine's fleet
        dispatch bit for bit."""
        tk = jnp.asarray([p.top_k], jnp.int32) if p.needs_mask else None
        tp = jnp.asarray([p.top_p], jnp.float32) if p.needs_mask else None
        return np.asarray(sample_tokens_per_request(
            logits, keys, jnp.asarray([p.temperature], jnp.float32),
            top_k=tk, top_p=tp))[0]

    def _prefill_len_fn(self, length: int):
        # one jit per distinct prompt length; prompts are clipped to
        # `capacity` on admit, so the cache is bounded by capacity entries
        if length not in self._prefill_cache:
            cfg, cap = self.cfg, self.ecfg.capacity

            @jax.jit
            def fn(params, tokens):
                return prefill(params, cfg, {"tokens": tokens}, capacity=cap)

            self._prefill_cache[length] = fn
        return self._prefill_cache[length]

    def _admit(self):
        for slot in range(len(self.slots)):
            if self.slots[slot] is not None or not self.queue \
                    or slot in self.quarantined:
                continue
            h = self.queue.popleft()
            self.admits += 1
            prompt = h.prompt[-self.ecfg.capacity:]
            self.slots[slot] = h          # resident before the dispatch so
            self._prompts[slot] = list(prompt)  # containment can attribute
            self._cursor[slot] = 0        # not decoding until token 0 lands
            h.t_admit = self._clock()
            h._slot = slot
            self.obs.request_admitted(h, slot)
            with self.obs.span("prefill_prepare"):
                fn = self._prefill_len_fn(len(prompt))
                tokens = jnp.asarray([prompt], jnp.int32)
            t_pf0 = self._clock()
            try:
                self._guard_dispatch("prefill", [slot])
                with self.obs.span("prefill_dispatch",
                                   args={"bucket": len(prompt), "rows": 1}):
                    logits, one_state = fn(self._serve_params, tokens)
            except EngineCrash as exc:  # engine death escapes containment
                self._attribute_crash(exc, [slot])
                raise
            except Exception as exc:  # serial admission: batch-1 containment
                self._admit_finished.extend(
                    self._contain("prefill", [slot], exc))
                continue
            self.state = self._merge(self.state, one_state, slot)
            self.prefill_steps += 1
            self.prefill_tokens += len(prompt)
            self.obs.h_prefill_chunk.observe(self._clock() - t_pf0)
            self.obs.prefill_chunk(h, slot, t_pf0, self._clock(),
                                   len(prompt), len(prompt))
            p = h.params
            if self._injector is not None \
                    and self._injector.poison_index(h.uid, 0, 1) == 0:
                logits = logits.at[0].set(jnp.nan)
            with self.obs.span("prefill_sync"):
                row_ok = bool(np.asarray(jnp.all(jnp.isfinite(logits[0]))))
            if not row_ok:
                self._free_slot(slot)
                self._quarantine(slot)
                h.error = "non-finite logits at prefill completion"
                self._finish(h, FINISH_ERROR, self._clock())
                self._admit_finished.append(h)
                continue
            # token 0 from the request's own stream (serial prefill logits
            # are batch-1: sample that one row directly)
            keys = request_keys(jnp.asarray([p.seed & 0xFFFFFFFF],
                                            jnp.uint32),
                                jnp.zeros((1,), jnp.int32))
            with self.obs.span("sample_collect", args={"rows": 1}):
                tok = int(self._sample_first_row(logits, keys, p))
            now = self._clock()
            h.output.append(tok)
            self.tokens_generated += 1
            self._mark_first(h, now)
            # the prefill-sampled token may already terminate the request
            if tok in h._stop_ids:
                self._finish(h, FINISH_STOP, now)
            elif len(h.output) >= h.params.max_new_tokens:
                self._finish(h, FINISH_LENGTH, now)
            else:
                self.last_tokens[slot] = tok
                # mark the prompt consumed → base class sees a decoding row
                self._cursor[slot] = len(prompt)
                self._slot_arrays = None
                continue
            self._admit_finished.append(h)
            self._free_slot(slot)
