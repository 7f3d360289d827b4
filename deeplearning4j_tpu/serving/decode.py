"""Continuous-batching generation: a persistent KV slot pool, one step.

``TransformerLM.generate`` compiles one whole-sequence scan per
(B, P, n_new, sampler) shape and runs it per request — every caller
pays full-batch decode alone. This module replaces that for serving:
the model's ``_build_decode_step`` program advances ``B_slots``
INDEPENDENT sequences by ``DL4J_TPU_SERVE_CHUNK`` tokens per dispatch
over a persistent ``[B_slots, kv_heads, max_len, hd]`` KV cache
(the bf16 ``_cache_dtype`` cache decode already uses); an active-row
mask and per-row position counters let the scheduler admit a NEW
request into a freed cache row mid-decode, so short and long
generations share the one compiled step instead of serializing.

Steady state is a SMALL FIXED ladder of compiled signatures — one
blessed ``_decode_signature(B_slots, chunk, W)`` step per KV window
rung (paged attention: each chunk dispatches at the smallest
``DL4J_TPU_SERVE_KV_LADDER`` rung covering the pool's max active
position, picked host-side off the existing position mirrors — zero
new syncs), one ``_prefill_signature(B_slots, W)`` program per
``DL4J_TPU_SERVE_PREFILL_LADDER`` rung (chunked prefill: a whole
window of prompt tokens per dispatch, interleaved with decode chunks
so a long prompt never stalls the active pool), and ONE
``_admit_signature(B_slots)`` slot writer — and ZERO steady-state
compiles. Prefill windows are memoised by prompt-prefix hash in a
byte-bounded LRU page cache (``DL4J_TPU_SERVE_PREFIX_CACHE_MB``), so a
repeated system prompt computes its KV once and later admissions
inject the cached pages instead of re-running the forward. Completion
is LENGTH-driven (the host mirrors every slot's position counter,
which advances by exactly ``chunk`` per dispatch for active rows), so
the scheduler never fetches tokens to decide what to do next; a slot's
``out`` row is fetched ONCE, when its request completes.

The first dispatch resolves ``B_slots``: an explicit
``DL4J_TPU_SERVE_SLOTS`` always wins; else a persisted decision from
the fusion autotuner's cache (``DL4J_TPU_TUNE_CACHE_DIR``); else, with
``DL4J_TPU_SERVE_AUTOTUNE`` armed, the ``DL4J_TPU_SERVE_SLOTS_LADDER``
is probed on the first full queue (dummy all-active chunks, losers
evicted from ``_jit_decode``, winner persisted through the
probe-and-persist protocol of ``tuning/autotuner.py``); else a
MEMORY-DERIVED default: the per-slot KV bytes (memlint's decode-row
``kv_cache`` formula) divided into the ``DL4J_TPU_MEM_BUDGET`` left
after parameters (the ROADMAP memory-as-scheduler item's first bite;
the derivation is logged). The resolved rung ladders persist beside
the slot decision in the autotuner cache, so a restarted server
re-arms the same compiled-program inventory. Sampling: per-slot
temperature rides the state as a
device array (temperature 0 = greedy, bit-identical to
``generate(temperature=0)``); sampled serving derives every row's key
counter-style from (pool base key, request seed, row position), so a
request's sampled tokens are bitwise-reproducible regardless of how
the scheduler interleaves admits with decode chunks.
"""

from __future__ import annotations

import hashlib
import logging
import time
from collections import OrderedDict
from concurrent.futures import Future

import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import (env_flag, env_float, env_int,
                                       env_str)
from deeplearning4j_tpu.errors import ServeStoppedError
from deeplearning4j_tpu.serving._base import (_DISCONNECTS, _OCCUPANCY,
                                              _REQ_SECONDS, ServingFrontEnd,
                                              int_ladder, resolve_deadline)
from deeplearning4j_tpu.testing import faults

__all__ = ["ContinuousLM", "slots_ladder", "kv_ladder", "prefill_ladder"]

_LOG = logging.getLogger(__name__)

# fallback slot-pool bounds when deriving the default width from
# DL4J_TPU_MEM_BUDGET (satellite: memory-as-scheduler first bite)
_MIN_DEFAULT_SLOTS = 1
_MAX_DEFAULT_SLOTS = 64
_PROBE_REPS = 2          # timed reps per ladder rung (min taken)
# dispatch-poll rounds the scheduler waits for the queue to reach the
# ladder's widest rung before probing a not-yet-full queue anyway
_PROBE_PATIENCE = 3

_TOKENS = obs.counter("serve.tokens_total",
                      "Generated tokens delivered to completed requests")
_STEPS = obs.counter(
    "serve.decode_steps_total",
    "Decode steps advanced across all KV slots (chunk x dispatches)")
_SLOTS_G = obs.gauge("serve.slots",
                     "Resolved continuous-batching KV slot width B_slots")
_ACTIVE_G = obs.gauge("serve.active_slots",
                      "KV slots currently decoding a request")
_PROBES = obs.counter(
    "serve.autotune_probes_total",
    "Decode-width ladder probe measurements (zero on a tune-cache hit)")
_KV_WINDOW_G = obs.gauge(
    "serve.kv_window",
    "KV attention-window rung of the last dispatched decode chunk "
    "(paged attention: the smallest ladder rung covering the pool's "
    "max active position)")
_PREFILL_SECONDS = obs.histogram(
    "serve.prefill_seconds",
    "Admission-to-activation wall time of chunked-prefill requests "
    "(includes decode chunks interleaved between prefill windows)")
_TTFT_SECONDS = obs.histogram(
    "serve.ttft_seconds",
    "Submit-to-first-token latency, recorded when the chunk containing "
    "a request's first sampled token returns from dispatch (dispatch "
    "clock: under async dispatch this can lead device completion by "
    "the in-flight chunk)")
_PREFILL_WINDOWS = obs.counter(
    "serve.prefill_windows_total",
    "Chunked-prefill window dispatches (compute + prefix-inject)")
_PREFIX_HITS = obs.counter(
    "serve.prefix_hits_total",
    "Prefill windows served by injecting prefix-cache KV pages")
_PREFIX_MISSES = obs.counter(
    "serve.prefix_misses_total",
    "Prefill windows computed fresh with the prefix cache enabled")
_PREFIX_EVICT = obs.counter(
    "serve.prefix_evictions_total",
    "Prefix-cache page entries evicted (LRU) past the "
    "DL4J_TPU_SERVE_PREFIX_CACHE_MB byte budget")
_PREFIX_BYTES_G = obs.gauge(
    "serve.prefix_cache_bytes",
    "Bytes of KV pages currently held by the prompt-prefix cache")


def slots_ladder():
    """The ``DL4J_TPU_SERVE_SLOTS_LADDER`` candidates (``int_ladder``
    semantics: sorted, deduplicated, warn-and-fall-back on malformed
    values)."""
    return int_ladder("DL4J_TPU_SERVE_SLOTS_LADDER", (2, 4, 8))


def kv_ladder(max_len, chunk, override=None):
    """The paged-attention KV window rungs for a model: sorted powers of
    2 capped at ``max_len`` (which is always the top rung — the
    scheduler must be able to cover any legal position), each rung at
    least ``chunk`` (a dispatch advances every active row by ``chunk``
    positions, so a smaller rung could never be selected).

    ``override``/knob semantics: ``None``/empty derives 32, 64, ...,
    max_len; ``"off"`` pins the single ``max_len`` rung (the pre-paging
    program, bit-identical); an explicit int sequence (ctor arg) or
    comma list (``DL4J_TPU_SERVE_KV_LADDER``) is clamped the same
    way."""
    if override is None:
        override = env_str("DL4J_TPU_SERVE_KV_LADDER").strip()
    if isinstance(override, str):
        if override.lower() == "off":
            return (max_len,)
        rungs = int_ladder("DL4J_TPU_SERVE_KV_LADDER", ()) if override \
            else ()
    else:
        rungs = tuple(int(r) for r in override)
    if not rungs:
        rungs, r = [], 32
        while r < max_len:
            rungs.append(r)
            r *= 2
    rungs = sorted({r for r in rungs if chunk <= r < max_len})
    return tuple(rungs) + (max_len,)


def prefill_ladder(max_len, override=None):
    """The chunked-prefill prompt-window rungs: sorted powers of 4
    (16, 64, 256, ...) capped at ``max_len``. ``"off"`` (or an empty
    explicit sequence) disables chunked prefill — prompts teacher-force
    through the decode chunk, the pre-prefill behaviour."""
    if override is None:
        override = env_str("DL4J_TPU_SERVE_PREFILL_LADDER").strip()
    if isinstance(override, str):
        if override.lower() == "off":
            return ()
        if override:
            rungs = int_ladder("DL4J_TPU_SERVE_PREFILL_LADDER", ())
        else:
            rungs, r = [], 16
            while r <= max_len:
                rungs.append(r)
                r *= 4
            rungs = rungs or [max_len]
    else:
        rungs = tuple(int(r) for r in override)
    return tuple(sorted({min(int(r), max_len) for r in rungs if r >= 1}))


# ContinuousLM's ctor parameters shadow the ladder helpers by design
# (the override arg and the helper share the knob's name) — aliases for
# use inside __init__
_kv_ladder_fn = kv_ladder
_prefill_ladder_fn = prefill_ladder


def _prefix_key(prompt, end):
    """Prefix-cache key: the hash of the prompt's first ``end`` tokens
    (windows are planned at deterministic boundaries, so two prompts
    sharing a prefix share keys for every full window inside it)."""
    return hashlib.sha1(np.ascontiguousarray(
        prompt[:end]).tobytes()).hexdigest()


class _PrefixKVCache:
    """Byte-bounded LRU of prefilled KV pages, keyed by prompt-prefix
    hash. Owner-thread state (the scheduler dispatch loop is the only
    reader/writer — the ServingFrontEnd owner-thread contract), bounded
    by construction: every insert evicts least-recently-used entries
    (``popitem``) until the byte budget holds, so the device-array map
    can never grow without bound (the G021 contract). ``pin`` holds the
    params the pages were computed from — pages from stale params are
    never injected (``clear`` on a params swap)."""

    def __init__(self, cap_bytes):
        self.cap = int(cap_bytes)
        self.pin = None
        self._map = OrderedDict()   # key -> (kpages, vpages, start, n, W)
        self._bytes = 0

    def __len__(self):
        return len(self._map)

    def get(self, key, start, n, W):
        e = self._map.get(key)
        if e is None or e[2:] != (start, n, W):
            return None
        self._map.move_to_end(key)
        return e[0], e[1]

    def put(self, key, kpages, vpages, start, n, W):
        nbytes = kpages.nbytes + vpages.nbytes
        if key in self._map or nbytes > self.cap:
            return
        self._map[key] = (kpages, vpages, start, n, W)
        self._bytes += nbytes
        while self._bytes > self.cap and self._map:
            _, old = self._map.popitem(last=False)   # LRU eviction
            self._bytes -= old[0].nbytes + old[1].nbytes
            _PREFIX_EVICT.inc()
        _PREFIX_BYTES_G.set(self._bytes)

    def clear(self):
        self._map.clear()
        self._bytes = 0
        _PREFIX_BYTES_G.set(0)


class _GenRequest:
    __slots__ = ("prompt", "n_new", "temp", "top_k", "top_p", "seed",
                 "future", "t0", "deadline", "on_tokens", "emitted")

    def __init__(self, prompt, n_new, temp, top_k, top_p, seed,
                 deadline=None, on_tokens=None):
        self.prompt = prompt
        self.n_new = n_new
        self.temp = temp
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        self.future = Future()
        self.t0 = time.monotonic()
        self.deadline = deadline     # absolute monotonic, None = none
        self.on_tokens = on_tokens   # streaming callback (ingress NDJSON)
        self.emitted = 0             # sampled tokens already streamed


class ContinuousLM(ServingFrontEnd):
    """Continuous-batching generation scheduler over one TransformerLM.

    ``submit(prompt, n_new)`` from any thread returns a Future of the
    full ``[P + n_new]`` token row; ONE scheduler thread (the
    ``ServingFrontEnd`` owner-thread contract) owns the device state.
    Admission happens at chunk boundaries into freed KV slots."""

    _thread_name = "dl4j-serve-decode"

    def __init__(self, lm, *, slots=None, chunk=None, queue_cap=None,
                 seed=0, kv_ladder=None, prefill_ladder=None,
                 prefix_cache_mb=None):
        super().__init__(queue_cap=queue_cap)
        # what the decode and prefill programs cannot serve is refused here,
        # by its name, not at the first request
        lm.conf.served_without_experts("continuous batching (ContinuousLM)")
        if lm.params is None:
            lm.init()
        self.lm = lm
        self._slots_arg = None if slots is None else int(slots)
        self._chunk = chunk if chunk is not None \
            else env_int("DL4J_TPU_SERVE_CHUNK", minimum=1)
        self._wait = max(env_float("DL4J_TPU_SERVE_WAIT", minimum=0.0),
                         0.001)
        self._seed = seed
        # paged-attention / chunked-prefill rung ladders (ctor override
        # > env knob > derived default; "off" = pre-paging behaviour)
        self._kv_ladder = _kv_ladder_fn(lm.conf.max_len, self._chunk,
                                        kv_ladder)
        self._prefill_ladder = _prefill_ladder_fn(lm.conf.max_len,
                                                  prefill_ladder)
        # explicitly-pinned ladders overwrite a persisted rung decision;
        # derived ones adopt it (_sync_ladders)
        self._kv_explicit = kv_ladder is not None \
            or bool(env_str("DL4J_TPU_SERVE_KV_LADDER").strip())
        self._prefill_explicit = prefill_ladder is not None \
            or bool(env_str("DL4J_TPU_SERVE_PREFILL_LADDER").strip())
        mb = env_int("DL4J_TPU_SERVE_PREFIX_CACHE_MB", minimum=0) \
            if prefix_cache_mb is None else int(prefix_cache_mb)
        self._prefix = _PrefixKVCache(mb << 20) \
            if mb and self._prefill_ladder else None
        # resolved on the first dispatch (autotune seam)
        self._slots = None
        self._probe_polls = 0
        self._admit_fn = None
        self._state = None
        # host mirrors of the device counters: slot -> [request, pos, tgt]
        # pos advances by exactly chunk per dispatch for active rows, so
        # completion needs NO device fetch (docstring contract)
        self._slot_req = {}
        # slots mid-prefill (admitted inactive): slot -> [request, plan,
        # next window index, admit time]
        self._prefilling = {}
        self._free = []
        # per-rung all-zero inject pages (the prefill program's prefix
        # args on a compute dispatch): allocated once per rung
        self._zero_pages = {}

    # ---- client surface ------------------------------------------------
    def submit(self, prompt, n_new, *, temperature=0.0, top_k=None,
               top_p=None, seed=0, deadline_s=None, on_tokens=None):
        """Enqueue one generation request: ``prompt`` is a 1-D int token
        array, the Future resolves to ``[P + n_new]`` (prompt included,
        the ``generate`` contract). ``top_k``/``top_p`` are PER-REQUEST
        sampler params riding the slot state as device vectors — every
        mix of requests shares the one compiled chunk signature. Typed
        backpressure past ``DL4J_TPU_SERVE_QUEUE`` pending requests.

        ``deadline_s`` is the request's deadline budget (seconds;
        default ``DL4J_TPU_SERVE_DEADLINE_S``): still queued past it,
        the request is swept with ``ServeDeadlineError`` BEFORE
        admission — zero device work. ``on_tokens`` opts this request
        into streaming: called from the scheduler thread with each
        newly sampled token span (1-D int array) as chunks complete —
        one bounded extra out-row fetch per chunk with streamers, the
        documented cost of streaming; a raising callback is treated as
        a client disconnect."""
        c = self.lm.conf
        # host request validation at the serving API seam: prompt/n_new
        # are caller-provided host values, never device arrays
        # graftlint: disable=G001 -- host request ingest, same seam as output()'s asarray
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # graftlint: disable=G001 -- host request-parameter parse, not a device sync
        n_new = int(n_new)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if prompt.size + n_new > c.max_len:
            raise ValueError(f"P+n_new={prompt.size + n_new} exceeds "
                             f"max_len={c.max_len}")
        # the generate() validation contract, k = vocab / p = 1.0 meaning
        # "off" on the device side
        if top_k is not None and not 1 <= int(top_k) <= c.vocab_size:
            raise ValueError(f"top_k must be in [1, {c.vocab_size}]")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if on_tokens is not None and not callable(on_tokens):
            raise ValueError("on_tokens must be callable")
        r = _GenRequest(prompt, n_new, float(temperature),
                        c.vocab_size if top_k is None else int(top_k),
                        1.0 if top_p is None else float(top_p), int(seed),
                        resolve_deadline(deadline_s), on_tokens)
        return self._enqueue(r)

    def generate(self, prompt, n_new, *, temperature=0.0, top_k=None,
                 top_p=None, seed=0, timeout=120.0):
        """Synchronous ``submit``: the ``[P + n_new]`` token row."""
        return self.submit(prompt, n_new, temperature=temperature,
                           top_k=top_k, top_p=top_p,
                           seed=seed).result(timeout)

    # ---- lifecycle -----------------------------------------------------
    def _loop(self):
        self._decode_loop()

    def warm_start(self, slots=None):
        """Resolve the slot width and compile the WHOLE program
        inventory up front (server BOOT — before the first submit): the
        admit writer, one decode step per KV window rung, and one
        prefill program per prompt-window rung, each exercised with a
        no-op dispatch (all rows inactive / zero valid tokens, so the
        pool stays logically pristine) because ``jax.jit`` compiles on
        first CALL, not construction. The first request then pays no
        compile, and a RESTART over the persistent compilation cache
        compiles nothing. The slot pool is scheduler-owned once the
        loop thread runs, so warming a live server is refused instead
        of racing it."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                raise RuntimeError(
                    "warm_start() must run before serving starts: the "
                    "scheduler thread owns the slot pool once submits "
                    "flow (stop() first)")
        s = self._resolve_slots(force=True) if slots is None else int(slots)
        self._bind_slots(s)
        lm = self.lm
        c = lm.conf
        # the admit writer too — same no-op shape _release dispatches
        # (slot 0 rewritten inactive), so the first real admission pays
        # no compile either
        # one bracket a program: the compile log (obs.compiles()) then names
        # the boot's seconds by program family and rung
        with obs.building("serve.warm.admit"):
            self._state = self._admit_fn(
                self._state, np.int32(0), np.zeros(c.max_len, np.int32),
                np.int32(1), np.int32(0), np.float32(0.0),
                np.int32(c.vocab_size), np.float32(1.0), np.bool_(False),
                np.int32(0))
        for w in self._kv_ladder:
            with obs.building(f"serve.warm.decode.w{w}"):
                _, step = lm._decode_fns(s, self._chunk, w)
                self._state = step(lm.params, self._state)
        for w in self._prefill_ladder:
            with obs.building(f"serve.warm.prefill.w{w}"):
                pf = lm._prefill_fn(s, w)
                ik, iv = self._inject_zeros(w)
                self._state, _, _ = pf(
                    lm.params, self._state, np.int32(0),
                    np.zeros(w, np.int32), np.int32(0), np.int32(0),
                    np.bool_(False), np.bool_(False), ik, iv)
        # the warm dispatches scribbled positions/outputs into the pool
        # (sampling keys are counter-derived, so the rng needs no reset);
        # rebuild it so the first real request starts from a blank slate
        self._state = lm._init_decode_state(s, self._seed)
        return s

    def _after_stop(self, joined):
        """The scheduler (single owner of the slot table) has exited —
        fail in-flight requests typed. When the join TIMED OUT the
        thread still owns the table: leave it alone (the base warned),
        racing it could double-resolve a future."""
        if not joined:
            return
        for rec in list(self._slot_req.values()) \
                + list(self._prefilling.values()):
            if not rec[0].future.done():
                rec[0].future.set_exception(
                    ServeStoppedError("serving stopped before this "
                                      "generation completed"))
        self._slot_req.clear()
        self._prefilling.clear()
        # reset the scheduler state whole: the dropped requests' rows are
        # still active on device and NOT in _free, so a restarted server
        # must rebuild a fresh (all-inactive) pool at full capacity —
        # the compiled programs stay cached in the model's _jit_decode
        self._slots = None
        self._state = None
        self._admit_fn = None
        self._free = []
        if self._prefix is not None:
            # drop the cached pages with the pool: a stopped server
            # frees ALL its device bytes (the leakwatch teardown
            # contract), and a restart simply re-fills the cache
            self._prefix.clear()
        # same contract for the per-rung zero pages (at most one small
        # pair per prefill rung, but teardown means zero device bytes)
        self._zero_pages = {}
        _ACTIVE_G.set(0)

    # ---- slot-width resolution (satellite: decode-width autotuner) -----
    def _resolve_slots(self, force=False):
        """B_slots for this server: explicit knob/ctor arg > persisted
        autotune decision > ladder probe (armed + first full queue) >
        default. Returns None to DEFER (queue not full yet, patience not
        exhausted)."""
        if self._slots_arg is not None:
            return self._slots_arg
        explicit = env_int("DL4J_TPU_SERVE_SLOTS", minimum=1)
        if explicit:
            return explicit
        from deeplearning4j_tpu.tuning import autotuner
        import jax
        mk = autotuner.model_key(self.lm)
        backend = jax.default_backend()
        bucket_key = ("serve_slots", self._chunk, self.lm.conf.max_len)
        hit = autotuner.lookup_decision(mk, backend, bucket_key)
        if hit is not None:
            return hit   # persisted decisions are ints (record_decision)
        if not env_flag("DL4J_TPU_SERVE_AUTOTUNE"):
            return self._default_slots()
        ladder = slots_ladder()
        if not force:
            with self._lock:
                depth = len(self._pending)
            if depth < ladder[-1] and self._probe_polls < _PROBE_PATIENCE:
                # "first full queue": wait (bounded) for enough pending
                # requests to exercise the widest rung before probing
                self._probe_polls += 1
                return None
        return self._probe_slots(mk, backend, bucket_key, ladder)

    def _default_slots(self):
        """Memory-derived default slot width (the ROADMAP memory-as-
        scheduler item's first bite): memlint's decode-row ``kv_cache``
        bytes per slot — ``2 * layers * kv_heads * max_len * head_dim *
        cache_dtype_size``, the ``_transformer_kv_bytes`` formula in
        tools/graftlint/shapes.py — divided into half the
        ``DL4J_TPU_MEM_BUDGET`` left after the parameters (the other
        half stays headroom for activations/logits buffers), clamped to
        [1, 64]. Replaces the old hard-coded 4."""
        import jax
        c = self.lm.conf
        # host metadata reads only: sizes/dtypes, never values
        dsize = np.dtype(self.lm._cache_dtype()).itemsize
        kv_slot = 2 * c.applications * c.kv_heads * c.max_len * c.hd * dsize
        params_b = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(self.lm.params))
        budget = env_int("DL4J_TPU_MEM_BUDGET", minimum=1)
        avail = max(budget // 2 - params_b, 0)
        slots = min(max(avail // kv_slot, _MIN_DEFAULT_SLOTS),
                    _MAX_DEFAULT_SLOTS)
        _LOG.info(
            "serve slots default derived from memory: budget=%d B, "
            "params=%d B, kv_cache/slot=%d B (decode-row formula) -> "
            "%d slots (clamped to [%d, %d])", budget, params_b, kv_slot,
            slots, _MIN_DEFAULT_SLOTS, _MAX_DEFAULT_SLOTS)
        return slots

    def _probe_slots(self, mk, backend, bucket_key, ladder):
        """Time one all-slots-active chunk per ladder rung on dummy state
        (compile + warm, then min of timed reps), pick the best per-token
        width, evict the losers' compiled programs, persist the decision
        through the autotuner's atomic cache."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.tuning import autotuner
        lm = self.lm
        top = self._kv_ladder[-1]   # probe at the max_len rung: the
        per_tok = {}                # conservative steady-state cost
        for s in ladder:
            _, step = lm._decode_fns(s, self._chunk, top)
            st = lm._init_decode_state(s, self._seed)
            st["active"] = jnp.ones((s,), bool)
            st["nnew"] = jnp.full((s,), lm.conf.max_len - 1, jnp.int32)
            st = step(lm.params, st)              # compile + warm
            np.asarray(st["pos"])   # graftlint: disable=G001 -- probe timing barrier: the measured dispatch must have finished
            best = None
            for _ in range(_PROBE_REPS):
                t0 = time.perf_counter()
                st = step(lm.params, st)
                np.asarray(st["pos"])   # graftlint: disable=G001 -- probe timing barrier: the measured dispatch must have finished
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            per_tok[s] = best / (s * self._chunk)
            _PROBES.inc()
        winner = min(ladder, key=lambda s: (per_tok[s], -s))
        for s in ladder:
            if s != winner:   # losers leave the cache: the winner's
                lm._jit_decode.pop(   # rung inventory remains
                    lm._decode_signature(s, self._chunk, top), None)
                lm._jit_decode.pop(lm._admit_signature(s), None)
        autotuner.record_decision(mk, backend, bucket_key, winner, per_tok)
        return winner

    def _sync_ladders(self):
        """Persist the resolved rung ladders beside the slot decision in
        the autotuner cache (and on a restart, adopt the persisted
        ladders when nothing pins them explicitly): a restarted server
        re-arms the SAME compiled-program inventory, so a warm boot over
        the persistent compilation cache compiles nothing. RECORDING is
        gated on the same ``DL4J_TPU_SERVE_AUTOTUNE`` arm flag as the
        slot probe — an unarmed server must never write the shared tune
        cache (explicit ctor ladders are per-server choices until the
        operator opts into persistence); ADOPTION reads whatever an
        armed run left behind."""
        import jax
        from deeplearning4j_tpu.tuning import autotuner
        mk = autotuner.model_key(self.lm)
        backend = jax.default_backend()
        c = self.lm.conf
        armed = env_flag("DL4J_TPU_SERVE_AUTOTUNE")
        for name, attr, explicit in (
                ("serve_kv_ladder", "_kv_ladder", self._kv_explicit),
                ("serve_prefill_ladder", "_prefill_ladder",
                 self._prefill_explicit)):
            bkey = (name, self._chunk, c.max_len)
            hit = autotuner.lookup_decision(mk, backend, bkey)
            cur = getattr(self, attr)
            if hit is not None and not explicit:
                setattr(self, attr, tuple(hit))
            elif armed and (hit is None or tuple(hit) != tuple(cur)):
                autotuner.record_decision(mk, backend, bkey, cur, {})

    def _bind_slots(self, s):
        if self._slots == s:
            return
        self._sync_ladders()
        self._slots = s
        self._admit_fn, _ = self.lm._decode_fns(s, self._chunk,
                                                self._kv_ladder[-1])
        self._state = self.lm._init_decode_state(s, self._seed)
        self._slot_req = {}
        self._prefilling = {}
        self._free = list(range(s))
        _SLOTS_G.set(s)

    # ---- scheduler (single owner thread) -------------------------------
    def _admit(self, slot, r):
        """Write request ``r`` into cache row ``slot`` (one compiled
        admit signature for every slot index — the index is a traced
        argument). Prompts that fill at least the SMALLEST prefill
        window (``P - 1 >= min(prefill_ladder)``, with chunked prefill
        enabled) are admitted INACTIVE and handed to the prefill pump;
        the final prefill window leaves ``pos`` at ``plen - 1`` and
        flips the row live, so the decode chunk re-processes only the
        LAST prompt token (bit-parity with the teacher-forced path).
        Everything else teacher-forces through the decode chunk as
        before — a short prompt rides the SHARED decode dispatch at ~no
        marginal cost, while a dedicated partial-window prefill dispatch
        would cost more than it saves (measured: routing sub-window
        prompts through the pump cut the short-prompt lane's throughput
        by a third)."""
        c = self.lm.conf
        span = r.prompt.size - 1   # prompt tokens the prefill ingests
        use_prefill = bool(self._prefill_ladder) \
            and span >= self._prefill_ladder[0]
        row = np.zeros(c.max_len, np.int32)
        row[:r.prompt.size] = r.prompt
        self._state = self._admit_fn(
            self._state, np.int32(slot), row, np.int32(r.prompt.size),
            np.int32(r.n_new), np.float32(r.temp), np.int32(r.top_k),
            np.float32(r.top_p), np.bool_(not use_prefill),
            np.int32(r.seed))
        if use_prefill:
            self._prefilling[slot] = [r, self._plan_prefill(span), 0,
                                      time.monotonic()]
        else:
            # completion is pos >= plen + n_new - 1 (the last needed
            # sample falls out of processing position plen + n_new - 2)
            self._slot_req[slot] = [r, 0, r.prompt.size + r.n_new - 1]

    def _plan_prefill(self, span):
        """Deterministic prefill window plan for a ``span``-token
        prompt prefix: full windows at the LARGEST ladder rung, one
        tail window at the smallest rung covering the remainder.
        Boundaries depend only on the token offset (never on the whole
        prompt's length), so two prompts sharing a prefix share every
        full window's prefix-cache key. Returns [(start, rung,
        n_valid), ...]."""
        top = self._prefill_ladder[-1]
        plan, s = [], 0
        while span - s > 0:
            rem = span - s
            if rem >= top:
                plan.append((s, top, top))
                s += top
            else:
                rung = min(r for r in self._prefill_ladder if r >= rem)
                plan.append((s, rung, rem))
                s = span
        return plan

    def _inject_zeros(self, W):
        """The per-rung all-zero K/V page pair handed to a COMPUTE
        prefill dispatch (the program's inject args must exist either
        way; allocated once per rung, so the steady state transfers
        nothing)."""
        pages = self._zero_pages.get(W)
        if pages is None:
            import jax.numpy as jnp
            c = self.lm.conf
            shape = (c.applications, c.kv_heads, W, c.hd)
            z = jnp.zeros(shape, self.lm._cache_dtype())
            pages = self._zero_pages[W] = (z, z)
        return pages

    def _pump_prefill(self):
        """Dispatch ONE prefill window (FIFO over mid-prefill slots) —
        called once per scheduler iteration, so long prompts interleave
        with decode chunks instead of stalling the active pool. On a
        prefix-cache hit the window's pages are injected instead of
        computed; on a miss the program's returned pages are memoised
        for the next prompt sharing the prefix."""
        if not self._prefilling:
            return
        slot = next(iter(self._prefilling))
        rec = self._prefilling[slot]
        r, plan, idx, t0 = rec
        start, W, n = plan[idx]
        final = idx == len(plan) - 1
        cache = self._prefix
        if cache is not None and cache.pin is not self.lm.params:
            cache.clear()   # pages from stale params must never inject
            cache.pin = self.lm.params
        key = entry = None
        if cache is not None:
            key = _prefix_key(r.prompt, start + n)
            entry = cache.get(key, start, n, W)
        toks = np.zeros(W, np.int32)
        toks[:n] = r.prompt[start:start + n]
        if entry is not None:
            ik, iv = entry
            _PREFIX_HITS.inc()
        else:
            ik, iv = self._inject_zeros(W)
            if cache is not None:
                _PREFIX_MISSES.inc()
        pf = self.lm._prefill_fn(self._slots, W)
        self._state, kp, vp = pf(
            self.lm.params, self._state, np.int32(slot), toks,
            np.int32(start), np.int32(n), np.bool_(final),
            np.bool_(entry is not None), ik, iv)
        _PREFILL_WINDOWS.inc()
        if cache is not None and entry is None:
            cache.put(key, kp, vp, start, n, W)
        if final:
            del self._prefilling[slot]
            span = r.prompt.size - 1
            self._slot_req[slot] = [r, span, r.prompt.size + r.n_new - 1]
            _PREFILL_SECONDS.record(time.monotonic() - t0)
        else:
            rec[2] = idx + 1

    def _select_rung(self):
        """Smallest KV window rung covering every active row through
        the NEXT chunk — host arithmetic over the existing position
        mirrors, zero new syncs. Rows advance ``chunk`` positions per
        dispatch, so the window must hold ``max(pos) + chunk``."""
        need = max(rec[1] for rec in self._slot_req.values()) + self._chunk
        for r in self._kv_ladder:
            if r >= need:
                return r
        return self._kv_ladder[-1]

    def _release(self, slot):
        c = self.lm.conf
        self._state = self._admit_fn(
            self._state, np.int32(slot), np.zeros(c.max_len, np.int32),
            np.int32(1), np.int32(0), np.float32(0.0),
            np.int32(c.vocab_size), np.float32(1.0), np.bool_(False),
            np.int32(0))
        self._free.append(slot)

    def _fill_free_slots(self):
        while self._free:
            r = self._pop_pending()
            if r is None:
                return
            # pre-admission deadline sweep: an expired request is failed
            # typed here and never touches a KV slot (zero device work)
            if not self._sweep_expired([r]):
                continue
            self._admit(self._free.pop(), r)

    def _decode_loop(self):
        while True:
            with self._lock:
                if self._stopping:
                    return
                if not self._pending and not self._slot_req \
                        and not self._prefilling:
                    self._more.wait(self._wait)   # bounded idle poll
                    continue
            if self._slots is None:
                s = self._resolve_slots()
                if s is None:        # autotune waiting for a full queue
                    time.sleep(self._wait)
                    continue
                self._bind_slots(s)
            self._fill_free_slots()
            self._pump_prefill()
            if not self._slot_req:
                continue
            if self._replica_fault():
                return   # kill-replica: hard crash, no cleanup — the
                         # router's heartbeat fails this replica over
            spec = faults.fire("slow-request")
            if spec is not None:
                time.sleep(spec.param_float(0.05))
            rung = self._select_rung()
            _, step = self.lm._decode_fns(self._slots, self._chunk, rung)
            self._state = step(self.lm.params, self._state)
            _KV_WINDOW_G.set(rung)
            _STEPS.inc(self._chunk * len(self._slot_req))
            _OCCUPANCY.record(len(self._slot_req) / self._slots)
            _ACTIVE_G.set(len(self._slot_req))
            done, now = [], None
            for slot, rec in self._slot_req.items():
                old = rec[1]
                rec[1] += self._chunk
                plen = rec[0].prompt.size
                if old < plen <= rec[1]:   # first sampled token's chunk
                    if now is None:
                        now = time.monotonic()
                    _TTFT_SECONDS.record(now - rec[0].t0)
                if rec[1] >= rec[2]:
                    done.append(slot)
            self._stream_emit()
            if done:
                self._complete(done)

    def _stream_emit(self):
        """Incremental token delivery for streaming requests: ONE
        bounded out-row fetch per dispatched chunk WITH streamers whose
        sampled count advanced (the documented extra sync a request
        opts into via ``on_tokens``), emitting each streaming row's
        newly sampled span. A raising callback is a client disconnect:
        the future is cancelled and ``_complete`` discards the row."""
        pend = []
        for slot, rec in self._slot_req.items():
            r = rec[0]
            if r.on_tokens is None or r.future.cancelled():
                continue
            have = min(max(rec[1] - (r.prompt.size - 1), 0), r.n_new)
            if have > r.emitted:
                pend.append((slot, r, have))
        if not pend:
            return
        out_host = np.asarray(self._state["out"])   # graftlint: disable=G001 -- streaming seam: one bounded fetch per chunk with streamers, opted into per request via on_tokens
        for slot, r, have in pend:
            try:
                r.on_tokens(out_host[slot, r.emitted:have])
            except Exception:
                r.future.cancel()   # dead stream consumer == disconnect
            r.emitted = have

    def _complete(self, done):
        """Fetch the out buffer ONCE for this chunk's completions, resolve
        their futures, then refill each freed row straight from the queue
        — or park it inactive (it stops advancing and drops out of the
        occupancy numerator)."""
        out_host = np.asarray(self._state["out"])   # graftlint: disable=G001 -- the request-completion seam: one bounded fetch per chunk WITH completions, never per token
        now = time.monotonic()
        for slot in done:
            r, _, _ = self._slot_req.pop(slot)
            if faults.fire("client-disconnect") is not None:
                r.future.cancel()
            if r.future.cancelled():
                _DISCONNECTS.inc()
            else:
                toks = np.concatenate([r.prompt, out_host[slot, :r.n_new]])
                r.future.set_result(toks)
                _TOKENS.inc(r.n_new)
                _REQ_SECONDS.record(now - r.t0)
        for slot in done:
            r = self._pop_pending()
            if r is not None:
                self._admit(slot, r)   # freed row reused mid-decode
            else:
                self._release(slot)
        _ACTIVE_G.set(len(self._slot_req))
