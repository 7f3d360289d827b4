"""Central registry of every ``DL4J_TPU_*`` environment knob.

Every env var the framework consults is declared here ONCE — name, type,
default, and a one-line doc — and read through :func:`env_flag` /
:func:`env_int` / :func:`env_str`. The graftlint G003 rule
(``tools/graftlint``) fails tier-1 if any module under
``deeplearning4j_tpu/`` reads a ``DL4J_TPU_*`` variable around this
registry, so a knob cannot exist without an entry (and therefore without
documentation): ``docs/CONFIG.md`` is generated from this table
(``python -m deeplearning4j_tpu.config``) and a tier-1 test keeps the two
in sync.

Contracts shared by every knob:

- values are read from ``os.environ`` at CALL time, never cached at
  import, so tests and tools may set a knob after importing the package.
  Caveat: a few knobs are consulted from inside traced code, so their
  EFFECT freezes when the program compiles — those say "read at trace
  time" in their doc line and declare ``trace_time=True``, which is what
  graftlint's G004 keys its trace-time allowance on (an env read in
  traced code through a knob NOT declared trace-time is a finding);
- a malformed value must not crash training startup: it warns and falls
  back to the declared default (the original DL4J_TPU_TRANSFER_STAGE
  contract, now uniform);
- reading an UNDECLARED name raises ``KeyError`` immediately — that is a
  programming error, not a user error.

This module must stay importable without jax (tests/conftest.py and the
doc generator run before any backend exists). The two bootstrap knobs
``DL4J_TPU_TEST_PLATFORM`` and ``DL4J_TPU_SLOW`` are declared here for the
table but are read raw in ``tests/conftest.py``: conftest must set
``JAX_PLATFORMS`` before ANY deeplearning4j_tpu import (the package
``__init__`` pulls in jax), so it cannot import this module first.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = ["Knob", "KNOBS", "env_flag", "env_int", "env_float", "env_str",
           "env_is_set", "knob_table_md"]


@dataclass(frozen=True)
class Knob:
    name: str       # full env var name, DL4J_TPU_*
    kind: str       # "flag" | "int" | "float" | "str"
    default: object
    doc: str        # one line, shown in the generated table
    # True for knobs whose documented contract is a TRACE-TIME read: the
    # value is consulted while a jitted/scanned function traces, so its
    # effect freezes into the compiled program (set it before the first
    # compile; changing it later needs a cache clear). graftlint's G004
    # reads this declaration STATICALLY (it parses this file's AST, never
    # imports it) and allows registry-routed reads of these — and only
    # these — knobs inside traced code; an undeclared trace-time read is
    # still a finding.
    trace_time: bool = False


KNOBS: dict[str, Knob] = {}


def _declare(name, kind, default, doc, *, trace_time=False):
    # trace_time is KEYWORD-ONLY on purpose: graftlint's G004 collects
    # the declarations statically by scanning for the `trace_time=True`
    # keyword, so a positional True would be a declaration the linter
    # cannot see — Python now refuses to let one be written
    if name in KNOBS:
        raise ValueError(f"duplicate knob declaration {name!r}")
    KNOBS[name] = Knob(name, kind, default, doc, trace_time)


# ---------------------------------------------------------------------------
# the registry — keep alphabetical so the generated table diffs cleanly
# ---------------------------------------------------------------------------
_declare("DL4J_TPU_AB_SMOKE", "flag", False,
         "Tooling: shrink the tools/ A/B harnesses (w2v_kernel_ab, "
         "transformer_longseq) to smoke-test sizes.")
_declare("DL4J_TPU_ALLOW_DOWNLOAD", "flag", False,
         "Enable the MNIST/LFW/CIFAR-10/Iris/trained-model download paths; "
         "off by default (air-gapped environments place files manually).")
_declare("DL4J_TPU_CKPT_EVERY", "int", 0,
         "Default periodic-checkpoint cadence (parameter updates between "
         "training checkpoints) for fit(checkpoint_dir=...); 0 disables "
         "unless fit's checkpoint_every argument overrides it.")
_declare("DL4J_TPU_CKPT_KEEP", "int", 3,
         "Rolling retention for training checkpoints: newest K verified "
         "checkpoints are kept per directory (fit periodic checkpoints "
         "and the orbax CheckpointManager default).")
_declare("DL4J_TPU_CKPT_VERIFY", "flag", True,
         "Verify per-payload CRC manifests when restoring checkpoints; "
         "0 skips the integrity pass (structural corruption still raises "
         "CheckpointCorruptError).")
_declare("DL4J_TPU_COLLECTIVE_TIMEOUT", "float", 300.0,
         "Per-round deadline (seconds) for coordinator collectives: a round "
         "not completed within it fails on EVERY waiter with "
         "CollectiveTimeoutError instead of hanging.")
_declare("DL4J_TPU_CONNECT_RETRIES", "int", 3,
         "Extra connection attempts (exponential backoff) a collective "
         "client makes before giving up on the coordinator.")
_declare("DL4J_TPU_CONNECT_TIMEOUT", "float", 10.0,
         "Per-attempt TCP connect timeout (seconds) for collective "
         "clients; retried DL4J_TPU_CONNECT_RETRIES times.")
_declare("DL4J_TPU_DATA_DIR", "str", "",
         "Offline dataset ingest root searched before "
         "~/.deeplearning4j_tpu and /root/data.")
_declare("DL4J_TPU_DISABLE_HELPERS", "flag", False,
         "Disable every accelerated layer helper (nn/helpers.py) — the "
         "reference's NO_HELPERS escape hatch for numerical triage; read "
         "at trace time, so set before the first forward builds.",
         trace_time=True)
_declare("DL4J_TPU_DP_SHARD", "int", None,
         "ZeRO shard level of the data-parallel sharding core "
         "(parallel/sharding_core.py, docs/PARALLELISM.md): 0 replicates "
         "params/grads/updater state per device; 1 shards updater state "
         "1/N (ZeRO-1); 2 additionally reduce-scatters gradients to "
         "shards inside the step; 3 additionally keeps params/layer "
         "states sharded between steps and all-gathers them just-in-time "
         "for the forward (arxiv 2004.13336). Unset defers to "
         "DL4J_TPU_DP_SHARD_UPDATER (level 1 when on — the historical "
         "default).")
_declare("DL4J_TPU_DP_SHARD_UPDATER", "flag", True,
         "ZeRO-1-style sharding of updater state across the data axis "
         "(the pre-DL4J_TPU_DP_SHARD knob, kept as the back-compat "
         "default: with DL4J_TPU_DP_SHARD unset this flag maps to level "
         "1, off maps to level 0; an explicit DL4J_TPU_DP_SHARD always "
         "wins).")
_declare("DL4J_TPU_ELASTIC", "flag", False,
         "Elastic training (parallel/elastic.py, docs/ROBUSTNESS.md §7): "
         "on PeerDeadError/CollectiveTimeoutError inside a distributed "
         "fit, survivors checkpoint, re-form a fresh collective wave at "
         "the new world size, re-shard, and continue instead of dying. "
         "Also gates the param-server wrapper's reassignment of a dead "
         "trainer's remaining batches to survivors.")
_declare("DL4J_TPU_ELASTIC_MIN_WORKERS", "int", 1,
         "Minimum world size an elastic re-form wave may commit at: a "
         "wave that cannot gather this many participants within "
         "DL4J_TPU_REFORM_TIMEOUT fails every arrival with "
         "CollectiveTimeoutError instead of training on at a width the "
         "operator considers useless.")
_declare("DL4J_TPU_FLASH_BWD", "str", "pallas",
         "'scan' falls the flash-attention backward to the rematerializing "
         "lax.scan (dense oracle when a window is set); read at trace "
         "time — set before the first backward builds.",
         trace_time=True)
_declare("DL4J_TPU_FAULT_SPEC", "str", "",
         "Deterministic fault-injection plan (testing/faults.py), e.g. "
         "'iter-raise@3,drop-conn[1]@2,nan-step@1'; empty disables every "
         "injection point. Grammar in docs/ROBUSTNESS.md.")
_declare("DL4J_TPU_FUSE_ADAPT", "flag", True,
         "Adaptive fused-loop grouping: only the trailing group of a shape "
         "bucket is padded to its full K; a mid-stream rebucket flush emits "
         "the partial group at the next power-of-2 (per-batch at length 1) "
         "and a bucket that thrashes on rebucket flushes halves its K toward "
         "1; 0 restores the PR-1 always-pad-to-K behaviour.")
_declare("DL4J_TPU_FUSE_AUTOTUNE", "flag", False,
         "First-compile fusion autotuner: when set AND DL4J_TPU_FUSE_STEPS "
         "is unset, probe the DL4J_TPU_FUSE_PROBE_KS ladder with zero-weight "
         "timed warm dispatches per (model, bucket shape, backend) at first "
         "compile, pick the steady-state winner and persist it under "
         "DL4J_TPU_TUNE_CACHE_DIR (docs/FUSED_LOOP.md).")
_declare("DL4J_TPU_FUSE_PROBE_KS", "str", "1,4,8,16",
         "Candidate fused-step ladder the autotuner probes (comma-separated "
         "ints); the largest entry is also the grouping size while a bucket "
         "is undecided.")
_declare("DL4J_TPU_FUSE_TBPTT", "flag", True,
         "Fuse tBPTT training into the K-step scan: the per-batch window "
         "loop runs as an inner lax.scan inside the fused train program "
         "(scan-of-scans — docs/FUSED_LOOP.md 'Sequence workloads'), so "
         "tBPTT runs hold one compiled signature and 0 in-fit compiles "
         "like standard backprop; 0 restores the host window loop exactly "
         "(per-window jit dispatch, fusion gated off).")
_declare("DL4J_TPU_FUSE_STEPS", "int", 8,
         "Fused-scan step count K for model fit(): K updates per jitted "
         "lax.scan dispatch; 1 disables (per-step host listeners). Leave "
         "UNSET with DL4J_TPU_FUSE_AUTOTUNE=1 to let the autotuner pick K "
         "per (model, bucket shape, backend).")
_declare("DL4J_TPU_FUSE_UNROLL", "int", None,
         "Override the fused-scan unroll factor (0 or negative = full "
         "unroll); unset = full unroll on CPU, rolled scan on accelerators. "
         "Read at trace time (unroll is a compile-time property).",
         trace_time=True)
_declare("DL4J_TPU_ITER_RETRIES", "int", 0,
         "Transient-error retries the async prefetch worker gives a flaky "
         "base iterator before surfacing the failure on the consumer; "
         "0 (default) fails fast.")
_declare("DL4J_TPU_MEM_BUDGET", "int", 17179869184,
         "Per-device HBM budget in BYTES for graftlint's static memory "
         "model (default 16 GiB, the v5e-class assumption): the "
         "--mem-report over-budget column and the G020 "
         "replicated-state-budget rule both compare against it. Read by "
         "the linter directly (it can never import this registry); "
         "declared here so the knob is documented with the rest.")
_declare("DL4J_TPU_METRICS", "flag", True,
         "Record into the obs metric registry (step times, queue depths, "
         "collective round latencies, checkpoint commits — "
         "docs/OBSERVABILITY.md); 0 turns every record into a no-op.")
_declare("DL4J_TPU_COMPILEWATCH", "flag", False,
         "Enable the runtime compile watcher (testing/compilewatch.py): "
         "records the in-repo stack of every XLA backend compile and "
         "attributes it to siglint's static dispatch inventory — steady-"
         "state or G025-flagged compiles fail the test (the dynamic twin "
         "of graftlint G025-G027). Test-only overhead — off by default, "
         "switched on for `make chaos`.")
_declare("DL4J_TPU_LEAKWATCH", "flag", False,
         "Enable the runtime resource-leak watcher (testing/leakwatch.py):"
         " wraps Thread/socket/open/TemporaryDirectory constructors keyed "
         "by creation site and fails tests that leave them live (the "
         "dynamic twin of graftlint G022-G024). Test-only overhead — off "
         "by default, switched on for `make chaos`.")
_declare("DL4J_TPU_LOCKWATCH", "flag", False,
         "Enable the TSAN-lite runtime lock-order validator "
         "(testing/lockwatch.py): wraps threading.Lock/RLock to detect "
         "ABBA inversions with both acquisition stacks. Test-only "
         "overhead — off by default, switched on for `make chaos`.")
_declare("DL4J_TPU_RNGWATCH", "flag", False,
         "Enable the runtime RNG-key watcher (testing/rngwatch.py): wraps "
         "the jax.random producer/consumer seams, fingerprints every "
         "concrete key by its bits keyed by creation site, and fails "
         "tests that consume one key twice — with both stacks (the "
         "dynamic twin of graftlint G028-G030). Fingerprinting forces a "
         "device sync per call — off by default, switched on for "
         "`make chaos`.")
_declare("DL4J_TPU_LM_ATTN", "str", "auto",
         "Force the TransformerLM block attention route {pallas, scan}; "
         "read at trace time, so set before the first fit_batch.",
         trace_time=True)
_declare("DL4J_TPU_LSTM_KERNEL", "str", "builtin",
         "LSTM cell implementation for the recurrent layers' time scan "
         "{builtin, pallas}: 'pallas' fuses the recurrent matmul epilogue "
         "+ gate math + cell update into one Pallas kernel per step "
         "(ops/pallas_kernels.lstm_cell; TPU, or interpreter via "
         "DL4J_TPU_PALLAS_INTERPRET) with a custom-vjp fused backward; "
         "falls back to the built-in scan for non-sigmoid/tanh "
         "activations. Read at trace time — set before the first fit.",
         trace_time=True)
_declare("DL4J_TPU_MODEL_CACHE", "str", "~/.dl4j_tpu/trainedmodels",
         "Root of the pretrained-model weight cache "
         "(modelimport/trained_models.py).")
_declare("DL4J_TPU_NANGUARD", "flag", True,
         "Device-side non-finite guard in the train step: a step whose "
         "loss/gradients are not finite is select-reverted (params/updater/"
         "rng/iteration untouched) and counted; 0 disables.")
_declare("DL4J_TPU_NANGUARD_CKPT", "str", "dl4j_tpu_diverged.zip",
         "Checkpoint path the non-finite guard writes (last good params) "
         "before raising TrainingDivergedError.")
_declare("DL4J_TPU_NANGUARD_PATIENCE", "int", 3,
         "Consecutive bad dispatch groups (>=1 non-finite-reverted step) "
         "the guard tolerates before auto-checkpointing and raising "
         "TrainingDivergedError.")
_declare("DL4J_TPU_PALLAS_INTERPRET", "flag", False,
         "Run pallas kernels in interpreter mode (tests on CPU); read "
         "at trace time — set before kernels build.",
         trace_time=True)
_declare("DL4J_TPU_REFORM_TIMEOUT", "float", 30.0,
         "Deadline (seconds) for one elastic re-form wave: every "
         "OP_REFORM arrival waits at most this long for the wave to "
         "commit; at expiry the wave commits with whoever arrived (if "
         ">= DL4J_TPU_ELASTIC_MIN_WORKERS) or fails every arrival with "
         "CollectiveTimeoutError — never an unbounded wait (G012).")
_declare("DL4J_TPU_ROUTER_HEARTBEAT_S", "float", 0.25,
         "Heartbeat interval (seconds) of the serving ReplicaRouter "
         "(serving/router.py): each beat re-checks every replica's "
         "health (scheduler thread alive, not stopping), updates the "
         "router.replicas_healthy gauge and the rolling-p99 SLO window, "
         "and fails over a dead replica's work.")
_declare("DL4J_TPU_SERVE_AUTOTUNE", "flag", False,
         "First-request decode-width autotuner for the serving tier "
         "(serving/decode.py): with DL4J_TPU_SERVE_SLOTS unset, probe the "
         "DL4J_TPU_SERVE_SLOTS_LADDER at the first decode dispatch and "
         "persist the winner under DL4J_TPU_TUNE_CACHE_DIR (the fusion "
         "autotuner's probe-and-persist protocol); an explicit "
         "DL4J_TPU_SERVE_SLOTS always wins.")
_declare("DL4J_TPU_SERVE_BUCKETS", "str", "8",
         "Batch-size bucket ladder (comma-separated ints) the serving "
         "batcher pads request batches into (serving/batcher.py): a "
         "partial batch pads to the smallest bucket that fits, so the "
         "whole serving run dispatches through a fixed pre-compiled "
         "signature set.")
_declare("DL4J_TPU_SERVE_CHUNK", "int", 8,
         "Decode steps per continuous-batching dispatch "
         "(serving/decode.py): each compiled dispatch advances every "
         "active KV slot by this many tokens; new requests are admitted "
         "at chunk boundaries.")
_declare("DL4J_TPU_SERVE_DEADLINE_S", "float", 0.0,
         "Default per-request deadline budget (seconds) for serving "
         "submits that do not carry an explicit one (serving/_base.py): "
         "a request still queued past its deadline is swept BEFORE "
         "dispatch — it fails with ServeDeadlineError (ingress: 504) "
         "and never reaches the device. 0 (default) disables the "
         "implicit deadline; explicit submit(deadline_s=...) / ingress "
         "X-Deadline-Ms always wins.")
_declare("DL4J_TPU_SERVE_GEN_CACHE", "int", 8,
         "Bound on TransformerLM's compiled sampler/beam cache "
         "(_jit_gen, keyed by the blessed _gen_signature builder): the "
         "oldest compiled program is evicted FIFO once the cache holds "
         "this many signatures.")
_declare("DL4J_TPU_SERVE_KV_LADDER", "str", "",
         "Power-of-2 KV attention-window rungs for paged continuous-"
         "batching decode (serving/decode.py): each dispatch attends "
         "over the smallest rung covering the pool's max active "
         "position, one blessed compiled program per rung. Empty "
         "(default) derives 32,64,... capped at max_len; 'off' pins a "
         "single max_len rung (the pre-paging behaviour); explicit "
         "comma-separated ints are capped at max_len.")
_declare("DL4J_TPU_SERVE_PREFILL_LADDER", "str", "",
         "Power-of-2 prompt-window rungs for chunked prefill "
         "(serving/decode.py): admission ingests a whole window of "
         "prompt tokens per compiled dispatch instead of teacher-"
         "forcing them through the chunk sampler. Empty (default) "
         "derives 16,64,256,... capped at max_len; 'off' disables "
         "chunked prefill (prompts teacher-force through the decode "
         "chunk, the pre-prefill behaviour).")
_declare("DL4J_TPU_SERVE_PREFIX_CACHE_MB", "int", 64,
         "Byte budget (MiB) of the prompt-prefix KV page cache "
         "(serving/decode.py): prefill windows are memoised by prompt-"
         "prefix hash so a repeated system prompt computes its KV once; "
         "least-recently-used pages are evicted past the budget. 0 "
         "disables prefix sharing.")
_declare("DL4J_TPU_SERVE_QUEUE", "int", 256,
         "Serving request-queue capacity (serving/batcher.py + "
         "serving/decode.py): a submit() past this depth fails fast with "
         "ServeQueueFullError (backpressure) instead of growing the "
         "queue unboundedly.")
_declare("DL4J_TPU_SERVE_SLOTS", "int", None,
         "Decode-slot count B_slots of the continuous-batching KV cache "
         "(serving/decode.py): rows of the persistent "
         "[B_slots, kv_heads, max_len, head_dim] cache that concurrent "
         "generations are slotted into. Unset selects the autotuned or "
         "default width; an explicit value always wins.")
_declare("DL4J_TPU_SERVE_SLOTS_LADDER", "str", "2,4,8",
         "Candidate B_slots ladder the serving decode-width autotuner "
         "probes (comma-separated ints) when DL4J_TPU_SERVE_AUTOTUNE is "
         "set and DL4J_TPU_SERVE_SLOTS is unset.")
_declare("DL4J_TPU_SERVE_SLO_MS", "float", 0.0,
         "Serving latency SLO (milliseconds) the ReplicaRouter's "
         "adaptive shed gate holds (serving/router.py): when the "
         "rolling p99 of serve.request_seconds (heartbeat-windowed "
         "bucket deltas) exceeds it, new submits are early-rejected "
         "with ServeQueueFullError (ingress: 429 + Retry-After) so "
         "overload degrades to fast sheds instead of FIFO collapse; "
         "admitted traffic keeps a bounded p99. 0 (default) disables "
         "shedding.")
_declare("DL4J_TPU_SERVE_WAIT", "float", 0.002,
         "Batcher linger (seconds): how long the serving batch loop "
         "waits for more same-shape requests before dispatching a "
         "partial (padded) batch; the continuous decoder uses it as its "
         "idle poll interval.")
_declare("DL4J_TPU_SLOW", "flag", False,
         "Select the slow test lane (examples mains, real-MNIST accuracy "
         "gate); read raw in tests/conftest.py — see module docstring.")
_declare("DL4J_TPU_TEST_PLATFORM", "str", "cpu",
         "Platform the test suite forces before jax import; read raw in "
         "tests/conftest.py — see module docstring.")
_declare("DL4J_TPU_TUNE_CACHE_DIR", "str", "~/.dl4j_tpu/tune",
         "Directory the fusion autotuner persists its (model, bucket shape, "
         "backend) -> K decisions into (atomic_io tmp+fsync+rename commits): "
         "a restarted run skips the probe entirely; empty disables "
         "persistence (in-memory decisions only).")
_declare("DL4J_TPU_TRANSFER_STAGE", "int", 8,
         "Super-batch host->HBM staging factor for fit() paths; 1 disables "
         "(low-latency links / tight device memory).")
_declare("DL4J_TPU_TRANSFER_STAGE_BYTES", "int", 256 * 1024 * 1024,
         "Byte cap on one staged super-batch transfer (and ~2x this on "
         "queued staged batches).")
_declare("DL4J_TPU_W2V_BATCH", "int", None,
         "Tooling: word2vec bench/A-B pair-batch size (defaults are "
         "per-harness: 8192 degraded, 32768 full).")
_declare("DL4J_TPU_W2V_DTYPE", "str", "float32",
         "Word2vec lookup-table storage dtype (float32 or bfloat16; kernel "
         "math stays f32).")
_declare("DL4J_TPU_W2V_SCATTER", "str", "sorted",
         "Word2vec scatter strategy {fused, sorted, two}; 'sorted' "
         "deduplicates rows so the TPU scatter-add never serializes. "
         "Read at trace time; lookup.set_scatter_impl() switches "
         "mid-process (clears compiled kernels).",
         trace_time=True)


def _warn(name, raw, kind, default):
    import warnings
    warnings.warn(f"{name}={raw!r} is not a valid {kind}; "
                  f"using the default ({default!r})")


_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off"))


def env_flag(name):
    """Boolean knob. Accepts 1/true/yes/on and 0/false/no/off (any case);
    anything else warns and falls back to the declared default. A SET but
    EMPTY variable counts as unset (wrapper scripts and k8s env entries
    export empty values; they must not silently flip default-on knobs
    like DL4J_TPU_DP_SHARD_UPDATER off)."""
    knob = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return knob.default
    low = raw.strip().lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    _warn(name, raw, "flag", knob.default)
    return knob.default


def env_int(name, *, minimum=None):
    """Integer knob with the warn-and-fall-back contract. ``minimum``
    clamps the parsed value (e.g. staging factors are at least 1); the
    declared default may be None for knobs whose absence selects a
    computed heuristic (DL4J_TPU_FUSE_UNROLL)."""
    knob = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    try:
        v = int(raw)
    except ValueError:
        _warn(name, raw, "int", knob.default)
        return knob.default
    return v if minimum is None else max(minimum, v)


def env_float(name, *, minimum=None):
    """Float knob (timeouts/backoffs) with the warn-and-fall-back
    contract; ``minimum`` clamps the parsed value."""
    knob = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    try:
        v = float(raw)
    except ValueError:
        _warn(name, raw, "float", knob.default)
        return knob.default
    return v if minimum is None else max(minimum, v)


def env_str(name):
    """String knob: the raw value, or the declared default when unset."""
    knob = KNOBS[name]
    return os.environ.get(name, knob.default)


def env_is_set(name):
    """Whether a declared knob is EXPLICITLY set (non-empty) in the
    environment — for features keying on "the operator chose a value" vs
    "the default applies" (the fusion autotuner only engages while
    DL4J_TPU_FUSE_STEPS is unset). Empty counts as unset, matching
    env_flag's wrapper-script contract."""
    KNOBS[name]   # KeyError on an undeclared name: programming error
    raw = os.environ.get(name)
    return raw is not None and bool(raw.strip())


def knob_table_md():
    """The knob table as GitHub markdown — the body of docs/CONFIG.md.
    Regenerate with ``python -m deeplearning4j_tpu.config`` (or
    ``make knobs``); tests/test_graftlint.py keeps docs in sync."""
    rows = ["| Variable | Type | Default | Description |",
            "| --- | --- | --- | --- |"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        default = "*(unset)*" if k.default is None else f"`{k.default}`"
        rows.append(f"| `{k.name}` | {k.kind} | {default} | {k.doc} |")
    return "\n".join(rows)


if __name__ == "__main__":
    print("# Environment knobs")
    print()
    print("All runtime tuning flags, generated from the typed registry in")
    print("`deeplearning4j_tpu/config.py` (`python -m deeplearning4j_tpu"
          ".config > docs/CONFIG.md`).")
    print("Reads outside the registry fail tier-1 via the graftlint G003")
    print("rule — see `docs/STATIC_ANALYSIS.md`.")
    print()
    print(knob_table_md())
    print()
    print("## JAX's own variables")
    print()
    print("Two variables belong to JAX, not to this registry, and the")
    print("package follows them instead of wrapping them:")
    print()
    print("- `JAX_PLATFORMS` — `cpu` is the test and rehearsal lane; on a")
    print("  machine with a chip leave it unset. Nothing in the package")
    print("  switches platform on its own.")
    print("- `JAX_COMPILATION_CACHE_DIR` — the persistent XLA compilation")
    print("  cache. Where it is set JAX reads it itself and the package")
    print("  sets no directory; where it is not, the package uses the fixed")
    print("  `<checkout>/.jax_cache` (never a temporary name: a cache that")
    print("  moves never hits).")
