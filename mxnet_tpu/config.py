"""Typed configuration with environment-variable overrides.

Reference parity: docs/faq/env_var.md (the ~60 MXNET_* knobs) +
src/engine/engine.cc engine selection. Every knob is declared once with
a type, default, and what it maps to in the TPU-native runtime; values
resolve from the environment at first read (so tests can monkeypatch
os.environ) and can be overridden programmatically via set().

Knobs whose reference meaning is subsumed by XLA (memory pools, cuDNN
autotune, engine thread counts) are accepted-and-documented no-ops so
reference launch scripts run unchanged.
"""
from __future__ import annotations

import os
import threading

__all__ = ['Knob', 'KNOBS', 'get', 'set', 'unset', 'describe',
           'naive_engine', 'NaiveEngineScope', 'configure_compile_cache',
           'cpu_rig']

_lock = threading.Lock()
_values = {}
# bumped on every set()/unset(): lets hot paths (ops.traceknobs) cache
# derived views of the knob table and re-read only when it changed
_epoch = 0


def epoch():
    """Monotonic counter of programmatic knob changes (lock-free read —
    an int load is atomic under the GIL)."""
    return _epoch


class Knob:
    __slots__ = ('name', 'typ', 'default', 'doc', 'effective')

    def __init__(self, name, typ, default, doc, effective=True):
        self.name = name
        self.typ = typ
        self.default = default
        self.doc = doc
        self.effective = effective  # False = accepted no-op under XLA

    def parse(self, raw):
        if self.typ is bool:
            return raw not in ('0', '', 'false', 'False', None)
        return self.typ(raw)


def _knob(name, typ, default, doc, effective=True):
    return Knob(name, typ, default, doc, effective)


KNOBS = {k.name: k for k in [
    # engine / execution
    _knob('MXNET_ENGINE_TYPE', str, 'ThreadedEnginePerDevice',
          "Engine selection (env_var.md:104). 'NaiveEngine' = debug mode:"
          ' ops run un-jitted and synchronously (jax.disable_jit +'
          ' block_until_ready) so python tracebacks land on the faulting'
          ' op, like the reference NaiveEngine.'),
    _knob('MXNET_EXEC_BULK_EXEC_TRAIN', bool, True,
          'Bulked execution of the train graph. Maps to compiled-dispatch'
          ' jit caching on the eager path; 0 disables the jit cache.'),
    _knob('MXNET_EXEC_BULK_EXEC_INFERENCE', bool, True,
          'Same for inference paths.'),
    _knob('MXNET_BACKWARD_DO_MIRROR', bool, False,
          'Trade compute for memory in backward (graph_executor.cc:338).'
          ' Maps to jax.checkpoint rematerialization of HybridBlock'
          ' forwards (gluon.Block.hybridize(remat=True) analog).'),
    _knob('MXNET_EXEC_ENABLE_ROW_SPARSE_PULL', bool, False,
          'kvstore row_sparse_pull support.'),
    # RNG
    _knob('MXNET_SEED', int, None,
          'Global random seed applied at import when set.'),
    # data pipeline
    _knob('MXNET_CPU_WORKER_NTHREADS', int, 4,
          'Decode/augment worker threads for ImageRecordIter and the'
          ' gluon DataLoader default.'),
    _knob('MXNET_CPU_PRIORITY_NTHREADS', int, 4,
          'Reserved; accepted for launch-script parity.', effective=False),
    # memory (XLA buffer assignment owns memory planning)
    _knob('MXNET_GPU_MEM_POOL_RESERVE', int, 5,
          'XLA owns device memory planning.', effective=False),
    _knob('MXNET_GPU_MEM_POOL_TYPE', str, 'Naive',
          'XLA owns device memory planning.', effective=False),
    _knob('MXNET_EXEC_NUM_TEMP', int, 1,
          'XLA owns temp-buffer planning.', effective=False),
    # cudnn knobs: no cuDNN on TPU
    _knob('MXNET_CUDNN_AUTOTUNE_DEFAULT', int, 1,
          'No cuDNN on TPU; XLA autotunes convolutions.', effective=False),
    _knob('MXNET_CUDNN_LIB_CHECKING', bool, True,
          'No cuDNN on TPU.', effective=False),
    # kvstore / distributed — mesh collectives run on ICI; the host
    # kvstore path reduces with single jnp calls, so these are no-ops
    _knob('MXNET_KVSTORE_REDUCTION_NTHREADS', int, 4,
          'Host-side reduction threads.', effective=False),
    _knob('MXNET_KVSTORE_BIGARRAY_BOUND', int, 1000000,
          'Size threshold for sharded server pushes.', effective=False),
    _knob('MXNET_ENABLE_GPU_P2P', bool, True,
          'ICI is always on for TPU meshes.', effective=False),
    # profiler
    _knob('MXNET_PROFILER_AUTOSTART', bool, False,
          'Start the profiler at import.'),
    _knob('MXNET_PROFILER_MODE', int, 0,
          'Profiler detail mode.', effective=False),
    # misc
    _knob('MXNET_HOME', str, os.path.join(os.path.expanduser('~'),
                                          '.mxnet'),
          'Model-store / data cache root.'),
    _knob('MXNET_GLUON_REPO', str, 'https://apache-mxnet.s3'
          '-accelerate.dualstack.amazonaws.com/',
          'Pretrained-weight repository base URL (model_store).'),
    _knob('MXNET_ENFORCE_DETERMINISM', bool, False,
          'Forbid non-deterministic kernels (env_var.md). XLA TPU'
          ' lowering is deterministic for everything this build emits,'
          ' so the flag is honored by construction.'),
    _knob('MXNET_UPDATE_ON_KVSTORE', bool, False,
          'Default for Trainer update_on_kvstore. Unlike the reference'
          " (default True on single-machine stores), the TPU build"
          ' defaults to False: the fused client-side step outperforms'
          ' the in-store optimizer path.'),
    _knob('MXNET_OPTIMIZER_AGGREGATION_SIZE', int, 4,
          'Max weights fused per multi-tensor optimizer call'
          ' (multi_sgd_update family). The fused ParallelTrainer step'
          ' already updates every weight in one XLA program, so this'
          ' only shapes the eager Updater path.'),
    _knob('MXNET_MP_WORKER_NTHREADS', int, 1,
          'gluon DataLoader multiprocessing workers default.'),
    # resilience layer (docs/RESILIENCE.md)
    _knob('MXNET_TPU_FAULT', str, None,
          'Scripted fault injection: comma list of kind[@site][:count]'
          ' (device_unavailable, device_stall, worker_crash, preempt,'
          ' hang, device_loss, and the value kinds nan/inf, e.g.'
          ' nan@grads:2 for the guardrail or preempt@train.step.12:1'
          ' to preempt exactly at step 12).'
          ' CI and tests only; leave unset in production.'),
    # automatic mixed precision (docs/PRECISION.md)
    _knob('MXNET_TPU_AMP', str, None,
          "Default AMP policy ('bf16' | 'fp16' | 'off') for"
          ' ParallelTrainer / Module.fit / gluon Trainer when no'
          ' explicit amp= is passed. Low-precision compute copies are'
          ' cast inside the compiled step; fp32 master weights,'
          ' optimizer state, guardrail sentinel and checkpoints stay'
          " float32 (bit-exact resume). 'fp16' auto-enables the"
          ' dynamic-loss-scaling guardrail. Unset/off keeps every'
          ' program float32, byte-identical to pre-AMP builds.'),
    # numerical guardrail (docs/GUARDRAILS.md)
    _knob('MXNET_TPU_GUARDRAIL', bool, False,
          'Default-enable the in-jit numerical guardrail (health'
          ' sentinel + dynamic loss scaling + skip-update) in'
          ' ParallelTrainer when no explicit guardrail= is passed.'),
    _knob('MXNET_TPU_LOSS_SCALE', float, 32768.0,
          'Initial dynamic loss scale (power of two; the schedule'
          ' halves on overflow, doubles after'
          ' MXNET_TPU_LOSS_SCALE_WINDOW good steps, capped at 2**24).'),
    _knob('MXNET_TPU_LOSS_SCALE_WINDOW', int, 2000,
          'Consecutive healthy steps before the loss scale doubles'
          ' (the reference contrib/amp scale_window).'),
    _knob('MXNET_TPU_GUARD_WINDOW', int, 64,
          'Rolling-window length for the host anomaly policy'
          ' (loss/grad-norm z-score baselines).'),
    _knob('MXNET_TPU_GUARD_ZSCORE', float, 6.0,
          'z-score threshold above the rolling baseline that trips a'
          ' loss-spike / grad-spike rollback.'),
    _knob('MXNET_TPU_GUARD_PATIENCE', int, 3,
          'Consecutive non-finite (skipped) steps before the policy'
          ' escalates from skipping to a checkpoint rollback.'),
    _knob('MXNET_TPU_GUARD_CHECK_EVERY', int, 1,
          'Host-side policy cadence: process queued sentinel events'
          ' every N steps (a sync point); 0 defers all processing to'
          ' explicit flush() calls (dispatch-pipelined loops).'),
    _knob('MXNET_TPU_GUARD_SNAPSHOT_EVERY', int, 25,
          'Steps between last-good rollback snapshots taken by guarded'
          ' drivers (guardrail/rollback.py).'),
    _knob('MXNET_TPU_GUARD_MAX_ROLLBACKS', int, 3,
          'Rollback budget per run; exhausting it raises'
          ' GuardrailExhausted instead of looping on a poisoned job.'),
    _knob('MXNET_TPU_ACQUIRE_ATTEMPTS', int, 3,
          'Backend-acquisition retry attempts before degrading to the'
          ' CPU fallback / unavailable status.'),
    _knob('MXNET_TPU_ACQUIRE_BACKOFF_S', float, 2.0,
          'Base exponential-backoff delay (seconds) between backend'
          ' acquisition attempts.'),
    _knob('MXNET_TPU_ACQUIRE_DEADLINE_S', float, 300.0,
          'Total wall-clock budget for backend acquisition retries.'),
    # telemetry / observability (docs/OBSERVABILITY.md)
    _knob('MXNET_TPU_TELEMETRY', bool, True,
          'Master switch for the unified telemetry layer (metrics'
          ' registry + step-phase spans + flight recorder). 0 turns'
          ' every instrument into a flag-check no-op with no per-step'
          ' allocation.'),
    _knob('MXNET_TPU_TELEMETRY_HTTP_PORT', int, 0,
          'Port for the stdlib Prometheus /metrics HTTP endpoint'
          ' (binds 127.0.0.1). 0 (default) keeps the server off;'
          ' production scrapes tail the file exporter instead.'),
    _knob('MXNET_TPU_TELEMETRY_HLO', bool, False,
          'Automatically account per-step collective bytes (optimized-'
          'HLO analysis) into the registry after each ParallelTrainer'
          ' build. Off by default: the accounting re-lowers the'
          ' program once per build; drivers can instead call'
          ' observability.trainer_collective_stats explicitly.'),
    _knob('MXNET_TPU_FLIGHT', bool, True,
          'Flight recorder enable (subordinate to MXNET_TPU_TELEMETRY):'
          ' keep a bounded ring of structured run events and dump a'
          ' mxnet_tpu.flight.v1 JSONL artifact on crash / stall /'
          ' preemption.'),
    _knob('MXNET_TPU_FLIGHT_CAPACITY', int, 2048,
          'Flight recorder ring size (events); the oldest events drop'
          ' when full.'),
    _knob('MXNET_TPU_FLIGHT_PATH', str, 'FLIGHT.jsonl',
          'Default dump path for the flight-recorder artifact.'),
    _knob('MXNET_TPU_TRACE', bool, False,
          'Distributed request tracing enable (off by default): carry'
          ' a trace context across gateway/replica hops in the'
          ' X-Mxnet-Trace header and emit mxnet_tpu.trace.v1 span'
          ' records into the bounded per-process span buffer served'
          ' at GET /trace.'),
    _knob('MXNET_TPU_TRACE_BUFFER', int, 4096,
          'Span-buffer capacity per process (records); the oldest'
          ' spans drop when full.'),
    # inference serving engine (docs/SERVING.md)
    _knob('MXNET_TPU_SERVE_MAX_BATCH', int, 64,
          'Micro-batcher aggregation cap and the default top of the'
          ' bucket ladder: a flush happens the moment this many'
          ' requests wait.'),
    _knob('MXNET_TPU_SERVE_DEADLINE_MS', float, 5.0,
          'Micro-batch flush deadline: the oldest queued request'
          ' never waits longer than this before its (possibly'
          ' partial) batch dispatches. The latency half of the'
          ' batching trade; MXNET_TPU_SERVE_MAX_BATCH is the'
          ' throughput half.'),
    _knob('MXNET_TPU_SERVE_QUEUE_DEPTH', int, 256,
          'Admission-control bound on pending requests; a submit'
          ' against a full queue raises the typed BackpressureError'
          ' (HTTP 429) immediately instead of queueing unboundedly.'),
    _knob('MXNET_TPU_SERVE_TIMEOUT_S', float, 30.0,
          'Per-request budget: a request older than this fails with'
          ' RequestTimeout (HTTP 504) instead of occupying a batch'
          ' slot after its client gave up; 0 disables.'),
    _knob('MXNET_TPU_SERVE_DRAIN_TIMEOUT_S', float, 30.0,
          'Graceful-drain handoff budget: a draining replica waits this'
          ' long for every exported seqstate payload to be fetched (or'
          ' readmitted) before the drain result records expired.'),
    _knob('MXNET_TPU_SERVE_BUCKETS', str, None,
          'Explicit batch bucket ladder as a comma list (e.g.'
          ' "1,8,32,128"); unset derives powers of two up to'
          ' MXNET_TPU_SERVE_MAX_BATCH. Recompile count is bounded by'
          ' the ladder size.'),
    _knob('MXNET_TPU_SERVE_BREAKER', int, 3,
          'Consecutive device-side batch failures before the serving'
          ' circuit breaker opens and batches go straight to the CPU'
          ' fallback until the reset probe succeeds.'),
    _knob('MXNET_TPU_SERVE_HTTP_PORT', int, 0,
          'Port for the stdlib JSON inference endpoint'
          ' (/predict, /generate, /status, /healthz; binds'
          ' 127.0.0.1). 0 (default) keeps the server off —'
          ' production fronts the engine with a real gateway.'),
    # autoregressive decode engine (docs/SERVING.md "Autoregressive
    # decoding")
    _knob('MXNET_TPU_SERVE_DECODE_SLOTS', int, 8,
          'In-flight sequence slots in the continuous decode batch —'
          ' the decode-step program\'s ONE compiled batch shape.'
          ' Sequences join/leave slots at token granularity; the'
          ' preallocated KV/state cache is slots x max_len.'),
    _knob('MXNET_TPU_SERVE_MAX_SEQ_LEN', int, 256,
          'Per-slot cache capacity: prompt + generated tokens per'
          ' sequence never exceed this (the KV cache length baked'
          ' into the decode programs at freeze time).'),
    _knob('MXNET_TPU_SERVE_PREFILL_BUCKETS', str, None,
          'Explicit prompt-length bucket ladder for prefill programs'
          ' as a comma list (e.g. "8,32,128"); unset derives powers'
          ' of two up to MXNET_TPU_SERVE_MAX_PREFILL. Total compiled'
          ' programs for any generation workload = ladder size + 1'
          ' (the single decode step).'),
    _knob('MXNET_TPU_SERVE_MAX_PREFILL', int, 64,
          'Default top of the prefill ladder: the longest admissible'
          ' prompt. Longer prompts reject typed at admission instead'
          ' of compiling new shapes.'),
    _knob('MXNET_TPU_SERVE_MAX_NEW_TOKENS', int, 64,
          'Default generation budget per request when the caller'
          ' does not pass max_new_tokens.'),
    _knob('MXNET_TPU_SERVE_PREFILL_INTERLEAVE', int, 1,
          'Prompt prefills admitted between consecutive decode steps'
          ' while sequences are in flight: raises join throughput at'
          ' the cost of decode-step latency jitter. An idle engine'
          ' always admits up to every free slot.'),
    _knob('MXNET_TPU_SERVE_PAGED', bool, True,
          'Use the block/paged KV cache for decode families that'
          ' support it (transformers): a shared page pool + per-'
          'sequence page tables instead of slots x max_len'
          ' preallocation, so HBM is reserved per page actually'
          ' used. 0 keeps the PR-6 slot cache'
          ' (docs/SERVING.md "Paged KV cache").'),
    _knob('MXNET_TPU_SERVE_PAGE_SIZE', int, 16,
          'KV rows per page of the paged decode cache (power of'
          ' two). Small pages waste less memory on short sequences'
          ' and share prefixes at finer grain; large pages shrink'
          ' page-table overhead and gather fan-in.'),
    _knob('MXNET_TPU_SERVE_PAGES', int, 0,
          'Page-pool size (pages, incl. the reserved trash page) for'
          ' the paged decode cache. 0 (default) sizes the pool to the'
          ' slot cache\'s worst case (slots x max_pages + 1); smaller'
          ' pools trade worst-case capacity for HBM — admission'
          ' rejects typed (BackpressureError) when the pool is'
          ' exhausted, never a stall.'),
    _knob('MXNET_TPU_SERVE_PREFIX_CACHE', bool, True,
          'Share common prompt prefixes across sequences in the paged'
          ' decode cache: full (and exactly-matching partial) prompt'
          ' pages are refcounted and referenced read-only by later'
          ' hash-matching prompts — prefilled once, copied-on-write'
          ' at the first divergent token. 0 disables sharing.'),
    _knob('MXNET_TPU_SERVE_SPEC_K', int, 0,
          'Speculative-decoding lookahead: the draft model proposes'
          ' this many tokens per scheduler tick and the target model'
          ' verifies them in ONE batched step (greedy acceptance).'
          ' 0 (default) disables speculation. Requires a paged target'
          ' program and a draft (MXNET_TPU_SERVE_SPEC_DRAFT or'
          ' DecodeEngine(draft=...)).'),
    _knob('MXNET_TPU_SERVE_SPEC_DRAFT', str, None,
          'Path to a frozen decode artifact to load as the'
          ' speculative-decoding draft model (same vocab as the'
          ' target; transformer family, so rejected proposals roll'
          ' back for free; frozen SLOT-addressed, paged=False — a'
          ' draft-sized cache has no memory wall to page). Unset ='
          ' no speculation unless a draft is passed'
          ' programmatically.'),
    _knob('MXNET_TPU_SERVE_MAX_CONCURRENT', int, 0,
          'Cap on in-flight HTTP POST handlers (one thread per'
          ' connection): past it requests shed instantly with 429 +'
          ' Retry-After instead of piling scheduling contention onto'
          ' admitted requests. 0 (default) = unbounded, the'
          ' pre-harness behavior; production fronts set it to a'
          ' small multiple of the batch/slot capacity.'),
    # multi-adapter (LoRA) serving + sampled decoding
    # (serving/adapters/, docs/SERVING.md "Multi-adapter serving &
    # sampling")
    _knob('MXNET_TPU_SERVE_SAMPLE_MASK', bool, False,
          'Also compile the per-request additive logit-mask argument'
          ' (grammar/JSON constrained decoding hook): a (rows, vocab)'
          ' float32 mask added to logits before sampling. Costs'
          ' slots x vocab of transfer per step when used; off by'
          ' default.'),
    _knob('MXNET_TPU_SERVE_ADAPTER_RANK', int, 0,
          'Low-rank adapter (LoRA) pool rank compiled into the decode'
          ' step: per-request A/B deltas gather from a device-'
          'resident pool inside the ONE compiled program, so adapter'
          ' switches are int32 array-arg changes (zero retraces).'
          ' 0 (default) freezes the base-only signature.'),
    _knob('MXNET_TPU_SERVE_ADAPTER_SLOTS', int, 8,
          'Device-resident adapter pool capacity (rows, incl. the'
          ' reserved all-zero base row 0): how many LoRA variants can'
          ' serve concurrently. Unpinned rows evict LRU on a cold'
          ' load; with every row pinned a new adapter admission'
          ' rejects typed (AdapterExhaustedError, shed/retry).'),
    _knob('MXNET_TPU_SERVE_ADAPTER_DIR', str, None,
          'Artifact-directory root the decode engine\'s adapter'
          ' registry resolves unknown adapter ids against:'
          ' <dir>/<id> must hold a mxnet_tpu.adapter.v1 artifact'
          ' (loaded lazily on first use, digest-verified). Unset ='
          ' only programmatically registered adapters resolve.'),
    # open-loop load harness + SLO gate (docs/SERVING.md "SLOs and
    # overload behavior", tools/slo_gate.py)
    _knob('MXNET_TPU_SLO_P99_MS', float, 500.0,
          'Admitted-request p99 latency budget (ms) the load harness'
          ' gates on: capacity search bisects the max QPS holding it,'
          ' overload mode asserts admission control protects it at'
          ' 2.5x capacity. SLO_BASELINE.json overrides it in CI.'),
    _knob('MXNET_TPU_SLO_SHED_P99_MS', float, 250.0,
          'p99 budget (ms) for SHED responses: a 429 must be a fast'
          ' rejection, not a slow timeout — overload mode fails when'
          ' shedding itself is slow.'),
    _knob('MXNET_TPU_SLO_AVAILABILITY', float, 0.85,
          'Chaos-soak availability floor: fraction of offered'
          ' requests that must be ADMITTED (2xx, degraded allowed)'
          ' while scripted faults fire. Sheds (429) count as'
          ' unavailable — the floor prices how much shedding the'
          ' degraded paths are allowed to need.'),
    _knob('MXNET_TPU_SLO_RECOVERY_S', float, 12.0,
          'Per-fault recovery ceiling (seconds): after a scripted'
          ' fault burst clears, /status must report every session ok'
          ' with its breaker closed within this budget.'),
    _knob('MXNET_TPU_SLO_PREFIX_TTFT_P99_MS', float, 400.0,
          'TTFT p99 budget (ms) for the shared-prefix loadgen'
          ' workload (mxnet_tpu.loadgen --mode prefix): Zipf-'
          'distributed system prompts + one-token suffixes against'
          ' the paged decode engine with prefix sharing on.'
          ' SLO_BASELINE.json prefix_ttft_p99_ms overrides it in the'
          ' slo CI stage.'),
    _knob('MXNET_TPU_SLO_GOODPUT', float, 0.9,
          'Capacity-search goodput floor: fraction of offered'
          ' requests served clean (200, no typed error) a rate must'
          ' sustain to count as within capacity.'),
    _knob('MXNET_TPU_SLO_GATEWAY_AVAILABILITY', float, 0.99,
          'Availability floor for the gateway-failover drill'
          ' (mxnet_tpu.loadgen --mode gateway-failover): fraction of'
          ' streams that must complete CLEAN — zero error lines —'
          ' while a replica is killed mid-stream and the gateway'
          ' resumes them on the survivors.'),
    _knob('MXNET_TPU_SLO_TENANT_TTFT_P99_MS', float, 400.0,
          'Steady-tenant TTFT p99 budget (ms) for the two-tenant'
          ' burst phase (--mode tenants): while another tenant'
          ' bursts past its bucket, the steady tenant\'s time to'
          ' first token must stay inside this budget (zero'
          ' cross-tenant SLO bleed).'),
    _knob('MXNET_TPU_SLO_TENANT_TPOT_P99_MS', float, 250.0,
          'Steady-tenant TPOT p99 budget (ms) for the two-tenant'
          ' burst phase: per-output-token latency of the steady'
          ' tenant\'s admitted streams under a neighbor\'s burst.'),
    _knob('MXNET_TPU_SLO_DRAIN_AVAILABILITY', float, 1.0,
          'Availability floor for the drain drill (--mode drain): a'
          ' GRACEFUL preemption loses nothing, so the default demands'
          ' every stream completes clean.'),
    _knob('MXNET_TPU_SLO_DISAGG_AVAILABILITY', float, 0.99,
          'Availability floor for the disaggregated prefill/decode'
          ' drill (--mode disagg): fraction of mixed long/short'
          ' streams that must complete CLEAN while one replica of'
          ' EACH class is hard-killed mid-run.'),
    _knob('MXNET_TPU_SLO_DISAGG_TTFT_P99_MS', float, 2500.0,
          'TTFT p99 budget (ms) for the disagg drill\'s mixed'
          ' workload: time to first token INCLUDING the prefill-class'
          ' admission (the boundary token streams from the prefill'
          ' replica before the handoff completes).'),
    _knob('MXNET_TPU_SLO_ADAPTER_TTFT_P99_MS', float, 600.0,
          'TTFT p99 budget (ms) for the multi-adapter loadgen'
          ' workload (--mode adapters): Zipf-distributed adapter ids'
          ' + sampled/greedy mix against one engine — admissions pay'
          ' at most one adapter pool upload, never a retrace.'
          ' SLO_BASELINE.json adapter_ttft_p99_ms overrides it in'
          ' the slo CI stage.'),
    _knob('MXNET_TPU_LOADGEN_SEED', int, 0,
          'Default seed for the open-loop arrival schedule'
          ' (mxnet_tpu.loadgen): same seed, same arrival times and'
          ' request kinds — load runs are replayable.'),
    _knob('MXNET_TPU_LOADGEN_MAX_QPS', float, 100.0,
          'Ceiling on the offered rate overload mode will drive:'
          ' past O(100) connections/s the stdlib endpoint\'s accept'
          ' loop (kernel SYN queue) owns the latency on a small'
          ' host, and the harness gates admission control, not the'
          ' accept path. Raise it when fronting with a real gateway.'),
    _knob('MXNET_TPU_LOADGEN_MAX_INFLIGHT', int, 512,
          'Client-side bound on concurrently in-flight harness'
          ' requests (one thread each). An arrival above the bound'
          ' resolves as client_saturated — counted against goodput,'
          ' never silently dropped.'),
    _knob('MXNET_TPU_LOADGEN_RETRIES', int, 0,
          'Loadgen client retry budget on 429/503: each retry honors'
          ' the server\'s Retry-After (capped by'
          ' MXNET_TPU_LOADGEN_RETRY_CAP_S) before re-firing, and the'
          ' record counts its retries in the taxonomy. 0 (default)'
          ' keeps the one-shot open-loop behavior the overload'
          ' verdicts are calibrated on.'),
    _knob('MXNET_TPU_LOADGEN_RETRY_CAP_S', float, 2.0,
          'Ceiling on a single loadgen retry backoff sleep: a'
          ' Retry-After above it is clamped so a mis-advertised hint'
          ' cannot stall the harness.'),
    # performance: roofline audit / vjp rescheduling / input prefetch
    # (docs/PERFORMANCE.md)
    _knob('MXNET_TPU_ROOFLINE_PEAK_TFLOPS', float, 197.0,
          'Reference-chip peak (bf16 TFLOP/s) for the roofline audit'
          ' classification (observability.roofline). Fixed reference'
          ' (TPU v5e-class) by default so artifacts diff stably across'
          ' hosts; set to the target chip when auditing for it.'),
    _knob('MXNET_TPU_ROOFLINE_PEAK_TFLOPS_FP32', float, 0.0,
          'Reference-chip fp32 peak (TFLOP/s) used when the roofline'
          ' audits a float32 (non-AMP) program — MFU/ridge against the'
          ' bf16 peak is meaningless for fp32 compute. 0 (default)'
          ' derives half the bf16 peak (the MXU fp32 passthrough'
          ' rate).'),
    _knob('MXNET_TPU_ROOFLINE_HBM_GBPS', float, 819.0,
          'Reference-chip HBM bandwidth (GB/s) for the roofline ridge'
          ' point (peak/bandwidth = flops-per-byte threshold between'
          ' memory- and compute-bound fusions).'),
    _knob('MXNET_TPU_FUSION_BUDGET_PCT', float, 2.0,
          'Fusion-budget regression gate (tools/fusion_audit.py'
          ' --gate): total HBM bytes/step may exceed the baseline'
          ' artifact by at most this percentage before the CI stage'
          ' fails. One-sided: improvements always pass.'),
    _knob('MXNET_TPU_FUSION_BUDGET_COUNT', int, 0,
          'Extra fusions (beyond the baseline count) the fusion-budget'
          ' gate tolerates before failing.'),
    _knob('MXNET_TPU_PALLAS', str, None,
          'Hand-written Pallas kernels for the audit-ranked memory-'
          'bound clusters (docs/PERFORMANCE.md "Hand-written'
          ' kernels"): comma list of families out of'
          ' attention,epilogue,xent (1 = all, 0/unset = off). Build-'
          'time knob snapshotted through ops.traceknobs and folded'
          ' into jit cache keys, so flips re-jit instead of latching.'
          ' Kernels Mosaic-compile on TPU and run through the Pallas'
          ' interpreter everywhere else; knob-off programs are byte-'
          'identical to pre-kernel builds.'),
    _knob('MXNET_TPU_VJP_RESCHEDULE', bool, True,
          'Use the hand-scheduled custom_vjp paths for the memory-'
          'bound hot ops (Activation/LeakyReLU save-output backward,'
          ' Dropout mask regeneration, softmax_cross_entropy one-pass'
          ' gradient, max-Pooling unrolled equality-mask backward) in'
          ' addition to the BatchNorm/LayerNorm cores. 0 falls back to'
          ' plain autodiff everywhere (the A/B reference; flip it'
          ' before the first trace — already-compiled eager programs'
          ' are not invalidated).'),
    # 2-D mesh / ZeRO sharded weight update (docs/PARALLEL.md)
    _knob('MXNET_TPU_ZERO', bool, False,
          'Shard the weight update + optimizer state across the dp'
          ' mesh axis (ZeRO / "Automatic Cross-Replica Sharding of'
          ' Weight Update" recipe): each replica owns 1/dp of every'
          ' state tensor, gradients reach the update via reduce-'
          'scatter, updated param shards are all-gathered back — all'
          ' inside the one compiled step program. Bit-identical to'
          ' the replicated update at dp-only shapes (docs/PARALLEL.md'
          ' contract); per-device optimizer-state memory drops ~1/dp.'),
    _knob('MXNET_TPU_MODEL_AXIS', str, 'model',
          'Name of the model-parallel mesh axis ShardingRules treats'
          ' as column-parallel by default and that gluon/Module'
          ' sharding annotations (P(None, "model")-style specs) refer'
          ' to. The elastic shrink path preserves this axis; only dp'
          ' shrinks.'),
    _knob('MXNET_TPU_PREFETCH', int, 2,
          'Host->device input staging depth for Module.fit /'
          ' ParallelTrainer.prefetch_iter / DataLoader'
          ' (io.DevicePrefetcher): a background thread pulls batches'
          ' and issues the device transfer so data_wait overlaps the'
          ' previous step\'s compute (double-buffered at the default'
          ' 2). 0 disables staging (fully synchronous input path).'),
    _knob('MXNET_TPU_PREFETCH_TIMEOUT_S', float, 30.0,
          'How long a consumer waits on the staging thread before'
          ' degrading to synchronous transfers (a hung staging thread'
          ' — real or injected hang@io.prefetch — must never deadlock'
          ' fit; pending batches are recovered, none are dropped).'),
    # pod-scale multi-host runtime (docs/DISTRIBUTED.md)
    _knob('MXNET_TPU_DIST_INIT_TIMEOUT_S', float, 300.0,
          'Budget for the jax.distributed join handshake at import'
          ' (read from the ENVIRONMENT by mxnet_tpu._dist_init — it'
          ' runs before this registry loads, so config.set has no'
          ' effect on it). Expiry raises the typed DistInitError'
          ' instead of blocking forever on a missing coordinator.'),
    _knob('MXNET_TPU_DIST_BARRIER_TIMEOUT_S', float, 60.0,
          'Default timeout for dist.Coordinator named barriers and'
          ' broadcasts: a peer that never arrives surfaces as a typed'
          ' HostLostError/BarrierTimeout within this budget — never a'
          ' collective hang.'),
    _knob('MXNET_TPU_DIST_HEARTBEAT_S', float, 2.0,
          'Cadence of the dist.Coordinator background liveness stamp'
          ' (key-value heartbeat on the coordination service).'),
    _knob('MXNET_TPU_DIST_HEARTBEAT_TIMEOUT_S', float, 10.0,
          'A peer whose newest heartbeat stamp is older than this is'
          ' declared lost (Coordinator.dead_peers/check_peers raise'
          ' HostLostError naming it).'),
    _knob('MXNET_TPU_DIST_LOCAL_DEVICES', int, 0,
          'Virtual CPU devices per worker the dist launcher forces'
          ' via --xla_force_host_platform_device_count (the 1-device-'
          'per-host pod simulation). 0 leaves XLA_FLAGS untouched.'),
    # serving gateway (docs/DISTRIBUTED.md "Gateway")
    _knob('MXNET_TPU_GATEWAY_PORT', int, 0,
          'Default port for the multi-replica serving gateway when'
          ' ServingGateway(port=None) (binds 127.0.0.1; 0 picks a'
          ' free port).'),
    _knob('MXNET_TPU_GATEWAY_HEALTH_S', float, 1.0,
          'Gateway health-probe cadence: each replica\'s /healthz is'
          ' polled this often; non-200 (or unreachable) replicas'
          ' leave the routing rotation until they recover.'),
    _knob('MXNET_TPU_GATEWAY_TIMEOUT_S', float, 30.0,
          'Per-request budget for a gateway-forwarded upstream call;'
          ' an unreachable replica fails over to the next healthy'
          ' one, and an all-replicas-down gateway answers typed 503.'),
    _knob('MXNET_TPU_GATEWAY_RESUME', bool, True,
          'Mid-stream failover for /generate: the gateway journals'
          ' every streamed token and, when a replica dies mid-stream,'
          ' re-admits the request on a healthy replica with'
          ' prompt+emitted-tokens as the new prefix, splicing the'
          ' resumed tokens into the SAME client NDJSON stream'
          ' (at-most-once per token index). 0 restores the pre-resume'
          ' behavior: typed abort line / cut connection.'),
    _knob('MXNET_TPU_GATEWAY_RESUME_MAX', int, 2,
          'Bounded resume attempts per stream: after this many'
          ' mid-stream failovers the gateway stops retrying and emits'
          ' the typed ReplicaLost abort line (partial tokens'
          ' attached), ending the chunked stream cleanly.'),
    _knob('MXNET_TPU_GATEWAY_AFFINITY', bool, True,
          'Prefix-affine /generate routing: rendezvous-hash the'
          ' prompt-prefix fingerprint over the healthy replica set so'
          ' a shared system prompt keeps landing on the replica whose'
          ' PrefixCache already holds it (resume targets prefer the'
          ' prefix owner too). 0 = plain round-robin.'),
    _knob('MXNET_TPU_GATEWAY_TENANT_HEADER', str, 'X-Tenant',
          'Request header naming the tenant for per-tenant admission'
          ' at the gateway; requests without it share the "default"'
          ' tenant bucket.'),
    _knob('MXNET_TPU_GATEWAY_TENANT_RPS', float, 0.0,
          'Per-tenant token-bucket refill rate (requests/second) at'
          ' the gateway: past it a tenant sheds typed 429s with a'
          ' Retry-After naming when its bucket refills, so one'
          ' tenant\'s burst cannot starve the pool. 0 (default)'
          ' disables rate admission.'),
    _knob('MXNET_TPU_GATEWAY_TENANT_BURST', float, 0.0,
          'Per-tenant token-bucket depth (burst allowance). 0 derives'
          ' it as max(1, 2x MXNET_TPU_GATEWAY_TENANT_RPS).'),
    _knob('MXNET_TPU_GATEWAY_TENANT_MAX_INFLIGHT', int, 0,
          'Gateway-wide in-flight request cap shared weighted-fair'
          ' across active tenants: a tenant may exceed its 1/k share'
          ' only while the pool has slack, so a burst queues behind'
          ' its own share, not everyone\'s. 0 = unbounded.'),
    _knob('MXNET_TPU_GATEWAY_JOURNAL_MAX', int, 0,
          'Per-stream resume-journal cap (tokens): past it the'
          ' journal degrades to the relayed COUNT — a later resume'
          ' re-admits the ORIGINAL prompt and greedy determinism +'
          ' index dedup re-derive the delivered prefix. 0 = unbounded'
          ' journal.'),
    _knob('MXNET_TPU_GATEWAY_CLASS_MAP', str, '',
          'Disaggregated replica classes as "url=class,url=class"'
          ' (class in prefill|decode|both): a prefill replica takes'
          ' /generate admissions and exports seqstate at the prefill'
          ' boundary, a decode replica takes the POST /import step'
          ' loop. Any replica declaring a role makes the gateway'
          ' disaggregated; unlisted replicas stay "both". Explicit'
          ' ServingGateway(classes=...) entries override this map.'),
    _knob('MXNET_TPU_GATEWAY_HANDOFF_TIMEOUT_S', float, 10.0,
          'Per-attempt budget for the prefill->decode seqstate'
          ' handoff POST /import: past it the attempt counts against'
          ' MXNET_TPU_GATEWAY_HANDOFF_RETRIES and the payload goes to'
          ' the next decode-class member.'),
    _knob('MXNET_TPU_GATEWAY_HANDOFF_RETRIES', int, 2,
          'Bounded handoff retries per prefill-boundary export:'
          ' refusals (pool pressure, geometry/version checks) and'
          ' dead decode targets each consume one; past the budget the'
          ' request falls back MONOLITHIC on the prefill class —'
          ' never dropped.'),
    _knob('MXNET_TPU_GATEWAY_DISAGG_MIN_PROMPT', int, 0,
          'Prompt-length threshold (tokens) for the disaggregated'
          ' path: prompts at/above it admit prefill_only on the'
          ' prefill class and hand their seqstate to the decode'
          ' class; shorter prompts run monolithically ON the prefill'
          ' class (the decode class only ever imports). 0'
          ' disaggregates every streamed /generate.'),
    # preemption / elasticity / watchdog (docs/RESILIENCE.md)
    _knob('MXNET_TPU_PREEMPT_EXIT_CODE', int, 75,
          'Process exit code marking a preempted-but-resumable run'
          ' (75 = BSD EX_TEMPFAIL). Launchers restart the same command'
          ' on this rc; any other non-zero rc is a real failure.'),
    _knob('MXNET_TPU_PREEMPT_GRACE_S', float, 30.0,
          'Drain budget after a SIGTERM/SIGINT: the emergency'
          ' checkpoint must finish within this many seconds (the'
          ' preemption notice-to-reclaim window).'),
    _knob('MXNET_TPU_CKPT_EVERY_N_STEPS', int, 0,
          'Step-granular checkpoint cadence for Module.fit /'
          ' ParallelTrainer when a checkpoint_dir is given; 0 keeps'
          ' epoch-boundary-only checkpoints.'),
    _knob('MXNET_TPU_CKPT_KEEP', int, 2,
          'How many step-granular checkpoints CheckpointManager'
          ' retains (keep=N pruning; the newest that validates wins'
          ' at resume).'),
    _knob('MXNET_TPU_ELASTIC', bool, True,
          'Allow a restart that sees fewer devices than the checkpoint'
          ' mesh to shrink the dp axis and preserve the global batch'
          ' via gradient accumulation; 0 makes a device-count mismatch'
          ' a hard error.'),
    _knob('MXNET_TPU_WATCHDOG_COMPILE_S', float, 1800.0,
          'Watchdog stall budget (seconds) for the compile phase'
          ' (first-program XLA compiles legitimately take minutes).'),
    _knob('MXNET_TPU_WATCHDOG_STEP_S', float, 300.0,
          'Watchdog stall budget for a dispatched compiled step.'),
    _knob('MXNET_TPU_WATCHDOG_COLLECTIVE_S', float, 600.0,
          'Watchdog stall budget for host-side collectives (kvstore'
          ' dist push/pull/barrier).'),
    _knob('MXNET_TPU_WATCHDOG_POLL_S', float, 10.0,
          'Poll cadence of the background watchdog monitor thread.'),
    _knob('MXNET_TPU_WORKER_RESTARTS', int, 2,
          'DataLoader worker-crash restarts per batch before the'
          ' failure propagates.'),
    _knob('MXNET_TPU_WORKER_TIMEOUT_S', float, 300.0,
          'Per-batch wait on a DataLoader worker task before treating'
          ' the worker as dead and resubmitting (covers hard process'
          ' death); 0 disables.'),
    _knob('MXNET_MP_OPENCV_NUM_THREADS', int, 0,
          'cv2 thread cap inside DataLoader workers (0 = cv2 default).'),
    # engine bulking segment sizes: one XLA program per graph already
    _knob('MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN', int, 15,
          'Bulking segment cap.', effective=False),
    _knob('MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN_FWD', int, 15,
          'Bulking segment cap (forward).', effective=False),
    _knob('MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN_BWD', int, 15,
          'Bulking segment cap (backward).', effective=False),
    _knob('MXNET_EXEC_ENABLE_INPLACE', bool, True,
          'XLA buffer assignment owns in-place reuse; the fused paths'
          ' donate buffers explicitly.', effective=False),
    _knob('MXNET_USE_OPERATOR_TUNING', bool, True,
          'CPU elemwise OMP tuning; XLA autotunes.', effective=False),
    _knob('MXNET_ENABLE_OPERATOR_TUNING', bool, True,
          'Alias of MXNET_USE_OPERATOR_TUNING.', effective=False),
    _knob('MXNET_USE_NUM_CORES_OPERATOR_TUNING', int, 0,
          'CPU tuning core count.', effective=False),
    _knob('MXNET_KVSTORE_USETREE', bool, False,
          'PCIe-topology tree reduce; ICI mesh collectives replace it.',
          effective=False),
    _knob('MXNET_KVSTORE_LOGTREE', bool, False,
          'Tree-reduce logging.', effective=False),
    _knob('MXNET_KVSTORE_TREE_ARRAY_BOUND', int, 10000000,
          'Tree-reduce threshold.', effective=False),
    _knob('MXNET_STORAGE_FALLBACK_LOG_VERBOSE', bool, True,
          'Sparse->dense fallback logging; the dense facade never'
          ' falls back.', effective=False),
    _knob('MXNET_GPU_WORKER_NTHREADS', int, 2,
          'Per-GPU worker threads; XLA streams replace them.',
          effective=False),
    _knob('MXNET_GPU_COPY_NTHREADS', int, 1,
          'GPU copy threads.', effective=False),
    _knob('MXNET_MKLDNN_ENABLED', bool, True,
          'No MKLDNN backend on TPU.', effective=False),
    _knob('MXNET_LIBRARY_PATH', str, None,
          'Dynamic backend library path; the native predict/recio'
          ' libraries build on demand instead.', effective=False),
]}


def get(name):
    """Resolved value of a knob: set() override > environment > default."""
    knob = KNOBS[name]
    with _lock:
        if name in _values:
            return _values[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    return knob.parse(raw)


def set(name, value):  # noqa: A001 - reference-style API
    """Programmatic override (wins over the environment). Values coerce
    through the knob's declared type, so set('...', '0') on a bool knob
    means False, same as the environment path."""
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError('unknown config knob %s (see config.describe())'
                       % name)
    if isinstance(value, str):
        value = knob.parse(value)
    elif value is not None and knob.typ is bool:
        value = bool(value)
    elif value is not None:
        value = knob.typ(value)
    global _epoch
    with _lock:
        _values[name] = value
        _epoch += 1


def unset(name):
    """Drop a programmatic override so the knob resolves from the
    environment/default again (set(name, None) pins the VALUE None —
    this restores precedence instead; tests that scripted a fault via
    set('MXNET_TPU_FAULT', ...) clean up with this)."""
    if name not in KNOBS:
        raise KeyError('unknown config knob %s (see config.describe())'
                       % name)
    global _epoch
    with _lock:
        _values.pop(name, None)
        _epoch += 1


def describe():
    """Human-readable table of every knob, its value and meaning."""
    lines = []
    for name in sorted(KNOBS):
        k = KNOBS[name]
        tag = '' if k.effective else '  [no-op under XLA]'
        summary = k.doc.split('. ')[0].rstrip('.')
        lines.append('%-36s = %-24r %s%s' % (name, get(name), summary,
                                             tag))
    return '\n'.join(lines)


# -- persistent compilation cache -------------------------------------------

_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def configure_compile_cache():
    """Make sure jax's persistent compilation cache is on, and return
    the directory in effect.

    One way to place it: where ``JAX_COMPILATION_CACHE_DIR`` is set jax
    has already taken the directory from it and nothing is set in code;
    otherwise the cache lives at one fixed path inside the checkout
    (``<repo>/.jax_cache`` — the path is part of the cache key, so it
    must never move between runs). Called once at package import,
    before any program compiles, so training steps and serving buckets
    alike warm-start from disk in a later process. jax's own thresholds
    stay: only programs that took over a second to compile are written,
    which keeps the thousands of tiny programs a CPU test run builds
    out of the directory.
    """
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', _REPO_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


# -- selftest platform --------------------------------------------------------

def cpu_rig(name):
    """First statement of every ``python -m mxnet_tpu.<subsystem>``
    selftest: they are CPU rigs. Unless ``JAX_PLATFORMS`` is exported,
    pin jax — and, through the environment, every process the selftest
    spawns — to the CPU, and say which platform it is in the first
    output line instead of defaulting silently. (The package import
    has already imported jax by the time ``__main__`` runs, so the
    environment variable alone would come too late for this process.)
    """
    import jax
    note = ''
    if not os.environ.get('JAX_PLATFORMS'):
        os.environ['JAX_PLATFORMS'] = 'cpu'
        jax.config.update('jax_platforms', 'cpu')
        note = ' (CPU rig: JAX_PLATFORMS was not set)'
    print('%s selftest: JAX_PLATFORMS=%s%s'
          % (name, os.environ['JAX_PLATFORMS'], note), flush=True)


# -- debug mode (NaiveEngine analog) ----------------------------------------

_naive_override = None


def naive_engine():
    """True when ops must run synchronously un-jitted (debug mode).

    Hot path (called per eager op dispatch): lock-free — CPython dict
    reads are atomic, and os.environ is a plain dict lookup."""
    if _naive_override is not None:
        return _naive_override
    v = _values.get('MXNET_ENGINE_TYPE')
    if v is None:
        v = os.environ.get('MXNET_ENGINE_TYPE')
    return v == 'NaiveEngine'


from . import engine as _engine  # lightweight: threading only


def bulk_exec(training):
    """Jit-cache enable for the eager dispatch path (reference:
    MXNET_EXEC_BULK_EXEC_TRAIN/_INFERENCE). Lock-free like
    naive_engine(). ``engine.set_bulk_size(0)`` (or the ``bulk(0)``
    scope) disables bulking the same way the env knobs do — the engine
    module's segment size is the scoped override."""
    if _engine._cur() <= 0:
        return False
    name = 'MXNET_EXEC_BULK_EXEC_TRAIN' if training else \
        'MXNET_EXEC_BULK_EXEC_INFERENCE'
    v = _values.get(name)
    if v is not None:
        return v
    raw = os.environ.get(name)
    if raw is None:
        return True
    return KNOBS[name].parse(raw)


class NaiveEngineScope:
    """Context manager forcing debug-mode execution:

        with mx.config.NaiveEngineScope():
            ...   # every op dispatches eagerly + synchronously
    """

    def __enter__(self):
        global _naive_override
        self._prev = _naive_override
        _naive_override = True
        return self

    def __exit__(self, *exc):
        global _naive_override
        _naive_override = self._prev
