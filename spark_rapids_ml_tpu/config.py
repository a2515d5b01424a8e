#
# Global configuration — the analog of the reference's Spark-conf tier
# (`spark.rapids.ml.{uvm.enabled, sam.enabled, gpuMemRatioForData,
# cpu.fallback.enabled, verbose, float32_inputs, num_workers}`, read at
# reference core.py:776-812 and core.py:1124-1170).  Without a Spark session
# the confs live in a process-global dict, overridable from the environment
# (`SPARK_RAPIDS_ML_TPU_<KEY>`) or `set_config()`.
#
import os
import threading
from typing import Any, Dict, Optional

_lock = threading.Lock()

# Keys deliberately mirror the reference conf names (docs/site/configuration.md
# in the reference repo) minus the spark.rapids.ml prefix.
_DEFAULTS: Dict[str, Any] = {
    # Cast float64 inputs to float32 on device (reference core.py:776,
    # params.py:276-286).  TPU MXU strongly prefers f32/bf16.
    "float32_inputs": True,
    # Number of model-parallel workers (= mesh size).  None -> all visible
    # jax devices (reference params.py:556-588 infers from cluster GPUs).
    "num_workers": None,
    # Fall back to sklearn on CPU when unsupported params are set
    # (reference `spark.rapids.ml.cpu.fallback.enabled`, core.py:1283-1297).
    "cpu_fallback_enabled": False,
    # Verbose logging level 0-6 (reference core.py:413-436).
    "verbose": 0,
    # Fraction of free device memory to budget for staged training data
    # (reference `spark.rapids.ml.gpuMemRatioForData`, utils.py:403-522).
    # On TPU, XLA owns HBM; this bounds the host->device staging chunking.
    "mem_ratio_for_data": 0.8,
    # Host staging buffer size in bytes for streaming parquet reads.
    "host_batch_bytes": 512 * 1024 * 1024,
    # Stream parquet datasets host->HBM chunk-by-chunk instead of
    # materializing them in controller RAM (reference
    # `_concat_with_reserved_gpu_mem` utils.py:403-522).
    "streaming_ingest": True,
    # Per-device memory in bytes for the resident-vs-streamed routing and
    # the device-cache budget.  The device is asked first
    # (`memory_stats()["bytes_limit"]`, parallel/device_cache.py
    # `device_hbm_bytes`); this value is used only when set explicitly
    # (an override) or when the backend reports nothing (the CPU test
    # mesh).  A TPU whose limit cannot be read is an error, not 16 GiB.
    "hbm_bytes": 16 * 1024 * 1024 * 1024,
    # Force the multi-pass streaming-statistics fit path regardless of the
    # device-memory estimate (testing / beyond-HBM workloads).
    "force_streaming_stats": False,
    # When set, fits run under jax.profiler.trace writing an XProf/
    # TensorBoard device profile here (tracing.py device_profile).
    "profile_dir": None,
    # Store dense LogisticRegression features as bfloat16 on device: the
    # L-BFGS matvecs are HBM-bandwidth-bound, so halving feature bytes
    # buys up to ~2x fit throughput at ~3 decimal digits of feature
    # precision (solver state stays f32).  Opt-in.
    "bf16_features": False,
    # Pad staged row counts up to {1, 1.5} x 2^k buckets so nearby dataset
    # sizes share one XLA compilation (k-fold CV / fitMultiple folds differ
    # by a few rows and would otherwise each pay the full compile).  Costs
    # at most 50% masked padding rows; disable for exact-shape staging.
    "shape_bucketing": True,
    # Multi-host bootstrap: coordinator address for jax.distributed
    # (analog of the NCCL-uid allGather bootstrap, cuml_context.py:96-102).
    "coordinator_address": None,
    "process_id": None,
    "num_processes": None,
    # Cross-process reduction backend for the multi-host data path
    # (parallel/context.py reduce_host_arrays): "psum" folds per-process
    # accumulators with one jitted psum over the pod mesh; "wire"
    # allgathers the versioned wire-format payloads through the
    # jax.distributed coordination-service KV store and folds on host in
    # rank order (deterministic); "auto" probes once per process and
    # picks psum where the backend supports cross-process collectives,
    # wire otherwise (CPU builds).
    "multiproc_reduce": "auto",
    # Seconds each rank waits for its peers' payloads at a cross-process
    # reduction barrier before failing the pass (a dead rank must
    # surface as a timeout, not a hang).
    "multiproc_reduce_timeout_s": 120.0,
    # Verify a content fingerprint (shapes/dtypes/keys of the reduced
    # payload) agrees across ranks before merging; divergence raises
    # RankDivergenceError instead of silently mis-merging statistics
    # computed from different inputs.  Costs one extra small allgather
    # per reduction.
    "multiproc_agreement_check": True,
    # Pod-scale rank-loss recovery (resilience/pod.py): "on" shrinks the
    # quorum to the surviving ranks when a peer process dies mid-pass
    # (bumped reduction generation, dead rank's row-group shares
    # reassigned, pass restarted with fresh accumulators); "off" keeps
    # the prior behavior — every cross-process wait is still BOUNDED and
    # raises a typed ReduceTimeout, but the failure is fatal.
    "pod_elastic": "on",
    # Seconds between liveness heartbeats each rank publishes into the
    # coordination-service KV namespace while pod_elastic is on; also
    # the slice at which bounded waits re-check peer liveness.
    "pod_heartbeat_interval_s": 2.0,
    # Straggler grace: a peer is declared DEAD only after its heartbeat
    # has not advanced for this many seconds — a slow-but-beating rank
    # is waited on to the full multiproc_reduce_timeout_s instead.
    "pod_death_grace_s": 10.0,
    # Pod incident bundles (telemetry/fleet.py): total deadline for the
    # dumping rank's best-effort pull of its peers' flight-recorder
    # rings.  Shared across all peers — a slow pod spends at most this
    # long collecting evidence before writing the bundle with whatever
    # arrived; absent rings are named in pod_incident.json.
    "pod_incident_ring_deadline_s": 2.0,
    # Fleet-merged drift windows (monitor/monitor.py + fleet.py): "on"
    # publishes each closed serve-time drift window's sketch blob to
    # the pod KV seam and merges peers' latest blobs rank-ordered, so
    # drift_score reflects pod-wide traffic (per-host partials stay on
    # drift_score_partial{model,process}); "off" keeps drift purely
    # per-process.
    "drift_fleet_merge": "on",
    # Spark-DataFrame exchange: datasets estimated above this many bytes
    # are written by the EXECUTORS to `spark_exchange_dir` as parquet and
    # fit through the streaming-ingest path instead of `toPandas()`
    # through the controller (the reference never materializes the dataset
    # on the driver either — workers pull partitions, core.py:742-1013).
    "spark_collect_max_bytes": 2 * 1024 * 1024 * 1024,
    # Shared-filesystem directory for the parquet exchange (must be
    # readable from the controller and writable from the executors, e.g.
    # NFS/GCS-fuse).  Empty -> always collect via Arrow (no size probe
    # runs in that case).
    "spark_exchange_dir": "",
    # Decode the next parquet chunk on a background thread while the
    # device consumes the current one (streaming.iter_chunks_prefetch);
    # costs one extra chunk of host memory.
    "streaming_prefetch": True,
    # How far the streaming prefetch thread may run ahead of the
    # consumer (streaming.iter_chunks_prefetch): a bounded queue of
    # depth-2 owned chunks plus the one in the reader's hand.  Each
    # extra level costs one chunk of host memory; 1 disables the thread
    # (serial decode).
    "streaming_prefetch_depth": 3,
    # Chunk cache (parallel/device_cache.py ChunkCache): "on" records
    # the DECODED fixed-shape chunks of a parquet scan the first time it
    # runs and replays them for every later identical scan — epoch 1
    # pays parquet once, epochs 2..n stream from memory.  Chunks sit
    # device-resident while free headroom under the shared device-budget
    # ledger allows, host-resident under `chunk_cache_host_bytes`, and
    # spill LRU-compressed (`chunk_cache_codec`) beyond that.  "off"
    # restores re-read-every-epoch.
    "chunk_cache": "on",
    # Host-memory budget (bytes) for the chunk cache's host + spill
    # tiers; LRU chunks spill (compressed, checksummed) and then whole
    # LRU streams evict beyond it.
    "chunk_cache_host_bytes": 1024 * 1024 * 1024,
    # Spill codec for the chunk cache (parallel/chunk_codec.py):
    # "none" (raw bytes, zero CPU), "zlib" (stdlib), or "lz4"/"zstd"
    # where the optional wheels exist; custom codecs register via
    # chunk_codec.register_codec.  Every spilled blob is crc32-
    # checksummed regardless of codec.
    "chunk_cache_codec": "none",
    # When set, spilled chunk blobs are written to files under this
    # directory instead of held in host memory (the host-bytes ledger
    # then counts only resident tiers).  Filenames embed the process
    # index and the content-stamped stream key, so multiple ranks
    # replaying the same parquet path through a SHARED directory cannot
    # collide.  Empty -> in-memory spill blobs (the default).
    "chunk_cache_spill_dir": "",
    # DuHL-style importance sampling of cached chunks for the
    # epoch-streaming solvers (streaming.py logreg/kmeans): "duhl" lets
    # an epoch revisit only the chunks whose contribution to the
    # solver's own statistics is still moving (per-chunk scores,
    # stale-contribution compensation, age-forced refresh), once the
    # chunk cache holds the full stream; "off" (default) keeps exact
    # full passes — bit-identical to the pre-cache trajectories.
    "streaming_chunk_sampling": "off",
    # Fraction of cached chunks a sampled epoch revisits (the rest
    # contribute their last-computed statistics).  Clamped to [0.1, 1].
    "streaming_chunk_sample_fraction": 0.5,
    # Pipelined per-device staging engine (parallel/mesh.py): host rows
    # are sliced per DEVICE SHARD and assembled with
    # jax.make_array_from_single_device_arrays, so each byte travels to
    # exactly one device (the serial chunked path's jitted global update
    # let GSPMD replicate every chunk to all devices — n_dev x the
    # minimal traffic).  `staging_chunk_bytes` bounds one host piece
    # (also the unit of pipeline overlap); it is additionally clamped to
    # the transfer-RPC ceiling (mesh._MAX_PUT_BYTES).
    "staging_chunk_bytes": 256 * 1024 * 1024,
    # How many prepared host pieces the staging pipeline may run ahead of
    # the device transfers (pad/cast/densify on a host thread overlaps
    # the in-flight device_put).  1 = serial fallback (no thread); each
    # extra level of depth costs one staged chunk of host memory.
    "staging_pipeline_depth": 2,
    # When set, epoch-streaming fits (hours-long at beyond-HBM scale)
    # write their full optimizer state here after every iteration and
    # RESUME the identical trajectory after a preemption/crash.
    "streaming_checkpoint_dir": "",
    # Estimator-wide checkpoint directory (resilience/checkpoint.py): every
    # iterative fit — in-memory KMeans Lloyd, host-dispatched L-BFGS, the
    # FISTA elastic-net solve, AND the epoch-streaming fits — saves its
    # solver state here per iteration and resumes after a crash/preemption.
    # Supersedes `streaming_checkpoint_dir` (kept as a fallback alias for
    # streaming fits only; it never affects in-memory fits).
    "checkpoint_dir": "",
    # Watchdog deadline (seconds) for blocking device work — dispatches,
    # `block_until_ready`, host fetches (resilience/guard.py `guarded`).
    # A hang past the deadline raises a typed DispatchTimeout instead of
    # blocking the controller forever.  0 disables the watchdog (no
    # worker thread).
    "dispatch_deadline_s": 0.0,
    # Declarative retry policy for guarded fit/transform dispatch
    # (resilience/retry.py RetryPolicy.from_config): total attempts, then
    # exponential backoff base/multiplier and jitter fraction for
    # transient (RPC/DEADLINE/timeout) errors.
    "retry_max_attempts": 3,
    "retry_backoff_s": 0.5,
    "retry_backoff_mult": 2.0,
    "retry_jitter": 0.25,
    # Deterministic fault injection (resilience/faults.py):
    # "site:kind[:times[:skip]]" comma list, e.g.
    # "fit_kernel:oom:1,transform_dispatch:timeout:1:2".  Kinds: oom,
    # timeout, preemption, hang, device_lost, rank_lost, kv_timeout.
    # Empty disables.  Tests use the `fault_inject` context manager
    # instead; this conf arms sites for whole-process runs (CI smoke,
    # bench rehearsals).
    "fault_inject_spec": "",
    # Fused Pallas distance+top-k kernel for brute-force kNN (the cuVS
    # fusedL2Knn analog, ops/pallas_knn.py).  RETIRED from the default
    # path ("win or delete", ROADMAP Design 5): three on-chip rounds
    # measured it LOSING — 0.28x, 0.38x, and in PR 21's chip run 0.21x
    # XLA's matmul+top_k at 100k x 10k x k=32, there also inexact (one
    # bf16 pass: 85 % neighbour agreement with the exact-f32 XLA path) —
    # and the "auto" measured probe burned a cold compile +
    # 6 timed evaluations per shape bucket of warm-up time re-discovering
    # that verdict every process.  "off" (default) uses the XLA
    # blocked/coltiled kernels outright; "auto" re-enables the per-bucket
    # measured probe (ops/knn.py knn_topk_single, the umap_kernel=auto
    # discipline) for future backends where the tradeoff may flip; "on"
    # forces the fused kernel everywhere (CPU runs the Pallas
    # interpreter — slow, experiments/tests only).
    "pallas_knn": "off",
    # MXU matmul precision for rank/threshold-critical distance kernels
    # (kNN/ANN/DBSCAN; ops/precision.py).  "highest" = exact f32 (cuML
    # parity; TPU default bf16 passes mis-rank near-tied neighbors —
    # measured CAGRA recall 0.996 -> 0.58), "high" = 3-pass bf16,
    # "default" = fastest.  Read at trace time.
    "distance_precision": "highest",
    # Per-dispatched-program FLOP budget of the L-BFGS solvers (dense and
    # sparse logistic regression): a solve whose total fitted work
    # exceeds this switches from the fused single-program fit to one
    # host-dispatched program per evaluation.  A dense fit whose
    # evaluation is the one-pass kernel does not read it (its route reads
    # device memory alone, models/classification.py), nor does KMeans
    # Lloyd: its route and its block size read device memory
    # (ops/kmeans.py kmeans_fit_auto).  2e12 FLOPs (~40 s at v5e f32
    # matmul throughput) was sized for a development link that failed
    # transfers behind long programs; the link is gone and the value is
    # inherited, to be re-justified on the chip or deleted (ROADMAP
    # Design 2).
    "dispatch_flops_limit": 2e12,
    # MXU precision for sufficient-statistics matmuls feeding a matrix
    # inversion/eigendecomposition (PCA covariance, LinReg Gram) —
    # ops/precision.py stats_precision().  "highest" = f32-exact (cuML
    # parity); "high"/"default" trade fidelity for speed at very large d;
    # "high_compensated" = 3-pass bf16 chunk products (~2x MXU throughput
    # at large d, like "high") PLUS Kahan-compensated f32 chunk-level
    # accumulation in the streamed/fused statistics paths, bounding the
    # across-chunk error plain "high" leaves uncontrolled.
    "stats_precision": "highest",
    # Fused stage-and-solve for one-pass sufficient-statistics estimators
    # (PCA, LinearRegression — fused.py): each host chunk's Gram/moment/
    # cross contribution is accumulated ON DEVICE as the chunk lands, with
    # the producer thread prepping chunk N+1 while the mesh accumulates
    # chunk N — the stage and solve phases collapse toward
    # max(stage, solve) instead of adding.  "auto" (default) fuses
    # eligible fits
    # (dense, single-process, est. staged bytes >= fused.py's
    # _AUTO_MIN_BYTES); "on" fuses every eligible fit regardless of size;
    # "off" keeps the two-phase stage-then-solve path.  Ineligible
    # consumers (device-cache CV/grid fits that refit resident data,
    # sparse/ELL staging, multi-process, DeviceDataset inputs already on
    # device) always keep the two-phase path.
    "fused_stage_solve": "auto",
    # Parallel parquet range-readers (fused.py iter_parquet_chunks and
    # the offset-carrying staging variant streaming.stage_parquet now
    # also consumes): each reader decodes ONLY its row-group share of a
    # single parquet file.  "auto" (default) probes the host —
    # os.cpu_count() clamped by the file's row-group count and by the
    # measured single-reader decode rate when one is on record
    # (fused.resolve_parquet_readers; the decision lands in the fit
    # report's solver_decision section) — so multi-core ingest hosts
    # parallelize and the 1-core CI box keeps resolving to 1 (where the
    # warm Arrow scan measured CPU-bound: readers=2 == readers=1).
    # Explicit ints still pin the count.
    "fused_parquet_readers": "auto",
    # PCA eigensolver (ops/pca.py): "full" = exact d x d covariance +
    # eigh (cuML PCAMG parity, O(n d^2)); "randomized" = Halko
    # randomized range-finder (O(n d l), l = k + pca_oversamples) —
    # the tradeoff the reference's cuML MG path makes when k << d;
    # "auto" (default) picks randomized when d is large and k small,
    # except on resident rows whose exact Gram and (d,d) eigensolve
    # are each a fraction of a second, which get the exact answer
    # (see ops/pca.py resolve_pca_solver).
    "pca_solver": "auto",
    # Oversampling columns for the randomized range-finder (l = k +
    # pca_oversamples; Halko et al. recommend 5-10).
    "pca_oversamples": 10,
    # Power (subspace) iterations for the randomized range-finder: each
    # adds one O(n d l) pass and sharpens the spectrum (2 is enough for
    # slowly-decaying spectra; 0 is fastest).
    "pca_power_iters": 2,
    # Statistic-program engine (stats/) sketch sizing.  Per-level item
    # capacity of the mergeable KLL-style quantile sketch
    # (stats/sketches.py): rank error shrinks ~1/k, memory grows
    # O(cols * levels * k).
    "summarizer_sketch_k": 256,
    # Misra-Gries frequent-items table capacity per column: every
    # reported count carries at most n/cap slack, and any value with
    # true frequency above n/cap is guaranteed present.
    "summarizer_frequent_k": 64,
    # HyperLogLog precision bits for the `distinct_count` program:
    # 2^bits int32 registers per column (~1.04/sqrt(2^bits) relative
    # error; 12 bits = 4096 registers = ~1.6% error).
    "summarizer_hll_bits": 12,
    # Contingency-table bins per axis for the `chi2` independence test:
    # integer-coded feature and label values are clipped into
    # [0, bins).
    "summarizer_chi2_bins": 16,
    # UMAP SGD epoch kernel: "auto" picks the scatter-free structured
    # kernel on TPU backends (unsorted scatter-adds serialize on TPU; the
    # structured form replaces them with dense sums + one sorted
    # segment_sum) and the generic scatter kernel elsewhere (CPU scatters
    # are cheap and the structured form's larger intermediates lose
    # ~1.7x there); "structured"/"generic" force a kernel.
    "umap_kernel": "auto",
    # Exact-kNN item sets up to this many bytes replicate on every host
    # (simple model contract); above it, multi-process fits keep feature
    # rows process-local and only the global id vector replicates (the
    # analog of the reference's distributed block exchange, knn.py:688-779).
    "knn_replicate_max_bytes": 1024 * 1024 * 1024,
    # Device-resident dataset cache (parallel/device_cache.py): "on"
    # stages a dataset onto the mesh ONCE and serves every subsequent
    # fit/evaluate of the same data (CrossValidator folds, fitMultiple
    # grids, the best-model refit) from views of the resident sharded
    # arrays — a k-fold CV run drops from 2k+1 host->device stagings to
    # 1.  "off" restores the legacy per-fold host-slicing path.
    "device_cache": "on",
    # Byte budget for resident cache entries (LRU-evicted beyond it).
    # 0 -> derive from the device-memory model the staging decisions
    # already use: hbm_bytes * mem_ratio_for_data * n_devices.  An entry
    # that cannot fit even after evicting everything is NOT cached (the
    # fit degrades gracefully to the uncached path).
    "device_cache_bytes": 0,
    # Elastic mesh recovery (resilience/elastic.py): "on" lets a fit that
    # loses a device mid-iteration SHRINK the mesh to the survivors,
    # re-stage its data, and resume from its last checkpoint instead of
    # re-running the whole fit and praying the same device count comes
    # back (the DrJAX elastic re-planning lesson, PAPERS.md).  "off"
    # restores the PR-1 behavior: a device loss is handled like a
    # preemption — reinit_distributed + a full retry on the unchanged
    # device set.
    "elastic": "on",
    # Smallest surviving-device count an elastic recovery may shrink the
    # mesh to.  Below it the recovery falls back to the full-retry
    # (preemption) path: a fit squeezed onto too few chips would OOM or
    # crawl, which is worse than waiting for the scheduler to restore
    # capacity.
    "elastic_min_devices": 1,
    # Per-fit telemetry reports (telemetry/report.py): when set, every
    # fit writes `<dir>/fit_<Estimator>_<run_id>.json` — stage timing
    # tree, bytes staged, cache hits, retries/recoveries, solver loss
    # curve.  The same dict is reachable as `model.fit_report()`.
    "telemetry_dir": "",
    # Opt-in Prometheus scrape endpoint (telemetry/exporters.py): a
    # stdlib HTTP server on this port serves /metrics with every
    # registry metric (`spark_rapids_ml_tpu_*` families).  0 = off.
    "telemetry_port": 0,
    # Progress heartbeat for long iterative solvers (telemetry/
    # heartbeat.py): KMeans Lloyd, L-BFGS, FISTA and epoch-streaming
    # loops log iteration/loss/throughput every this many seconds.
    # <= 0 silences the log line (the solver progress gauges still
    # update every iteration).
    "heartbeat_interval_s": 30.0,
    # Device-memory telemetry source (telemetry/memory.py): "auto" reads
    # `device.memory_stats()` where the backend reports it (TPU/GPU) and
    # falls back to the deterministic simulated provider (a
    # `jax.live_arrays()` census) elsewhere — so the watermark/drift
    # path runs on the CPU test mesh too.  On a TPU backend "auto" is the
    # real provider or an error, never the census.  "real"/"simulated"
    # force a provider, "off" disables sampling entirely.
    "memory_provider": "auto",
    # Background device-memory sampling cadence while a fit is active
    # (seconds).  0 (default) = sample only at the explicit points
    # (fit open/close, after each staging, rate-limited solver
    # heartbeats); > 0 adds a daemon-thread sampler so long device-bound
    # stretches can't hide an HBM peak between explicit samples.
    "memory_sample_interval_s": 0.0,
    # Small-batch direct staging fast path (parallel/mesh.py): a 2-D
    # host array below the pipelined-engine threshold stages as plain
    # per-device slices + one device_put per shard — no full padded host
    # copy, no interleave-permutation copy, no jitted update programs.
    # Byte-identical to the serial path; the serving layer's 1-row..
    # few-row micro-batches live on it.  Off restores the legacy
    # pad/layout/global-put path everywhere.
    "staging_small_direct": True,
    # Serving micro-batch coalescer (serving/): hard cap on the rows one
    # coalesced dispatch may carry.  The effective cap is
    # min(serving_max_batch_rows, host_batch_bytes / row_bytes) — the
    # same byte model every staged transfer is sized by — and an
    # OOM-degraded server halves it further (floor: one row per device).
    "serving_max_batch_rows": 4096,
    # Longest a queued serving request may wait for co-batchable traffic
    # before its batch dispatches anyway (milliseconds).  Raising it
    # trades p50 latency for larger coalesced batches (higher QPS).
    "serving_max_wait_ms": 2.0,
    # Admission control (serving/): requests beyond this many queued
    # across all models are rejected with a typed ServingOverload
    # instead of growing the queue without bound (the caller sheds load
    # or retries with backoff).
    "serving_max_queue": 1024,
    # Opt-in serving HTTP JSON endpoint (serving/http.py): a stdlib
    # server on this port exposes POST /v1/models/<name>:transform plus
    # the per-model latency report.  Binds LOOPBACK like the
    # `telemetry_port` endpoint; 0 = off (in-process ServingClient only).
    "serving_port": 0,
    # Slow-request capture (serving/server.py): a request whose total
    # latency reaches this many milliseconds has its batch's FULL span
    # tree captured (queue -> coalesce -> stage -> compute -> scatter)
    # into a bounded in-memory buffer (`ServingServer.slow_traces()`)
    # and marked with a `serving_slow[...]` instant event.  <= 0
    # disables the capture; request ids still attach to every latency
    # observation as exemplars either way.
    "serving_slow_trace_ms": 0.0,
    # Declared p99 latency target (milliseconds) every served model is
    # held to: `slo_burn_rate{model,window}` gauges report the measured
    # over-target request fraction divided by the 1% error budget a p99
    # target implies (burn 1.0 = exactly on budget, >1 = burning).
    # <= 0 disables the burn-rate gauges.  Per-model overrides via
    # `serving_slo_targets`.
    "serving_slo_p99_ms": 0.0,
    # Per-model p99 target overrides: "model=ms,model2=ms" comma list
    # (e.g. "logreg=5,pca=20").  Models not listed fall back to
    # `serving_slo_p99_ms`.  Empty = no per-model overrides.
    "serving_slo_targets": "",
    # Closed-loop serving controller (serving/control.py): "on" ticks a
    # per-model AIMD feedback loop from the dispatcher that scales the
    # coalescing cap and max-wait against the measured `slo_burn_rate`,
    # enforces priority-class admission, and runs the brownout phase
    # machine.  "off" restores static knobs: the configured cap/wait
    # apply unscaled and every request admits against the global queue
    # bound only.
    "serving_controller": "on",
    # Seconds between controller feedback steps per model.  Shorter
    # reacts faster but amplifies sampling noise in the burn gauge
    # (which itself refreshes at ~1 Hz); longer smooths at the cost of
    # SLO budget burned while waiting.
    "serving_controller_interval_s": 1.0,
    # AIMD high water: a 1m burn rate at or above this halves the
    # model's effective coalescing cap and max-wait (smaller batches,
    # earlier dispatch — the tail-latency actuators).  1.0 = act the
    # moment the error budget burns faster than it accrues.
    "serving_controller_burn_high": 1.0,
    # AIMD low water: burn at or below this regrows the actuators
    # additively (1/8 of full scale per step) back toward the
    # configured values.  The gap between the waters is the hysteresis
    # band where the controller HOLDS — set low == high to disable it.
    "serving_controller_burn_low": 0.5,
    # Batch-class queue/dispatch share: batch-priority requests admit
    # into at most this fraction of `serving_max_queue`, and when both
    # classes have a due head the dispatcher grants batch this much
    # credit per interactive win (0.25 = one batch round per four
    # contested rounds).  0 starves batch entirely under contention;
    # values clamp to [0, 1].
    "serving_batch_share": 0.25,
    # Admission class for requests that name no priority AND whose
    # model registered no default: "interactive" (latency-sensitive,
    # full queue) or "batch" (background scoring, bounded share, shed
    # first under brownout).
    "serving_priority_default": "interactive",
    # Brownout trigger: a 1m burn rate at or above this, sustained for
    # `serving_brownout_sustain_s`, escalates the model one brownout
    # phase (normal -> shed_batch -> shed_interactive).  Set above the
    # AIMD high water — brownout is what happens when shrinking batches
    # was not enough.
    "serving_brownout_burn": 2.0,
    # Seconds the burn must hold at/above `serving_brownout_burn`
    # before each brownout escalation (re-armed per phase, so a flap
    # cannot ratchet straight to shed_interactive).
    "serving_brownout_sustain_s": 5.0,
    # Seconds the burn must hold at/below the AIMD low water before
    # each brownout de-escalation re-admits the shed class.
    "serving_brownout_recover_s": 5.0,
    # Shape-bucketed serving padding classes (serving/control.py): on,
    # coalesced micro-batches pad to the {1, 1.5} x 2^k row-bucket grid
    # (parallel/mesh.py bucket_rows) REGARDLESS of the global
    # `shape_bucketing` conf, so churning request sizes reuse one
    # compiled transform program per bucket instead of recompiling per
    # distinct row count.  Off stages exact shapes (the pre-controller
    # behavior).
    "serving_padding_buckets": True,
    # Staged dispatch pipeline depth (serving/server.py): how many
    # coalesced batches may be in flight at once across the
    # stage -> compute -> collect/scatter stages.  1 fully serializes
    # (dispatch N+1 only after N's outputs scattered — the byte-parity
    # baseline); 2 matches the legacy overlap (collect N while
    # dispatching N+1); 3+ lets batch N+2 stage while N+1 computes and
    # N scatters.  0 (default) = auto: resolved from the serving
    # idle-gap profile (telemetry/utilization.py) — depth grows while
    # host-side phases are measurably stealing device-idle seconds,
    # bounded by `serving_pipeline_max_depth`.
    "serving_pipeline_depth": 0,
    # Upper bound for the AUTO depth resolution (explicit
    # `serving_pipeline_depth` values bypass it, clamped to 8).  Deeper
    # pipelines hold more staged batches in device memory and lengthen
    # the requeue window a mid-flight failure must drain.
    "serving_pipeline_max_depth": 4,
    # Per-model round-robin interleave (serving/server.py): when several
    # models in the SAME priority class have due batches, rotate which
    # model dispatches each round instead of draining the oldest queue
    # first.  FIFO within each model's class is preserved either way;
    # off restores strict oldest-head order across models.
    "serving_pipeline_interleave": True,
    # Failure flight recorder (telemetry/flight_recorder.py): "on" keeps
    # an always-on bounded ring of recent trace events, rate-limited
    # metric deltas and heartbeats (O(1) memory), and the typed failure
    # paths — retry exhaustion, DispatchTimeout, device-loss elastic
    # recovery, sustained ServingOverload — dump a post-mortem bundle
    # (Chrome trace of the last `flight_recorder_window_s` seconds,
    # Prometheus snapshot, effective config, solver state) so every
    # failure leaves a black box behind.  "off" disables recording.
    "flight_recorder": "on",
    # Ring capacity of the flight recorder: how many recent trace
    # events it retains (a deque — O(1) appends, memory bounded by this
    # count regardless of process lifetime).
    "flight_recorder_events": 4096,
    # How many seconds of recent history a post-mortem bundle's Chrome
    # trace covers (events older than this at dump time are dropped
    # from the bundle; the ring itself is bounded by count, not time).
    "flight_recorder_window_s": 60.0,
    # Where post-mortem bundles are written.  Empty -> `telemetry_dir`;
    # when both are empty the recorder still records (the in-memory
    # ring stays queryable) but failure dumps are skipped with a log
    # line.
    "flight_recorder_dir": "",
    # Fit-time drift-baseline capture (monitor/baseline.py): "auto"
    # (default) captures a baseline fingerprint (per-column moments,
    # KLL quantile sketch, Misra-Gries frequent items, HLL distinct
    # counts) on the chunked fit paths — fused stage-and-solve and the
    # multi-pass streamed-statistics fits — where the host chunks
    # already flow (zero extra data passes); "on" additionally captures
    # in-memory staged fits via one host pass over the extracted batch;
    # "off" disables capture.  The fingerprint lands on the model
    # (`model._drift_baseline`), persists as `drift_baseline.bin` next
    # to the model arrays, and registers with the serving pin.
    "drift_baseline": "auto",
    # Serving-side drift window length (seconds): the monitor's
    # sliding-window sketches tumble at this cadence, and scoring sees
    # the last closed window merged with the current partial one —
    # bounded memory (two sketch sets per model) regardless of traffic.
    "drift_window_s": 60.0,
    # Rows a serving window must hold before divergences are scored
    # (below it the sketches are noise, not a distribution).
    "drift_min_window_rows": 64,
    # How many highest-scoring columns export `drift_score{model,
    # column,stat}` gauges per model (the rest stay in the divergence
    # table, off the metric surface — the family's cardinality bound).
    "drift_top_k": 8,
    # Alert threshold on the per-model overall drift score (the max of
    # PSI / KS / frequent-churn / null-rate / cardinality deltas across
    # columns; 0.25 is the classic "actionable PSI" level).  Breaching
    # it for `drift_alert_sustain_s` fires a flight-recorder
    # post-mortem (`postmortems_total{reason="drift"}`) carrying both
    # fingerprints and the divergence table.  <= 0 disables alerting
    # (the gauges still export).
    "drift_alert_threshold": 0.25,
    # How long (seconds) the overall drift score must stay above
    # `drift_alert_threshold` before the alert fires — a single noisy
    # window must not dump a post-mortem.
    "drift_alert_sustain_s": 30.0,
    # Named-lock contention profiling (telemetry/locks.py): a blocked
    # acquire that waited at least this many milliseconds drops a
    # `lock_slow_wait[<name>]` instant marker into the active run's
    # span tree (the cumulative wait/hold counters record regardless).
    # <= 0 disables the markers.
    "lock_slow_wait_ms": 50.0,
    # Automatic hang doctor (telemetry/hang_doctor.py): "on" (default)
    # runs the always-on stall watchdog — a daemon thread watching
    # trace-event flow, heartbeat gauge advance and serving collect
    # counts; a thread stuck on a named lock (or in-flight work making
    # no progress) for `hang_doctor_stall_s` dumps a reason="stall"
    # flight-recorder bundle with all-thread stacks and the lock
    # wait-for graph.  "off" disables the watchdog.
    "hang_doctor": "on",
    # Seconds of no forward progress (or of one thread stuck waiting on
    # one named lock) before the hang doctor declares a stall.  Long XLA
    # compiles emit no progress signals while they run, so keep this
    # comfortably above the slowest expected compile.  Checked on the
    # chip (chip_smoke.py, PR 21): the whole cold refconfig logreg path is
    # 30 compiles in 5 s on a v5e host and the doctor did not fire, so
    # 120 s stays.
    "hang_doctor_stall_s": 120.0,
}

_ENV_PREFIX = "SPARK_RAPIDS_ML_TPU_"

_config: Dict[str, Any] = {}


# Explicit types for keys whose default is None (type can't be inferred).
_TYPES: Dict[str, type] = {
    "num_workers": int,
    "process_id": int,
    "num_processes": int,
    "coordinator_address": str,
    "profile_dir": str,
}


def _coerce(key: str, raw: str) -> Any:
    ty = _TYPES.get(key)
    if ty is None:
        ty = type(_DEFAULTS[key])
    if ty is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    return raw


def _effective_locked(key: str, default: Optional[Any] = None) -> Any:
    """Effective (env-aware) value; caller must hold _lock (non-reentrant)."""
    if key in _config:
        return _config[key]
    env = os.environ.get(_ENV_PREFIX + key.upper())
    if env is not None and key in _DEFAULTS:
        return _coerce(key, env)
    return _DEFAULTS.get(key, default)


def get_config(key: str, default: Optional[Any] = None) -> Any:
    if key not in _DEFAULTS and default is None:
        raise KeyError(f"Unknown config key: {key}")
    with _lock:
        return _effective_locked(key, default)


def is_explicit(key: str) -> bool:
    """Whether `key` was set by the caller (`set_config` or the
    environment) rather than left at its default — for keys whose
    default stands in for something the code can measure."""
    if key not in _DEFAULTS:
        raise KeyError(f"Unknown config key: {key}")
    with _lock:
        return key in _config or (_ENV_PREFIX + key.upper()) in os.environ


def _invalidate_traced(old: Any, new: Any) -> None:
    """`distance_precision` is baked into kernels at trace time; a change
    must drop compiled programs or same-shape calls silently keep the old
    precision.  jax.clear_caches() is coarse but correct, and precision
    flips are rare (benchmarking / explicit opt-out)."""
    import sys

    if old == new or "jax" not in sys.modules:
        # jax never imported -> nothing compiled to drop (and configuring
        # the library must not pay the multi-second jax import)
        return
    import jax

    jax.clear_caches()
    from .telemetry.compile import note_recompile

    # every same-shape call after this re-lowers: make the storm visible
    note_recompile("traced_kernels", "precision_change")


def _traced_keys_locked() -> tuple:
    """Effective values of every conf baked into kernels at TRACE time
    (precision levels); caller must hold _lock.  A change to any of them
    must drop compiled programs."""
    return (
        _effective_locked("distance_precision"),
        _effective_locked("stats_precision"),
    )


def set_config(**kwargs: Any) -> None:
    # read-check-update under ONE lock acquisition so two concurrent
    # precision changes cannot both observe old==new and skip cache
    # invalidation; the invalidation itself runs after release (it may
    # import jax, which must not happen under the config lock)
    with _lock:
        prev = _traced_keys_locked()
        for k, v in kwargs.items():
            if k not in _DEFAULTS:
                raise KeyError(f"Unknown config key: {k}")
        _config.update(kwargs)
        new = _traced_keys_locked()
    _invalidate_traced(prev, new)


def config_snapshot() -> Dict[str, Any]:
    """Effective (env-aware) value of EVERY known conf key — the
    operator-facing "what was this process actually configured as" dump
    the flight recorder writes into post-mortem bundles.  Values are the
    plain Python scalars `_DEFAULTS` holds, so the dict JSON-serializes."""
    with _lock:
        return {k: _effective_locked(k) for k in sorted(_DEFAULTS)}


def reset_config() -> None:
    with _lock:
        prev = _traced_keys_locked()
        _config.clear()
        new = _traced_keys_locked()
    _invalidate_traced(prev, new)
