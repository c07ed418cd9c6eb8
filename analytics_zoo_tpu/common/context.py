"""Cluster runtime / context layer (L1').

TPU-native replacement for the reference's `init_orca_context` /
`init_nncontext` / RayOnSpark stack
(/root/reference/pyzoo/zoo/orca/common.py:161, pyzoo/zoo/common/nncontext.py:335,
pyzoo/zoo/ray/raycontext.py:325).

Where the reference bootstraps a SparkContext (and optionally a Ray cluster
inside the Spark cluster) to get N worker processes, a TPU program is SPMD:
one Python process per host, all hosts running the same program, with the
devices of the whole pod visible as one `jax.sharding.Mesh`.  So
`init_orca_context` here:

  * `cluster_mode="local"`  — single-process JAX (1 real chip, or N CPU
    devices under `--xla_force_host_platform_device_count=N`),
  * `cluster_mode="tpu_pod"` — calls `jax.distributed.initialize()` so every
    host sees the global device set (the control-plane analog of RayOnSpark's
    barrier-job gang bootstrap, raycontext.py:560-589),

then builds the global device mesh that every training engine in the framework
shards over.  There is no Py4J bridge and no per-backend cluster (SURVEY.md
§2.3): DP-1..DP-8 collapse into shardings on this one mesh.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Dict, Optional, Sequence

logger = logging.getLogger("analytics_zoo_tpu")

#: Canonical mesh axis order.  Data-like axes come first so that
#: batch sharding over ("dp", "fsdp") composes with parameter sharding
#: over ("fsdp", "tp") the way the scaling playbook prescribes.
MESH_AXES = ("dp", "fsdp", "pp", "ep", "sp", "tp")
#: Axes a batch dimension is sharded over by default.
DATA_AXES = ("dp", "fsdp")


class OrcaContextMeta(type):
    """Class-level config properties, mirroring the reference's
    `OrcaContextMeta` (pyzoo/zoo/orca/common.py:21-134): global knobs that
    user code reads/writes as `OrcaContext.<knob>`."""

    _pandas_read_backend = "pandas"
    _serialize_data_creator = False
    _shard_size = None
    _log_output = False
    _train_data_store = "DRAM"
    _device_cache_bytes = 256 * 1024 * 1024
    _epoch_scan_unroll = "auto"
    _failure_retry_times = 5
    _failure_retry_interval_s = 1.0
    _observability_dir = None
    _kernel_tuning_mode = "off"
    _kernel_tuning_cache_dir = None
    _goodput_sample_every = 16
    _watchdog_deadline_s = None
    _nonfinite_watchdog = False
    _slo_targets = None
    _request_log_size = 256
    _blame_tolerance = 0.05
    _exemplar_count = 16
    _exemplar_max_bytes = 64 * 1024
    _memory_sample_interval_s = 1.0
    _fault_plan = None
    _background_checkpointing = False
    _slo_shed_attainment = None
    _host_input_prefetch = 2
    _telemetry_spool_interval_s = 1.0
    _telemetry_spool_max_bytes = 1024 * 1024
    _tenant_quotas = None
    _metrics_history_interval_s = None
    _metrics_history_max_bytes = 8 * 1024 * 1024
    _hardware_peak_flops = None

    # --- TPU runtime state ---
    _mesh = None
    _cluster_mode = None
    _initialized = False
    _auto_initialized = False
    _lock = threading.Lock()

    @property
    def pandas_read_backend(cls):
        """Backend for `orca.data.pandas.read_csv` ("pandas" only; the
        reference also offered "spark", pyzoo/zoo/orca/common.py:36)."""
        return cls._pandas_read_backend

    @pandas_read_backend.setter
    def pandas_read_backend(cls, value):
        value = str(value).lower()
        if value not in ("pandas",):
            raise ValueError(f"unsupported pandas_read_backend: {value}")
        cls._pandas_read_backend = value

    @property
    def serialize_data_creator(cls):
        """Whether to wrap data-creator calls in an inter-process file lock
        (reference: orca/common.py:72-84, used to serialize downloads)."""
        return cls._serialize_data_creator

    @serialize_data_creator.setter
    def serialize_data_creator(cls, value):
        cls._serialize_data_creator = bool(value)

    @property
    def shard_size(cls):
        """Target rows per XShards shard (reference orca/common.py:100)."""
        return cls._shard_size

    @shard_size.setter
    def shard_size(cls, value):
        if value is not None and int(value) <= 0:
            raise ValueError("shard_size must be positive or None")
        cls._shard_size = None if value is None else int(value)

    @property
    def log_output(cls):
        return cls._log_output

    @log_output.setter
    def log_output(cls, value):
        cls._log_output = bool(value)
        logger.setLevel(logging.DEBUG if cls._log_output else logging.INFO)

    @property
    def train_data_store(cls):
        """"DRAM", "DISK_n" or "DEVICE" — where training data lives between
        epochs (reference FeatureSet tiers,
        zoo/src/main/scala/.../feature/FeatureSet.scala:233,557).  "DEVICE"
        is the TPU-native tier the reference couldn't have: the dataset is
        uploaded to HBM once (sharded over the mesh's data axes) and every
        epoch reads it in place — zero host→device traffic in the steady
        state.  Capped by `device_cache_bytes`; mutating the source numpy
        arrays after fit() starts will NOT be seen by cached epochs."""
        return cls._train_data_store

    @train_data_store.setter
    def train_data_store(cls, value):
        value = str(value).upper()
        if value not in ("DRAM", "DEVICE") and not value.startswith("DISK"):
            raise ValueError(
                "train_data_store must be 'DRAM', 'DEVICE' or 'DISK_n'")
        cls._train_data_store = value

    @property
    def device_cache_bytes(cls):
        """Max TOTAL bytes the DEVICE store pins in HBM across cached
        datasets (an estimator evicts older entries before exceeding
        it); a single dataset over the cap falls back to host streaming
        with a warning."""
        return cls._device_cache_bytes

    @device_cache_bytes.setter
    def device_cache_bytes(cls, value):
        cls._device_cache_bytes = int(value)

    @property
    def epoch_scan_unroll(cls):
        """Unroll factor for the DEVICE-store epoch `lax.scan`.  XLA's
        scan double-buffers the loop carry, copying the whole
        params+optimizer tree every iteration — ~2ms/step measured on an
        NCF-sized model, 30% of its step time.  Unrolling amortizes that
        copy over `unroll` steps at the cost of an `unroll`x bigger
        program to compile.  "auto" (default) unrolls 8x for models up
        to ~50M params and leaves 1x for bigger ones (a BERT-base epoch
        program already takes minutes to compile; 8x would be hours)."""
        return cls._epoch_scan_unroll

    @epoch_scan_unroll.setter
    def epoch_scan_unroll(cls, value):
        if value != "auto":
            value = int(value)
            if value < 1:
                raise ValueError("epoch_scan_unroll must be >= 1 or 'auto'")
        cls._epoch_scan_unroll = value

    @property
    def failure_retry_times(cls):
        """How many times Estimator.fit restores the latest checkpoint and
        resumes after a training failure (reference: `bigdl.failure.
        retryTimes` sysprop driving the retry loop in
        Topology.scala:1255-1310)."""
        return cls._failure_retry_times

    @failure_retry_times.setter
    def failure_retry_times(cls, value):
        if int(value) < 0:
            raise ValueError("failure_retry_times must be >= 0")
        cls._failure_retry_times = int(value)

    @property
    def failure_retry_interval_s(cls):
        """Seconds to wait between failure retries (reference:
        `bigdl.failure.retryTimeInterval`)."""
        return cls._failure_retry_interval_s

    @failure_retry_interval_s.setter
    def failure_retry_interval_s(cls, value):
        if float(value) < 0:
            raise ValueError("failure_retry_interval_s must be >= 0")
        cls._failure_retry_interval_s = float(value)

    @property
    def observability_dir(cls):
        """Directory for the structured-event JSONL sink
        (`observability.log_event` and completed spans append to
        `<dir>/events.jsonl`).  None (default) disables the sink;
        in-memory metrics/spans and the serving /metrics and /spans
        endpoints work regardless."""
        return cls._observability_dir

    @observability_dir.setter
    def observability_dir(cls, value):
        cls._observability_dir = None if value is None else str(value)

    @property
    def telemetry_spool_interval_s(cls):
        """Minimum seconds between telemetry spool snapshots
        (observability/telemetry_spool.py).  Each participating process
        (replica loops, stream consumers, elastic members) rewrites
        `<observability_dir>/telemetry/<proc>/snapshot.json` at most this
        often so its last metrics/spans survive a SIGKILL.  Spooling is
        armed only when `observability_dir` is set."""
        return cls._telemetry_spool_interval_s

    @telemetry_spool_interval_s.setter
    def telemetry_spool_interval_s(cls, value):
        if float(value) < 0:
            raise ValueError("telemetry_spool_interval_s must be >= 0")
        cls._telemetry_spool_interval_s = float(value)

    @property
    def telemetry_spool_max_bytes(cls):
        """Byte cap per spooled snapshot file.  The span and request-log
        tails are halved until the encoded snapshot fits; the metric
        exposition text is always kept whole.  Retention is one file per
        process (tmp -> fsync -> rename replaces in place), so this also
        bounds the per-process on-disk footprint."""
        return cls._telemetry_spool_max_bytes

    @telemetry_spool_max_bytes.setter
    def telemetry_spool_max_bytes(cls, value):
        if int(value) < 4096:
            raise ValueError("telemetry_spool_max_bytes must be >= 4096")
        cls._telemetry_spool_max_bytes = int(value)

    @property
    def metrics_history_interval_s(cls):
        """Sampling cadence of the metrics history recorder
        (observability/history.py) in seconds; None (default) leaves
        the recorder disarmed.  When set, `maybe_record()` hooks in the
        generation engine loop, the durable-stream consumer and the
        elastic supervisor sample every registered registry into a
        bounded in-memory ring and — when `observability_dir` is set —
        an append-only CRC32C-framed sample log under
        `observability_dir/history/<proc>/` (crash-durable: recovery
        truncates at the first torn frame).  Each sample also steps the
        built-in AlertEngine (docs/observability.md, 'Metrics history
        + alerting').  A forced sample is always available on demand
        (`GET /metrics/history` takes one), so None only disables the
        cadence, not the plane."""
        return cls._metrics_history_interval_s

    @metrics_history_interval_s.setter
    def metrics_history_interval_s(cls, value):
        if value is not None and float(value) <= 0:
            raise ValueError(
                "metrics_history_interval_s must be > 0 or None")
        cls._metrics_history_interval_s = (None if value is None
                                           else float(value))

    @property
    def metrics_history_max_bytes(cls):
        """On-disk budget for one process's metrics-history sample log
        (default 8 MiB).  The recorder rotates segments and drops the
        oldest whole segments once the per-process directory exceeds
        this — retention is bounded, never the append path (appends are
        tmp-less and flushed per sample so a SIGKILL'd replica's
        history survives)."""
        return cls._metrics_history_max_bytes

    @metrics_history_max_bytes.setter
    def metrics_history_max_bytes(cls, value):
        if int(value) < 4096:
            raise ValueError("metrics_history_max_bytes must be >= 4096")
        cls._metrics_history_max_bytes = int(value)

    @property
    def hardware_peak_flops(cls):
        """Hardware peak FLOP/s the profiling plane's MFU gauges
        divide by (observability/profiling.py).  None (default) falls
        back to `profiling.DEFAULT_PEAK_FLOPS` (1 TFLOP/s) — a
        placeholder roofline so CPU-CI MFU numbers stay comparable
        across rounds; set the accelerator's real dense peak (e.g.
        ~275e12 for a v4 TPU chip in bf16) for meaningful ratios."""
        return cls._hardware_peak_flops

    @hardware_peak_flops.setter
    def hardware_peak_flops(cls, value):
        if value is not None and float(value) <= 0:
            raise ValueError("hardware_peak_flops must be > 0 or None")
        cls._hardware_peak_flops = (None if value is None
                                    else float(value))

    @property
    def tenant_quotas(cls):
        """Per-tenant admission quotas for the unified AdmissionCore
        (serving/control_plane/admission.py; docs/control-plane.md).
        A dict mapping tenant name -> sustained requests/sec (float)
        or ``{"rate": r, "burst": b}`` (token bucket: ``rate`` refill
        per second, ``burst`` bucket depth, default ``max(rate, 1)``).
        An over-quota request is shed with 429 `TenantQuotaExceeded`
        carrying a Retry-After hint; tenants absent from the dict are
        unlimited.  None (default) disables quota enforcement.  Read
        at admission time — live updates apply to the next request."""
        return cls._tenant_quotas

    @tenant_quotas.setter
    def tenant_quotas(cls, value):
        if value is None:
            cls._tenant_quotas = None
            return
        quotas = {}
        for tenant, q in dict(value).items():
            if not str(tenant):
                raise ValueError("tenant_quotas key must be non-empty")
            if isinstance(q, dict):
                rate = float(q.get("rate", 0.0))
                burst = float(q.get("burst", max(rate, 1.0)))
            else:
                rate = float(q)
                burst = max(rate, 1.0)
            if rate <= 0 or burst <= 0:
                raise ValueError(
                    f"tenant_quotas[{tenant!r}]: rate and burst must "
                    "be > 0")
            quotas[str(tenant)] = {"rate": rate, "burst": burst}
        cls._tenant_quotas = quotas

    @property
    def goodput_sample_every(cls):
        """Fence cadence of the goodput `StepClock`s
        (observability/goodput.py): every Nth step is closed with a
        `block_until_ready` fence so its wall time decomposes exactly
        into compile / host-input / device-compute / blocked-collective
        / overhead buckets.  Default 16 (≈6% of steps pay one fence);
        1 fences every step (full accounting)."""
        return cls._goodput_sample_every

    @goodput_sample_every.setter
    def goodput_sample_every(cls, value):
        if int(value) < 1:
            raise ValueError("goodput_sample_every must be >= 1")
        cls._goodput_sample_every = int(value)

    @property
    def watchdog_deadline_s(cls):
        """Stall-watchdog deadline in seconds (None = off, the
        default).  When set, `Estimator.fit` and the generation engine
        arm a `Watchdog` (observability/watchdog.py): no step/decode
        progress for this long → `watchdog_stall_total` increments and
        a flight-recorder bundle (all-thread stacks, ring, metrics) is
        written to `observability_dir`.  Size it above the slowest
        expected dispatch — for the one-dispatch epoch-scan path the
        heartbeat is per EPOCH, so the deadline must exceed an epoch's
        wall time (plus the first epoch's XLA compile)."""
        return cls._watchdog_deadline_s

    @watchdog_deadline_s.setter
    def watchdog_deadline_s(cls, value):
        if value is not None and float(value) <= 0:
            raise ValueError("watchdog_deadline_s must be > 0 or None")
        cls._watchdog_deadline_s = (None if value is None
                                    else float(value))

    @property
    def nonfinite_watchdog(cls):
        """Opt-in nonfinite sentinel (default False).  The SPMD train
        step always folds a cheap isfinite all-reduce over loss+grads
        into the jitted program (its `_nan_steps` stat — detection is
        free, it fuses into the backward pass); with the sentinel ON
        the host CHECKS that stat per step and, on the first
        non-finite step, runs the per-tensor localization pass
        (`observability.localize_nonfinite`) naming the first
        offending leaf and writes a flight-recorder bundle.  The
        per-step check syncs the host with the device (that is its
        cost); OFF leaves the dispatch pattern and the zero-recompile
        guarantees byte-identical."""
        return cls._nonfinite_watchdog

    @nonfinite_watchdog.setter
    def nonfinite_watchdog(cls, value):
        cls._nonfinite_watchdog = bool(value)

    @property
    def slo_targets(cls):
        """Per-request latency SLO targets (observability/slo.py) as a
        dict over {"ttft_s", "tpot_s", "queue_wait_s", "e2e_s"} —
        seconds each; any subset may be set.  Every finished generation
        request is judged against the configured dimensions:
        violations count in ``slo_violation_total`` (and the per-
        dimension ``slo_violation_<dim>_total`` family), and the
        rolling-window attainment rides the ``slo_attainment_ratio``
        gauge and GET /slo.  Keyed overlays refine the base targets per
        model or tenant (docs/control-plane.md): a ``"model:<name>"`` /
        ``"tenant:<name>"`` key maps to its own sub-dict over the same
        dimensions, merged over the base when that request's model/
        tenant matches (tenant overlay wins over model).  None
        (default) disables SLO judging — request latency histograms
        are recorded regardless."""
        return cls._slo_targets

    @slo_targets.setter
    def slo_targets(cls, value):
        if value is None:
            cls._slo_targets = None
            return
        from analytics_zoo_tpu.observability.slo import SLO_DIMENSIONS

        def _dims(d, who):
            out = {}
            for k, v in dict(d).items():
                if k not in SLO_DIMENSIONS:
                    raise ValueError(
                        f"unknown SLO dimension {k!r}{who}; valid: "
                        f"{SLO_DIMENSIONS}")
                if float(v) <= 0:
                    raise ValueError(f"SLO target {k} must be > 0")
                out[k] = float(v)
            return out

        targets = {}
        for k, v in dict(value).items():
            if isinstance(k, str) and (k.startswith("model:")
                                       or k.startswith("tenant:")):
                if not k.split(":", 1)[1]:
                    raise ValueError(
                        f"keyed SLO target {k!r} names no model/tenant")
                targets[k] = _dims(v, f" under {k!r}")
            else:
                targets.update(_dims({k: v}, ""))
        cls._slo_targets = targets

    @property
    def request_log_size(cls):
        """Capacity of the per-request lifecycle log's finished-request
        ring (observability/request_log.py).  Read when the process
        log is first created (`reset_request_log()` re-reads it);
        active requests are tracked regardless of the ring size."""
        return cls._request_log_size

    @request_log_size.setter
    def request_log_size(cls, value):
        if int(value) < 1:
            raise ValueError("request_log_size must be >= 1")
        cls._request_log_size = int(value)

    @property
    def blame_tolerance(cls):
        """Relative slack of the phase-ledger additivity invariant
        (observability/blame.py): a finished request's ledger must sum
        to its e2e within this fraction (an absolute 0.1 ms floor
        covers sub-millisecond e2e).  Violations flip the ledger's
        `additive_ok` flag and tick
        `blame_additivity_violations_total` (default 5%)."""
        return cls._blame_tolerance

    @blame_tolerance.setter
    def blame_tolerance(cls, value):
        if not (0.0 < float(value) <= 1.0):
            raise ValueError("blame_tolerance must be in (0, 1]")
        cls._blame_tolerance = float(value)

    @property
    def exemplar_count(cls):
        """Max tail exemplars held by the per-process store
        (observability/exemplars.py).  SLO violators displace
        non-violators; otherwise classic top-k-slowest.  0 disables
        capture entirely."""
        return cls._exemplar_count

    @exemplar_count.setter
    def exemplar_count(cls, value):
        if int(value) < 0:
            raise ValueError("exemplar_count must be >= 0")
        cls._exemplar_count = int(value)

    @property
    def exemplar_max_bytes(cls):
        """JSON byte bound per captured exemplar: span/dispatch/
        scheduler/event tails are halved (newest kept) until the
        document fits — degrade, don't die, same idiom as the
        telemetry spool."""
        return cls._exemplar_max_bytes

    @exemplar_max_bytes.setter
    def exemplar_max_bytes(cls, value):
        if int(value) < 2048:
            raise ValueError("exemplar_max_bytes must be >= 2048")
        cls._exemplar_max_bytes = int(value)

    @property
    def memory_sample_interval_s(cls):
        """Minimum seconds between memory-telemetry samples
        (observability/memory.py: host RSS, jax live-buffer bytes,
        registered pool providers).  Samples are taken opportunistically
        from fenced goodput steps and forced by GET /timeline; the
        interval bounds the cost of the `jax.live_arrays()` walk.
        None disables opportunistic sampling (forced samples still
        work)."""
        return cls._memory_sample_interval_s

    @memory_sample_interval_s.setter
    def memory_sample_interval_s(cls, value):
        if value is not None and float(value) < 0:
            raise ValueError(
                "memory_sample_interval_s must be >= 0 or None")
        cls._memory_sample_interval_s = (None if value is None
                                         else float(value))

    @property
    def fault_plan(cls):
        """Armed fault-injection plan (resilience/faults.py;
        docs/fault-tolerance.md).  None (default) leaves every
        injection site a no-op.  Accepts a `FaultPlan` or its dict
        form, ``{"seed": 0, "faults": [{"site": ..., "action": ...,
        "at": N, "times": 1}, ...]}``; firing is deterministic in the
        plan (hit indices / seeded probabilities), never wall time.
        Arming a plan changes NO jitted program — the zero-recompile
        contracts hold with faults armed."""
        return cls._fault_plan

    @fault_plan.setter
    def fault_plan(cls, value):
        if value is None:
            cls._fault_plan = None
            return
        from analytics_zoo_tpu.resilience.faults import FaultPlan
        cls._fault_plan = FaultPlan.from_config(value)

    @property
    def background_checkpointing(cls):
        """True routes Estimator trigger saves through the
        `BackgroundCheckpointer` (resilience/checkpointing.py): the
        critical path pays one device->host snapshot, the atomic
        write->rename->commit-marker protocol runs on a writer thread,
        and the save cost shows up in the goodput ``checkpoint``
        bucket leaving the step wall.  False (default) keeps saves
        synchronous (still committed via the same atomic protocol)."""
        return cls._background_checkpointing

    @background_checkpointing.setter
    def background_checkpointing(cls, value):
        cls._background_checkpointing = bool(value)

    @property
    def slo_shed_attainment(cls):
        """SLO-aware overload shedding threshold for the generation
        engine (None = off, the default).  When set (0 < x <= 1) and
        `slo_targets` are configured, `GenerationEngine.submit` sheds
        new requests (QueueFull -> HTTP 503 with Retry-After) while
        the rolling SLO attainment is below the threshold and the
        waiting queue is at least `slo_shed_min_queue` deep — load is
        turned away by the latency objective it would violate, not by
        a blind `max_queue` constant."""
        return cls._slo_shed_attainment

    @slo_shed_attainment.setter
    def slo_shed_attainment(cls, value):
        if value is not None:
            value = float(value)
            if not 0.0 < value <= 1.0:
                raise ValueError(
                    "slo_shed_attainment must be in (0, 1] or None")
        cls._slo_shed_attainment = value

    @property
    def host_input_prefetch(cls):
        """Host-input double-buffering depth for the SPMD host-
        streaming train/eval loops (orca/learn/spmd.py).  With depth
        N >= 1 the engine keeps N batches staged ahead and assembles +
        `device_put`s the NEXT batch while the CURRENT step runs on
        the device, so the goodput ``host_input`` bucket shrinks
        toward zero.  0 disables
        prefetching: each batch is assembled synchronously before its
        step (the comparison baseline).  Default 2."""
        return cls._host_input_prefetch

    @host_input_prefetch.setter
    def host_input_prefetch(cls, value):
        if int(value) < 0:
            raise ValueError("host_input_prefetch must be >= 0")
        cls._host_input_prefetch = int(value)

    @property
    def kernel_tuning_mode(cls):
        """Pallas kernel autotuning policy (ops/tuning, docs/kernels.md):
        "off" (default) — tuned configs come from the persisted cache /
        checked-in default tables only, a cache miss falls back to the
        builtin defaults and NEVER benchmarks (CI-safe); "auto" — a
        cache miss outside a jax trace on real hardware runs the
        block-size search once and persists the winner."""
        return cls._kernel_tuning_mode

    @kernel_tuning_mode.setter
    def kernel_tuning_mode(cls, value):
        value = str(value).lower()
        if value not in ("off", "auto"):
            raise ValueError(
                f"kernel_tuning_mode must be 'off' or 'auto', got {value!r}")
        cls._kernel_tuning_mode = value

    @property
    def kernel_tuning_cache_dir(cls):
        """Directory holding `kernel_tuning.json`, the persisted
        per-(kernel, shape-bucket, dtype, platform) block-config cache
        search winners are written to (and read back from, ahead of the
        checked-in default tables).  None (default) disables
        persistence; tuning results then live only in process memory."""
        return cls._kernel_tuning_cache_dir

    @kernel_tuning_cache_dir.setter
    def kernel_tuning_cache_dir(cls, value):
        cls._kernel_tuning_cache_dir = None if value is None else str(value)

    @property
    def mesh(cls):
        """The global `jax.sharding.Mesh` everything shards over.  Reading
        it before `init_orca_context` auto-initializes local mode; a later
        *explicit* `init_orca_context` call overrides an auto-init."""
        if cls._mesh is None:
            init_orca_context(cluster_mode="local")
            cls._auto_initialized = True
        return cls._mesh

    @property
    def cluster_mode(cls):
        return cls._cluster_mode

    @property
    def initialized(cls):
        return cls._initialized

    @property
    def num_devices(cls):
        return cls.mesh.devices.size

    @property
    def devices(cls):
        return list(cls.mesh.devices.flat)


class OrcaContext(metaclass=OrcaContextMeta):
    pass


def _build_mesh(devices, mesh_shape: Optional[Dict[str, int]]):
    """Build the global mesh.  `mesh_shape` maps axis name → size, e.g.
    ``{"dp": 2, "tp": 4}``; unspecified devices fold into "dp".  Default is
    all devices on "dp" (pure data parallelism, the only strategy the
    reference implements — SURVEY.md §2.3)."""
    import numpy as np
    import jax

    n = len(devices)
    if not mesh_shape:
        mesh_shape = {"dp": n}
    unknown = set(mesh_shape) - set(MESH_AXES)
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; valid: {MESH_AXES}")
    sizes = dict(mesh_shape)
    prod = 1
    for v in sizes.values():
        prod *= v
    if prod != n:
        if n % prod != 0:
            raise ValueError(
                f"mesh_shape {mesh_shape} (={prod}) does not divide "
                f"device count {n}")
        if "dp" in sizes:
            # the user pinned dp explicitly — never silently resize it
            raise ValueError(
                f"mesh_shape {mesh_shape} covers {prod} of {n} devices; "
                "either make the axis sizes multiply to the device count "
                "or omit 'dp' to let it absorb the remainder")
        sizes["dp"] = n // prod
    axis_names = [a for a in MESH_AXES if a in sizes]
    shape = [sizes[a] for a in axis_names]
    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, axis_names)


def init_orca_context(cluster_mode: str = "local",
                      cores: Optional[int] = None,
                      num_nodes: int = 1,
                      mesh_shape: Optional[Dict[str, int]] = None,
                      coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None,
                      **kwargs):
    """One-call runtime bootstrap (reference: pyzoo/zoo/orca/common.py:161).

    cluster_mode:
      * "local" — this process's devices only (the real TPU chip(s) attached,
        or host-platform CPU devices in tests).
      * "tpu_pod" / "distributed" — multi-host: runs
        `jax.distributed.initialize(coordinator_address, num_processes,
        process_id)` (args optional on Cloud TPU, where they are inferred
        from the metadata server) so `jax.devices()` is the whole pod.

    mesh_shape: axis name → size over `MESH_AXES`; default all-"dp".
    cores: optional cap on host CPU threading for data loading.
    Returns the global `jax.sharding.Mesh`.
    """
    import jax

    cluster_mode = cluster_mode.lower()
    with OrcaContextMeta._lock:
        if OrcaContextMeta._initialized:
            if OrcaContextMeta._auto_initialized:
                # implicit local auto-init must never mask an explicit init
                _stop_locked()
            elif (cluster_mode == OrcaContextMeta._cluster_mode
                    and mesh_shape is None):
                logger.warning("init_orca_context called twice; returning "
                               "the existing mesh")
                return OrcaContextMeta._mesh
            else:
                raise RuntimeError(
                    "runtime already initialized with cluster_mode="
                    f"'{OrcaContextMeta._cluster_mode}'; call "
                    "stop_orca_context() before re-initializing with a "
                    "different configuration")

        if cluster_mode in ("tpu_pod", "distributed"):
            dist_kwargs = {}
            if coordinator_address is not None:
                dist_kwargs["coordinator_address"] = coordinator_address
            if num_processes is not None:
                dist_kwargs["num_processes"] = num_processes
            if process_id is not None:
                dist_kwargs["process_id"] = process_id
            jax.distributed.initialize(**dist_kwargs)
        elif cluster_mode not in ("local",):
            raise ValueError(
                f"unsupported cluster_mode '{cluster_mode}'; the TPU build "
                "supports 'local' and 'tpu_pod' (Spark modes like 'yarn'/'k8s' "
                "do not apply — hosts are provisioned by the TPU platform)")

        if cores is not None:
            os.environ.setdefault("OMP_NUM_THREADS", str(cores))

        devices = jax.devices()
        mesh = _build_mesh(devices, mesh_shape)
        OrcaContextMeta._mesh = mesh
        OrcaContextMeta._cluster_mode = cluster_mode
        OrcaContextMeta._initialized = True
        atexit.register(stop_orca_context)
        logger.info("init_orca_context: %d device(s), mesh axes %s shape %s",
                    len(devices), mesh.axis_names, mesh.devices.shape)
        return mesh


def init_nncontext(*args, **kwargs):
    """Alias preserved from the reference
    (pyzoo/zoo/common/nncontext.py:335)."""
    return init_orca_context(*args, **kwargs)


def _stop_locked():
    if not OrcaContextMeta._initialized:
        return
    if OrcaContextMeta._cluster_mode in ("tpu_pod", "distributed"):
        import jax
        try:
            jax.distributed.shutdown()
        except Exception:  # already down / never fully up
            pass
    OrcaContextMeta._mesh = None
    OrcaContextMeta._cluster_mode = None
    OrcaContextMeta._initialized = False
    OrcaContextMeta._auto_initialized = False
    logger.info("stop_orca_context: runtime stopped")


def stop_orca_context():
    """Tear down the runtime (reference: pyzoo/zoo/orca/common.py:269)."""
    with OrcaContextMeta._lock:
        _stop_locked()
