"""Framework-wide constants and typed environment variables.

Capability parity with the reference's constant/env layer
(``/root/reference/autodist/const.py:32-89``): a working directory for
serialized strategies/logs/traces, name prefixes for framework-introduced
structure, and a typed ``ENV`` enum that doubles as the chief->worker
environment contract for multi-host launches.
"""
import enum
import os

DEFAULT_WORKING_DIR = os.environ.get("AUTODIST_WORKING_DIR", "/tmp/autodist_tpu")
DEFAULT_SERIALIZATION_DIR = os.path.join(DEFAULT_WORKING_DIR, "strategies")
DEFAULT_LOG_DIR = os.path.join(DEFAULT_WORKING_DIR, "logs")
DEFAULT_TRACE_DIR = os.path.join(DEFAULT_WORKING_DIR, "traces")
DEFAULT_GRAPH_DUMP_DIR = os.path.join(DEFAULT_WORKING_DIR, "graphs")
DEFAULT_CHECKPOINT_DIR = os.path.join(DEFAULT_WORKING_DIR, "checkpoints")

# Default port used by the JAX coordination service on the chief host
# (replaces the reference's 15000-16000 gRPC server port range,
# /root/reference/autodist/const.py:38).
DEFAULT_COORDINATOR_PORT = 15500

# How long a worker waits for the chief to publish the serialized strategy
# on the coordination service's KV store (strategy building can trail the
# worker's own arrival by a full capture + build).  Default only — large
# models can exceed it; override with AUTODIST_STRATEGY_SHIP_TIMEOUT_MS.
STRATEGY_SHIP_TIMEOUT_MS = 120_000


def strategy_ship_timeout_ms():
    """Effective ship timeout: the typed ENV override, else the default."""
    return ENV.AUTODIST_STRATEGY_SHIP_TIMEOUT_MS.val or STRATEGY_SHIP_TIMEOUT_MS

# Name prefix attached to framework-introduced pytree scopes / mesh axes.
AUTODIST_PREFIX = "AutoDist-"

# Canonical mesh axis names. Every strategy compiles down to shardings over
# (a subset of) these axes.
MESH_AXIS_DATA = "data"        # data parallel / gradient reduction axis
MESH_AXIS_MODEL = "model"      # tensor / parameter partition axis
MESH_AXIS_SEQ = "seq"          # sequence/context parallel axis (ring attention)
MESH_AXIS_EXPERT = "expert"    # expert parallel axis (MoE)
MESH_AXIS_PIPELINE = "pipe"    # pipeline stage axis
ALL_MESH_AXES = (MESH_AXIS_DATA, MESH_AXIS_MODEL, MESH_AXIS_SEQ,
                 MESH_AXIS_EXPERT, MESH_AXIS_PIPELINE)
# Nested sub-axes of the data axis for hierarchical collectives
# (cluster.build_hierarchical_mesh / kernel/synchronization/hierarchical.py):
# dcn spans hosts (slow leg), ici spans devices within a host (fast leg).
MESH_AXIS_DCN = "dcn"
MESH_AXIS_ICI = "ici"


class ENV(enum.Enum):
    """Typed environment variables (the chief->worker launch contract).

    Mirrors the reference's 9-variable contract
    (``/root/reference/autodist/const.py:55-89``) with TPU-pod semantics:
    process index / coordinator address replace the SSH worker identity.
    """

    AUTODIST_WORKER = ("AUTODIST_WORKER", str, "")           # non-empty => this process is a worker, value = host address
    AUTODIST_STRATEGY_ID = ("AUTODIST_STRATEGY_ID", str, "") # strategy artifact id to load instead of building
    AUTODIST_MIN_LOG_LEVEL = ("AUTODIST_MIN_LOG_LEVEL", str, "INFO")
    AUTODIST_IS_TESTING = ("AUTODIST_IS_TESTING", bool, False)
    AUTODIST_DEBUG_REMOTE = ("AUTODIST_DEBUG_REMOTE", bool, False)
    AUTODIST_COORDINATOR = ("AUTODIST_COORDINATOR", str, "") # "host:port" of the coordination service
    AUTODIST_PROCESS_ID = ("AUTODIST_PROCESS_ID", int, 0)    # jax process index assigned by the launcher
    AUTODIST_NUM_PROCESSES = ("AUTODIST_NUM_PROCESSES", int, 1)
    AUTODIST_DUMP_GRAPHS = ("AUTODIST_DUMP_GRAPHS", bool, False)  # dump jaxpr/HLO at each compile stage
    AUTODIST_SSH_BIN = ("AUTODIST_SSH_BIN", str, "ssh")      # ssh client override (tests: loopback shim)
    AUTODIST_SCP_BIN = ("AUTODIST_SCP_BIN", str, "scp")      # scp client override
    # -- resilience (docs/resilience.md) ------------------------------------
    AUTODIST_STRATEGY_SHIP_TIMEOUT_MS = ("AUTODIST_STRATEGY_SHIP_TIMEOUT_MS", int, 0)  # 0 => STRATEGY_SHIP_TIMEOUT_MS default
    AUTODIST_CHAOS = ("AUTODIST_CHAOS", str, "")             # fault injection knobs (resilience/chaos.py)
    AUTODIST_GUARD_CHECK_EVERY = ("AUTODIST_GUARD_CHECK_EVERY", int, 10)   # StepGuard host-check cadence (steps)
    AUTODIST_SUPERVISION = ("AUTODIST_SUPERVISION", str, "abort")          # abort | restart-worker | checkpoint-and-exit | elastic
    AUTODIST_MAX_WORKER_RESTARTS = ("AUTODIST_MAX_WORKER_RESTARTS", int, 2)  # per-worker respawn budget (restart-worker)
    AUTODIST_RETRY_MAX_ATTEMPTS = ("AUTODIST_RETRY_MAX_ATTEMPTS", int, 4)  # transient-I/O retry budget (resilience/retry.py)
    # -- elastic N->M resharding (docs/elasticity.md) ------------------------
    AUTODIST_ELASTIC_MIN_WORLD = ("AUTODIST_ELASTIC_MIN_WORLD", int, 1)  # elastic supervision never shrinks below this world size (escalates to abort)
    AUTODIST_ELASTIC_WORLD = ("AUTODIST_ELASTIC_WORLD", int, 0)  # re-formed world-size override applied to the resource spec (set by Coordinator.reform_now; 0 => spec as written)
    # -- the fused reductions' bucket cap (docs/usage/performance.md) ---------
    AUTODIST_AR_BUCKET_MB = ("AUTODIST_AR_BUCKET_MB", int, 0)  # fusion-bucket size cap in MiB (0 => one bucket per strategy group/compressor/dtype)

    # -- observability (docs/observability.md) -------------------------------
    AUTODIST_UNROLL = ("AUTODIST_UNROLL", int, 1)  # fused steps per XLA dispatch (megastep; 1 => one dispatch per step)

    # -- strategy autotuner (docs/tuning.md) ---------------------------------
    AUTODIST_STRATEGY = ("AUTODIST_STRATEGY", str, "")       # "auto" => tuner picks; else a builder name ("allreduce", "parallax", ...)
    AUTODIST_TUNER_BUDGET = ("AUTODIST_TUNER_BUDGET", int, 0)  # max candidates costed (0 => default 64; >= space size => exhaustive)
    AUTODIST_TUNER_CALIBRATION = ("AUTODIST_TUNER_CALIBRATION", str, "")  # calibration file override (default <working_dir>/tuner_calibration.json)
    AUTODIST_AUTOMAP_BUDGET = ("AUTODIST_AUTOMAP_BUDGET", int, 0)  # automap mesh candidates priced incl. the DP base (0 => default 8; 1 forces the DP base)

    # -- hierarchical collectives (docs/collectives.md) ----------------------
    AUTODIST_HIER_COLLECTIVES = ("AUTODIST_HIER_COLLECTIVES", str, "auto")  # auto => tuner searches the two-level +hier=<codec> exec variants on multi-host topologies; off/0 => flat collectives only
    AUTODIST_HIER_DCN_CODEC = ("AUTODIST_HIER_DCN_CODEC", str, "")  # restrict the searched DCN-leg codec: bf16 | int8 | int8ef ("" => all three)
    AUTODIST_HIER_ICI = ("AUTODIST_HIER_ICI", int, 0)  # ICI-leg size (devices per host) override for the execution-side leg split (0 => ResourceSpec.devices_per_host; testing knob)

    # -- pipeline parallelism (docs/pipelining.md) ---------------------------
    AUTODIST_PIPELINE_STAGES = ("AUTODIST_PIPELINE_STAGES", int, 0)  # pipeline stage count S for Pipeline() with no explicit num_stages (0 => the spec's pipeline: mesh hint, else the stage cutter's choice)
    AUTODIST_MICROBATCHES = ("AUTODIST_MICROBATCHES", int, 0)  # GPipe microbatch count M (0 => 2 * stages; bubble fraction (S-1)/(S+M-1))
    AUTODIST_PIPELINE_SCHEDULE = ("AUTODIST_PIPELINE_SCHEDULE", str, "shift")  # shift (pipelined) | sequential (the bitwise unpipelined control arm, numerics debugging) | 1f1b (shift order + stage rematerialization: activation hold capped at min(S, M) microbatches)

    # -- online re-tuning controller (docs/retuning.md) ----------------------
    AUTODIST_RETUNE = ("AUTODIST_RETUNE", str, "")  # "" / "0" => off (step loop makes zero retune calls); "exec" => tier-1 exec-knob switches only; "1" / "full" => exec-knob AND live strategy switches via reshard
    AUTODIST_RETUNE_PATIENCE = ("AUTODIST_RETUNE_PATIENCE", int, 3)  # consecutive evaluation windows the SAME challenger must stay past the margin before the switch fires (resets on regime flips)
    AUTODIST_RETUNE_SHIP_TIMEOUT_MS = ("AUTODIST_RETUNE_SHIP_TIMEOUT_MS", int, 60_000)  # worker wait for the chief's per-window retune verdict on the coordination-service KV store
    # -- self-healing reshape-on-degrade (docs/retuning.md) ------------------
    AUTODIST_SELFHEAL = ("AUTODIST_SELFHEAL", bool, True)  # degraded-host shrink-and-reshape decisions (active only when AUTODIST_RETUNE is on and a coordinator is bound)
    AUTODIST_SELFHEAL_PATIENCE = ("AUTODIST_SELFHEAL_PATIENCE", int, 3)  # consecutive cluster-sync rounds the SAME host must hold the straggler verdict before eviction is priced (a transient blip never evicts)

    # -- serving runtime (docs/serving.md) -----------------------------------
    AUTODIST_SERVE_BUCKETS = ("AUTODIST_SERVE_BUCKETS", str, "")  # comma list of padded batch buckets, e.g. "8,32,128" ("8x128,32x128" pads (rows, seq))
    AUTODIST_DECODE_SLOTS = ("AUTODIST_DECODE_SLOTS", int, 8)  # decode engine slot count per (slots, cache_len) bucket (must divide the per-replica device count evenly)
    AUTODIST_DECODE_CACHE_LEN = ("AUTODIST_DECODE_CACHE_LEN", int, 128)  # preallocated KV-cache length per slot (prompt + generated tokens must fit)
    AUTODIST_AUTOSCALE = ("AUTODIST_AUTOSCALE", bool, False)  # SLO-driven autoscaler: grow/shrink decode replicas on serve.slo_burn + queue depth (serve/autoscale.py)
    AUTODIST_AUTOSCALE_MIN = ("AUTODIST_AUTOSCALE_MIN", int, 1)  # autoscaler replica floor
    AUTODIST_AUTOSCALE_MAX = ("AUTODIST_AUTOSCALE_MAX", int, 0)  # autoscaler replica ceiling (0 => local device count)

    AUTODIST_PROFILE = ("AUTODIST_PROFILE", bool, True)  # per-layer device-time profiler (finalize-only cost; telemetry off => provably zero calls)

    # -- goodput / run-level accounting (docs/goodput.md) --------------------
    AUTODIST_RUN_ID = ("AUTODIST_RUN_ID", str, "")  # run identity carried across elastic re-exec generations (minted by the chief when unset)
    AUTODIST_RUN_GENERATION = ("AUTODIST_RUN_GENERATION", int, 0)  # process-generation index within a run (bumped by Coordinator.reform_now)
    AUTODIST_PEAK_TFLOPS = ("AUTODIST_PEAK_TFLOPS", float, 0.0)  # per-device peak TFLOP/s override for MFU (0 => built-in per-backend table)

    # -- HBM memory ledger (docs/memory.md) ----------------------------------
    AUTODIST_HBM_GB = ("AUTODIST_HBM_GB", float, 0.0)  # per-device HBM capacity override in GiB (0 => spec memory: block, else the built-in per-backend table)
    AUTODIST_MEM_HEADROOM = ("AUTODIST_MEM_HEADROOM", float, 0.9)  # feasibility fraction of HBM capacity a candidate's predicted peak may use before it is pruned

    # -- cluster timeline / straggler forensics (docs/observability.md) ------
    AUTODIST_CLOCK_SYNC = ("AUTODIST_CLOCK_SYNC", bool, True)  # cross-host clock-offset ping over the coordination-service KV store (0 => no pings; traces still carry the local epoch anchor)
    AUTODIST_SKEW_RING = ("AUTODIST_SKEW_RING", int, 256)  # per-dispatch window ring for the skew decomposition (entries; 0 => no ring, no decomposition)

    AUTODIST_TELEMETRY = ("AUTODIST_TELEMETRY", bool, True)  # master switch: metrics + spans + flight recorder
    AUTODIST_TRACE = ("AUTODIST_TRACE", str, "chrome")       # chrome (trace-event JSON file) | 0 (no file)
    AUTODIST_MONITOR_PORT = ("AUTODIST_MONITOR_PORT", int, 0)  # chief HTTP monitor (/metrics + /status); 0 => no server, no thread
    AUTODIST_FLIGHT_MAX_MB = ("AUTODIST_FLIGHT_MAX_MB", int, 64)  # total on-disk cap across logs/flight_*.jsonl (oldest-file eviction)
    AUTODIST_SERVE_SLO_MS = ("AUTODIST_SERVE_SLO_MS", int, 50)  # serving p99 SLO target (monitor slo-burn gauge)

    def __init__(self, var_name, var_type, default):
        self.var_name = var_name
        self.var_type = var_type
        self.default = default

    @property
    def val(self):
        raw = os.environ.get(self.var_name)
        if raw is None:
            return self.default
        if self.var_type is bool:
            return raw.lower() in ("1", "true", "yes")
        return self.var_type(raw)


def ensure_working_dirs():
    for d in (DEFAULT_WORKING_DIR, DEFAULT_SERIALIZATION_DIR, DEFAULT_LOG_DIR,
              DEFAULT_TRACE_DIR, DEFAULT_GRAPH_DUMP_DIR, DEFAULT_CHECKPOINT_DIR):
        os.makedirs(d, exist_ok=True)
