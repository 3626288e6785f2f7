"""Session-wide constants for the ray_tpu core runtime.

Counterpart of the reference's `python/ray/_private/ray_constants.py` plus
the native config table (`src/ray/common/ray_config_def.h`): every tunable
is declared once in the typed option table (`_private/config.py` —
name, type, default, doc) and env-overridable with the ``RAY_TPU_``
prefix, mirroring the reference's ``RAY_<name>`` convention
(ray_config.h:74). `ray_tpu config list` (scripts/cli.py) prints the
table with effective values.
"""

import os

from ray_tpu._private.config import define

# Objects whose serialized envelope is at most this many bytes travel inline
# in control messages; larger ones go to the shared-memory store (the
# reference inlines <=100KB returns in the gRPC reply, core_worker.cc).
INLINE_OBJECT_MAX_BYTES = define(
    "INLINE_OBJECT_MAX_BYTES", int, 100 * 1024,
    "Objects at most this many serialized bytes ride inline in control "
    "messages instead of the shared-memory store.")

# Where shared-memory object files live (tmpfs). The reference mounts plasma
# over /dev/shm (plasma/store.h); we use one file per object under a session
# directory, which keeps ownership trivially correct (driver unlinks on exit).
SHM_ROOT = define(
    "SHM_ROOT", str, "/dev/shm" if os.path.isdir("/dev/shm") else "/tmp",
    "Root for session directories (object arena + sockets); tmpfs.")

SESSION_PREFIX = "ray_tpu_session_"

MAX_WORKERS_CAP = define(
    "MAX_WORKERS_CAP", int, 32,
    "Hard cap on generic (pool) worker processes per node.")

WORKER_REGISTER_TIMEOUT_S = define(
    "WORKER_REGISTER_TIMEOUT_S", float, 60.0,
    "Seconds to wait for a spawned worker/daemon to phone home before "
    "declaring startup failure (reference: "
    "worker_register_timeout_seconds).")

DEFAULT_TASK_NUM_CPUS = define(
    "DEFAULT_TASK_NUM_CPUS", float, 1.0,
    "CPUs a task holds when @remote doesn't say (reference: tasks "
    "default to num_cpus=1, ray_option_utils.py).")

DEFAULT_ACTOR_LIFETIME_CPUS = define(
    "DEFAULT_ACTOR_LIFETIME_CPUS", float, 0.0,
    "CPUs an actor holds for its lifetime when @remote doesn't say "
    "(reference: actors hold 0 lifetime CPUs by default).")

BUFFER_ALIGNMENT = define(
    "BUFFER_ALIGNMENT", int, 64,
    "Byte alignment of buffers inside serialized envelopes so zero-copy "
    "numpy views land 64-byte aligned (plasma aligns to 64 too).")

WAIT_POLL_S = define(
    "WAIT_POLL_S", float, 0.01,
    "Polling granularity for blocking waits in the client runtime.")

MAX_INFLIGHT_SUBMISSIONS = define(
    "MAX_INFLIGHT_SUBMISSIONS", int, 100_000,
    "How many task submissions a single client may have in flight before "
    "submit blocks (reference has per-lease backlogs).")

# Env var handed to workers that were allocated TPU chips, mirroring how the
# reference sets CUDA_VISIBLE_DEVICES from the resource assignment
# (_private/utils.py:342-355).
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"

MAX_OBJECT_RECONSTRUCTIONS = define(
    "MAX_OBJECT_RECONSTRUCTIONS", int, 3,
    "How many times a lost task-produced object may be rebuilt from "
    "lineage before readers get ObjectLostError (reference: task max "
    "retries gate reconstruction, object_recovery_manager.h:41).")

MAX_LINEAGE_ENTRIES = define(
    "MAX_LINEAGE_ENTRIES", int, 100_000,
    "Lineage table entry cap; oldest specs evict first and their objects "
    "stop being reconstructable.")

MAX_LINEAGE_BYTES = define(
    "MAX_LINEAGE_BYTES", int, 256 * 1024 * 1024,
    "Lineage table byte cap over retained specs (function blobs + inline "
    "args) — the reference's RAY_max_lineage_bytes.")

OBJECT_SPILL_ROOT = define(
    "OBJECT_SPILL_ROOT", str, "/tmp/ray_tpu_spill",
    "Real-disk root for arena-overflow and spilled objects (reference: "
    "external_storage.py FileSystemStorage); bounds shm usage by the "
    "arena capacity.")

SPILL_HIGH_WATER = define(
    "SPILL_HIGH_WATER", float, 0.80,
    "Arena-usage fraction above which the store owner spills sealed "
    "objects to disk (local_object_manager.h:110).")

SPILL_LOW_WATER = define(
    "SPILL_LOW_WATER", float, 0.50,
    "Spill passes drain arena usage down to this fraction.")

MEMORY_MONITOR_THRESHOLD = define(
    "MEMORY_MONITOR_THRESHOLD", float, 0.95,
    "Host/cgroup memory-usage fraction above which the newest retriable "
    "worker is killed (memory_monitor.h:52); 0 disables.")

MEMORY_MONITOR_INTERVAL_S = define(
    "MEMORY_MONITOR_INTERVAL_S", float, 1.0,
    "Memory monitor poll interval in seconds.")

OBJECT_STORE_BYTES = define(
    "OBJECT_STORE_BYTES", int, 0,
    "Shared-memory arena capacity per node (plasma store size analog). "
    "0 = auto: 20% of system memory, min 512 MiB (the reference sizes "
    "plasma at 30% of RAM by default; the arena file is sparse, so "
    "unused capacity costs nothing).")

RUNTIME_ENV_CACHE = define(
    "RUNTIME_ENV_CACHE", str, "/tmp/ray_tpu_runtime_envs",
    "Content-addressed cache dir for materialized runtime environments "
    "(working_dir copies, pip venvs; reference: uri_cache.py).")

RUNTIME_ENV_CACHE_ENTRIES = define(
    "RUNTIME_ENV_CACHE_ENTRIES", int, 20,
    "LRU cap on cached runtime-env entries.")

PUBSUB_RING_MESSAGES = define(
    "PUBSUB_RING_MESSAGES", int, 1000,
    "Per-channel cap on retained pubsub messages (long-poll publisher "
    "ring, reference: publisher.h buffered channels).")

# --- transport (reference: gRPC-over-TCP for every cross-host edge,
# src/ray/rpc/grpc_server.h; UDS only worker<->local raylet) ---

TRANSPORT = define(
    "TRANSPORT", str, "uds",
    "Cluster transport for daemon/client<->head and peer pulls: 'uds' "
    "(single machine) or 'tcp' (cluster spans machines). Workers always "
    "ride UDS to their local daemon, like the reference. Read at init() "
    "time via config.get, so tests can flip it per-session.")

HEAD_PORT = define(
    "HEAD_PORT", int, 0,
    "TCP port for the head listener when TRANSPORT=tcp (0 = ephemeral). "
    "Reference: --port on `ray start --head` (scripts.py:537).")

HEAD_BIND_HOST = define(
    "HEAD_BIND_HOST", str, "0.0.0.0",
    "Bind host for the head's TCP listener.")

NODE_IP = define(
    "NODE_IP", str, "",
    "Advertised IP of this machine for cross-host dials; empty = "
    "autodetect via the outbound interface (reference: "
    "node_ip_address detection, services.py:1353).")

DAEMON_RECONNECT_GRACE_S = define(
    "DAEMON_RECONNECT_GRACE_S", float, 60.0,
    "How long a HostDaemon keeps retrying the head channel after it "
    "closes (head crash/restart) before giving up and dying "
    "(reference: raylets ride out GCS restarts, "
    "gcs_rpc_server_reconnect_timeout_s). 0 disables reconnect.")

HEAD_SNAPSHOT_INTERVAL_S = define(
    "HEAD_SNAPSHOT_INTERVAL_S", float, 1.0,
    "Standalone-head metadata snapshot period (named actors, KV, jobs, "
    "placement groups -> session_dir/head_state.pkl; reference: "
    "Redis-backed GCS persistence, redis_store_client.h:33).")

HEAD_SNAPSHOT_URI = define(
    "HEAD_SNAPSHOT_URI", str, "",
    "Optional URI (mem:// fake, registered gs://...) the standalone "
    "head mirrors its metadata snapshot to; a NEW head on ANY machine "
    "restores from it when its session dir has no local snapshot — "
    "head failover (reference: Redis-backed GCS persistence, "
    "redis_store_client.h:33).")

AUTOSCALER_UPDATE_INTERVAL_S = define(
    "AUTOSCALER_UPDATE_INTERVAL_S", float, 1.0,
    "Head monitor tick: refresh LoadMetrics from cluster state and run "
    "StandardAutoscaler.update (reference: monitor.py:371 loop, "
    "AUTOSCALER_UPDATE_INTERVAL_S=5).")

WORKER_LOG_REDIRECT = define(
    "WORKER_LOG_REDIRECT", bool, True,
    "Write each worker/daemon process's stdout+stderr to its own file "
    "under the session (node) logs dir instead of inheriting the "
    "driver's terminal (reference: per-process files under the session "
    "dir, log_monitor.py). Disable for raw interleaved output.")

LOG_TAIL_INTERVAL_S = define(
    "LOG_TAIL_INTERVAL_S", float, 0.5,
    "How often the head/daemon LogTailer polls its local log files for "
    "new lines (reference: LOG_NAME_UPDATE_INTERVAL_S).")

LOG_RING_LINES = define(
    "LOG_RING_LINES", int, 2000,
    "Per-source cap on log lines the head retains for the dashboard "
    "/api/logs endpoint and `ray_tpu logs`.")

PG_AUTOSCALE_WAIT_S = define(
    "PG_AUTOSCALE_WAIT_S", float, 60.0,
    "With an autoscaler attached, how long placement-group creation "
    "waits for capacity (the gang rides the demand queue) before "
    "raising PlacementGroupError (reference: PENDING placement groups "
    "feed autoscaler demand).")

# --- object data plane (object_manager.h chunking / pull admission) ---

PULL_CHUNK_BYTES = define(
    "PULL_CHUNK_BYTES", int, 8 << 20,
    "Chunk size for node-to-node object pulls (reference: "
    "object_manager_default_chunk_size; 8 MiB measured best for GiB-"
    "scale broadcasts on the pickle-framed channel, see SCALE.json).")

PULL_TIMEOUT_S = define(
    "PULL_TIMEOUT_S", float, 120.0,
    "Deadline for one chunked object pull before the reader declares "
    "the object unavailable from that source.")

PULL_RETRY_ATTEMPTS = define(
    "PULL_RETRY_ATTEMPTS", int, 4,
    "How many sources/attempts a head-side pull tries (promotion or "
    "reconstruction can re-home the object between attempts).")

OBJECT_REPLACEMENT_WAIT_S = define(
    "OBJECT_REPLACEMENT_WAIT_S", float, 60.0,
    "After an object's source died mid-pull, how long to wait for a "
    "promoted copy or lineage reconstruction to re-register it.")

SUBMIT_INLINE_BACKLOG = define(
    "SUBMIT_INLINE_BACKLOG", int, 32,
    "Pending-queue depth beyond which task submission skips its inline "
    "dispatch attempt and becomes a pure enqueue: with a deep backlog "
    "the attempt is futile (older tasks wait on the same capacity) and "
    "completions pull from the backlog directly. Keeps saturated "
    "submission O(1) while idle-cluster submit->execute latency stays "
    "on the fast path.")

SCHEDULER_DISPATCH_WINDOW = define(
    "SCHEDULER_DISPATCH_WINDOW", int, 64,
    "Max non-dispatchable tasks one schedule pass examines before "
    "leaving the rest queued (the pass rotates the examined prefix to "
    "the back, so successive passes cover the whole backlog). Bounds "
    "every scheduling event to O(window) instead of O(backlog) — the "
    "reference caps its dispatch loop the same way.")

FREED_REFS_CAP = define(
    "FREED_REFS_CAP", int, 100_000,
    "Bounded FIFO of freed object ids kept as tombstones so racing "
    "get/wait calls fail fast instead of hanging.")

ARGS_RELEASED_CAP = define(
    "ARGS_RELEASED_CAP", int, 200_000,
    "Bounded FIFO of task ids whose args were already released "
    "(exactly-once guard on the refcount decrement).")

COLLECTIVE_MAX_BYTES = define(
    "COLLECTIVE_MAX_BYTES", int, 64 << 20,
    "Per-payload cap on host-side util.collective verbs — the rendezvous "
    "actor is a control-plane funnel; device tensors belong in-graph "
    "(psum/all_gather over a Mesh axis).")

DATA_PUSH_SHUFFLE_MIN_BLOCKS = define(
    "DATA_PUSH_SHUFFLE_MIN_BLOCKS", int, 32,
    "Input-block count above which all-to-all data exchanges insert the "
    "push-based merge tier (push_based_shuffle.py analog): ~sqrt(M) "
    "merger fan-in instead of every reducer fetching from all M maps.")

RUNTIME_ENV_CACHE_BYTES = define(
    "RUNTIME_ENV_CACHE_BYTES", int, 10 << 30,
    "Total-bytes cap on the runtime-env cache; least-recently-used "
    "entries are evicted above it (uri_cache.py byte budget analog).")

RUNTIME_ENV_CONDA_TIMEOUT_S = define(
    "RUNTIME_ENV_CONDA_TIMEOUT_S", float, 1800.0,
    "Timeout for `conda env create` when materializing a conda "
    "runtime environment.")

CONDA_BINARY = define(
    "CONDA_BINARY", str, "conda",
    "Conda executable used for runtime_env={'conda': ...}.")

CONTAINER_RUNTIME = define(
    "CONTAINER_RUNTIME", str, "",
    "Container runtime for runtime_env={'container': ...}; empty = "
    "autodetect docker then podman.")

HEAD_BACKLOG_CAP = define(
    "HEAD_BACKLOG_CAP", int, 10_000,
    "Max daemon->head messages buffered during a head-channel blip for "
    "replay after reconnect (completions must survive the window).")

# --- control-plane timeouts / cadences ---

HEAD_CONTROL_TIMEOUT_S = define(
    "HEAD_CONTROL_TIMEOUT_S", float, 30.0,
    "Daemon-issued control RPCs to the head (peer address lookup etc.) "
    "fail after this many seconds.")

ACTOR_LEASE_WAIT_S = define(
    "ACTOR_LEASE_WAIT_S", float, 30.0,
    "How long a daemon waits for an actor's worker to (re)appear before "
    "failing a leased actor method call.")

ATTACH_CONTROL_TIMEOUT_S = define(
    "ATTACH_CONTROL_TIMEOUT_S", float, 30.0,
    "Default timeout for CLI/job attach-client control calls.")

SPILL_PASS_INTERVAL_S = define(
    "SPILL_PASS_INTERVAL_S", float, 1.0,
    "How often the head/daemon spill loop checks the arena high-water "
    "mark (local_object_manager spill polling analog).")

REF_FLUSH_INTERVAL_S = define(
    "REF_FLUSH_INTERVAL_S", float, 0.5,
    "Workers batch ObjectRef hold/release events and flush them to the "
    "head at this cadence (__del__ storms never become message storms).")

JOB_ADOPT_POLL_S = define(
    "JOB_ADOPT_POLL_S", float, 0.5,
    "Poll interval while a restarted head watches an adopted job's "
    "process for exit.")

METRICS_FLUSH_PERIOD_S = define(
    "METRICS_FLUSH_PERIOD_S", float, 5.0,
    "Workers push metric snapshots to the head at this cadence "
    "(reference: metrics_report_interval_ms).")

TASK_EVENT_QUERY_LIMIT = define(
    "TASK_EVENT_QUERY_LIMIT", int, 10_000,
    "Default cap on task records returned by the state API "
    "(reference: RAY_MAX_LIMIT_FROM_API_SERVER).")

GC_STALE_SESSIONS = define(
    "GC_STALE_SESSIONS", bool, True,
    "init() sweeps session dirs whose driver/head process is dead "
    "before creating a new one.")

DASHBOARD_BIND_HOST = define(
    "DASHBOARD_BIND_HOST", str, "127.0.0.1",
    "Bind host for the dashboard HTTP server.")

# --- ray_tpu.data streaming executor budgets (reference: Data streaming
# backpressure, streaming_executor_state.py) ---

DATA_MAX_TASKS_IN_FLIGHT = define(
    "DATA_MAX_TASKS_IN_FLIGHT", int, 8,
    "Per-operator cap on concurrently running Data tasks when the "
    "DataContext doesn't override it.")

DATA_BYTES_IN_FLIGHT = define(
    "DATA_BYTES_IN_FLIGHT", int, 128 * 1024 * 1024,
    "Per-operator byte budget of in-flight blocks (streaming "
    "backpressure, reference byte-budget model).")

DATA_BLOCK_SIZE_ESTIMATE = define(
    "DATA_BLOCK_SIZE_ESTIMATE", int, 8 * 1024 * 1024,
    "Default estimated output block size used for read planning before "
    "any block has materialized.")

# --- ray_tpu.serve control/data plane cadences ---

SERVE_RECONCILE_PERIOD_S = define(
    "SERVE_RECONCILE_PERIOD_S", float, 1.0,
    "Serve controller reconcile loop period (deployment_state.py "
    "analog).")

SERVE_HANDLE_REFRESH_S = define(
    "SERVE_HANDLE_REFRESH_S", float, 2.0,
    "How often a ServeHandle refreshes its replica set from the "
    "controller (long-poll refresh analog).")

SERVE_STREAM_BATCH = define(
    "SERVE_STREAM_BATCH", int, 16,
    "Streaming responses ship at most this many chunks per proxy "
    "round-trip (a reply goes out with what is ready and the first "
    "chunk it had to wait for).")

SERVE_STREAM_IDLE_TTL_S = define(
    "SERVE_STREAM_IDLE_TTL_S", float, 300.0,
    "Undrained response streams are reaped after this idle time.")

SERVE_DOWNSCALE_DELAY_S = define(
    "SERVE_DOWNSCALE_DELAY_S", float, 30.0,
    "Default delay before the Serve autoscaler honors a downscale "
    "decision (reference: downscale_delay_s).")

SERVE_STATS_TIMEOUT_S = define(
    "SERVE_STATS_TIMEOUT_S", float, 10.0,
    "Timeout for the controller's replica stats fan-out each "
    "autoscaling tick.")

SERVE_DRAIN_TIMEOUT_S = define(
    "SERVE_DRAIN_TIMEOUT_S", float, 30.0,
    "On scale-down, how long the controller waits for a victim "
    "replica's in-flight requests and response streams to drain "
    "before it is killed anyway.")

SERVE_DRAIN_POLL_S = define(
    "SERVE_DRAIN_POLL_S", float, 0.1,
    "Poll period for the scale-down drain loop's replica stats checks.")

# --- ray_tpu.serve fault tolerance (health plane, retries, breaker) ---

SERVE_HEALTH_FAILURE_THRESHOLD = define(
    "SERVE_HEALTH_FAILURE_THRESHOLD", int, 3,
    "Consecutive failed health pings before the controller declares a "
    "replica dead (an ActorDiedError is authoritative immediately). "
    "Reference: health_check_failure_threshold, deployment_state.py.")

SERVE_HEALTH_STARTUP_GRACE_S = define(
    "SERVE_HEALTH_STARTUP_GRACE_S", float, 60.0,
    "Startup probation: ping failures of a replica that has never yet "
    "passed a health check don't count as strikes for this long after "
    "creation (slow engine construction is not flapping). Real deaths "
    "still replace immediately.")

SERVE_BREAKER_THRESHOLD = define(
    "SERVE_BREAKER_THRESHOLD", int, 3,
    "Replica deaths within SERVE_BREAKER_WINDOW_S that trip a "
    "deployment's circuit breaker from closed to open.")

SERVE_BREAKER_WINDOW_S = define(
    "SERVE_BREAKER_WINDOW_S", float, 30.0,
    "Sliding window over replica deaths for the breaker trip decision.")

SERVE_BREAKER_COOLDOWN_S = define(
    "SERVE_BREAKER_COOLDOWN_S", float, 10.0,
    "How long an open breaker quarantines a deployment (no replica "
    "restarts) before moving to half-open and allowing one probe.")

SERVE_BREAKER_PROBE_S = define(
    "SERVE_BREAKER_PROBE_S", float, 5.0,
    "How long a half-open breaker's single probe replica must stay "
    "healthy before the breaker closes and normal restarts resume.")

SERVE_RETRY_MAX_ATTEMPTS = define(
    "SERVE_RETRY_MAX_ATTEMPTS", int, 3,
    "Default attempt budget for handle-level request retries through "
    "replica death (capped exponential backoff between attempts).")

SERVE_RETRY_BASE_S = define(
    "SERVE_RETRY_BASE_S", float, 0.05,
    "Base delay of the handle retry backoff; attempt n sleeps "
    "min(cap, base * 2**n) with jitter.")

SERVE_RETRY_CAP_S = define(
    "SERVE_RETRY_CAP_S", float, 2.0,
    "Cap on a single handle retry backoff sleep.")

SERVE_STREAM_FAILOVERS = define(
    "SERVE_STREAM_FAILOVERS", int, 2,
    "How many mid-stream failovers one streaming call may ride before "
    "the replica-death error propagates to the consumer.")

SERVE_HTTP_HOST = define(
    "SERVE_HTTP_HOST", str, "127.0.0.1",
    "Default bind host for the Serve HTTP proxy.")

SERVE_HTTP_PORT = define(
    "SERVE_HTTP_PORT", int, 8000,
    "Default port for the Serve HTTP proxy (reference: "
    "serve.start(http_options).")

# --- multi-tenant inference: priority classes + preemption ---

ENGINE_PRIORITY_CLASSES = define(
    "ENGINE_PRIORITY_CLASSES", int, 3,
    "Number of request priority classes the inference engine admits "
    "(0 = lowest .. N-1 = highest). submit(priority=) must be in "
    "range; the admission queue weights, sheds, and preempts by "
    "class.")

ENGINE_PRIORITY_AGING_S = define(
    "ENGINE_PRIORITY_AGING_S", float, 2.0,
    "Admission aging quantum: a pending request older than "
    "(priority_classes - its class) * this jumps the weighted-share "
    "order entirely (FIFO among the escalated), bounding how long a "
    "low class can wait behind sustained high-class load.")

ENGINE_PRIORITY_WEIGHT_BASE = define(
    "ENGINE_PRIORITY_WEIGHT_BASE", float, 4.0,
    "Weighted-share base for class admission: class c gets stride "
    "weight base**c, so each step up the class ladder gets base x the "
    "admission share of the class below while every backlogged class "
    "keeps a nonzero guaranteed share (no starvation even before "
    "aging kicks in).")

# --- runtime environments ---

RUNTIME_ENV_VENV_CREATE_TIMEOUT_S = define(
    "RUNTIME_ENV_VENV_CREATE_TIMEOUT_S", int, 120,
    "Timeout for creating a pip runtime-env virtualenv.")

RUNTIME_ENV_PIP_INSTALL_TIMEOUT_S = define(
    "RUNTIME_ENV_PIP_INSTALL_TIMEOUT_S", int, 600,
    "Timeout for installing a pip runtime-env's requirements "
    "(reference: pip runtime env install timeout).")
# --- control-plane throughput (channel framing + pipelined submission) ---

CHANNEL_BATCHING = define(
    "CHANNEL_BATCHING", bool, True,
    "Coalesce control-plane messages into one wire frame per channel "
    "flush (netaddr.BatchedConnection). Each logical message keeps its "
    "own identity for fault injection and FIFO order; turning this off "
    "restores one pickle per send (the parity smoke test runs both).")

CHANNEL_QUEUE_CAP = define(
    "CHANNEL_QUEUE_CAP", int, 65536,
    "Backpressure bound on a batched channel's outbound queue: past "
    "this many queued logical messages send() blocks until the flusher "
    "drains, matching the blocking a raw full pipe would impose.")

SUBMIT_PIPELINE = define(
    "SUBMIT_PIPELINE", bool, True,
    "Workers stream nested task submissions without a per-task ack, "
    "under a windowed credit scheme with sequence-numbered nack/replay "
    "(reference: Ray's pipelined task submission to the raylet). Off "
    "restores one blocking SubmitRequest/SubmitReply round trip each.")

SUBMIT_WINDOW = define(
    "SUBMIT_WINDOW", int, 1024,
    "Max unacknowledged pipelined submissions per worker channel before "
    "submit_spec blocks waiting for credit.")

SUBMIT_RESYNC_S = define(
    "SUBMIT_RESYNC_S", float, 1.0,
    "With unacked pipelined submissions and no credit progress for this "
    "long, the worker replays its unacked ring (the head dedupes by "
    "seq and re-credits, so a lost tail message cannot stall forever).")

SCHEDULER_FREED_BATCH = define(
    "SCHEDULER_FREED_BATCH", int, 16,
    "How many queued plain tasks the completion fast path may dispatch "
    "under ONE scheduler-lock acquisition when workers free up.")

LINK_GROUPS = define(
    "LINK_GROUPS", str, "",
    "Comma-separated interconnect link-group ids (ICI ring / DCN pod) "
    "this host hangs off, advertised in RegisterNode for the "
    "contention-aware gang placement model (2207.07817). Empty = no "
    "topology information; contention scoring is a no-op.")
