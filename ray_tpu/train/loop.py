"""Overlapped training loop: host→device prefetch, fused multi-step
dispatch, async metrics.

The jitted step (train/spmd.py) is fast; what stalls real training is
everything AROUND it: waiting on host→device transfer of the next batch,
re-entering Python once per step to dispatch, and pulling metrics to the
host after every step. The Podracer "sebulba" split (arXiv:2104.06272)
wins TPU throughput by overlapping the host data feed with device compute
and batching many steps per dispatch; this module is that loop for the
SPMD trainers:

  * `DevicePrefetcher` — keeps `depth` sharded `device_put` transfers in
    flight ahead of the consumer, so DMA of batch N+1 rides under compute
    of step N.
  * `fuse_steps` / `TrainLoop(unroll=u)` — `lax.scan`s u steps into one
    jitted dispatch with state donation: one Python round-trip and one
    XLA launch per u steps.
  * `MetricsRing` — device-side metric handles ride in a ring and are
    fetched to host at most every `interval` steps, always from a
    dispatch that is already `lag` dispatches old, so no step ever blocks
    on a host sync.

`ray_tpu.data.Dataset.iter_device_batches` bridges `iter_batches` into a
`DevicePrefetcher`; the benchmark's training cells
(`benchmarks/harness/train_cell.py`) stream fresh host batches through
the whole thing.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.sharding import logical_to_spec
from ray_tpu.util import telemetry as _telemetry

# Host-fetch seam: the ONLY place this module moves device values to the
# host. Tests monkeypatch it to assert the no-per-step-sync property.
_device_get = jax.device_get

# What every step's metrics hold (`spmd.make_train_step`). Whatever other
# scalars a trainer's steps carry, `TrainLoop.stats()` totals.
STEP_BASICS = ("loss", "grad_norm", "step")


def step_totals(history: list) -> dict:
    """What a run's steps carried beside `STEP_BASICS`, by the names the
    trainer gave: {name: the total over the run's steps, ...,
    "first_step": {name: its value at the first step}, "last_step": the
    same at the last}. A count's total is a count; a level (a maximum, a
    mean) is read at the two steps, or as one total over another. Nothing
    where the steps carry nothing more."""
    first = history[0] if history and isinstance(history[0], dict) else {}
    names = [k for k, v in first.items()
             if k not in STEP_BASICS and np.ndim(v) == 0]
    if not names:
        return {}

    def row(m) -> dict:
        return {k: np.asarray(m[k]).item() for k in names}

    return {**{k: sum(np.asarray(m[k]).item() for m in history)
               for k in names},
            "first_step": row(history[0]), "last_step": row(history[-1])}


def make_placer(mesh: Mesh, rules: dict | None = None,
                stacked: bool = False) -> Callable[[Any], Any]:
    """Host-batch placement fn: leaves go to the mesh sharded over the
    data-like axes on their leading dim (batch→data/fsdp), trailing dims
    replicated. stacked=True expects a leading unroll/group axis ahead of
    the batch dim (kept unsharded — it is the scan axis of a fused
    multi-step dispatch)."""
    spec = logical_to_spec(("batch",), rules, mesh)
    lead = [None] if stacked else []

    def place(tree):
        def put(a):
            dims = lead + list(spec)
            full = PartitionSpec(*(dims + [None] * (a.ndim - len(dims))))
            return jax.device_put(a, NamedSharding(mesh, full))
        return jax.tree.map(put, tree)
    return place


class DevicePrefetcher:
    """Double-buffered host→device prefetcher (flax `prefetch_to_device`
    idiom, sharding-aware).

    Keeps `depth` transfers in flight: `device_put` of batch N+depth is
    issued before batch N is consumed, and JAX transfers are async, so
    host→device DMA overlaps device compute. Every yielded batch is a
    FRESH device allocation — a yielded buffer is never re-filled or
    re-yielded, so a consumer that donates batch buffers into its step
    can never alias a transfer still in flight (donation-safe rotation);
    rotation is the deque of in-flight batches, bounded at `depth`.

    group=g stacks g host batches leaf-wise (leading [g, ...] axis)
    before placing — the input shape of a fused multi-step dispatch
    (`TrainLoop(unroll=g)`). A trailing ragged group is dropped and
    counted in `skipped_ragged` (it would change the compiled dispatch
    shape), so silently shortened epochs are observable.

    A host-iterator exception is never masked as end-of-stream: batches
    already transferred are still delivered in order, then the original
    exception is re-raised (and keeps re-raising — a failed feed must
    not look like a clean epoch boundary to a retrying consumer).
    """

    def __init__(self, host_iter: Iterable, place: Callable[[Any], Any],
                 *, depth: int = 2, group: int = 1):
        self._host = iter(host_iter)
        self._place = place
        self._depth = max(1, int(depth))
        self._group = max(1, int(group))
        self._buf: collections.deque = collections.deque()
        self._err: BaseException | None = None
        self._exhausted = False
        self.issued = 0         # transfers dispatched (observability)
        self.skipped_ragged = 0  # host batches dropped in a ragged tail
        # `train/host_batch` (the data source's `next`) and `train/place`
        # (the transfer's enqueue): the buffer fills on the consumer's
        # thread inside `__next__`, so in a trace both lie inside the
        # loop's `train/next_batch`.
        self.phases = _telemetry.Phases()

    def _next_host_batch(self):
        with self.phases.phase("train/host_batch"):
            if self._group == 1:
                return next(self._host)
            parts = list(itertools.islice(self._host, self._group))
            if len(parts) < self._group:
                self.skipped_ragged += len(parts)
                raise StopIteration
            return jax.tree.map(lambda *xs: np.stack(xs), *parts)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        while (not self._exhausted and self._err is None
                and len(self._buf) < self._depth):
            try:
                host = self._next_host_batch()
                with self.phases.phase("train/place"):
                    self._buf.append(self._place(host))
                self.issued += 1
            except StopIteration:
                self._exhausted = True
            except Exception as e:
                self._err = e
        if self._buf:
            return self._buf.popleft()
        if self._err is not None:
            raise self._err
        raise StopIteration


class MetricsRing:
    """Device-side metrics ring with bounded, lagged host fetches.

    `push` stores the device pytree a dispatch returned (no sync);
    entries are fetched to host at most every `interval` steps, and only
    once they are at least `lag` dispatches old — by then the device has
    long finished computing them (the loop has dispatched past them), so
    the `device_get` returns without stalling the device queue. `drain`
    fetches everything left (the one deliberate end-of-run sync).
    """

    def __init__(self, interval: int = 10, lag: int = 2):
        self.interval = max(1, int(interval))
        self.lag = max(0, int(lag))
        self._pending: collections.deque = collections.deque()
        self.history: list = []
        self.fetches = 0        # host syncs performed (tests assert this)
        self.phases = _telemetry.Phases()    # `train/metrics_fetch`
        self._steps_pushed = 0
        self._last_sync = 0

    def push(self, metrics, count: int = 1) -> None:
        """Store one dispatch's device metrics (`count` = steps in the
        dispatch; leaves carry a leading [count] axis when count > 1)."""
        self._pending.append((count, metrics))
        self._steps_pushed += count
        if (self._steps_pushed - self._last_sync >= self.interval
                and len(self._pending) > self.lag):
            self._sync(keep=self.lag)
            self._last_sync = self._steps_pushed

    def _sync(self, keep: int) -> None:
        """ONE host fetch covering every pending entry older than the
        newest `keep` dispatches."""
        take = len(self._pending) - keep
        if take <= 0:
            return
        items = [self._pending.popleft() for _ in range(take)]
        with self.phases.phase("train/metrics_fetch", entries=take):
            # graftlint: disable-next-line=R001 intentional lagged fetch: fires at most every `interval` pushed steps and only for entries >= `lag` dispatches old, so the device queue is never drained behind the live dispatch
            hosts = _device_get([m for _, m in items])
        self.fetches += 1
        for (count, _), host in zip(items, hosts):
            if count == 1:
                self.history.append(host)
            else:
                self.history.extend(
                    jax.tree.map(lambda a, i=i: a[i], host)
                    for i in range(count))

    def drain(self) -> list:
        self._sync(keep=0)
        # Reset the cadence counters so a ring reused across runs starts
        # the next run's interval from zero instead of inheriting stale
        # push counts (which either fired a fetch on the first push or
        # deferred one for a whole extra interval).
        self._steps_pushed = 0
        self._last_sync = 0
        return self.history


def fuse_steps(step_fn: Callable, unroll: int,
               donate: bool = True,
               on_trace: Callable[[], None] | None = None) -> Callable:
    """One jitted dispatch running `unroll` chained steps via lax.scan.

    step_fn: (state, batch) -> (state, metrics); jitted is fine (the
    inner pjit inlines under the outer trace). The fused call takes
    batch leaves stacked [unroll, ...] and returns metrics stacked the
    same way. State is donated across the dispatch, so param/opt
    buffers update in place exactly as in the single-step path.

    on_trace (if given) is called once per python trace of the fused
    dispatch — the compile-once counter seam the retrace sentinel
    watches, same idiom as the engine's `decode_traces`.
    """
    unroll = int(unroll)
    if unroll < 1:
        raise ValueError(f"unroll must be >= 1, got {unroll}")

    def multi(state, stacked):
        if on_trace is not None:
            on_trace()
        return jax.lax.scan(step_fn, state, stacked)

    kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(multi, **kwargs)


class TrainLoop:
    """Overlap-aware driver around a (state, batch) -> (state, metrics)
    step.

    Builds its dispatch once (so repeated `run` calls — warmup then the
    timed region — hit the same jit cache): the step itself for
    unroll=1, `fuse_steps(step_fn, unroll)` otherwise. Metrics go
    through a `MetricsRing` (host fetch at most every
    `metrics_interval` steps, `metrics_lag` dispatches behind); `run`
    returns the drained per-step host metrics, so the only blocking
    sync is at the very end of each run.
    """

    def __init__(self, step_fn: Callable, *, unroll: int = 1,
                 metrics_interval: int = 10, metrics_lag: int = 2,
                 donate: bool = True, checkpointer=None,
                 publisher: Callable | None = None):
        self.unroll = max(1, int(unroll))
        self.metrics_interval = metrics_interval
        self.metrics_lag = metrics_lag
        # Compile-once accounting for the fused dispatch (engine idiom:
        # the counter increments inside the traced fn, once per trace).
        # For unroll=1 the dispatch is the caller's step_fn — its jit
        # cache isn't ours to instrument, so the watch is unroll>1 only.
        self.dispatch_traces = 0

        def _count_trace():
            self.dispatch_traces += 1

        self._dispatch = (step_fn if self.unroll == 1
                          else fuse_steps(step_fn, self.unroll, donate,
                                          on_trace=_count_trace))
        self.last_ring: MetricsRing | None = None
        # Step-time breakdown of the last run (the totals of the loop's
        # program spans, `self.phases` — host-side timers only, no device
        # syncs beyond the ones already there), goodput derived from it,
        # and the retrace sentinel.
        self.phases = _telemetry.Phases()
        self.last_breakdown: dict = {}
        self.last_step_totals: dict = {}
        self.last_goodput = 0.0
        self.name = _telemetry.next_name("train")
        self.sentinel = _telemetry.RetraceSentinel(self.name)
        if self.unroll > 1:
            self.sentinel.watch("dispatch",
                                lambda: self.dispatch_traces, cap=1,
                                registered=True)
        _telemetry.register_stats_source(self.name, self, kind="train")
        # Optional train/ft.AsyncCheckpointer (any object with
        # maybe_snapshot(state, step) + flush()). Mutable attribute so a
        # compiled loop can toggle checkpointing between runs without
        # rebuilding (and re-tracing) the fused dispatch.
        self.checkpointer = checkpointer
        # Optional weight publisher `publisher(state, step)` — the RL
        # flywheel's seam (rl.FlywheelLoop wires it to
        # InferenceEngine.update_params). Called at the same
        # donation-safety point as the checkpointer: after a dispatch
        # returns and BEFORE the next dispatch donates the state's
        # buffers, so a publisher that device-copies (update_params
        # does) never races the training step. Mutable for the same
        # reason as `checkpointer`.
        self.publisher = publisher

    def run(self, state, device_batches: Iterable,
            num_steps: int | None = None, *, start_step: int = 0):
        """Drive steps until `num_steps` TOTAL steps are reached (or the
        batch iterator ends). `device_batches` yields one pytree per
        DISPATCH: leaves [B, ...] for unroll=1, [unroll, B, ...]
        otherwise — exactly what `DevicePrefetcher(group=unroll)`
        produces. Returns (state, per-step host metrics list).

        start_step seeds the global step counter for elastic resume
        (ft.restore_resharded): the caller fast-forwards the host
        iterator past the first `start_step` batches and the loop picks
        up checkpoint cadence from there, so `num_steps` keeps meaning
        "train through step N" across kills and restarts."""
        ring = MetricsRing(self.metrics_interval, self.metrics_lag)
        self.last_ring = ring
        ckpt = self.checkpointer
        done = int(start_step)
        # Host-side step-time breakdown: one program span around each
        # host activity of the loop (`telemetry.Phases`: a profiler
        # annotation and a perf_counter total). These time where the
        # HOST thread waits (the overlap design's whole point is keeping
        # these small) and add no device syncs — the no-host-sync tests
        # monkeypatch `_device_get` and still see only the ring's lagged
        # fetches, which count in this run's totals too.
        phases = ring.phases = self.phases
        phases.clear()
        phase = phases.phase
        pc = time.perf_counter
        t_run = pc()
        it = iter(device_batches)
        while True:
            try:
                with phase("train/next_batch"):
                    batch = next(it)
            except StopIteration:
                break
            with phase("train/dispatch", step=done):
                state, metrics = self._dispatch(state, batch)
            with phase("train/metrics"):
                ring.push(metrics, count=self.unroll)
            done += self.unroll
            # Snapshot/publish BEFORE the next dispatch donates these
            # buffers: both hooks device-copy what they keep, which is
            # the donation-safety seam (ft.AsyncCheckpointer docstring;
            # engine.update_params copies into its own buffers).
            if ckpt is not None:
                with phase("train/checkpoint"):
                    ckpt.maybe_snapshot(state, done)
            if self.publisher is not None:
                with phase("train/publish"):
                    self.publisher(state, done)
            if self.unroll > 1:
                self.sentinel.check()
            if num_steps is not None and done >= num_steps:
                break
        if ckpt is not None:
            with phase("train/checkpoint"):
                ckpt.flush()
        with phase("train/metrics"):
            out = ring.drain()
        self.last_step_totals = step_totals(out)
        total_s = pc() - t_run
        steps_run = done - int(start_step)
        denom = max(total_s, 1e-12)
        prefetch_s = phases.seconds("train/next_batch")
        dispatch_s = phases.seconds("train/dispatch")
        metrics_s = phases.seconds("train/metrics")
        checkpoint_s = phases.seconds("train/checkpoint")
        publish_s = phases.seconds("train/publish")
        self.last_breakdown = {
            "steps": steps_run,
            "total_s": total_s,
            "prefetch_s": prefetch_s,
            "dispatch_s": dispatch_s,
            "metrics_s": metrics_s,
            "checkpoint_s": checkpoint_s,
            "publish_s": publish_s,
            "prefetch_share": prefetch_s / denom,
            "dispatch_share": dispatch_s / denom,
            "metrics_share": metrics_s / denom,
            "checkpoint_share": checkpoint_s / denom,
            "publish_share": publish_s / denom,
        }
        # Host goodput: fraction of wall time the host spends inside
        # device dispatch (i.e. not stalled on data, checkpoint or
        # metrics plumbing).
        self.last_goodput = dispatch_s / denom
        return state, out

    def stats(self) -> dict:
        """Telemetry-bridge stats dict (util.telemetry republishes these
        as train_* gauges at every /metrics scrape): the last run's
        step-time breakdown plus goodput, the fused-dispatch
        compile-once accounting, and under their own names the totals of
        what its steps carried beside the loss (`step_totals`)."""
        return {
            **self.last_step_totals,
            "dispatch_traces": self.dispatch_traces,
            "retraces_unexpected": self.sentinel.retraces_unexpected,
            "unroll": self.unroll,
            "goodput": self.last_goodput,
            **self.last_breakdown,
        }


def run_steps(step_fn: Callable, state, device_batches: Iterable,
              *, num_steps: int | None = None, unroll: int = 1,
              metrics_interval: int = 10, metrics_lag: int = 2):
    """One-shot convenience over `TrainLoop` (build + run). Prefer
    holding a `TrainLoop` when calling more than once — each `run_steps`
    call with unroll > 1 builds (and re-compiles) its own fused
    dispatch."""
    loop = TrainLoop(step_fn, unroll=unroll,
                     metrics_interval=metrics_interval,
                     metrics_lag=metrics_lag)
    return loop.run(state, device_batches, num_steps=num_steps)
