"""Continuous-batching autoregressive inference engine over a paged KV
cache.

The Podracer serving recipe (Hessel et al., 2104.06272): device shapes
are STATIC and the model stays resident. The engine owns one fixed block
pool, made by the model family it serves (`models.family.ServingFamily`,
found as `cfg.family`: a dict of arrays with the blocks on axis 1 of
each; for `models/gpt.py` ``{"k", "v"}`` of
``[L, n_blocks, block_size, H, Dh]``), and streams ragged traffic through
it via int32 block tables — the only thing that changes between steps is
*data*, never shapes. Prefill, decode, verify and the block moves named
below are that family's; `gpt.`'s names stand for them:

- **paged allocation**: each request holds exactly the blocks its
  prompt + generation footprint needs (a 100-token chat no longer pins a
  4k-token row). `BlockAllocator` refcounts physical blocks; block 0 is
  the trash block idle decode rows scatter into. A family may keep a
  state of fixed size a sequence instead of pages, or beside them
  (`ServingFamily.state_blocks`, `models/retention.py`,
  `models/linear_latent.py`): a request then holds that many state
  blocks whatever its length and, where the family is `paged`, its pages
  as well, under the same allocator, release, cancel and hand-off
  (`_written_blocks` is the one footprint arithmetic); the two points
  below do not apply to such a family.
- **radix prefix sharing**: a host-side `RadixTree` maps token prefixes
  to cached blocks at block granularity. A repeated system prompt is
  prefilled ONCE; later requests admit by taking references on the
  shared blocks and prefilling only their suffix. A prefix that ends
  mid-block is shared copy-on-write: the partial block is device-copied
  into a private block before the request writes into it. Zero-ref
  cached prefixes are evicted LRU under pool pressure.
- **chunked prefill**: admission no longer runs a whole prompt's
  prefill synchronously inside `step()`. Prompts prefill in fixed-size
  chunks (bucketized, one compile per chunk bucket) interleaved between
  decode steps — when any sequence is decoding, a tick runs at most ONE
  chunk, so a long admission never stalls in-flight streams for more
  than one chunk's worth of work. That chunk goes into the device's
  queue BEHIND the tick's decode step: the host builds it while the
  device decodes and emits the step's tokens while the device prefills,
  and reads nothing before both programs are in flight. The sequence
  whose prompt ends in such a chunk emits its first token in that tick
  and takes its first decode step in the next.
- **decode** advances ALL slots one token per call through a single
  jitted, pool-donating wrapper around `gpt.decode_step_paged` —
  compiled exactly once for the engine's lifetime (asserted in tests
  via the trace counter). Idle and mid-prefill rows decode garbage
  into the trash block; nobody reads it.
- **speculative decoding** (``spec='ngram' | 'draft'``): each tick
  proposes k tokens per slot — n-gram lookahead matches the request's
  recent suffix against its own prompt+output history (zero model
  cost), the draft backend runs a smaller GPT with its own paged pool
  through one jitted k-step scan — then ONE batched verify forward
  (`gpt.verify_step_paged`) scores the whole window and accepts/
  corrects in-jit (greedy exact; temperature via the standard
  rejection-sampling correction, exact for any proposal). Acceptance
  emits up to k+1 tokens per KV-pool read. No device rollback is
  needed on rejection: per-slot `pos` is authoritative, attention
  masks past it, and sequential future writes overwrite stale K/V
  before any read. Decode and verify each still compile exactly once
  (`decode_traces` / `verify_traces`).

- **RL flywheel hooks** (`ray_tpu.rl`): every emitted token is a
  `TokenEvent` — an ``int`` subclass carrying the target model's
  per-token log-probability and the ``params_version`` it was sampled
  under — and `update_params()` hot-swaps new weights into the live
  engine between ticks with NO recompile and NO restart: the new
  pytree (validated leaf-for-leaf against the old one) is copied
  in-place into the old params' donated device buffers, the radix
  prefix cache is flushed (its K/V was computed under the old
  weights), and the version tag bumps so learners can bound staleness
  and apply importance correction. Mid-flight sequences keep decoding
  over their already-written K/V — the standard in-place-sync
  tradeoff (MindSpeed RL, 2507.19017) — which the per-token version
  tags make visible to the learner.

- **priority classes + preemption** (multi-tenant serving): `submit`
  takes a class (``priority=``, 0 = lowest). Admission runs weighted
  shares across backlogged classes (stride scheduling, weight =
  base**class) with an aging escalation bound so low classes never
  starve; overload shedding is class-ordered (the lowest-class QUEUED
  request sheds first, typed `OverloadedError` delivered through its
  `tokens_for`); and when the block pool can't serve a higher class,
  the lowest-class ACTIVE stream is preempted — its written blocks are
  published to the radix tree, its blocks released, and the stream
  requeued as a chunked re-prefill of prompt+emitted with the SAME rid
  and output queue. A resumed greedy stream is token-identical to an
  unpreempted run (same KV ⇒ same continuation — the property the
  serve handle's `token_resume` failover already relies on), including
  across shared-prefix/COW admissions and both spec-decode backends.

Sampling (greedy + temperature) runs inside the jitted functions, as
before. `step()` is the one scheduler tick (admit, then decode step and
chunk overlapped where the tick holds both, else chunks, then the step);
`submit()` / `tokens_for()` / `cancel()` are the request-side API. A
consumer that stops iterating `tokens_for` releases its request's
blocks and queues automatically (generator finalization cancels it).

Three locks. The scheduler lock (`_lock`) is a tick's, for its whole
length. The delivery lock (`_delivery`, a condition) holds what crosses
between a tick and its consumers — rids, `submit`'s inbox, the output
queues — for a few dict operations at a time. The pump mutex (`_pump`)
names the one consumer that runs the next tick; the others sleep on the
condition (`_await`). Nothing waits for `_lock` to submit or to pop.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import functools
import logging
import os
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from ray_tpu.exceptions import OverloadedError
from ray_tpu.models.family import EMBED, HEAD
from ray_tpu.util import faults as _faults

logger = logging.getLogger("ray_tpu.serve")


class TokenEvent(int):
    """A generated token id that is also an ``int``, carrying the RL
    metadata the flywheel needs:

    - ``logprob``: the TARGET model's natural (temperature-1)
      log-likelihood of this token given its prefix,
      ``log_softmax(logits)[token]`` in f32 — i.e. log pi(a|s) for the
      learner, regardless of the sampling temperature or whether the
      token came off the plain decode, prefill, or speculative verify
      path. Matches a full-forward recompute to f32 tolerance.
    - ``params_version``: the engine's weight version
      (`InferenceEngine.update_params` bumps it) the token was computed
      under, so learners can bound staleness / importance-correct.

    Subclassing ``int`` keeps every existing consumer working unchanged
    (equality with plain ints, json/pickle, serve streaming)."""

    def __new__(cls, token: int, logprob: float = 0.0,
                params_version: int = 0):
        ev = super().__new__(cls, token)
        ev.logprob = float(logprob)
        ev.params_version = int(params_version)
        return ev

    def __reduce__(self):
        # int subclasses need an explicit recipe for the metadata to
        # survive pickling (object-store / serve transit).
        return (TokenEvent, (int(self), self.logprob,
                             self.params_version))

    def __repr__(self):
        return (f"TokenEvent({int(self)}, logprob={self.logprob:.4f}, "
                f"params_version={self.params_version})")


def _default_buckets(max_len: int) -> tuple[int, ...]:
    out, b = [], 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list allocator over the physical blocks of a
    paged KV pool. Block 0 is reserved as the engine's trash block
    (never handed out — idle decode rows scatter there), so a pool of
    ``n_blocks`` has ``n_blocks - 1`` usable blocks.

    One space of ids for the three kinds of block a family may keep
    (`models.family.ServingFamily`): ids ``1 .. n_state`` are state
    blocks, the next ``n_bounded`` ids bounded pages, the ids after them
    pages that grow, each kind with a free list of its own. A block's
    id less the ids of the kinds before it is its index on axis 1 of
    the pool's arrays of its kind (`index_of`).

    Invariants (asserted by `check()` and the fuzz tests): a block is
    either free with refcount 0 or allocated with refcount >= 1;
    used + free + free_state + free_bounded == n_blocks - 1, and a
    block freed returns to the list of its own kind; decref of a free
    block (double free) raises."""

    def __init__(self, n_blocks: int, n_state: int = 0, n_bounded: int = 0):
        if n_blocks < 2:
            raise ValueError("need at least one usable block")
        self.n_blocks, self.n_state = n_blocks, n_state
        self.n_bounded = n_bounded
        first_page = n_state + n_bounded
        self._free = list(range(n_blocks - 1, first_page, -1))  # pop() -> 1, 2…
        self._free_bounded = list(range(first_page, n_state, -1))
        self._free_state = list(range(n_state, 0, -1))
        self._ref = [0] * n_blocks

    @property
    def free(self) -> int:
        """Free pages."""
        return len(self._free)

    @property
    def free_state(self) -> int:
        return len(self._free_state)

    @property
    def free_bounded(self) -> int:
        return len(self._free_bounded)

    @property
    def used(self) -> int:
        return ((self.n_blocks - 1) - len(self._free)
                - len(self._free_state) - len(self._free_bounded))

    def kind_of(self, block: int) -> str:
        """"state", "bounded" or "page", by the block's id."""
        if block <= self.n_state:
            return "state"
        return ("bounded" if block <= self.n_state + self.n_bounded
                else "page")

    def index_of(self, block: int) -> int:
        """The block's index on axis 1 of the pool's arrays of its kind."""
        return block - {"state": 0, "bounded": self.n_state,
                        "page": self.n_state + self.n_bounded}[
                            self.kind_of(block)]

    def _free_list(self, kind: str) -> list:
        return {"state": self._free_state, "bounded": self._free_bounded,
                "page": self._free}[kind]

    def alloc(self, kind: str = "page") -> int:
        free = self._free_list(kind)
        if not free:
            raise RuntimeError("out of KV cache blocks")
        b = free.pop()
        self._ref[b] = 1
        return b

    def ref(self, block: int):
        if self._ref[block] <= 0:
            raise RuntimeError(f"ref of free block {block}")
        self._ref[block] += 1

    def decref(self, block: int):
        if block <= 0 or self._ref[block] <= 0:
            raise RuntimeError(f"double free of block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free_list(self.kind_of(block)).append(block)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def check(self):
        n_free = self.free + self.free_state + self.free_bounded
        assert self.used + n_free == self.n_blocks - 1
        free = (set(self._free) | set(self._free_state)
                | set(self._free_bounded))
        assert len(free) == n_free, "free-list duplicate"
        for kind in ("state", "bounded", "page"):
            assert all(self.kind_of(b) == kind
                       for b in self._free_list(kind)), \
                f"a block of another kind among the free {kind} blocks"
        for b in range(1, self.n_blocks):
            if b in free:
                assert self._ref[b] == 0, f"free block {b} has refs"
            else:
                assert self._ref[b] >= 1, f"used block {b} has no refs"


# ---------------------------------------------------------------------------
# radix tree over token prefixes
# ---------------------------------------------------------------------------

def _common(a, b) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class _RadixNode:
    __slots__ = ("key", "blocks", "children", "parent", "last_access")

    def __init__(self, key, blocks, parent):
        self.key = key              # tuple of tokens, len % bs == 0
        self.blocks = blocks        # physical block per key block
        self.children = {}          # first-block token tuple -> node
        self.parent = parent
        self.last_access = 0


class RadixTree:
    """Host-side radix tree mapping token prefixes to cached KV blocks.

    Keys are block-aligned (every edge covers whole blocks of
    ``block_size`` tokens); edges are path-compressed and split at block
    boundaries when sequences diverge inside them. Tree blocks are
    IMMUTABLE — only full prompt blocks are ever inserted, and decode
    never writes into a full block — so sharing needs no
    synchronization. A match may end mid-block; the caller then shares
    that block read-only and must copy-on-write before writing
    (`InferenceEngine._try_admit`).

    The tree holds one allocator reference per block it records;
    `evict()` walks zero-ref leaves (blocks only the tree still holds)
    in LRU order and releases them."""

    def __init__(self, block_size: int, allocator: BlockAllocator):
        self.bs = block_size
        self.alloc = allocator
        self.root = _RadixNode((), [], None)
        self._clock = 0

    # -- internals ----------------------------------------------------

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _best_child(self, node, rest):
        best, best_c = None, 0
        for child in node.children.values():
            c = _common(child.key, rest)
            if c > best_c:
                best, best_c = child, c
        return best, best_c

    def _split(self, node, fb: int):
        """Split `node`'s edge after `fb` blocks; returns the new upper
        node (which keeps the prefix blocks)."""
        parent = node.parent
        cut = fb * self.bs
        upper = _RadixNode(node.key[:cut], node.blocks[:fb], parent)
        upper.last_access = node.last_access
        del parent.children[node.key[:self.bs]]
        parent.children[upper.key[:self.bs]] = upper
        node.key = node.key[cut:]
        node.blocks = node.blocks[fb:]
        node.parent = upper
        upper.children[node.key[:self.bs]] = node
        return upper

    def _nodes(self):
        stack = [self.root]
        while stack:
            nd = stack.pop()
            yield nd
            stack.extend(nd.children.values())

    # -- public -------------------------------------------------------

    def match(self, tokens):
        """Longest cached prefix of `tokens`: returns
        ``(blocks, matched)`` where `blocks` covers
        ``ceil(matched / bs)`` physical blocks. When ``matched % bs``
        is nonzero the last block is only partially matched — the
        caller shares it read-only and must COW before writing."""
        toks = tuple(int(t) for t in tokens)
        node, blocks, matched = self.root, [], 0
        now = self._tick()
        while matched < len(toks):
            rest = toks[matched:]
            best, c = self._best_child(node, rest)
            if best is None or c == 0:
                break
            best.last_access = now
            if c == len(best.key) and c < len(rest):
                blocks += best.blocks
                matched += c
                node = best
                continue
            fb = c // self.bs
            blocks += best.blocks[:fb]
            if c % self.bs:
                blocks.append(best.blocks[fb])
            matched += c
            break
        return blocks, matched

    def insert(self, tokens, blocks):
        """Record `tokens` (truncated down to a block multiple) as a
        cached prefix backed by `blocks` (one physical id per logical
        block of `tokens`). Existing matches are walked (and split at a
        block boundary on divergence); only the unmatched tail is
        adopted, taking one tree reference per newly-held block."""
        n = (len(tokens) // self.bs) * self.bs
        toks = tuple(int(t) for t in tokens[:n])
        node, i = self.root, 0
        now = self._tick()
        while i < n:
            rest = toks[i:]
            best, c = self._best_child(node, rest)
            fb = c // self.bs if best is not None else 0
            if fb == 0:
                blks = list(blocks[i // self.bs: n // self.bs])
                child = _RadixNode(rest, blks, node)
                child.last_access = now
                node.children[rest[:self.bs]] = child
                for b in blks:
                    self.alloc.ref(b)
                return
            best.last_access = now
            if fb * self.bs < len(best.key):
                best = self._split(best, fb)
                best.last_access = now
            node = best
            i += fb * self.bs

    def evict(self, need: int) -> int:
        """Free zero-ref cached prefixes (blocks only the tree holds),
        LRU leaves first, until `need` blocks have been released or
        nothing more is evictable. Returns blocks freed."""
        freed = 0
        while freed < need:
            leaves = [nd for nd in self._nodes()
                      if nd is not self.root and not nd.children
                      and all(self.alloc.refcount(b) == 1
                              for b in nd.blocks)]
            if not leaves:
                break
            victim = min(leaves, key=lambda nd: nd.last_access)
            for b in victim.blocks:
                self.alloc.decref(b)
            freed += len(victim.blocks)
            del victim.parent.children[victim.key[:self.bs]]
        return freed

    def clear(self) -> int:
        """Drop every cached prefix (used by tests); returns blocks
        freed. Nodes whose blocks live requests still reference are
        kept."""
        return self.evict(self.n_blocks() or 1)

    def flush(self) -> int:
        """Drop the WHOLE tree unconditionally — every node, including
        ones whose blocks live requests still reference (the tree's own
        reference is released; the requests keep theirs, so their blocks
        stay alive until the slot retires). Used on weight hot-swap:
        cached prefix K/V was computed under the old params and must not
        be shared into post-swap admissions. Returns blocks whose LAST
        reference was the tree's (i.e. blocks actually freed)."""
        freed = 0
        for nd in self._nodes():
            if nd is self.root:
                continue
            for b in nd.blocks:
                self.alloc.decref(b)
                if self.alloc.refcount(b) == 0:
                    freed += 1
        self.root = _RadixNode((), [], None)
        return freed

    def n_blocks(self) -> int:
        return sum(len(nd.blocks) for nd in self._nodes())

    def n_nodes(self) -> int:
        return sum(1 for nd in self._nodes()) - 1   # minus root


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

# what `tokens_for`'s pop returns at the end of a stream (None means
# "nothing yet", and a token id can be 0)
_END = object()


@dataclass
class _Pending:
    rid: int
    prompt: np.ndarray            # [P] int32
    max_new_tokens: int
    temperature: float
    eos_id: int | None
    ts: float = 0.0               # submit time (queue-wait accounting)
    priority: int = 0             # class (0 = lowest); admission order,
    # shed order, and preemption eligibility all key off it
    resumed: bool = False         # a preempted stream's re-prefill:
    # prompt is the ORIGINAL prompt + every token already delivered, so
    # admission must not re-count TTFT/queue-wait for it
    aged: bool = False            # escalated past the weighted-share
    # order by the aging bound (counted once per request)


@dataclass
class _Slot:
    rid: int = -1
    phase: str = "idle"           # idle | prefill | decode
    prompt: np.ndarray | None = None
    filled: int = 0               # prompt tokens whose KV is resident
    blocks: list = field(default_factory=list)
    table: np.ndarray | None = None   # [max_blocks] int32 (0 = trash)
    order: int = 0                # admission sequence (chunk FIFO)
    token: int = 0                # token the next decode consumes
    token_logp: float = 0.0       # its logprob (parked through prefill)
    token_ver: int = 0            # params_version it was computed under
    pos: int = 0                  # its position in the logical sequence
    version: int = 0              # params_version at admission (a slot
    # admitted under old weights must not publish its prefix blocks to
    # the radix tree after a swap — its K/V would be stale)
    remaining: int = 0
    temperature: float = 0.0
    eos_id: int | None = None
    submit_ts: float = 0.0
    priority: int = 0
    resumed: bool = False
    # every token this stream has emitted, in order — preemption
    # requeues prompt+emitted as a re-prefill, which (greedy) resumes
    # token-identical: the KV it recomputes is exactly the KV released
    emitted: list = field(default_factory=list)
    # speculative decoding state: the request's token history (prompt +
    # emitted, n-gram lookahead's corpus) and, for the draft-model
    # backend, this slot's blocks/table in the DRAFT pool.
    history: list = field(default_factory=list)
    draft_blocks: list = field(default_factory=list)
    draft_table: np.ndarray | None = None
    draft_filled: int = 0

    @property
    def active(self) -> bool:
        return self.phase != "idle"


@dataclass
class _ChunkInFlight:
    """A prompt chunk between `_start_chunk` and `_finish_chunk`."""
    tok: object                   # device scalar; an int once read
    lp: object
    counts: object
    tokens: int
    bucket: int
    span: object                  # its `engine/prefill_chunk`
    overlapped: bool              # enqueued behind a decode step
    enqueued: float = 0.0         # perf_counter at the span's end


@dataclass
class _PromptEnded:
    """The chunk that ended a prompt inside a step's program
    (`_enqueue_fused`): its first token comes with the step's."""
    slot: int
    rid: int
    tok: object                   # device scalars, as a chunk's are: the
    lp: object                    # next program's `chunk_tok` operand


@dataclass
class _StepInFlight:
    """A decode step between its enqueue and the read of its tokens."""
    nxt: object                   # device int32[slots]: the next
    # step's operand where it is chained behind this one
    lps: object
    counts: object
    rows: dict                    # slot -> rid, the rows it decodes
    chained: int                  # of them, marked FROM_STEP / FROM_CHUNK
    version: int                  # params_version it was computed under
    dispatch_s: float             # its `engine/decode_dispatch`
    ended: _PromptEnded | None = None   # a fused chunk's prompt


# --- a program's host-built input: one int32 array, one transfer --------
#
# What the host builds for a device program of the tick crosses to the
# device as ONE flat int32 array: a transfer's price is fixed, not by
# the byte, and each one is a point where the pump's thread gives the
# interpreter lock up. A temperature rides as its own bits
# (`float32.view(int32)`), so what the program reads back is the host's
# float32 to the bit. The layouts follow from shapes the engine fixes at
# construction (slots, max_blocks, the verify window, the chunk bucket).

# A decode row's token field says where the row's token comes from: the
# value itself (a row that joins from the host), or one of two marks for
# a token that has not left the device: the row of the step before, or
# the token of the prompt chunk enqueued before the step.
FROM_STEP, FROM_CHUNK = -1, -2


def rows_size(slots: int, blocks: int, w: int = 1) -> int:
    """Length of `pack_rows`' array: a row a slot of `w` tokens, the
    position, the temperature and a table of `blocks`, then the step
    counter."""
    return slots * (w + 2 + blocks) + 1


def pack_rows(tokens, pos, temps, tables, step) -> np.ndarray:
    """A decode, verify or propose step's input: a row a slot of
    ``[tokens (1 or W) | pos | temperature bits | block table]``, the
    rows flattened, then the step counter `_sample` folds into the key.
    `tokens` is ``[S]`` (decode, propose) or ``[S, W]`` (verify); a
    decode row's may be `FROM_STEP` or `FROM_CHUNK`."""
    slots = len(pos)
    tokens = tokens.reshape(slots, -1)
    w = tokens.shape[1]
    packed = np.empty(rows_size(slots, tables.shape[1], w), np.int32)
    rows = packed[:-1].reshape(slots, -1)
    rows[:, :w] = tokens
    rows[:, w] = pos
    rows[:, w + 1] = temps.view(np.int32)    # float32 [S], to the bit
    rows[:, w + 2:] = tables
    packed[-1] = step
    return packed


def unpack_rows(packed, slots: int, window: int | None = None):
    """`pack_rows` undone inside a jitted program: ``(tokens, pos,
    temps, tables, step)``; `tokens` is ``[S]`` without `window`, else
    ``[S, window]``."""
    from jax import lax
    w = 1 if window is None else window
    rows = packed[:-1].reshape(slots, -1)
    tokens = rows[:, 0] if window is None else rows[:, :w]
    temps = lax.bitcast_convert_type(rows[:, w + 1], np.float32)
    return tokens, rows[:, w], temps, rows[:, w + 2:], packed[-1]


def chunk_size(cap: int, blocks: int) -> int:
    """Length of `pack_chunk`'s array for the bucket `cap` and a table of
    `blocks`."""
    return cap + blocks + 4


def pack_chunk(tokens, cap: int, table, start, temp, step) -> np.ndarray:
    """A prompt chunk's input: ``[tokens, zero-padded to the bucket
    `cap` | block table | start | length | temperature bits | step
    counter]``; `length` is `tokens`' own. One layout a chunk bucket, so
    the program compiles once a bucket."""
    length, blocks = len(tokens), len(table)
    packed = np.zeros(chunk_size(cap, blocks), np.int32)
    packed[:length] = tokens
    packed[cap:cap + blocks] = table
    packed[cap + blocks:] = (start, length,
                             np.float32(temp).view(np.int32), step)
    return packed


def unpack_chunk(packed, max_blocks: int):
    """`pack_chunk` undone inside a jitted program: ``(tokens [1, cap],
    table, start, length, temp, step)``."""
    from jax import lax
    cap = packed.shape[0] - max_blocks - 4
    start, length, temp, step = (packed[cap + max_blocks + i]
                                 for i in range(4))
    return (packed[None, :cap], packed[cap:cap + max_blocks], start,
            length, lax.bitcast_convert_type(temp, np.float32), step)


def _compile_ahead(jitted, *args):
    """`jitted` for a program that no request alone makes run, so that no
    warm-up reaches it: traced, lowered and compiled for the shapes and
    dtypes of `args` (and the shardings of those committed to one; an
    array that is not is described without, as `jitted` called with it
    would see it) in a thread started here, under the matmul precision,
    default device and mesh of the thread that asks, which a thread of
    its own would not inherit. -> the program as a callable: called with
    arrays like `args` it waits for what is left of the compile, as a
    jitted function's first call waits for its own, and runs; other
    shapes raise where `jitted` would trace again. Its `compiled` is the
    future of the executable. A compile that fails is logged when it does
    and raised by every call. The thread is not a daemon (a process that
    ends while XLA compiles waits for it) and ends with its one
    compile."""
    import jax
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if getattr(a, "committed", False) else None),
        args)
    precision = jax.config.jax_default_matmul_precision
    device = jax.config.jax_default_device
    mesh = jax.sharding.get_mesh()
    compiled = concurrent.futures.Future()

    def work():
        try:
            with jax.default_matmul_precision(precision), \
                    jax.default_device(device), jax.set_mesh(mesh):
                compiled.set_result(jitted.lower(*specs).compile())
        except BaseException as e:
            logger.exception("compiling %s ahead of its first use failed",
                             getattr(jitted, "__name__", jitted))
            compiled.set_exception(e)

    def program(*arrays):
        return compiled.result()(*arrays)

    program.compiled = compiled
    threading.Thread(target=work, name="engine-compile-ahead").start()
    return program


class InferenceEngine:
    """Slot-based continuous-batching scheduler over one model with a
    paged, prefix-shared cache.

    params/cfg are a model family's pytree and config (`cfg.family` is
    its `models.family.ServingFamily`); `slots` is the
    resident decode batch, `max_len` the per-sequence logical capacity
    (prompt + generated). `block_size` sets the paging granule and
    `cache_blocks` the pool's usable pages (default: enough for every
    slot at full length — shrink it to trade HBM for prefix-cache
    churn); a family that also keeps state blocks gets one set of them
    a slot besides, and one that keeps nothing else has `cache_blocks`
    count those. `prefill_chunk` caps prompt tokens absorbed per scheduler
    tick while anything is decoding; `prefix_cache=False` disables the
    radix tree. All device work happens in `step()`."""

    def __init__(self, params, cfg, *, slots: int = 4,
                 max_len: int | None = None,
                 prefill_buckets: tuple[int, ...] | None = None,
                 block_size: int = 16,
                 cache_blocks: int | None = None,
                 bounded_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 prefix_cache: bool = True,
                 spec: str | None = None, spec_k: int = 4,
                 ngram_max: int = 3, ngram_min: int = 1,
                 draft_params=None, draft_cfg=None,
                 draft_cache_blocks: int | None = None,
                 mesh=None, seed: int = 0,
                 telemetry_sample: float | None = None,
                 max_queue: int | None = None,
                 shed_high_water: float | None = None,
                 watchdog_s: float | None = None,
                 priority_classes: int | None = None,
                 priority_aging_s: float | None = None,
                 priority_weight_base: float | None = None,
                 role: str = "colocated"):
        import jax
        import jax.numpy as jnp
        self._jax = jax
        self.cfg = cfg
        # Everything the engine asks of the model goes through its
        # family (`models.family.ServingFamily`): the pool, prefill, decode,
        # the block moves, and optionally verify and load.
        fam = self._family = cfg.family
        # Disaggregated serving role. "prefill": this engine runs
        # chunked prefill only — a completed prompt's KV blocks are
        # gathered to host and parked as a handoff blob for a decode
        # engine to import; nothing ever enters the decode phase here.
        # "decode": behaviorally a colocated engine (it can still serve
        # whole requests) that additionally advertises itself as an
        # import target — the role tag drives serve routing, per-role
        # autoscaling signals, and per-role telemetry. "colocated"
        # (default): the classic single-engine path. import_handoff is
        # available on any non-prefill engine.
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        self.params = params
        self.mesh = mesh
        self.num_slots = slots
        self.max_len = cfg.max_seq_len if max_len is None else max_len
        self.block_size = block_size
        # What a request holds (`ServingFamily`): `state_blocks` blocks
        # of fixed size, a sequence's state, and where the family is
        # `paged` a page a `block_size` tokens. A slot never needs more
        # state blocks than its own, so there are slots x state_blocks
        # of them; `cache_blocks` counts the pages (for a family without
        # pages the state blocks, its only kind).
        self._state_blocks = fam.state_blocks
        if (self._state_blocks or fam.bounded_tokens) and prefix_cache:
            raise ValueError(
                f"prefix_cache=True: a request of {type(cfg).__name__}'s "
                "family holds a sequence's state, rewritten by every "
                "token, or pages that are written again once a window has "
                "left them; neither names a lasting range of tokens to "
                "share as a prefix; pass prefix_cache=False")
        max_pages = -(-self.max_len // block_size) if fam.paged else 0
        n_state = slots * self._state_blocks
        if not fam.paged:
            n_state, n_pages = (n_state if cache_blocks is None
                                else cache_blocks), 0
        else:
            n_pages = (slots * max_pages if cache_blocks is None
                       else cache_blocks)
        self.buckets = tuple(sorted(
            b for b in (prefill_buckets or _default_buckets(self.max_len))
            if b <= self.max_len))
        if not self.buckets:
            raise ValueError("no prefill bucket <= max_len")
        self.prefill_chunk = (min(64, self.buckets[-1])
                              if prefill_chunk is None else prefill_chunk)
        # Chunk capacities: the existing buckets up to the budget, plus
        # the budget itself — one prefill compile per capacity, ever.
        self.chunk_buckets = tuple(sorted(
            {b for b in self.buckets if b < self.prefill_chunk}
            | {self.prefill_chunk}))
        # Bounded pages (`ServingFamily.bounded_tokens`, a window): a
        # request holds them as a ring of at most `_ring` pages, what the
        # window and the longest chunk can have live at once (a chunk at
        # `n` writes up to page `(n + C - 1) // bs` while its first query
        # still reads page `(n - window + 1) // bs`). Logical page `j`
        # lies in ring page `j % _ring`; the table spells that out a
        # column a logical page, so the family never sees the ring.
        if fam.bounded_tokens:
            if not fam.paged:
                raise ValueError("bounded pages go beside pages that grow")
            self._ring = min(max_pages, -(-(
                fam.bounded_tokens + self.prefill_chunk - 2) // block_size)
                + 1)
            n_bounded = (slots * self._ring if bounded_blocks is None
                         else bounded_blocks)
            if n_bounded < self._ring:
                raise ValueError(
                    f"bounded_blocks {n_bounded} holds no request's ring "
                    f"of {self._ring} pages")
        else:
            self._ring = n_bounded = 0
        self.bounded_blocks = n_bounded
        self.max_blocks = self._state_blocks + max_pages * (
            2 if self._ring else 1)
        self.cache_blocks = n_state + n_bounded + n_pages
        # the largest footprint that can ever be admitted: a request's
        # own state blocks, every page, and a ring
        self._max_footprint = (self._state_blocks + n_pages
                               + min(n_pages, self._ring))
        # +1: physical block 0 is the trash block (idle rows write there).
        self.cache = fam.init_pool(
            cfg, n_pages + 1, block_size, mesh,
            **({"state_blocks": n_state + 1} if n_state else {}),
            **({"bounded_blocks": n_bounded + 1} if n_bounded else {}))
        self._alloc = BlockAllocator(self.cache_blocks + 1, n_state,
                                     n_bounded)
        self._tree = (RadixTree(block_size, self._alloc)
                      if prefix_cache else None)
        self._base_key = jax.random.PRNGKey(seed)

        # --- speculative decoding setup -------------------------------
        if spec not in (None, "ngram", "draft"):
            raise ValueError(f"unknown spec backend {spec!r}")
        if spec is not None and spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if spec is not None and fam.verify is None:
            raise ValueError(
                f"spec={spec!r}: {type(cfg).__name__}'s family has no "
                "verify step, so it cannot be served with speculative "
                "decoding")
        self.spec = spec
        self.spec_k = int(spec_k)
        # Verify window: [current token, k speculated tokens].
        self.spec_window = self.spec_k + 1
        self.ngram_max, self.ngram_min = int(ngram_max), int(ngram_min)
        self.draft_cfg, self.draft_params = draft_cfg, draft_params
        if spec == "draft":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "spec='draft' needs draft_params and draft_cfg")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft model must share the tokenizer")
            self.draft_cache_blocks = (
                self.cache_blocks if draft_cache_blocks is None
                else draft_cache_blocks)
            self.draft_cache = draft_cfg.family.init_pool(
                draft_cfg, self.draft_cache_blocks + 1, block_size, mesh)
            self._draft_alloc = BlockAllocator(self.draft_cache_blocks + 1)
        else:
            self.draft_cache_blocks = 0
            self.draft_cache = None
            self._draft_alloc = None
        if mesh is not None:
            from ray_tpu.parallel.sharding import engine_io_shardings
            self._io_sh = engine_io_shardings(mesh)["inputs"]
        else:
            self._io_sh = None

        # Compile-once accounting: the counters increment inside the
        # traced python functions, i.e. once per (re)trace. Tests pin
        # decode_traces == 1 (and verify_traces == 1 under speculation)
        # across a whole multi-request run.
        self.prefill_traces = 0
        self.decode_traces = 0
        self.tick_traces = 0
        self.verify_traces = 0
        self.draft_traces = 0
        self.draft_prefill_traces = 0
        self.load_traces = 0

        # --- load time: masters in, the tree the steps read out -------
        # A family's `load` (`models.family.ServingFamily`) casts, and
        # for cfg.weight_dtype="int8" quantizes, the published masters
        # once, so that no compiled step converts a weight. It runs
        # here and, the same jitted fn, on every update_params, so
        # trainers keep publishing f32 masters and the cast rides the
        # swap (zero decode/verify retraces — the tree the compiled
        # paths close over keeps its shapes and dtypes). One trace per
        # distinct tree: target and draft each at most once, ever. The
        # engine keeps no reference to the masters.
        def _loader(cfg_, tree):
            """`cfg_.family.load` jitted and counted, or None where
            the family has none or it would hand `tree` back leaf for
            leaf (told from the shapes and dtypes `eval_shape` gives
            it): the engine then holds the caller's buffers and runs
            nothing, where a `jit` would copy the whole tree."""
            if cfg_.family.load is None:
                return None
            load = functools.partial(cfg_.family.load, cfg=cfg_)

            def signature(t):
                return jax.tree.map(lambda a: (a.shape, a.dtype), t)

            if signature(jax.eval_shape(load, tree)) == signature(tree):
                return None

            def _load(p):
                self.load_traces += 1
                return load(p)

            return jax.jit(_load)

        self._load_target = _loader(cfg, params)
        self._load_draft = (_loader(draft_cfg, draft_params)
                            if spec == "draft" else None)
        # a family's `load` reads leaves by name, so a swap holds the
        # published tree to the structure of what was given here
        self._given = (jax.tree.structure(params),
                       jax.tree.structure(draft_params))
        if self._load_target is not None:
            self.params = self._load_target(self.params)
        if self._load_draft is not None:
            self.draft_params = self._load_draft(self.draft_params)

        # Capacity gauges: total device bytes of the block pool(s) and
        # the bytes one cached position costs — the lever kv_dtype
        # pulls (`stats()` gives both: `pool_bytes`, `kv_bytes_per_token`).
        self._pool_bytes = sum(
            int(arr.nbytes) for arr in self.cache.values())
        if self.draft_cache is not None:
            self._pool_bytes += sum(
                int(arr.nbytes) for arr in self.draft_cache.values())
        # the tree(s) the steps read; a swap keeps shapes and dtypes
        self._weight_bytes = sum(
            int(leaf.nbytes) for leaf in jax.tree.leaves(
                (self.params, self.draft_params if spec == "draft"
                 else None)))
        # a page's bytes over its tokens, and a sequence's state blocks'
        # over a sequence of up to max_len
        self._kv_bytes_per_token = sum(
            int(arr.nbytes) / arr.shape[1]
            * (self._state_blocks / self.max_len
               if key in fam.state_keys else 1 / block_size)
            for key, arr in self.cache.items())

        def _sample(logits, temps, key, step):
            """Sample one token per row; also return the model's NATURAL
            (temperature-1) f32 log-likelihood of the sampled token —
            the per-token logprob the RL flywheel trains against."""
            with jax.named_scope(HEAD):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                k = jax.random.fold_in(key, step)
                safe = jnp.where(temps > 0, temps, 1.0)
                sampled = jax.random.categorical(
                    k, logits.astype(jnp.float32) / safe[:, None]
                ).astype(jnp.int32)
                tok = jnp.where(temps > 0, sampled, greedy)
                nat = jax.nn.log_softmax(logits.astype(jnp.float32),
                                         axis=-1)
                logp = jnp.take_along_axis(nat, tok[:, None],
                                           axis=-1)[:, 0]
                return tok, logp

        max_blocks = self.max_blocks

        def _prefill(params, inputs, cache, key):
            self.prefill_traces += 1
            with jax.named_scope(EMBED):
                tokens, table, start, length, temp, step = unpack_chunk(
                    inputs, max_blocks)
            logits, cache, counts = fam.prefill(
                params, tokens, cache, cfg, mesh, block_table=table,
                start=start, length=length)
            tok, logp = _sample(logits, temp[None], key, step)
            return tok[0], logp[0], cache, counts

        def _decode(params, cache, inputs, key, prev, chunk_tok):
            """`prev` is the step before's `tok` and `chunk_tok` the
            `tok` of the chunk enqueued before this step, both as they
            lie on the device; a row marked `FROM_STEP` / `FROM_CHUNK`
            takes its token there, any other the packed value."""
            self.decode_traces += 1
            with jax.named_scope(EMBED):
                tokens, pos, temps, tables, step = unpack_rows(inputs,
                                                               slots)
                tokens = jnp.where(
                    tokens == FROM_STEP, prev,
                    jnp.where(tokens == FROM_CHUNK, chunk_tok, tokens))
            logits, cache, counts = fam.decode(
                params, tokens, cache, pos, tables, cfg, mesh)
            tok, logp = _sample(logits, temps, key, step)
            return tok, logp, cache, counts

        rows_len = rows_size(slots, max_blocks)

        def _tick(params, cache, inputs, key, prev, chunk_tok):
            """`_decode` and `_prefill` as one program (`fam.tick`), for
            a tick's step and its chunk of another sequence: `inputs` is
            the step's packed rows, then the chunk's. Each samples as in
            its own program: the step's rows, resolved as `_decode`
            resolves them (the tick before may have ended a prompt in
            this program too), and the chunk's last live row, whose
            token is read where the chunk ends its prompt."""
            self.tick_traces += 1
            with jax.named_scope(EMBED):
                tokens, pos, temps, tables, step = unpack_rows(
                    inputs[:rows_len], slots)
                tokens = jnp.where(
                    tokens == FROM_STEP, prev,
                    jnp.where(tokens == FROM_CHUNK, chunk_tok, tokens))
                chunk_tokens, table, start, length, temp, chunk_step = \
                    unpack_chunk(inputs[rows_len:], max_blocks)
            last, logits, cache, counts = fam.tick(
                params, chunk_tokens, tokens, cache, pos, tables, cfg, mesh,
                block_table=table, start=start, length=length)
            tok, logp = _sample(logits, temps, key, step)
            ended, ended_lp = _sample(last, temp[None], key, chunk_step)
            return tok, logp, cache, counts, ended[0], ended_lp[0]

        def _verify(params, cache, inputs, key):
            """One batched W-token forward + in-jit accept/correct.

            `tokens[:, 0]` is each slot's current token, `tokens[:, 1:]`
            its k speculated continuations. Returns ``(out [B, W],
            accepted [B], cache)`` where `out[:, :accepted + 1]` are the
            tokens to emit: the accepted drafts followed by one bonus
            (all accepted) or corrected (first rejection) target token.
            Rejected positions need NO device rollback — `pos` is
            authoritative, attention masks past it, and sequential
            future writes overwrite the stale K/V before any read.
            """
            self.verify_traces += 1
            with jax.named_scope(EMBED):
                tokens, pos, temps, tables, step = unpack_rows(
                    inputs, slots, self.spec_window)
            logits, cache = fam.verify(
                params, tokens, cache, pos, tables, cfg, mesh)
            with jax.named_scope(HEAD):
                b, w = tokens.shape
                drafts = tokens[:, 1:]                       # [B, W-1]
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                k = jax.random.fold_in(key, step)
                safe = jnp.where(temps > 0, temps, 1.0)
                logp = jax.nn.log_softmax(
                    logits / safe[:, None, None], axis=-1)   # [B, W, V]
                # Accept draft j iff it matches greedy (temp 0) or w.p.
                # p_target(draft) (rejection sampling with the draft as a
                # point-mass proposal — exact for ANY proposal, so padded /
                # garbage drafts stay distribution-correct).
                p_draft = jnp.exp(jnp.take_along_axis(
                    logp[:, :-1], drafts[..., None], axis=-1)[..., 0])
                u = jax.random.uniform(jax.random.fold_in(k, 1),
                                       drafts.shape)
                match = jnp.where((temps > 0)[:, None], u < p_draft,
                                  drafts == greedy[:, :-1])
                acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
                accepted = jnp.sum(acc, axis=1)              # [B] in [0,W-1]
                # Residual for the first rejected position: target dist with
                # the rejected draft masked out. Col W-1 (the bonus token
                # when everything is accepted) is sampled unmasked.
                res = logp.at[jnp.arange(b)[:, None],
                              jnp.arange(w - 1)[None, :], drafts].set(-1e30)
                corr = jax.random.categorical(
                    jax.random.fold_in(k, 2), res, axis=-1).astype(jnp.int32)
                corr = jnp.where((temps > 0)[:, None], corr, greedy)
                drafts_pad = jnp.concatenate(
                    [drafts, jnp.zeros_like(drafts[:, :1])], axis=1)
                cols = jnp.arange(w)[None, :]
                out = jnp.where(cols < accepted[:, None], drafts_pad, corr)
                # Natural (temperature-1) logprob of each emitted token:
                # logits[:, j] is the next-token distribution after the
                # prefix extended by out[:, :j], so column j's emitted token
                # scores against column j's untempered log-softmax — same
                # contract as the plain decode path.
                nat = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                out_lp = jnp.take_along_axis(
                    nat, out[..., None], axis=-1)[..., 0]
                return out, out_lp, accepted, cache

        # Cache donation: the [L, n_blocks, bs, H, D] pool is by far the
        # engine's biggest array; donating it lets XLA alias input to
        # output so every step updates the pool in place in HBM.
        # Every `tok` (a fused program's two) is laid out as the packed
        # input is, so a step takes the one compile whether its `prev` /
        # `chunk_tok` came from a program or are the placeholders below.
        tok_first = (self._io_sh, None, None, None)
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(2,),
                                   out_shardings=tok_first)
        self._decode_fn = jax.jit(_decode, donate_argnums=(1,),
                                  out_shardings=tok_first)
        # what a step that chains behind nothing takes for them (unread)
        self._no_prev = jax.device_put(np.zeros(slots, np.int32),
                                       self._io_sh)
        self._no_chunk_tok = jax.device_put(np.int32(0), self._io_sh)
        # A family that can read a weight once for a step and a chunk:
        # the one program that no request alone makes run (it takes
        # decoders and another request's chunk in one tick), so a replica
        # that warms up a request at a time would compile it under its
        # first load, every stream waiting. It is compiled from here on
        # (`_compile_ahead`), for the one shape it has (the full bucket;
        # `start` and `length` are data), and called as the others are.
        # A `role="prefill"` engine never decodes and has no use for it.
        self._tick_fn = None
        if fam.tick is not None and spec is None and role != "prefill":
            tick_jit = jax.jit(_tick, donate_argnums=(1,),
                               out_shardings=(*tok_first, self._io_sh, None))
            self._tick_fn = _compile_ahead(
                tick_jit, self.params, self.cache, jax.device_put(
                    np.zeros(rows_len + chunk_size(self.prefill_chunk,
                                                   max_blocks), np.int32),
                    self._io_sh),
                self._base_key, self._no_prev, self._no_chunk_tok)
        self._copy_fn = jax.jit(fam.copy_block, donate_argnums=(0,))
        self._verify_fn = (jax.jit(_verify, donate_argnums=(1,))
                           if spec is not None else None)

        # Disaggregation transport jits: gather one block's KV (payload
        # plus any int8 scale rows) into standalone device arrays for
        # host export, and scatter one transferred block back into a
        # pool. The block index is traced, so each compiles once per
        # pool geometry — target and draft pools differ in shape, hence
        # at most two traces each (sentinel-capped below).
        self.kv_gather_traces = 0
        self.kv_scatter_traces = 0

        def _gather(cache, idx):
            self.kv_gather_traces += 1
            return fam.gather_block(cache, idx)

        def _scatter_blk(cache, block, idx):
            self.kv_scatter_traces += 1
            return fam.scatter_block(cache, block, idx)

        self._gather_fn = jax.jit(_gather)
        self._scatter_block_fn = jax.jit(_scatter_blk,
                                         donate_argnums=(0,))

        if spec == "draft":
            W = self.spec_window

            def _propose(dparams, dcache, inputs, key):
                """W draft decode steps as one jitted scan: consume
                c_0..c_{W-1}, write their K/V at pos..pos+W-1, sample
                c_1..c_W; the first W-1 samples are the proposal (the
                last scan step exists only to write d_{k}'s K/V so the
                draft cache stays lockstep with the target's)."""
                self.draft_traces += 1
                tokens, pos, temps, tables, step = unpack_rows(
                    inputs, slots)
                k = jax.random.fold_in(jax.random.fold_in(key, step), 3)

                def body(carry, i):
                    tok, cache = carry
                    logits, cache, _ = draft_cfg.family.decode(
                        dparams, tok, cache, pos + i, tables,
                        draft_cfg, mesh)
                    nxt, _ = _sample(logits, temps, k, i)
                    return (nxt, cache), nxt

                (_, dcache), outs = jax.lax.scan(
                    body, (tokens, dcache),
                    jnp.arange(W, dtype=jnp.int32))
                return outs[:-1].T, dcache               # [B, W-1]

            def _draft_prefill(dparams, inputs, dcache):
                self.draft_prefill_traces += 1
                tokens, table, start, length, _, _ = unpack_chunk(
                    inputs, max_blocks)
                _, dcache, _ = draft_cfg.family.prefill(
                    dparams, tokens, dcache, draft_cfg, mesh,
                    block_table=table, start=start, length=length)
                return dcache

            self._propose_fn = jax.jit(_propose, donate_argnums=(1,))
            self._draft_prefill_fn = jax.jit(_draft_prefill,
                                             donate_argnums=(2,))
        else:
            self._propose_fn = None
            self._draft_prefill_fn = None

        self._slots = [_Slot() for _ in range(slots)]
        self._pending: collections.deque[_Pending] = collections.deque()
        self._admit_seq = 0
        # The scheduler lock: slots, `_pending`, the allocator, the
        # tree, the counters. A tick holds it for its whole length, the
        # device round trip included.
        self._lock = threading.RLock()
        # The delivery lock, and the condition consumers sleep on: rids,
        # the inbox, and what a tick hands to its streams (`_out`,
        # `_done`, `_errors`, `_handoffs`). Held for a few dict and
        # deque operations, never across device work; taken after
        # `_lock` where both are held, never before it.
        self._delivery_lock = threading.RLock()
        self._delivery = threading.Condition(self._delivery_lock)
        # Consumers asleep in `_await`, by the rid they wait for, each
        # on a condition of its own over the delivery lock: woken when
        # something is handed to that rid (`_wake`) or to take the pump
        # over (`_wake_one`), so a tick wakes the streams it served and
        # not every stream that waits for a slot.
        self._sleepers: dict[int, threading.Condition] = {}
        self._rid = 0
        # submitted, not yet seen by a tick: `_take_inbox` (under
        # `_lock`) moves it into `_pending`
        self._inbox: collections.deque[_Pending] = collections.deque()
        # rid -> deque of emitted token ids; rid dropped when done AND
        # drained (tokens_for pops, then deletes) or cancelled.
        self._out: dict[int, collections.deque] = {}
        self._done: set[int] = set()
        # rid -> exception for requests terminated while QUEUED (class-
        # ordered shedding): tokens_for raises it to the consumer.
        self._errors: dict[int, Exception] = {}
        # Held by the one consumer that runs the next tick (`_await`);
        # only ever tried, never waited for.
        self._pump = threading.Lock()
        # threads asking for `_lock` that run no tick (`_before_pump`)
        self._lock_waiters = 0

        # --- disaggregated prefill/decode handoff state ---------------
        # Export side (role="prefill"): rid -> host-side KV blob parked
        # when the prompt's prefill completes, until the serve layer (or
        # a test) collects it via handoff_for/take_handoff. Import side
        # (any non-prefill role): FIFO of (rid, blob) waiting for a free
        # slot; `_import_rids` mirrors it for O(1) membership.
        self._handoffs: dict[int, dict] = {}
        self._imports: collections.deque = collections.deque()
        self._import_rids: set[int] = set()
        self._handoffs_exported = 0
        self._imports_completed = 0
        self._handoffs_abandoned = 0
        self._kv_blocks_exported = 0
        self._kv_blocks_imported = 0
        self._kv_export_bytes = 0
        self._kv_import_bytes = 0
        self._kv_export_ms = collections.deque(maxlen=256)
        self._kv_import_ms = collections.deque(maxlen=256)

        # --- priority classes (multi-tenant admission) ----------------
        from ray_tpu._private.constants import (
            ENGINE_PRIORITY_AGING_S, ENGINE_PRIORITY_CLASSES,
            ENGINE_PRIORITY_WEIGHT_BASE)
        self.priority_classes = (ENGINE_PRIORITY_CLASSES
                                 if priority_classes is None
                                 else int(priority_classes))
        if self.priority_classes < 1:
            raise ValueError("priority_classes must be >= 1")
        self.priority_aging_s = (ENGINE_PRIORITY_AGING_S
                                 if priority_aging_s is None
                                 else float(priority_aging_s))
        if self.priority_aging_s <= 0:
            raise ValueError("priority_aging_s must be > 0")
        self.priority_weight_base = (ENGINE_PRIORITY_WEIGHT_BASE
                                     if priority_weight_base is None
                                     else float(priority_weight_base))
        if self.priority_weight_base < 1.0:
            raise ValueError("priority_weight_base must be >= 1")
        # stride-scheduler pass value per backlogged class; shares the
        # scheduler lock (the admission queue has no lock of its own —
        # R004: no new lock-order edge)
        self._class_pass: dict[int, float] = {}
        # per-class counters/waits (lazily created per class seen)
        self._per_class: dict[int, dict] = {}
        self._class_waits: dict[int, collections.deque] = {}
        self._preemptions = 0
        self._reprefill_blocks = 0
        self._aging_promotions = 0
        # Serializes weight hot-swaps; exists so the blocking
        # host->device upload in _place_tree happens OUTSIDE _lock.
        self._swap_mutex = threading.Lock()
        self._decode_steps = 0
        # The decode step whose tokens no one has read yet: the plain
        # tick leaves its newest step here and the next tick enqueues a
        # step behind it before reading it (`_chain_tick`); `_rest`
        # reads it for whoever needs the engine at rest.
        self._flight: _StepInFlight | None = None
        self._steps_chained = 0
        self._chain_drains = 0
        # host-to-device transfers made for the programs' inputs
        self._host_puts = 0
        # what the family's programs count (`ServingFamily.counts`),
        # summed over the window; None until a program returns some
        self._model_counts = None
        self._step_times = collections.deque(maxlen=512)
        self._occupancy = collections.deque(maxlen=512)
        self._block_util = collections.deque(maxlen=512)
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._prefill_chunks = 0
        # chunks enqueued behind a decode step of their tick, and their
        # time from the end of `engine/prefill_chunk` to the token's read
        self._chunks_overlapped = 0
        self._chunk_tail_s = 0.0
        # ticks whose chunk and step were one program (`_tick_fn`), and
        # those of them whose chunk ended its prompt
        self._ticks_fused = self._ticks_fused_last = 0
        self._prefix_hit_tokens = 0
        self._prompt_tokens = 0
        self._cow_copies = 0
        self._evicted_blocks = 0
        self._bounded_reused = 0
        self._cancelled = 0
        self._max_admission_stall = 0.0
        # Windowed / speculative accounting (all reset_stats-covered).
        self._tok_window = collections.deque(maxlen=512)  # (dt, tokens)
        self._queue_waits = collections.deque(maxlen=512)  # submit->tok1
        self._decode_slot_steps = 0   # sum of decoding-slot count/step
        self._spec_steps = 0
        self._spec_proposed = 0
        self._spec_accepted = 0

        # --- graceful degradation: admission shedding + tick watchdog.
        # Both OFF by default: an engine with no bounds queues exactly as
        # before (the autoscaler's queue_depth signal depends on queues
        # being allowed to form). Opt in per deployment via
        # engine_kwargs.
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if shed_high_water is not None and not 0.0 < shed_high_water <= 1.0:
            raise ValueError("shed_high_water must be in (0, 1]")
        self.max_queue = max_queue
        self.shed_high_water = shed_high_water
        self._sheds = 0
        self._watchdog_s = watchdog_s
        self._watchdog_stalls = 0
        self._tick_seq = 0
        self._tick_started: float | None = None
        self._watchdog_stop = threading.Event()
        if watchdog_s is not None:
            if watchdog_s <= 0:
                raise ValueError("watchdog_s must be > 0")
            t = threading.Thread(target=self._watchdog_loop, daemon=True,
                                 name="engine-watchdog")
            t.start()

        # --- RL flywheel: in-place donated weight hot-swap ------------
        # update_params() copies a new pytree INTO the old params'
        # device buffers (donation lets XLA alias input->output leaf by
        # leaf), so the arrays the jitted decode/verify closures see
        # keep their shapes, dtypes, shardings — and, critically, their
        # identity as far as compiled executables are concerned: no
        # retrace, no recompile, no restart. The source pytree is NOT
        # donated — the trainer keeps its own state alive.
        self._params_version = 0
        self._swaps = 0
        self._swap_pending_ts: float | None = None
        self._last_swap_ms = 0.0
        self.swap_traces = 0   # traces once per distinct treedef
                               # (target and draft trees each once)

        def _swap(old, new):
            self.swap_traces += 1
            return jax.tree.map(jnp.copy, new)

        self._swap_fn = jax.jit(_swap, donate_argnums=(0,))

        # --- flight recorder + retrace sentinel (util.telemetry) ------
        # Per-request lifecycle tracing (sampled; telemetry_sample
        # overrides RAY_TPU_TELEMETRY_SAMPLE) and the runtime watcher
        # that enforces the compile-once contract the tests above pin.
        # Shape-pinned paths carry hard caps from construction; the
        # bucket-dependent prefill paths join on arm_retrace_sentinel().
        from ray_tpu.util import telemetry as _telemetry
        self.name = _telemetry.next_name("engine")
        self._recorder = _telemetry.FlightRecorder(
            self.name, sample=telemetry_sample)
        # Program spans of the tick, of `submit` and of a consumer's
        # wait for a tick it does not run (`engine/*`, `stream/wait`):
        # profiler annotations whose totals feed stats().
        self._phases = _telemetry.Phases()
        # The gap between two ticks, taken at a tick's two ends
        # (`_note_tick_gap`): when the previous one ended, whether it
        # left work behind, and which thread ran it.
        self._tick_ended: float | None = None
        self._tick_carried = False
        self._tick_thread: int | None = None
        self._tick_gaps = self._pump_handoffs = 0
        self._tick_gap_s = self._tick_gap_max_s = 0.0
        self._sentinel = _telemetry.RetraceSentinel(self.name)
        self._sentinel.watch("decode", lambda: self.decode_traces, cap=1,
                             registered=True)
        if self._tick_fn is not None:
            # a chunk that does not end its prompt is a full one: the
            # fused program sees the largest bucket and no other
            self._sentinel.watch("tick", lambda: self.tick_traces, cap=1,
                                 registered=True)
        self._sentinel.watch("swap", lambda: self.swap_traces,
                             cap=2 if spec == "draft" else 1,
                             registered=True)
        loaders = sum(fn is not None
                      for fn in (self._load_target, self._load_draft))
        if loaders:
            self._sentinel.watch("load", lambda: self.load_traces,
                                 cap=loaders, registered=True)
        if spec is not None:
            self._sentinel.watch("verify", lambda: self.verify_traces,
                                 cap=1, registered=True)
        if spec == "draft":
            self._sentinel.watch("draft", lambda: self.draft_traces,
                                 cap=1, registered=True)
            self._sentinel.watch("draft_prefill",
                                 lambda: self.draft_prefill_traces,
                                 registered=True)
        self._sentinel.watch("prefill", lambda: self.prefill_traces,
                             registered=True)
        # Block gather/scatter trace once per pool geometry: the draft
        # pool's shapes differ from the target's, so a draft engine gets
        # two traces; everyone else exactly one.
        self._sentinel.watch("kv_gather", lambda: self.kv_gather_traces,
                             cap=2 if spec == "draft" else 1,
                             registered=True)
        self._sentinel.watch("kv_scatter",
                             lambda: self.kv_scatter_traces,
                             cap=2 if spec == "draft" else 1,
                             registered=True)
        _telemetry.register_stats_source(self.name, self, kind="engine")

    def arm_retrace_sentinel(self):
        """Declare shape warmup over: every watched compile path —
        including the bucket-dependent prefill ones — is baselined at
        its current trace count, and ANY further trace increments
        `retraces_unexpected` and WARNs. The hard-capped paths (decode,
        verify, swap) are watched from construction regardless."""
        self._sentinel.arm()

    # ------------------------------------------------------------------
    # watchdog + admission shedding
    # ------------------------------------------------------------------

    def _watchdog_loop(self) -> None:
        """Detect a stuck scheduler tick: sample the in-progress tick's
        start time (lock-free reads — the watchdog must keep working
        precisely when the lock holder is wedged) and count + WARN once
        per tick that overruns the budget."""
        flagged = -1
        while not self._watchdog_stop.wait(self._watchdog_s / 4):
            started, seq = self._tick_started, self._tick_seq
            if (started is not None and seq != flagged
                    and time.perf_counter() - started > self._watchdog_s):
                flagged = seq
                self._watchdog_stalls += 1
                logger.warning(
                    "engine %s: scheduler tick %d stuck for > %.2fs",
                    getattr(self, "name", "?"), seq, self._watchdog_s)

    def _shed_verdict(self, n_blocks: int) -> str | None:
        """Overload decision for one admission of `n_blocks` footprint;
        called under the lock. None = admit; else the reason string."""
        if self.max_queue is not None and \
                len(self._pending) >= self.max_queue:
            return (f"queue full ({len(self._pending)} >= "
                    f"max_queue {self.max_queue})")
        if self.shed_high_water is not None:
            # Projected utilization: live blocks + the committed
            # footprints already queued + this request. Using the
            # projection (not just instantaneous usage) keeps a burst of
            # submits between two ticks from overshooting the mark.
            queued = sum(
                self._slot_blocks_for(q.prompt.size, q.max_new_tokens)
                for q in self._pending)
            projected = (self._alloc.used + queued + n_blocks) \
                / max(self.cache_blocks, 1)
            if projected > self.shed_high_water:
                return (f"projected block utilization {projected:.2f} > "
                        f"high water {self.shed_high_water:.2f}")
        return None

    # ------------------------------------------------------------------
    # request side
    # ------------------------------------------------------------------

    def _footprint(self, n: int) -> tuple:
        """(state blocks, bounded pages, pages) that hold a sequence's
        first `n` tokens: the family's state blocks, the same whatever
        `n`; where it is paged the pages of `n` tokens; and where it
        keeps bounded pages as many again, up to a ring. Every footprint
        is made of this."""
        pages = (n - 1) // self.block_size + 1 if self._family.paged else 0
        return self._state_blocks, min(pages, self._ring), pages

    def _written_blocks(self, n: int) -> int:
        return sum(self._footprint(n))

    def _block_at(self, pool: dict, block: int):
        """Block id -> (the pool's arrays of its kind, its index on
        their axis 1): what the block moves are given."""
        fam = self._family
        kind = self._alloc.kind_of(block)
        keys = {"state": fam.state_keys, "bounded": fam.bounded_keys}.get(
            kind)
        if keys is None:
            keys = [k for k in pool
                    if k not in fam.state_keys + fam.bounded_keys]
        return {k: pool[k] for k in keys}, self._alloc.index_of(block)

    def _take_blocks(self, n: int, held: int = 0):
        """Fresh blocks for the footprint of `n` tokens of which `held`
        pages are already in hand: the state blocks first, then the
        bounded pages, then pages; None where any kind is short."""
        ns, nb, pages = self._footprint(n)
        alloc = self._alloc
        if alloc.free_state < ns or alloc.free_bounded < nb \
                or alloc.free < pages - held:
            return None
        return ([alloc.alloc(kind="state") for _ in range(ns)]
                + [alloc.alloc(kind="bounded") for _ in range(nb)]
                + [alloc.alloc() for _ in range(pages - held)])

    def _written_of(self, blocks: list, n: int) -> list:
        """Of a request's `blocks`, those that hold its first `n`
        tokens, in the list's order (state, bounded, pages): what a
        hand-off carries."""
        ns, nb, pages = self._footprint(n)
        held_b = sum(self._alloc.kind_of(b) == "bounded" for b in blocks)
        return (blocks[:ns + nb]
                + blocks[ns + held_b:ns + held_b + pages])

    def _table_of(self, blocks: list) -> np.ndarray:
        """A request's block table: its state blocks' ids, then a column
        a logical page with the page's index among its kind: the pages
        that grow in order, then (a family with bounded pages) as many
        columns again that walk the request's ring, column `j` naming
        ring page `j % ring`; 0 past its footprint."""
        ns = self._state_blocks
        index = self._alloc.index_of
        table = np.zeros((self.max_blocks,), np.int32)
        table[:ns] = blocks[:ns]
        ring = [index(b) for b in blocks[ns:]
                if self._alloc.kind_of(b) == "bounded"]
        pages = [index(b) for b in blocks[ns + len(ring):]]
        table[ns:ns + len(pages)] = pages
        if ring:
            half = ns + (self.max_blocks - ns) // 2
            table[half:half + len(pages)] = np.resize(ring, len(pages))
        return table

    @staticmethod
    def _tokens_written(p: int, max_new: int) -> int:
        """Positions a request writes: prefill 0..p-1, decode
        p..p+max_new-2 (the final sampled token is never written)."""
        return p + max(max_new - 1, 0)

    def _blocks_for(self, p: int, max_new: int) -> int:
        """Blocks a request's full footprint needs."""
        return self._written_blocks(self._tokens_written(p, max_new))

    def _slot_blocks_for(self, p: int, max_new: int) -> int:
        """Blocks THIS engine must hold for a request. A prefill-role
        engine never decodes: its slots only write the prompt's
        positions before handing off, so its footprint is the prompt
        blocks alone — the generation footprint is the importing
        engine's problem. Every other role needs the full
        prompt+generation footprint (`_blocks_for`)."""
        return self._written_blocks(self._slot_tokens(p, max_new))

    def _slot_tokens(self, p: int, max_new: int) -> int:
        """The positions `_slot_blocks_for` counts."""
        return p if self.role == "prefill" else self._tokens_written(
            p, max_new)

    def submit(self, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0,
               eos_id: int | None = None,
               priority: int = 0) -> int:
        """Queue a prompt (sequence of token ids); returns a request id
        for `tokens_for`. Admission happens inside `step()` — long
        prompts are absorbed in chunks, so there is no per-bucket prompt
        length limit, only the cache-capacity ones.

        Does not wait for a tick: under the delivery lock it takes its
        rid, makes its output queue and leaves the request in an inbox,
        which the next `step()` moves to the admission queue. Only an
        engine with shedding configured (`max_queue` /
        `shed_high_water`) takes the scheduler lock here, because the
        verdict reads the queue and the pool: such a submit can wait
        for the tick in progress.

        `priority` is the request's class (0 = lowest, up to
        ``priority_classes - 1``): higher classes get proportionally
        more admission share, shed last, and may preempt strictly-lower
        active streams under block pressure."""
        with self._phases.phase("engine/submit"):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.size == 0:
                raise ValueError("empty prompt")
            priority = int(priority)
            if not 0 <= priority < self.priority_classes:
                raise ValueError(
                    f"priority {priority} outside "
                    f"[0, {self.priority_classes})")
            if prompt.size + max_new_tokens > self.max_len:
                raise ValueError(
                    f"prompt {prompt.size} + max_new_tokens "
                    f"{max_new_tokens} exceeds cache max_len "
                    f"{self.max_len}")
            n_blocks = self._slot_blocks_for(prompt.size, max_new_tokens)
            if n_blocks > self._max_footprint:
                raise ValueError(
                    f"request footprint {n_blocks} blocks exceeds cache "
                    f"blocks {self.cache_blocks}")
            if self._draft_alloc is not None and \
                    n_blocks > self.draft_cache_blocks:
                raise ValueError(
                    f"request footprint exceeds draft cache blocks "
                    f"{self.draft_cache_blocks}")
            req = _Pending(0, prompt, max_new_tokens, temperature, eos_id,
                           time.perf_counter(), priority=priority)
            if self.max_queue is None and self.shed_high_water is None:
                return self._enqueue(req)
            with self._before_pump(), self._lock:
                self._take_inbox()
                reason = self._shed_verdict(n_blocks)
                # Class-ordered shedding: pressure evicts the lowest-
                # class QUEUED request first; the incoming request is
                # only shed when nothing queued ranks below it (so an
                # all-one-class engine behaves exactly as before).
                while reason is not None and \
                        self._shed_lowest_below(priority):
                    reason = self._shed_verdict(n_blocks)
                if reason is not None:
                    self._sheds += 1
                    self._class_counter(priority)["sheds"] += 1
                    raise OverloadedError(
                        f"engine overloaded, request shed: {reason}")
                return self._enqueue(req)

    def _enqueue(self, req: _Pending) -> int:
        """`submit`'s one step under the delivery lock: the rid, the
        output queue, the inbox."""
        with self._delivery:
            rid = req.rid = self._rid
            self._rid += 1
            self._out[rid] = collections.deque()
            self._inbox.append(req)
            self._recorder.on_submit(rid, req.prompt.size)
        return rid

    def _take_inbox(self) -> None:
        """Under `_lock`: what `submit` left in the inbox joins the
        admission queue, in order. Everything that reads the backlog
        (`step`, `stats`, `cancel`, a shedding `submit`) calls this
        first. Only holders of `_lock` pop the inbox, and a deque's
        `popleft` is atomic beside `submit`'s `append`."""
        while self._inbox:
            req = self._inbox.popleft()
            self._pending.append(req)
            self._class_counter(req.priority)["submitted"] += 1

    @contextlib.contextmanager
    def _before_pump(self):
        """Around `with self._lock:` in every thread that runs no tick
        (`stats`, `cancel`, `update_params`, `import_handoff`,
        `reset_stats`, a shedding `submit`). A thread that releases an
        `RLock` and asks for it again wins it, so consumers that tick
        back to back would keep these waiting for seconds; instead the
        pump lets every thread counted here go first (`_await`)."""
        with self._delivery:
            self._lock_waiters += 1
        try:
            yield
        finally:
            with self._delivery:
                self._lock_waiters -= 1
                if not self._lock_waiters:
                    self._delivery.notify_all()

    def _shed_lowest_below(self, priority: int) -> bool:
        """Shed the lowest-class queued request strictly below
        `priority` (newest of that class — least sunk wait), delivering
        a typed `OverloadedError` through its `tokens_for`. Returns
        False when no queued request ranks below `priority`. Resumed
        (preempted) streams are never shed here: they have already
        delivered tokens to a live consumer."""
        victim_i = None
        for i, q in enumerate(self._pending):
            if q.priority >= priority or q.resumed:
                continue
            if victim_i is None:
                victim_i = i
                continue
            v = self._pending[victim_i]
            if (q.priority, -q.ts) < (v.priority, -v.ts):
                victim_i = i
        if victim_i is None:
            return False
        victim = self._pending[victim_i]
        del self._pending[victim_i]
        with self._delivery:
            self._errors[victim.rid] = OverloadedError(
                f"engine overloaded: request (class {victim.priority}) "
                f"shed from the queue for a class-{priority} admission")
            self._wake(victim.rid)
        self._sheds += 1
        self._class_counter(victim.priority)["sheds"] += 1
        self._recorder.on_finish(victim.rid, "shed")
        return True

    def _class_counter(self, c: int) -> dict:
        """Per-class counter row (lazily created; under the lock)."""
        d = self._per_class.get(c)
        if d is None:
            d = {"submitted": 0, "completed": 0, "sheds": 0,
                 "preemptions": 0, "decode_tokens": 0}
            self._per_class[c] = d
            self._class_waits[c] = collections.deque(maxlen=256)
        return d

    def cancel(self, rid: int) -> bool:
        """Abort a request wherever it is — in the inbox, pending,
        mid-prefill, decoding, or finished-but-undrained — releasing its
        cache blocks and output queue. Idempotent; returns True if
        anything was released."""
        with self._before_pump(), self._lock:
            self._take_inbox()
            flight = self._flight
            if flight is not None and (
                    rid in flight.rows.values()
                    or flight.ended is not None and flight.ended.rid == rid):
                # its token in flight is read (and dropped with its
                # queue) before its blocks go
                self._rest()
            hit = False
            for i, req in enumerate(self._pending):
                if req.rid == rid:
                    del self._pending[i]
                    hit = True
                    break
            for i, s in enumerate(self._slots):
                if s.rid == rid:
                    self._release(i)
                    hit = True
                    break
            if rid in self._import_rids:
                self._import_rids.discard(rid)
                for i, (irid, _) in enumerate(self._imports):
                    if irid == rid:
                        del self._imports[i]
                        break
                hit = True
            with self._delivery:
                if self._handoffs.pop(rid, None) is not None:
                    # an exported-but-never-collected prefill: the
                    # device blocks were already freed at export, so
                    # abandoning only drops the host blob
                    self._handoffs_abandoned += 1
                    hit = True
                hit |= self._out.pop(rid, None) is not None
                hit |= self._errors.pop(rid, None) is not None
                self._done.discard(rid)
                self._wake(rid)
            if hit:
                self._cancelled += 1
                self._recorder.on_finish(rid, "cancelled")
            return hit

    def _wake(self, rid: int) -> None:
        """Under the delivery lock: something was handed to `rid` (a
        token, its end, an error, a hand-off, a cancel); its consumer,
        if it sleeps in `_await`, wakes."""
        cond = self._sleepers.get(rid)
        if cond is not None:
            cond.notify_all()

    def _wake_one(self) -> None:
        """Under the delivery lock: wake the consumer asleep longest, to
        take the pump if it is free."""
        cond = next(iter(self._sleepers.values()), None)
        if cond is not None:
            cond.notify_all()

    def _await(self, take, rid: int):
        """What `tokens_for` and `handoff_for` block in. `take()` runs
        under the delivery lock and returns what request `rid` has
        ready, or None. With nothing ready the caller either becomes the
        pump (it gets the pump mutex without waiting, and runs ONE
        `step()`, which takes `_lock` itself) or sleeps, on a condition
        of its own, until something is handed to its request (`_wake`)
        or the pump wants a taker (`_wake_one`). Nobody queues to become
        the pump and nobody asks for `_lock` in order to pop, so N
        consumers cost a tick nothing; a lone consumer is always the
        pump, and sees the ticks it would see calling `step()` itself.
        A tick that raises does so in the consumer that ran it, and in
        no other.

        A tick wakes the consumers it handed something to, when it hands
        it over, and at its end one more: the pump's own thread pumps on
        while its request has nothing ready, and when it leaves with
        something, or with the tick's exception, the one it woke takes
        the mutex; one that leaves with something while the mutex is
        free wakes the next. The mutex is given up under the delivery
        lock, and a consumer that found it taken is in `_sleepers`
        before it lets go of that lock, so it cannot miss the call.
        Before a tick the pump lets `_before_pump`'s threads have
        `_lock`."""
        while True:
            with self._delivery:
                got = take()
                if got is not None:
                    if not self._pump.locked():
                        self._wake_one()
                    return got
                if not self._pump.acquire(blocking=False):
                    cond = self._sleepers.setdefault(
                        rid, threading.Condition(self._delivery_lock))
                    try:
                        with self._phases.phase("stream/wait"):
                            cond.wait()
                    finally:
                        if self._sleepers.get(rid) is cond:
                            del self._sleepers[rid]
                    continue
                while self._lock_waiters:
                    self._delivery.wait()
            try:
                self.step()
            finally:
                with self._delivery:
                    self._pump.release()
                    self._wake_one()

    def tokens_for(self, rid: int):
        """Generator of generated tokens for one request — each yielded
        value is a `TokenEvent`: an ``int`` (token id) that also carries
        ``.logprob`` (natural log pi(token|prefix) under the weights it
        was sampled with) and ``.params_version``. Each next() pops
        this request's next token under the delivery lock; when there
        is none it waits in `_await`: one consumer at a time runs
        `step()` and the others sleep until that tick has handed its
        tokens over, so N concurrent consumers collectively drive one
        continuously-batched device loop, and a lone one drives it
        alone. Abandoning the generator (break / close / GC) cancels
        the request and releases its cache blocks."""
        first = True

        def take():
            nonlocal first
            q = self._out.get(rid)
            if q is None:               # cancelled, or never submitted
                return _END
            err = self._errors.pop(rid, None)
            if err is not None:
                # terminated while queued (class-ordered shed): surface
                # the typed error to this consumer
                del self._out[rid]
                self._done.discard(rid)
                raise err
            fin = rid in self._done
            if not q:
                if not fin:
                    return None
                tok = _END
            else:
                tok = q.popleft()
                if first:
                    first = False
                    self._recorder.on_first_yield(rid)
            if fin and not q:
                self._done.discard(rid)
                del self._out[rid]
            return tok

        try:
            # yield OUTSIDE every lock: a generator suspends at yield
            while (tok := self._await(take, rid)) is not _END:
                yield tok
        finally:
            self.cancel(rid)

    def generate(self, prompt, **kw) -> list[int]:
        """Blocking convenience: submit + drain one request."""
        return list(self.tokens_for(self.submit(prompt, **kw)))

    # ------------------------------------------------------------------
    # disaggregated prefill/decode handoff
    # ------------------------------------------------------------------

    def _export_handoff(self, slot_idx: int):
        """Prefill-role endgame for one slot (under the lock, called
        from `_finish_chunk` the tick the prompt completes): gather
        every written KV block — payload and any int8 scale rows travel
        together, block-aligned — to host, park the blob for collection,
        and free the device blocks. The blob carries everything a
        decode-role `import_handoff` needs to continue the stream
        token-identically: the parked first token (with logprob/version,
        sampled from the final prefill chunk HERE so the decode engine
        never re-runs prefill), the sampling state, and the weight
        version the KV was computed under."""
        self._rest()
        s = self._slots[slot_idx]
        p = s.prompt.size
        n_written = self._written_blocks(p)
        t0 = time.perf_counter()

        def _dump(pool, blocks):
            out = []
            for b in (blocks[:n_written] if pool is self.draft_cache
                      else self._written_of(blocks, p)):
                arrays, at = self._block_at(pool, b)
                blk = self._gather_fn(arrays, np.int32(at))
                # graftlint: disable-next-line=R001,R004 the export IS the handoff's one deliberate device->host pull: the blob must be host bytes before it can ride netaddr to the decode replica
                out.append({k: np.asarray(v) for k, v in blk.items()})
            return out

        payload = _dump(self.cache, s.blocks)
        draft_payload = (_dump(self.draft_cache, s.draft_blocks)
                         if self._draft_alloc is not None else None)
        kv_bytes = sum(int(a.nbytes) for blk in payload
                       for a in blk.values())
        if draft_payload is not None:
            kv_bytes += sum(int(a.nbytes) for blk in draft_payload
                            for a in blk.values())
        dt = time.perf_counter() - t0
        blob = {
            "rid": s.rid,
            "prompt": s.prompt,
            "token": int(s.token),
            "token_logp": float(s.token_logp),
            "token_ver": int(s.token_ver),
            "max_new_tokens": int(s.remaining),
            "temperature": float(s.temperature),
            "eos_id": s.eos_id,
            "priority": int(s.priority),
            "params_version": int(self._params_version),
            "block_size": self.block_size,
            "n_blocks": n_written,
            "payload": payload,
            "draft_payload": draft_payload,
            "kv_bytes": int(kv_bytes),
        }
        self._handoffs_exported += 1
        self._kv_blocks_exported += n_written * (
            2 if draft_payload is not None else 1)
        self._kv_export_bytes += kv_bytes
        self._kv_export_ms.append(dt * 1e3)
        self._recorder.on_kv_export(s.rid, n_written, kv_bytes, dt)
        self._recorder.on_finish(s.rid, "handoff")
        # No token consumer on a prefill engine: park the blob and drop
        # the output queue in one hold (handoff_for reads `_handoffs`
        # first, and takes a missing queue for a cancel), and release
        # the device blocks — the prompt's full blocks live on in the
        # radix tree for shared-prefix admissions, everything else is
        # host-side in the blob.
        with self._delivery:
            self._handoffs[s.rid] = blob
            self._out.pop(s.rid, None)
            self._done.discard(s.rid)
            self._wake(s.rid)
        self._release(slot_idx)

    def handoff_for(self, rid: int) -> dict:
        """Wait in `_await` (running the scheduler, or sleeping while
        another consumer runs it) until `rid`'s prefill completes, then
        pop and return its handoff blob — the prefill-role analogue of
        draining `tokens_for`. Raises the parked error for a request
        shed from the queue, KeyError for an unknown/cancelled rid."""
        if self.role != "prefill":
            raise RuntimeError(
                "handoff_for is only available on a prefill-role "
                f"engine (this engine is {self.role!r})")

        def take():
            blob = self._handoffs.pop(rid, None)
            if blob is not None:
                return blob
            err = self._errors.pop(rid, None)
            if err is not None:
                self._out.pop(rid, None)
                self._done.discard(rid)
                raise err
            if rid not in self._out:
                raise KeyError(
                    f"unknown or cancelled handoff rid {rid}")
            return None

        return self._await(take, rid)

    def take_handoff(self, rid: int) -> dict | None:
        """Non-blocking collect: pop `rid`'s parked blob if its prefill
        already completed, else None."""
        with self._delivery:
            return self._handoffs.pop(rid, None)

    def import_handoff(self, blob: dict) -> int:
        """Adopt a prefill-role engine's handoff blob: queue its KV
        blocks for scatter into this pool and its stream for a decode
        slot. Returns a fresh LOCAL rid for `tokens_for` — the stream
        picks up at the first generated token (already sampled by the
        prefill engine and delivered from here), greedy token-identical
        to a colocated run over the same prompt."""
        if self.role == "prefill":
            raise RuntimeError(
                "a prefill-role engine cannot import handoffs")
        prompt = np.asarray(blob["prompt"], np.int32).reshape(-1)
        p = prompt.size
        max_new = int(blob["max_new_tokens"])
        if int(blob["block_size"]) != self.block_size:
            raise ValueError(
                f"handoff block_size {blob['block_size']} != engine "
                f"block_size {self.block_size} — prefill and decode "
                f"pools must share the paging granule")
        n_written = self._written_blocks(p)
        if len(blob["payload"]) != n_written:
            raise ValueError(
                f"handoff payload has {len(blob['payload'])} blocks, "
                f"expected {n_written} for a {p}-token prompt")
        if p + max_new > self.max_len:
            raise ValueError(
                f"handoff prompt {p} + max_new_tokens {max_new} "
                f"exceeds cache max_len {self.max_len}")
        if self._blocks_for(p, max_new) > self._max_footprint:
            raise ValueError(
                f"handoff footprint {self._blocks_for(p, max_new)} "
                f"blocks exceeds cache blocks {self.cache_blocks}")
        if self._draft_alloc is not None:
            if blob.get("draft_payload") is None:
                raise ValueError(
                    "draft-spec engine needs the handoff's draft-pool "
                    "blocks (prefill engine must run the same spec)")
            if self._blocks_for(p, max_new) > self.draft_cache_blocks:
                raise ValueError(
                    "handoff footprint exceeds draft cache blocks "
                    f"{self.draft_cache_blocks}")
        priority = int(blob.get("priority", 0))
        if not 0 <= priority < self.priority_classes:
            raise ValueError(
                f"handoff priority {priority} outside "
                f"[0, {self.priority_classes})")
        with self._before_pump(), self._lock:
            with self._delivery:
                rid = self._rid
                self._rid += 1
                self._out[rid] = collections.deque()
                self._recorder.on_submit(rid, p)
            self._imports.append((rid, blob))
            self._import_rids.add(rid)
            self._class_counter(priority)["submitted"] += 1
        return rid

    def _admit_imports(self) -> bool:
        """Move queued handoff imports into decode slots (FIFO), ahead
        of regular pending admissions — an import's prefill cost is
        already sunk on another engine, so making it wait behind local
        prefills would throw that work away latency-wise. Under block
        pressure an import may preempt strictly-lower-class active
        streams, exactly like `_admit_or_preempt`."""
        did = False
        while self._imports:
            rid, blob = self._imports[0]
            free = next((i for i, s in enumerate(self._slots)
                         if s.phase == "idle"), None)
            if free is None:
                break
            if not self._try_import(free, rid, blob):
                victim = self._pick_victim(int(blob.get("priority", 0)))
                if victim is None:
                    break
                self._preempt(victim, "import-pressure")
                continue
            self._imports.popleft()
            self._import_rids.discard(rid)
            did = True
        return did

    def _try_import(self, slot_idx: int, rid: int, blob: dict) -> bool:
        """Install one handoff into a slot: share any radix-cached full
        prefix blocks by reference, scatter the remaining transferred
        blocks into freshly allocated ones, and enter the decode phase
        at the first generated token. Returns False (leaving the import
        queued) when the pool can't supply the footprint even after
        eviction. Unlike `_try_admit` there is NO copy-on-write: a
        prefix match ending mid-block just means that block is
        re-scattered from the transferred payload instead of shared —
        cheaper than a device copy and bit-identical by construction."""
        bs = self.block_size
        # graftlint: disable-next-line=R001,R004 blob arrays are host numpy (they crossed the wire); this asarray is a view/cast, not a device sync
        prompt = np.asarray(blob["prompt"], np.int32).reshape(-1)
        p = prompt.size
        max_new = int(blob["max_new_tokens"])
        total = self._blocks_for(p, max_new)
        payload = blob["payload"]
        n_written = len(payload)
        try:
            _faults.check("engine.alloc")
        except _faults.FaultInjected:
            return False
        if self._draft_alloc is not None and \
                self._draft_alloc.free < total:
            return False
        # Prefix sharing only under a matching weight version: imported
        # KV was computed under the blob's params_version, and mixing it
        # with tree blocks from a different version would splice stale
        # context into the sequence.
        blocks, matched = ([], 0)
        if self._tree is not None and \
                int(blob["params_version"]) == self._params_version:
            blocks, matched = self._tree.match(prompt)
        n_full = min(matched // bs, n_written)
        for b in blocks[:n_full]:
            self._alloc.ref(b)
        fresh_needed = total - n_full
        if self._alloc.free < fresh_needed and self._tree is not None:
            self._evicted_blocks += self._tree.evict(
                fresh_needed - self._alloc.free)
        fresh = self._take_blocks(self._tokens_written(p, max_new), n_full)
        if fresh is None:
            for b in blocks[:n_full]:
                self._alloc.decref(b)
            return False
        slot_blocks = blocks[:n_full] + fresh
        jnp = self._jax.numpy
        t0 = time.perf_counter()
        scattered = 0
        written = self._written_of(slot_blocks, p)
        for j in range(n_full, n_written):
            arrays, at = self._block_at(self.cache, written[j])
            self.cache = {**self.cache, **self._scatter_block_fn(
                arrays,
                {k: jnp.asarray(v) for k, v in payload[j].items()},
                np.int32(at))}
            scattered += 1
        table = self._table_of(slot_blocks)
        s = self._slots[slot_idx]
        s.rid, s.phase = rid, "decode"
        s.prompt, s.filled = prompt, p
        s.blocks, s.table = slot_blocks, table
        s.order = self._admit_seq
        self._admit_seq += 1
        s.temperature = float(blob["temperature"])
        s.eos_id = blob["eos_id"]
        s.remaining = max_new - 1
        s.pos = p
        s.token = int(blob["token"])
        s.token_logp = float(blob["token_logp"])
        s.token_ver = int(blob["token_ver"])
        s.submit_ts = time.perf_counter()
        s.priority = int(blob.get("priority", 0))
        # resumed: TTFT was recorded on the prefill engine — counting
        # the import here would double-book the same first token.
        s.resumed = True
        s.emitted = []
        s.history = prompt.tolist() if self.spec == "ngram" else []
        if self._draft_alloc is not None:
            dblocks = [self._draft_alloc.alloc() for _ in range(total)]
            dtable = np.zeros((self.max_blocks,), np.int32)
            dtable[:len(dblocks)] = dblocks
            for j in range(n_written):
                self.draft_cache = self._scatter_block_fn(
                    self.draft_cache,
                    {k: jnp.asarray(v)
                     for k, v in blob["draft_payload"][j].items()},
                    np.int32(dblocks[j]))
                scattered += 1
            s.draft_blocks, s.draft_table = dblocks, dtable
            s.draft_filled = p
        # Version trust: a same-version import's prompt blocks are as
        # publishable as a local prefill's; a cross-version one must
        # never enter the tree (its K/V predates the current weights).
        if int(blob["params_version"]) == self._params_version:
            s.version = self._params_version
            if self._tree is not None and p >= bs:
                self._tree.insert(prompt, slot_blocks)
        else:
            s.version = self._params_version - 1
        dt = time.perf_counter() - t0
        kv_bytes = int(blob.get("kv_bytes", 0))
        self._imports_completed += 1
        self._kv_blocks_imported += scattered
        self._kv_import_bytes += kv_bytes
        self._kv_import_ms.append(dt * 1e3)
        self._prefix_hit_tokens += n_full * bs
        self._prompt_tokens += p
        self._recorder.on_kv_import(rid, scattered, kv_bytes, dt)
        self._recorder.on_admit(rid, n_full * bs, False)
        # Deliver the parked first token through the normal emit path
        # (it carries the logprob/version the prefill engine computed);
        # a max_new_tokens=1 request retires right here.
        self._emit(s, slot_idx, s.token, s.token_logp, s.token_ver)
        return True

    # ------------------------------------------------------------------
    # weight hot-swap (RL flywheel)
    # ------------------------------------------------------------------

    def _place_tree(self, old, new, what: str):
        """Validate leaf-for-leaf compatibility and place `new` on the
        old leaves' shardings. Pure host+transfer work against a
        *snapshot* of the old tree — runs under the swap mutex only,
        never the scheduler lock, so ticks proceed during the upload."""
        jax = self._jax
        old_leaves, old_def = jax.tree.flatten(old)
        new_leaves, new_def = jax.tree.flatten(new)
        if old_def != new_def:
            raise ValueError(
                f"update_params: {what} pytree structure changed "
                f"({new_def} != {old_def})")
        for o, n in zip(old_leaves, new_leaves):
            if tuple(o.shape) != tuple(n.shape) or o.dtype != n.dtype:
                raise ValueError(
                    f"update_params: {what} leaf mismatch "
                    f"{n.shape}/{n.dtype} != {o.shape}/{o.dtype} — "
                    f"hot-swap requires identical shapes and dtypes")
        return jax.tree.unflatten(old_def, [
            jax.device_put(n, o.sharding) if hasattr(o, "sharding")
            else jax.numpy.asarray(n)
            for o, n in zip(old_leaves, new_leaves)])

    def _load_published(self, load, given, tree, what: str):
        """A published `tree` through `load`, the family's jitted
        load-time function (as it is where there is none, or no tree).
        The function reads leaves by name, so a tree whose structure is
        not `given`, that of the tree the engine was built from, is
        refused here as `_place_tree` refuses one after it."""
        if load is None or tree is None:
            return tree
        got = self._jax.tree.structure(tree)
        if got != given:
            raise ValueError(
                f"update_params: {what} pytree structure changed "
                f"({got} != {given}): publish what the engine was "
                "built from")
        return load(tree)

    def update_params(self, new_params, *, draft_params=None) -> int:
        """Hot-swap model weights into the live engine between ticks.

        `new_params` must match the current params pytree leaf-for-leaf
        in structure, shape, and dtype (optimizer steps preserve this by
        construction). The swap is an in-place donated device copy into
        the OLD buffers, so nothing the compiled decode / verify / prefill
        executables depend on changes: trace counters stay untouched —
        asserted in tests — and in-flight requests are not restarted.
        `draft_params` optionally swaps the speculative draft model the
        same way.

        Consequences the caller should know:

        - The engine owns its buffers: the params object passed at
          construction (or returned by a previous swap) is invalidated
          by donation. `new_params` itself is NOT donated — a trainer
          can keep training on the same state it published.
        - Publish what was given at construction, the f32 masters:
          where the family's load-time function ran there (it casts
          the masters to the activation dtype and, for
          `weight_dtype="int8"`, quantizes them), the same jitted
          function re-runs on the published tree before
          validation/placement, so the RL flywheel never handles bf16
          or int8 and the swap stays retrace-free. Where it did not
          run (no such function, or a tree already in the dtype the
          steps read), the published tree is placed as it is.
        - The radix prefix cache is flushed: cached K/V was computed
          under the old weights and must not be shared into post-swap
          admissions. In-flight sequences keep their already-written
          K/V and finish on mixed old/new-weight context — the standard
          in-place-sync staleness tradeoff (MindSpeed RL, 2507.19017) —
          which the per-token `params_version` tags make visible so
          learners can bound staleness or importance-correct.
        - `params_version` increments and stamps every subsequently
          computed token (`TokenEvent.params_version`); `stats()`
          reports it alongside the `swaps` counter and `weight_swap_ms`
          (update_params call to first post-swap token).

        Returns the new `params_version`."""
        # Swappers serialize on the swap mutex; the scheduler lock is
        # held only for the two brief sections that touch engine state
        # (snapshot, commit). Validation and the host->device upload of
        # the new tree — the slow part — happen between them, so decode
        # ticks keep running while weights stream in (R004: the swap
        # mutex is declared blocking_ok for exactly this).
        with self._swap_mutex:
            t0 = time.perf_counter()
            with self._before_pump(), self._lock:
                old = self.params
                old_draft = self.draft_params
            if draft_params is not None and old_draft is None:
                raise ValueError(
                    "update_params: draft_params given but the "
                    "engine has no draft model")
            # The engine holds loaded trees (cast, or quantized): load
            # the published f32 masters BEFORE validation, so the
            # leaf-for-leaf check compares like with like and the
            # donated swap copies what the steps read. Shapes repeat, so
            # this hits the cached _load trace (load_traces is
            # sentinel-pinned).
            new_params = self._load_published(
                self._load_target, self._given[0], new_params, "params")
            draft_params = self._load_published(
                self._load_draft, self._given[1], draft_params,
                "draft_params")
            placed = self._place_tree(old, new_params, "params")
            placed_draft = (
                self._place_tree(old_draft, draft_params, "draft_params")
                if draft_params is not None else None)
            with self._before_pump(), self._lock:
                # a step in flight ran under the old weights: its tokens
                # are emitted with that version before the swap
                self._rest()
                self.params = self._swap_fn(old, placed)
                if placed_draft is not None:
                    self.draft_params = self._swap_fn(
                        old_draft, placed_draft)
                if self._tree is not None:
                    self._tree.flush()
                self._params_version += 1
                self._swaps += 1
                self._swap_pending_ts = t0
                return self._params_version

    @property
    def params_version(self) -> int:
        return self._params_version

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------

    def _chunk_bucket_for(self, n: int) -> int:
        for b in self.chunk_buckets:
            if n <= b:
                return b
        raise ValueError(f"no chunk bucket for length {n}")

    def _release(self, slot_idx: int):
        s = self._slots[slot_idx]
        if self._ring and s.blocks:
            # the pages of what it wrote that lay past its ring took a
            # ring page written before, in place
            written = s.pos if s.phase == "decode" else s.filled
            self._bounded_reused += max(
                0, -(-written // self.block_size) - self._ring)
        for b in s.blocks:
            self._alloc.decref(b)
        for b in s.draft_blocks:
            self._draft_alloc.decref(b)
        self._slots[slot_idx] = _Slot()

    def _try_admit(self, slot_idx: int, req: _Pending) -> bool:
        """Allocate a slot's blocks (sharing any cached prefix) and put
        it in the prefill phase. Returns False — leaving the request
        pending — if the pool can't supply the footprint even after
        evicting zero-ref cached prefixes."""
        bs = self.block_size
        p = req.prompt.size
        total = self._slot_blocks_for(p, req.max_new_tokens)
        # fault site: 'fail' here reads as deterministic allocator
        # exhaustion — the admission is refused exactly as if the pool
        # had no free blocks, driving the class-preemption path (it
        # does NOT unwind to the consumer)
        try:
            _faults.check("engine.alloc")
        except _faults.FaultInjected:
            return False
        # The draft pool has no prefix sharing or eviction — the full
        # footprint must be free up front, checked before any main-pool
        # work so failure needs no rollback.
        if self._draft_alloc is not None and \
                self._draft_alloc.free < total:
            return False
        blocks, matched = ([], 0)
        if self._tree is not None:
            blocks, matched = self._tree.match(req.prompt)
        # Always leave >= 1 token to prefill: the request's first
        # generated token is sampled from its final prefill chunk.
        matched = min(matched, p - 1)
        blocks = blocks[:-(-matched // bs)] if matched else []
        n_full = matched // bs
        partial = matched % bs != 0
        # Reference the shared blocks BEFORE any eviction so the tree
        # can't free them out from under this admission.
        for b in blocks:
            self._alloc.ref(b)
        fresh_needed = total - n_full
        if self._alloc.free < fresh_needed and self._tree is not None:
            self._evicted_blocks += self._tree.evict(
                fresh_needed - self._alloc.free)
        fresh = self._take_blocks(
            self._slot_tokens(p, req.max_new_tokens), n_full)
        if fresh is None:
            for b in blocks:
                self._alloc.decref(b)
            return False
        slot_blocks = blocks[:n_full] + fresh
        if partial:
            # Copy-on-write: the matched prefix ends inside a shared
            # block; this request's own tokens land in that block, so
            # copy it into a private one first.
            src, dst = blocks[n_full], fresh[0]
            self.cache = self._copy_fn(self.cache, np.int32(src),
                                       np.int32(dst))
            self._cow_copies += 1
            self._alloc.decref(src)
        table = self._table_of(slot_blocks)
        s = self._slots[slot_idx]
        s.rid, s.phase = req.rid, "prefill"
        s.prompt, s.filled = req.prompt, matched
        s.blocks, s.table = slot_blocks, table
        s.order = self._admit_seq
        self._admit_seq += 1
        s.temperature, s.eos_id = req.temperature, req.eos_id
        s.remaining = req.max_new_tokens
        s.submit_ts = req.ts
        s.version = self._params_version
        s.priority = req.priority
        s.resumed = req.resumed
        s.emitted = []
        if req.resumed:
            # blocks' worth of KV this resume recomputes (the radix
            # match absorbed the rest for free)
            self._reprefill_blocks += self._written_blocks(p - matched)
        s.history = req.prompt.tolist() if self.spec == "ngram" else []
        if self._draft_alloc is not None:
            dblocks = [self._draft_alloc.alloc() for _ in range(total)]
            dtable = np.zeros((self.max_blocks,), np.int32)
            dtable[:len(dblocks)] = dblocks
            s.draft_blocks, s.draft_table = dblocks, dtable
            s.draft_filled = 0
        self._prefix_hit_tokens += matched
        self._prompt_tokens += p
        self._recorder.on_admit(req.rid, matched, partial)
        return True

    def _admission_order(self) -> list[_Pending]:
        """Class-aware admission order over the pending queue.

        Two mechanisms compose (ROADMAP item 4's multi-tenant
        admission): **weighted shares** — a stride scheduler across
        backlogged classes with weight ``priority_weight_base**class``,
        so class c+1 gets base x class c's admission share while every
        backlogged class keeps a guaranteed nonzero share — and
        **aging** — a request older than
        ``(priority_classes - class) * priority_aging_s`` escalates
        past the stride order entirely (oldest first), which bounds the
        worst-case wait of the lowest class under sustained high-class
        load. Within one class, order is FIFO."""
        now = time.perf_counter()
        aged: list[_Pending] = []
        backlog: dict[int, collections.deque] = {}
        for req in self._pending:
            bound = (self.priority_classes - req.priority) \
                * self.priority_aging_s
            if now - req.ts > bound:
                if not req.aged:
                    req.aged = True
                    self._aging_promotions += 1
                aged.append(req)
            else:
                backlog.setdefault(
                    req.priority, collections.deque()).append(req)
        aged.sort(key=lambda r: (r.ts, r.rid))
        order = aged
        # A class entering the backlog starts at the current pass floor
        # so it can't claim banked credit for the time it was idle.
        floor = max(self._class_pass.values(), default=0.0)
        for c in backlog:
            self._class_pass.setdefault(c, floor)
        sim = dict(self._class_pass)
        while backlog:
            c = min(backlog, key=lambda k: (sim[k], -k))
            order.append(backlog[c].popleft())
            sim[c] += 1.0 / (self.priority_weight_base ** c)
            if not backlog[c]:
                del backlog[c]
        return order

    def _pick_victim(self, below: int) -> int | None:
        """Preemption victim: the active slot of the lowest class
        strictly below `below`; ties broken by most recent admission
        (least progress = cheapest re-prefill)."""
        best = None
        for i, s in enumerate(self._slots):
            if not s.active or s.priority >= below:
                continue
            if best is None or \
                    (s.priority, -s.order) < (self._slots[best].priority,
                                              -self._slots[best].order):
                best = i
        return best

    def _preempt(self, slot_idx: int, why: str) -> None:
        """Evict one active stream under block pressure: publish its
        written blocks to the radix tree (resume admits them by
        reference — mostly free), release the slot, and requeue
        prompt+emitted as a re-prefill under the SAME rid and output
        queue. The consumer keeps iterating `tokens_for` unaware; a
        greedy stream resumes token-identical because the re-prefilled
        KV is bit-identical to the KV released (prefill and decode
        share the paged attention math). With no tree (a family of
        state blocks keeps none: its block is rewritten by every token)
        nothing is published and the resume re-prefills from its first
        token; the first chunk resets the block it is given. A decode
        step in flight is read first, so the resume holds every token
        computed; a victim that ended with that token needs no more."""
        rid = self._slots[slot_idx].rid
        self._rest()
        s = self._slots[slot_idx]
        if s.rid != rid:
            return
        seq = [int(t) for t in s.prompt.tolist()] \
            + [int(t) for t in s.emitted]
        # KV written so far covers seq[:pos] in decode (the parked
        # last token is sampled but never written), prompt[:filled]
        # mid-prefill.
        written = s.pos if s.phase == "decode" else s.filled
        if self._tree is not None and written >= self.block_size \
                and s.version == self._params_version:
            self._tree.insert(seq[:written], s.blocks)
        resume = _Pending(
            s.rid,
            np.concatenate([
                s.prompt.astype(np.int32, copy=False),
                np.fromiter((int(t) for t in s.emitted), np.int32,
                            len(s.emitted))]),
            s.remaining, s.temperature, s.eos_id, s.submit_ts,
            priority=s.priority, resumed=True)
        self._preemptions += 1
        self._class_counter(s.priority)["preemptions"] += 1
        logger.info(
            "engine %s: preempted rid=%d class=%d (%s) after %d tokens",
            getattr(self, "name", "?"), s.rid, s.priority, why,
            len(s.emitted))
        self._recorder.on_finish(s.rid, f"preempted:{why}")
        self._release(slot_idx)
        self._pending.appendleft(resume)

    def _force_preempt(self) -> bool:
        """Fault-injected preemption (site ``engine.preempt``): evict
        the lowest-class active stream regardless of pressure."""
        self._rest()
        victim = self._pick_victim(self.priority_classes)
        if victim is None:
            return False
        self._preempt(victim, "forced")
        return True

    def _admit_or_preempt(self, req: _Pending) -> bool:
        """Admit one request, preempting strictly-lower-class active
        streams while the block pool can't serve it. Slot exhaustion
        defers instead of preempting: the stride order already decided
        who deserves the slots, and letting a later entry evict this
        pass's winners would undo the weighted shares (observed as
        full class-1 drain before any class-0 admission). Bounded:
        every retry removes one active victim."""
        free = next((i for i, s in enumerate(self._slots)
                     if s.phase == "idle"), None)
        if free is None:
            return False
        while not self._try_admit(free, req):
            victim = self._pick_victim(req.priority)
            if victim is None:
                return False
            self._preempt(victim, "block-pressure")
        return True

    def _admit_pending(self) -> bool:
        """Move pending requests into slots, in class-aware order
        (`_admission_order`). A request whose first block of tokens
        matches an in-flight prefill's is deferred one tick — once that
        prefill completes and its full blocks enter the radix tree, the
        latecomer admits by reference instead of re-prefilling the
        shared prefix. When a request fails admission even after
        preemption, strictly LOWER classes are locked out for the rest
        of the tick — freed blocks accrue to the blocked class instead
        of leaking to small low-class requests forever."""
        if not self._pending:
            return False
        bs = self.block_size
        heads = set()
        if self._tree is not None:
            heads = {tuple(s.prompt[:bs].tolist())
                     for s in self._slots
                     if s.phase == "prefill" and s.prompt.size >= bs}
        order = self._admission_order()
        # Reset the live queue: preemptions during the loop appendleft
        # their resumes here (re-admitted next tick); deferred requests
        # are re-extended below.
        self._pending = collections.deque()
        admitted, keep = False, []
        blocked_pri: int | None = None
        for req in order:
            head = (tuple(req.prompt[:bs].tolist())
                    if req.prompt.size >= bs else None)
            if head is not None and head in heads \
                    and self._tree is not None:
                keep.append(req)
                continue
            if blocked_pri is not None and req.priority < blocked_pri:
                keep.append(req)
                continue
            if self._admit_or_preempt(req):
                admitted = True
                self._class_pass[req.priority] = \
                    self._class_pass.get(req.priority, 0.0) \
                    + 1.0 / (self.priority_weight_base ** req.priority)
                if head is not None:
                    heads.add(head)
            else:
                keep.append(req)
                if blocked_pri is None or req.priority > blocked_pri:
                    blocked_pri = req.priority
        # keep is in admission order — per-class FIFO is preserved,
        # which is the only order the scheduler depends on. Preempted
        # resumes (appendleft during the loop) stay at the front.
        self._pending.extend(keep)
        # drop stride state for classes with no backlog left so a
        # long-idle class can't bank credit
        live = {q.priority for q in self._pending}
        for c in [c for c in self._class_pass if c not in live]:
            del self._class_pass[c]
        return admitted

    def _start_chunk(self, slot_idx: int,
                     overlapped: bool = False) -> "_ChunkInFlight | None":
        """Build and enqueue the next chunk of a slot's prompt (and the
        draft pool's, where there is one). Alone in the tick
        (`overlapped` false) the chunk's token is read here, inside
        `engine/prefill_chunk`, which build, enqueue and wait then tile;
        behind a decode step of the same tick the span ends with the
        enqueue and `_finish_chunk` does the reading, once the step's
        tokens are out. None where the main pool holds the whole prompt
        already and only the draft pool catches up."""
        s = self._slots[slot_idx]
        flight = None
        if s.filled < s.prompt.size:
            clen = min(self.prefill_chunk, s.prompt.size - s.filled)
            cap = self._chunk_bucket_for(clen)
            phase = self._phases.phase
            with phase("engine/prefill_chunk", tokens=clen, bucket=cap,
                       overlapped=int(overlapped), start=s.filled) as chunk:
                with phase("engine/prefill_build", puts=1):
                    # keyed by the count before the tick's decode step:
                    # in the speculative tick this chunk follows that
                    # step and in the plain tick it goes before it, so
                    # there by the step before it on the device
                    step = self._decode_steps
                    if overlapped and self.spec is None:
                        step += (self._flight is not None) - 1
                    inputs = self._dev(pack_chunk(
                        s.prompt[s.filled:s.filled + clen], cap, s.table,
                        s.filled, s.temperature, step))
                with phase("engine/prefill_dispatch"):
                    tok, lp, self.cache, counts = self._prefill_fn(
                        self.params, inputs, self.cache, self._base_key)
                flight = _ChunkInFlight(tok, lp, counts, clen, cap, chunk,
                                        overlapped)
                if not overlapped:
                    self._read_chunk_token(flight)
            flight.enqueued = time.perf_counter()
        # Draft-model backend: the draft pool has no prefix sharing, so
        # it absorbs the FULL prompt through its own chunk loop — one
        # draft chunk per tick, alongside the main chunk. No host sync:
        # device dataflow orders these writes before the first propose.
        if self._draft_alloc is not None and \
                s.draft_filled < s.prompt.size:
            dclen = min(self.prefill_chunk,
                        s.prompt.size - s.draft_filled)
            dcap = self._chunk_bucket_for(dclen)
            with self._phases.phase("engine/draft_prefill_chunk",
                                    tokens=dclen, bucket=dcap):
                # the chunk's layout, its temperature and step unread
                inputs = self._dev(pack_chunk(
                    s.prompt[s.draft_filled:s.draft_filled + dclen], dcap,
                    s.draft_table, s.draft_filled, 0.0, 0))
                self.draft_cache = self._draft_prefill_fn(
                    self.draft_params, inputs, self.draft_cache)
            s.draft_filled += dclen
        return flight

    def _read_chunk_token(self, flight: "_ChunkInFlight") -> None:
        with self._phases.phase("engine/prefill_sync"):
            # graftlint: disable-next-line=R001,R004 the chunk's one deliberate sync: its token must reach the host to park on the slot, and the tick ends with nothing unread but its newest decode step. A chunk alone in its tick waits at once, inside engine/prefill_chunk, which keeps the prefill timing honest; a chunk among the decode steps waits after engine/emit, so its build and the emit lie under device time, the tick's two waits never overlap, and the step enqueued behind the chunk runs while the host ends the tick
            flight.tok = int(np.asarray(flight.tok))

    def _finish_chunk(self, slot_idx: int,
                      flight: "_ChunkInFlight | None") -> None:
        """The rest of a chunk `_start_chunk` enqueued: the wait for its
        token where that is still out, the counts, `filled`, and at the
        prompt's end the tree insert, the slot's turn to `decode` (or
        its hand-off) and the first token's `_emit`."""
        s = self._slots[slot_idx]
        if flight is not None:
            seconds = flight.span.seconds
            if flight.overlapped:
                self._read_chunk_token(flight)
                # the chunk's time runs on past its span, to this read
                tail = time.perf_counter() - flight.enqueued
                self._chunk_tail_s += tail
                seconds += tail
                self._chunks_overlapped += 1
            clen = flight.tokens
            self._add_counts(flight.counts)
            self._recorder.on_prefill_chunk(s.rid, clen, flight.bucket,
                                            seconds)
            self._prefill_tokens += clen
            self._prefill_chunks += 1
            s.filled += clen
            if s.filled >= s.prompt.size:
                # Park the first generated token (with its logprob and
                # compute-time version) until the draft cache (if any)
                # catches up and the slot joins decode.
                s.token = flight.tok
                # lp came with the token `_read_chunk_token` waited
                # for: a cast, not a second round-trip
                s.token_logp = float(flight.lp)
                s.token_ver = self._params_version
        if s.filled < s.prompt.size or (
                self._draft_alloc is not None
                and s.draft_filled < s.prompt.size):
            return
        self._publish_prompt(s)
        if self.role == "prefill":
            # Disaggregated handoff: the first token is sampled (TTFT
            # closes HERE — the decode side never re-counts it), then
            # the written blocks ship to host and the slot frees for
            # the next prompt. No decode phase ever runs on this
            # engine.
            if not s.resumed:
                wait = time.perf_counter() - s.submit_ts
                self._queue_waits.append(wait)
                self._class_waits[s.priority].append(wait)
                self._recorder.on_first_token(s.rid, wait)
            self._export_handoff(slot_idx)
            return
        self._first_token(slot_idx)

    def _publish_prompt(self, s: _Slot) -> None:
        """Prefill complete: publish the prompt's full blocks to the
        radix tree (decode writes only past them, so they are
        immutable). A slot admitted under an older params_version
        spanned a hot-swap mid-prefill — its K/V mixes weight versions
        and must NOT enter the prefix cache."""
        if self._tree is not None and s.prompt.size >= self.block_size \
                and s.version == self._params_version:
            self._tree.insert(s.prompt, s.blocks)

    def _first_token(self, slot_idx: int) -> None:
        """A slot whose prompt is absorbed and whose first token is
        parked on it joins the decode batch and emits that token."""
        s = self._slots[slot_idx]
        s.phase = "decode"
        s.pos = s.prompt.size
        s.remaining -= 1
        if not s.resumed:
            # A resumed (preempted) stream delivered its first token
            # long ago — re-counting its original submit_ts here would
            # poison the TTFT/queue-wait percentiles.
            wait = time.perf_counter() - s.submit_ts
            self._queue_waits.append(wait)
            self._class_waits[s.priority].append(wait)
            self._recorder.on_first_token(s.rid, wait)
        self._emit(s, slot_idx, s.token, s.token_logp, s.token_ver)

    def _decoding(self) -> list:
        return [i for i, s in enumerate(self._slots)
                if s.phase == "decode"]

    def _next_prefilling(self) -> int | None:
        """The prefilling slot admitted first: whose chunk runs next.
        Not the slot whose prompt ended in the program in flight
        (`_enqueue_fused`): it has no chunk left and turns to `decode`
        where that flight is read."""
        ended = self._flight.ended if self._flight is not None else None
        return min((i for i, s in enumerate(self._slots)
                    if s.phase == "prefill"
                    and (ended is None or i != ended.slot)),
                   key=lambda i: self._slots[i].order, default=None)

    def _prefill_tick(self, had_decoders: bool) -> bool:
        """The chunks of a tick whose decode step, if it has one, comes
        after them: each is built, enqueued and waited for before the
        next. With nothing decoding they drain freely (the ramp, an idle
        engine, a `role="prefill"` engine: nobody is waiting); at most
        ONE where the tick began with decoders and admission took them
        all. A tick that holds decoders and a prefilling slot does not
        come here: `_decode_with_chunk`."""
        did = False
        while (slot_idx := self._next_prefilling()) is not None:
            self._finish_chunk(slot_idx, self._start_chunk(slot_idx))
            did = True
            if had_decoders:
                break
        return did

    def _decode_with_chunk(self, decoding: list, slot_idx: int) -> float:
        """The tick that holds decoders and a prefilling slot: the
        slot's chunk is built and enqueued among the tick's decode
        programs (`_chain_tick`; behind the speculative tick's, which
        reads its own at once) and its token is read last, after the
        tokens the tick reads are emitted. Every program donates and
        returns `self.cache`, so the device runs them in the order of
        their enqueue with no gap; no step reads what a chunk of another
        slot writes, so the order changes no stream's tokens. A slot
        whose prompt ends here emits its first token in this tick.
        Where the family offers `tick` and the chunk is of the full
        bucket (the fused program's one shape), whether or not it ends
        its prompt, chunk and step are ONE program (`_enqueue_fused`),
        which reads every weight once: nothing of the chunk is read in
        this tick and it keeps no tick open; the token of one that ends
        its prompt comes with the step's, a tick later. Returns the
        seconds the chunk kept the tick open past the emit."""
        s = self._slots[slot_idx]
        if self._tick_fn is not None and self._step_follows() \
                and self._chunk_bucket_for(min(
                    self.prefill_chunk, s.prompt.size - s.filled)) \
                == self.prefill_chunk:
            self._chain_tick(fused=slot_idx)
            return 0.0
        flights = []

        def start_chunk():
            flights.append(self._start_chunk(slot_idx, overlapped=True))
            return flights[0]

        try:
            if self.spec is None:
                self._chain_tick(slot_idx, start_chunk)
            else:
                self._spec_tick(decoding, enqueued=start_chunk)
        finally:
            # whatever the emit raised (fault site `engine.emit`), the
            # chunk's result is not left unread when the tick ends
            t_emitted = time.perf_counter()
            if flights:
                self._finish_chunk(slot_idx, flights[0])
        return time.perf_counter() - t_emitted

    def _emit(self, s: _Slot, slot_idx: int, tok: int,
              logp: float = 0.0, ver: int | None = None):
        """Route one generated token (as a `TokenEvent` carrying its
        logprob and params_version); retire the slot (releasing its
        blocks) when finished."""
        # fault site: 'kill' here is the deterministic
        # kill-replica-at-step — the process dies between token N and
        # N+1, exactly what mid-stream failover must survive
        _faults.check("engine.emit")
        ev = TokenEvent(tok, logp,
                        self._params_version if ver is None else ver)
        if self._swap_pending_ts is not None:
            # First token computed after a hot-swap closes the
            # weight_swap_ms measurement window.
            self._last_swap_ms = (time.perf_counter()
                                  - self._swap_pending_ts) * 1e3
            self._swap_pending_ts = None
            self._recorder.on_swap_crossing(s.rid)
        hit_eos = s.eos_id is not None and tok == s.eos_id
        # pos of the *next* token; it must still fit in the cache row.
        finished = s.remaining <= 0 or hit_eos or s.pos + 1 >= self.max_len
        # the hand-over: a stream's last token and its end in one hold
        with self._delivery:
            self._out[s.rid].append(ev)
            if finished:
                self._done.add(s.rid)
            self._wake(s.rid)
        s.emitted.append(int(tok))
        cc = self._class_counter(s.priority)
        cc["decode_tokens"] += 1
        self._recorder.on_token(s.rid)
        if self.spec == "ngram":
            s.history.append(tok)
        if finished:
            cc["completed"] += 1
            self._release(slot_idx)
            self._recorder.on_finish(s.rid, "finished")

    def step(self) -> bool:
        """One scheduler tick: admit pending requests into free slots,
        then the tick's device work, in the order that what it holds
        allows. The decode step is chained on the device
        (`_chain_tick`): the last tick left step t enqueued and unread,
        and this one enqueues ONE prefill chunk if a prompt waits, then
        step t+1 (the two as one program where the family offers `tick`
        and the chunk is of the full bucket: `_enqueue_fused`; one that
        ends its prompt there has its token read with step t+1's), whose
        continuing rows take their tokens from step t's
        output where it lies, and only then waits for step t's tokens,
        emits them, and waits for the chunk's; so the host's work of a
        tick runs while the device has a step to run. With nothing
        decoding, every pending chunk runs, each waited for, and the
        sequences that thereby start decoding have their first step
        enqueued in the same tick. `step()` returns with AT MOST ONE
        DECODE STEP enqueued and unread (a fused chunk's token is an
        output of that step's program), and no other result:
        `update_params`, `cancel`, preemption, a hand-off's export,
        `check_invariants` and `run_until_idle`'s end read it first
        (`_rest`). The speculative tick proposes from the tokens on the
        host, so it reads its programs in the tick that enqueues them
        and leaves nothing. Returns True if any device work happened."""
        with self._lock:
            t_tick = time.perf_counter()
            # watchdog window: seq first, then start ts, cleared in the
            # finally — a fault-failed tick must not read as stuck forever
            self._tick_seq += 1
            self._tick_started = t_tick
            # the recorder tags its events with the tick they fall in:
            # that number joins them to this `engine/tick` annotation
            self._recorder.tick = self._tick_seq
            phase = self._phases.phase
            gap_us, carried = self._note_tick_gap(t_tick)
            try:
                with phase("engine/tick", tick=self._tick_seq,
                           gap_us=gap_us, carried=carried) as tick:
                    # fault site: 'fail' surfaces FaultInjected to the
                    # pumping consumer; 'delay' wedges the tick (what the
                    # watchdog exists to catch)
                    _faults.check("engine.tick")
                    # fault site: 'fail' forces preemption of the lowest-
                    # class active stream this tick (absorbed — consumers
                    # see only the token-identical resume)
                    try:
                        _faults.check("engine.preempt")
                    except _faults.FaultInjected:
                        self._force_preempt()
                    had_decoders = any(
                        s.phase == "decode" for s in self._slots)
                    if not had_decoders and self._flight is not None:
                        # every row of it has ended since (on eos_id):
                        # nothing to chain behind it, its tokens dropped
                        # (a prompt that ended in it decodes from here)
                        flight, self._flight = self._flight, None
                        self._read_step(flight)
                    with phase("engine/admit") as admit:
                        self._take_inbox()
                        seq = self._admit_seq
                        imported = self._admit_imports()
                        admitted = self._admit_pending() or imported
                        admit.set(admitted=self._admit_seq - seq)
                    t_admitted = time.perf_counter()
                    decoding = self._decoding()
                    slot_idx = self._next_prefilling()
                    # the order comes from what the tick holds: with
                    # decoders and a prompt to absorb, ONE chunk among
                    # the decode programs; else as ever, chunks then
                    # the step
                    overlap = bool(had_decoders and decoding
                                   and slot_idx is not None)
                    chunked = overlap or self._prefill_tick(had_decoders)
                    if not overlap:
                        # a prompt that ended in a chunk already waited
                        # for joins this tick's step
                        decoding = self._decoding()
                        if had_decoders and (admitted or chunked):
                            self._max_admission_stall = max(
                                self._max_admission_stall,
                                time.perf_counter() - t_tick)
                    active = sum(s.active for s in self._slots)
                    self._occupancy.append(active / self.num_slots)
                    self._block_util.append(
                        self._alloc.used / max(self.cache_blocks, 1))
                    tick.set(decoding=len(decoding),
                             prefilling=active - len(decoding))
                    if not decoding:  # idle, or admissions finished early
                        self._sentinel.check()
                        return admitted or chunked
                    if overlap:
                        # admission held this step up, the chunk's end
                        # the next
                        self._max_admission_stall = max(
                            self._max_admission_stall,
                            t_admitted - t_tick
                            + self._decode_with_chunk(decoding, slot_idx))
                    elif self.spec is not None:
                        self._spec_tick(decoding)
                    else:
                        self._chain_tick()
                    self._sentinel.check()
                    return True
            finally:
                self._tick_started = None
                self._tick_carried = bool(
                    self._inbox or self._pending or self._imports
                    or any(s.active for s in self._slots))
                self._tick_ended = time.perf_counter()

    def _note_tick_gap(self, t_tick: float) -> tuple[int, int]:
        """(`gap_us`, `carried`) of the tick that starts at `t_tick`:
        the time since the previous tick ended, and whether that tick
        left work behind (a slot active, a request pending or in the
        inbox). Only a carried gap is the engine's to answer for: it is
        counted into `tick_gap_s`, and into `pump_handoffs` where
        another thread than the previous tick's runs this one. A gap
        after a tick that left nothing is demand that was not there."""
        ident = threading.get_ident()
        ended, carried = self._tick_ended, int(self._tick_carried)
        gap = t_tick - ended if ended is not None else 0.0
        if carried:
            self._tick_gaps += 1
            self._tick_gap_s += gap
            self._tick_gap_max_s = max(self._tick_gap_max_s, gap)
            self._pump_handoffs += ident != self._tick_thread
        self._tick_thread = ident
        return int(gap * 1e6), carried

    def _dev(self, packed: np.ndarray):
        """A program's packed input (`pack_rows`, `pack_chunk`), host ->
        device in one transfer, counted in `host_puts`; replicated over
        the mesh when the engine runs on one."""
        self._host_puts += 1
        # graftlint: disable-next-line=R004 µs-scale host->device placement of one tiny per-program input; placing outside the lock would race slot state, and the transfer is async (no sync back)
        return self._jax.device_put(packed, self._io_sh)

    def _goes_on(self, s: _Slot) -> bool:
        """Whether a decoding slot whose next token is in flight decodes
        past it: `_emit`'s verdict less `eos_id`, from what the host
        knows before the token."""
        return s.remaining > 1 and s.pos + 2 < self.max_len

    def _batch_arrays(self, flight=None, joining=None):
        """Per-slot decode inputs ``(tokens, pos, tables, temps)`` and
        the step's rows (slot -> rid). Rows not decoding (idle or
        mid-prefill) point at the trash block with pos 0: their garbage
        write collides harmlessly there and their sampled token is
        never read.

        With `flight`, the step enqueued and unread, this is the step
        behind it, built from what the host knows without its values: a
        row of `flight` that goes on is marked `FROM_STEP` at `pos + 1`,
        one that ends with the token in flight by `remaining` or
        `max_len` is left out (one that ends on `eos_id` is known a
        step late: `_read_step`), and any other decoding slot joins from
        the host as ever. `joining` is the slot whose prompt's last
        chunk is enqueued and unread (a program of its own before this
        step, or inside `flight`'s): its row is marked `FROM_CHUNK`."""
        slots = self.num_slots
        tokens = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        tables = np.zeros((slots, self.max_blocks), np.int32)
        rows = {}
        for i, s in enumerate(self._slots):
            if s.phase == "decode":
                if flight is None or flight.rows.get(i) != s.rid:
                    tokens[i], pos[i] = s.token, s.pos
                elif self._goes_on(s):
                    tokens[i], pos[i] = FROM_STEP, s.pos + 1
                else:
                    continue
            elif i == joining:
                tokens[i], pos[i] = FROM_CHUNK, s.prompt.size
            else:
                continue
            tables[i] = s.table
            rows[i] = s.rid
        temps = np.array([s.temperature for s in self._slots],
                         np.float32)
        return (tokens, pos, tables, temps), rows

    def _put_rows(self, tokens, pos, tables, temps, step: int,
                  chunk=None):
        """A step's packed input (`pack_rows`; with `chunk`, a packed
        chunk of the same program behind it), put in a span of its own
        inside the caller's `engine/decode_build`."""
        packed = pack_rows(tokens, pos, temps, tables, step)
        if chunk is not None:
            packed = np.concatenate([packed, chunk])
        with self._phases.phase("engine/decode_put", puts=1):
            return self._dev(packed)

    def _decode_inputs(self, host, *, window=None, tables=None):
        """A verify or propose step's packed input on the device, as
        one span. `host` is `_batch_arrays`' four arrays; `window`
        takes the tokens' place for verify, `tables` the target pool's
        for the draft pool's propose."""
        with self._phases.phase("engine/decode_build"):
            tokens, pos, own_tables, temps = host
            return self._put_rows(
                tokens if window is None else window, pos,
                own_tables if tables is None else tables, temps,
                self._decode_steps)

    def _enqueue_step(self, behind=None, joining=None, chunk=None,
                      built=None, fused=None, ends=None) -> _StepInFlight:
        """Build (`_batch_arrays`, or `built`: its result), put and
        dispatch one decode step; nothing is waited for. `behind` is
        the step enqueued and unread whose output the `FROM_STEP` rows
        read (this step's key takes the counter after that step's) and
        `chunk` the chunk in flight whose token the `joining` slot's
        row reads. `fused` is a packed chunk (`pack_chunk`) that rides
        in the step's program (`_enqueue_fused`), and `ends` the
        (slot, rid) of the prompt it ends, if it ends one: the flight
        then carries that chunk's token beside the step's."""
        phase = self._phases.phase
        with phase("engine/decode_build"):
            (tokens, pos, tables, temps), rows = (
                built or self._batch_arrays(behind, joining))
            inputs = self._put_rows(
                tokens, pos, tables, temps,
                self._decode_steps + (behind is not None), fused)
        with phase("engine/decode_dispatch") as dispatch:
            program = self._decode_fn if fused is None else self._tick_fn
            nxt, lps, self.cache, counts, *last = program(
                self.params, self.cache, inputs, self._base_key,
                self._no_prev if behind is None else behind.nxt,
                self._no_chunk_tok if joining is None else chunk.tok)
            bound = self._family.bounded_tokens
            if bound and dispatch.is_enabled():
                # what the step's bounded pages hold of each decoding
                # stream: min(context, bound), which no sum of contexts
                # gives; summed only where a profiler session reads it
                dispatch.set(bounded_rows=sum(
                    min(int(pos[i]) + 1, bound) for i in rows))
        return _StepInFlight(nxt, lps, counts, rows,
                             int(np.count_nonzero(tokens < 0)),
                             self._params_version, dispatch.seconds,
                             ends and _PromptEnded(*ends, *last))

    def _read_step(self, flight: _StepInFlight) -> None:
        """Wait for a step's tokens and emit them, each stamped with the
        `params_version` the step ran under. A row whose slot has gone
        to another request since the enqueue ended on `eos_id` in the
        step before: its token is thrown away (its write went to the
        stream's own next position, and device order is enqueue order,
        so whoever took the blocks writes after it)."""
        phase = self._phases.phase
        with phase("engine/token_sync") as sync:
            # graftlint: disable-next-line=R001,R004 the decode tick IS the scheduler's unit of work: it must sync on the sampled tokens to route them, and the lock is held for exactly one tick by design. The plain tick waits here for the step the tick before enqueued, after it has enqueued the next one behind it, so the device has a step to run while the host routes these
            nxt = np.asarray(flight.nxt)    # device sync
            # graftlint: disable-next-line=R001,R004 same sync as nxt above — lps arrives in the same device batch, so this is a no-cost host view
            lps = np.asarray(flight.lps)
            self._add_counts(flight.counts)
            ended = flight.ended
            if ended is not None:
                # outputs of the program `nxt` waited for: casts, not a
                # second round-trip
                ended.tok, ended.lp = int(ended.tok), float(ended.lp)
        live = [i for i, rid in flight.rows.items()
                if self._slots[i].rid == rid]
        dt = flight.dispatch_s + sync.seconds
        self._step_times.append(dt)
        self._decode_steps += 1
        self._decode_tokens += len(live)
        self._decode_slot_steps += len(live)
        self._tok_window.append((dt, len(live)))
        with phase("engine/emit", tokens=len(live) + (ended is not None)):
            try:
                for i in live:
                    s = self._slots[i]
                    s.token, s.pos = int(nxt[i]), s.pos + 1
                    s.remaining -= 1
                    self._emit(s, i, s.token, float(lps[i]),
                               flight.version)
            except BaseException:
                # the rows not reached keep the host's token and
                # position; a step chained behind this one took them for
                # advanced, so it is dropped and the next joins from the
                # host, as after any failed emit
                self._flight = None
                raise
            finally:
                # whatever the emit raised, the prompt that ended in
                # this program is not left without its token: parked on
                # its slot (which nothing releases before this read), it
                # is emitted as `_finish_chunk` emits a chunk's
                if ended is not None:
                    s = self._slots[ended.slot]
                    s.token, s.token_logp = ended.tok, ended.lp
                    s.token_ver = flight.version
                    self._first_token(ended.slot)

    def _rest(self) -> None:
        """Under `_lock`: read the decode step in flight, if there is
        one (and with it the first token of a prompt that ended in its
        program), so that nothing is enqueued and unread. For whoever
        must find the engine at rest between two ticks
        (`chain_drains`)."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._chain_drains += 1
            self._read_step(flight)

    def _enqueue_fused(self, behind, slot_idx: int, joining=None,
                       chunk=None) -> _StepInFlight:
        """The tick's decode step and the next chunk of `slot_idx`'s
        prompt, one of the full bucket, as one program
        (`ServingFamily.tick`) inside `engine/tick_fused`. The flight is
        the step's: the chunk's counts come with the step's, the tick
        ends with nothing unread but the flight and the next chains
        behind it as behind a step. What `_finish_chunk` does without a
        chunk's token is done here, at the enqueue: device order is
        enqueue order, so whatever follows finds the chunk written.
        Where the chunk ends its prompt the flight carries its token
        (`_PromptEnded`: sampled under the chunk's temperature and the
        counter `_start_chunk` would give it): the slot stays out of
        this program's rows, joins the next as `FROM_CHUNK`
        (`_joining_behind`) and turns to `decode` where the flight is
        read (`_read_step`), one tick after a chunk of its own program
        would have. The span carries what `engine/prefill_chunk` does
        (`tokens`, the live ones; `bucket`; `start`: a reader of the
        chunk's attention needs them) and `ends_prompt`."""
        s = self._slots[slot_idx]
        clen = min(self.prefill_chunk, s.prompt.size - s.filled)
        ends = s.filled + clen >= s.prompt.size
        with self._phases.phase("engine/tick_fused", tokens=clen,
                                bucket=self.prefill_chunk, start=s.filled,
                                ends_prompt=int(ends)) as span:
            flight = self._enqueue_step(
                behind, joining, chunk,
                fused=pack_chunk(
                    s.prompt[s.filled:s.filled + clen], self.prefill_chunk,
                    s.table, s.filled, s.temperature,
                    self._decode_steps + (behind is not None) - 1),
                ends=(slot_idx, s.rid) if ends else None)
        self._recorder.on_prefill_chunk(s.rid, clen, self.prefill_chunk,
                                        span.seconds)
        self._prefill_tokens += clen
        self._prefill_chunks += 1
        self._ticks_fused += 1
        s.filled += clen
        if ends:
            self._ticks_fused_last += 1
            self._publish_prompt(s)
        return flight

    def _joins(self, s: _Slot) -> bool:
        """`_finish_chunk`'s verdict on a slot whose prompt's last chunk
        is enqueued and unread, from what the host knows without its
        token: whether the slot decodes past it."""
        return s.remaining > 1 and s.prompt.size + 1 < self.max_len

    def _joining_behind(self, behind) -> int | None:
        """The slot whose prompt ended inside `behind`, the step in
        flight, where it decodes on: the step enqueued behind it holds
        its row."""
        if behind is None or behind.ended is None \
                or not self._joins(self._slots[behind.ended.slot]):
            return None
        return behind.ended.slot

    def _step_follows(self) -> bool:
        """Whether a plain tick enqueues a decode step: where none is in
        flight, a slot decodes; behind one in flight, where a decoding
        slot joins or goes on past the token in flight (else every
        stream ends with that token) or a prompt that ended in its
        program decodes on."""
        behind = self._flight
        return behind is None \
            or self._joining_behind(behind) is not None or any(
                s.phase == "decode" and (behind.rows.get(i) != s.rid
                                         or self._goes_on(s))
                for i, s in enumerate(self._slots))

    def _chain_tick(self, chunk_slot: int | None = None,
                    start_chunk=None, fused: int | None = None) -> None:
        """The plain tick's decode programs: this tick's chunk where it
        has one (`start_chunk` enqueues it), then the next decode step,
        and only then the wait for the tokens of the step the last tick
        left unread, and their emit. That next step is chained behind
        the unread one inside `engine/decode_chain` (`_batch_arrays`:
        its rows are the rows it would have had with the other read
        first, the chunk's slot among them if its prompt ends here);
        with nothing unread (the first step, the first after `_rest`)
        its rows join from the host and the pipeline is full again. The
        device runs chunk, step, chunk, step either way. The step is
        left unread; the chunk's token is the caller's to read
        (`_decode_with_chunk`). `fused` is the slot whose chunk rides in
        the step's own program instead (`_enqueue_fused`). A step has
        one `chunk_tok`: where a prompt ended inside the step in flight
        its slot is the one that joins, and the slot of this tick's own
        last chunk joins from the host a tick later, its token read."""
        behind = self._flight
        joining = self._joining_behind(behind)
        chunk = behind.ended if joining is not None else None

        def enqueue():
            if fused is not None:
                return self._enqueue_fused(behind, fused, joining, chunk)
            return self._enqueue_step(behind, joining, chunk)

        if start_chunk is not None:
            started = start_chunk()
            s = self._slots[chunk_slot]
            if joining is None and started is not None \
                    and s.filled + started.tokens >= s.prompt.size \
                    and self._joins(s):
                joining, chunk = chunk_slot, started
        if behind is None:
            self._flight = enqueue()
            return
        if joining is not None or self._step_follows():
            with self._phases.phase("engine/decode_chain") as chain:
                self._flight = enqueue()
                self._steps_chained += 1
                chain.set(rows=self._flight.chained)
        else:
            # every stream ends with the token in flight
            self._flight = None
        self._read_step(behind)

    def _decode_tick(self, host, enqueued=None):
        """One decode step for every decoding slot, read in the tick
        that enqueues it: the speculative tick's fallback where nothing
        is worth speculating on. `host` is what `_batch_arrays` gave
        it; `enqueued`, where given, is called once the step is in the
        device's queue and before its tokens are waited for
        (`_decode_with_chunk`)."""
        flight = self._enqueue_step(built=host)
        if enqueued is not None:
            enqueued()
        self._read_step(flight)

    def _add_counts(self, counts) -> None:
        """Sum what a prefill or decode program counted (after the
        tick's own sync: the vector came with the token)."""
        if counts is None:
            return
        # graftlint: disable-next-line=R001,R004 a few int32 that arrived with the token this tick already waited for
        counts = np.asarray(counts, np.int64)
        self._model_counts = (counts if self._model_counts is None
                              else self._model_counts + counts)

    def _ngram_propose(self, s: _Slot) -> list | None:
        """Prompt-lookup proposal: find the longest n-gram (ngram_max
        down to ngram_min) whose latest earlier occurrence in the
        request's own prompt+output history matches the current suffix,
        and propose the up-to-k tokens that followed it."""
        h, n_hist = s.history, len(s.history)
        for n in range(min(self.ngram_max, n_hist - 1),
                       self.ngram_min - 1, -1):
            suf = h[-n:]
            for i in range(n_hist - n - 1, -1, -1):
                if h[i:i + n] == suf:
                    return h[i + n:i + n + self.spec_k]
        return None

    def _spec_tick(self, decoding: list, enqueued=None):
        """One speculative device step: propose (n-gram host lookup or
        one jitted draft-model scan), verify the whole window in ONE
        batched target forward, emit `accepted + 1` tokens per slot.
        Falls back to the plain decode step when nothing is worth
        speculating on, so both paths stay compiled-exactly-once.
        `enqueued` as in `_decode_tick`: called once verify (or the
        fallback's step) is in the device's queue."""
        W = self.spec_window
        phase = self._phases.phase
        # Slots one token from retiring can't use speculation (and, for
        # the draft backend, retire before their stale draft cache
        # could ever be consulted again).
        worth = [i for i in decoding
                 if self._slots[i].remaining >= 2]
        proposals: dict[int, list] = {}
        with phase("engine/decode_build"):
            built = self._batch_arrays()
            tokens, *_ = host = built[0]
        with phase("engine/propose") as propose:
            if self.spec == "ngram":
                for i in worth:
                    prop = self._ngram_propose(self._slots[i])
                    if prop is not None:
                        proposals[i] = prop
                if proposals:
                    # Junk default (repeat the current token) for rows
                    # without a proposal; any accidental accepts are
                    # still exact.
                    drafts = np.repeat(tokens[:, None], W - 1, axis=1)
                    for i, prop in proposals.items():
                        drafts[i, :] = (
                            prop + [prop[-1]] * (W - 1))[:W - 1]
            elif worth:
                zeros = np.zeros((self.max_blocks,), np.int32)
                dtables = np.stack(
                    [s.draft_table if s.phase == "decode" else zeros
                     for s in self._slots])
                dj, self.draft_cache = self._propose_fn(
                    self.draft_params, self.draft_cache,
                    self._decode_inputs(host, tables=dtables),
                    self._base_key)
                # graftlint: disable-next-line=R001,R004 draft proposals must reach the host to build the verify window; one sync per spec tick, same budget as the plain decode tick's
                drafts = np.asarray(dj)
                for i in worth:
                    proposals[i] = drafts[i].tolist()
        if not proposals:
            self._decode_tick(built, enqueued)
            return
        window = np.concatenate([tokens[:, None], drafts], axis=1)
        inputs = self._decode_inputs(host, window=window)
        with phase("engine/verify_dispatch") as verify:
            out, out_lp, acc, self.cache = self._verify_fn(
                self.params, self.cache, inputs, self._base_key)
        if enqueued is not None:
            enqueued()
        with phase("engine/token_sync") as sync:
            # graftlint: disable-next-line=R001,R004 the spec tick's one deliberate sync: accepted tokens must reach the host to emit; replaces W plain-tick syncs
            out, acc = np.asarray(out), np.asarray(acc)   # device sync
            # graftlint: disable-next-line=R001,R004 same device batch as out/acc above — already materialized, no extra round-trip
            out_lp = np.asarray(out_lp)
        dt = propose.seconds + verify.seconds + sync.seconds
        self._step_times.append(dt)
        self._decode_steps += 1
        self._spec_steps += 1
        self._decode_slot_steps += len(decoding)
        emitted = 0
        with phase("engine/emit") as emit:
            for i in decoding:
                s = self._slots[i]
                if i in proposals:
                    self._spec_proposed += W - 1
                    self._spec_accepted += int(acc[i])
                for j in range(int(acc[i]) + 1):
                    if self._slots[i] is not s:
                        break   # slot retired mid-window (eos/len/budget)
                    tok = int(out[i, j])
                    s.token, s.pos = tok, s.pos + 1
                    s.remaining -= 1
                    self._decode_tokens += 1
                    emitted += 1
                    self._emit(s, i, tok, float(out_lp[i, j]))
            emit.set(tokens=emitted)
        self._tok_window.append((dt, emitted))

    def run_until_idle(self):
        """Drive the scheduler until every submitted request finished."""
        while True:
            with self._lock:
                busy = self._inbox or self._pending or any(
                    s.active for s in self._slots)
                if not busy:
                    # a row that ended on eos_id left a step behind it
                    self._rest()
                    return
                self.step()

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def check_invariants(self):
        """Allocator/tree/slot cross-checks for the fuzz tests: every
        allocated block is accounted for by exactly its holders; an int8
        pool's scale arrays must additionally track their payload's
        block geometry exactly (one f32 scale per (position, head) row —
        refcounts need no separate audit because scales share the
        payload's block axis and ride the same copy/evict/free paths).
        Reads a decode step in flight first: the checks are of an engine
        at rest."""
        with self._lock:
            self._rest()

        def _audit_scales(pool, label):
            if pool is None or "k_scale" not in pool:
                return
            for nm in ("k", "v"):
                pay, sc = pool[nm], pool[nm + "_scale"]
                assert tuple(sc.shape) == tuple(pay.shape[:-1]), \
                    f"{label}{nm}_scale shape {tuple(sc.shape)} != " \
                    f"payload rows {tuple(pay.shape[:-1])}"
                assert str(sc.dtype) == "float32", \
                    f"{label}{nm}_scale dtype {sc.dtype} != float32"
                assert str(pay.dtype) == "int8", \
                    f"{label}{nm} payload dtype {pay.dtype} != int8 " \
                    f"despite scale arrays present"

        _audit_scales(self.cache, "")
        _audit_scales(self.draft_cache, "draft ")
        self._alloc.check()
        holds = collections.Counter()
        for s in self._slots:
            holds.update(s.blocks)
            ring = sum(self._alloc.kind_of(b) == "bounded"
                       for b in s.blocks)
            assert ring <= self._ring, \
                f"rid {s.rid} holds {ring} bounded pages, bound {self._ring}"
        if self._tree is not None:
            for nd in self._tree._nodes():
                holds.update(nd.blocks)
        for b in range(1, self._alloc.n_blocks):
            assert self._alloc.refcount(b) == holds[b], \
                f"block {b}: refcount {self._alloc.refcount(b)} != " \
                f"{holds[b]} holders"
        if self._draft_alloc is not None:
            self._draft_alloc.check()
            dholds = collections.Counter()
            for s in self._slots:
                dholds.update(s.draft_blocks)
            for b in range(1, self._draft_alloc.n_blocks):
                assert self._draft_alloc.refcount(b) == dholds[b], \
                    f"draft block {b}: refcount " \
                    f"{self._draft_alloc.refcount(b)} != {dholds[b]}"
        # Preempted-stream state: after any preempt→resume→cancel
        # interleaving, a request must live in exactly one place and
        # every output queue must still be owned by someone — a leaked
        # `_out` deque (or an errored rid still scheduled) would pin
        # consumer state forever.
        queued = list(self._pending) + list(self._inbox)
        pend_rids = [q.rid for q in queued]
        assert len(pend_rids) == len(set(pend_rids)), \
            f"duplicate pending rids: {pend_rids}"
        slot_rids = [s.rid for s in self._slots if s.active]
        assert len(slot_rids) == len(set(slot_rids)), \
            f"duplicate slot rids: {slot_rids}"
        assert not set(pend_rids) & set(slot_rids), \
            "rid both pending and active"
        for rid in pend_rids + slot_rids:
            assert rid in self._out, f"rid {rid} has no output queue"
            assert rid not in self._done, f"rid {rid} done but scheduled"
        for rid in self._errors:
            assert rid in self._out, f"errored rid {rid} has no queue"
            assert rid not in set(pend_rids) | set(slot_rids), \
                f"errored rid {rid} still scheduled"
        # Disaggregation registries: a queued import owns a live output
        # queue and must not be scheduled anywhere else yet; a parked
        # handoff's slot/queue were already released at export, so its
        # rid must appear NOWHERE else.
        import_rids = {irid for irid, _ in self._imports}
        assert import_rids == self._import_rids, \
            f"import registry drift: {import_rids} != {self._import_rids}"
        assert not import_rids & (set(pend_rids) | set(slot_rids)), \
            "import rid also pending/active"
        for irid in import_rids:
            assert irid in self._out, f"import rid {irid} has no queue"
        handoff_rids = set(self._handoffs)
        assert not handoff_rids & (set(pend_rids) | set(slot_rids)
                                   | import_rids), \
            "handoff rid still scheduled"
        for hrid in handoff_rids:
            assert hrid not in self._out, \
                f"handoff rid {hrid} still owns an output queue"
        owners = set(pend_rids) | set(slot_rids) | self._done \
            | set(self._errors) | import_rids
        for rid in self._out:
            assert rid in owners, f"orphaned output queue for rid {rid}"
        for q in queued:
            assert 0 <= q.priority < self.priority_classes
            assert q.max_new_tokens >= 1, \
                f"rid {q.rid} requeued with no token budget"

    def reset_stats(self):
        """Zero the throughput/latency accounting — benches call this
        after warmup so compile time stays out of the timed region.
        NOT reset: the trace counters (`*_traces`, `swap_traces`), the
        cache itself, and `params_version` — version is identity, not a
        rate; a learner correlating trajectory tags against
        `stats()["params_version"]` must not see it rewind. The windowed
        `swaps` counter and `weight_swap_ms` DO reset."""
        with self._before_pump(), self._lock:
            # a step in flight belongs to the window that enqueued it
            self._rest()
            self._decode_steps = self._host_puts = 0
            self._steps_chained = self._chain_drains = 0
            self._model_counts = None
            self._prefill_tokens = self._decode_tokens = 0
            self._phases.clear()
            # the next tick starts a new count: no gap before it
            self._tick_ended, self._tick_carried = None, False
            self._tick_gaps = self._pump_handoffs = 0
            self._tick_gap_s = self._tick_gap_max_s = 0.0
            self._recorder.deliver_waits.clear()
            self._prefill_chunks = self._chunks_overlapped = 0
            self._chunk_tail_s = 0.0
            self._ticks_fused = self._ticks_fused_last = 0
            self._prefix_hit_tokens = self._prompt_tokens = 0
            self._cow_copies = self._evicted_blocks = 0
            self._bounded_reused = 0
            self._cancelled = 0
            self._max_admission_stall = 0.0
            self._step_times.clear()
            self._occupancy.clear()
            self._block_util.clear()
            self._tok_window.clear()
            self._queue_waits.clear()
            self._decode_slot_steps = 0
            self._spec_steps = 0
            self._spec_proposed = self._spec_accepted = 0
            self._swaps = 0
            self._last_swap_ms = 0.0
            self._sheds = 0
            self._watchdog_stalls = 0
            self._handoffs_exported = 0
            self._imports_completed = 0
            self._handoffs_abandoned = 0
            self._kv_blocks_exported = self._kv_blocks_imported = 0
            self._kv_export_bytes = self._kv_import_bytes = 0
            self._kv_export_ms.clear()
            self._kv_import_ms.clear()
            self._preemptions = 0
            self._reprefill_blocks = 0
            self._aging_promotions = 0
            # Zero per-class counters in place and clear wait windows —
            # the dicts themselves must survive (admitted slots index
            # into `_class_waits` by class on prefill completion).
            for cc in self._per_class.values():
                for k in cc:
                    cc[k] = 0
            for w in self._class_waits.values():
                w.clear()

    def stats(self) -> dict:
        """The engine's one stats contract — this dict feeds the serve
        autoscaler (`autoscaler.load_metrics.
        replica_demands_from_engine_stats`), the benchmark's serving
        cells (`benchmarks/harness/serve_replica.ENGINE_STATS`), the RL
        flywheel's staleness accounting, and a profile: under an open
        `jax.profiler` session each call writes one `engine/counters`
        span that carries every entry of this dict whose value is an int
        or a float (no bool, string or nested dict: `role`, `spec`,
        `prefix_cache`, `per_class` stay out) under its own key, what the
        family's programs counted (`ServingFamily.counts`) among them:
        a window's totals on the device planes' clock, and a series
        where a controller polls. The rule is the value's type, so a new entry
        needs no other edit; with no session on nothing is built
        (`benchmarks/layer_metrics/span_counter_ratio.py` reads it).
        Keys:

        Scheduler/throughput:
          ``slots`` / ``active`` / ``pending`` — slot capacity, occupied
          slots, queued (unadmitted) requests, those no tick has seen
          yet included.
          ``decode_steps`` — decode/verify steps read since reset (the
          plain tick reads a step in the tick after the one that
          enqueued it; one enqueued and unread is not counted yet).
          ``steps_chained`` — of the plain tick's steps, those enqueued
          behind a step whose tokens were still unread, their continuing
          rows fed from that step's output on the device (each inside an
          `engine/decode_chain` span): every step but the pipeline's
          fills, the first step and the first after each drain.
          ``chain_drains`` — reads of the step in flight forced by
          whoever needed the engine at rest between two ticks
          (`update_params`, a `cancel` or a preemption of a live stream,
          `check_invariants`, `run_until_idle`'s end).
          ``host_puts`` — host-to-device transfers made for the
          programs' inputs since reset: one a decode, verify, propose,
          prefill or fused program (`pack_rows`, `pack_chunk`), so
          ``decode_steps + prefill_chunks - ticks_fused`` where nothing
          speculates with a draft model.
          ``prefill_tokens`` / ``decode_tokens`` — tokens absorbed /
          emitted since reset; ``prefill_time_s`` / ``decode_time_s``
          the device time attributed to each: what the recorder is told
          each chunk took, from the start of its build to the read of
          its token (the prefill-chunk spans, and for a chunk enqueued
          behind a decode step the time from its span's end to that
          read, which the step's wait and emit share), and the totals of
          the decode/verify dispatch, propose and token-sync spans
          (below).
          ``prefill_chunks`` — chunked-admission device calls;
          ``chunks_overlapped`` — those enqueued behind a decode step of
          the device's queue and built while the device ran it (their
          `engine/prefill_chunk` carries ``overlapped=1``): every chunk
          of a tick that held a decoder, none of a tick that held none,
          less the fused ones: where the family offers `tick`, the last
          chunks of a smaller bucket than the full one.
          ``ticks_fused`` — ticks whose chunk and decode step were ONE
          program (`ServingFamily.tick`, inside `engine/tick_fused`):
          where the family offers it, every chunk of the full bucket in
          a tick that held a decoder. Such a chunk is counted in
          ``prefill_chunks`` and ``prefill_tokens`` at its enqueue, and
          its device time lies in the step's (``decode_time_s``), not in
          ``prefill_time_s``. ``ticks_fused_last`` — those of them whose
          chunk ended its prompt: its first token is read with the
          step's tokens, a tick later, and its slot's first decode row
          takes it where it lies (`FROM_CHUNK`).
          ``slot_occupancy`` — mean fraction of slots active per tick.
          ``p50_token_latency_ms`` / ``p99_token_latency_ms`` —
          percentiles over a 512-step window of a step's dispatch plus
          the wait for its tokens, which for a chained step lie in two
          ticks with the host's work between them: no longer a tick's
          length.

        Compile-once accounting (NEVER reset — identity, not rate):
          ``prefill_traces`` / ``decode_traces`` / ``tick_traces`` /
          ``verify_traces`` / ``draft_traces`` / ``draft_prefill_traces``
          — python traces of each jitted path; tests pin decode/verify
          to 1 per lifetime, and the fused tick sees one chunk bucket.
          ``swap_traces`` — traces of the hot-swap copy fn (once per
          distinct pytree: target and draft each trace once, ever).
          ``load_traces`` — traces of the family's load-time fn, which
          casts the masters to the activation dtype and quantizes the
          int8 ones (0 where nothing ran: no such fn, or a tree given
          in the dtype the steps read; else once per distinct tree —
          target and draft each at most once, however many hot-swaps
          re-run it).

        Paged cache:
          ``block_size`` / ``cache_blocks`` / ``blocks_in_use`` /
          ``blocks_free`` — pool geometry and live allocation.
          For a family with state blocks (`ServingFamily.state_blocks`)
          these count both kinds together, a sequence's state blocks and
          its pages; ``state_blocks`` / ``state_blocks_in_use`` are the
          state blocks' part (``blocks_free`` counts pages, or for a
          family without pages the state blocks). A state block costs
          the same at any length: ``kv_bytes_per_token`` is a page's
          bytes a token plus a sequence's state over the `max_len`
          tokens it may stand for. Without pages ``block_size`` is the
          constructor's argument and sizes nothing.
          For a family with bounded pages (`ServingFamily.bounded_tokens`,
          a window layer's) ``bounded_blocks`` / ``bounded_blocks_in_use``
          are that kind's pages held by the pool and by requests,
          ``bounded_ring`` the most one request holds (the window and a
          chunk, in pages); ``blocks_free`` then counts the pages that
          grow alone, and ``kv_bytes_per_token`` counts a bounded page's
          bytes like a page's, which a sequence past the bound no longer
          pays. ``bounded_pages_reused`` counts the logical pages past
          their request's ring, each of which took a ring page written
          before, in place; a request adds its own when it leaves its
          slot (finished, cancelled or preempted).
          ``prefix_cache`` — whether a radix tree is kept (never for a
          family of state blocks).
          ``cached_prefix_blocks`` — blocks the radix tree holds.
          ``cache_block_utilization`` — mean pool utilization per tick.
          ``prefix_hit_rate`` / ``prefix_hit_tokens`` — prompt tokens
          admitted by cache reference instead of prefill.
          ``cow_copies`` — mid-block copy-on-write splits.
          ``evicted_blocks`` — blocks LRU-evicted under pressure.
          ``cancelled`` — requests cancelled/abandoned.
          ``max_admission_stall_ms`` — worst single-tick admission work
          while anything was decoding.
          ``pool_bytes`` — total device bytes of the preallocated block
          pool(s), payload plus any int8 scale arrays (draft pool
          included); fixed at construction.
          ``weight_bytes`` — device bytes of the tree(s) the steps read
          (a draft model's included), after the load-time fn: half the
          f32 masters' for bf16 activations; fixed at construction.
          ``kv_bytes_per_token`` — main-pool bytes one cached position
          costs (all layers, K+V, scales included) — the capacity
          lever `kv_dtype="int8"` pulls (~4x down vs an f32 pool).

        Autoscaler load signals:
          ``queue_depth`` — unadmitted requests (demand ~ inflight +
          queue_depth); ``decode_tok_s`` — windowed emission rate;
          ``queue_wait_ms_p50`` / ``queue_wait_ms_p99`` — submit to
          first token.

        Telemetry (util.telemetry flight recorder + retrace sentinel):
          ``ttft_ms_p50`` / ``ttft_ms_p99`` — time-to-first-token
          percentiles, the canonical latency names over the same
          submit-to-first-token window as queue_wait_ms_* (which stay
          for the autoscaler contract).
          ``retraces_unexpected`` — traces of pinned compile-once paths
          beyond their allowance (NEVER reset; nonzero means a
          compile-once guarantee broke at runtime — each violation also
          logs one WARN).

          ``deliver_wait_ms_p50`` / ``deliver_wait_ms_p99`` — first
          token made (the recorder's first_token) to first token handed
          to the stream's consumer by `tokens_for` (its first_yield),
          over the last 512 sampled requests: what a stream waits, once
          its token exists, for the tick that made it to end and for its
          consumer to wake. ``ttft_ms_*`` stops at the engine's edge;
          this is the step past it.

        Program spans (util.telemetry.Phases; each is also an annotation
        of the same name in a `jax.profiler` trace, on the device
        planes' clock — PERF.md, section 3; seconds since reset):
          ``ticks`` / ``tick_s`` — `engine/tick`: scheduler ticks and
          their wall time under the lock.
          ``admit_s`` — `engine/admit`: import and pending admission.
          ``decode_build_s`` — `engine/decode_build`: the per-slot input
          arrays, their packing and the put; ``decode_put_s`` —
          `engine/decode_put`, inside it: the one put alone (``puts=1``
          on the span).
          ``prefill_build_s`` / ``prefill_dispatch_s`` /
          ``prefill_sync_s`` — `engine/prefill_build`,
          `engine/prefill_dispatch`, `engine/prefill_sync`: the chunk's
          packed input and its one put, enqueueing the program, and the
          host blocked until the chunk's token is back (the device's
          time shows here). Alone in its tick the three tile
          `engine/prefill_chunk`; behind a decode step the span holds
          the first two and the wait comes after `engine/emit`, so the
          two waits of a tick never overlap.
          ``decode_dispatch_s`` — `engine/decode_dispatch` and
          `engine/verify_dispatch`: enqueueing the decode/verify step.
          ``token_sync_s`` — `engine/token_sync`: waiting for the
          sampled tokens on the host (the device's time shows here).
          ``emit_s`` — `engine/emit`: routing tokens to their streams.
          ``stream_waits`` / ``stream_wait_s`` — `stream/wait`: times a
          consumer (`tokens_for`, `handoff_for`) slept until a tick that
          another consumer ran had ended, and the total it slept:
          consumers asleep, not contending.
          ``submits`` / ``submit_s`` — `engine/submit`: calls of
          `submit`, validation and refusals included, and their time.

        The gap between two ticks (taken by `step()` itself; each
        `engine/tick` annotation carries its own as `gap_us` and
        `carried`):
          ``tick_gaps`` / ``tick_gap_s`` / ``tick_gap_max_s`` — ticks
          that began after a tick that left work behind (a slot active,
          a request pending or in the inbox), and the time from that
          tick's end to their start, in total and at the most: the
          engine standing still with work to do, while one consumer
          hands the pump to the next. A gap after a tick that left
          nothing is counted nowhere. The mean is ``tick_gap_s`` /
          ``tick_gaps``; beside ``tick_s`` / ``ticks`` it says what
          share of a token's gap no tick was running.
          ``pump_handoffs`` — of those ticks, the ones run by another
          thread than the tick before: near ``tick_gaps`` where many
          consumers take turns at the pump, near 0 where one stream's
          consumer runs every tick.

        Speculative decoding:
          ``spec`` / ``spec_k`` — backend ('' when off) and window.
          ``spec_steps`` — verify ticks; ``acceptance_rate`` — accepted
          / proposed drafts; ``tokens_per_step`` — emitted tokens per
          decoding-slot-step (1.0 when spec is off).

        RL flywheel:
          ``params_version`` — monotonically increasing weight version;
          bumped by `update_params`, stamped on every `TokenEvent`,
          survives `reset_stats`.
          ``swaps`` — hot-swaps since reset.
          ``weight_swap_ms`` — last measured update_params-call to
          first-post-swap-token latency (0.0 until a post-swap token
          lands).

        Fault tolerance (serve-plane robustness counters):
          ``sheds`` — admissions refused with `OverloadedError` because
          the pending queue hit the `max_queue` knob or projected
          block-pool utilization crossed `shed_high_water` (both 0 when
          the knobs are off — the default).
          ``watchdog_stalls`` — scheduler ticks the watchdog thread saw
          overrun the `watchdog_s` budget (always present; 0 with the
          watchdog disabled). Each stall also logs one WARN.

        Disaggregated prefill/decode (role-specialized serving):
          ``role`` — this engine's role: 'colocated' (default) /
          'prefill' (chunked prefill only, exports KV handoffs) /
          'decode' (colocated behavior + import target; the tag
          drives role-aware routing and per-role autoscaling).
          ``handoffs`` — prompts prefilled and exported as KV blobs
          since reset; ``imports`` — handoffs adopted into this pool.
          ``handoffs_abandoned`` — exported blobs cancelled before
          collection. ``handoffs_pending`` / ``imports_queued`` —
          blobs parked awaiting pickup / imports awaiting a slot
          (imports also count into ``queue_depth``: they are demand
          exactly like queued prompts).
          ``kv_blocks_exported`` / ``kv_blocks_imported`` — paged KV
          blocks gathered to host / scattered into this pool;
          ``kv_export_bytes`` / ``kv_import_bytes`` the host bytes
          moved (payload + int8 scale rows).
          ``kv_export_ms_p50`` / ``kv_export_ms_p99`` /
          ``kv_import_ms_p50`` / ``kv_import_ms_p99`` — per-handoff
          device->host gather / host->device scatter latency
          percentiles over a 256-handoff window.
          ``kv_gather_traces`` / ``kv_scatter_traces`` — compile-once
          counters for the block transport jits (NEVER reset; at most
          one trace per pool geometry — two with a draft pool —
          sentinel-enforced like ``decode_traces``).

        Priority / preemption (multi-tenant plane):
          ``priority_classes`` — number of configured classes (identity,
          not rate; class c+1 outranks class c).
          ``preemptions`` — active streams evicted mid-flight for a
          higher class (or a forced fault site) since reset; each one
          requeues as a chunked re-prefill and resumes token-identical.
          ``reprefill_blocks`` — KV blocks re-filled on resume that the
          radix cache did NOT cover (the true cost of preemption; 0
          when the preempt-time tree insert survives to re-admission).
          ``aging_promotions`` — starvation-guard escalations: requests
          whose queue wait exceeded the per-class aging bound and were
          admitted ahead of stride order.
          ``per_class`` — dict keyed by class id (str) with per-class
          'submitted' / 'completed' / 'sheds' / 'preemptions' /
          'decode_tokens' counters plus 'pending' / 'active' occupancy
          and 'queue_wait_ms_p50' / 'queue_wait_ms_p99' over a
          256-request window — the fairness/usage series the telemetry
          bridge fans out as class-tagged gauges. (Double backticks are
          for this dict's own keys only: the contract test reads them.)
        """
        with self._before_pump(), self._lock:
            self._sentinel.check()   # surface retraces since last tick
            self._take_inbox()
            per_class = {}
            pend_by = collections.Counter(q.priority for q in self._pending)
            act_by = collections.Counter(
                s.priority for s in self._slots if s.active)
            for c in sorted(set(self._per_class) | set(pend_by)
                            | set(act_by)):
                cw = sorted(self._class_waits.get(c, ()))

                def cpct(p, _cw=cw):
                    if not _cw:
                        return 0.0
                    return _cw[min(len(_cw) - 1,
                                   int(p / 100 * len(_cw)))] * 1e3
                per_class[str(c)] = {
                    **{k: v for k, v in self._per_class.get(c, {}).items()},
                    "pending": pend_by.get(c, 0),
                    "active": act_by.get(c, 0),
                    "queue_wait_ms_p50": cpct(50),
                    "queue_wait_ms_p99": cpct(99),
                }
            times = sorted(self._step_times)
            occ = list(self._occupancy)
            util = list(self._block_util)
            waits = sorted(self._queue_waits)
            win_t = sum(dt for dt, _ in self._tok_window)
            win_toks = sum(n for _, n in self._tok_window)

            def pct(p):
                if not times:
                    return 0.0
                return times[min(len(times) - 1,
                                 int(p / 100 * len(times)))] * 1e3

            def wpct(p):
                if not waits:
                    return 0.0
                return waits[min(len(waits) - 1,
                                 int(p / 100 * len(waits)))] * 1e3

            ph = self._phases
            delivered = sorted(self._recorder.deliver_waits)

            def dpct(p):
                if not delivered:
                    return 0.0
                return delivered[min(len(delivered) - 1,
                                     int(p / 100 * len(delivered)))]

            exp_ms = sorted(self._kv_export_ms)
            imp_ms = sorted(self._kv_import_ms)

            def xpct(p):
                if not exp_ms:
                    return 0.0
                return exp_ms[min(len(exp_ms) - 1,
                                  int(p / 100 * len(exp_ms)))]

            def ipct(p):
                if not imp_ms:
                    return 0.0
                return imp_ms[min(len(imp_ms) - 1,
                                  int(p / 100 * len(imp_ms)))]
            out = {
                "slots": self.num_slots,
                "active": sum(s.active for s in self._slots),
                "pending": len(self._pending),
                "decode_steps": self._decode_steps,
                "steps_chained": self._steps_chained,
                "chain_drains": self._chain_drains,
                "host_puts": self._host_puts,
                "prefill_tokens": self._prefill_tokens,
                "decode_tokens": self._decode_tokens,
                "prefill_time_s": ph.seconds(
                    "engine/prefill_chunk", "engine/draft_prefill_chunk")
                + self._chunk_tail_s,
                "decode_time_s": ph.seconds(
                    "engine/decode_dispatch", "engine/verify_dispatch",
                    "engine/propose", "engine/token_sync"),
                "prefill_traces": self.prefill_traces,
                "decode_traces": self.decode_traces,
                "prefill_chunks": self._prefill_chunks,
                "chunks_overlapped": self._chunks_overlapped,
                "ticks_fused": self._ticks_fused,
                "ticks_fused_last": self._ticks_fused_last,
                "tick_traces": self.tick_traces,
                "slot_occupancy": (sum(occ) / len(occ)) if occ else 0.0,
                "p50_token_latency_ms": pct(50),
                "p99_token_latency_ms": pct(99),
                # paged-cache accounting
                "block_size": self.block_size,
                "cache_blocks": self.cache_blocks,
                "blocks_in_use": self._alloc.used,
                "blocks_free": (self._alloc.free if self._family.paged
                                else self._alloc.free_state),
                "state_blocks": self._alloc.n_state,
                "state_blocks_in_use": (self._alloc.n_state
                                        - self._alloc.free_state),
                "bounded_blocks": self._alloc.n_bounded,
                "bounded_blocks_in_use": (self._alloc.n_bounded
                                          - self._alloc.free_bounded),
                "bounded_ring": self._ring,
                "bounded_pages_reused": self._bounded_reused,
                "prefix_cache": self._tree is not None,
                "cached_prefix_blocks": (self._tree.n_blocks()
                                         if self._tree else 0),
                "cache_block_utilization": (sum(util) / len(util)
                                            if util else 0.0),
                "prefix_hit_rate": (
                    self._prefix_hit_tokens / self._prompt_tokens
                    if self._prompt_tokens else 0.0),
                "prefix_hit_tokens": self._prefix_hit_tokens,
                "cow_copies": self._cow_copies,
                "evicted_blocks": self._evicted_blocks,
                "cancelled": self._cancelled,
                "max_admission_stall_ms": self._max_admission_stall * 1e3,
                "pool_bytes": self._pool_bytes,
                "weight_bytes": self._weight_bytes,
                "kv_bytes_per_token": self._kv_bytes_per_token,
                # load stats the autoscaler consumes (queued imports
                # are demand exactly like queued prompts)
                "queue_depth": len(self._pending) + len(self._imports),
                "decode_tok_s": (win_toks / win_t) if win_t > 0 else 0.0,
                "queue_wait_ms_p50": wpct(50),
                "queue_wait_ms_p99": wpct(99),
                # telemetry
                "ttft_ms_p50": wpct(50),
                "ttft_ms_p99": wpct(99),
                "retraces_unexpected": self._sentinel.retraces_unexpected,
                "deliver_wait_ms_p50": dpct(50),
                "deliver_wait_ms_p99": dpct(99),
                # program spans
                "ticks": ph.count("engine/tick"),
                "tick_s": ph.seconds("engine/tick"),
                "admit_s": ph.seconds("engine/admit"),
                "decode_build_s": ph.seconds("engine/decode_build"),
                "decode_put_s": ph.seconds("engine/decode_put"),
                "prefill_build_s": ph.seconds("engine/prefill_build"),
                "prefill_dispatch_s": ph.seconds(
                    "engine/prefill_dispatch"),
                "prefill_sync_s": ph.seconds("engine/prefill_sync"),
                "tick_gaps": self._tick_gaps,
                "tick_gap_s": self._tick_gap_s,
                "tick_gap_max_s": self._tick_gap_max_s,
                "pump_handoffs": self._pump_handoffs,
                "decode_dispatch_s": ph.seconds(
                    "engine/decode_dispatch", "engine/verify_dispatch"),
                "token_sync_s": ph.seconds("engine/token_sync"),
                "emit_s": ph.seconds("engine/emit"),
                "stream_waits": ph.count("stream/wait"),
                "stream_wait_s": ph.seconds("stream/wait"),
                "submits": ph.count("engine/submit"),
                "submit_s": ph.seconds("engine/submit"),
                # speculative decoding
                "spec": self.spec or "",
                "spec_k": self.spec_k if self.spec else 0,
                "verify_traces": self.verify_traces,
                "draft_traces": self.draft_traces,
                "draft_prefill_traces": self.draft_prefill_traces,
                "spec_steps": self._spec_steps,
                "acceptance_rate": (
                    self._spec_accepted / self._spec_proposed
                    if self._spec_proposed else 0.0),
                "tokens_per_step": (
                    self._decode_tokens / self._decode_slot_steps
                    if self._decode_slot_steps else 0.0),
                # RL flywheel
                "params_version": self._params_version,
                "swaps": self._swaps,
                "weight_swap_ms": self._last_swap_ms,
                "swap_traces": self.swap_traces,
                "load_traces": self.load_traces,
                # fault tolerance
                "sheds": self._sheds,
                "watchdog_stalls": self._watchdog_stalls,
                # disaggregated prefill/decode
                "role": self.role,
                "handoffs": self._handoffs_exported,
                "imports": self._imports_completed,
                "handoffs_abandoned": self._handoffs_abandoned,
                "handoffs_pending": len(self._handoffs),
                "imports_queued": len(self._imports),
                "kv_blocks_exported": self._kv_blocks_exported,
                "kv_blocks_imported": self._kv_blocks_imported,
                "kv_export_bytes": self._kv_export_bytes,
                "kv_import_bytes": self._kv_import_bytes,
                "kv_export_ms_p50": xpct(50),
                "kv_export_ms_p99": xpct(99),
                "kv_import_ms_p50": ipct(50),
                "kv_import_ms_p99": ipct(99),
                "kv_gather_traces": self.kv_gather_traces,
                "kv_scatter_traces": self.kv_scatter_traces,
                # priority / preemption
                "priority_classes": self.priority_classes,
                "preemptions": self._preemptions,
                "reprefill_blocks": self._reprefill_blocks,
                "aging_promotions": self._aging_promotions,
                "per_class": per_class,
                # what the model family's programs counted
                **(self._family.counts(self.cfg, self._model_counts)
                   if self._family.counts is not None else {}),
            }
            with ph.phase("engine/counters") as span:
                if span.is_enabled():
                    span.set(**{k: v for k, v in out.items()
                                if isinstance(v, (int, float))
                                and not isinstance(v, bool)})
            return out


class InferenceReplica:
    """Serve deployment hosting one InferenceEngine; `__call__` returns
    a generator of token ids, which `serve.replica` automatically turns
    into a `next_chunks` stream — so `handle.stream(prompt)` yields
    tokens as they are decoded (a reply carries the tokens made while
    its client was away and leaves with the first it had to wait for,
    so the gap between a stream's tokens is the engine's tick), and
    concurrent requests continuously batch into the shared engine's
    slots: each stream's reply thread is a consumer of `tokens_for`,
    one of them at a time runs the tick and the rest sleep until it
    ends. A client that walks away mid-stream closes the generator,
    which cancels the request and frees its cache blocks.

    Construction takes *config kwargs*, not arrays: params are
    initialized on the replica from `seed`, so nothing heavyweight rides
    the deployment's pickled init args. Real deployments would load
    checkpointed params here instead.

    A worker sees a chip only if it asked for one: bind the deployment
    with ``ray_actor_options={"num_tpus": 1}``, or the replica serves
    from the CPU (`stats()["platform"]` says which).
    """

    def __init__(self, cfg_kwargs: dict | None = None, *,
                 slots: int = 4, max_len: int = 64, seed: int = 0,
                 engine_kwargs: dict | None = None):
        import jax
        from ray_tpu.models import gpt
        cfg = gpt.small(**(cfg_kwargs or {}))
        params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
        ek = dict(engine_kwargs or {})
        # spec='draft' convenience: build the draft model here from the
        # target's config kwargs (params never ride pickled init args).
        if ek.get("spec") == "draft" and "draft_params" not in ek:
            dl = ek.pop("draft_layers", 1)
            dcfg = gpt.small(**{**(cfg_kwargs or {}), "n_layers": dl})
            ek["draft_cfg"] = dcfg
            ek["draft_params"] = gpt.init_params(
                jax.random.PRNGKey(seed + 1), dcfg)
        self.engine = InferenceEngine(
            params, cfg, slots=slots, max_len=max_len, **ek)
        # Where this replica's process actually runs the model: a
        # deployment bound without `num_tpus` gets a CPU worker, and
        # nothing else in stats() or the stream would say so.
        devices = jax.devices()
        self._device_info = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        }

    def __call__(self, prompt, max_new_tokens: int = 8,
                 temperature: float = 0.0, priority: int | None = None):
        # Explicit kwarg wins; otherwise pick up the class the serve
        # path stamped on this request's context (handle/proxy), so
        # priority rides `handle.stream(prompt)` with no signature
        # changes at every hop.
        if priority is None:
            from ray_tpu.serve import priority as _prio
            priority = _prio.get_request_priority()
        rid = self.engine.submit(prompt, max_new_tokens=max_new_tokens,
                                 temperature=temperature,
                                 priority=priority)
        return self.engine.tokens_for(rid)

    def cancel(self, rid: int) -> bool:
        return self.engine.cancel(rid)

    def update_params(self, new_params, *, draft_params=None) -> int:
        """Hot-swap weights into this replica's live engine (the serve
        path the flywheel publishes through); returns the new
        params_version."""
        return self.engine.update_params(new_params,
                                         draft_params=draft_params)

    def stats(self) -> dict:
        """The engine's stats plus the device this process holds:
        ``platform`` / ``device_kind`` / ``device_count`` as JAX reports
        them here, and ``visible_chips``, the host chips the scheduler
        scoped this worker to ("" for a CPU worker)."""
        return {**self.engine.stats(), **self._device_info}
