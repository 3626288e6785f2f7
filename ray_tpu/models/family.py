"""The interface between `serve/engine.py` and the model families it can
serve. A family's module builds one `ServingFamily` from its own functions
and its configuration object returns it as `cfg.family`; the engine and
every family import this module and none imports another family for it."""

from __future__ import annotations

from typing import Callable, NamedTuple


class ServingFamily(NamedTuple):
    """What `serve/engine.py` asks of a model family, found as the
    `family` of the configuration object it is given. The pool is a dict
    of arrays, any number of kinds, with the blocks on axis 1 of each.

    init_pool(cfg, n_blocks, block_size, mesh) -> pool
    prefill(params, tokens [1, C], pool, cfg, mesh, *, block_table,
            start, length) -> (logits [1, V] f32, pool, counts)
    decode(params, tokens [B], pool, pos, tables, cfg, mesh)
            -> (logits [B, V] f32, pool, counts)
    copy_block(pool, src, dst), gather_block(pool, idx),
    scatter_block(pool, block, idx): over every array of the pool
    verify(params, tokens [B, W], pool, pos, tables, cfg, mesh)
            -> (logits [B, W, V] f32, pool): speculative decoding; a
            family without it cannot be given `spec=`
    load(params, cfg) -> params: the family's one load-time function,
            published masters in, the tree that prefill, decode and
            verify read out: every leaf in the dtype the steps would
            cast it to at use, for every `cfg.weight_dtype`, so that no
            step converts a weight. Pure; the engine jits it, runs it
            at construction and on every `update_params`, on the target
            and on a draft model, and runs nothing where it would hand
            a tree back as it is. A family without it (its weights are
            stored in the dtype its steps read) is given its tree as
            published
    counts(cfg, totals) -> {name: number}: what the int32 vector that
            prefill and decode return third, summed over a window, adds
            to `stats()`; None where they return None
    state_blocks: None for a cache that grows, a block a range of
            `block_size` tokens written once. A number (1) for a family
            whose block is a sequence's whole state, of fixed size and
            rewritten by every token: a request then holds that many
            blocks whatever its length, its table has that width, and
            the engine keeps no prefix tree (a block's content names no
            range of tokens) and re-prefills a preempted stream from its
            first token
    """
    init_pool: Callable
    prefill: Callable
    decode: Callable
    copy_block: Callable
    gather_block: Callable
    scatter_block: Callable
    verify: Callable | None = None
    load: Callable | None = None
    counts: Callable | None = None
    state_blocks: int | None = None
