"""The two ways out of `ray_tpu/models/`: the interface between
`serve/engine.py` and the model families it can serve, and the one between
`train/spmd.py` and those it can train. A family's module builds a
`ServingFamily`, a `TrainingFamily` or both from its own functions and its
configuration object returns them as `cfg.family` and `cfg.training`; the
engine, the trainer and every family import this module and none imports
another family for it."""

from __future__ import annotations

from typing import Callable, NamedTuple

# The parts of a step, the one vocabulary every family and both trainers
# open as `jax.named_scope` where the work is traced, so that an HLO
# instruction's `op_name` says whose it is (PERF.md, section 3):
#   embed      the token table's gather and what precedes the first layer
#   mixer      a layer's token mixing whole: norm, projections, the
#              attention / state kernel, the cache's write, the residual
#   ffn        a layer's feed-forward whole: norm, dense or routed and
#              shared experts, a latent's projections, the residual
#   head       the final norm, the output matrix, sampling, the loss
#   optimizer  training: the update and whatever else walks the gradients
# A part is the outermost scope of a layer's half; what a family opens
# below it keeps its own name.
PARTS = ("embed", "mixer", "ffn", "head", "optimizer")
EMBED, MIXER, FFN, HEAD, OPTIMIZER = PARTS


class ServingFamily(NamedTuple):
    """What `serve/engine.py` asks of a model family, found as the
    `family` of the configuration object it is given. The pool is a dict
    of arrays with the blocks on axis 1 of each, of up to three kinds.

    init_pool(cfg, n_blocks, block_size, mesh[, state_blocks=]
              [, bounded_blocks=]) -> pool
    prefill(params, tokens [1, C], pool, cfg, mesh, *, block_table,
            start, length) -> (logits [1, V] f32, pool, counts)
    decode(params, tokens [B], pool, pos, tables, cfg, mesh)
            -> (logits [B, V] f32, pool, counts)
    copy_block(pool, src, dst), gather_block(pool, idx),
    scatter_block(pool, block, idx): over every array of the pool
    verify(params, tokens [B, W], pool, pos, tables, cfg, mesh)
            -> (logits [B, W, V] f32, pool): speculative decoding; a
            family without it cannot be given `spec=`
    tick(params, chunk_tokens [1, C], step_tokens [B], pool, pos, tables,
         cfg, mesh, *, block_table, start, length)
            -> (chunk logits [1, V] f32, step logits [B, V] f32, pool,
            counts): `decode`'s step and `prefill`'s chunk as one
            program, for a family whose weights' read is what a program
            costs: each weight is read once for the rows of both. The
            engine calls it in a tick that holds decoders and a chunk
            of the full bucket, whether or not it ends its prompt; the
            chunk's sequence is none of the step's (its slot's row is
            idle, and joins the next program's step where the prompt
            ended), so the two write disjoint blocks and pages and
            either order of them is the two programs' result. The
            chunk's logits are its last live row's (by `length`): the
            engine samples them in the same program and reads the token
            where the chunk ended its prompt. The kernels it calls carry names
            of their own: the benchmark's readers sum a kernel's seconds
            by name over a whole trace and divide by the runs of one
            program, so a kernel under the name it has in `prefill` or
            `decode` would be counted into their metrics. `counts` is
            the two programs' summed, where a count is of rows; a count
            of the program's calls is its own. A family without it has
            its chunk and its step enqueued as two programs
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
    What a request holds of the pool: three things, one contract for
    every family and one footprint arithmetic in the engine
    (`InferenceEngine._footprint`).
      1. `state_blocks` blocks of fixed size whatever its length: a
         sequence's state, rewritten by every token.
      2. Where `paged`, pages that grow: one a `block_size` tokens of
         its prompt and output, kept to the end.
      3. Where `bounded_tokens`, pages that grow up to a bound: one a
         `block_size` tokens while the sequence is short, and never more
         than hold `bounded_tokens` positions and the longest prefill
         chunk at once. Past that the engine reuses them in place, as a
         ring: the page that the last `bounded_tokens` positions have
         left whole takes the next positions.
    Its block table is `state_blocks` columns of state blocks, then one
    column a page of `max_len` for the pages that grow, then as many
    again for the bounded ones (0: the trash block of that kind). Column
    `j` of either run names the page that holds positions `j * block_size
    ..`; in a ring several columns name one page, and only the latest of
    them is true. A family reads no column that its own bound has left.
    `models/gpt.py` is (0, paged): pages alone. `models/retention.py` is
    (1, not paged): a state alone. `models/linear_latent.py`,
    `models/mamba_moe.py` and `models/parallel_hybrid.py` are (1, paged):
    both (KDA states beside latent rows; Mamba-2 states beside one
    attention layer's keys and values; and, new with the third, both
    kinds in every layer: a layer's state branch and its attention
    branch read one norm's output, so every array of the pool has a
    layer of the model a layer).
    `models/window_moe.py` is (0, paged, bounded): full layers' pages
    that grow and window layers' pages that do not.

    state_blocks: how many blocks of fixed size a request holds
    paged: whether it also holds pages that grow
    state_keys: the pool's arrays whose axis 1 counts state blocks; every
            other array's axis 1 counts pages. Block 0 of each kind is
            the trash block. A family with state blocks is given their
            number as `init_pool(..., state_blocks=n)` beside the pages'
            `n_blocks`; a state names no range of tokens, so the engine
            keeps no prefix tree for such a family (`prefix_cache=True`
            is refused) and re-prefills a preempted stream from its
            first token
    bounded_keys: the pool's arrays whose axis 1 counts bounded pages,
            given as `init_pool(..., bounded_blocks=n)`; a page that is
            overwritten names no lasting range of tokens either, so the
            same two things hold for such a family
    bounded_tokens: the bound, in positions (a window); 0: no such kind
    """
    init_pool: Callable
    prefill: Callable
    decode: Callable
    copy_block: Callable
    gather_block: Callable
    scatter_block: Callable
    verify: Callable | None = None
    tick: Callable | None = None
    load: Callable | None = None
    counts: Callable | None = None
    state_blocks: int = 0
    paged: bool = True
    state_keys: tuple = ()
    bounded_keys: tuple = ()
    bounded_tokens: int = 0


class TrainingFamily(NamedTuple):
    """What `train/spmd.py` asks of a family that trains through its
    features and the fused loss (`make_features_trainer`), found as the
    `training` of the configuration object it is given.

    init_params(key, cfg) -> the float32 masters
    param_logical_axes(cfg) -> a tuple of logical axes a leaf
    forward_features(params, tokens [B, T], cfg, mesh) -> (final-normed
            activations [B, T, D], which the loss multiplies by the
            leaf `head` [V, D]; what the forward counted)
    aux_update(params, counts, cfg) -> (params, metrics) and
    frozen(params) -> a tree of bools: `make_train_step`'s two hooks;
            no `frozen`: every leaf is the optimizer's
    """
    init_params: Callable
    param_logical_axes: Callable
    forward_features: Callable
    head: str
    aux_update: Callable
    frozen: Callable | None = None
