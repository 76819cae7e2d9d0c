"""The decoder interface: the one place that knows which caption decoders
exist (``Config.decoder``) and what each gives the rest of the program.

==================  =====================================================
``init_params``     the ``params['decoder']`` sub-tree (captioner, and
                    through it ``create_train_state`` and the checkpoint
                    path, which see only a tree of named leaves)
``split_frozen``    which of that sub-tree trains (train/step.py)
``train_logits``    teacher-forced logits ``[B, T, V]`` (+ attention maps
                    where the decoder has them) for the loss
``search``          what ``ops/beam_search.run_search`` needs of a decoder:
                    a state from the image (the LSTM's initial carry; a
                    language model's prefill), one step over ``[B*K]``
                    rows, which leaves are per beam and follow it (a
                    plain tree, or ``StepState.beam``: the search gathers
                    them by parent), which are per beam and stay where
                    the step wrote them (``StepState.at_source``: the
                    search hands over ``source`` and the STEP reads them
                    at the parent's row), which are carried unreordered
                    (``StepState.shared``) and which are per image
                    (closed over, never tiled), and what the decoder
                    itself reports of the batch (``Search.finish``)
==================  =====================================================

The language-model decoders (``lfm2_moe``: convs and grouped-query
attention; ``deepseek_v3``: latent attention; ``glm_moe_dsa``: latent
attention over positions an indexer chooses; ``dots3_note``: such layers
beside window layers at widths of their own; ``cohere2_moe``: a parallel
block over grouped-query window and full layers; ``qwen3_next``: Gated
DeltaNet layers, whose per-beam leaf is a float32 matrix state that every
token rewrites whole, beside gated grouped-query layers) are a module each with one
set of entry points, and share one search (``_lm_search``): what differs
between them is the KIND of leaf their caches hold, which the search never
looks at, and whether a module names fields of its cache in ``AT_SOURCE``
(``qwen3_next`` alone: the matrix state), which the search then leaves in
place.

No other module tests ``Config.decoder``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import Config
from . import cohere2_moe, deepseek_v3, dots3_note, glm_moe_dsa, lfm2, qwen3_next
from .decoder import (
    DecoderState,
    decoder_step,
    init_decoder_params,
    init_state,
    precompute_attend,
    teacher_forced_decode,
)

Params = Dict[str, Any]

# the language-model decoders: a module each, with one set of entry points
# (init_params, teacher_forced, prefill, start_beams, step; optional:
# report, AT_SOURCE)
_LM = {
    "lfm2_moe": lfm2, "deepseek_v3": deepseek_v3, "glm_moe_dsa": glm_moe_dsa,
    "dots3_note": dots3_note, "cohere2_moe": cohere2_moe, "qwen3_next": qwen3_next,
}


class StepState(NamedTuple):
    """A decoder's loop-carried state where not all of it is per beam, or
    not all of what is per beam follows its beam.  A decoder whose whole
    state is per beam (the LSTM's ``DecoderState``) hands the search that
    tree itself.  What is per IMAGE and never changes (an image prefix's
    keys and values) is no state at all: the step function closes over it,
    ``[B, ...]``, untiled.

    Three kinds of leaf, and who moves which: ``beam`` the search gathers
    by parent after every step; ``shared`` nobody moves; ``at_source`` the
    search leaves in the slots the step wrote and says in ``source`` which
    row each slot's beam now descends from, and the STEP reads the leaf
    there: ``new[r] = f(old[source[r]], inputs[r])``.  That is for a leaf
    every step rewrites whole (a recurrent matrix state): a gather of it is
    a second pass over what the step passes over anyway.  A decoder with no
    such leaf leaves both fields None and gets the program it got."""

    beam: Any     # leaves [B*K, ...]: reordered by parent every step
    shared: Any   # counters and the like: carried, never reordered
    at_source: Any = None   # leaves [B*K, ...]: never moved, read by the step at ``source``
    # [B*K] int32, ``b * K + parent[b, k]``: the row of the LAST step's
    # ``at_source`` leaves that slot (b, k) descends from; the rows' own
    # index before the first step.  Written by the search (``_reorder_beams``)
    source: Any = None


class Search(NamedTuple):
    """One batch's search, as the decoder hands it to ``run_search``."""

    # step_fn(state, last_word [B*K] int32) -> (state, logits [B*K, V],
    # alpha [B*K, alpha_width])
    step_fn: Callable
    state0: Any            # a tree of per-beam leaves, or a StepState
    alpha_width: int       # 0: the decoder has no map over the grid
    # finish(result, final state) -> result: where the decoder attaches
    # what it reports of the batch (``BeamResult.decoder_stats``)
    finish: Callable


@jax.named_scope("beam/tile")
def tile_beams(x: jnp.ndarray, K: int) -> jnp.ndarray:
    """[B, ...] -> [B*K, ...] with each image's row repeated K times: what
    starts out per image and then differs per beam (the initial state),
    flattened to the search's [B*K] step batch.  What never differs per
    beam (the context grid, its hoisted projection) is not tiled."""
    B = x.shape[0]
    return jnp.broadcast_to(x[:, None], (B, K) + x.shape[1:]).reshape(
        (B * K,) + x.shape[1:]
    )


def init_params(rng: jax.Array, config: Config) -> Params:
    if config.decoder in _LM:
        return _LM[config.decoder].init_params(rng, config)
    return init_decoder_params(rng, config)


def split_frozen(decoder: Params, config: Config) -> Tuple[Params, Params]:
    """(trainable, frozen) of ``params['decoder']``.  The language-model
    stack is frozen as the CNN is (``train_lm``, ``train_cnn``'s twin):
    the connector alone trains and the optimizer holds slots for it
    alone."""
    if config.decoder in _LM and not config.train_lm:
        return {"connector": decoder["connector"]}, {"lm": decoder["lm"]}
    return decoder, {}


def train_logits(
    decoder: Params,
    config: Config,
    contexts: jnp.ndarray,
    sentences: jnp.ndarray,
    train: bool,
    rng: Optional[jax.Array],
    with_activity: bool = False,
):
    """(logits [B,T,V], alphas [B,T,N] or None, fc activity L1 or None)."""
    if config.decoder in _LM:
        # no dropout and no activity term: the stacks define neither
        lm = _LM[config.decoder]
        return lm.teacher_forced(decoder, config, contexts, sentences), None, None
    out = teacher_forced_decode(
        decoder, config, contexts, sentences, train, rng, with_activity=with_activity
    )
    return out if with_activity else (*out, None)


def search(
    params: Params,
    config: Config,
    contexts: jnp.ndarray,
    K: int,
    T: int,
    hoist_attention: bool = True,
    return_alphas: bool = False,
) -> Search:
    """The search of one batch of grids ``[B, N, D]`` with K beams an
    image over at most T steps."""
    if config.decoder in _LM:
        if return_alphas:
            raise ValueError(
                f"decoder={config.decoder!r} has no per-word attention map "
                "over the grid: return_alphas is refused"
            )
        return _lm_search(
            _LM[config.decoder], params, config, contexts, K, T,
            # the latent cache's reason to be is its size: it reports it
            report_state=config.decoder != "lfm2_moe",
        )

    # the grid and the hoisted context half of the attention MLP stay per
    # IMAGE: every beam of an image attends over the same [N, D] block
    # (loop-invariant at inference; the reference recomputes the
    # projection every step).  The per-step oracle keeps a grid per row.
    if hoist_attention:
        grid, proj = contexts, precompute_attend(params, config, contexts)
    else:
        grid, proj = tile_beams(contexts, K), None
    state0 = init_state(params, config, contexts, train=False)  # [B, H]
    state0 = DecoderState(*(tile_beams(s, K) for s in state0))

    def step_fn(state, last_word):
        return decoder_step(
            params, config, grid, state, last_word, train=False, ctx_proj=proj
        )

    return Search(step_fn, state0, contexts.shape[1], lambda result, state: result)


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def _lm_search(
    lm, params: Params, config: Config, contexts: jnp.ndarray, K: int, T: int,
    report_state: bool = False,
) -> Search:
    """A language-model decoder's (``lm``: its module): the N prefix
    positions go through the stack once per IMAGE; what they leave for
    the steps (keys and values; latents) stays ``[B, N, ...]`` for every
    beam of the image to read in place.  Per beam: what ``lm.start_beams``
    gives (an empty suffix cache of T positions, the record of the experts
    the beam's own tokens chose, the stack's per-beam state)."""
    B = contexts.shape[0]
    with jax.named_scope("beam/prefill"):
        prefix, counts, prefix_routes = lm.prefill(params, config, contexts)
    # the fields of the beams' cache that this stack's step reads at a
    # source row (none: every leaf follows its beam)
    at_source = getattr(lm, "AT_SOURCE", ())

    def split(cache, counters) -> StepState:
        if not at_source:
            return StepState(beam=cache, shared=counters)
        return StepState(
            beam=cache._replace(source=None, **{name: None for name in at_source}), shared=counters,
            at_source={name: getattr(cache, name) for name in at_source}, source=cache.source,
        )

    def join(state: StepState):
        """The stack's own view of its cache: one tree, ``source`` in it."""
        return state.beam._replace(source=state.source, **state.at_source) if at_source else state.beam

    state0 = split(lm.start_beams(config, prefix, K, T, tile_beams), lm.init_counters(counts, T))

    def step_fn(state, last_word):
        cache, counters, logits = lm.step(params, config, prefix, join(state), state.shared, last_word)
        alpha = jnp.zeros((last_word.shape[0], 0), jnp.float32)
        return split(cache, counters), logits, alpha

    def finish(result, state):
        stats = {
            # [moe layers, E]: tokens each expert took, prefill + steps
            "moe_counts": state.shared.moe_counts,
            # [moe layers, T]: experts that took a token at each step
            "moe_step_visits": state.shared.step_visits,
            # [B, N, moe layers * k]: the experts each prefix position chose
            "prefix_routes": prefix_routes,
            # [B, K, T, moe layers * k]: the experts the tokens of each
            # LIVE beam chose, step by step along its own ancestry
            "step_routes": state.beam.routes.reshape(B, K, T, -1),
        }
        if report_state:
            # bytes of the search's state a batch, from the shapes: what
            # the steps close over per image + the per-beam tree
            stats["state_bytes"] = jnp.float32(_tree_bytes(prefix) + _tree_bytes(state.beam))
        if hasattr(lm, "report"):       # what the stack itself counts besides
            # (of its whole cache: what is read at a source row is resolved
            # by the LAST step's sources there, for the rows it reports)
            stats.update(lm.report(config, prefix, state._replace(beam=join(state)), B, K, T))
        return result._replace(decoder_stats=stats)

    return Search(step_fn, state0, 0, finish)
