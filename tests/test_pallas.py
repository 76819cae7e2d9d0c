"""Pallas fused attention + hoisted-projection decode paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu.config import Config
from sat_tpu.models.decoder import (
    attend,
    attend_with_precomputed,
    init_decoder_params,
    init_state,
    precompute_attend,
)
from sat_tpu.ops.beam_search import beam_search
from sat_tpu.ops.pallas_attention import fused_attend, fused_attend_reference


def _cfg(**kw):
    base = dict(
        image_size=32,
        vocabulary_size=50,
        dim_embedding=8,
        num_lstm_units=8,
        dim_initialize_layer=8,
        dim_attend_layer=16,
        dim_decode_layer=16,
        max_caption_length=6,
        compute_dtype="float32",
    )
    return Config(**{**base, **kw})


def _kernel_inputs(rng, B, K, N=17, da=16, D=24):
    """Per-image grid and projection, K beam rows an image."""
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B * K, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))
    return t1, t2, w2, ctx


@pytest.mark.parametrize("K", [1, 3])
def test_fused_attend_matches_reference(rng, K):
    t1, t2, w2, ctx = _kernel_inputs(rng, 3, K)

    want_ctx, want_alpha = fused_attend_reference(t1, t2, w2, ctx)
    got_ctx, got_alpha = fused_attend(t1, t2, w2, ctx, interpret=True)
    assert got_ctx.shape == (3 * K, 24) and got_alpha.shape == (3 * K, 17)
    np.testing.assert_allclose(got_alpha, want_alpha, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_ctx, want_ctx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_alpha).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize(
    "B,K,block_b", [(5, 1, 4), (5, 3, 4), (3, 3, 8), (13, 3, 8), (8, 3, 8)]
)
def test_fused_attend_per_image_equals_tiled(rng, B, K, block_b, masked):
    """One grid per image under K beams gives, bitwise, what the call on
    K copies of the grid gives (every row a grid of its own: the layout
    before the kernel's grid ran over images): a row's arithmetic does not
    depend on K.  Odd B pads the image axis.  Masked: a dead row among an
    image's beams, its per-row input poisoned, comes out exactly zero and
    leaves its siblings as they were."""
    t1, t2, w2, ctx = _kernel_inputs(rng, B, K)
    kwargs = {}
    if masked:
        mask = jnp.asarray(rng.integers(0, 2, size=(B * K,)).astype(bool))
        mask = mask.at[0].set(False).at[K - 1].set(K > 1)
        t2 = t2.at[~mask].set(jnp.nan)
        kwargs = {"row_mask": mask}

    def tile(x):
        return jnp.repeat(x, K, axis=0)

    got_ctx, got_alpha = fused_attend(
        t1, t2, w2, ctx, interpret=True, block_b=block_b, **kwargs
    )
    base_ctx, base_alpha = fused_attend(
        tile(t1), t2, w2, tile(ctx), interpret=True, block_b=block_b, **kwargs
    )
    np.testing.assert_array_equal(np.asarray(got_ctx), np.asarray(base_ctx))
    np.testing.assert_array_equal(np.asarray(got_alpha), np.asarray(base_alpha))

    # the oracle takes the same shapes, and agrees with itself on the tiled
    ref_ctx, ref_alpha = fused_attend_reference(t1, t2, w2, ctx, **kwargs)
    tiled_ctx, tiled_alpha = fused_attend_reference(
        tile(t1), t2, w2, tile(ctx), **kwargs
    )
    np.testing.assert_allclose(ref_alpha, tiled_alpha, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ref_ctx, tiled_ctx, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_alpha, ref_alpha, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_ctx, ref_ctx, rtol=1e-5, atol=1e-5)
    if masked:
        dead = np.asarray(~mask)
        assert dead.any() and not dead.all()
        assert bool(jnp.isfinite(got_ctx).all() and jnp.isfinite(got_alpha).all())
        assert (np.asarray(got_ctx)[dead] == 0).all()
        assert (np.asarray(got_alpha)[dead] == 0).all()


def test_fused_attend_refuses_rows_that_are_no_whole_number_of_beams(rng):
    t1, t2, w2, ctx = _kernel_inputs(rng, 3, 2)
    with pytest.raises(ValueError, match="whole number of beams"):
        fused_attend(t1, t2[:5], w2, ctx, interpret=True)


@pytest.mark.parametrize("B,block_b", [(5, 4), (8, 8), (2, 8), (13, 4)])
def test_fused_attend_batch_tiling(rng, B, block_b):
    """Batch-tile grid: every (B, block_b) combination — including
    non-divisible and B < block_b — must pad internally and match."""
    N, da, D = 21, 16, 24
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    want_ctx, want_alpha = fused_attend_reference(t1, t2, w2, ctx)
    got_ctx, got_alpha = fused_attend(
        t1, t2, w2, ctx, interpret=True, block_b=block_b
    )
    np.testing.assert_allclose(got_alpha, want_alpha, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_ctx, want_ctx, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layers", [1, 2])
def test_precomputed_attend_matches_plain(rng, layers):
    """Hoisting the context projection must be numerically exact in fp32."""
    config = _cfg(num_attend_layers=layers)
    params = init_decoder_params(jax.random.PRNGKey(0), config)
    B, N, D = 2, config.num_ctx, config.dim_ctx
    contexts = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))
    output = jnp.asarray(
        rng.normal(size=(B, config.num_lstm_units)).astype(np.float32)
    )

    alpha_plain = attend(params, config, contexts, output, train=False)
    ctx_plain = (contexts * alpha_plain[..., None]).sum(axis=1)

    proj = precompute_attend(params, config, contexts)
    ctx_fast, alpha_fast = attend_with_precomputed(
        params, config, contexts, proj, output
    )
    np.testing.assert_allclose(alpha_fast, alpha_plain, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ctx_fast, ctx_plain, rtol=1e-5, atol=1e-6)


def test_beam_search_hoisted_matches_per_step_oracle(rng):
    """Hoisting the attention projection out of the decode loop must not
    change the search at all (fp32: identical op sequence per step)."""
    config = _cfg(beam_size=3)
    params = init_decoder_params(jax.random.PRNGKey(1), config)
    B, N, D = 2, config.num_ctx, config.dim_ctx
    contexts = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    fast = beam_search(params, config, contexts, eos_id=7, hoist_attention=True)
    oracle = beam_search(
        params, config, contexts, eos_id=7, hoist_attention=False
    )
    np.testing.assert_array_equal(np.asarray(fast.words), np.asarray(oracle.words))
    np.testing.assert_allclose(
        np.asarray(fast.log_scores), np.asarray(oracle.log_scores),
        rtol=1e-6, atol=1e-6,
    )


def test_beam_search_pallas_kernel_matches_xla(rng, monkeypatch):
    """The interpret-mode Pallas decode produces the same captions as the
    XLA combine (exercises the kernel through the full search off-TPU)."""
    from sat_tpu.ops import pallas_attention

    config = _cfg(beam_size=3, use_pallas_attention=True)
    params = init_decoder_params(jax.random.PRNGKey(1), config)
    B, N, D = 2, config.num_ctx, config.dim_ctx
    contexts = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    base = beam_search(
        params, config.replace(use_pallas_attention=False), contexts, eos_id=7
    )
    monkeypatch.setattr(pallas_attention, "FORCE_INTERPRET", True)
    out = beam_search(params, config, contexts, eos_id=7)
    np.testing.assert_array_equal(np.asarray(out.words), np.asarray(base.words))
    np.testing.assert_allclose(
        np.asarray(out.log_scores), np.asarray(base.log_scores),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("B,block_b", [(3, 8), (7, 4), (8, 8), (13, 8)])
def test_fused_attend_row_mask_geometry(rng, B, block_b):
    """Slot-pool geometry: odd batch sizes with a dead-row mask.

    Dead rows (inputs poisoned with NaN, as a retired slot's stale carry
    could be) must come out exactly zero; live rows must stay BITWISE
    equal to the unmasked kernel; and the masked kernel must agree with
    the masked XLA reference."""
    N, da, D = 17, 16, 24
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))
    mask = jnp.asarray(rng.integers(0, 2, size=(B,)).astype(bool))

    t1p = t1.at[~mask].set(jnp.nan)
    t2p = t2.at[~mask].set(jnp.nan)
    ctxp = ctx.at[~mask].set(jnp.nan)

    got_ctx, got_alpha = fused_attend(
        t1p, t2p, w2, ctxp, row_mask=mask, interpret=True, block_b=block_b
    )
    want_ctx, want_alpha = fused_attend_reference(
        t1p, t2p, w2, ctxp, row_mask=mask
    )
    assert bool(jnp.isfinite(got_ctx).all() and jnp.isfinite(got_alpha).all())
    dead = np.asarray(~mask)
    assert (np.asarray(got_ctx)[dead] == 0).all()
    assert (np.asarray(got_alpha)[dead] == 0).all()
    np.testing.assert_allclose(got_alpha, want_alpha, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_ctx, want_ctx, rtol=1e-5, atol=1e-5)

    live = np.asarray(mask)
    base_ctx, base_alpha = fused_attend(
        t1, t2, w2, ctx, interpret=True, block_b=block_b
    )
    np.testing.assert_array_equal(
        np.asarray(got_ctx)[live], np.asarray(base_ctx)[live]
    )
    np.testing.assert_array_equal(
        np.asarray(got_alpha)[live], np.asarray(base_alpha)[live]
    )


def test_fused_attend_all_dead_and_all_live_masks(rng):
    """Edge masks: all-live equals the unmasked call bitwise; all-dead is
    all-zero output (never NaN), even at a batch size that needs padding."""
    B, N, da, D = 5, 17, 16, 24
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    base_ctx, base_alpha = fused_attend(t1, t2, w2, ctx, interpret=True)
    ctx_l, alpha_l = fused_attend(
        t1, t2, w2, ctx, row_mask=jnp.ones((B,), bool), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(ctx_l), np.asarray(base_ctx))
    np.testing.assert_array_equal(np.asarray(alpha_l), np.asarray(base_alpha))

    ctx_d, alpha_d = fused_attend(
        jnp.full_like(t1, jnp.nan), jnp.full_like(t2, jnp.nan), w2,
        jnp.full_like(ctx, jnp.nan), row_mask=jnp.zeros((B,), bool),
        interpret=True,
    )
    assert (np.asarray(ctx_d) == 0).all() and (np.asarray(alpha_d) == 0).all()


@pytest.mark.parametrize("layers", [1, 2])
def test_attend_with_precomputed_row_mask_xla_path(rng, layers):
    """The XLA fallback (and 1-layer path) apply the same masking
    semantics as the kernel: live rows bitwise-unchanged, dead rows
    zeroed even when their inputs are NaN."""
    config = _cfg(num_attend_layers=layers, use_pallas_attention=False)
    params = init_decoder_params(jax.random.PRNGKey(0), config)
    B, N, D = 5, config.num_ctx, config.dim_ctx
    contexts = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))
    output = jnp.asarray(
        rng.normal(size=(B, config.num_lstm_units)).astype(np.float32)
    )
    mask = jnp.asarray(np.array([True, False, True, False, True]))
    proj = precompute_attend(params, config, contexts)

    ctx_base, alpha_base = attend_with_precomputed(
        params, config, contexts, proj, output
    )
    contexts_p = contexts.at[~mask].set(jnp.nan)
    output_p = output.at[~mask].set(jnp.nan)
    proj_p = proj.at[~mask].set(jnp.nan)
    ctx_m, alpha_m = attend_with_precomputed(
        params, config, contexts_p, proj_p, output_p, row_mask=mask
    )
    live, dead = np.asarray(mask), np.asarray(~mask)
    assert bool(jnp.isfinite(ctx_m).all() and jnp.isfinite(alpha_m).all())
    assert (np.asarray(ctx_m)[dead] == 0).all()
    assert (np.asarray(alpha_m)[dead] == 0).all()
    np.testing.assert_array_equal(
        np.asarray(ctx_m)[live], np.asarray(ctx_base)[live]
    )
    np.testing.assert_array_equal(
        np.asarray(alpha_m)[live], np.asarray(alpha_base)[live]
    )


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("layers", [1, 2])
def test_attend_with_precomputed_per_image_equals_tiled_xla_path(rng, layers, masked):
    """The XLA twin under K beams an image: the grid broadcast over the
    beams inside the fusion gives, bitwise, what K copies of the grid
    gave; a dead beam row with NaN state comes out zero."""
    config = _cfg(num_attend_layers=layers, use_pallas_attention=False)
    params = init_decoder_params(jax.random.PRNGKey(0), config)
    B, K, N, D = 3, 3, config.num_ctx, config.dim_ctx
    contexts = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))
    output = jnp.asarray(
        rng.normal(size=(B * K, config.num_lstm_units)).astype(np.float32)
    )
    proj = precompute_attend(params, config, contexts)
    mask = None
    if masked:
        mask = jnp.asarray(np.arange(B * K) % 4 != 1)
        output = output.at[~mask].set(jnp.nan)

    got_ctx, got_alpha = attend_with_precomputed(
        params, config, contexts, proj, output, row_mask=mask
    )
    base_ctx, base_alpha = attend_with_precomputed(
        params, config, jnp.repeat(contexts, K, axis=0),
        jnp.repeat(proj, K, axis=0), output, row_mask=mask,
    )
    assert got_ctx.shape == (B * K, D) and got_alpha.shape == (B * K, N)
    np.testing.assert_array_equal(np.asarray(got_ctx), np.asarray(base_ctx))
    np.testing.assert_array_equal(np.asarray(got_alpha), np.asarray(base_alpha))
    if masked:
        dead = np.asarray(~mask)
        assert (np.asarray(got_ctx)[dead] == 0).all()
        assert (np.asarray(got_alpha)[dead] == 0).all()
    with pytest.raises(ValueError, match="whole number of beams"):
        attend_with_precomputed(params, config, contexts, proj, output[:-1])


def test_fused_attend_bf16_scoring_matches_oracle(rng):
    """compute_dtype='bfloat16' must use bf16 for the scoring matmul in
    both the kernel and the oracle — the default-config path."""
    B, N, da, D = 2, 20, 16, 24
    t1 = jnp.asarray(rng.normal(size=(B, N, da)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(B, da)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(da, 1)).astype(np.float32))
    ctx = jnp.asarray(rng.normal(size=(B, N, D)).astype(np.float32))

    want_ctx, want_alpha = fused_attend_reference(
        t1, t2, w2, ctx, compute_dtype="bfloat16"
    )
    got_ctx, got_alpha = fused_attend(
        t1, t2, w2, ctx, compute_dtype="bfloat16", interpret=True
    )
    # bf16 scoring: kernel and XLA round at slightly different points, so
    # agreement is at bf16-rounding scale, not exact
    np.testing.assert_allclose(got_alpha, want_alpha, rtol=5e-2, atol=5e-3)
    np.testing.assert_allclose(got_ctx, want_ctx, rtol=5e-2, atol=5e-2)

    # and the bf16 kernel must be far closer to the bf16 oracle than the
    # fp32 oracle is (i.e. the dtype knob actually changes the matmul)
    fp32_ctx, fp32_alpha = fused_attend_reference(
        t1, t2, w2, ctx, compute_dtype="float32"
    )
    assert float(jnp.abs(got_alpha - want_alpha).max()) < float(
        jnp.abs(fp32_alpha - want_alpha).max()
    )
