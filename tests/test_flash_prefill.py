"""ops/flash_prefill.py (causal attention of a whole sequence under a mask
all heads share, the scores in VMEM under an online softmax) in interpret
mode on the CPU, against the ``lax`` blocked form it stands in for
(``models/lm_common.py`` ``attend_blocks``: a block's float32 scores
whole, one softmax a row) on the same inputs, at toy widths with
``d_qk != d_v``.

Both forms round their weights to bfloat16 before the second product and
their output to bfloat16 after the division, the kernel against a running
maximum and the ``lax`` form against the row's, so a weight's rounding
(2^-9 of its size) falls elsewhere and an output moves by a bfloat16 step
or two of the outputs' scale: the cases hold every element to 2^-8 of the
scale (0.4%) and the mean to a tenth of that.  That Mosaic takes the tiles at the
published widths is tests/test_aot_tpu.py's to say; what the kernel costs,
the chip's (PERF.md section 6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sat_tpu.models import glm_moe_dsa as dsa
from sat_tpu.models import lm_common
from sat_tpu.ops import flash_prefill as fp

HEADS, D_QK, D_V, BLOCK, TOPK = 4, 48, 24, 8, 16
SCALE = D_QK ** -0.5


def _inputs(S, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)  # noqa: E731
    scores = jnp.asarray(rng.standard_normal((S, S)), jnp.float32)
    return draw(HEADS, S, D_QK), draw(HEADS, S, D_QK), draw(HEADS, S, D_V), scores


def _masks(index_scores, S, topk=TOPK):
    """The blocks' masks as ``attend_sequence`` makes them: causal where a
    block sees ``topk`` keys or fewer, else the ``topk`` best visible."""
    positions = jnp.arange(S)
    masks = []
    for a, b in lm_common.query_blocks(S):
        causal = positions[a:b, None] >= positions[None, :b]
        masks.append(causal if b <= topk else dsa._select_mask(index_scores[a:b, :b], causal, topk))
    return masks


def _both(q, k, v, masks, tiles=None):
    S = q.shape[1]
    want = lm_common.attend_blocks(q, k, v, masks, SCALE)
    got = fp.flash_prefill(
        q, k, v, dsa._one_mask(masks, S, TOPK), scale=SCALE, tiles=tiles, interpret=True
    )
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _assert_close(got, want):
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2.0 ** -8 * scale, np.abs(got - want).max() / scale
    assert np.abs(got - want).mean() <= 2.0 ** -11 * scale


@pytest.fixture(autouse=True)
def blocks_of_8(monkeypatch):
    monkeypatch.setattr(lm_common, "QUERY_BLOCK", BLOCK)


@pytest.mark.parametrize("case,S,tiles", [
    ("causal_only", 16, (8, 8, 2)),                 # two blocks, each sees topk keys or fewer: no mask operand
    ("one_block", 8, None),                         # the diagonal block alone: its keys end where it ends
    ("topk_mask", 40, (8, 8, 2)),                   # blocks 2-4 select 16 of up to 40
    ("eight_blocks", 64, None),                     # L = 8 blocks, the module's own tiles cut to the shapes
    ("eight_blocks_wide_key_tiles", 64, (8, 32, 4)),
    ("query_tiles_of_two_blocks", 64, (16, 8, 1)),
])
def test_the_kernel_against_the_lax_blocks(case, S, tiles):
    q, k, v, index_scores = _inputs(S, seed=len(case))
    masks = _masks(index_scores, S)
    assert (dsa._one_mask(masks, S, TOPK) is None) == (S <= TOPK)
    _assert_close(*_both(q, k, v, masks, tiles))


def test_a_row_that_sees_nothing_in_its_first_key_tiles():
    """The selection need not keep the keys nearest the start (or the
    query's own position): rows whose first TWO key tiles are wholly
    unselected keep a running maximum at the floor until a visible key
    arrives, and what they gathered meanwhile is wiped: no NaN, the
    ``lax`` form's output."""
    S = 64
    q, k, v, index_scores = _inputs(S, seed=3)
    index_scores = index_scores.at[:, :16].set(-1e9)            # never among the best where 16 others are visible
    masks = _masks(index_scores, S)
    mask = np.asarray(dsa._one_mask(masks, S, TOPK))
    blind = ~mask[:, :16].any(axis=1)
    assert blind.sum() >= 24 and mask.any(axis=1).all()
    got, want = _both(q, k, v, masks, tiles=(8, 8, 2))
    _assert_close(got, want)
    # with every score at the floor in those tiles a -inf floor would have
    # made exp(-inf - -inf): the kernel's is finite
    assert np.isfinite(fp._NEG_INF) and fp._NEG_INF < -1e29


def test_two_tilings_agree_to_the_rounding_of_the_weights():
    """Tile sizes change which running maximum a weight is rounded to
    bfloat16 against and the order of the running sums, nothing else: two
    tilings' outputs are equal in most places and a bfloat16 step apart in
    the rest."""
    S = 64
    q, k, v, index_scores = _inputs(S, seed=5)
    masks = _masks(index_scores, S)
    one, _ = _both(q, k, v, masks, tiles=(8, 8, 1))
    other, _ = _both(q, k, v, masks, tiles=(16, 32, 4))
    assert (one != other).mean() < 0.25
    _assert_close(one, other)


def test_tiles_are_cut_to_the_shapes_and_refused_where_they_do_not_divide():
    assert fp._tiles(4096, 2048, 64) == fp._TILES
    assert fp._tiles(36, 12, 4) == (4, 4, 4)
    q, k, v, index_scores = _inputs(24)
    masks = _masks(index_scores, 24)                            # the mask starts at query 16
    with pytest.raises(ValueError, match="do not divide"):
        fp.flash_prefill(q, k, v, dsa._one_mask(masks, 24, TOPK), scale=SCALE, tiles=(12, 8, 2), interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        fp.flash_prefill(q, k, v, None, scale=SCALE, tiles=(8, 8, 3), interpret=True)


def test_only_the_tpu_or_the_tests_hook_take_the_kernel(monkeypatch):
    assert not fp.available()
    monkeypatch.setattr(fp, "FORCE_INTERPRET", True)
    assert fp.available()
    monkeypatch.setattr(fp, "FORCE_INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fp.available()
