"""Program against the plain reference at tiny widths on the CPU, for both
encoders, and the same comparison with a lower precision in the program's
place failing (the control of PERF.md section 2)."""

import numpy as np
import pytest

from conftest import TINY


def _program_state(model, seed):
    import jax

    from sat_tpu.config import Config
    from sat_tpu.train.step import create_train_state

    from reference.params import make_weights

    config = Config(**model)
    shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), config))
    weights = make_weights(model, seed)

    def fill(tree, prefix):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        names = [prefix + "/" + "/".join(str(p.key) for p in path) for path, _ in flat]
        for name, (_, leaf) in zip(names, flat):
            assert tuple(weights[name].shape) == tuple(leaf.shape), name
        return jax.tree_util.tree_unflatten(treedef, [weights[n] for n in names]), names

    params, names = fill(shapes.params, "params")
    stats, more = fill(shapes.batch_stats, "batch_stats") if shapes.batch_stats else ({}, [])
    assert sorted(names + more) == sorted(weights), "param_spec and the program's tree differ"
    return config, weights, params, stats


@pytest.mark.parametrize("cnn", ["vgg16", "resnet50"])
def test_program_matches_reference_and_lower_precision_fails(cnn):
    import jax.numpy as jnp

    from sat_tpu.models.captioner import encode
    from sat_tpu.models.decoder import teacher_forced_decode

    from reference import check, model as ref

    model = dict(TINY, cnn=cnn)
    config, weights, params, stats = _program_state(model, seed=3)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (4, 32, 32, 3), dtype=np.uint8)
    tokens = rng.integers(2, model["vocabulary_size"], (4, 8)).astype(np.int32)
    variables = {"params": params, **({"batch_stats": stats} if stats else {})}
    grid, _ = encode(variables, config, jnp.asarray(images))
    logits, _ = teacher_forced_decode(params["decoder"], config, grid, jnp.asarray(tokens))
    want_grid = np.asarray(ref.encode(weights, cnn, jnp.asarray(images)))
    want = ref.served_logits(weights, model, images, tokens)
    # the program computes in bfloat16 (8 bits of mantissa): its grid and
    # logits sit within about 2**-7 of the float32 reference's range
    scale = np.abs(want_grid).max()
    assert np.abs(np.asarray(grid) - want_grid).max() < 0.03 * scale
    lengths = [8] * 4
    prog_lp = np.take_along_axis(check._log_softmax(np.asarray(logits)), tokens[..., None], -1)[..., 0].sum(1)
    sound = check.served_numbers(want, tokens, lengths, prog_lp, beam=3)
    assert sound["score_gap"] < 0.05, sound["score_gap"]
    for mode in ("fp8", "fp8enc"):           # fp8 throughout; fp8 in the encoder alone
        low = ref.served_logits(weights, model, images, tokens, mode=mode)
        control = check.control_numbers(want, low, tokens, lengths, beam=3)
        assert control["score_gap"] > 3 * sound["score_gap"], (mode, control, sound["score_gap"])


def test_train_reference_follows_the_program_and_fp8_does_not():
    import jax
    import jax.numpy as jnp

    from sat_tpu.train.step import TrainState, make_jit_train_step, split_trainable
    from sat_tpu.train.optimizer import make_optimizer

    from drivers.train_job import StepRecorder, hyper, program_numbers
    from reference import check, model as ref

    model = dict(TINY, cnn="vgg16", batch_size=4, rng_impl="rbg")
    config, weights, params, stats = _program_state(model, seed=5)
    trainable, _ = split_trainable(params, config)
    state = TrainState(params=jax.tree_util.tree_map(jnp.copy, params), batch_stats=stats,
                       opt_state=make_optimizer(config).init(trainable), step=jnp.zeros((), jnp.int32))
    rec = StepRecorder(make_jit_train_step(config), log_every=10, check_steps=3)
    rng = np.random.default_rng(1)
    root = jax.random.key(config.seed + 1, impl=config.rng_impl)
    batches = []
    for step in range(3):
        batch = {"images": rng.integers(0, 256, (4, 32, 32, 3), dtype=np.uint8),
                 "word_idxs": rng.integers(2, 64, (4, 8)).astype(np.int32),
                 "masks": np.ones((4, 8), np.float32)}
        batches.append((batch["images"], batch["word_idxs"], batch["masks"]))
        state, _ = rec(state, {k: jnp.asarray(v) for k, v in batch.items()}, jax.random.fold_in(root, step))
    got = program_numbers(rec, weights, config.beta1)
    want = ref.train_steps(weights, model, hyper(config), batches, config.seed)
    sound = check.train_numbers(got[0], want[0], got[1], want[1], got[2], want[2])
    assert max(sound[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-3, sound   # same dropout masks
    assert sound["grad_norm_gap"] < 0.05, sound
    for mode in ("fp8", "fp8enc"):
        low = ref.train_steps(weights, model, hyper(config), batches, config.seed, mode=mode)
        control = check.train_numbers(low[0], want[0], low[1], want[1], low[2], want[2])
        assert control["grad_norm_gap"] > 3 * sound["grad_norm_gap"], (mode, control, sound)
