"""Plain reference of the GLM-5.2 captioner (``configs/sat-glm-5.2.json``):
straight ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
the FULL forward over ``[prefix; <start>; served tokens]`` with no cache, no
prefill/step split, no absorbed form, no gather and no grouping of experts.
It imports nothing of the program, and is given only what the benchmark
itself made from the seed (``params_glm52.make_weights``, generated images).

The stack follows zai-org's ``glm_moe_dsa`` (``GLM-5.2`` config.json; the
block is DeepSeek-V3's with DeepSeek-V3.2's sparse attention on top, as
their public modelling code has them), pre-norm RMSNorm ``rms_norm_eps``:

    h = x + DSA(operator_norm(x));   y = h + ffn(ffn_norm(h))

* query, ``u = operator_norm(x)``: ``qr = q_a_layernorm(u W_qa)``
  (``q_lora_rank``), ``q = qr W_qb``, per head ``q_nope``
  (``qk_nope_head_dim``) and ``q_rope`` (``qk_rope_head_dim``, interleaved
  rope, ``rope_theta``, no scaling);
* latent: ``[c_raw ; k_rope_raw] = u W_kva``; ``c = kv_a_layernorm(c_raw)``;
  ``k_rope = rope(k_rope_raw)``; per head ``[k_nope ; v] = c W_kvb``
  (``v_head_dim`` need not equal ``qk_nope_head_dim``); scores
  ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5``;
* indexer, in the layers whose ``indexer_types`` entry is "full":
  ``qI = qr W_qI`` (``index_n_heads`` x ``index_head_dim``),
  ``kI = LayerNorm(u W_kI)`` (weight and bias, eps 1e-6), the first
  ``qk_rope_head_dim`` numbers of each turned by the same rope,
  ``w = u W_w * index_n_heads^-0.5 * index_head_dim^-0.5``,
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
  ``S_t`` = the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]`` by ``jax.lax.top_k`` on these float32 scores (a position is
  in ``S_t`` when its score reaches the top-k's least value).  A "shared"
  layer takes ``S_t`` of the nearest "full" layer before it;
* attention: softmax over ``s in S_t`` alone, values summed, ``o_proj``;
* ffn: a dense SwiGLU in the first ``num_dense_layers`` layers; else
  ``s = sigmoid(x W_r)`` over ALL ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` chosen; weights
  ``s`` at the chosen over their sum + 1e-20, times
  ``routed_scaling_factor``; the experts ``[first_expert, first_expert +
  experts_held)`` applied to every token and masked by those weights, the
  other experts' part left out (another chip's to add: the
  configuration's ``deployment``); plus the shared expert for every token;
* ``norm`` after the last kept layer, then the untied head over the
  vocabulary's slice.

Departures from the source, each a line of the configuration's
``assumed``: the indexer's Hadamard rotation of ``qI`` and ``kI`` is left
out (orthogonal: ``qI . kI`` is unchanged in exact arithmetic); the
indexer runs in this reference's float32 where the source uses float8;
the rotary part is the FIRST ``qk_rope_head_dim`` of an indexer head and
the key norm is a LayerNorm with bias, as in DeepSeek-V3.2's public code;
``head_dim: 192`` is read as the nope width; multi-token prediction
(``num_nextn_predict_layers``) is not run; the image enters through a
connector as N prefix positions in raster order, then ``<start>`` (id 0),
then the caption; the weights are random; the 73 layers and 240 experts
the cut leaves out add nothing, here as in the program.

It runs in blocks so that it fits: ``block`` captions at a time through a
layer whose float32 weights are on the device one layer at a time, a
caption at a time inside the attention, ``_QUERY_BLOCK`` queries at a time
inside a caption (scores ``[heads, block, S]``).  ``calibrate`` fits the
connector's bias and every ``expert_bias`` (all ``num_experts`` outputs)
on a seeded calibration batch, as ``lfm2_captioner.calibrate`` does and
for its reasons.  ``mode``: "f32" is the reference; "fp8" (the CONTROL)
rounds both operands of every matmul, the indexer's included, to float8
e4m3 and leaves the router's product exact.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import params_glm52
from .kanana2_captioner import _rope, route
from .lfm2_captioner import _f32, _grids, _mm, _rms, _sequence, _Static, dense_ffn
from .model import _quant
from .params import nest

_QUERY_BLOCK = 512
_INDEX_NORM_EPS = 1e-6


def _rope_first(x, rope: int, theta: float):
    """x [1, S, heads, d]: its first ``rope`` numbers turned, the rest kept."""
    return jnp.concatenate([_rope(x[..., :rope], theta), x[..., rope:]], axis=-1)


def index_scores(p, x, qr, m, mode):
    """x [S, H] normed, qr [S, q_lora_rank] -> I [S, S] float32, -inf
    above the diagonal."""
    S = x.shape[0]
    nI, dI, rope = int(m["index_n_heads"]), int(m["index_head_dim"]), int(m["qk_rope_head_dim"])
    theta = float(m["rope_theta"])
    qI = _rope_first(_mm(qr, p["wq_b"], mode).reshape(1, S, nI, dI), rope, theta)[0]
    k = _mm(x, p["wk"], mode)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + _INDEX_NORM_EPS)
    k = k * p["k_norm_weight"] + p["k_norm_bias"]
    kI = _rope_first(k.reshape(1, S, 1, dI), rope, theta)[0, :, 0]
    w = _mm(x, p["weights_proj"], mode) * (nI ** -0.5 * dI ** -0.5)
    rows = []
    for a in range(0, S, _QUERY_BLOCK):
        dots = jnp.einsum("sjd,td->jst", _quant(qI[a:a + _QUERY_BLOCK], mode), _quant(kI, mode))
        rows.append(jnp.sum(jax.nn.relu(dots) * w[a:a + _QUERY_BLOCK].T[:, :, None], axis=0))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    return jnp.where(causal, jnp.concatenate(rows, axis=0), -jnp.inf)


def select(scores, topk: int):
    """scores [S, S] (-inf where not visible) -> S_t as a mask [S, S]: the
    visible positions whose score reaches the least of the row's top-k."""
    k = min(int(topk), scores.shape[-1])
    least = jax.lax.top_k(scores, k)[0][:, -1:]
    return (scores >= least) & (scores > -jnp.inf)


def dsa_one(p, x, mask, m, mode):
    """Sparse latent attention over ONE sequence x [S, H] (normed),
    expanded.  ``mask`` [S, S]: S_t of the nearest "full" layer before, or
    None in a layer with an indexer.  Returns (output [S, H], mask)."""
    S = x.shape[0]
    nh, rank = int(m["num_attention_heads"]), int(m["kv_lora_rank"])
    nope, rope, vd = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"]), int(m["v_head_dim"])
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    qr = _rms(_mm(x, p["q_a_proj"], mode), p["q_a_layernorm"], eps)
    q = _mm(qr, p["q_b_proj"], mode).reshape(1, S, nh, nope + rope)
    raw = _mm(x, p["kv_a_proj"], mode)
    latent = _rms(raw[..., :rank], p["kv_a_layernorm"], eps)
    k_rope = _rope(raw[None, :, None, rank:], theta)                     # [1, S, 1, rope]
    kv = _mm(latent, p["kv_b_proj"], mode).reshape(S, nh, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[0], (S, nh, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)[0]
    if mask is None:
        mask = select(index_scores(p["indexer"], x, qr, m, mode), int(m["index_topk"]))
    kq, vq = _quant(k, mode), _quant(kv[..., nope:], mode)
    ctx = []
    for a in range(0, S, _QUERY_BLOCK):
        scores = jnp.einsum("shd,thd->hst", _quant(q[a:a + _QUERY_BLOCK], mode), kq) * ((nope + rope) ** -0.5)
        probs = jax.nn.softmax(jnp.where(mask[None, a:a + _QUERY_BLOCK], scores, -jnp.inf), axis=-1)
        ctx.append(jnp.einsum("hst,thd->shd", _quant(probs, mode), vq))
    return _mm(jnp.concatenate(ctx, axis=0).reshape(S, nh * vd), p["o_proj"], mode), mask


def mix(p, x, masks, m, mode: str = "f32"):
    """The first half of a layer over sequences x [n, S, H], a sequence at
    a time: (x + DSA(operator_norm(x)), the selections [n, S, S])."""
    h = _rms(x, p["operator_norm"], float(m["norm_eps"]))
    if masks is None:
        y, masks = jax.lax.map(lambda one: dsa_one(p["self_attn"], one, None, m, mode), h)
    else:
        y, masks = jax.lax.map(lambda one: dsa_one(p["self_attn"], one[0], one[1], m, mode), (h, masks))
    return x + y, masks


def expert_ffn(p, x, m, mode):
    """The experts held here applied to every token, masked by the routing
    weights over ALL experts; plus the shared expert, which every token
    goes through."""
    chosen, weights = route(p, x, m)
    first, held = int(m.get("first_expert", 0)), params_glm52.held_experts(m)
    xq = _quant(x, mode)

    def one(acc, ew):
        w1, w3, w2, we = ew                       # one expert's maps, its weight per token
        y = _mm(jax.nn.silu(jnp.matmul(xq, _quant(w1, mode))) * jnp.matmul(xq, _quant(w3, mode)),
                w2, mode)
        return acc + y * we[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weights[..., first:first + held], -1, 0)))
    if "shared" in p:
        out = out + dense_ffn(p["shared"], x, mode)
    return out, chosen


def ffn(p, x, moe: bool, m, mode: str = "f32"):
    """The second half: (x + ffn(ffn_norm(x)), chosen experts or None)."""
    h = _rms(x, p["ffn_norm"], float(m["norm_eps"]))
    if moe:
        y, chosen = expert_ffn(p["feed_forward"], h, m, mode)
        return x + y, chosen
    return x + dense_ffn(p["feed_forward"], h, mode), None


_mix_jit = jax.jit(mix, static_argnames=("m", "mode"))
_ffn_jit = jax.jit(ffn, static_argnames=("moe", "m", "mode"))


def _through_the_stack(weights_of, model: dict, xs, mode: str, fit=None):
    """xs: blocks [b, S, H] (host float32) through every layer, a layer's
    float32 weights on the device at a time.  ``fit(name, p, blocks)``:
    the calibration's hook before an expert layer's ffn; it returns the
    layer with its fitted bias in.  Returns (the blocks after the last
    layer, chosen experts a moe layer [n, S, k], the selections of each
    "full" layer [n, S, S] bool), on the host."""
    m = _Static(model)
    masks = None
    routes, selections = [], []
    for i, kind in enumerate(model["indexer_types"]):
        name = f"lm/layers/{params_glm52.layer_name(i)}"
        p = _f32(weights_of(name))
        moe = params_glm52.is_moe(model, i)
        full = kind == "full"
        mixed = [_mix_jit(p, jnp.asarray(x), None if full else jnp.asarray(masks[j]), m=m, mode=mode)
                 for j, x in enumerate(xs)]
        xs = [np.asarray(x) for x, _ in mixed]
        if full:
            masks = [np.asarray(mk) for _, mk in mixed]
            selections.append(np.concatenate(masks))
        del mixed
        if moe and fit is not None:
            p = fit(name, p, xs)
        out = [_ffn_jit(p, jnp.asarray(x), moe=moe, m=m, mode=mode) for x in xs]
        xs = [np.asarray(x) for x, _ in out]
        if moe:
            routes.append(np.concatenate([np.asarray(c) for _, c in out]))
        del p, out
    return xs, routes, selections


def forward(weights_of, model: dict, contexts, tokens, mode: str = "f32", block: int = 4):
    """contexts [n, N, D] float32, tokens [n, T] -> (logits [n, T, V] of
    the caption positions, chosen experts [moe layers, n, N+T, k], S_t of
    the caption positions [full layers, n, T, N+T] bool), on the host.
    ``weights_of(prefix)``: the leaves under ``params/decoder/<prefix>``
    as nested dicts; called once per layer."""
    m = model
    n, T = tokens.shape
    N = contexts.shape[1]
    with jax.default_matmul_precision("highest"):
        xs = _inputs(weights_of, contexts, tokens, mode, block)
        xs, routes, selections = _through_the_stack(weights_of, m, xs, mode)
        head = _f32(weights_of("lm/embed_tokens")).T if m.get("tie_word_embeddings", False) \
            else _f32(weights_of("lm/lm_head"))
        norm = _f32(weights_of("lm/norm"))
        logits = np.concatenate([np.asarray(jnp.einsum(
            "nth,hv->ntv", _quant(_rms(jnp.asarray(x[:, N:]), norm, float(m["norm_eps"])), mode),
            _quant(head, mode))) for x in xs])
    routes = np.stack(routes) if routes else np.zeros((0, n, N + T, 0), np.int32)
    return logits, routes, np.stack([s[:, N:] for s in selections])


def _inputs(weights_of, contexts, tokens, mode: str, block: int):
    """``lfm2_captioner._sequence`` (the prefix through the connector, then
    ``<start>`` and the tokens but the last, embedded) a block of
    sequences at a time, each [b, N+T, H] to the host: 32 sequences of
    4,116 positions are 3.2 GB of float32 at once."""
    c = _f32(weights_of("connector"))
    embed = _f32(weights_of("lm/embed_tokens"))
    tokens = np.asarray(tokens, np.int32)
    out = []
    for i in range(0, tokens.shape[0], block):
        words_in = np.concatenate([np.zeros((len(tokens[i:i + block]), 1), np.int32), tokens[i:i + block, :-1]], axis=1)
        out.append(np.asarray(jnp.concatenate(
            [_mm(jnp.asarray(contexts[i:i + block]), c["kernel"], mode) + c["bias"], embed[words_in]], axis=1)))
    return out


def _seeded(model: dict, seed: int, fitted=None):
    """``weights_of(prefix)`` over the seed's leaves, made when asked for
    (a layer at a time), with the calibration's leaves laid over them."""
    fitted = fitted or {}

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        under = lambda name: name == path or name.startswith(path + "/")  # noqa: E731
        flat = params_glm52.make_weights(model, seed, only=under)
        flat.update({k: v for k, v in fitted.items() if under(k)})
        return flat[path] if path in flat else nest(flat, path)

    return weights_of


def served_logits(model: dict, seed: int, images_u8, tokens, mode: str = "f32", fitted=None, block: int = 4):
    """Teacher-forced logits [n, T, V] of the captions an evaluated path
    returned, the experts the reference chose [moe layers, n, N+T, k] and
    the positions it attended at the caption's steps
    [full layers, n, T, N+T] bool."""
    cnn = params_glm52.make_weights(model, seed, only=lambda name: name.startswith("params/cnn/"))
    ctx = _grids(model, cnn, images_u8, mode, block=2)
    return forward(_seeded(model, seed, fitted), model, ctx, np.asarray(tokens), mode, block=block)


def fit_expert_bias(scores, share, k: int, bias, rounds: int = 400, first: float = 0.02, last: float = 1e-4):
    """``lfm2_captioner.fit_expert_bias``'s rule (the source's: after a
    batch, an expert that took more than the mean share has its bias
    lowered by the update rate, one that took less has it raised; the rate
    decays from ``first`` to ``last``) over ONE batch's router scores
    [n, E], on the device: 400 rounds over 32,928 x 256 scores are minutes
    of numpy and a second here.  ``share`` [n]: each token's weight in the
    load (sums to 1)."""
    scores, share = jnp.asarray(scores, jnp.float32), jnp.asarray(share, jnp.float32)
    experts = jnp.arange(scores.shape[1])

    def one(r, bias):
        rate = first * (last / first) ** (r / max(rounds - 1, 1))
        # the k largest of a row as k passes of argmax (the lower index
        # first among equals, as top_k has it): on the chip ``lax.top_k``
        # whose values go unused is a sort of every row, 19 ms a round
        left, chosen = scores + bias, jnp.zeros(scores.shape, bool)
        for _ in range(k):
            best = jnp.argmax(left, axis=-1)[:, None] == experts
            left, chosen = jnp.where(best, -jnp.inf, left), chosen | best
        load = share @ chosen.astype(jnp.float32)
        return bias + rate * jnp.sign(load.mean() - load)

    return np.asarray(jax.jit(lambda b: jax.lax.fori_loop(0, rounds, one, b))(jnp.asarray(bias, jnp.float32)))


def calibrate(model: dict, weights: Dict[str, np.ndarray], images_u8, tokens, block: int = 4) -> Dict[str, np.ndarray]:
    """{leaf path: value} of the connector's bias and of every expert
    layer's ``expert_bias`` (all ``num_experts`` outputs: the deployment's
    router, not this chip's share of it), fitted on the calibration batch
    in float32, layer by layer (``lfm2_captioner.calibrate``'s procedure
    over this stack's layers: a layer's bias is fitted on the scores its
    router gives the batch, and the batch goes on through the layer as
    routed WITH that bias; prefix and caption positions weigh one half
    each)."""
    k = int(model["num_experts_per_tok"])

    def weights_of(prefix: str):
        path = "params/decoder/" + prefix
        return weights[path] if path in weights else nest(weights, path)

    t0 = time.perf_counter()
    ctx = _grids(model, weights, images_u8, "f32", block=2)
    spent = {"grids": time.perf_counter() - t0, "fits": 0.0}
    tokens = np.asarray(tokens)
    n, T = tokens.shape
    N, D = ctx.shape[1:]
    fitted: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        kernel = np.asarray(weights_of("connector")["kernel"], np.float32)
        centre = -(ctx.reshape(-1, D).astype(np.float64).mean(axis=0) @ kernel.astype(np.float64))
        fitted["params/decoder/connector/bias"] = params_glm52._round_bf16(centre.astype(np.float32))

        def with_bias(prefix: str):
            got = weights_of(prefix)
            return {**got, "bias": fitted["params/decoder/connector/bias"]} if prefix == "connector" else got

        xs = _inputs(with_bias, ctx, tokens, "f32", block)
        share = np.concatenate([np.full((n, N), 0.5 / (n * N)), np.full((n, T), 0.5 / (n * T))], axis=1)

        def fit(name, p, blocks):
            t1 = time.perf_counter()
            f = p["feed_forward"]
            scores = np.concatenate([np.asarray(jax.nn.sigmoid(jnp.matmul(
                _rms(jnp.asarray(b), p["ffn_norm"], float(model["norm_eps"])), f["gate"]))) for b in blocks])
            bias = fit_expert_bias(scores.reshape(n * (N + T), -1), share.ravel(), k,
                                   np.asarray(f["expert_bias"]))
            fitted[f"params/decoder/{name}/feed_forward/expert_bias"] = bias
            spent["fits"] += time.perf_counter() - t1
            return {**p, "feed_forward": {**f, "expert_bias": jnp.asarray(bias)}}

        _through_the_stack(weights_of, model, xs, "f32", fit=fit)
    print(f"benchmark: calibration {time.perf_counter() - t0:.1f} s: the encoder's grids {spent['grids']:.1f}, "
          f"the router's fits {spent['fits']:.1f}, the stack over {n} sequences the rest", flush=True)
    return fitted


def train_loss(weights: Dict[str, np.ndarray], model: dict, contexts, tokens, masks):
    """The masked token cross-entropy of the teacher-forced forward, as a
    function of the connector alone (the stack is frozen): returns
    (loss, {'kernel', 'bias'} gradient).  ``weights``: every decoder leaf
    (toy sizes: the tests).  Differentiable, so all on the device in one
    block: the selections are constants of the differentiation."""
    dec = nest(weights, "params/decoder")
    m = _Static(model)
    tokens = np.asarray(tokens)
    N = contexts.shape[1]

    def loss_of(connector):
        held = {**dec, "connector": connector}

        def weights_of(prefix):
            node = held
            for part in prefix.split("/"):
                node = node[part]
            return node

        x, _ = _sequence(weights_of, contexts, tokens, "f32")
        selection = None
        for i, kind in enumerate(model["indexer_types"]):
            p = _f32(weights_of(f"lm/layers/{params_glm52.layer_name(i)}"))
            x, selection = mix(p, x, None if kind == "full" else selection, m)
            x, _ = ffn(p, x, params_glm52.is_moe(model, i), m)
        h = _rms(x[:, N:], _f32(weights_of("lm/norm")), float(model["norm_eps"]))
        logp = jax.nn.log_softmax(jnp.einsum("nth,hv->ntv", h, _f32(weights_of("lm/lm_head"))), axis=-1)
        ce = -jnp.take_along_axis(logp, jnp.asarray(tokens)[..., None], axis=-1)[..., 0]
        return (ce * masks).sum() / masks.sum()

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(_f32(dec["connector"]))
