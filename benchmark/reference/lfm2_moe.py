"""Plain reference for the LFM2-MoE family (gated short convolutions beside
grouped-query attention; sigmoid router with a selection bias).

Follows the published ``config.json`` of ``LiquidAI/LFM2-24B-A2B``
(``model_type: lfm2_moe``) and HF ``modeling_lfm2_moe.py``.  With ``N``
RMSNorm (eps ``norm_eps``, learned scale) and ``h`` the residual stream,
layer ``l`` is ``h <- h + Op_l(N_op(h))`` then ``h <- h + FF_l(N_ffn(h))``:

- ``Op_l``, where ``layer_types[l] == "conv"``: ``[B, C, X] = W_in x``
  (split in that order), ``u_t = B_t * X_t``, ``c_t = w_0 u_{t-2} + w_1
  u_{t-1} + w_2 u_t`` (depthwise over the channels, causal, ``u`` zero
  before the sequence starts; ``conv_L_cache`` taps, the last on the token
  itself), ``y_t = W_out (C_t * c_t)``; no bias, no activation.
- ``Op_l``, where ``"full_attention"``: q, k, v without bias; an RMSNorm
  with a learned scale of ``head_dim`` over EACH HEAD of q and of k; rotary
  embedding on every dim of a head (half-split "rotate_half" form);
  causal softmax attention at scale ``1 / sqrt(head_dim)``, grouped-query;
  output projection without bias.
- ``FF_l`` for ``l < num_dense_layers``: ``W2(silu(W1 x) * W3 x)``.
- ``FF_l`` otherwise: ``s = sigmoid(W_g x)`` in float32; the chosen set
  ``I`` is the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  per-expert selection bias, ``use_expert_bias``); ``a_i = s_i / (sum_{j in
  I} s_j + 1e-6)`` (``norm_topk_prob``; the bias is NOT in the weights),
  times ``routed_scaling_factor``; ``y = sum_{i in I} a_i W2_i(silu(W1_i x)
  * W3_i x)``.  No capacity: no token is ever dropped.  No shared expert.
- ``logits = E N_final(h)`` with ``E`` the embedding matrix (tied).

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, and inside an expert
layer the experts in groups of eight, so that at most an eighth of one
layer's bank is held in float32.  It shares no code with the program: it
reads only the parameter tree's leaves, by the names flax gives them.

Departures from the description: none in the mathematics.  Assumed, and
listed under ``assumed`` in the configuration file, because ``config.json``
has no key for them: ``head_dim`` = hidden / heads; the tied head (the
family's dense siblings set ``tie_embedding``); per-head QK-norm, the split
order ``B, C, X``, the tap order and the ``+ 1e-6`` (all unconditional in
the model code).  The weights are whatever the caller hands over (the
program's random weights, cast up), the selection bias among them.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
router stays in float32: which experts a token takes is the architecture).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("taps_reversed", "bcx_permuted", "bias_in_weights",
             "no_selection_bias", "softmax_scores", "no_renormalisation",
             "qk_norm_whole", "no_qk_norm")
EXPERT_GROUP = 8


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x: [S, H, D]; rotate_half form: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ops(precision: str):
    """``(q8, f32, mm)``: round to the precision under test, a leaf as
    float32 so rounded, and a matmul of so rounded operands."""
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    return q8, f32, lambda a, b: q8(a) @ b


def _short_conv(h, conv, *, mutate, precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    parts = jnp.split(mm(h, f32(conv["in_proj"]["kernel"])), 3, axis=-1)
    if mutate == "bcx_permuted":
        parts = [parts[2], parts[0], parts[1]]
    b, c, x = parts
    u = b * x
    taps = _leaf(conv["conv_weight"]).astype(jnp.float32)    # [C, K]
    if mutate == "taps_reversed":
        taps = taps[:, ::-1]
    k = taps.shape[1]
    out = jnp.zeros_like(u)
    for d in range(k):                      # d steps back: tap k - 1 - d
        back = jnp.pad(u, ((d, 0), (0, 0)))[:s]
        out = out + taps[:, k - 1 - d] * back
    return mm(c * out, f32(conv["out_proj"]["kernel"]))


def _attention(h, attn, *, heads, kv_heads, eps, theta, mutate, precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    q = mm(h, f32(attn["q_proj"]["kernel"]))
    k = mm(h, f32(attn["k_proj"]["kernel"]))
    v = mm(h, f32(attn["v_proj"]["kernel"]))
    d = q.shape[-1] // heads
    q_scale = _leaf(attn["q_norm"]["scale"]).astype(jnp.float32)    # [d]
    k_scale = _leaf(attn["k_norm"]["scale"]).astype(jnp.float32)
    if mutate == "qk_norm_whole":           # OLMoE's form
        q = _rms_norm(q, jnp.tile(q_scale, heads), eps)
        k = _rms_norm(k, jnp.tile(k_scale, kv_heads), eps)
    q = q.reshape(s, heads, d)
    k = k.reshape(s, kv_heads, d)
    if mutate not in ("qk_norm_whole", "no_qk_norm"):
        q = _rms_norm(q, q_scale, eps)      # over each head's own values
        k = _rms_norm(k, k_scale, eps)
    pos = jnp.arange(s)
    q = _rope(q, pos, theta)
    k = _rope(k, pos, theta)
    v = v.reshape(s, kv_heads, d)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q8(q), q8(k)) / np.sqrt(d)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores,
                       -jnp.inf)
    y = jnp.einsum("hqk,khd->qhd", q8(jax.nn.softmax(scores, axis=-1)),
                   q8(v))
    return mm(y.reshape(s, heads * d), f32(attn["o_proj"]["kernel"]))


def _dense_mlp(h, mlp, *, precision):
    q8, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _experts(h, mlp, *, top_k, renormalise, use_bias, scaling, mutate,
             precision):
    q8, f32, _ = _ops(precision)
    # the router, never in low precision
    logits = h @ _leaf(mlp["gate"]).astype(jnp.float32)
    scores = (jax.nn.softmax(logits, -1) if mutate == "softmax_scores"
              else jax.nn.sigmoid(logits))
    n_experts = scores.shape[-1]
    bias = (_leaf(mlp["expert_bias"]).astype(jnp.float32) if use_bias
            else jnp.zeros((n_experts,), jnp.float32))
    select = scores if mutate == "no_selection_bias" else scores + bias
    _, idx = jax.lax.top_k(select, top_k)
    vals = jnp.take_along_axis(
        select if mutate == "bias_in_weights" else scores, idx, axis=-1)
    if renormalise and mutate != "no_renormalisation":
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-6)
    vals = vals * scaling
    # [S, E]: a token's weight for each expert, zero for those not taken
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
                     * vals[..., None], axis=1)
    hq = q8(h)

    def group(acc, g):
        w1, w3, w2, wt = g                  # [8, M, I] x2, [8, I, M], [8, S]
        act = (jax.nn.silu(jnp.einsum("sm,emi->esi", hq, f32(w1))) *
               jnp.einsum("sm,emi->esi", hq, f32(w3)))
        out = jnp.einsum("esi,eim->esm", q8(act), f32(w2))
        return acc + jnp.sum(wt[..., None] * out, axis=0), None

    per = EXPERT_GROUP if n_experts % EXPERT_GROUP == 0 else n_experts
    grp = lambda a: a.reshape((n_experts // per, per) + a.shape[1:])
    y, _ = jax.lax.scan(
        group, jnp.zeros_like(h),
        (grp(_leaf(mlp["w1"])), grp(_leaf(mlp["w3"])),
         grp(_leaf(mlp["w2"])), grp(weight.T)))
    return y


def _layer(x, layer, *, conv: bool, dense: bool, heads, kv_heads, eps,
           theta, top_k, renormalise, use_bias, scaling, mutate, precision):
    h = _rms_norm(x, _leaf(layer["operator_norm"]["scale"]), eps)
    if conv:
        x = x + _short_conv(h, layer["conv"], mutate=mutate,
                            precision=precision)
    else:
        x = x + _attention(h, layer["self_attn"], heads=heads,
                           kv_heads=kv_heads, eps=eps, theta=theta,
                           mutate=mutate, precision=precision)
    h = _rms_norm(x, _leaf(layer["ffn_norm"]["scale"]), eps)
    if dense:
        return x + _dense_mlp(h, layer["feed_forward"], precision=precision)
    return x + _experts(h, layer["feed_forward"], top_k=top_k,
                        renormalise=renormalise, use_bias=use_bias,
                        scaling=scaling, mutate=mutate, precision=precision)


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    types = list(config["layer_types"])
    n_layers = int(config["num_hidden_layers"])
    assert len(types) == n_layers, (len(types), n_layers)
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              eps=float(config["norm_eps"]),
              theta=float(config["rope_parameters"]["rope_theta"]),
              top_k=int(config["num_experts_per_tok"]),
              renormalise=bool(config["norm_topk_prob"]),
              use_bias=bool(config["use_expert_bias"]),
              scaling=float(config["routed_scaling_factor"]),
              mutate=mutate, precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        # one compiled step a kind of layer (mixer x feed-forward)
        steps: Dict[Any, Any] = {}
        embedding = _leaf(model["embed_tokens"]["embedding"])
        x = embedding[tokens].astype(jnp.float32)
        for i in range(n_layers):
            kind = (types[i] == "conv", i < int(config["num_dense_layers"]))
            if kind not in steps:
                steps[kind] = jax.jit(
                    lambda x, layer, kind=kind: _layer(
                        x, layer, conv=kind[0], dense=kind[1], **kw))
            x = steps[kind](x, model[f"layers_{i}"])
        x = _rms_norm(x, _leaf(model["norm"]["scale"]).astype(jnp.float32),
                      kw["eps"])
        if last is not None:
            x = x[-last:]
        return jax.jit(lambda a, e: a @ e.astype(jnp.float32).T)(
            x, embedding)


def token_losses(params: Dict[str, Any], config: Dict[str, Any],
                 tokens) -> np.ndarray:
    """Next-token cross entropy at every position of one sequence, float32:
    entry ``t`` is ``logsumexp(logits[t]) - logits[t, tokens[t + 1]]``."""
    tokens = np.asarray(tokens, np.int32)
    lg = logits(params, config, tokens)[:-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, jnp.asarray(tokens[1:])[:, None], 1)[:, 0]
    return np.asarray(lse - tgt)


def loss(params: Dict[str, Any], config: Dict[str, Any], tokens) -> float:
    """Mean next-token cross entropy of one sequence, float32."""
    return float(np.mean(token_losses(params, config, tokens)))


def generated_gaps(params: Dict[str, Any], config: Dict[str, Any],
                   prompt, generated, mutate: Optional[str] = None,
                   precision: str = "float32") -> np.ndarray:
    """For each greedy token the system generated after ``prompt``: how far
    its reference logit lies under the reference maximum at its position
    (0 where the reference picks the same token)."""
    n = len(generated)
    seq = list(prompt) + list(generated)
    lg = np.asarray(logits(params, config, seq[:-1], last=n, mutate=mutate,
                           precision=precision))
    picked = lg[np.arange(n), np.asarray(generated)]
    return lg.max(axis=-1) - picked
