"""Plain reference for the OLMoE family (many small experts, QK-norm).

Follows Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language
Models" (arXiv:2409.02060) and HF ``modeling_olmoe.py``: token embedding;
per layer RMSNorm -> q/k/v projections without bias -> **RMSNorm with a
learned scale over the whole q projection and the whole k projection**
(all heads together, before the split into heads and before RoPE) -> rotary
embedding on every dim of a head (half-split "rotate_half" form) -> causal
multi-head attention -> output projection, residual; RMSNorm -> router
``softmax(h W_g)`` over ALL experts in float32, the ``num_experts_per_tok``
largest taken, their softmax values used as they are (``norm_topk_prob``
false: not renormalised) -> ``y = sum_e p_e * W2_e(silu(W1_e h) * W3_e h)``,
residual; final RMSNorm; untied head.  No token is ever dropped: there is
no capacity.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, and inside a layer the
experts in groups of eight (a loop over experts, eight at a time: every
token through every expert of the group, weighted by its router value or by
zero), so that at most an eighth of one layer's bank is held in float32.
It shares no code with the program: it reads only the parameter tree's
leaves, by the names flax gives them (``gate``, ``w1``, ``w3``, ``w2``
stacked over experts, ``q_norm``, ``k_norm``).

Departures from the description: none in the mathematics.  Assumed, and
listed under ``assumed`` in the configuration file: the form of QK-norm
(``config.json`` has no key for it; it is unconditional in the model code)
and ``intermediate_size`` read as ONE expert's width (HF ``OlmoeMLP``).
``clip_qkv`` is null in the source and is not applied.  The weights are
whatever the caller hands over (the program's random weights, cast up).

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("no_qk_norm", "per_head_qk_norm", "renormalised_topk",
             "one_expert_fewer", "drop_expert_0")
EXPERT_GROUP = 8


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree   # loss wrapper or bare LM


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


def _layer(x, layer, *, heads: int, kv_heads: int, eps: float, theta: float,
           top_k: int, renormalise: bool, mutate: Optional[str],
           precision: str):
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    mm = lambda a, b: q8(a) @ b
    s = x.shape[0]
    attn, mlp = layer["self_attn"], layer["mlp"]
    h = _rms_norm(x, _leaf(layer["input_layernorm"]["scale"]), eps)
    q = mm(h, f32(attn["q_proj"]["kernel"]))
    k = mm(h, f32(attn["k_proj"]["kernel"]))
    v = mm(h, f32(attn["v_proj"]["kernel"]))
    d = q.shape[-1] // heads
    q_scale = _leaf(attn["q_norm"]["scale"]).astype(jnp.float32)
    k_scale = _leaf(attn["k_norm"]["scale"]).astype(jnp.float32)
    if mutate == "per_head_qk_norm":        # the other published form
        q = _rms_norm(q.reshape(s, heads, d), q_scale.reshape(heads, d),
                      eps).reshape(s, heads * d)
        k = _rms_norm(k.reshape(s, kv_heads, d),
                      k_scale.reshape(kv_heads, d), eps
                      ).reshape(s, kv_heads * d)
    elif mutate != "no_qk_norm":
        q = _rms_norm(q, q_scale, eps)      # over all heads together
        k = _rms_norm(k, k_scale, eps)
    pos = jnp.arange(s)
    q = _rope(q.reshape(s, heads, d), pos, theta)
    k = _rope(k.reshape(s, kv_heads, d), pos, theta)
    v = v.reshape(s, kv_heads, d)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q8(q), q8(k)) / np.sqrt(d)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores,
                       -jnp.inf)
    y = jnp.einsum("hqk,khd->qhd", q8(jax.nn.softmax(scores, axis=-1)),
                   q8(v))
    x = x + mm(y.reshape(s, heads * d), f32(attn["o_proj"]["kernel"]))

    h = _rms_norm(x, _leaf(layer["post_attention_layernorm"]["scale"]), eps)
    # the router, never in low precision: which experts a token takes is
    # the architecture, not an approximation of it
    probs = jax.nn.softmax(h @ _leaf(mlp["gate"]).astype(jnp.float32), -1)
    n_experts = probs.shape[-1]
    take = top_k - 1 if mutate == "one_expert_fewer" else top_k
    vals, idx = jax.lax.top_k(probs, take)
    if renormalise or mutate == "renormalised_topk":
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    # [S, E]: a token's weight for each expert, zero for those not taken
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
                     * vals[..., None], axis=1)
    if mutate == "drop_expert_0":
        weight = weight.at[:, 0].set(0.0)
    hq = q8(h)

    def experts(acc, g):
        w1, w3, w2, wt = g                  # [8, M, I] x2, [8, I, M], [8, S]
        act = (jax.nn.silu(jnp.einsum("sm,emi->esi", hq, f32(w1))) *
               jnp.einsum("sm,emi->esi", hq, f32(w3)))
        out = jnp.einsum("esi,eim->esm", q8(act), f32(w2))
        return acc + jnp.sum(wt[..., None] * out, axis=0), None

    per = EXPERT_GROUP if n_experts % EXPERT_GROUP == 0 else n_experts
    grp = lambda a: a.reshape((n_experts // per, per) + a.shape[1:])
    y, _ = jax.lax.scan(
        experts, jnp.zeros_like(x),
        (grp(_leaf(mlp["w1"])), grp(_leaf(mlp["w3"])),
         grp(_leaf(mlp["w2"])), grp(weight.T)))
    return x + y


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              eps=float(config["rms_norm_eps"]),
              theta=float(config["rope_theta"]),
              top_k=int(config["num_experts_per_tok"]),
              renormalise=bool(config["norm_topk_prob"]),
              mutate=mutate, precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, layer: _layer(x, layer, **kw))
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            x = step(x, model[f"layers_{i}"])
        x = _rms_norm(x, _leaf(model["norm"]["scale"]).astype(jnp.float32),
                      kw["eps"])
        if last is not None:
            x = x[-last:]
        return jax.jit(lambda a, w: a @ w.astype(jnp.float32))(
            x, _leaf(lm["lm_head"]["kernel"]))


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
