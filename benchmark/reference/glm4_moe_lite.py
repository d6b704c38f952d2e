"""Plain reference for the GLM-4-MoE-Lite family (multi-head latent
attention with a low-rank query; a sigmoid router with a selection bias
beside one ungated shared expert).

Follows the published ``config.json`` of ``zai-org/GLM-4.7-Flash``
(``model_type: glm4_moe_lite``) and the DeepSeek-V2/V3 equations it names.
With ``N`` RMSNorm (eps ``rms_norm_eps``, learned scale) and ``h`` the
residual stream, layer ``l`` is ``h <- h + Attn_l(N_in(h))`` then ``h <- h +
FF_l(N_post(h))``:

- ``Attn``: ``c_q = N_q(W_qa x)``; per head ``[q_nope | q_rope] = W_qb
  c_q``; ``[c_raw | k_r_raw] = W_kva x``; ``c = N_kv(c_raw)``; ``k_r =
  RoPE(k_r_raw)``, one rotated key shared by the heads; ``q_rope <-
  RoPE(q_rope)`` (all ``qk_rope_head_dim`` dims, half-split "rotate_half"
  form); per head ``[k_nope | v] = W_kvb c``.  Scores ``(q_nope . k_nope +
  q_rope . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal
  softmax, ``o = W_o concat_h(sum_u p v)``.  No bias anywhere.  THE EXPANDED
  FORM: per-head keys and values from ``W_kvb``, no absorption, no cache.
- ``FF_l`` for ``l < first_k_dense_replace``: ``W_down(silu(W_gate x) *
  W_up x)``.
- ``FF_l`` otherwise: ``s = sigmoid(W_g x)`` in float32; the chosen set
  ``I`` is the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  selection bias; ``n_group = topk_group = 1``: no group step); ``a_i =
  routed_scaling_factor * s_i / (sum_{j in I} s_j + 1e-20)``
  (``norm_topk_prob``; the bias is NOT in the weights); ``y = sum_{i in I}
  a_i E_i(x) + E_shared(x)``, every ``E`` a SwiGLU, the shared one ungated
  and unscaled.  No capacity: no token is ever dropped.
- ``logits = W_head N_final(h)``, untied.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, inside an expert layer
the experts in groups of eight (at most an eighth of a bank in float32),
attention in blocks of queries and the head in slices of the vocabulary, so
that sixteen thousand tokens fit beside the served model.  It shares no
code with the program: it reads only the parameter tree's leaves, by the
names flax gives them.

Departures from the description: none in the mathematics.  Assumed, and
listed under ``assumed`` in the configuration file, because ``config.json``
has no key for them: the rotary form, the ``1e-20``, the softmax scale.

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

MUTATIONS = (
    # what the cache holds
    "latent_unnormed",        # c cached (and expanded) BEFORE its norm
    "k_rope_unrotated",       # k_r cached before its rotation
    # the softmax scale
    "scale_nope",             # 1 / sqrt(qk_nope_head_dim)
    "scale_latent_width",     # 1 / sqrt(kv_lora_rank + qk_rope_head_dim)
    # the value as wide as the cached row: its k_r columns enter the sum
    # too, through the value up-projection's first columns
    "value_full_row",
    # the expert layer
    "no_shared_expert", "shared_expert_scaled", "no_routed_scaling",
    "bias_in_weights", "no_selection_bias", "softmax_scores",
    "no_renormalisation")
EXPERT_GROUP = 8
QUERY_BLOCK = 256
VOCAB_SLICES = 8


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _leaf(scale).astype(jnp.float32)


def _rope(x, positions, theta):
    """x: [S, ..., D]; rotate_half form: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv_freq
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


def _attention(h, attn, *, heads, nope, rope, v_dim, eps, theta, mutate,
               precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    pos = jnp.arange(s)
    c_q = _rms_norm(mm(h, f32(attn["q_a_proj"]["kernel"])),
                    attn["q_a_layernorm"]["scale"], eps)
    q = mm(c_q, f32(attn["q_b_proj"]["kernel"])).reshape(
        s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
    kv_a = mm(h, f32(attn["kv_a_proj_with_mqa"]["kernel"]))
    rank = kv_a.shape[-1] - rope
    c = kv_a[:, :rank]
    if mutate != "latent_unnormed":
        c = _rms_norm(c, attn["kv_a_layernorm"]["scale"], eps)
    k_r = kv_a[:, rank:]
    if mutate != "k_rope_unrotated":
        k_r = _rope(k_r, pos, theta)
    w_kvb = f32(attn["kv_b_proj"])                    # [rank, H * (n + v)]
    kv = mm(c, w_kvb).reshape(s, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if mutate == "value_full_row":
        first = w_kvb.reshape(rank, heads, nope + v_dim)[:rope, :, nope:]
        v = v + jnp.einsum("sr,rhv->shv", q8(k_r), first)
    scale = {"scale_nope": nope, "scale_latent_width": rank + rope}.get(
        mutate, nope + rope) ** -0.5
    k_nope, k_r, v = q8(k_nope), q8(k_r), q8(v)

    def block(q_blk):
        qn, qr, qpos = q_blk                          # [B, H, n], [B, H, r]
        scores = (jnp.einsum("qhn,khn->hqk", q8(qn), k_nope)
                  + jnp.einsum("qhr,kr->hqk", q8(qr), k_r)) * scale
        scores = jnp.where((pos[None, :] <= qpos[:, None])[None], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khv->qhv",
                          q8(jax.nn.softmax(scores, axis=-1)), v)

    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                               ).reshape((-1, blk) + a.shape[1:])
    y = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), blocks(pos)))
    y = y.reshape(-1, heads * v_dim)[:s]
    return mm(y, f32(attn["o_proj"]["kernel"]))


def _mlp(h, mlp, *, precision):
    q8, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _experts(h, mlp, *, top_k, renormalise, scaling, mutate, precision):
    q8, f32, _ = _ops(precision)
    # the router, never in low precision
    logits = h @ _leaf(mlp["gate"]).astype(jnp.float32)
    scores = (jax.nn.softmax(logits, -1) if mutate == "softmax_scores"
              else jax.nn.sigmoid(logits))
    n_experts = scores.shape[-1]
    bias = _leaf(mlp["expert_bias"]).astype(jnp.float32)
    select = scores if mutate == "no_selection_bias" else scores + bias
    _, idx = jax.lax.top_k(select, top_k)
    vals = jnp.take_along_axis(
        select if mutate == "bias_in_weights" else scores, idx, axis=-1)
    if renormalise and mutate != "no_renormalisation":
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    if mutate != "no_routed_scaling":
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


def _layer(x, layer, *, dense: bool, shared: bool, eps, mutate, precision,
           attention, experts):
    h = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
    x = x + _attention(h, layer["self_attn"], eps=eps, mutate=mutate,
                       precision=precision, **attention)
    h = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
    if dense:
        return x + _mlp(h, layer["mlp"], precision=precision)
    y = _experts(h, layer["mlp"], mutate=mutate, precision=precision,
                 **experts)
    if shared and mutate != "no_shared_expert":
        y = y + _mlp(h, layer["shared_experts"], precision=precision) * (
            experts["scaling"] if mutate == "shared_expert_scaled" else 1.0)
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
    eps = float(config["rms_norm_eps"])
    kw = dict(
        eps=eps, mutate=mutate, precision=precision,
        shared=int(config["n_shared_experts"]) > 0,
        attention=dict(heads=int(config["num_attention_heads"]),
                       nope=int(config["qk_nope_head_dim"]),
                       rope=int(config["qk_rope_head_dim"]),
                       v_dim=int(config["v_head_dim"]),
                       theta=float(config["rope_theta"])),
        experts=dict(top_k=int(config["num_experts_per_tok"]),
                     renormalise=bool(config["norm_topk_prob"]),
                     scaling=float(config["routed_scaling_factor"])))
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        # one compiled step a kind of layer
        steps = {dense: jax.jit(
            lambda x, layer, dense=dense: _layer(x, layer, dense=dense, **kw))
            for dense in (True, False)}
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            x = steps[i < int(config["first_k_dense_replace"])](
                x, model[f"layers_{i}"])
        x = _rms_norm(x, model["norm"]["scale"], eps)
        if last is not None:
            x = x[-last:]
        head = _leaf(lm["lm_head"]["kernel"])               # [M, V]
        cols = -(-head.shape[1] // VOCAB_SLICES)
        part = jax.jit(lambda a, w: a @ w.astype(jnp.float32))
        return jnp.concatenate(
            [part(x, head[:, i:i + cols])
             for i in range(0, head.shape[1], cols)], axis=-1)


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
