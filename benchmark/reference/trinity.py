"""Plain reference for the Trinity family (window attention layers and full
attention layers in one model; gated grouped-query attention with a norm of
q and k a head, rotary embedding on the window layers only; four norms a
layer; leading dense layers, then a sigmoid router over experts of which one
chip holds a share, beside one shared expert).

Follows the published ``config.json`` of ``arcee-ai/Trinity-Large-Preview``
(``model_type: afmoe``) and ISSUE 59 section 1.  With ``N(x) = x *
rsqrt(mean(x^2) + rms_norm_eps) * w`` and ``h`` the residual stream:

- ``h0 = E[token] * sqrt(hidden_size)`` (``mup_enabled``);
- layer ``l``: ``a = h + N2(Attn_l(N1(h)))``, ``y = a + N4(FF_l(N3(a)))``
  (``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
  ``post_mlp_layernorm``); ``logits = W_head N(h_L)``;
- ``Attn_l(u)``: ``q = W_q u`` (``num_attention_heads`` of ``head_dim``),
  ``k, v = W_k u, W_v u`` (``num_key_value_heads``), no bias; ``q, k <-
  Nq(q), Nk(k)`` over each head's ``head_dim``, one learned scale each;
  where ``layer_types[l]`` is ``sliding_attention``: the rotary embedding on
  q and k (``rope_theta``, every dimension, halves rotated) and row ``p``
  attends keys ``p - sliding_window + 1 .. p``; where ``full_attention``: NO
  rotary embedding and every key ``<= p``; softmax at ``1 / sqrt(head_dim)``;
  ``W_o [o * sigmoid(W_g u)]`` elementwise over heads x head_dim;
- ``FF_l``, ``l < num_dense_layers``: ``W_d(silu(W_g z) * W_u z)``;
- ``FF_l`` otherwise: ``s = sigmoid(W_r z)`` over ALL ``num_experts *
  expert_shards`` experts; the chosen set ``I`` the ``num_experts_per_tok``
  largest of ``s + e``; ``w_i = route_scale * s_i / (sum_{j in I} s_j +
  1e-20)`` (``route_norm``; else ``route_scale * s_i``); ``y = E_shared(z) +
  sum_{i in I, i HELD} w_i E_i(z)``, every ``E`` a SwiGLU.  **The banks hold
  experts ``[expert_shard * num_experts, (expert_shard + 1) * num_experts)``
  and no other**: what an absent expert would have added is left out, here
  as in the program (``expert_shards`` 1: the whole layer).

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no page, no batching; one layer at a time, attention a
``lax.map`` over blocks of queries against every key under the layer's own
mask, the experts in groups of four.  It shares no code with the program: it
reads only the parameter tree's leaves, by the names flax gives them.

What ``config.json`` does not say, each also under ``assumed`` in the
configuration file: where the four norms stand; that the window layers and
no other take the rotary embedding; the gate's form; the head norms; the
``1e-20``; "depth-scaled" read as a first value of the output norms' scales.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
router stays in float32).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = (
    # the two kinds of layer
    "window_ignored",         # a window layer attends every key
    "window_on_full",         # the full layers windowed too
    "rope_on_full", "no_rope_on_window",
    # the mixer and the block
    "no_attn_gate", "no_qk_norm",
    "no_output_norms",        # N2 and N4 left out
    "no_mup_scale",
    # the expert layer
    "route_scale_one",
    "held_range_ignored",     # every routed copy computed: an absent
    #                           expert's by the held bank ``e mod held``
    "no_shared_expert")
QUERY_BLOCK = 256
EXPERT_GROUP = 4
WINDOW, FULL = "sliding_attention", "full_attention"


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
    """x: [S, H, D]; rotate_half form: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ops(precision: str):
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    return q8, f32, lambda a, b: q8(a) @ b


def _attention(u, mixer, *, kind, heads, kv_heads, d, window, theta, eps,
               mutate, precision):
    q8, f32, mm = _ops(precision)
    s = u.shape[0]
    q = mm(u, f32(mixer["q_proj"]["kernel"])).reshape(s, heads, d)
    k = mm(u, f32(mixer["k_proj"]["kernel"])).reshape(s, kv_heads, d)
    v = mm(u, f32(mixer["v_proj"]["kernel"])).reshape(s, kv_heads, d)
    if mutate != "no_qk_norm":
        q = _rms_norm(q, mixer["q_norm"]["scale"], eps)
        k = _rms_norm(k, mixer["k_norm"]["scale"], eps)
    rotary = kind == WINDOW and mutate != "no_rope_on_window"
    if rotary or (kind == FULL and mutate == "rope_on_full"):
        pos = jnp.arange(s)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    windowed = kind == WINDOW and mutate != "window_ignored"
    if kind == FULL and mutate == "window_on_full":
        windowed = True
    group = heads // kv_heads
    key_pos = jnp.arange(s)

    def one_query(args):
        q_t, t = args                                       # [H, d]
        qg = q8(q_t).reshape(kv_heads, group, d)
        sc = jnp.einsum("grd,kgd->grk", qg, q8(k)) / np.sqrt(d)
        seen = key_pos <= t
        if windowed:
            seen = jnp.logical_and(seen, key_pos > t - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("grk,kgd->grd", q8(p), q8(v)).reshape(heads, d)

    y = jax.lax.map(one_query, (q, key_pos), batch_size=min(QUERY_BLOCK, s))
    y = y.reshape(s, heads * d)
    if mutate != "no_attn_gate":
        y = y * jax.nn.sigmoid(mm(u, f32(mixer["gate_proj"]["kernel"])))
    return mm(y, f32(mixer["o_proj"]["kernel"]))


def _swiglu(h, mlp, *, precision):
    _, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _experts(h, mlp, *, top_k, renormalise, scaling, first_held, mutate,
             precision):
    q8, f32, _ = _ops(precision)
    # the router, never in low precision, over every expert of the layer
    scores = jax.nn.sigmoid(h @ _leaf(mlp["gate"]).astype(jnp.float32))
    bias = _leaf(mlp["expert_bias"]).astype(jnp.float32)
    _, idx = jax.lax.top_k(scores + bias, top_k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    if mutate != "route_scale_one":
        vals = vals * scaling
    held = _leaf(mlp["w1"]).shape[0]            # the banks this chip holds
    bank = idx - first_held
    if mutate == "held_range_ignored":
        bank = idx % held
    # [S, held]: a token's weight for each HELD expert, zero for those not
    # taken; a copy routed to an absent expert matches no column
    weight = jnp.sum(
        (bank[..., None] == jnp.arange(held)).astype(jnp.float32)
        * vals[..., None], axis=1)
    hq = q8(h)

    def group(acc, g):
        w1, w3, w2, wt = g                  # [4, M, I] x2, [4, I, M], [4, S]
        act = (jax.nn.silu(jnp.einsum("sm,emi->esi", hq, f32(w1))) *
               jnp.einsum("sm,emi->esi", hq, f32(w3)))
        out = jnp.einsum("esi,eim->esm", q8(act), f32(w2))
        return acc + jnp.sum(wt[..., None] * out, axis=0), None

    per = EXPERT_GROUP if held % EXPERT_GROUP == 0 else held
    grp = lambda a: a.reshape((held // per, per) + a.shape[1:])
    y, _ = jax.lax.scan(
        group, jnp.zeros_like(h),
        (grp(_leaf(mlp["w1"])), grp(_leaf(mlp["w3"])),
         grp(_leaf(mlp["w2"])), grp(weight.T)))
    return y


def _layer(x, layer, *, kind: str, dense: bool, kw: Dict[str, Any]):
    eps, precision, mutate = kw["eps"], kw["precision"], kw["mutate"]
    norm = lambda name, a: _rms_norm(a, layer[name]["scale"], eps)
    out_norm = (lambda name, a: a) if mutate == "no_output_norms" else norm
    y = _attention(norm("input_layernorm", x), layer["self_attn"], kind=kind,
                   heads=kw["heads"], kv_heads=kw["kv_heads"],
                   d=kw["head_dim"], window=kw["window"], theta=kw["theta"],
                   eps=eps, mutate=mutate, precision=precision)
    x = x + out_norm("post_attention_layernorm", y)
    h = norm("pre_mlp_layernorm", x)
    if dense:
        y = _swiglu(h, layer["mlp"], precision=precision)
    else:
        y = _experts(h, layer["mlp"], top_k=kw["top_k"],
                     renormalise=kw["renormalise"], scaling=kw["scaling"],
                     first_held=kw["first_held"], mutate=mutate,
                     precision=precision)
        if kw["shared"] and mutate != "no_shared_expert":
            y = y + _swiglu(h, layer["shared_experts"], precision=precision)
    return x + out_norm("post_mlp_layernorm", y)


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names; ``num_experts`` the experts HELD,
    ``expert_shards`` and ``expert_shard`` the share)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    n_layers = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])
    assert len(kinds) == n_layers and set(kinds) <= {WINDOW, FULL}, kinds
    for key, want in (("rope_scaling", None), ("n_group", 1),
                      ("topk_group", 1), ("score_func", "sigmoid"),
                      ("tie_word_embeddings", False)):
        assert config.get(key, want) == want, (key, config[key])
    held = int(config["num_experts"])
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              head_dim=int(config["head_dim"]),
              window=int(config["sliding_window"]),
              theta=float(config["rope_theta"]),
              top_k=int(config["num_experts_per_tok"]),
              renormalise=bool(config.get("route_norm", True)),
              scaling=float(config.get("route_scale", 1.0)),
              first_held=int(config.get("expert_shard", 0)) * held,
              shared=bool(config.get("num_shared_experts", 0)),
              eps=float(config["rms_norm_eps"]), mutate=mutate,
              precision=precision)
    n_dense = int(config["num_dense_layers"])
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        steps: Dict[Any, Any] = {}       # one compiled step a kind of layer
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        if config.get("mup_enabled", True) and mutate != "no_mup_scale":
            x = x * np.sqrt(float(config["hidden_size"]))
        for i in range(n_layers):
            key = (kinds[i], i < n_dense)
            if key not in steps:
                steps[key] = jax.jit(
                    lambda x, layer, key=key: _layer(
                        x, layer, kind=key[0], dense=key[1], kw=kw))
            x = steps[key](x, model[f"layers_{i}"])
        x = _rms_norm(x, model["norm"]["scale"], kw["eps"])
        if last is not None:
            x = x[-last:]
        q8, f32, _ = _ops(precision)
        return jax.jit(lambda a_, w: a_ @ w.astype(jnp.float32))(
            q8(x), f32(lm["lm_head"]["kernel"]))


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
