"""Plain reference for the SmallThinker family (window attention layers and
full attention layers in one model, plain grouped-query attention, rotary
embedding on the window layers only; in every layer a softmax router that
reads the layer's INPUT, before the input norm and before attention, over
ReGLU experts).

Follows the published ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct`` (``model_type: smallthinker``) and
ISSUE 64 section 1.  With ``N(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w``
and ``h`` the residual stream, ``h0 = E[token]`` with no multiplier:

- ``g = W_r h`` in float32, ``moe_num_primary_experts`` wide: the router reads
  the layer's input itself, unnormed, before anything else in the layer;
- ``u = N1(h)``; ``q = W_q u`` (``num_attention_heads`` of ``head_dim``),
  ``k, v = W_k u, W_v u`` (``num_key_value_heads``), no bias, no norm of q or
  k, no gate;
- where ``rope_layout[l]`` reads 1: the rotary embedding on q and k
  (``rope_theta``, every dimension, halves rotated, no scaling); where
  ``sliding_window_layout[l]`` reads 1: row ``p`` attends keys ``p -
  sliding_window_size + 1 .. p``, else every key ``<= p``; scores at ``1 /
  sqrt(head_dim)``, softmax in float32; ``a = h + W_o attn``;
- ``x = N2(a)``; the chosen ``moe_num_active_primary_experts`` are the largest
  of ``g`` (ties to the lower expert), their weights ``softmax(g)`` at the
  chosen over its sum there (``moe_primary_router_apply_softmax``,
  ``norm_topk_prob``); ``y = a + sum_j w_j W_down^j (relu(W_gate^j x) *
  W_up^j x)``.  No shared expert, no scaling factor, no capacity;
- after the last layer a final norm and an untied head.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no page, no batching; one layer at a time, attention a
``lax.map`` over blocks of queries against every key under the layer's own
mask, the experts in groups of four.  It shares no code with the program: it
reads only the parameter tree's leaves, by the names flax gives them.

What ``config.json`` does not say, each also under ``assumed`` in the
configuration file: that the router's input is the layer's unnormed input
(``router_after_attention`` and ``router_on_normed_input`` are the other
readings); that no secondary experts exist in this model.

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
    "window_ignored",           # a window layer attends every key
    "window_on_full",           # the full layers windowed too
    "rope_on_full", "no_rope_on_window",
    # the expert layer
    "router_after_attention",   # the router reads N2(a), as Mixtral's does
    "router_on_normed_input",   # the router reads N1(h)
    "silu_gate",                # SwiGLU experts
    "no_topk_norm",             # softmax values as they are
    "sigmoid_router")           # sigmoids of g, renormalised over the chosen
QUERY_BLOCK = 256
EXPERT_GROUP = 4


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


def _attention(u, mixer, *, rotary, windowed, heads, kv_heads, d, window,
               theta, precision):
    q8, f32, mm = _ops(precision)
    s = u.shape[0]
    q = mm(u, f32(mixer["q_proj"]["kernel"])).reshape(s, heads, d)
    k = mm(u, f32(mixer["k_proj"]["kernel"])).reshape(s, kv_heads, d)
    v = mm(u, f32(mixer["v_proj"]["kernel"])).reshape(s, kv_heads, d)
    if rotary:
        pos = jnp.arange(s)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
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
    return mm(y.reshape(s, heads * d), f32(mixer["o_proj"]["kernel"]))


def _experts(x, routed, mlp, *, top_k, mutate, precision):
    """``sum_j w_j E_j(x)`` with the chosen and their weights from ``routed``
    (the router's input, never in low precision)."""
    q8, f32, _ = _ops(precision)
    g = routed @ _leaf(mlp["gate"]).astype(jnp.float32)
    _, idx = jax.lax.top_k(g, top_k)
    scores = (jax.nn.sigmoid(g) if mutate == "sigmoid_router"
              else jax.nn.softmax(g, axis=-1))
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if mutate != "no_topk_norm":
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    n = _leaf(mlp["w1"]).shape[0]
    # [S, n]: a token's weight for each expert, zero for those not taken
    weight = jnp.sum((idx[..., None] == jnp.arange(n)).astype(jnp.float32)
                     * vals[..., None], axis=1)
    act = jax.nn.silu if mutate == "silu_gate" else jax.nn.relu
    xq = q8(x)

    def group(acc, g_):
        w1, w3, w2, wt = g_                 # [4, M, I] x2, [4, I, M], [4, S]
        mid = (act(jnp.einsum("sm,emi->esi", xq, f32(w1))) *
               jnp.einsum("sm,emi->esi", xq, f32(w3)))
        out = jnp.einsum("esi,eim->esm", q8(mid), f32(w2))
        return acc + jnp.sum(wt[..., None] * out, axis=0), None

    per = EXPERT_GROUP if n % EXPERT_GROUP == 0 else n
    grp = lambda a: a.reshape((n // per, per) + a.shape[1:])
    y, _ = jax.lax.scan(
        group, jnp.zeros_like(x),
        (grp(_leaf(mlp["w1"])), grp(_leaf(mlp["w3"])),
         grp(_leaf(mlp["w2"])), grp(weight.T)))
    return y


def _layer(h, layer, *, rotary: bool, windowed: bool, kw: Dict[str, Any]):
    eps, precision, mutate = kw["eps"], kw["precision"], kw["mutate"]
    norm = lambda name, a: _rms_norm(a, layer[name]["scale"], eps)
    u = norm("input_layernorm", h)
    a = h + _attention(u, layer["self_attn"], rotary=rotary,
                       windowed=windowed, heads=kw["heads"],
                       kv_heads=kw["kv_heads"], d=kw["head_dim"],
                       window=kw["window"], theta=kw["theta"],
                       precision=precision)
    x = norm("post_attention_layernorm", a)
    routed = {"router_after_attention": x,
              "router_on_normed_input": u}.get(mutate, h)
    return a + _experts(x, routed, layer["block_sparse_moe"],
                        top_k=kw["top_k"], mutate=mutate,
                        precision=precision)


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    n_layers = int(config["num_hidden_layers"])
    ropes = [int(v) for v in config["rope_layout"]]
    windows = [int(v) for v in config["sliding_window_layout"]]
    assert len(ropes) == len(windows) == n_layers, (ropes, windows)
    for key, want in (("rope_scaling", None),
                      ("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        assert config.get(key, want) == want, (key, config[key])
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              head_dim=int(config["head_dim"]),
              window=int(config["sliding_window_size"]),
              theta=float(config["rope_theta"]),
              top_k=int(config["moe_num_active_primary_experts"]),
              eps=float(config["rms_norm_eps"]), mutate=mutate,
              precision=precision)

    def kind(i):
        windowed, rotary = bool(windows[i]), bool(ropes[i])
        if windowed:
            windowed = mutate != "window_ignored"
            rotary = rotary and mutate != "no_rope_on_window"
        else:
            windowed = mutate == "window_on_full"
            rotary = rotary or mutate == "rope_on_full"
        return rotary, windowed

    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        steps: Dict[Any, Any] = {}       # one compiled step a kind of layer
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(n_layers):
            key = kind(i)
            if key not in steps:
                steps[key] = jax.jit(
                    lambda x, layer, key=key: _layer(
                        x, layer, rotary=key[0], windowed=key[1], kw=kw))
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
