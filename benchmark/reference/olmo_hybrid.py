"""Plain reference for the Olmo-Hybrid family (a gated delta rule over a
matrix state beside multi-head attention without rotary embedding).

Follows the published ``config.json`` of ``allenai/Olmo-Hybrid-7B``
(``model_type: olmo_hybrid``) and ISSUE 50 section 1.  With ``N`` RMSNorm
(eps ``rms_norm_eps``, learned scale) and ``x`` the residual stream::

    layer_types[l] == "linear_attention":  h = x + GDN(N_a(x))
                                           y = h + MLP(N_f(h))
    layer_types[l] == "full_attention":    h = x + N_1(Attn(x))
                                           y = h + N_2(MLP(h))
    logits = W_head N(x)

- ``GDN(u)``: ``q~, k~, v~ = W_q u, W_k u, W_v u`` (no bias); every channel
  convolved along the sequence, causally, with ``linear_conv_kernel_dim``
  taps of its own (inputs before position 0 are zero), then SiLU; a head at
  a time ``q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(dk)``, ``k = k' / sqrt(|k'|^2
  + 1e-6)``, ``v = v'``; ``b = 2 sigmoid(W_b u)`` (the 2:
  ``linear_allow_neg_eigval``); ``g = -exp(A_log) * softplus(W_a u +
  dt_bias)``, ``a = exp(g)``; per head a ``dk x dv`` state, zero at position
  0, **token by token**: ``S' = a_t S``, ``d = b_t (v_t - S'^T k_t)``, ``S =
  S' + k_t d^T``, ``o_t = S^T q_t``; ``GDN = W_o [N_o(o) * silu(W_g u)]`` with
  ``N_o`` over each head's ``dv``, one learned scale shared by the heads.
- ``Attn(x)``: ``q = N_q(W_q x)``, ``k = N_k(W_k x)`` (norms over the whole
  projection), ``v = W_v x``, heads of ``hidden / heads``, causal softmax at
  ``1 / sqrt(d)``, no rotary embedding, no window, ``W_o``.
- ``MLP(z) = W_d (silu(W_g z) * W_u z)``.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, the recurrence a
``lax.scan`` over tokens and attention a ``lax.map`` over blocks of
queries.  It shares no code with the program: it reads only the parameter
tree's leaves, by the names flax gives them.

What ``config.json`` does not say, each also under ``assumed`` in the
configuration file: the two norm placements (pre-norm in a
``linear_attention`` layer, norms on the outputs in a ``full_attention``
layer); ``rope_theta: null`` read as no rotary embedding.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
state, its gates and the recurrence stay in float32).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("no_gate", "no_delta", "beta_unit", "no_conv", "no_l2norm",
             "rope_on_full", "prenorm_full", "carry_zeroed")
QUERY_BLOCK = 256
CARRY_ZEROED_EVERY = 1024
ROPE_THETA_OF_THE_MUTATION = 10000.0


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


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


def _gated_delta_net(u, mixer, *, heads, dk, dv, eps, neg_eigval, mutate,
                     precision):
    _, f32, mm = _ops(precision)
    s = u.shape[0]
    proj = lambda name: mm(u, f32(mixer[name]["kernel"]))
    qkv = jnp.concatenate([proj("q_proj"), proj("k_proj"), proj("v_proj")],
                          axis=-1)                    # [S, 2 H dk + H dv]
    if mutate != "no_conv":
        taps = _leaf(mixer["conv"]["weight"]).astype(jnp.float32)  # [C, K]
        n_taps = taps.shape[1]
        qkv = sum(jnp.pad(qkv, ((back, 0), (0, 0)))[:s]
                  * taps[:, n_taps - 1 - back] for back in range(n_taps))
    qkv = jax.nn.silu(qkv)
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q, k = q.reshape(s, heads, dk), k.reshape(s, heads, dk)
    v = v.reshape(s, heads, dv)
    if mutate != "no_l2norm":
        unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)
                                      + 1e-6)
        q, k = unit(q), unit(k)
    q = q / np.sqrt(dk)
    beta = jax.nn.sigmoid(proj("b_proj"))                   # [S, H]
    if neg_eigval and mutate != "beta_unit":
        beta = 2.0 * beta
    g = -jnp.exp(_leaf(mixer["A_log"]).astype(jnp.float32)) * jax.nn.softplus(
        proj("a_proj") + _leaf(mixer["dt_bias"]).astype(jnp.float32))
    a = jnp.ones_like(g) if mutate == "no_gate" else jnp.exp(g)
    keep = jnp.ones((s,), jnp.float32)
    if mutate == "carry_zeroed":        # the carried state lost a chunk
        keep = (jnp.arange(s) % CARRY_ZEROED_EVERY != 0).astype(jnp.float32)

    def step(state, x):                 # one token; state [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t, keep_t = x
        state = (keep_t * a_t)[:, None, None] * state
        answer = jnp.einsum("hkv,hk->hv", state, k_t)
        d = b_t[:, None] * (v_t if mutate == "no_delta" else v_t - answer)
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, a, beta, keep))
    o = _rms_norm(o, _leaf(mixer["o_norm"]["scale"]), eps)  # a head's dv
    gate = jax.nn.silu(proj("g_proj"))
    return mm(o.reshape(s, heads * dv) * gate, f32(mixer["o_proj"]["kernel"]))


def _attention(x, mixer, *, heads, eps, mutate, precision):
    q8, f32, mm = _ops(precision)
    s, hidden = x.shape
    d = hidden // heads
    q = _rms_norm(mm(x, f32(mixer["q_proj"]["kernel"])),
                  _leaf(mixer["q_norm"]["scale"]), eps).reshape(s, heads, d)
    k = _rms_norm(mm(x, f32(mixer["k_proj"]["kernel"])),
                  _leaf(mixer["k_norm"]["scale"]), eps).reshape(s, heads, d)
    v = mm(x, f32(mixer["v_proj"]["kernel"])).reshape(s, heads, d)
    if mutate == "rope_on_full":
        pos = jnp.arange(s)
        q = _rope(q, pos, ROPE_THETA_OF_THE_MUTATION)
        k = _rope(k, pos, ROPE_THETA_OF_THE_MUTATION)
    key_pos = jnp.arange(s)

    def one_query(args):
        q_t, t = args                                       # [H, d]
        sc = jnp.einsum("hd,khd->hk", q8(q_t), q8(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where((key_pos <= t)[None], sc, -jnp.inf), -1)
        return jnp.einsum("hk,khd->hd", q8(p), q8(v))

    y = jax.lax.map(one_query, (q, key_pos), batch_size=min(QUERY_BLOCK, s))
    return mm(y.reshape(s, heads * d), f32(mixer["o_proj"]["kernel"]))


def _mlp(h, mlp, *, precision):
    _, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _layer(x, layer, *, linear: bool, kw: Dict[str, Any]):
    eps, precision, mutate = kw["eps"], kw["precision"], kw["mutate"]
    scale = lambda name: _leaf(layer[name]["scale"])
    if linear:
        h = x + _gated_delta_net(
            _rms_norm(x, scale("attention_layer_norm"), eps),
            layer["linear_attn"], heads=kw["lin_heads"], dk=kw["dk"],
            dv=kw["dv"], eps=eps, neg_eigval=kw["neg_eigval"], mutate=mutate,
            precision=precision)
        return h + _mlp(_rms_norm(h, scale("feedforward_layer_norm"), eps),
                        layer["mlp"], precision=precision)
    attend = lambda z: _attention(z, layer["self_attn"], heads=kw["heads"],
                                  eps=eps, mutate=mutate, precision=precision)
    n1, n2 = scale("post_attention_norm"), scale("post_feedforward_norm")
    if mutate == "prenorm_full":        # the other placement, same scales
        h = x + attend(_rms_norm(x, n1, eps))
        return h + _mlp(_rms_norm(h, n2, eps), layer["mlp"],
                        precision=precision)
    h = x + _rms_norm(attend(x), n1, eps)
    return h + _rms_norm(_mlp(h, layer["mlp"], precision=precision), n2, eps)


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
              lin_heads=int(config["linear_num_key_heads"]),
              dk=int(config["linear_key_head_dim"]),
              dv=int(config["linear_value_head_dim"]),
              neg_eigval=bool(config.get("linear_allow_neg_eigval", True)),
              eps=float(config["rms_norm_eps"]), mutate=mutate,
              precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        steps: Dict[bool, Any] = {}      # one compiled step a kind of layer
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(n_layers):
            linear = types[i] == "linear_attention"
            if linear not in steps:
                steps[linear] = jax.jit(
                    lambda x, layer, linear=linear: _layer(
                        x, layer, linear=linear, kw=kw))
            x = steps[linear](x, model[f"layers_{i}"])
        x = _rms_norm(x, _leaf(model["norm"]["scale"]), kw["eps"])
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
