"""Plain reference for the Solar-Open2 family (Kimi Delta Attention, whose
decay is a vector a key channel, beside gated grouped-query attention without
rotary embedding; every layer's feed-forward a sigmoid router over experts of
which one chip holds a share, beside one shared expert).

Follows the published ``config.json`` of ``upstage/Solar-Open2-250B``
(``model_type: solar_open2``) and ISSUE 56 section 1.  With ``N`` RMSNorm
(eps ``rms_norm_eps``, learned scale) and ``x`` the residual stream, every
layer is ``h = x + Mixer(N_a(x))``, ``y = h + FF(N_f(h))``; ``logits = W_head
N(x)``.

- ``KDA(u)`` (a layer ``gqa_layers`` does not name): ``q~, k~, v~ = W_q u,
  W_k u, W_v u`` (no bias); every channel convolved along the sequence,
  causally, with ``short_conv_kernel_size`` taps of its own (inputs before
  position 0 are zero), then SiLU; a head at a time ``q = q' / sqrt(|q'|^2 +
  1e-6) / sqrt(d)``, ``k = k' / sqrt(|k'|^2 + 1e-6)``, ``v = v'``; ``b = 2
  sigmoid(W_b u)`` (the 2: ``kda_allow_neg_eigval``); ``g = -exp(A_log) *
  softplus(W_f2 W_f1 u + dt_bias)``, a VECTOR of ``d`` a head and token
  (``A_log`` a head, ``dt_bias`` a channel), ``a = exp(g)``; per head a ``d x
  d`` state, zero at position 0, **token by token**: ``S' = diag(a_t) S``,
  ``d_t = b_t (v_t - S'^T k_t)``, ``S = S' + k_t d_t^T``, ``o_t = S^T q_t``;
  ``KDA = W_o [N_o(o) * sigmoid(W_g2 W_g1 u + c_g)]`` with ``N_o`` over each
  head's ``d``, one learned scale shared by the heads.
- ``Attn(u)`` (a layer of ``gqa_layers``): ``q = W_q u`` (``num_attention_
  heads`` heads of ``head_dim``), ``k, v = W_k u, W_v u``
  (``num_key_value_heads``), no bias, no rotary embedding, no QK-norm,
  causal softmax at ``1 / sqrt(head_dim)``, ``W_o [o * sigmoid(W_g u)]``.
- ``FF(z)``: ``s = sigmoid(W_r z)`` over ALL ``n_routed_experts *
  expert_shards`` experts; the chosen set ``I`` the ``num_experts_per_tok``
  largest of ``s + e``; ``w_i = routed_scaling_factor * s_i / (sum_{j in I}
  s_j + 1e-20)``; ``y = E_shared(z) + sum_{i in I, i HELD} w_i E_i(z)``,
  every ``E`` a SwiGLU.  **The banks hold experts ``[expert_shard *
  n_routed_experts, (expert_shard + 1) * n_routed_experts)`` and no other**:
  what an absent expert would have added is left out, here as in the
  program (``expert_shards`` 1: the whole layer).

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, the recurrence a
``lax.scan`` over tokens, attention a ``lax.map`` over blocks of queries,
the experts in groups of four.  It shares no code with the program: it
reads only the parameter tree's leaves, by the names flax gives them.

What ``config.json`` does not say, each also under ``assumed`` in the
configuration file: pre-norm; the router's score function and its ``1e-20``;
the form of the GQA gate; ``kda_use_full_proj: false`` as the low-rank pair;
the KDA gate's bias.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
router, the state, its gates and the recurrence stay in float32).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = (
    # the KDA mixer
    "scalar_decay",           # one decay a head: the mean of g's channels
    "no_delta",               # d = b v: nothing the state answers is taken
    "carry_zeroed",           # the carried state lost at a chunk's boundary
    "beta_unit",              # no factor 2 on b
    "no_conv", "no_l2norm",
    "silu_kda_gate",          # Olmo-Hybrid's gate in place of the sigmoid
    # the softmax layers
    "no_gqa_gate", "rope_on_gqa",
    # the expert layer
    "held_range_ignored",     # every routed copy computed: an absent
    #                           expert's by the held bank ``e mod held``
    "no_shared_expert")
QUERY_BLOCK = 256
EXPERT_GROUP = 4
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


def _kda(u, mixer, *, heads, d, eps, neg_eigval, mutate, precision):
    _, f32, mm = _ops(precision)
    s = u.shape[0]
    proj = lambda name, x=u: mm(x, f32(mixer[name]["kernel"]))
    taps = _leaf(mixer["conv"]["weight"]).astype(jnp.float32)   # [3 H d, K]
    n_taps = taps.shape[1]

    def conv(name, part):
        x = proj(name)
        if mutate != "no_conv":
            t = taps[part * heads * d:(part + 1) * heads * d]
            x = sum(jnp.pad(x, ((back, 0), (0, 0)))[:s]
                    * t[:, n_taps - 1 - back] for back in range(n_taps))
        return jax.nn.silu(x).reshape(s, heads, d)

    q, k, v = conv("q_proj", 0), conv("k_proj", 1), conv("v_proj", 2)
    if mutate != "no_l2norm":
        unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)
                                      + 1e-6)
        q, k = unit(q), unit(k)
    q = q / np.sqrt(d)
    beta = jax.nn.sigmoid(proj("b_proj"))                       # [S, H]
    if neg_eigval and mutate != "beta_unit":
        beta = 2.0 * beta
    # the state's gates in float32 whatever ``precision``
    full = lambda name, x: x @ _leaf(mixer[name]["kernel"]).astype(
        jnp.float32)
    dt = jax.nn.softplus(full("f_b_proj", full("f_a_proj", u))
                         + _leaf(mixer["dt_bias"]).astype(jnp.float32))
    g = -jnp.exp(_leaf(mixer["A_log"]).astype(jnp.float32))[:, None] \
        * dt.reshape(s, heads, d)                               # [S, H, d]
    if mutate == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    a = jnp.exp(g)
    keep = jnp.ones((s,), jnp.float32)
    if mutate == "carry_zeroed":        # the carried state lost a chunk
        keep = (jnp.arange(s) % CARRY_ZEROED_EVERY != 0).astype(jnp.float32)

    def step(state, x):                 # one token; state [H, d, d]
        q_t, k_t, v_t, a_t, b_t, keep_t = x
        state = (keep_t * a_t)[:, :, None] * state
        answer = jnp.einsum("hkv,hk->hv", state, k_t)
        dlt = b_t[:, None] * (v_t if mutate == "no_delta" else v_t - answer)
        state = state + k_t[:, :, None] * dlt[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, a, beta, keep))
    o = _rms_norm(o, mixer["o_norm"]["scale"], eps)             # a head's d
    pre = proj("g_b_proj", proj("g_a_proj")) + _leaf(
        mixer["g_b_proj"]["bias"]).astype(jnp.float32)
    gate = (jax.nn.silu if mutate == "silu_kda_gate"
            else jax.nn.sigmoid)(pre)
    return mm(o.reshape(s, heads * d) * gate, f32(mixer["o_proj"]["kernel"]))


def _attention(u, mixer, *, heads, kv_heads, d, mutate, precision):
    q8, f32, mm = _ops(precision)
    s = u.shape[0]
    q = mm(u, f32(mixer["q_proj"]["kernel"])).reshape(s, heads, d)
    k = mm(u, f32(mixer["k_proj"]["kernel"])).reshape(s, kv_heads, d)
    v = mm(u, f32(mixer["v_proj"]["kernel"])).reshape(s, kv_heads, d)
    if mutate == "rope_on_gqa":
        pos = jnp.arange(s)
        q = _rope(q, pos, ROPE_THETA_OF_THE_MUTATION)
        k = _rope(k, pos, ROPE_THETA_OF_THE_MUTATION)
    group = heads // kv_heads
    key_pos = jnp.arange(s)

    def one_query(args):
        q_t, t = args                                       # [H, d]
        qg = q8(q_t).reshape(kv_heads, group, d)
        sc = jnp.einsum("grd,kgd->grk", qg, q8(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where((key_pos <= t)[None, None], sc,
                                     -jnp.inf), -1)
        return jnp.einsum("grk,kgd->grd", q8(p), q8(v)).reshape(heads, d)

    y = jax.lax.map(one_query, (q, key_pos), batch_size=min(QUERY_BLOCK, s))
    y = y.reshape(s, heads * d)
    if mutate != "no_gqa_gate":
        y = y * jax.nn.sigmoid(mm(u, f32(mixer["g_proj"]["kernel"])))
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


def _layer(x, layer, *, gqa: bool, kw: Dict[str, Any]):
    eps, precision, mutate = kw["eps"], kw["precision"], kw["mutate"]
    u = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
    if gqa:
        x = x + _attention(u, layer["self_attn"], heads=kw["heads"],
                           kv_heads=kw["kv_heads"], d=kw["head_dim"],
                           mutate=mutate, precision=precision)
    else:
        x = x + _kda(u, layer["linear_attn"], heads=kw["lin_heads"],
                     d=kw["lin_dim"], eps=eps, neg_eigval=kw["neg_eigval"],
                     mutate=mutate, precision=precision)
    h = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
    y = _experts(h, layer["mlp"], top_k=kw["top_k"],
                 renormalise=kw["renormalise"], scaling=kw["scaling"],
                 first_held=kw["first_held"], mutate=mutate,
                 precision=precision)
    if kw["shared"] and mutate != "no_shared_expert":
        y = y + _swiglu(h, layer["shared_experts"], precision=precision)
    return x + y


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names; ``n_routed_experts`` the experts HELD,
    ``expert_shards`` and ``expert_shard`` the share)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    n_layers = int(config["num_hidden_layers"])
    gqa_layers = {int(i) for i in config["gqa_layers"]}
    la = config["linear_attn_config"]
    assert la.get("num_kv_heads") in (None, la["num_heads"]), la
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("first_k_dense_replace", 0)):
        assert config.get(key, want) == want, (key, config[key])
    held = int(config["n_routed_experts"])
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              head_dim=int(config["head_dim"]),
              lin_heads=int(la["num_heads"]), lin_dim=int(la["head_dim"]),
              neg_eigval=bool(config.get("kda_allow_neg_eigval", True)),
              top_k=int(config["num_experts_per_tok"]),
              renormalise=bool(config.get("norm_topk_prob", True)),
              scaling=float(config.get("routed_scaling_factor", 1.0)),
              first_held=int(config.get("expert_shard", 0)) * held,
              shared=bool(config.get("n_shared_experts", 0)),
              eps=float(config["rms_norm_eps"]), mutate=mutate,
              precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        steps: Dict[bool, Any] = {}      # one compiled step a kind of layer
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(n_layers):
            gqa = i in gqa_layers
            if gqa not in steps:
                steps[gqa] = jax.jit(
                    lambda x, layer, gqa=gqa: _layer(x, layer, gqa=gqa,
                                                     kw=kw))
            x = steps[gqa](x, model[f"layers_{i}"])
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
