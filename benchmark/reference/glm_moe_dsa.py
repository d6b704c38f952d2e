"""Plain reference for the GLM-MoE-DSA family (GLM-5): multi-head latent
attention with a low-rank query whose softmax runs over the keys a
lightning indexer chooses, and a sigmoid router with a selection bias beside
one ungated shared expert.

Follows the published ``config.json`` of ``zai-org/GLM-5`` (``model_type:
glm_moe_dsa``) and the DeepSeek-V3 / V3.2 equations it names.  With ``N``
RMSNorm (eps ``rms_norm_eps``, learned scale) and ``h`` the residual stream,
layer ``l`` is ``h <- h + Attn_l(N_in(h))`` then ``h <- h + FF_l(N_post(h))``:

- ``Attn``: ``c_q = N_q(W_qa x)``; per head ``[q_nope | q_rope] = W_qb
  c_q``; ``[c_raw | k_r_raw] = W_kva x``; ``c = N_kv(c_raw)``; ``k_r =
  RoPE(k_r_raw)``, one rotated key shared by the heads; ``q_rope <-
  RoPE(q_rope)`` (all ``qk_rope_head_dim`` dims, half-split "rotate_half"
  form, theta ``rope_parameters.rope_theta``); per head ``[k_nope | v] =
  W_kvb c``.  Scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(qk_head_dim)``;
  the softmax of query ``t`` over the set ``S(t)`` below; ``o = W_o
  concat_h(sum_s p v)``.  No bias.  THE EXPANDED FORM: per-head keys and
  values from ``W_kvb``, no absorption, no cache.
- Indexer (``index_n_heads`` x ``index_head_dim``, ``index_topk``): ``qI =
  W_Iq c_q`` (from the query latent); ``kI = LayerNorm(W_Ik x)`` (scale and
  bias, eps 1e-6, ONE key a token); rotary embedding, half-split, same theta,
  on the FIRST ``qk_rope_head_dim`` dims of both; ``w = W_w x * HI^(-1/2) *
  dI^(-1/2)``.  **Query by query**: ``I(t, s) = sum_j w(t, j) * relu(qI(t,
  j) . kI(s))`` for ``s <= t``; the keys SORTED by ``I`` descending (a full
  stable sort: equals keep the lower ``s`` first) and the first
  ``index_topk`` taken: ``S(t)``; all of them where ``t + 1 <= index_topk``.
- ``FF_l`` for ``l < first_k_dense_replace``: ``W_down(silu(W_gate x) *
  W_up x)``.
- ``FF_l`` otherwise: ``s = sigmoid(W_g x)`` in float32 over ALL
  ``n_routed_experts * expert_shards`` experts; the chosen set ``I`` the
  ``num_experts_per_tok`` largest of ``s + b`` (``n_group = topk_group =
  1``); ``a_i = routed_scaling_factor * s_i / (sum_{j in I} s_j + 1e-20)``
  (the bias NOT in the weights); ``y = sum_{i in I, i held} a_i E_i(x) +
  E_shared(x)``.  THE SHARE: the parameter tree holds ``n_routed_experts``
  experts, those numbered ``[expert_shard * n_routed_experts, (expert_shard +
  1) * n_routed_experts)`` of the router's; a copy routed to an absent expert
  adds nothing, as in the program (``expert_shards`` 1: the whole layer).
- ``logits = W_head N_final(h)``, untied, over the rows the tree holds.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time; the queries, the
selection and the attention a ``lax.map`` over blocks of queries (a block's
queries are projected, choose, attend and are projected out inside it, so
nothing ``S x heads x 256`` wide in float32 is held for the queries), the
experts in groups of eight and the head in slices of the vocabulary, so
that 12,300 tokens fit beside the served model.  It shares no code with the
program: it reads only the parameter tree's leaves, by the names flax gives
them.

Assumed, and listed under ``assumed`` in the configuration file: the rotary
forms (the source's ``rope_interleave`` / ``indexer_rope_interleave`` pair a
dim with its neighbour: with weights from a seed a fixed permutation of
columns), which index dims turn, the LayerNorm's eps, the ``1e-20``.
Left out: V3.2's Hadamard rotation and fp8 of ``qI``, ``kI``; the
multi-token-prediction block.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
indexer's scores and the router stay in float32: which keys a query takes
and which experts a token takes are the architecture).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = (
    "selection_left_out",         # dense latent attention: every s <= t
    "relu_left_out", "head_weights_left_out",
    # the indexer's queries from the layer's input (its first q_lora_rank
    # columns, which have c_q's unit RMS) and not from the query latent
    "index_query_from_x",
    "index_rope_left_out",
    "scale_nope",                 # 1 / sqrt(qk_nope_head_dim)
    "key_cached_before_rotation",  # k_r enters the scores unrotated
    "shared_expert_left_out", "no_renormalisation",
    "routed_scaling_left_out", "bias_in_weights")
EXPERT_GROUP = 8
QUERY_BLOCK = 128
VOCAB_SLICES = 8
INDEX_NORM_EPS = 1e-6


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _leaf(scale).astype(jnp.float32)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, positions, theta, dims: Optional[int] = None):
    """``x [S, ..., D]`` with its first ``dims`` dims (all: None) turned;
    rotate_half form: pairs ``(i, i + dims / 2)``."""
    d = x.shape[-1] if dims is None else dims
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., : d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _ops(precision: str):
    """``(q8, f32, mm)``: round to the precision under test, a leaf as
    float32 so rounded, and a matmul of so rounded operands."""
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    return q8, f32, lambda a, b: q8(a) @ b


def _attention(h, attn, *, heads, nope, rope, v_dim, eps, theta, index,
               mutate, precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    pos = jnp.arange(s)
    c_q = _rms_norm(mm(h, f32(attn["q_a_proj"]["kernel"])),
                    attn["q_a_layernorm"]["scale"], eps)
    kv_a = mm(h, f32(attn["kv_a_proj_with_mqa"]["kernel"]))
    rank = kv_a.shape[-1] - rope
    c = _rms_norm(kv_a[:, :rank], attn["kv_a_layernorm"]["scale"], eps)
    k_r = kv_a[:, rank:]
    if mutate != "key_cached_before_rotation":
        k_r = _rope(k_r, pos, theta)
    kv = mm(c, f32(attn["kv_b_proj"])).reshape(s, heads, nope + v_dim)
    k_nope, k_r, v = q8(kv[..., :nope]), q8(k_r), q8(kv[..., nope:])
    scale = (nope if mutate == "scale_nope" else nope + rope) ** -0.5

    # the indexer: never in low precision (which keys a query takes is the
    # architecture, not an approximation of it)
    ix = attn["indexer"]
    hi, di, topk = index["heads"], index["dim"], index["topk"]
    f = lambda a: _leaf(a).astype(jnp.float32)
    ki = _layer_norm(h @ f(ix["wk"]["kernel"]), f(ix["k_norm"]["scale"]),
                     f(ix["k_norm"]["bias"]), INDEX_NORM_EPS)
    if mutate != "index_rope_left_out":
        ki = _rope(ki, pos, theta, rope)
    w = (h @ f(ix["weights_proj"]["kernel"])) * (hi ** -0.5) * (di ** -0.5)
    if mutate == "head_weights_left_out":
        w = jnp.ones_like(w) * (hi ** -0.5) * (di ** -0.5)
    w_qb, w_iq = f32(attn["q_b_proj"]["kernel"]), f(ix["wq_b"]["kernel"])
    w_o = f32(attn["o_proj"]["kernel"])
    index_in = h[:, :c_q.shape[1]] if mutate == "index_query_from_x" else c_q

    def one_query(qi_t, w_t, t):
        seen = pos <= t
        dots = jnp.einsum("jd,sd->js", qi_t, ki)
        if mutate != "relu_left_out":
            dots = jnp.maximum(dots, 0.0)
        score = jnp.where(seen, jnp.einsum("j,js->s", w_t, dots), -jnp.inf)
        # a full stable sort, best first: equals keep the lower s first
        order = jnp.argsort(-score, stable=True)
        place = jnp.zeros((s,), jnp.int32).at[order].set(
            jnp.arange(s, dtype=jnp.int32))
        taken = jnp.logical_and(seen, place < topk)
        return seen if mutate == "selection_left_out" else taken

    def block(args):
        cq_blk, in_blk, w_blk, p_blk = args
        q = mm(cq_blk, w_qb).reshape(-1, heads, nope + rope)
        qn, qr = q[..., :nope], _rope(q[..., nope:], p_blk, theta)
        qi = (in_blk @ w_iq).reshape(-1, hi, di)
        if mutate != "index_rope_left_out":
            qi = _rope(qi, p_blk, theta, rope)
        taken = jax.vmap(one_query)(qi, w_blk, p_blk)           # [B, S]
        scores = (jnp.einsum("qhn,khn->hqk", q8(qn), k_nope)
                  + jnp.einsum("qhr,kr->hqk", q8(qr), k_r)) * scale
        p = jax.nn.softmax(jnp.where(taken[None], scores, -jnp.inf), -1)
        y = jnp.einsum("hqk,khv->qhv", q8(p), v)
        return mm(y.reshape(-1, heads * v_dim), w_o), taken

    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                               ).reshape((-1, blk) + a.shape[1:])
    y, taken = jax.lax.map(
        block, (blocks(c_q), blocks(index_in), blocks(w), blocks(pos)))
    return y.reshape(-1, y.shape[-1])[:s], taken.reshape(-1, s)[:s]


def _mlp(h, mlp, *, precision):
    q8, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _experts(h, mlp, *, top_k, renormalise, scaling, first_held, mutate,
             precision):
    q8, f32, _ = _ops(precision)
    # the router, never in low precision, over every expert of the layer
    scores = jax.nn.sigmoid(h @ _leaf(mlp["gate"]).astype(jnp.float32))
    select = scores + _leaf(mlp["expert_bias"]).astype(jnp.float32)
    _, idx = jax.lax.top_k(select, top_k)
    vals = jnp.take_along_axis(
        select if mutate == "bias_in_weights" else scores, idx, axis=-1)
    if renormalise and mutate != "no_renormalisation":
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    if mutate != "routed_scaling_left_out":
        vals = vals * scaling
    held = _leaf(mlp["w1"]).shape[0]            # the banks this chip holds
    # [S, held]: a token's weight for each HELD expert, zero for those not
    # taken; a copy routed to an absent expert matches no column
    weight = jnp.sum(
        ((idx - first_held)[..., None] == jnp.arange(held)).astype(
            jnp.float32) * vals[..., None], axis=1)
    hq = q8(h)

    def group(acc, g):
        w1, w3, w2, wt = g                  # [8, M, I] x2, [8, I, M], [8, S]
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
    return y, (idx, vals)


def _layer(x, layer, *, dense: bool, shared: bool, eps, mutate, precision,
           attention, experts):
    h = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
    y, taken = _attention(h, layer["self_attn"], eps=eps, mutate=mutate,
                          precision=precision, **attention)
    x = x + y
    h = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
    if dense:
        return x + _mlp(h, layer["mlp"], precision=precision), taken, None
    y, routed = _experts(h, layer["mlp"], mutate=mutate, precision=precision,
                         **experts)
    if shared and mutate != "shared_expert_left_out":
        y = y + _mlp(h, layer["shared_experts"], precision=precision)
    return x + y, taken, routed


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens,
            last: Optional[int] = None, mutate: Optional[str] = None,
            precision: str = "float32", keep: bool = False):
    """``(logits [S, V] float32, taken, routed)``: the logits (the last
    ``last`` positions if given) and, where ``keep``, a layer at a time the
    keys every query took (``[S, S]`` bool) and the experts every token took
    (``(ids [S, k], weights [S, k])``; None of a dense layer)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    eps = float(config["rms_norm_eps"])
    held = int(config["n_routed_experts"])
    kw = dict(
        eps=eps, mutate=mutate, precision=precision,
        shared=int(config["n_shared_experts"]) > 0,
        attention=dict(heads=int(config["num_attention_heads"]),
                       nope=int(config["qk_nope_head_dim"]),
                       rope=int(config["qk_rope_head_dim"]),
                       v_dim=int(config["v_head_dim"]),
                       theta=float(config["rope_parameters"]["rope_theta"]),
                       index=dict(heads=int(config["index_n_heads"]),
                                  dim=int(config["index_head_dim"]),
                                  topk=int(config["index_topk"]))),
        experts=dict(top_k=int(config["num_experts_per_tok"]),
                     renormalise=bool(config["norm_topk_prob"]),
                     scaling=float(config["routed_scaling_factor"]),
                     first_held=int(config.get("expert_shard", 0)) * held))
    tokens = jnp.asarray(tokens, jnp.int32)
    taken, routed = [], []
    with jax.default_matmul_precision("highest"):
        # one compiled step a kind of layer
        steps = {dense: jax.jit(
            lambda x, layer, dense=dense: _layer(x, layer, dense=dense, **kw))
            for dense in (True, False)}
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            x, t, r = steps[i < int(config["first_k_dense_replace"])](
                x, model[f"layers_{i}"])
            if keep:
                taken.append(t)
                routed.append(r)
        x = _rms_norm(x, model["norm"]["scale"], eps)
        if last is not None:
            x = x[-last:]
        q8, f32, _ = _ops(precision)
        head = _leaf(lm["lm_head"]["kernel"])               # [M, V]
        cols = -(-head.shape[1] // VOCAB_SLICES)
        part = jax.jit(lambda a, w: q8(a) @ q8(w.astype(jnp.float32)))
        lg = jnp.concatenate(
            [part(x, head[:, i:i + cols])
             for i in range(0, head.shape[1], cols)], axis=-1)
    return lg, taken, routed


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    return forward(params, config, tokens, last, mutate, precision)[0]


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
