"""Plain reference for the language model of Keye-VL-2.0 (a Qwen3-MoE-shaped
block whose attention reads only the keys a lightning indexer chooses).

Follows the published ``config.json`` of ``Kwai-Keye/Keye-VL-2.0-30B-A3B``
(``model_type: KeyeVL2``) and, where it is silent, the two families'
conventions (each listed under ``assumed`` in the configuration file).  With
``N`` RMSNorm (eps ``rms_norm_eps``, learned scale), ``h`` the residual
stream, no biases::

    h_0 = E[id]
    h <- h + W_o att(N(h))
    h <- h + sum_e p_e W_down,e(silu(W_gate,e u) * W_up,e u),   u = N(h)
    logits = W_head N(h)

- Attention inputs: ``q = W_q u`` (``num_attention_heads`` x ``head_dim``),
  ``k, v = W_k u, W_v u`` (``num_key_value_heads`` x ``head_dim``); RMSNorm
  with a learned ``head_dim``-vector over EACH HEAD of q and of k; rotary
  embedding, half-split, ``rope_theta``, **by sections**:
  ``rope_scaling.mrope_section`` ``[16, 24, 24]`` gives the first 16
  frequency pairs the temporal position row, the next 24 the height row,
  the last 24 the width row.  The input here is text, whose three rows are
  equal: the reference builds the three rows and applies the sections all
  the same.
- Indexer (``sa_config``): ``qI = W_qI u`` (``indexer_num_heads`` x
  ``indexer_head_dim``); ``kI = LayerNorm(W_kI u)`` (scale and bias, one
  key a token); rotary embedding, half-split, over the whole of both (one
  position row: the indexer's 32 pairs are not sectioned); ``w = W_w u *
  HI^(-1/2) * dI^(-1/2)``.  **Query by query**: ``I(t, s) = sum_j w(t, j) *
  relu(qI(t, j) . kI(s))`` for ``s <= t``; the keys SORTED by ``I``
  descending (a full stable sort: equals keep the lower ``s`` first) and the
  first ``topk`` taken; all of them where ``t + 1 <= topk``.
- ``att(t, h) = softmax over the taken s of (q(t, h) . k(s, g(h)) /
  sqrt(head_dim)) v(s, g(h))``.
- Experts: ``p = softmax(W_r u)`` over all ``num_experts`` in float32, the
  ``num_experts_per_tok`` largest, divided by their sum (``norm_topk_prob``
  true); width ``moe_intermediate_size``; no shared expert; no capacity.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, the selection and the
attention a ``lax.map`` over queries (in blocks, so that it fits) and the
experts in groups of eight.  It shares no code with the program: it reads
only the parameter tree's leaves, by the names flax gives them.

Departures from the source, each also under ``assumed``: the vision tower
is left out (the catalog gives no width of it); DeepSeek-V3.2's Hadamard
rotation and fp8 of ``qI``, ``kI`` are left out (an orthogonal change of
basis plus a quantisation); ``q_chunk_size`` / ``kv_chunk_size`` are read
as the source kernel's tiling and take no part.

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

MUTATIONS = ("no_selection", "no_relu", "no_head_weights",
             "topk_not_renormalised", "no_index_key_norm", "no_qk_norm",
             "no_index_rope")
QUERY_BLOCK = 128
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


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope_sections(x, rows, theta, sections):
    """``x [S, H, D]``, ``rows [3, S]`` (temporal, height, width positions);
    rotate_half form: pair ``i`` is ``(i, i + D/2)`` and turns by the row
    its section names times ``theta^(-2 i / D)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    assert sum(sections) == d // 2, (sections, d)
    row_of_pair = np.repeat(np.arange(len(sections)), sections)
    pos = rows.astype(jnp.float32)[row_of_pair]             # [D/2, S]
    ang = (pos * inv_freq[:, None]).T[:, None, :]           # [S, 1, D/2]
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


def _attention(h, attn, *, heads, kv_heads, d, eps, theta, sections, sa,
               mutate, precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    q = mm(h, f32(attn["q_proj"]["kernel"])).reshape(s, heads, d)
    k = mm(h, f32(attn["k_proj"]["kernel"])).reshape(s, kv_heads, d)
    v = mm(h, f32(attn["v_proj"]["kernel"])).reshape(s, kv_heads, d)
    if mutate != "no_qk_norm":
        q = _rms_norm(q, _leaf(attn["q_norm"]["scale"]), eps)
        k = _rms_norm(k, _leaf(attn["k_norm"]["scale"]), eps)
    pos = jnp.arange(s)
    rows3 = jnp.stack([pos, pos, pos])      # text: the three rows are equal
    q = _rope_sections(q, rows3, theta, sections)
    k = _rope_sections(k, rows3, theta, sections)

    # the indexer: never in low precision (which keys a query takes is the
    # architecture, not an approximation of it)
    ix = attn["indexer"]
    hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    f = lambda a: _leaf(a).astype(jnp.float32)
    qi = (h @ f(ix["q_proj"]["kernel"])).reshape(s, hi, di)
    ki = h @ f(ix["k_proj"]["kernel"])
    if mutate != "no_index_key_norm":
        ki = _layer_norm(ki, f(ix["k_norm"]["scale"]), f(ix["k_norm"]["bias"]),
                         eps)
    if mutate != "no_index_rope":
        one_row = jnp.stack([pos])
        qi = _rope_sections(qi, one_row, theta, [di // 2])
        ki = _rope_sections(ki[:, None], one_row, theta, [di // 2])[:, 0]
    w = (h @ f(ix["weights_proj"]["kernel"])) * (hi ** -0.5) * (di ** -0.5)
    if mutate == "no_head_weights":
        w = jnp.ones_like(w) * (hi ** -0.5) * (di ** -0.5)

    group = heads // kv_heads
    qg = q.reshape(s, kv_heads, group, d)

    def one_query(args):
        q_t, qi_t, w_t, t = args
        seen = pos <= t
        dots = jnp.einsum("jd,sd->js", qi_t, ki)
        if mutate != "no_relu":
            dots = jnp.maximum(dots, 0.0)
        score = jnp.where(seen, jnp.einsum("j,js->s", w_t, dots), -jnp.inf)
        # a full stable sort, best first: equals keep the lower s first
        order = jnp.argsort(-score, stable=True)
        rank = jnp.zeros((s,), jnp.int32).at[order].set(
            jnp.arange(s, dtype=jnp.int32))
        taken = jnp.logical_and(seen, rank < topk)
        if mutate == "no_selection":
            taken = seen
        sc = jnp.einsum("gid,kgd->gik", q8(q_t), q8(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(taken[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("gik,kgd->gid", q8(p), q8(v)), taken

    y, taken = jax.lax.map(one_query, (qg, qi, w, pos),
                           batch_size=min(QUERY_BLOCK, s))
    return mm(y.reshape(s, heads * d), f32(attn["o_proj"]["kernel"])), taken


def _experts(h, mlp, *, top_k, renormalise, mutate, precision):
    q8, f32, _ = _ops(precision)
    # the router, never in low precision
    probs = jax.nn.softmax(h @ _leaf(mlp["gate"]).astype(jnp.float32), -1)
    n_experts = probs.shape[-1]
    vals, idx = jax.lax.top_k(probs, top_k)
    if renormalise and mutate != "topk_not_renormalised":
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
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
    return y, (idx, vals)


def _layer(x, layer, kw: Dict[str, Any]):
    eps = kw["eps"]
    h = _rms_norm(x, _leaf(layer["input_layernorm"]["scale"]), eps)
    y, taken = _attention(
        h, layer["self_attn"], heads=kw["heads"], kv_heads=kw["kv_heads"],
        d=kw["d"], eps=eps, theta=kw["theta"], sections=kw["sections"],
        sa=kw["sa"], mutate=kw["mutate"], precision=kw["precision"])
    x = x + y
    h = _rms_norm(x, _leaf(layer["post_attention_layernorm"]["scale"]), eps)
    y, routed = _experts(h, layer["mlp"], top_k=kw["top_k"],
                         renormalise=kw["renormalise"], mutate=kw["mutate"],
                         precision=kw["precision"])
    return x + y, taken, routed


def _settings(config: Dict[str, Any], mutate, precision) -> Dict[str, Any]:
    heads = int(config["num_attention_heads"])
    d = int(config.get("head_dim", int(config["hidden_size"]) // heads))
    sections = list(config.get("rope_scaling", {}).get("mrope_section")
                    or [d // 2])
    return dict(heads=heads, kv_heads=int(config["num_key_value_heads"]),
                d=d, eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_theta"]), sections=sections,
                sa={k: int(v) for k, v in config["sa_config"].items()},
                top_k=int(config["num_experts_per_tok"]),
                renormalise=bool(config["norm_topk_prob"]), mutate=mutate,
                precision=precision)


def forward(params: Dict[str, Any], config: Dict[str, Any], tokens,
            last: Optional[int] = None, mutate: Optional[str] = None,
            precision: str = "float32", keep: bool = False):
    """``(logits [S, V] float32, taken, routed)``: the logits (the last
    ``last`` positions if given) and, where ``keep``, a layer at a time the
    keys every query took (``[S, S]`` bool) and the experts every token took
    (``(ids [S, k], weights [S, k])``)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    kw = _settings(config, mutate, precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    taken, routed = [], []
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, layer: _layer(x, layer, kw))
        x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(
            jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            x, t, r = step(x, model[f"layers_{i}"])
            if keep:
                taken.append(t)
                routed.append(r)
        x = _rms_norm(x, _leaf(model["norm"]["scale"]).astype(jnp.float32),
                      kw["eps"])
        if last is not None:
            x = x[-last:]
        q8, f32, _ = _ops(precision)
        lg = jax.jit(lambda a, w: a @ w.astype(jnp.float32))(
            q8(x), f32(lm["lm_head"]["kernel"]))
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
