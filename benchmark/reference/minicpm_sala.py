"""Plain reference for the MiniCPM-SALA family (sparse attention over
chosen blocks beside linear attention over a decaying matrix state).

Follows the published ``config.json`` of ``openbmb/MiniCPM-SALA``
(``model_type: minicpm_sala``).  With ``N`` RMSNorm (eps ``rms_norm_eps``,
learned scale), ``h`` the residual stream and ``a = scale_depth /
sqrt(32)`` (the PUBLISHED depth, ``depth_scale_layers``)::

    h_0 = E[id] * scale_emb
    h <- h + a * Mixer_l(N(h))
    h <- h + a * W_down(silu(W_gate u) * W_up u),  u = N(h)
    logits = W_head(N(h) / (hidden_size / dim_model_base))

- ``Mixer_l``, where ``mixer_types[l] == "lightning-attn"``: ``q, k, v``
  without bias; RMSNorm over EACH HEAD of q and of k; rotary embedding
  (half-split, ``rope_theta``) on both; ``q / sqrt(d)``; per head ``h`` a
  ``d x d`` state, **token by token**: ``S_t = l_h S_(t-1) + k_t^T v_t``,
  ``o_t = q_t S_t``, ``l_h = exp(-2^(-8 (h + 1) / heads))``; ``y =
  W_o(N_E(o) * sigmoid(W_g u))``.
- ``Mixer_l``, where ``"minicpm4"``: grouped-query attention, no rotary
  embedding, RMSNorm over each head of q and of k, scale ``1 / sqrt(d)``,
  ``y = W_o(att * sigmoid(W_g u))``.  **Query by query**: a query at
  position ``t < dense_len`` attends every key ``i <= t``.  From
  ``dense_len`` on: (i) compressed keys ``c_j = mean(k_i, stride j <= i <
  stride j + kernel)`` a KV head, over the windows with ``stride j + kernel
  - 1 <= t``; (ii) ``p_(h,j) = softmax_j(q_(t,h) . c_j / sqrt(d))``; (iii)
  ``r_(g,j)`` the sum of ``p_(h,j)`` over the heads of group ``g``; (iv) a
  block's score the maximum of ``r_(g,j)`` over the windows that overlap
  it (``stride j < block (m + 1)`` and ``stride j + kernel > block m``);
  (v) chosen: the first ``init_blocks`` blocks, the blocks that overlap
  positions ``t - window_size + 1 .. t``, and the best-scored others until
  ``topk`` stand (neighbouring blocks often tie, the window that straddles
  them being the best of both: the lower block number wins, as
  ``lax.top_k`` has it); (vi) softmax attention over the keys ``i <= t`` of the
  chosen blocks of the head's group.

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one layer at a time, the recurrence a
``lax.scan`` over tokens and the selection a ``lax.map`` over queries.  It
shares no code with the program: it reads only the parameter tree's
leaves, by the names flax gives them.

Departures from the description, each also under ``assumed`` in the
configuration file: **the rule by position** (HF's code takes the dense
path when the CALL is shorter than ``dense_len`` and the sparse path for
every query of a longer call; a chunked prefill cannot reproduce a rule by
call length, a rule by position gives the same logits however a prompt is
chunked, and a token-by-token decode agrees with both); the selection's
sizes are MiniCPM4's ``sparse_config`` (absent from this ``config.json``);
the head-wise slopes are Lightning Attention's without MiniMax's factor by
layer; the output norm is over the concatenated heads and before the gate;
``a`` keeps the published depth under a cut in depth.

``mutate`` and ``precision`` are set only by the tests of the tolerance and
by the margin's own measurement: each ``mutate`` value is one way a wrong
program could differ, and ``precision="float8"`` rounds every matmul
operand to e4m3, the nearest precision below the configuration's bf16 (the
selection's scores and the state stay in float32: which blocks a query
takes is the architecture).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("no_selection", "no_decay", "rope_on_sparse", "no_output_gate",
             "no_output_norm", "no_qk_norm", "depth_scale_cut",
             "no_local_window")
QUERY_BLOCK = 256
SPARSE_DEFAULTS = dict(kernel_size=32, kernel_stride=16, block_size=64,
                       init_blocks=1, window_size=2048, topk=64,
                       dense_len=8192)


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
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    return q8, f32, lambda a, b: q8(a) @ b


def _qkv(h, mixer, heads, kv_heads, d, eps, mutate, precision):
    _, f32, mm = _ops(precision)
    s = h.shape[0]
    q = mm(h, f32(mixer["q_proj"]["kernel"])).reshape(s, heads, d)
    k = mm(h, f32(mixer["k_proj"]["kernel"])).reshape(s, kv_heads, d)
    v = mm(h, f32(mixer["v_proj"]["kernel"])).reshape(s, kv_heads, d)
    if mutate != "no_qk_norm":
        q = _rms_norm(q, _leaf(mixer["q_norm"]["scale"]), eps)
        k = _rms_norm(k, _leaf(mixer["k_norm"]["scale"]), eps)
    gate = jax.nn.sigmoid(mm(h, f32(mixer["g_proj"]["kernel"])))
    if mutate == "no_output_gate":
        gate = jnp.ones_like(gate)
    return q, k, v, gate


def _lightning(h, mixer, *, heads, d, eps, theta, mutate, precision):
    _, f32, mm = _ops(precision)
    s = h.shape[0]
    q, k, v, gate = _qkv(h, mixer, heads, heads, d, eps, mutate, precision)
    pos = jnp.arange(s)
    q = _rope(q, pos, theta) / np.sqrt(d)
    k = _rope(k, pos, theta)
    slope = 2.0 ** (-8.0 * (np.arange(heads, dtype=np.float32) + 1) / heads)
    decay = jnp.asarray(np.ones_like(slope) if mutate == "no_decay"
                        else np.exp(-slope))[:, None, None]

    def step(state, qkv):                 # one token
        q_t, k_t, v_t = qkv               # [H, d]
        state = decay * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v))
    o = o.reshape(s, heads * d)
    if mutate != "no_output_norm":
        o = _rms_norm(o, _leaf(mixer["o_norm"]["scale"]), eps)
    return mm(o * gate, f32(mixer["o_proj"]["kernel"]))


def _chosen_blocks(q_t, t, ckeys, n_blocks, sp, mutate):
    """One query: ``q_t [Hkv, G, d]`` at position ``t`` -> ``[Hkv, NB]``
    bool, the blocks its groups attend (steps (i)-(v))."""
    kernel, stride, block = (sp["kernel_size"], sp["kernel_stride"],
                             sp["block_size"])
    d = q_t.shape[-1]
    n_windows = ckeys.shape[1]
    j = jnp.arange(n_windows)
    seen = j * stride + kernel - 1 <= t
    s = jnp.einsum("gid,gjd->gij", q_t, ckeys) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    p = jnp.where(seen[None, None], p, 0.0)
    r = jnp.sum(p, axis=1)                                      # [Hkv, NW]
    m = jnp.arange(n_blocks)
    overlap = jnp.logical_and(j[None] * stride < (m[:, None] + 1) * block,
                              j[None] * stride + kernel > m[:, None] * block)
    score = jnp.max(jnp.where(
        jnp.logical_and(overlap, seen[None])[None], r[:, None, :],
        -jnp.inf), axis=-1)                                       # [Hkv, NB]
    first_local = jnp.maximum(t - sp["window_size"] + 1, 0) // block
    forced = m < sp["init_blocks"]
    if mutate != "no_local_window":
        forced = jnp.logical_or(forced, m >= first_local)
    visible = m * block <= t
    score = jnp.where(forced[None], jnp.inf, score)
    score = jnp.where(visible[None], score, -jnp.inf)
    k_top = min(sp["topk"], n_blocks)
    _, idx = jax.lax.top_k(score, k_top)
    picked = jnp.zeros((score.shape[0], n_blocks), bool).at[
        jnp.arange(score.shape[0])[:, None], idx].set(True)
    return jnp.logical_and(picked, visible[None])


def _select_attention(h, mixer, *, heads, kv_heads, d, eps, theta, sp,
                      mutate, precision):
    q8, f32, mm = _ops(precision)
    s = h.shape[0]
    q, k, v, gate = _qkv(h, mixer, heads, kv_heads, d, eps, mutate,
                         precision)
    if mutate == "rope_on_sparse":
        q, k = _rope(q, jnp.arange(s), theta), _rope(k, jnp.arange(s), theta)
    kernel, stride, block = (sp["kernel_size"], sp["kernel_stride"],
                             sp["block_size"])
    n_blocks = -(-s // block)
    n_windows = max((s - kernel) // stride + 1, 1)
    kp = jnp.pad(k, ((0, max(kernel - s, 0)), (0, 0), (0, 0)))
    window = (jnp.arange(n_windows)[:, None] * stride
              + jnp.arange(kernel)[None])
    ckeys = jnp.mean(kp[window], axis=1).transpose(1, 0, 2)  # [Hkv, NW, d]
    group = heads // kv_heads
    qg = q.reshape(s, kv_heads, group, d)
    key_pos = jnp.arange(s)

    def one_query(args):
        q_t, t = args
        dense = jnp.broadcast_to(key_pos // block <= t // block,
                                 (kv_heads, s))
        if mutate == "no_selection":
            allowed = dense
        else:
            chosen = _chosen_blocks(q_t, t, ckeys, n_blocks, sp, mutate)
            allowed = jnp.where(t < sp["dense_len"], dense,
                                jnp.repeat(chosen, block, axis=-1)[:, :s])
        allowed = jnp.logical_and(allowed, (key_pos <= t)[None])
        sc = jnp.einsum("gid,kgd->gik", q8(q_t), q8(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed[:, None], sc, -jnp.inf), -1)
        return jnp.einsum("gik,kgd->gid", q8(p), q8(v))

    y = jax.lax.map(one_query, (qg, key_pos),
                    batch_size=min(QUERY_BLOCK, s))
    return mm(y.reshape(s, heads * d) * gate, f32(mixer["o_proj"]["kernel"]))


def _dense_mlp(h, mlp, *, precision):
    _, f32, mm = _ops(precision)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    return mm(act, f32(mlp["down_proj"]["kernel"]))


def _layer(x, layer, *, linear: bool, a: float, kw: Dict[str, Any]):
    eps = kw["eps"]
    h = _rms_norm(x, _leaf(layer["input_layernorm"]["scale"]), eps)
    if linear:
        y = _lightning(h, layer["lightning_attn"], heads=kw["lin_heads"],
                       d=kw["lin_d"], eps=eps, theta=kw["theta"],
                       mutate=kw["mutate"], precision=kw["precision"])
    else:
        y = _select_attention(h, layer["self_attn"], heads=kw["heads"],
                              kv_heads=kw["kv_heads"], d=kw["d"], eps=eps,
                              theta=kw["theta"], sp=kw["sp"],
                              mutate=kw["mutate"], precision=kw["precision"])
    x = x + a * y
    h = _rms_norm(x, _leaf(layer["post_attention_layernorm"]["scale"]), eps)
    return x + a * _dense_mlp(h, layer["mlp"], precision=kw["precision"])


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    types = list(config["mixer_types"])
    n_layers = int(config["num_hidden_layers"])
    assert len(types) == n_layers, (len(types), n_layers)
    sp = dict(SPARSE_DEFAULTS)
    sp.update(config.get("sparse_config", {}))
    hidden = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    depth = (n_layers if mutate == "depth_scale_cut"
             else int(config.get("depth_scale_layers", 32)))
    a = float(config["scale_depth"]) / float(np.sqrt(depth))
    kw = dict(heads=heads, kv_heads=int(config["num_key_value_heads"]),
              d=int(config.get("head_dim", hidden // heads)),
              lin_heads=int(config["lightning_nh"]),
              lin_d=int(config["lightning_head_dim"]),
              eps=float(config["rms_norm_eps"]),
              theta=float(config["rope_theta"]), sp=sp, mutate=mutate,
              precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        steps: Dict[bool, Any] = {}      # one compiled step a kind of layer
        embedding = _leaf(model["embed_tokens"]["embedding"])
        x = embedding[tokens].astype(jnp.float32) * float(
            config["scale_emb"])
        for i in range(n_layers):
            linear = types[i] == "lightning-attn"
            if linear not in steps:
                steps[linear] = jax.jit(
                    lambda x, layer, linear=linear: _layer(
                        x, layer, linear=linear, a=a, kw=kw))
            x = steps[linear](x, model[f"layers_{i}"])
        x = _rms_norm(x, _leaf(model["norm"]["scale"]).astype(jnp.float32),
                      kw["eps"])
        if last is not None:
            x = x[-last:]
        x = x / (hidden / float(config["dim_model_base"]))
        q8, f32, mm = _ops(precision)
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
