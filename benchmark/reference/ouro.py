"""Plain reference for the Ouro family (looped language models).

Follows Zhu et al., "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741) and HF ``modeling_ouro.py``: token embedding ``h_0``;
then ``total_ut_steps`` PASSES over the same stack of blocks with the same
weights, ``h_u = N_f(Block_L(... Block_1(h_{u-1})))``: the final RMSNorm
``N_f`` closes EVERY pass and its output opens the next.  A block has four
RMSNorms ("sandwich"): ``x + N2(Attn(N1 x))``, then ``x + N4(SwiGLU(N3
x))`` (HF's ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``).  ``Attn`` is
causal multi-head attention, rotary embedding on every dim of a head
(half-split "rotate_half" form), no bias, no QK-norm; ``SwiGLU(h) =
W_down(silu(W_gate h) * W_up h)``.  Logits ``= W_head h_U`` (untied head).

The exit gate: ``lambda_u = sigmoid(w_g . h_u + b_g)``, one ``Linear(hidden,
1)`` shared by the passes; ``p_u = lambda_u * prod_{j<u}(1 - lambda_j)`` for
``u < U`` and ``p_U`` the remainder (:func:`exit_pdf`).  At the published
``early_exit_threshold`` 1 a token stops at its last pass, so the gate moves
no logit; a threshold below 1 is not this reference.

There is no cache here, so which keys a pass attends to is not a choice:
pass ``u`` of layer ``l`` sees the keys and values that ITS OWN input gives
at every earlier position.  A system that serves pass ``u`` the K/V another
pass wrote computes something else (``tests/unit/models/test_ouro.py`` makes
an engine do so, and it fails).

``jax.numpy`` only, float32 throughout, ``matmul precision highest``, no
kernel, no cache, no batching; one block at a time (a jitted call a block a
pass: one block's matrices in float32 at a time, 205 MB at the published
widths), so that 716 tokens fit beside a serving engine on a 16 GB chip.
It shares no code with the program: it reads only the parameter tree's
leaves, by the names flax gives them.

Departures from the description: none in the mathematics.  Assumed, and
listed under ``assumed`` in the configuration file: the sandwich norms and
``N_f`` inside the loop (``config.json`` has no key for either; both are
unconditional in the model code), the gate's form.  The weights are whatever
the caller hands over (the program's random weights, cast up).

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

MUTATIONS = ("one_step_fewer", "final_norm_once", "no_post_norms",
             "rope_theta_10000")


def _leaf(x):
    """A parameter as an array, whether or not flax boxed it."""
    return jax.tree_util.tree_leaves(x)[0]


def _lm_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    tree = params["params"] if "params" in params else params
    return tree["lm"] if "lm" in tree else tree   # loss wrapper or bare LM


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


def _block(x, layer, *, heads: int, kv_heads: int, eps: float, theta: float,
           post_norms: bool, precision: str):
    if precision == "float8":
        q8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q8 = lambda a: a
    f32 = lambda a: q8(_leaf(a).astype(jnp.float32))
    mm = lambda a, b: q8(a) @ b
    s = x.shape[0]
    attn, mlp = layer["self_attn"], layer["mlp"]
    h = _rms_norm(x, layer["input_layernorm"]["scale"], eps)
    q = mm(h, f32(attn["q_proj"]["kernel"]))
    k = mm(h, f32(attn["k_proj"]["kernel"]))
    v = mm(h, f32(attn["v_proj"]["kernel"]))
    d = q.shape[-1] // heads
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
    a = mm(y.reshape(s, heads * d), f32(attn["o_proj"]["kernel"]))
    if post_norms:
        a = _rms_norm(a, layer["input_layernorm_2"]["scale"], eps)
    x = x + a

    h = _rms_norm(x, layer["post_attention_layernorm"]["scale"], eps)
    act = (jax.nn.silu(mm(h, f32(mlp["gate_proj"]["kernel"]))) *
           mm(h, f32(mlp["up_proj"]["kernel"])))
    m = mm(act, f32(mlp["down_proj"]["kernel"]))
    if post_norms:
        m = _rms_norm(m, layer["post_attention_layernorm_2"]["scale"], eps)
    return x + m


def _passes(params: Dict[str, Any], config: Dict[str, Any], tokens,
            mutate: Optional[str], precision: str):
    """``[h_1 .. h_U]``, each ``[S, hidden]`` float32 (under a mutation:
    what the wrong model would call them), and the language-model tree."""
    assert mutate is None or mutate in MUTATIONS, mutate
    lm = _lm_tree(params)
    model = lm["model"]
    eps = float(config["rms_norm_eps"])
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]), eps=eps,
              theta=(10000.0 if mutate == "rope_theta_10000"
                     else float(config["rope_theta"])),
              post_norms=mutate != "no_post_norms", precision=precision)
    steps = int(config["total_ut_steps"])
    if mutate == "one_step_fewer":
        steps -= 1
    tokens = jnp.asarray(tokens, jnp.int32)
    step = jax.jit(lambda x, layer: _block(x, layer, **kw))
    x = _leaf(model["embed_tokens"]["embedding"])[tokens].astype(jnp.float32)
    hs = []
    for u in range(steps):
        for i in range(int(config["num_hidden_layers"])):
            x = step(x, model[f"layers_{i}"])
        if mutate != "final_norm_once" or u == steps - 1:
            x = _rms_norm(x, model["norm"]["scale"], eps)
        hs.append(x)
    return hs, lm


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           last: Optional[int] = None, mutate: Optional[str] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    with jax.default_matmul_precision("highest"):
        hs, lm = _passes(params, config, tokens, mutate, precision)
        x = hs[-1] if last is None else hs[-1][-last:]
        return jax.jit(lambda a, w: a @ w.astype(jnp.float32))(
            x, _leaf(lm["lm_head"]["kernel"]))


def exit_pdf(params: Dict[str, Any], config: Dict[str, Any],
             tokens) -> np.ndarray:
    """``[S, U]``: for every position the probability of stopping at each
    pass, from the gate on ``h_1 .. h_U``; rows sum to 1."""
    with jax.default_matmul_precision("highest"):
        hs, lm = _passes(params, config, tokens, None, "float32")
        gate = lm["model"]["early_exit_gate"]
        w = _leaf(gate["kernel"]).astype(jnp.float32)
        b = _leaf(gate["bias"]).astype(jnp.float32)
        lam = [np.asarray(jax.nn.sigmoid(h @ w + b))[:, 0] for h in hs]
    left = np.ones_like(lam[0])
    pdf = []
    for lam_u in lam[:-1]:
        pdf.append(lam_u * left)
        left = left * (1.0 - lam_u)
    pdf.append(left)
    return np.stack(pdf, axis=1)


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
