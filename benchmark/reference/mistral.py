"""Plain reference for the Mistral family (Llama-shaped blocks).

Follows the published description (Jiang et al., "Mistral 7B", 2023, and
the ``MistralForCausalLM`` of the source's ``config.json``): token
embedding; per layer RMSNorm -> q/k/v projections -> rotary embedding
(half-split "rotate_half" form, theta from the config) -> grouped-query
attention under a causal **and** sliding-window mask -> output projection,
residual; RMSNorm -> SwiGLU ``down(silu(gate(x)) * up(x))``, residual;
final RMSNorm; untied head.

``jax.numpy`` only, float32 throughout, ``matmul precision highest`` (on a
TPU a float32 matmul otherwise runs in bf16 passes), no kernel, no cache,
no batching; one layer at a time, so that only one layer's weights are held
in float32 at once.  It shares no code with the program: it reads only the
parameter tree's leaves, by the names flax gives them.

Departures from the description: none in the mathematics.  The weights are
whatever the caller hands over (the program's random weights, cast up).

``mask_shift`` and ``precision`` are set only where the tolerance of the
serving check is set or tested: ``precision="float8"`` rounds every matmul
operand of the layers to e4m3, the nearest precision below the
configuration's bf16 (the control of ``benchmark/margin.py``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _layers(tree: Dict[str, Any], n_layers: int):
    """Layer ``i``'s leaves from either layout the program uses: unrolled
    (``layers_<i>``) or stacked for a scan (``layers/block`` with a leading
    layer axis)."""
    if "layers" in tree:
        stacked = tree["layers"]["block"]
        return lambda i: jax.tree_util.tree_map(lambda x: x[i], stacked)
    return lambda i: tree[f"layers_{i}"]


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
           window: Optional[int], mask_shift: int,
           precision: str = "float32"):
    if precision == "float32":
        q8 = lambda a: a
    else:
        low = {"float8": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}
        q8 = lambda a: a.astype(low[precision]).astype(jnp.float32)
    scale = lambda a: a.astype(jnp.float32)
    f32 = lambda a: q8(a.astype(jnp.float32))
    mm = lambda a, b: q8(a) @ b
    s = x.shape[0]
    attn, mlp = layer["self_attn"], layer["mlp"]
    h = _rms_norm(x, scale(layer["input_layernorm"]["scale"]), eps)
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
    # key j is seen by query i iff j <= i and j > i - window.  mask_shift
    # moves the causal edge; only the test of the tolerance sets it
    i, j = pos[:, None], pos[None, :]
    keep = j <= i + mask_shift
    if window is not None:
        keep &= j > i - window
    scores = jnp.where(keep[None], scores, -jnp.inf)
    y = jnp.einsum("hqk,khd->qhd", q8(jax.nn.softmax(scores, axis=-1)),
                   q8(v))
    x = x + mm(y.reshape(s, heads * d), f32(attn["o_proj"]["kernel"]))
    h = _rms_norm(x, scale(layer["post_attention_layernorm"]["scale"]), eps)
    gate = mm(h, f32(mlp["gate_proj"]["kernel"]))
    up = mm(h, f32(mlp["up_proj"]["kernel"]))
    return x + mm(jax.nn.silu(gate) * up, f32(mlp["down_proj"]["kernel"]))


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens,
           mask_shift: int = 0, last: Optional[int] = None,
           precision: str = "float32") -> jax.Array:
    """Float32 logits ``[S, V]`` (the last ``last`` positions if given) of
    one sequence ``tokens`` under ``config`` (the configuration file's
    keys, the source's names)."""
    lm = _lm_tree(params)
    model = lm["model"]
    n_layers = int(config["num_hidden_layers"])
    get = _layers(model, n_layers)
    kw = dict(heads=int(config["num_attention_heads"]),
              kv_heads=int(config["num_key_value_heads"]),
              eps=float(config["rms_norm_eps"]),
              theta=float(config["rope_theta"]),
              window=config.get("sliding_window"), mask_shift=mask_shift,
              precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, layer: _layer(x, layer, **kw))
        x = model["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
        for i in range(n_layers):
            x = step(x, get(i))
        x = _rms_norm(x, model["norm"]["scale"].astype(jnp.float32),
                      kw["eps"])
        if last is not None:
            x = x[-last:]
        return jax.jit(lambda a, w: a @ w.astype(jnp.float32))(
            x, lm["lm_head"]["kernel"])


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
                   prompt, generated, mask_shift: int = 0) -> np.ndarray:
    """For each greedy token the system generated after ``prompt``: how far
    its reference logit lies under the reference maximum at its position
    (0 where the reference picks the same token)."""
    n = len(generated)
    seq = list(prompt) + list(generated)
    lg = np.asarray(logits(params, config, seq[:-1], mask_shift=mask_shift,
                           last=n))
    picked = lg[np.arange(n), np.asarray(generated)]
    return lg.max(axis=-1) - picked
