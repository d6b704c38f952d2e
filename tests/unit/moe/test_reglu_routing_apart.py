"""``moe/layer.py``: the ReGLU expert body, a router that reads another
tensor than the rows it dispatches (two calls of one module), and an unknown
``activation`` refused by name, each against a ten-line einsum."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.layer import MoE

M, E, I, K, G = 32, 8, 16, 3, 40


def _moe(**kw):
    return MoE(hidden_size=M, num_experts=E, intermediate_size=I, k=K,
               dtype=jnp.float32, param_dtype=jnp.float32,
               expert_parallel=False, **kw)


class _RoutedApart(nn.Module):
    """The router reads ``h``; the experts take ``x``."""
    impl: str = "grouped"

    @nn.compact
    def __call__(self, h, x, live=None):
        moe = _moe(activation="reglu", dispatch_impl=self.impl, name="moe")
        routing = moe(h, route_only=True)
        return moe(x, live=live, routing=routing)[0]


def _plain(p, h, x, gate_act=jax.nn.relu):
    """sum_j w_j W_down^j (act(W_gate^j x) * W_up^j x), the chosen and their
    weights from ``softmax(h W_r)`` renormalised over the chosen."""
    probs = jax.nn.softmax(h @ p["gate"], axis=-1)
    vals, idx = jax.lax.top_k(probs, K)
    w = jnp.sum(jax.nn.one_hot(idx, E) * (vals / vals.sum(-1, keepdims=True)
                                          )[..., None], axis=1)   # [G, E]
    mid = (gate_act(jnp.einsum("gm,emi->egi", x, p["w1"]))
           * jnp.einsum("gm,emi->egi", x, p["w3"]))
    return jnp.einsum("ge,egm->gm", w, jnp.einsum("egi,eim->egm", mid,
                                                  p["w2"]))


@pytest.fixture(scope="module")
def inputs():
    h, x = jax.random.normal(jax.random.PRNGKey(1), (2, G, M))
    return h, x


def test_reglu_with_a_router_input_apart_from_the_rows(inputs):
    h, x = inputs
    net = _RoutedApart()
    params = net.init(jax.random.PRNGKey(0), h, x)["params"]
    # one module, one set of leaves: the routing call made no second gate
    assert sorted(params["moe"]) == ["gate", "w1", "w2", "w3"]
    with jax.default_matmul_precision("highest"):
        got = net.apply({"params": params}, h, x)
        want = _plain(params["moe"], h, x)
        np.testing.assert_allclose(got, want, atol=2e-5)
        # and it IS the router's input that chose: routed from the rows
        # themselves, or through SwiGLU, the result is another
        for other in (_plain(params["moe"], x, x),
                      _plain(params["moe"], h, x, jax.nn.silu)):
            assert np.abs(got - other).max() > 1e-2
        # rows that are no token take no expert row
        live = jnp.arange(G) % 3 != 0
        masked = net.apply({"params": params}, h, x, live)
        np.testing.assert_allclose(masked[live], want[live], atol=2e-5)
        assert (np.asarray(masked[~live]) == 0).all()


@pytest.mark.parametrize("impl", ["grouped", "sorted", "einsum"])
def test_reglu_in_both_expert_bodies(inputs, impl):
    """A caller that passes neither routes from its rows, as before; the
    grouped body and the dense body both know ``reglu``."""
    _, x = inputs
    moe = _moe(activation="reglu", dispatch_impl=impl, drop_tokens=False)
    params = moe.init(jax.random.PRNGKey(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        got, _ = moe.apply({"params": params}, x, False)
        np.testing.assert_allclose(got, _plain(params, x, x), atol=2e-5)


def test_a_router_input_apart_is_the_dropless_forms_alone(inputs):
    h, x = inputs
    with pytest.raises(NotImplementedError, match="apart from the"):
        _RoutedApart(impl="sorted").init(jax.random.PRNGKey(0), h, x)


@pytest.mark.parametrize("impl", ["grouped", "sorted"])
def test_an_unknown_activation_raises_by_name(inputs, impl):
    moe = _moe(activation="geglu", dispatch_impl=impl)
    with pytest.raises(ValueError, match="geglu"):
        moe.init(jax.random.PRNGKey(0), inputs[1])
