"""An expert layer that HOLDS a share of the experts its router names
(``moe/layer.py``: ``expert_shards``, ``expert_shard``; ``moe/sharded_moe.py:
held_moe``): the router stays as wide as the layer, a copy routed to an
absent expert takes no row and adds nothing, and **the shares' routed parts,
with the shared expert counted once, add up to the uncut layer**, which is
what ties one chip's share to the model.  The uncut layer here is the
benchmark's plain reference GIVEN THE WHOLE (``expert_shards`` 1 and every
bank), so the sum is held against code the program shares nothing with.
With the whole range a layer traces the program it traced before the range
existed."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import solar_open2 as ref  # noqa: E402
from deepspeed_tpu.moe import sharded_moe  # noqa: E402
from deepspeed_tpu.moe.layer import MoE  # noqa: E402

M, I, HELD, SHARDS, K, G = 32, 16, 3, 4, 4, 40
E = HELD * SHARDS


def _layer(shards=SHARDS, shard=0, held=HELD, **kw):
    return MoE(hidden_size=M, num_experts=held, intermediate_size=I, k=K,
               dtype=jnp.float32, param_dtype=jnp.float32,
               expert_parallel=False, dispatch_impl="grouped",
               scoring="sigmoid", expert_bias=True, expert_bias_init_std=0.1,
               routed_weight_eps=1e-20, expert_shards=shards,
               expert_shard=shard, **kw)


@pytest.fixture(scope="module")
def whole():
    """The uncut layer's parameters (12 experts) and 40 rows."""
    x = jax.random.normal(jax.random.PRNGKey(1), (G, M), jnp.float32)
    params = _layer(shards=1, held=E).init(jax.random.PRNGKey(0), x)
    return params["params"], x


def _share(params, shard):
    """What chip ``shard`` holds of the uncut layer: the router and its
    bias whole, its own experts' banks."""
    cut = lambda w: w[shard * HELD:(shard + 1) * HELD]
    return {**params, **{n: cut(params[n]) for n in ("w1", "w2", "w3")}}


def _reference(params, x, mutate=None):
    """The uncut routed layer by the plain reference."""
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, params, top_k=K, renormalise=True,
                            scaling=1.0, first_held=0, mutate=mutate,
                            precision="float32")


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Four shares of three experts under a router twelve wide: each share's
    routed part, summed, is the uncut layer's; with a shared expert, which
    every chip computes alike, counted ONCE, it is the whole feed-forward.
    A share's banks are three, its router and bias twelve wide."""
    params, x = whole
    parts, rows = [], 0
    for shard in range(SHARDS):
        layer = _layer(shard=shard)
        mine = _share(params, shard)
        shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
        assert jax.tree_util.tree_map(lambda a: a.shape, shapes["params"]) \
            == jax.tree_util.tree_map(lambda a: a.shape, mine)
        (y, _), stats = layer.apply({"params": mine}, x,
                                    mutable=["moe_stats"])
        parts.append(np.asarray(y))
        counts = np.asarray(stats["moe_stats"]["counts"][0])
        held = np.asarray(stats["moe_stats"]["held"][0])
        assert held.tolist() == [G * K, HELD]
        rows += int(counts[0])
    assert rows == G * K                # every copy went to exactly one chip
    want = np.asarray(_reference(params, x))
    assert np.abs(sum(parts) - want).max() < 1e-5
    assert np.abs(want).max() > 0.1
    # the uncut PROGRAM agrees too, and one share alone does not
    (uncut, _) = _layer(shards=1, held=E).apply({"params": params}, x)
    assert np.abs(np.asarray(uncut) - want).max() < 1e-5
    assert np.abs(parts[0] - want).max() > 1e-2
    # the shared expert, counted once
    shared = {n: {"kernel": jax.random.normal(jax.random.PRNGKey(i), s)}
              for i, (n, s) in enumerate((("gate_proj", (M, I)),
                                          ("up_proj", (M, I)),
                                          ("down_proj", (I, M))))}
    with jax.default_matmul_precision("highest"):
        once = np.asarray(ref._swiglu(x, shared, precision="float32"))
    assert np.abs((sum(parts) + once) - (want + once)).max() < 1e-5
    assert np.abs((sum(p + once for p in parts)) - (want + once)).max() > 1


def test_the_eight_shares_of_a_trinity_layer_add_up_to_the_uncut_layer():
    """``trinity-large-serve``'s share, small: eight chips hold four experts
    each of a router thirty-two wide, top 4 by sigmoid + bias, the chosen
    scores renormalised (``route_norm``) and scaled by 2.448
    (``route_scale``).  The eight routed parts, with the shared expert, which
    every chip computes alike, counted ONCE, are the uncut layer of the
    family's own plain reference (``benchmark/reference/trinity.py``)."""
    from benchmark.reference import trinity as tref

    shards, held, k, scale = 8, 4, 4, 2.448
    layer = lambda n, held, shard=0: MoE(       # noqa: E731
        hidden_size=M, num_experts=held, intermediate_size=I, k=k,
        dtype=jnp.float32, param_dtype=jnp.float32, expert_parallel=False,
        dispatch_impl="grouped", scoring="sigmoid", expert_bias=True,
        expert_bias_init_std=0.1, normalize_weights=True,
        routed_scaling_factor=scale, routed_weight_eps=1e-20,
        expert_shards=n, expert_shard=shard)
    x = jax.random.normal(jax.random.PRNGKey(2), (G, M), jnp.float32)
    params = layer(1, shards * held).init(jax.random.PRNGKey(3), x)["params"]
    parts, rows = [], 0
    for shard in range(shards):
        mine = {**params, **{n: params[n][shard * held:(shard + 1) * held]
                             for n in ("w1", "w2", "w3")}}
        (y, _), stats = layer(shards, held, shard).apply(
            {"params": mine}, x, mutable=["moe_stats"])
        parts.append(np.asarray(y))
        rows += int(np.asarray(stats["moe_stats"]["counts"][0])[0])
        assert np.asarray(stats["moe_stats"]["held"][0]).tolist() == [
            G * k, held]
    assert rows == G * k                # every copy went to exactly one chip
    shared = {n: {"kernel": 0.1 * jax.random.normal(jax.random.PRNGKey(i), s)}
              for i, (n, s) in enumerate((("gate_proj", (M, I)),
                                          ("up_proj", (M, I)),
                                          ("down_proj", (I, M))))}
    with jax.default_matmul_precision("highest"):
        routed = lambda mutate=None: np.asarray(tref._experts(  # noqa: E731
            x, params, top_k=k, renormalise=True, scaling=scale,
            first_held=0, mutate=mutate, precision="float32"))
        once = np.asarray(tref._swiglu(x, shared, precision="float32"))
    want = routed() + once
    assert np.abs((sum(parts) + once) - want).max() < 1e-5
    assert np.abs(sum(p + once for p in parts) - want).max() > 0.1
    assert np.abs(parts[0] + once - want).max() > 1e-2
    # and the scale is in the sum: the reference without it differs
    assert np.abs(sum(parts) - routed("route_scale_one")).max() > 1e-2


def test_a_share_is_the_references_share(whole):
    """One share against the reference given the same share, and against
    the reference's mutation that ignores the held range."""
    params, x = whole
    mine = _share(params, 2)
    (y, _) = _layer(shard=2).apply({"params": mine}, x)
    with jax.default_matmul_precision("highest"):
        given = lambda mutate: np.asarray(ref._experts(
            x, mine, top_k=K, renormalise=True, scaling=1.0,
            first_held=2 * HELD, mutate=mutate, precision="float32"))
        assert np.abs(np.asarray(y) - given(None)).max() < 1e-5
        assert np.abs(np.asarray(y) - given("held_range_ignored")).max() > 0.1


def test_dead_rows_and_absent_experts_take_no_row(whole):
    params, x = whole
    live = jnp.arange(G) % 3 != 0
    layer, mine = _layer(shard=1), _share(params, 1)
    (y, _), stats = layer.apply({"params": mine}, x, live=live,
                                mutable=["moe_stats"])
    (full, _) = layer.apply({"params": mine}, x)
    assert (np.asarray(y)[~np.asarray(live)] == 0).all()
    assert np.abs(np.asarray(y - full)[np.asarray(live)]).max() < 1e-6
    counts = np.asarray(stats["moe_stats"]["counts"][0])
    held = np.asarray(stats["moe_stats"]["held"][0])
    assert held[0] == int(live.sum()) * K and counts[0] < held[0]
    assert counts[1] <= HELD


@pytest.mark.parametrize("bias", [8.0, -8.0], ids=["all-held", "none-held"])
def test_a_router_that_sends_everything_here_drops_nothing(whole, bias):
    """A selection bias that puts every token's four copies on this chip's
    three experts and one more (12 x 40 / 4 = 120 rows of room, 120 live
    copies and then more with ``k`` raised is the other form's to take), or
    none of them: the count picks the form on the device and the result is
    the reference's share either way."""
    params, x = whole
    params = dict(params, expert_bias=params["expert_bias"].at[:HELD].add(
        bias))
    mine = _share(params, 0)
    (y, _), stats = _layer(shard=0).apply({"params": mine}, x,
                                          mutable=["moe_stats"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._experts(
            x, mine, top_k=K, renormalise=True, scaling=1.0, first_held=0,
            mutate=None, precision="float32"))
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    rows = int(np.asarray(stats["moe_stats"]["counts"][0])[0])
    assert rows == (G * HELD if bias > 0 else 0)
    room = -(-G * K // sharded_moe.HELD_ROOM)
    assert (rows > room) == (bias > 0)  # both forms are taken


def test_the_whole_range_traces_the_program_it_traced_before(whole):
    """``expert_shards`` 1 (every family the benchmark had) reaches
    ``grouped_moe``'s own body: no ``cond``, no ``held`` leaf, and the text
    a layer built without the two fields lowers to, to the letter."""
    params, x = whole
    text = lambda layer, p: str(jax.make_jaxpr(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["moe_stats"]))(p, x))
    plain = MoE(hidden_size=M, num_experts=E, intermediate_size=I, k=K,
                dtype=jnp.float32, param_dtype=jnp.float32,
                expert_parallel=False, dispatch_impl="grouped",
                scoring="sigmoid", expert_bias=True,
                expert_bias_init_std=0.1, routed_weight_eps=1e-20)
    whole_text = text(_layer(shards=1, held=E), params)
    assert whole_text == text(plain, params)
    assert "cond" not in whole_text
    assert "cond" in text(_layer(shard=0), _share(params, 0))
    _, stats = plain.apply({"params": params}, x, mutable=["moe_stats"])
    assert set(stats["moe_stats"]) == {"counts", "bias_swaps"}


def test_a_share_has_its_serving_form_only(whole):
    params, x = whole
    layer = MoE(hidden_size=M, num_experts=HELD, intermediate_size=I, k=2,
                dtype=jnp.float32, expert_parallel=False,
                dispatch_impl="sorted", expert_shards=SHARDS)
    with pytest.raises(NotImplementedError, match="expert_shards=4"):
        layer.init(jax.random.PRNGKey(0), x)
