"""The process-wide compile counter (``telemetry/compiles.py``): the
"zero compilations in steady state" tests lean on it, and the engines
compile on worker threads — so it must count there too."""
import threading

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.compiles import count_compiles


def _fresh_jit():
    # a new function object per call: never in jit's in-memory cache
    return jax.jit(lambda x: x * 3 + 1)


def test_counts_compile_on_this_thread_and_not_cache_hits():
    f = _fresh_jit()
    x = jnp.ones((7,))
    with count_compiles() as compiles:
        f(x).block_until_ready()
    assert compiles.count >= 1 and compiles.seconds > 0
    with count_compiles() as compiles:
        f(x).block_until_ready()            # warm: in-memory hit
    assert compiles.count == 0


def test_counts_compile_on_a_second_thread():
    f = _fresh_jit()
    with count_compiles() as compiles:
        t = threading.Thread(
            target=lambda: f(jnp.ones((5,))).block_until_ready())
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    assert compiles.count >= 1, (
        "a jit compiled on a worker thread must be counted")


def test_listeners_are_removed_on_exit():
    with count_compiles() as compiles:
        pass
    _fresh_jit()(jnp.ones((3,))).block_until_ready()
    assert compiles.count == 0
