"""What callers, the benchmark's readers and the trace's readers hold the
ragged engine to, as literals: its constructor, its config fields, the keys
it reports, and the names of its spans, events and compiled programs.  A
refactor that moves code keeps every one of them."""
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.common import HostStageStats
from deepspeed_tpu.inference.config import InferenceV2Config
from deepspeed_tpu.inference.v2 import kv_state, programs, ragged_engine
from deepspeed_tpu.inference.v2.ragged_engine import RaggedInferenceEngineV2
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config

SIGNATURE = dict(
    model=inspect.Parameter.empty, params=None, max_seqs=8, max_seq_len=512,
    prefill_chunk=128, rng=None, page_size=64, num_pages=None,
    topology=None, decode_block_size=8, kv_cache_dtype=None,
    kv_pool_bytes=None, window_pool_bytes=None, quantize_weights=None,
    kv_reserve="on_demand",
    pipeline=None, async_depth=None, harvest_interval=None, kv_tiering=None,
    prefix_cache=None, slo=None, trace_sample=None, replica=None,
    control=None, config=None)
V2_FIELDS = ["pipeline", "async_depth", "harvest_interval", "kv_cache_dtype",
             "kv_tiering", "prefix_cache", "control", "slo", "trace_sample"]
SPANS = {"plan": "engine.plan", "upload": "engine.upload",
         "dispatch": "engine.dispatch", "device": "engine.fetch",
         "harvest": "engine.harvest", "spill": "engine.spill",
         "restore": "engine.restore", "prefix": "engine.prefix",
         "rng_split": "engine.rng_split", "sample": "engine.sample",
         "reap": "engine.reap"}
EVENTS = {"decode_block", "prefill_chunk", "prefix_cow", "prefix_demote",
          "prefix_revive", "request_admit", "request_cancel", "request_evict",
          "request_export", "request_handoff", "request_handoff_ready",
          "request_import", "request_reap", "request_restore",
          "request_restore_failed", "request_spill", "request_submit"}
PROGRAMS = {"ragged_decode_block", "ragged_fused_step"}
# keys of a plain engine that served one request; "metrics" and
# "trace_sampling" come and go with the process-wide registry
STAGES = {"admissions", "admissions_ahead", "blocking_gets",
          "decode_blocks", "decode_blocks_ahead", "decode_key_token_steps",
          "device_ms", "device_wait_s", "dispatch_ms", "dispatches",
          "drains", "fused_head", "fused_tick_host",
          "fused_ticks", "fused_ticks_ahead", "harvest_ms", "harvests",
          "host_bound_fraction", "host_s",
          "kernels", "meta_uploads", "pipeline_window", "plan_ms", "pool",
          "prefill_key_tokens", "prefill_tokens", "prefix_ms", "reap_s",
          "requests", "restore_ms", "rng_split_s", "sample_s", "spill_ms",
          "ticks", "upload_ms"}
COMPLETED = {"errors", "finish_t", "handoff_stall_ms", "handoffs",
             "prefill_ms", "prompt_tokens", "queue_wait_ms",
             "router_queue_wait_ms", "spill_stall_ms", "spills", "submit_t",
             "tokens", "tpot_ms", "ttft_ms", "uid"}
MODULES = (ragged_engine, kv_state, programs)


def test_the_constructor_and_the_config_fields_are_the_published_ones():
    got = inspect.signature(RaggedInferenceEngineV2.__init__).parameters
    assert list(got)[1:] == list(SIGNATURE)
    assert {k: got[k].default for k in SIGNATURE} == SIGNATURE
    assert list(InferenceV2Config.model_fields) == V2_FIELDS


def test_span_event_and_program_names_are_the_published_ones():
    assert HostStageStats.SPAN_NAMES == SPANS
    src = "".join(inspect.getsource(m) for m in MODULES)
    assert set(re.findall(r'trace\.event\(\s*"([a-z_]+)"', src)) == EVENTS
    assert set(re.findall(r'__qualname__ = "([a-z_]+)"', src)) == PROGRAMS


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tinyllama", dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=False,
                     remat=False, use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 np.zeros((1, 8), np.int32))
    eng = RaggedInferenceEngineV2(model, params=params, max_seqs=2,
                                  max_seq_len=64, prefill_chunk=8,
                                  page_size=8, decode_block_size=4)
    eng.generate_all([np.arange(1, 10, dtype=np.int32)], max_new_tokens=6)
    return eng


def test_what_a_served_engine_reports(served):
    assert set(served.serving_stages()) - {"metrics",
                                           "trace_sampling"} == STAGES
    assert set(served.request_latency.completed()[0]) == COMPLETED
    assert served.fused_step().__name__ == "ragged_fused_step"
    assert served.fused_sample_step(False).__name__ == "ragged_fused_step"
    assert served.decode_block(False).__name__ == "ragged_decode_block"


def test_the_engine_reads_its_kv_state_and_nothing_else_writes_it(served):
    for name in ("allocator", "page_table", "cache", "tiering", "spills",
                 "restores"):
        assert getattr(served, name) is getattr(served.kv, name)
        with pytest.raises(AttributeError):
            setattr(served, name, None)
    # the router's prefix warming reads the index under this name
    assert served.prefix_index is served.kv.pfx
    assert served.cache_bytes() == served.kv.cache_bytes() > 0
    assert served.evictions == 0
    # the rule a reviewer can grep: pages move in kv_state.py alone
    moves = re.compile(r"allocator\.(free|grow|attach|cow|incref|decref)\("
                       r"|page_table\[[^]]*\] *=")
    for m in (ragged_engine, programs):
        assert not moves.search(inspect.getsource(m)), m.__name__
    assert "ragged_engine" not in inspect.getsource(programs)
    for line in inspect.getsource(kv_state).split("\n"):
        if line.startswith(("import ", "from ")):
            assert "ragged_engine" not in line and "programs" not in line
