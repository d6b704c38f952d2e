"""The pairing of client and engine records that places a request's pages
in the window (``drivers/serve.py:_pool_filled``), and what it refuses."""
from benchmark.layer_metrics import windows as W

OFFSET = 100.0          # the engine's clock runs this far ahead


def _records():
    """Two requests: prompts of 1000 and 200 tokens, 11 and 5 generated;
    the engine admits each 0.1 s after it was sent, prefills for 1 s and
    0.2 s, decodes for 1 s.  Before them, the set-up's own request."""
    client, engine = [], [
        {"submit_t": OFFSET - 5.0, "finish_t": OFFSET - 4.0,
         "queue_wait_ms": 0.0, "prefill_ms": 10.0, "tokens": 16}]
    for send, prompt, new, prefill in ((10.0, 1000, 11, 1.0),
                                       (11.5, 200, 5, 0.2)):
        client.append({"ok": True, "send_t": send, "first_t": send + 1.5,
                       "last_t": send + 2.5, "prompt_tokens": prompt,
                       "max_new_tokens": new, "streamed": new})
        engine.append({"submit_t": OFFSET + send + 0.01,
                       "finish_t": OFFSET + send + 0.11 + prefill + 1.0,
                       "queue_wait_ms": 100.0, "prefill_ms": prefill * 1e3,
                       "tokens": new})
    return client, engine


def test_spans_pair_in_order_and_come_back_on_the_benchmarks_clock():
    client, engine = _records()
    spans = W.request_spans(client, engine, OFFSET)
    assert [s["prompt_tokens"] for s in spans] == [1000.0, 200.0]
    assert abs(spans[0]["admit"] - 10.11) < 1e-9
    assert abs(spans[0]["prefill_end"] - 11.11) < 1e-9
    assert abs(spans[1]["finish"] - 12.81) < 1e-9
    # half-way through its prefill a request holds half its prompt; at its
    # finish the prompt and everything generated
    assert abs(W.kv_tokens_held(spans[0], 10.61) - 500.0) < 1e-6
    assert abs(W.kv_tokens_held(spans[0], 12.11) - 1011.0) < 1e-6
    assert W.kv_tokens_held(spans[0], 9.0) == 0.0


def test_a_pairing_that_does_not_check_out_is_refused():
    client, engine = _records()
    assert W.request_spans(client, engine[:-1], OFFSET) is None
    engine[1]["tokens"] += 1
    assert W.request_spans(client, engine, OFFSET) is None
    assert W.request_spans([], engine, OFFSET) is None
