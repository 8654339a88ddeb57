"""The serving cell at a tiny size on the CPU: the plain reference agrees
with what the engine served; an altered token comes out as not correct
through the driver's own comparison, and so does the fp8 control."""
import time

import pytest

import tiny
from tiny import harness


@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "serve.py")


def _run(driver, tamper=None, seed=4000000007, **kw):
    import jax
    return driver.run(tiny.serve_cell(), seed, 2.0, False, jax.devices()[:1],
                      time.monotonic(), tamper=tamper, **kw)


def test_reference_agrees_with_what_was_served(driver):
    res = _run(driver)
    assert res["correct"], res["compared"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["notes"]["sampled_requests"] == 6
    # f32 on the CPU: greedy tokens are the reference's own
    assert res["compared"]["token_logit_gap"]["value"] < 1e-3
    assert res["compared"]["logprob_gap"]["value"] < 1e-3
    m = res["metrics"]
    assert m["serve_tpot_p95_ms"] > 0 and m["setup_s"] > 0
    assert 0 < m["serve_ttft_p95_ms"] < 1e9
    # tokens of requests completed in the window, not tokens produced in it
    assert 0 < m["serve_tokens_per_s"] <= res["notes"]["tokens_produced_per_s"]


def test_a_token_altered_where_it_is_produced_is_not_correct(driver):
    def alter(engine):
        record = engine._record_token
        vocab = engine.model.config.vocab_size

        def altered(s, tok, logp=None):
            if len(s.req.tokens) == 1:         # every request's 2nd token
                tok = (tok + 7) % vocab
            return record(s, tok, logp)
        engine._record_token = altered
    res = _run(driver, tamper=alter)
    assert not res["correct"]
    assert not res["compared"]["token_logit_gap"]["ok"]


def test_a_shed_request_is_not_correct(driver):
    def tiny_queue(engine):
        engine.queue_depth = 0
    res = _run(driver, tamper=tiny_queue)
    assert not res["correct"]
    assert res["failed"] > 0 and not res["compared"]["requests_failed"]["ok"]


def test_the_fp8_control_is_not_correct(driver):
    """The reference's own pass with fp8 operands, read at the served
    prompts and tokens, goes through the run's comparison and fails it."""
    import jax
    out = driver.control_readings(tiny.serve_cell(), 4000000009,
                                  jax.devices()[:1])
    assert out["program"]["correct"], out["program"]
    assert not out["control_fp8"]["correct"], out["control_fp8"]["compared"]
