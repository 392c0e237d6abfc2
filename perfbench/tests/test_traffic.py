"""The traffic generator is a pure function of (mix, seed) and keeps to the
mix's clips and to `prompt bucket + output <= max_seq_len`."""

import json
import os

import numpy as np

from perfbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def test_token_batches_pure_and_in_vocab():
    m = mix("train-s2048")
    a = traffic.token_batches(m, 4_000_000_123, 50304)
    b = traffic.token_batches(m, 4_000_000_123, 50304)
    c = traffic.token_batches(m, 4_000_000_124, 50304)
    assert a.shape == (m["queue"], m["batch"], m["seq"]) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 50304
    rows = a.reshape(-1, m["seq"])
    assert len({r.tobytes() for r in rows}) == len(rows)   # rows all differ


def test_request_sizes_keep_to_clips_and_capacity():
    m = mix("decode-conv-closed48")
    s = traffic.request_sizes(m)
    assert s.shape == (m["n_sizes"], 2)
    assert np.array_equal(s, traffic.request_sizes(m))
    p, o = s[:, 0], s[:, 1]
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    for pi, oi in s:
        assert traffic.prompt_bucket(pi, m["prompt_bucket_min"]) + oi \
            <= m["max_seq_len"]
    # prompts longer than answers, the tails INSIDE the ranges the engine's
    # buckets leave (a clip that binds half the prompts is no tail): the mix
    # states what binds, names no source and says so
    assert 200 < np.median(p) < 300 and 100 < np.median(o) < 160
    assert (p == m["prompt"]["max"]).sum() == 3 <= 0.05 * len(p)
    assert (o == m["output"]["max"]).sum() == 4 <= 0.05 * len(o)
    assert "3 of the 256 prompts" in m["lengths"] and "source" not in m
    assert "NOT from a trace" in m["lengths"] and m["assumed"]


def test_requests_same_sizes_in_the_same_order_other_tokens():
    m = mix("decode-conv-closed48")
    a = traffic.requests(m, 7, 50304)
    b = traffic.requests(m, 7, 50304)
    c = traffic.requests(m, 2 ** 31 + 5, 50304)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in c]
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 50304
               for p, _o in a)


def test_prompt_bucket_is_the_programs_ladder():
    from heat_tpu.nn.transformer import TransformerLM

    for n in (1, 7, 8, 9, 31, 32, 33, 1000, 1024):
        assert traffic.prompt_bucket(n, TransformerLM.PROMPT_BUCKET_MIN) \
            == TransformerLM.prompt_bucket(n)
