"""The one traffic generator against the mix files it reads."""
import json
import pathlib
import statistics

import numpy as np
import pytest

from benchmarks.chip.gen import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
SEED = 2**31 + 12345                      # seeds beyond 32 bits are valid


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def build(name, seed=SEED, **kw):
    m = mix(name)
    if m["arrival"]["process"] == "backlog":
        kw.setdefault("n_requests", 600)
    else:
        kw.setdefault("horizon_s", 400.0)
    return traffic.build(m, seed=seed, vocab=151936, max_len=4096, **kw)


@pytest.mark.parametrize("name", ["chat_prefix", "reason_decode",
                                  "long_docs"])
def test_same_seed_same_requests(name):
    a, b = build(name), build(name)
    assert a == b
    c = build(name, seed=SEED + 1)
    assert [r.tokens for r in a] != [r.tokens for r in c]


def _mean(spec):
    rng = np.random.default_rng(0)
    return float(np.mean([traffic.length(spec, rng) for _ in range(20000)]))


@pytest.mark.parametrize("name", ["chat_prefix", "reason_decode",
                                  "long_docs"])
def test_lengths_within_cuts_with_stated_medians(name):
    m = mix(name)
    reqs = build(name)
    outs = [r.max_new_tokens for r in reqs]
    o = m["output"]
    assert min(outs) >= o["min"] and max(outs) <= o["max"]
    assert statistics.median(outs) == pytest.approx(o["median"], rel=0.05)
    for r in reqs:
        assert len(r.tokens) + r.max_new_tokens <= 4096
    if name != "chat_prefix":
        # single-turn: the prompt is the message's segments
        seg = m["message"][0]
        if len(m["message"]) == 1:
            lens = [len(r.tokens) for r in reqs]
            assert min(lens) >= seg["min"] and max(lens) <= seg["max"]
            assert statistics.median(lens) == pytest.approx(seg["median"],
                                                            rel=0.05)
        else:
            q = m["message"][1]
            lens = [len(r.tokens) for r in reqs]
            assert min(lens) >= seg["min"] + q["min"]
            assert max(lens) <= seg["max"] + q["max"]
            assert statistics.median(lens) == pytest.approx(
                seg["median"] + (q["min"] + q["max"]) / 2, rel=0.05)


def test_open_loop_rate_and_backlog_due_at_zero():
    m = mix("chat_prefix")
    reqs = build("chat_prefix")
    span = max(r.due_s for r in reqs if r.due_s <= 400.0)
    rate = sum(1 for r in reqs if r.due_s <= 400.0) / span
    assert rate == pytest.approx(m["arrival"]["rate_req_s"], rel=0.1)
    assert all(r.due_s == 0.0 for r in build("reason_decode"))


def _lcp(a, b):
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def test_chat_prefix_share_is_what_the_parameters_imply():
    m = mix("chat_prefix")
    reqs = build("chat_prefix", horizon_s=300.0)
    seen = []
    shared = total = 0
    for r in reqs:                           # in due order
        best = max((_lcp(r.tokens, p) for p in seen), default=0)
        shared += best
        total += len(r.tokens)
        seen.append(r.tokens)
    measured = shared / total
    # implied: turn k of a session repeats the system prompt, k messages
    # and k - 1 replies; a retry repeats its whole prompt
    S = m["system_prompts"]["tokens"]
    M, R = _mean(m["message"][0]), _mean(m["reply"])
    s = m["sessions"]
    turns = range(s["turns_min"], s["turns_max"] + 1)
    sh = pr = 0.0
    for n in turns:
        for k in range(n):
            prompt = S + (k + 1) * M + k * R
            prev = S + k * M + max(k - 1, 0) * R if k else S
            sh += prev + s["retry_share"] * prompt
            pr += (1 + s["retry_share"]) * prompt
    implied = sh / pr
    assert measured == pytest.approx(implied, abs=0.04)


@pytest.mark.parametrize("name", ["reason_decode", "long_docs"])
def test_batch_mixes_share_no_prefix_longer_than_a_page(name):
    reqs = build(name, n_requests=300)
    firsts = {}
    for r in reqs:
        key = r.tokens[:17]
        assert key not in firsts, "two prompts share 17 leading tokens"
        firsts[key] = r.rid


def test_structure_seed_replays_one_trace_with_the_seed_s_tokens():
    a, b = build("chat_prefix", seed=1), build("chat_prefix", seed=2)
    shape = [(r.due_s, len(r.tokens), r.max_new_tokens, r.turn, r.retry)
             for r in a]
    assert shape == [(r.due_s, len(r.tokens), r.max_new_tokens, r.turn,
                      r.retry) for r in b]
    assert [r.tokens for r in a] != [r.tokens for r in b]
    assert a[0].due_s == 0.0


def test_system_prompts_are_the_ones_the_sessions_start_with():
    m = mix("chat_prefix")
    systems = {tuple(p) for p in traffic.system_prompts(
        m, seed=SEED, vocab=151936)}
    n = m["system_prompts"]["tokens"]
    firsts = [r for r in build("chat_prefix") if r.turn == 0]
    assert firsts and all(r.tokens[:n] in systems for r in firsts)
