"""Traffic: each mix's generator is a function of its seed, follows its
stated distributions, and gives every seed the same work in another order."""

import json
import math

import numpy as np
import pytest

import bench_testlib as T
from harness import traffic

MIXES = sorted(p.stem for p in (T.BENCH / "traffic").glob("*.json"))


def load(name):
    return json.loads((T.BENCH / "traffic" / f"{name}.json").read_text())


def drain(gen, finish_rounds=3):
    """Every item the generator sends, finishing each at its due time with a
    served answer of max_new tokens, for a few rounds of follow-ups."""
    gen.horizon = 200.0
    gen.start()
    out = []
    for _ in range(finish_rounds):
        items = gen.pop_due(math.inf)
        if not items:
            break
        for it in items:
            out.append(it)
            gen.finished(it, [7] * it.max_new, it.due)
    return out


def fingerprint(items):
    return [(round(it.due, 9), it.owner, it.max_new, it.prompt.tobytes()) for it in items]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_traffic(mix):
    a = drain(traffic.make_generator(load(mix), 2**40 + 3, 1000))
    b = drain(traffic.make_generator(load(mix), 2**40 + 3, 1000))
    c = drain(traffic.make_generator(load(mix), 4, 1000))
    assert a and fingerprint(a) == fingerprint(b)
    assert fingerprint(a) != fingerprint(c)
    assert all(0 <= int(t) < 1000 for it in a for t in it.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_stated_distributions(mix):
    m = load(mix)
    for key in ("prompt", "output"):
        d = m[key]
        deck = traffic.quantile_deck(d, 4001)
        assert deck.min() >= d["min"] and deck.max() <= d["max"]
        assert abs(np.median(deck) - d["median"]) <= 1
        # the quartiles sit at median * exp(+-0.674 sigma), unless clipped
        q1, q3 = np.percentile(deck, [25, 75])
        assert q1 == pytest.approx(max(d["min"], d["median"] * math.exp(-0.6745 * d["sigma"])),
                                   rel=0.02, abs=1)
        assert q3 == pytest.approx(min(d["max"], d["median"] * math.exp(0.6745 * d["sigma"])),
                                   rel=0.02, abs=1)


def test_other_distributions():
    e = traffic.quantile_deck({"dist": "exponential", "mean": 2.0}, 20000)
    assert e.mean() == pytest.approx(2.0, rel=0.01)
    u = traffic.quantile_deck({"dist": "uniform_int", "min": 2, "max": 6}, 500)
    assert sorted(set(u.tolist())) == [2, 3, 4, 5, 6]
    assert np.bincount(u)[2:].tolist() == [100] * 5
    z = traffic.quantile_deck({"dist": "zipf", "s": 1.0, "n_items": 8}, 10000)
    share = np.bincount(z, minlength=8) / 10000
    w = 1 / np.arange(1, 9)
    np.testing.assert_allclose(share, w / w.sum(), atol=2e-3)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    m = load(mix)
    n = m.get("deck", traffic.DECK)

    def lengths(seed):
        deck = traffic.make_generator(m, seed, 1000)._deck(m["output"])
        return sorted(deck.draw() for _ in range(n))

    assert lengths(1) == lengths(2**35 + 9)


def test_each_deck_of_draws_holds_every_quantile_once():
    m = dict(load("batch_decode"), deck=16)
    gen = traffic.make_generator(m, 2**33 + 5, 1000)
    quantiles = sorted(traffic.quantile_deck(m["output"], 16).tolist())
    draws = [gen.output.draw() for _ in range(3 * 16)]
    for i in range(0, len(draws), 16):
        assert sorted(draws[i:i + 16]) == quantiles
    assert draws[:16] != draws[16:32]  # reshuffled each time it runs out


def test_closed_loop_starts_stationary():
    m = load("batch_decode")
    gen = traffic.make_generator(m, 5, 1000)
    gen.start()
    first = gen.pop_due(0.0)
    assert len(first) == m["closed"]["clients"]
    # a uniform share of each first request's output is already prompt
    assert max(len(it.prompt) for it in first) > m["prompt"]["max"]
    assert min(it.max_new for it in first) < m["output"]["min"]
    gen.finished(first[0], [1] * first[0].max_new, 3.0)
    (nxt,) = gen.pop_due(3.0 + m["closed"]["think_s"])
    assert nxt.owner == first[0].owner and nxt.due == 3.0 + m["closed"]["think_s"]


def test_sessions_share_system_prompts_and_grow_history():
    m = load("chat_sessions")
    gen = traffic.make_generator(m, 8, 1000)
    gen.horizon = 100.0
    gen.start()
    firsts = gen.pop_due(math.inf)
    gaps = np.diff([0.0] + [it.due for it in firsts])
    assert np.mean(gaps) == pytest.approx(1 / m["sessions"]["rate_per_s"], rel=0.2)
    systems = {gen.systems[i].tobytes() for i in range(m["sessions"]["system_prompts"])}
    sl = m["sessions"]["system_len"]
    assert all(it.prompt[:sl].tobytes() in systems for it in firsts)
    it = firsts[0]
    served = list(range(it.max_new))
    gen.finished(it, served, it.due + 1.0)
    nxt = [x for x in gen.pop_due(math.inf) if x.owner == it.owner]
    if nxt:  # the session may have ended (turn count or max_len)
        head = np.concatenate([it.prompt, np.asarray(served, np.int32)])
        assert nxt[0].prompt[: len(head)].tobytes() == head.tobytes()
        assert nxt[0].due > it.due + 1.0
        assert len(nxt[0].prompt) + nxt[0].max_new <= m["engine"]["max_len"]
