"""The traffic generator: deterministic per seed, the same work for every
seed, and the declared distributions."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.harness.traffic import Mix, population, quantile, zipf_population

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def drain(m: Mix, n_sessions: int):
    """Every turn of the first sessions, answering each with one token."""
    out = []
    for _ in range(n_sessions):
        s = m.next_session()
        if s is None:
            break
        for _ in range(4):
            t = m.next_turn(s)
            if t is None:
                break
            think = m.answered(s, t, [7])
            out.append((s.start_s, s.tenant, t.request_id, t.prompt.tobytes(),
                        t.max_new_tokens, think))
    return out


@pytest.mark.parametrize("name", ["chat_sessions", "batch_decode"])
def test_same_seed_same_inputs(name):
    seed = 2**31 + 12345
    a = drain(Mix(mix(name), seed, vocab=32000, max_len=2048), 12)
    b = drain(Mix(mix(name), seed, vocab=32000, max_len=2048), 12)
    assert a == b


def test_a_turn_does_not_depend_on_the_order_sessions_ask():
    p = mix("batch_decode")
    m1 = Mix(p, 5, vocab=32000, max_len=2048)
    m2 = Mix(p, 5, vocab=32000, max_len=2048)
    s1 = [m1.next_session() for _ in range(3)]
    s2 = [m2.next_session() for _ in range(3)]
    t1 = [m1.next_turn(s) for s in s1]
    t2 = [m2.next_turn(s) for s in reversed(s2)][::-1]
    assert [t.prompt.tobytes() for t in t1] == [t.prompt.tobytes() for t in t2]
    assert [t.max_new_tokens for t in t1] == [t.max_new_tokens for t in t2]


@pytest.mark.parametrize("name", ["chat_sessions", "batch_decode"])
def test_every_seed_offers_the_same_schedule(name):
    """Seeds change token ids, never sizes, turns or start times."""
    a = drain(Mix(mix(name), 1, vocab=32000, max_len=2048), 12)
    b = drain(Mix(mix(name), 2**31 + 7, vocab=32000, max_len=2048), 12)
    assert [x[:3] + x[4:] for x in a] == [x[:3] + x[4:] for x in b]
    assert [x[3] for x in a] != [x[3] for x in b]


def test_the_schedule_holds_the_whole_population():
    p = mix("chat_sessions")
    m = Mix(p, 1, vocab=32000, max_len=2048)
    n, span = p["pool"], p["span"]
    assert sorted(m.user.ravel()) == sorted(
        population(p["user_tokens"], n * span, integer=True))
    assert sorted(m.gaps) == sorted(population(
        {"dist": "exponential", "mean": 1 / p["sessions"]["rate_per_s"]}, n))


def test_lognormal_population_follows_its_declaration():
    spec = {"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 16,
            "max": 512}
    pop = population(spec, 1001)
    assert np.median(pop) == pytest.approx(64, rel=1e-3)
    assert pop.min() >= 16 and pop.max() <= 512
    # the quantiles inside the clip are those of the lognormal
    assert np.quantile(pop, 0.75) == pytest.approx(
        64 * np.exp(0.8 * 0.6744897501960817), rel=2e-2)
    assert quantile(spec, 0.999999) == 512 and quantile(spec, 1e-6) == 16


def test_exponential_and_geometric_means():
    exp = population({"dist": "exponential", "mean": 2.0}, 4000)
    assert exp.mean() == pytest.approx(2.0, rel=2e-2)
    geo = population({"dist": "geometric", "mean": 2.5, "max": 10**6}, 4000,
                     integer=True)
    assert geo.min() == 1
    assert geo.mean() == pytest.approx(2.5, rel=2e-2)
    assert population({"dist": "geometric", "mean": 2.5, "max": 8}, 400,
                      integer=True).max() == 8


def test_zipf_tenants_in_proportion():
    ids = zipf_population(8, 1.0, 8000)
    w = 1 / np.arange(1, 9)
    want = w / w.sum() * 8000
    got = Counter(ids.tolist())
    for t in range(8):
        assert abs(got[t] - want[t]) <= 1


def test_chat_turns_carry_their_history_and_end_before_max_len():
    p = mix("chat_sessions")
    m = Mix(p, 99, vocab=32000, max_len=2048)
    for _ in range(20):
        s = m.next_session()
        prev = None
        while True:
            t = m.next_turn(s)
            if t is None:
                break
            system = m.system[s.tenant]
            assert np.array_equal(t.prompt[:len(system)], system)
            if prev is not None:
                answer = np.arange(prev.max_new_tokens, dtype=np.int32)
                body = np.concatenate([prev.prompt, answer])
                assert np.array_equal(t.prompt[:len(body)], body)
            room = 2048 - p["context_margin"]
            assert len(t.prompt) + t.max_new_tokens <= room
            m.answered(s, t, np.arange(t.max_new_tokens, dtype=np.int32))
            prev = t


def test_closed_loop_starts_every_client_in_the_stagger():
    p = mix("batch_decode")
    m = Mix(p, 3, vocab=32000, max_len=2048)
    starts = []
    while (s := m.next_session()) is not None:
        starts.append(s.start_s)
        assert s.turns is None
    assert len(starts) == p["sessions"]["clients"]
    assert max(starts) < p["sessions"]["stagger_s"]
