"""The traffic generator: same seed, same trace; lengths from their sets;
every seed offered the same work."""
import collections
import json
import os

from bench import arrivals

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG_SEED = 2**33 + 12345


def mix(name):
    with open(os.path.join(ROOT, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_trace():
    a = arrivals.generate(mix("chat"), BIG_SEED, 4.0, 30.0, 92544)
    b = arrivals.generate(mix("chat"), BIG_SEED, 4.0, 30.0, 92544)
    assert a == b
    c = arrivals.generate(mix("chat"), BIG_SEED + 1, 4.0, 30.0, 92544)
    assert a != c


def test_lengths_come_from_their_sets():
    for name, vocab in (("chat", 92544), ("rag", 64000)):
        m = mix(name)
        for a in arrivals.generate(m, 7, 6.0, 40.0, vocab):
            assert len(a.prompt) in m["prompt_len"]["values"]
            assert m["max_new"]["lo"] <= a.max_new <= m["max_new"]["hi"]
            assert all(1 <= t < vocab for t in a.prompt)


def test_every_seed_gets_the_same_schedule_with_other_tokens():
    m = mix("rag")
    traces = [arrivals.generate(m, seed, 8.0, 60.0, 64000)
              for seed in (1, 2, BIG_SEED)]
    schedules = [[(round(a.at_s, 9), len(a.prompt), a.max_new) for a in t]
                 for t in traces]
    assert schedules[0] == schedules[1] == schedules[2]
    assert traces[0][0].prompt != traces[1][0].prompt


def test_every_block_holds_the_same_work():
    m = mix("chat")
    block = m["block"]
    trace = arrivals.generate(m, 5, 4.0, 3 * block / 4.0 * 0.999, 92544)
    blocks = [trace[i:i + block] for i in (0, block)]
    assert (collections.Counter(len(a.prompt) for a in blocks[0])
            == collections.Counter(len(a.prompt) for a in blocks[1]))
    assert (sorted(a.max_new for a in blocks[0])
            == sorted(a.max_new for a in blocks[1]))


def test_mean_rate_and_length_median():
    m = mix("chat")
    trace = arrivals.generate(m, 3, 5.0, 200.0, 92544)
    rate = len(trace) / trace[-1].at_s
    assert abs(rate - 5.0) / 5.0 < 0.05
    lens = sorted(len(a.prompt) for a in trace)
    assert lens[len(lens) // 2] == 512
    budgets = sorted(a.max_new for a in trace)
    assert 150 <= budgets[len(budgets) // 2] <= 210


def test_gamma_gaps_keep_the_mean_rate():
    m = dict(mix("chat"), process="gamma", cv=3.0)
    gaps = arrivals.block_gaps(m, 5.0, m["block"])
    assert abs(sum(gaps) / len(gaps) - 0.2) < 1e-9
    assert max(gaps) > 5 * min(gaps)
