"""The generator gives every seed the same work in another order."""

import json
import os

import numpy as np

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 2 ** 31 + 12345


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_requests():
    a = traffic.request_set(mix("prefill-local"), BIG, 151936, stream=1)
    b = traffic.request_set(mix("prefill-local"), BIG, 151936, stream=1)
    assert len(a) == len(b) == mix("prefill-local")["set_size"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new


def test_seeds_share_the_lengths():
    m = mix("prefill-local")
    a = traffic.request_set(m, 1, 1000, stream=1)
    b = traffic.request_set(m, 2, 1000, stream=1)
    la = sorted(len(s.prompt) for s in a)
    assert la == sorted(len(s.prompt) for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert min(la) >= 256 and max(la) <= 1792
    assert abs(float(np.median(la)) - 768) < 20


def test_runs_and_iid_tokens():
    rng = traffic.rng_for(3, 0)
    t = traffic.make_tokens({"kind": "runs", "min_run": 4, "max_run": 32},
                            2000, 151936, rng)
    edges = np.flatnonzero(np.diff(t)) + 1
    runs = np.diff(np.concatenate([[0], edges, [len(t)]]))
    assert runs[:-1].min() >= 4 and runs.max() <= 32
    iid = traffic.make_tokens({"kind": "iid"}, 2000, 151936, rng)
    assert len(np.unique(iid)) > 1900
    assert iid.dtype == np.int32 and iid.min() >= 0 and iid.max() < 151936


def test_open_loop_covers_the_window():
    m = mix("chat")
    rate = m["arrival"]["rate_per_s"]
    a = traffic.request_set(m, 5, 151936, stream=1, seconds=50)
    b = traffic.request_set(m, 6, 151936, stream=1, seconds=50)
    assert len(a) == len(b) == round(rate * 50)
    dues = [s.due for s in a]
    assert dues[0] == 0.0 and all(x <= y for x, y in zip(dues, dues[1:]))
    assert 40 < dues[-1] < 50
    assert sorted(np.diff(dues).round(9)) != [] and \
        sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
