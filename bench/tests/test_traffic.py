"""The one traffic generator: seeded, the same work for every seed."""

import json

import numpy as np
import pytest

import gen
from conftest import BENCH


def _mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


BIG = 2**31 + 12345


@pytest.mark.parametrize("name", ["chat", "backlog-short"])
def test_same_seed_same_requests(name):
    a = gen.generate(_mix(name), BIG, 20, 50304)
    b = gen.generate(_mix(name), BIG, 20, 50304)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat", "backlog-short"])
def test_seeds_offer_the_same_work_in_another_order(name):
    mix = _mix(name)
    a = gen.generate(mix, 1, 20, 50304)
    b = gen.generate(mix, 2**40 + 7, 20, 50304)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                     for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])
    # the same work falls due inside the window, whatever the seed
    lo = gen.window_open(mix) or 0.0

    def window(reqs):
        inside = [r for r in reqs if lo <= r.due < lo + 20]
        return (sorted(len(r.prompt) for r in inside),
                sorted(r.max_new for r in inside))
    assert window(a) == window(b)


def test_stratified_order():
    rng = np.random.default_rng(3)
    n, k = 76, gen.GROUP
    order = gen.stratified_order(n, rng)
    assert sorted(order) == list(range(n))
    for g in range(0, n - k + 1, k):
        assert sorted(int(p) * k // n for p in order[g:g + k]) == list(
            range(k))


def test_chat_shape():
    mix = _mix("chat")
    reqs = gen.generate(mix, 5, 40, 50304)
    arr = mix["arrival"]
    assert len(reqs) == sum(round(arr["rate"] * s)
                            for s in (arr["ramp_s"], 40, arr["tail_s"]))
    window = [r for r in reqs
              if arr["ramp_s"] <= r.due < arr["ramp_s"] + 40]
    assert len(window) == round(arr["rate"] * 40)
    shared = [r for r in reqs if r.system >= 0]
    assert len(shared) == int(np.floor(0.6 * len(reqs)))
    assert len({r.system for r in shared}) == 8
    for r in shared:
        same = [s for s in shared if s.system == r.system]
        np.testing.assert_array_equal(r.prompt[:512], same[0].prompt[:512])
    users = [len(r.prompt) - (512 if r.system >= 0 else 0) for r in reqs]
    assert min(users) >= 32 and max(users) <= 1024
    assert 200 <= np.median(users) <= 320
    outs = [r.max_new for r in reqs]
    assert min(outs) >= 16 and max(outs) <= 512
    assert all(len(r.prompt) + r.max_new <= 2048 for r in reqs)
    # open loop: due times fill the ramp, the window and the tail
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert 0 < min(dues) and max(dues) < arr["ramp_s"] + 40 + arr["tail_s"]


def test_backlog_shape():
    mix = _mix("backlog-short")
    reqs = gen.generate(mix, 5, 40, 49152)
    assert all(r.due == 0.0 for r in reqs)
    assert all(r.system == -1 for r in reqs)
    assert len(reqs) == mix["arrival"]["requests"]
    assert all(64 <= len(r.prompt) <= 512 and 128 <= r.max_new <= 512
               for r in reqs)
