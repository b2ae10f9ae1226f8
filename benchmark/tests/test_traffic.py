import collections
import os

from benchmark.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    return traffic.load(traffic.find(HERE, "traffic", name))


def _epoch_of(stream, n):
    return [stream.next() for _ in range(n)]


def _lengths(items):
    return collections.Counter((i["prompt_len"], i["max_new"],
                                i["temperature"]) for i in items)


def test_two_seeds_offer_the_same_multiset_of_lengths():
    for name in ("decode_closed64", "mixed_open"):
        mix = _mix(name)
        n = mix["epoch"]
        a = traffic.RequestStream(mix, 50304, 1)
        b = traffic.RequestStream(mix, 50304, 2**31 + 12345)
        ea, eb = _epoch_of(a, n), _epoch_of(b, n)
        assert _lengths(ea) == _lengths(eb)
        # another order, other token ids
        assert [i["prompt_len"] for i in ea] != [i["prompt_len"] for i in eb]
        assert not (ea[0]["prompt"][:8] == eb[0]["prompt"][:8]).all() \
            or ea[0]["prompt_len"] != eb[0]["prompt_len"]
        # the second epoch holds the same work again
        assert _lengths(_epoch_of(a, n)) == _lengths(ea)


def test_lengths_keep_to_the_files_clips_and_median():
    mix = _mix("decode_closed64")
    items = traffic.epoch(mix)
    prompts = sorted(i["prompt_len"] for i in items)
    assert prompts[0] >= mix["prompt"]["min"]
    assert prompts[-1] <= mix["prompt"]["max"]
    mid = prompts[len(prompts) // 2]
    assert abs(mid - mix["prompt"]["median"]) < 0.1 * mix["prompt"]["median"]
    greedy = sum(1 for i in items if i["temperature"] == 0.0)
    assert greedy == len(items) // 2


def test_every_seed_gets_the_same_arrival_gaps_in_another_order():
    mix = _mix("mixed_open")
    n = mix["epoch"]
    a = traffic.RequestStream(mix, 50304, 3)
    b = traffic.RequestStream(mix, 50304, 4)
    da = [i["due"] for i in _epoch_of(a, n)]
    db = [i["due"] for i in _epoch_of(b, n)]
    # one epoch lasts exactly epoch / rate seconds whatever the seed
    assert abs(da[-1] - n / mix["rate_rps"]) < 1e-9
    assert abs(db[-1] - n / mix["rate_rps"]) < 1e-9
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip([0.0] + d, d))
    assert gaps(da) == gaps(db)
    assert da != db


def test_same_seed_gives_the_same_inputs():
    mix = _mix("mixed_open")
    a = _epoch_of(traffic.RequestStream(mix, 50304, 2**31 + 7), 5)
    b = _epoch_of(traffic.RequestStream(mix, 50304, 2**31 + 7), 5)
    for x, y in zip(a, b):
        assert (x["prompt"] == y["prompt"]).all() and x["seed"] == y["seed"]
        assert x["due"] == y["due"]
