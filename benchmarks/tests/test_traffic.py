"""The traffic generators: one seed, one traffic; every seed, the same
work in another order."""
import numpy as np

from benchmarks.traffic import host_batches, lognormal_chat

CHAT = {"rate_per_s": 10.0, "arrivals": "poisson",
        "prompt": {"median": 512, "sigma": 0.8, "min": 32, "max": 2048},
        "output": {"median": 128, "sigma": 0.6, "min": 16, "max": 512},
        "tenants": ["a", "b"]}


def test_chat_is_the_same_for_the_same_seed():
    one = lognormal_chat.generate(CHAT, 2**31 + 5, 30, 32768)
    two = lognormal_chat.generate(CHAT, 2**31 + 5, 30, 32768)
    assert [r["due_s"] for r in one] == [r["due_s"] for r in two]
    assert all((a["prompt"] == b["prompt"]).all()
               for a, b in zip(one, two))


def test_chat_gives_every_seed_the_same_requests_with_other_tokens():
    one = lognormal_chat.generate(CHAT, 1, 30, 32768)
    two = lognormal_chat.generate(CHAT, 2, 30, 32768)
    assert len(one) == len(two) == 300
    for key in ("max_new", "tenant"):
        assert [r[key] for r in one] == [r[key] for r in two]
    assert [len(r["prompt"]) for r in one] == [len(r["prompt"])
                                               for r in two]
    assert not (one[0]["prompt"] == two[0]["prompt"]).all()


def test_poisson_arrivals_come_from_the_seed_and_bunch_by_chance():
    gaps = []
    for seed in (1, 2, 2**31 + 9):
        due = [r["due_s"] for r in lognormal_chat.generate(
            CHAT, seed, 30, 32768)]
        gaps.append(np.diff(due))
    assert not np.allclose(gaps[0], gaps[1])
    # exponential gaps: the deviation is about the mean (0.1 s), and
    # some stretch of a second holds twice its share of requests
    for g in gaps:
        assert 0.8 < g.std() / g.mean() < 1.25
        starts = np.cumsum(g)
        assert max(np.sum((starts >= t) & (starts < t + 1.0))
                   for t in np.arange(0, 29, 0.25)) >= 17


def test_quantile_arrivals_are_the_same_for_every_seed_and_even():
    even = dict(CHAT, arrivals="quantiles")
    one = [r["due_s"] for r in lognormal_chat.generate(even, 1, 30, 32768)]
    two = [r["due_s"] for r in lognormal_chat.generate(even, 2, 30, 32768)]
    assert one == two
    per_s = np.histogram(one, bins=30, range=(0, 30))[0]
    assert 6 <= per_s.min() and per_s.max() <= 14


def test_chat_spreads_the_work_evenly_over_the_window():
    for seed in (1, 2, 2**31 + 9):
        mix = lognormal_chat.generate(CHAT, seed, 30, 32768)
        thirds = [mix[i * 100:(i + 1) * 100] for i in range(3)]
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
            sums = [sum(map(key, part)) for part in thirds]
            assert max(sums) < 1.06 * min(sums)


def test_chat_keeps_to_its_limits_and_its_window():
    mix = lognormal_chat.generate(CHAT, 3, 30, 32768)
    lens = [len(r["prompt"]) for r in mix]
    outs = [r["max_new"] for r in mix]
    assert min(lens) >= 32 and max(lens) <= 2048
    assert min(outs) >= 16 and max(outs) <= 512
    assert 400 < np.median(lens) < 640 and 100 < np.median(outs) < 160
    due = [r["due_s"] for r in mix]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30
    assert {r["tenant"] for r in mix} == {"a", "b"}
    assert all(r["prompt"].dtype == np.int32 and r["prompt"].max() < 32768
               for r in mix)


def test_host_batches_cycle_a_seeded_pool_of_distinct_rows():
    params = {"batch": 4, "pool": 3, "batches_per_call": 2}
    cfg = {"image_size": 8, "num_channels": 3, "num_classes": 5}
    pool = host_batches.make_pool(params, cfg, 2**31 + 9)
    again = host_batches.make_pool(params, cfg, 2**31 + 9)
    assert all((a[0] == b[0]).all() and (a[1] == b[1]).all()
               for a, b in zip(pool, again))
    rows = np.concatenate([x.reshape(len(x), -1) for x, _ in pool])
    assert len(np.unique(rows, axis=0)) == 12
    assert pool[0][0].dtype == np.float32 and pool[0][1].shape == (4, 5)
    import contextlib
    first = list(host_batches.CycledBatches(
        pool, 2, 0, lambda name: contextlib.nullcontext()))
    nxt = list(host_batches.CycledBatches(
        pool, 2, 2, lambda name: contextlib.nullcontext()))
    assert first[0] is pool[0] and first[1] is pool[1]
    assert nxt[0] is pool[2] and nxt[1] is pool[0]
