"""The traffic generator: the same seed gives the same queries, the
streams differ, and every parameter lies in its mix's range."""

import datetime

from bench import spec, traffic

MIXES = ("power", "throughput4")


def test_same_seed_same_sequence():
    mix = spec.traffic("throughput4")
    for k in range(mix["streams"]):
        assert traffic.first(mix, 2**33 + 5, k, 60) == traffic.first(mix, 2**33 + 5, k, 60)


def test_streams_and_seeds_differ():
    mix = spec.traffic("throughput4")
    seqs = [traffic.first(mix, 12, k, 30) for k in range(mix["streams"])]
    assert len({str(s) for s in seqs}) == len(seqs)
    assert traffic.first(mix, 12, 0, 30) != traffic.first(mix, 13, 0, 30)


def test_every_seed_offers_the_same_passes_in_another_order():
    mix = spec.traffic("throughput4")
    n = len(mix["queries"])
    a, b = traffic.first(mix, 1, 2, 10 * n), traffic.first(mix, 2**40 + 3, 2, 10 * n)
    assert a != b
    for p in range(10):
        assert sorted(map(str, a[p * n:(p + 1) * n])) == sorted(map(str, b[p * n:(p + 1) * n]))


def test_each_pass_runs_every_query_once():
    for name in MIXES:
        mix = spec.traffic(name)
        n = len(mix["queries"])
        seq = traffic.first(mix, 99, 0, 5 * n)
        for p in range(5):
            assert sorted(q for q, _ in seq[p * n:(p + 1) * n]) == sorted(mix["queries"])


def test_parameters_in_their_ranges():
    mix = spec.traffic("power")
    day = lambda y, m: (datetime.date(y, m, 1) - datetime.date(1992, 1, 1)).days  # noqa: E731
    assert traffic.support({"jan1_of_year": [1993, 1997]}) == [366, 731, 1096, 1461, 1827]
    months = traffic.support({"first_of_month": ["1993-01", "1997-10"]})
    assert (months[0], months[-1], len(months)) == (day(1993, 1), day(1997, 10), 58)
    for q, params in traffic.first(mix, 2**31 + 7, 0, 600):
        for p, v in params.items():
            assert v in traffic.support(mix["params"][q][p]), (q, p, v)
        if q == "q1":
            assert 60 <= params["delta_days"] <= 120
