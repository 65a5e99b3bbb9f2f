"""The traffic generator: the same work for every seed, in another
order, and requests that only the seed decides."""
import json
import os

import numpy as np
import pytest

from harness import traffic
from perfbench_fixtures import BENCH, tiny_spec


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("arrival_seed", [0, 7, 2 ** 31 + 5, 2 ** 70 + 1])
def test_poisson_arrivals_one_schedule_per_mix(arrival_seed):
    m = dict(mix("turbo-poisson"), arrival_seed=arrival_seed)
    due = traffic.arrivals(m, 51.0)
    ref = traffic.arrivals(mix("turbo-poisson"), 51.0)
    assert len(due) == int(m["rate_per_s"] * 51)
    assert np.all(np.diff(due) > 0) and due[-1] < 51.0
    # the same gaps (a Poisson process's quantiles) in the mix's order
    np.testing.assert_allclose(np.sort(np.diff(np.r_[0.0, due])),
                               np.sort(np.diff(np.r_[0.0, ref])))
    assert np.array_equal(due, traffic.arrivals(m, 51.0))
    gaps = np.diff(np.r_[0.0, due])
    assert np.mean(gaps) == pytest.approx(1 / m["rate_per_s"], rel=0.05)


def test_bursts_arrive_only_while_on():
    m = dict(mix("turbo-poisson"), rate_per_s=12.0,
             bursts={"on_s": 2.0, "off_s": 4.0})
    due = traffic.arrivals(m, 30.0)
    assert len(due) == 12 * 10                      # 5 periods x 2 s on
    assert np.all(due % 6.0 < 2.0)


def test_requests_follow_the_seed_and_the_mix():
    spec = tiny_spec("q3_k")
    m = mix("cfg20-offline")
    a = traffic.requests(m, spec, 11, 6)
    assert a == traffic.requests(m, spec, 11, 6)
    assert a != traffic.requests(m, spec, 12, 6)
    assert len({tuple(r["tokens"]) for r in a}) == 6
    for r in a:
        assert len(r["tokens"]) == len(r["neg_tokens"]) == 77
        assert max(r["tokens"]) < spec["text_encoder"]["vocab_size"]
        assert (r["sampler"], r["steps"], r["guidance"]) == ("euler", 20, 7.0)
        assert r["latent_hw"] == spec["latent_hw"] and traffic.uses_cfg(r)
        assert 0 <= r["seed"] < 2 ** 31


def test_classes_and_step_lists_give_every_seed_the_same_kinds():
    spec = tiny_spec("q8_0")
    m = dict(mix("turbo-poisson"), classes=[
        dict(mix("turbo-poisson")["request"], weight=3),
        dict(mix("cfg20-offline")["request"], steps=[4, 8, 25], weight=1)])
    kinds = [sorted(map(str, map(traffic.kind,
                                 traffic.requests(m, spec, s, 40))))
             for s in (1, 2)]
    assert kinds[0] == kinds[1]
    reqs = traffic.requests(m, spec, 1, 40)
    assert sum(r["sampler"] == "turbo" for r in reqs) == 30
    assert sorted(r["steps"] for r in reqs if r["sampler"] == "euler") == \
        [4] * 4 + [8] * 3 + [25] * 3
