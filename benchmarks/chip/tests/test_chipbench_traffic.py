"""Each traffic mix is a pure function of its seed, and every seed offers
the same work in another order."""
import glob
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import spec, traffic  # noqa: E402

MIXES = sorted(os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(spec.HERE, "traffic", "*.json")))
BIG = 2 ** 31 + 12345


def _serve(mix, seed):
    return traffic.serve_requests(mix, seed, 30.0, 49152)


@pytest.mark.parametrize("name", MIXES)
def test_pure_function_of_seed(name):
    mix = spec.traffic(name)
    if mix["kind"] == "train":
        a = traffic.train_batch(BIG, 3, mix["batch"], 64, 49152)
        b = traffic.train_batch(BIG, 3, mix["batch"], 64, 49152)
        c = traffic.train_batch(BIG + 1, 3, mix["batch"], 64, 49152)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
        assert not np.array_equal(a["tokens"], c["tokens"])
        rows = {r.tobytes() for r in a["tokens"]}
        assert len(rows) == mix["batch"]          # every row differs
        np.testing.assert_array_equal(a["labels"][:, :-1],
                                      a["tokens"][:, 1:])
        return
    a, b, c = _serve(mix, BIG), _serve(mix, BIG), _serve(mix, 7)
    assert [(r.due_s, r.max_new_tokens, r.prompt.tobytes()) for r in a] == \
        [(r.due_s, r.max_new_tokens, r.prompt.tobytes()) for r in b]
    # another seed: the same multiset of sizes, another order
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c] \
        or len(a) == 1


@pytest.mark.parametrize("name", MIXES)
def test_sizes_follow_the_mix(name):
    mix = spec.traffic(name)
    if mix["kind"] == "train":
        return
    reqs = _serve(mix, 11)
    p, o = mix["prompt"], mix["output"]
    allowed = set(p.get("buckets") or p.get("values") or [p.get("value")])
    assert {len(r.prompt) for r in reqs} <= allowed
    if "max" in o:
        assert max(r.max_new_tokens for r in reqs) <= o["max"]
    if mix["arrivals"]["process"] == "poisson":
        rate = mix["arrivals"]["rate_per_s"]
        assert len(reqs) == round(rate * 30.0)
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and due[0] == 0.0
        assert abs(due[-1] - 30.0) < 30.0 * 0.2


def test_lognormal_quantiles_have_the_stated_mean():
    x = traffic.lengths({"dist": "lognormal", "mean": 256, "sigma": 0.8},
                        4000)
    assert abs(x.mean() / 256 - 1) < 0.02


def test_key_seed_takes_any_size_of_seed():
    ks = {traffic.key_seed(s) for s in (0, 1, 2 ** 31 - 1, 2 ** 31,
                                        2 ** 33 + 5)}
    assert len(ks) == 5 and all(0 <= k < 2 ** 31 for k in ks)
