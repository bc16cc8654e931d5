"""The trace reduction, on a small recorded trace and by hand.

``data/train_host_slice.json`` is 3 ms of a traced window of
``smollm-135m.train.host`` on a TPU v5e, around one fetch of a stashed
layer input from host memory, as ``harness.trace.load`` reduced it.  Each
share is checked against a brute-force count on a 100 ns grid."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "train_host_slice.json")
GRID = 100.0      # ns


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        t = json.load(f)
    return t, t["window"][0], t["window"][1]


def _mask(intervals, lo, hi):
    ts = np.arange(lo, hi, GRID) + GRID / 2
    m = np.zeros(ts.shape, bool)
    for s, d in intervals:
        m |= (ts >= s) & (ts < s + d)
    return m


def test_busy_is_the_union_of_nested_operations(recorded):
    t, lo, hi = recorded
    ops = t["devices"]["0"]["ops"]
    brute = _mask([(o[1], o[2]) for o in ops], lo, hi).sum() * GRID / 1e9
    busy = tr.busy_s(t, lo, hi)
    assert busy == pytest.approx(brute, rel=1e-3)
    # operations nest (a while loop spans its body), so their summed
    # durations overshoot the window and the union does not
    assert sum(o[2] for o in ops) / 1e9 > hi / 1e9 >= busy


def test_exposed_transfer_share(recorded):
    t, lo, hi = recorded
    d = t["devices"]["0"]
    xfer = _mask([(x[1], x[2]) for x in d["xfer"] if x[3] == "host"], lo, hi)
    comp = _mask([(o[1], o[2]) for o in d["ops"]
                  if not o[4] and not tr.is_wait(o[0])], lo, hi)
    brute = (xfer & ~comp).sum() * GRID / 1e9
    got = tr.exposed_s(t, lo, hi, ("host",))
    assert got == pytest.approx(brute, rel=1e-2, abs=2e-7)
    # the slice holds a fetch that the device waits on
    assert got > 0
    waits = [o for o in d["ops"] if o[0].startswith("dynamic-slice-done")]
    assert waits and got >= 0.5 * sum(o[2] for o in waits) / 1e9


def test_op_time_by_name_counts_self_time(recorded):
    t, lo, hi = recorded
    ops = tr.op_seconds(t, lo, hi)
    top = tr.top_ops(t, lo, hi, top=5)
    assert len(top) == 5 and top[0][1] == max(ops.values())
    started = [o for o in t["devices"]["0"]["ops"] if lo <= o[1] < hi]
    assert sum(ops.values()) == pytest.approx(
        sum(o[3] for o in started) / 1e9)


def test_idle_gaps_are_charged_to_host_spans(recorded):
    t, lo, hi = recorded
    gaps = tr.idle_gaps(t, lo, hi)
    idle = hi / 1e9 - tr.busy_s(t, lo, hi)
    assert sum(g[1] for g in gaps) == pytest.approx(idle, abs=1e-9)


def test_interval_helpers():
    assert tr.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.length(tr.union([(0, 1), (0.5, 2)])) == 2


def test_nesting_and_names():
    rows = tr._nest([("while.1", 0.0, 10.0), ("fusion.2", 1.0, 3.0),
                     ("copy-done.3", 5.0, 2.0), ("fusion.4", 12.0, 1.0)])
    assert rows[0] == ["while.1", 0.0, 10.0, 5.0, 1]
    assert [r[4] for r in rows[1:]] == [0, 0, 0]
    assert tr.is_wait("copy-done.3") and tr.is_wait("dynamic-slice-done")
    assert not tr.is_wait("fusion.2")
    hlo = ("%dynamic-update-slice-start = ((bf16[30,16,2048,576]{2,3,1,0:"
           "T(8,128)(2,1)S(5)}, ...) async-start(...)")
    assert tr.short_name(hlo) == "dynamic-update-slice-start"
    assert tr._transfer_tag(hlo) == "host"
    assert tr._transfer_tag("%all-to-all.3 = bf16[4] all-to-all(x)") == \
        "collective"
    assert tr._transfer_tag("%copy-start.1 = (f32[4]{0:S(1)}) c") == \
        "device"


def test_host_spans_are_read_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    names = {h[0] for h in t["host"]}
    assert {"bench.window", "bench.step"} <= names
    lo, hi = tr.window(t)
    assert hi > lo
