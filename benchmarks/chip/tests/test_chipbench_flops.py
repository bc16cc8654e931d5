"""FLOP and byte counts from shapes, and the roofline arithmetic of the
per-layer readers, against hand-computed figures."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import flops, runner, spec  # noqa: E402
from harness import trace as tr  # noqa: E402

SMOLLM = spec.config("smollm-135m")
DANUBE = {"hidden_size": 2560, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 80,
          "intermediate_size": 6912, "num_hidden_layers": 24,
          "vocab_size": 32000, "sliding_window": 4096}


def test_smollm_forward_flops_per_token():
    assert flops.layer_matmul_params(SMOLLM) == 3_538_944
    assert flops.forward_flops(SMOLLM, 1, 1, 1) == \
        2 * 30 * 3_538_944 + 4 * 30 * 576 + 2 * 49152 * 576


def test_train_step_counts_three_forwards_without_recompute():
    pairs = 2048 * 2049 // 2
    one = flops.forward_flops(SMOLLM, 2048, pairs, 2048)
    assert flops.train_step_flops(SMOLLM, 16, 2048) == 3 * 16 * one
    # about 1.02 GFLOP a token at seq 2,048
    assert flops.train_step_flops(SMOLLM, 16, 2048) / (16 * 2048) == \
        pytest.approx(1.019e9, rel=2e-3)


def test_window_caps_attention_pairs():
    assert flops.visible_pairs(10, 0) == 55
    assert flops.visible_pairs(10, 4) == 10 + 6 * 4
    assert flops.decode_flops(DANUBE, 9000) == \
        flops.forward_flops(DANUBE, 1, 4096, 1)


def test_paged_decode_need_full_attention():
    need = flops.paged_decode_need(SMOLLM, 100, 2, 16)
    assert need["bytes"] == 2 * (2 * 7 * 16 * 3 * 64 * 2 + 2 * 9 * 64 * 2)
    assert need["flops"] == 2 * 4 * 9 * 64 * 101


def test_paged_decode_need_skips_pages_below_the_window():
    need = flops.paged_decode_need(DANUBE, 5000, 1, 16)
    assert need["bytes"] == 2 * 257 * 16 * 8 * 80 * 2 + 2 * 32 * 80 * 2
    assert need["flops"] == 4 * 32 * 80 * 4096


class _Traced:
    def __init__(self, window_s, trace, lo, hi):
        self.trace, self.lo, self.hi = trace, lo, hi
        self._w = window_s

    @property
    def window_s(self):
        return self._w


def _measurement(counters, events):
    trace = {"devices": {"0": {"ops": tr._nest(events), "xfer": []}},
             "host": []}
    return runner.Measurement(counters, _Traced(1.0, trace, 0.0, 1e9),
                              runner.peaks()["TPU v5 lite"])


def test_roofline_share_is_least_time_over_kernel_time():
    need = [flops.paged_decode_need(SMOLLM, 1000, 4, 16)]
    c = {"kind": "serve", "decode_kernel": True, "paged_need": need,
         "layers": 30}
    least = 30 * need[0]["bytes"] / 819e9        # bandwidth bound
    assert least > 30 * need[0]["flops"] / 197e12
    events = [("fusion.1", 0.0, 1e6), ("paged_kernel.2", 2e6, 4e6)]
    share = spec.metric_reader("paged_decode_roofline")(
        _measurement(c, events))
    assert share == pytest.approx(100 * least / 4e-3)


def test_roofline_reader_is_silent_without_its_kernel():
    c = {"kind": "serve", "decode_kernel": True, "layers": 30,
         "paged_need": [flops.paged_decode_need(SMOLLM, 10, 1, 16)]}
    read = spec.metric_reader("paged_decode_roofline")
    assert read(_measurement(c, [("fusion.1", 0.0, 1e6)])) is None
    assert read(_measurement(dict(c, paged_need=[]), [])) is None


def test_mfu_train_reader():
    c = {"kind": "train", "chips": 1, "steps": 2, "model_flops": 197e12 * 0.3}
    assert spec.metric_reader("mfu.train")(_measurement(c, [])) == \
        pytest.approx(30.0)
