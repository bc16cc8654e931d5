"""One tiny cell on a mesh of virtual CPU devices, through the benchmark's
own run (``runner.run``, the look for a chip skipped); prints the result
line.  ``test_chipbench_mesh.py`` starts it in a process of its own, as
the device count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmarks/chip/tests/mesh_run.py <train|serve> <d0>x<d1> \
        <seed> [policy]

``train`` is the training mix at batch 4 x seq 64 under ``policy`` (the
mix's own where not given), ``serve`` a short open-loop chat mix on four
slots with the gather decode path.
"""
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import runner, spec  # noqa: E402

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)
BENCH = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                        {"name": "train_tokens_per_s", "unit": "tokens/s",
                         "workloads": ["train"]},
                        {"name": "serve_tokens_per_s", "unit": "tokens/s",
                         "workloads": ["serve"]}],
         "per_layer": []}
# sound runs of the tiny serving mix read 0 to 2.3e-3, its fp8 control
# 0.075 to 0.165 (test_chipbench_correct.py)
SERVE_LIMITS = {"served_logit_gap": 0.02}


def main(kind: str, shape: str, seed: int, policy: str = "") -> None:
    dims = [int(n) for n in shape.split("x")]
    mesh = {"shape": dims, "axes": ["data", "model"][:len(dims)]}
    cfg = dict(spec.config("smollm-135m"), **TINY)
    if kind == "train":
        mix = dict(spec.traffic("train.host"), batch=4, seq=64, mesh=mesh)
        mix["policy"] = policy or mix["policy"]
        limits = spec.limits("smollm-135m.train.host")
    else:
        mix = spec.traffic("serve.chat")
        mix = dict(mix, mesh=mesh,
                   engine=dict(mix["engine"], slots=4, max_len=128,
                               decode_kernel=False),
                   arrivals={"process": "poisson", "rate_per_s": 4.0},
                   prompt={"dist": "lognormal", "mean": 24, "sigma": 0.8,
                           "buckets": [16, 32]},
                   output={"dist": "lognormal", "mean": 8, "sigma": 0.5,
                           "max": 16})
        limits = SERVE_LIMITS
    cell = {"name": kind, "chips": math.prod(dims), "config_spec": cfg,
            "traffic_spec": mix}
    out = runner.run(kind, seed, 2.0, False, require_tpu=False, cell=cell,
                     limits=limits, bench=BENCH)
    print(json.dumps(out, default=float))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), *sys.argv[4:])
