"""Tiny stand-ins of the benchmark's configurations, for CPU tests.

Same registries, files and traffic as the real cells, at widths a CPU runs
in seconds.  ``cell(name)`` gives a cell object for ``harness.run(cell=)``.
"""
from __future__ import annotations

import copy

from bench import harness

TINY = {
    "smollm-360m": {
        "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                    "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                    "vocab_size": 257, "norm_eps": 1e-5},
        "sizes": {"num_hidden_layers": 2, "hidden_size": 64,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16, "intermediate_size": 128,
                  "vocab_size": 257},
    },
    "mamba2-1.3b": {
        "program": {"num_layers": 2, "d_model": 64, "d_inner": 128,
                    "ssm_state": 16, "ssm_head_dim": 32, "ssm_chunk": 8,
                    "vocab_size": 257, "norm_eps": 1e-5},
        "sizes": {"num_hidden_layers": 2, "hidden_size": 64, "d_inner": 128,
                  "state_size": 16, "head_dim": 32, "chunk_size": 8,
                  "vocab_size": 257},
    },
}
TRAIN = {"batch": 2, "seq_len": 32, "reference_rows": 1}
# limits at these sizes, set by the rule the real cells' limits follow
# (PERF.md): between the program's and the fp8 control's readings on the
# CPU over seeds 5, 99, 123456789012 and 2718281828459045 -- train
# grad_norm_gap 0.00078-0.00084 / 0.0112-0.0168; score_gap SmolLM
# 0.00050-0.00079 / 0.0046-0.0096, Mamba2 0.00046-0.00077 / 0.0030-0.0058;
# update_norm_gap 0.00093-0.00166 / 0.0042-0.0060 is under 3x, so there
# the gradient is the control's number and the update is not held
LIMITS = {"train.smollm-360m": {"grad_norm_gap": 0.004,
                                "update_norm_gap": 0.1},
          "fanout.smollm-360m": {"score_gap": 0.002},
          "fanout.mamba2-1.3b": {"score_gap": 0.0015}}
# tiny parameters of each traffic mix, by the mix's name
MIXES = {"fanout": {"cycle": [32, 8, 16, 8, 8, 32, 8, 16, 8, 16],
                    "warm_chains_per_length": 1, "checked_chains": 4}}


def config(name: str) -> dict:
    conf = copy.deepcopy(harness.load_json(
        harness.ROOT / "bench" / "configs" / f"{name}.json"))
    conf["program"] = dict(conf.get("program", {}), **TINY[name]["program"])
    conf["sizes"].update(TINY[name]["sizes"])
    conf["train"] = dict(TRAIN)
    return conf


def cell(name: str):
    real = harness.load_cell(name)
    real.config = config(real.workload["config"])
    real.workload = dict(real.workload,
                         params=dict(real.workload.get("params", {}),
                                     **MIXES.get(real.workload["traffic"],
                                                 {})),
                         limits=LIMITS.get(name, real.workload["limits"]))
    return real
