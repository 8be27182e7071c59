"""``bench/roofline.py`` and the splash kernel's share of its roofline:
hand-made traces, a hand count at SmolLM's sizes, and one train step
traced on the chip."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, roofline, trace as tr

MS = 1e6            # nanoseconds in a millisecond
DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
METRIC = "splash_roofline.train"
CELL = "train.smollm-360m"


def _trace(ops, window=(0, 100 * MS)):
    return tr.Trace([{"ops": list(ops), "modules": []}],
                    [("window", window[0], window[1] - window[0])])


def _read(trace):
    ctx = SimpleNamespace(cell=harness.load_cell(CELL), trace=trace,
                          peaks=tr.peaks_for("TPU v5 lite"))
    return harness.load_metric(METRIC).read(ctx)


def test_share_is_roofline_time_over_device_time():
    """1 GFLOP at 1 TFLOP/s is 1 ms; 10 MB at 1 GB/s is 10 ms: the larger
    of the two bounds each call."""
    work = {"k_a": (1e9, 1e3), "k_b": (1e6, 1e7)}
    t = _trace([("k_a.1", 10 * MS, 4 * MS), ("k_b.2", 20 * MS, 20 * MS),
                ("fusion.3", 0, 50 * MS)])
    share = roofline.share(t, "k_", lambda n: work[n.split(".")[0]], PEAKS)
    assert share == pytest.approx(100 * (1 + 10) / (4 + 20))


def test_a_call_clipped_by_the_window_does_not_count():
    t = _trace([("k_a.1", -2 * MS, 4 * MS), ("k_a.1", 10 * MS, 4 * MS),
                ("k_a.1", 98 * MS, 4 * MS)])
    share = roofline.share(t, "k_", lambda n: (1e9, 0.0), PEAKS)
    assert share == pytest.approx(25.0)


def test_no_call_or_an_uncountable_call_reads_none():
    t = _trace([("fusion.1", 0, 10 * MS)])
    assert roofline.share(t, "k_", lambda n: (1.0, 1.0), PEAKS) is None
    t = _trace([("k_a.1", 0, 10 * MS), ("k_z.2", 20 * MS, 10 * MS)])
    count = {"k_a.1": (1e9, 0.0)}.get
    assert roofline.share(t, "k_", count, PEAKS) is None


def test_splash_count_by_hand_at_smollm_sizes():
    """B 8, S 2048, 15 query and 5 key-value heads of 64: W causal pair
    products of one head width."""
    count = harness.load_metric(METRIC).counter(
        harness.load_cell(CELL).config)
    W = 8 * 15 * 64 * 2048 * 2049 / 2
    assert W == 16113991680.0
    q, kv = 8 * 2048 * 15 * 64 * 2, 8 * 2048 * 5 * 64 * 2
    assert count("splash_mha_fwd_residuals.15") == (4 * W, 2 * q + 2 * kv)
    assert count("splash_mha_fwd_no_residuals.3") == (4 * W, 2 * q + 2 * kv)
    assert count("splash_mha_dq_no_residuals.9") == (6 * W, 3 * q + 2 * kv)
    assert count("splash_mha_dkv_no_residuals.9") == (8 * W, 2 * q + 4 * kv)
    assert count("splash_mha_bwd.1") is None
    # compute-bound: 4W FLOPs at 197 TFLOP/s take 0.33 ms, its bytes at
    # 819 GB/s 0.10 ms
    assert 4 * W / 197e12 > 3 * (2 * q + 2 * kv) / 819e9


def test_splash_roofline_reads_the_kernel_calls_only():
    W = 8 * 15 * 64 * 2048 * 2049 / 2
    fwd_ms = 4 * W / 197e12 * 1e3                 # the call at its roofline
    t = _trace([("splash_mha_fwd_residuals.1", 0, 4 * fwd_ms * MS),
                ("fusion.2", 50 * MS, 10 * MS)])
    assert _read(t) == pytest.approx(25.0)
    assert _read(_trace([("fusion.2", 50 * MS, 10 * MS)])) is None
    assert _read(None) is None


def test_recorded_train_step_on_the_chip():
    """One whole `train.smollm-360m` step (640 ms) traced on a v5e: four
    kernel calls a layer (forward, its rematerialisation, dq, dkv), each
    over the whole batch, and the share as read when it was recorded,
    within the range predicted for it."""
    with gzip.open(DATA / "trace_splash_train_smollm.json.gz", "rt") as fh:
        obj = json.load(fh)
    t = tr.from_json(obj)
    layers = harness.load_cell(CELL).config["sizes"]["num_hidden_layers"]
    calls = [n for n, s, d in t.devices[0]["ops"]
             if n.startswith("splash_mha_") and t.t0 <= s
             and s + d <= t.t1]
    kinds = sorted(harness.load_metric(METRIC).KIND.match(n).group(1)
                   for n in calls)
    assert len(calls) == 4 * layers
    assert kinds == ["dkv"] * layers + ["dq"] * layers + ["fwd"] * 2 * layers
    assert [n for n, _, _ in t.devices[0]["modules"]
            if n.startswith("jit_train_step")] != []
    assert 18 < _read(t) < 30                     # read 22.55
