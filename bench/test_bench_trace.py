"""The trace reduction and the per-layer metrics' arithmetic, without a chip.

One trace is built here with the planes' event names a v5e trace uses; a
second, recorded on a v5e and trimmed to a few rounds, is kept under
``testdata/``.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce as tm  # noqa: E402

MS = 1_000_000  # ns


def _built_trace():
    """Two chips, a 10 ms window, two rounds. Chip 0: a fusion 0-2 ms
    overlapping a second 1-3 ms, the wire kernel 3-4 ms, a copy 5-7 ms
    with 1 ms of a fusion under it; chip 1: the kernel 2-4 ms and a 6-7 ms
    copy. An op before the window is cut off."""
    return {
        "devices": {
            "0": [["fusion.1", 0, 2 * MS], ["fusion.2", 1 * MS, 3 * MS],
                  ["_wire_stage.1", 3 * MS, 4 * MS],
                  ["copy.2", 5 * MS, 7 * MS],
                  ["fusion.3", 6 * MS, 7 * MS], ["copy.1", -5 * MS, -1 * MS]],
            "1": [["_wire_stage.1", 2 * MS, 4 * MS],
                  ["copy.2", 6 * MS, 7 * MS]],
        },
        "host": [["bench_window", 0, 10 * MS], ["bench_batch", 4 * MS, 5 * MS],
                 ["bench_fetch", 7 * MS, 10 * MS]],
    }


def test_reduce_busy_kernel_and_exposed_collective():
    red = tm.reduce(_built_trace(), ["_wire_stage.1"])
    assert red["window_s"] == pytest.approx(0.010)
    c0, c1 = red["chips"]
    assert c0["busy_s"] == pytest.approx(0.006)  # 0-4 and 5-7 ms
    assert c1["busy_s"] == pytest.approx(0.003)
    assert c0["kernel_s"] == pytest.approx(0.001)
    assert c1["kernel_s"] == pytest.approx(0.002)
    # chip 0's gaps: 4-5 ms under bench_batch, 7-10 ms under bench_fetch
    assert red["idle_gaps"] == [["bench_fetch", pytest.approx(0.003)],
                                ["bench_batch", pytest.approx(0.001)]]
    top = {n for n, _ in red["device_ops"][:2]}
    assert top == {"_wire_stage.1", "copy.2"}


def test_op_names_are_hlo_instruction_names():
    # a v5e trace names each op by its whole HLO instruction text
    raw = ("%_fused_round.1 = (f32[2,512]{1,0:T(2,128)}) custom-call("
           "f32[2,512]{1,0} %get-tuple-element.3), custom_call_target="
           '"tpu_custom_call"')
    assert tm.op_name(raw) == "_fused_round.1"
    assert tm.op_name("fusion.3") == "fusion.3"


def test_kernel_names_match_exactly():
    red = tm.reduce(_built_trace(), ["_wire_stage"])
    assert all(c["kernel_events"] == 0 for c in red["chips"])


def test_window_without_device_ops_reads_nothing():
    tr = _built_trace()
    tr["host"][0] = ["bench_window", 20 * MS, 30 * MS]
    red = tm.reduce(tr, ["_wire_stage.1"])
    assert "chips" not in red
    ctx = {"trace": red, "rounds": 2, "chips": 2, "flops_per_round": 1.0,
           "wire_bytes_per_chip": 1.0,
           "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    for name in ("device_idle_share", "wire_kernel_ms", "wire_roofline",
                 "mfu", "local_step_ms", "wire_stage_ms", "round_metrics_ms",
                 "transport_ms"):
        assert _reader(name).read(ctx) is None


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_metric_readers():
    red = tm.reduce(_built_trace(), ["_wire_stage.1"])
    ctx = {"trace": red, "rounds": 2, "chips": 2, "flops_per_round": 2.0e9,
           "wire_bytes_per_chip": 1.0e6,
           "peaks": {"flops_per_s": 1.0e12, "hbm_bytes_per_s": 1.0e9}}
    assert _reader("device_idle_share").read(ctx) == pytest.approx(55.0)
    assert _reader("wire_kernel_ms").read(ctx) == pytest.approx(0.75)
    # 1 ms at 1e9 B/s for 1e6 B, over 0.75 ms of kernel a round
    assert _reader("wire_roofline").read(ctx) == pytest.approx(100 / 0.75)
    # 2 rounds x 2e9 FLOP over 10 ms x 2 chips x 1e12 FLOP/s
    assert _reader("mfu").read(ctx) == pytest.approx(20.0)


def test_counts_from_shapes():
    cell = harness.resolve("smollm360m-ring2-dsgd-q2")
    c, t = cell.config, cell.traffic
    d, f, L, v, s = 960, 2560, 32, 49152, 128
    per_token = L * (2 * d * 960 + 2 * d * 320 + 3 * d * f) + d * v
    attn = L * 2 * 960 * s * (s + 1) // 2
    assert cell.model.train_flops(c, t) == 6 * (s * per_token + attn)
    # 2 sites x 2 steps x 2 sequences: about 2.2e12 FLOP a round
    assert cell.model.train_flops(c, t) * 8 == pytest.approx(2.247e12, rel=1e-3)
    total = 361_821_184
    # DSGD megakernel: read bf16 params, f32 grad, recon, residual; write
    # bf16 params, recon, residual
    assert cell.engine.wire_bytes(t, total, 2) == 2 * total * (2 + 12 + 2 + 8)
    ehr = harness.resolve("ehr-h20-dsgt-q10")
    assert ehr.model.train_flops(ehr.config, ehr.traffic) == 6 * (42 * 32 + 32 * 2)
    # DSGT megakernel, f32 state: 8 buffers read, 6 written, 4 B each
    assert ehr.engine.wire_bytes(ehr.traffic, 1536, 20) == 20 * 1536 * 14 * 4
    ring4 = harness.resolve("smollm360m-ring4-4chip-dsgd-q2")
    # the sharded wire stage's compiled call for a v5e: reads f32 x, g,
    # recon, residual; writes f32 h, recon, residual, int8 values and one
    # f32 scale a 512-column chunk
    assert ring4.engine.wire_bytes(ring4.traffic, total, 1) == (
        total * (4 * 4 + 3 * 4 + 1) + total // 512 * 4)


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_recorded_chip_trace():
    rec = json.loads((BENCH / "testdata" / "trace-ehr-h20-dsgt-q10.json")
                     .read_text())
    red = tm.reduce(rec["trace"], rec["kernels"])
    (chip,) = red["chips"]
    assert chip["kernel_events"] == rec["rounds"]
    assert 0 < chip["busy_s"] < red["window_s"]
    assert 0 < chip["kernel_s"] < chip["busy_s"]
