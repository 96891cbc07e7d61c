from pathlib import Path

import pytest

import costs
import readers
import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ("%ternary_decode_gemm_fused.68 = bf16[2048,4096]{1,0:T(8,128)} "
          "custom-call(u8[2048,408]{1,0:T(8,128)(4,1)S(1)} %pad.117, "
          "bf16[408,5,4096]{2,1,0:T(8,128)(2,1)S(1)} %pad.118, "
          "f32[1,4096]{1,0:T(1,128)S(1)} %bitcast.357, f32[2048,1]{1,0} "
          "%copy.2), custom_call_target=\"tpu_custom_call\"")
LOOP = ("%while.2 = (s32[]{:T(128)}, bf16[16,256,2048]{1,0,2:T(8,128)(2,1)}) "
        "while((s32[]{:T(128)}, bf16[16,256,2048]{1,0,2:T(8,128)(2,1)}) %t), "
        "condition=%c, body=%b")
FUSION = ("%fusion.107 = (f32[16,8]{1,0}, f32[16,8,4160]{2,1,0:T(8,128)}) "
          "fusion(f32[16,8,4160]{2,1,0:T(8,128)} %x), kind=kOutput")


def _events():
    return {
        "host": [["bench.trace_window", 0, 1000], ["scheduler.tick", 50, 650],
                 ["engine.step", 100, 500], ["python", 0, 1000]],
        "devices": [[
            [LOOP, 150, 400],                   # encloses the two below
            [FUSION, 200, 200],
            [KERNEL, 400, 100],
            [FUSION, 1100, 50],                 # outside the window
        ]],
    }


def test_busy_window_and_idle_gaps_by_host_span():
    out = tr.reduce_events(_events())
    assert out["window_s"] == pytest.approx(1e-6)
    assert out["busy_s"] == pytest.approx(300e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [0, 200): no benchmark span open at 0; [500, 1000): engine.step
    assert gaps == pytest.approx({"no host span": 200e-9, "engine.step": 500e-9})
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({
        "fusion (f32[16,8], f32[16,8,4160])": 200e-9,
        "ternary_decode_gemm_fused bf16[2048,4096]": 100e-9})


def test_mpgemm_calls_carry_their_operand_shapes():
    out = tr.reduce_events(_events())
    assert out["mpgemm"] == [{"seconds": 100e-9, "shape": {
        "m": 2048, "kg": 408, "g": 5, "n": 4096, "act_bytes": 2, "out_bytes": 2}}]


def test_trace_without_window_or_device_ops_is_refused():
    ev = _events()
    with pytest.raises(ValueError, match="no host span"):
        tr.reduce_events({"host": ev["host"][1:], "devices": ev["devices"]})
    with pytest.raises(ValueError, match="no device operations"):
        tr.reduce_events({"host": ev["host"], "devices": [[]]})


def test_saved_events_round_trip(tmp_path: Path):
    tr.save_events(_events(), tmp_path / "e.json.gz")
    assert tr.read_saved(tmp_path / "e.json.gz") == _events()


class _Run:
    def __init__(self, trace_data):
        self.trace_data = trace_data
        self.peaks = costs.peaks_for("TPU v5 lite")


def test_recorded_chunk_step_of_internlm_offline():
    """One whole chunk step (16 slots x 256 rows) of the internlm2-1.8b
    offline cell, recorded on a TPU v5e and trimmed to the step."""
    ev = tr.read_saved(DATA / "internlm2-1.8b.prefill_offline.chunk_step.json.gz")
    out = tr.reduce_events(ev)
    assert out["window_s"] == pytest.approx(0.884731643)
    assert out["busy_s"] == pytest.approx(0.849504424)
    # 24 layers x 7 linears x (a g=5 and a g=4 segment at K=2048 and 8192)
    assert len(out["mpgemm"]) == 336
    assert {c["shape"]["n"] for c in out["mpgemm"]} == {4096}
    top = out["breakdown"]["device_ops"]
    assert top[0][0] == "ternary_decode_gemm_fused f32[8192,4096]"
    assert len(top) == 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert out["breakdown"]["idle_gaps"][0][0] == "engine.step"
    run = _Run(out)
    assert 8.0 < readers.mpgemm_roofline_pct(run) < 10.0
    assert readers.idle_share_pct(run) == pytest.approx(
        100 * (1 - 0.849504424 / 0.884731643))
