"""Reduction of a profiler trace (``bench/trace.py``)."""
import json
from pathlib import Path

import pytest

from bench import trace as TR

MS = 1_000_000  # ns


def _hand_trace():
    # window 0-100 ms; two steps 0-40 and 50-100; a wait 40-50
    ops = [["fusion.1", 5 * MS, 10 * MS, {}],
           ["sealed_matmul", 10 * MS, 15 * MS, {}],      # overlaps fusion.1
           ["fusion.2", 30 * MS, 5 * MS, {}],
           ["sealed_matmul", 60 * MS, 20 * MS, {}],
           ["copy.3", 95 * MS, 10 * MS, {}]]             # runs past the end
    modules = [["jit_tick(7)", 5 * MS, 30 * MS],
               ["jit_chunk_step.2", 60 * MS, 20 * MS],
               ["jit_tick(7)", 95 * MS, 10 * MS]]        # ends outside
    spans = [["bench.traced", 0, 100 * MS], ["bench.step", 0, 40 * MS],
             ["bench.wait", 40 * MS, 10 * MS], ["bench.step", 50 * MS, 50 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "spans": spans}


def test_busy_is_the_union_of_operations_inside_the_window():
    s = TR.summarize(_hand_trace())
    assert s.window_s == pytest.approx(0.1)
    # 5-25, 30-35, 60-80, 95-100 -> 50 ms
    assert s.busy_s == pytest.approx(0.050)


def test_idle_share_inside_step_spans():
    s = TR.summarize(_hand_trace())
    # step spans cover 90 ms, of which 50 ms are busy
    assert s.idle_share("bench.step") == pytest.approx(40 / 90)
    assert s.idle_share("bench.wait") == pytest.approx(1.0)
    assert s.idle_share("bench.nothing") is None


def test_program_and_kernel_time_by_name():
    s = TR.summarize(_hand_trace())
    assert s.programs == {"tick": [pytest.approx(0.03)],
                          "chunk_step": [pytest.approx(0.02)]}
    assert [d for d, _ in s.kernel_events("sealed_matmul")] == [
        pytest.approx(0.015), pytest.approx(0.02)]


def test_breakdown_names_gaps_by_host_span():
    b = TR.summarize(_hand_trace()).breakdown()
    assert b["device_ops"][0] == ["sealed_matmul", pytest.approx(0.035)]
    gaps = {(n, round(v, 4)) for n, v in b["idle_gaps"]}
    # 0-5 step, 25-30 step, 35-40 step, 40-50 wait, 50-60 step, 80-95 step
    assert gaps == {("bench.step", 0.005), ("bench.step", 0.005),
                    ("bench.wait", 0.01), ("bench.step", 0.01),
                    ("bench.step", 0.015)}
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(0.015)]


def test_union_and_gaps():
    assert TR.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert TR.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert TR.overlap([(1, 2), (4, 5)], 1.5, 4.5) == pytest.approx(1.0)


def test_sealed_matmul_roofline_reads_each_calls_shapes():
    import types
    from bench import roofline
    from bench.spec import Cell
    long_name = ("%custom-call.5 = f32[32,8192]{1,0} custom-call(u32[8]{0} "
                 "%k, f32[32,2048]{1,0} %x, u32[2048,8192]{1,0} %w, "
                 "s32[2048,1]{1,0} %m)")
    ops = [["custom-call.5", 10 * MS, 2 * MS,
            {"long_name": long_name,
             "tf_op": "jit(tick)/while/body/sealed_matmul"}],
           ["fusion.9", 12 * MS, 1 * MS, {}]]
    ex = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
          "spans": [["bench.traced", 0, 100 * MS]]}
    peak = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(trace=TR.summarize(ex), peak=peak)
    read = Cell.reader(types.SimpleNamespace(bench_dir=Path(
        __file__).resolve().parents[2] / "bench"), "sealed_matmul_roofline")
    ideal, bound = roofline.matmul_roofline_s(32, 2048, 8192, peak)
    assert bound == "memory"
    assert ideal == pytest.approx(
        2 * (2048 * 8192 + 32 * 2048 + 32 * 8192) / 819e9)
    assert read(ctx) == pytest.approx(100 * ideal / 0.002)
    ctx.trace.ops[0][3].pop("long_name")
    assert read(ctx) is None


def test_sealed_matmul_roofline_skips_the_wrappers_operations():
    """On the chip the event's name is the HLO instruction and the
    operations of the kernel's jitted wrapper, and those that consume its
    result, bear its name too: those are skipped, and the custom call is
    read from its name."""
    import types
    from bench import roofline
    from bench.spec import Cell
    call = ("%sealed_matmul.91 = f32[256,8192]{1,0:T(8,128)S(1)} custom-call("
            "u32[8]{0:T(128)S(1)} %copy-done.88, u32[3]{0:T(128)S(1)} %c.1, "
            "u32[1]{0:T(128)} %dynamic_slice.491, f32[256,2048]{1,0:T(8,128)"
            "S(1)} %bitcast.900, u32[2048,8192]{1,0:T(8,128)} %fusion.10, "
            "s32[2048,1]{1,0:T(8,128)} %copy.355), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints={u32[8]{0}, "
            "f32[256,2048]{1,0}, u32[2048,8192]{1,0}, s32[2048,1]{1,0}}")
    wrapper = ("%fusion.34 = u32[4718592]{0:T(1024)S(1)} fusion(u32[24,8,"
               "24576]{2,1,0:T(8,128)S(1)} %pad.3, s32[4718592]{0} %b.8), "
               "kind=kCustom, calls=%fused_computation.10.clone")
    ops = [[call, 10 * MS, 3 * MS, {"tf_op": "jit(tick)/sealed_matmul"}],
           [wrapper, 13 * MS, 1 * MS, {"tf_op": "jit(tick)/sealed_matmul"}],
           ["%copy.355 = s32[2048,1]{1,0} copy(s32[2048,1]{1,0} %m)",
            14 * MS, 1 * MS, {"tf_op": "jit(tick)/jit(sealed_matmul)/convert"}],
           # consumers of the kernel's result name it among their operands
           ["%fusion.7 = f32[256,8192]{1,0} fusion(f32[256,8192]{1,0} "
            "%sealed_matmul.91), kind=kLoop", 15 * MS, 1 * MS, {}],
           ["%other.2 = f32[256,8192]{1,0} custom-call(f32[256,2048]{1,0} "
            "%x, f32[2048,8192]{1,0} %sealed_matmul.91)", 16 * MS, 1 * MS, {}]]
    ex = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
          "spans": [["bench.traced", 0, 100 * MS]]}
    peak = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(trace=TR.summarize(ex), peak=peak)
    read = Cell.reader(types.SimpleNamespace(bench_dir=Path(
        __file__).resolve().parents[2] / "bench"), "sealed_matmul_roofline")
    ideal, _ = roofline.matmul_roofline_s(256, 2048, 8192, peak)
    assert read(ctx) == pytest.approx(100 * ideal / 0.003)
    ctx.trace.ops[:] = ctx.trace.ops[1:]
    assert read(ctx) is None
