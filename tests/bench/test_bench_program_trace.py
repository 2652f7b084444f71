"""Reduction of the program's scopes, spans and counters
(``bench/program_trace.py``) on a hand-made trace."""
import pytest

from bench import program_trace as PT

MS = 1_000_000  # ns


def _op(name, t, d, op_name=None, **stats):
    text = name if op_name is None else (
        f"{name}, metadata={{op_name=\"{op_name}\" source_line=3}}")
    return [text, t * MS, d * MS, stats]


def _hand_trace():
    """A window of 100 ms: one step with a decode tick (10-50 ms on the
    device), one with a chunk step (60-70 ms); a ``while`` event encloses
    the tick's layer scan. Names take the chip's form: the whole
    instruction, with its metadata."""
    body = "jit(tick)/while/body"
    ops = [
        _op("%fusion.8 = u32[8] fusion(%p)", 10, 2,
            "jit(tick)/weight_decrypt/xor"),
        _op("%while.1 = (s32[]) while(%t), body=%b", 12, 34, "jit(tick)/while"),
        _op("%fusion.1 = u32[8] fusion(%a)", 12, 8, f"{body}/kv_view/kv_gather/gather"),
        _op("%fusion.2 = u32[8] fusion(%a)", 20, 6, f"{body}/kv_view/kv_mac/xor"),
        _op("%fusion.3 = u32[8] fusion(%a)", 26, 4, f"{body}/kv_view/kv_unseal/xor"),
        _op("%fusion.4 = bf16[8] fusion(%a)", 30, 2, f"{body}/kv_view/kv_mask/select_n"),
        _op("%sealed_matmul.5 = f32[32,64] custom-call(%x, %w)", 32, 8,
            f"{body}/pallas_call"),
        _op("%fusion.6 = f32[8] fusion(%a)", 40, 4, f"{body}/attention/dot_general"),
        _op("%fusion.7 = u32[8] fusion(%a)", 44, 2, "jit(tick)/kv_append/scatter"),
        _op("%fusion.9 = s32[8] fusion(%a)", 46, 2, "jit(tick)/sampling/argmax"),
        _op("%copy.10 = s32[8] copy(%a)", 48, 2, "jit(tick)/add"),
        # the chunk step: no metadata in the name, the program's HLO has it
        _op("%fusion.1 = u32[8] fusion(%a)", 60, 10),
    ]
    modules = [["jit_tick(7)", 10 * MS, 40 * MS],
               ["jit_chunk_step(3)", 60 * MS, 10 * MS]]
    spans = [["bench.traced", 0, 100 * MS], ["bench.step", 0, 55 * MS],
             ["bench.step", 55 * MS, 45 * MS]]
    counters = lambda res: {"blocks_gathered": 8, "blocks_resident": res,
                            "blocks_reserved": 5, "running": 2}
    program = [["serve.step", 0, 55 * MS, {}],
               ["serve.admit", 0, 5 * MS, {}],
               ["serve.decode", 8 * MS, 44 * MS, counters(3)],
               ["serve.decode.readback", 45 * MS, 7 * MS, {}],
               ["serve.step", 55 * MS, 45 * MS, {}],
               ["serve.chunk", 58 * MS, 37 * MS, {}],
               ["serve.chunk.readback", 85 * MS, 10 * MS, {}],
               ["serve.decode", 95 * MS, 4 * MS, counters(4)]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
            "spans": spans, "program_spans": program}


HLO = {"chunk_step": {
    "fusion.1": "jit(chunk_step)/while/body/kv_view/kv_gather/gather"}}


def test_scope_time_per_execution_counts_leaves_once():
    red = PT.reduce(_hand_trace(), HLO)
    tick = red["programs"]["tick"]
    assert tick["runs"] == 1 and tick["device_ms"] == pytest.approx(40)
    # the while event (34 ms) encloses its body's operations: not counted
    assert tick["leaf_ms"] == pytest.approx(40)
    assert tick["scopes_ms"] == {
        "kv_view": pytest.approx(20), "kv_gather": pytest.approx(8),
        "kv_mac": pytest.approx(6), "kv_unseal": pytest.approx(4),
        "kv_mask": pytest.approx(2), "attention": pytest.approx(4),
        "kv_append": pytest.approx(2), "weight_decrypt": pytest.approx(2),
        "sampling": pytest.approx(2)}
    assert tick["split_ms"] == {
        "kv_view": pytest.approx(20), "sealed_matmul": pytest.approx(8),
        "attention": pytest.approx(4), "kv_append": pytest.approx(2),
        "weight_decrypt": pytest.approx(2), "sampling": pytest.approx(2),
        "other": pytest.approx(2)}
    assert sum(tick["split_ms"].values()) == pytest.approx(tick["leaf_ms"])
    assert tick["other_top"] == [["%copy.10 = s32[8] copy(%a), metadata={op_"
                                  "name=\"jit(tick)/add\" source_line=3}",
                                  "jit(tick)/add", pytest.approx(2)]]
    # the same instruction name in another program is looked up there
    chunk = red["programs"]["chunk_step"]
    assert chunk["scopes_ms"] == {"kv_view": pytest.approx(10),
                                  "kv_gather": pytest.approx(10)}
    assert PT.reduce(_hand_trace())["programs"]["chunk_step"][
        "split_ms"] == {"other": pytest.approx(10)}


def test_counters_and_host_work_per_step():
    red = PT.reduce(_hand_trace())
    c = red["counters"]
    assert (c["ticks"], c["blocks_gathered"], c["blocks_resident"],
            c["blocks_reserved"], c["running"]) == (2, 16, 7, 10, 4)
    assert c["kv_view_useful_share"] == pytest.approx(100 * 7 / 16)
    assert c["kv_pool_resident_share"] == pytest.approx(100 * 7 / 10)
    # 55 - 7 and 45 - 10 ms of host work between the device's results
    assert red["host_sched_ms"] == pytest.approx((48 + 35) / 2)
    assert red["spans_ms"]["serve.decode"] == [2, pytest.approx(24)]


def test_idle_gaps_go_to_the_innermost_span():
    red = PT.reduce(_hand_trace())
    per = {k: round(v, 6) for k, v in red["idle_s_by_span"].items()}
    # idle: 0-10, 50-60, 70-100 ms; the harness's spans hold them all, a
    # program span over the same time lies inside
    assert per == {"serve.admit": 0.005, "serve.step": 0.010,
                   "serve.decode": 0.006, "serve.decode.readback": 0.002,
                   "serve.chunk": 0.017, "serve.chunk.readback": 0.01}
    assert red["idle_gaps"][0] == ["serve.chunk", pytest.approx(0.015)]
    assert sum(per.values()) == pytest.approx(0.1 - red["busy_s"])


def test_a_trace_without_the_programs_instrumentation_reads_nothing():
    ex = _hand_trace()
    ex["program_spans"] = []
    for op in ex["devices"][0]["ops"]:
        op[0] = op[0].split(", metadata=")[0]
    red = PT.reduce(ex)
    assert red["counters"] == {"ticks": 0, "blocks_gathered": 0,
                               "blocks_resident": 0, "blocks_reserved": 0,
                               "running": 0}
    assert red["host_sched_ms"] is None
    assert red["programs"]["tick"]["scopes_ms"] == {}


@pytest.mark.parametrize("name, container", [
    ("%while.1241 = (s32[], bf16[32,1,2048]) while((s32[]) %t), "
     "condition=%c, body=%b", True),
    ("while.3", True), ("conditional.2", True), ("call.7", True),
    ("%fusion.19 = u32[8] fusion(u32[8] %while.3), kind=kLoop", False),
    ("%sealed_matmul.91 = f32[256,8192] custom-call(f32[256,2048] %x)",
     False)])
def test_containers_are_told_from_leaves(name, container):
    assert PT.is_container(name) is container


def test_op_names_of_an_hlo_module():
    text = ('  %fusion.3 = u32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(tick)/kv_view/kv_mac/xor" '
            'stack_frame_id=2}\n'
            '  ROOT %tuple.9 = (u32[8]) tuple(%fusion.3)\n'
            '  %copy.1 = u32[8]{0} copy(%p), metadata={op_name="jit(tick)/'
            'sampling/copy"}\n')
    assert PT.hlo_op_names(text) == {
        "fusion.3": "jit(tick)/kv_view/kv_mac/xor",
        "copy.1": "jit(tick)/sampling/copy"}


def test_tool_on_a_tiny_cell_reads_the_programs_spans(tmp_path, monkeypatch,
                                                      capsys):
    """The whole tool on the CPU at a tiny size: the trace holds no device
    plane here, but the program's spans and counters come through."""
    import json
    import shutil

    from bench import roofline, run, system
    from helpers import ROOT, tiny_tree
    from repro.configs import get_reduced
    root = tiny_tree(tmp_path / "root")
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    monkeypatch.setattr(PT, "ROOT", root)
    monkeypatch.setattr(system, "model_config",
                        lambda conf: get_reduced(conf["model_id"]))
    monkeypatch.setattr(run, "open_chip", lambda cell: {"kind": "cpu"})
    monkeypatch.setattr(roofline, "peaks", lambda kind: {})
    assert PT.main(["--workload", "tiny.chat", "--seed", str(2**31 + 3),
                    "--seconds", "2", "--out", str(tmp_path / "out")]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c = res["counters"]
    assert c["ticks"] > 0 and c["running"] > 0
    assert 0 < c["kv_view_useful_share"] < 100
    assert 0 < c["kv_pool_resident_share"] <= 100
    assert res["host_sched_ms"] > 0
    assert {"serve.step", "serve.admit", "serve.decode",
            "serve.decode.readback"} <= set(res["spans_ms"])
    assert res["itl_p95_ms"]["traced_part"] > 0
    assert res["stats"]["kv_blocks_gathered"] > 0
    # no device plane on the CPU: the device metrics read nothing
    assert res["per_layer"] == {}


def test_the_tools_extract_reads_the_benchmarks_metrics_unchanged():
    """What the tool adds to an extract (program spans, every op stat)
    leaves the benchmark's window and per-layer readings as they were."""
    import copy
    import types

    from bench import trace as TR
    from bench.spec import Cell
    from helpers import ROOT
    from test_bench_trace import _hand_trace as bench_trace
    ex = bench_trace()
    more = copy.deepcopy(ex)
    more["program_spans"] = [["serve.step", 1 * MS, 38 * MS, {}],
                             ["serve.decode", 2 * MS, 30 * MS,
                              {"blocks_gathered": 8, "blocks_resident": 3,
                               "blocks_reserved": 5, "running": 2}]]
    for op in more["devices"][0]["ops"]:
        op[3].update(device_offset_ps="1", device_duration_ps="2")
    bench = types.SimpleNamespace(bench_dir=ROOT / "bench")
    read = lambda e, m: Cell.reader(bench, m)(
        types.SimpleNamespace(trace=TR.summarize(e)))
    for m in ("chunk_step_ms", "decode_tick_ms", "device_idle_share"):
        assert read(more, m) == read(ex, m) is not None
    assert TR.summarize(more).window == TR.summarize(ex).window
    assert PT.reduce(more)["counters"]["blocks_resident"] == 3
