"""The trace reduction on hand-built planes: idle share, program time,
kernel time, gap attribution."""
import pytest

import benchmark_testlib as lib  # noqa: F401  (puts the repo on the path)
from benchmark import trace
from benchmark.readers import (idle_share, kernel_roofline, op_share,
                               trace_program_time)


def plane():
    """Two runs of jit_step (10 ms each, a 6 ms kernel inside), a
    5 ms host gap between them, then jit_chunk; an all-reduce of 2 ms
    of which 1 ms overlaps nothing."""
    mods = [("jit_step", 0.000, 0.010), ("jit_step", 0.015, 0.010),
            ("jit_chunk", 0.030, 0.004)]
    ops = [("custom-call/step.4", 0.001, 0.006), ("fusion/fusion.1", 0.007, 0.003),
           ("custom-call/step.4", 0.016, 0.006), ("fusion/fusion.1", 0.022, 0.003),
           ("fusion/fusion.9", 0.030, 0.002), ("all-reduce/all-reduce.2", 0.032, 0.002),
           ("copy/copy.3", 0.000, 0.001), ("copy/copy.3", 0.015, 0.001)]
    return trace.DevicePlane("/device:TPU:0", sorted(mods, key=lambda e: e[1]),
                             sorted(ops, key=lambda e: e[1]))


def test_op_label_parses_the_hlo_text():
    raw = ("%step.7 = f32[16,25,1,64]{3,2,1,0:T(1,128)S(1)} custom-call("
           "s32[16,64]{1,0:T(8,128)S(1)} %a), custom_call_target=\"tpu\"")
    assert trace.op_label(raw) == "custom-call/step.7"
    tup = ("%fusion.347 = (bf16[64]{0:T(256)(128)(2,1)}, bf16[32,224]{1,0}) "
           "fusion(bf16[3]{0} %p), kind=kOutput")
    assert trace.op_label(tup) == "fusion/fusion.347"
    assert trace.short_name("custom-call/step.7") == "step"
    assert trace.program_name("jit_step_fn(10866082386106580105)") == "jit_step_fn"


def test_interval_arithmetic():
    u = trace.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)] and trace.total(u) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 1), (4, 5)], [(0, 5)]) == []


def test_busy_window_and_idle_share():
    p = plane()
    busy, win = trace.busy_and_window([p])
    assert win == pytest.approx(0.034)
    assert busy == pytest.approx(0.010 + 0.010 + 0.004)
    assert idle_share.read({"trace": [p]}) == pytest.approx(
        100 * (1 - 0.024 / 0.034))


def test_program_and_kernel_time():
    p = plane()
    assert trace.program_seconds([p], "^jit_step$") == (pytest.approx(0.020), 2)
    obs = {"trace": [p]}
    assert trace_program_time.read(obs, program="^jit_step$") == pytest.approx(10.0)
    assert trace.op_seconds(p, r"^custom-call/step(\.\d+)?$", "^jit_step$") \
        == pytest.approx(0.012)
    assert op_share.read(obs, op=r"^custom-call/step", program="^jit_step$") \
        == pytest.approx(60.0)
    # nothing to read is nothing, never 0
    assert trace_program_time.read(obs, program="^jit_nothing$") is None
    assert op_share.read({"trace": None}, op="x", program="y") is None


def test_kernel_metrics_read_any_named_custom_call_of_the_decode_program():
    """A Pallas kernel is a custom call named after its function or its
    ``name=`` (today the enclosing ``step``); XLA's own custom calls
    keep the default name. A renamed kernel must not go silent."""
    import re
    for metric in ("paged_attention_roofline", "paged_attn_share_of_decode"):
        args = lib.load(lib.BENCH, "metrics", metric + ".json")["args"]
        rx = re.compile(args["op"])
        assert rx.search("custom-call/step.7") and rx.search("custom-call/step")
        assert rx.search("custom-call/paged_decode_kernel.2")
        assert not rx.search("custom-call/custom-call.3")
        assert not rx.search("custom-call/custom-call")
        assert not rx.search("fusion/fusion.9")
        assert re.compile(args["program"]).search("jit_step")
        assert op_share.read({"trace": [plane()]}, **args) == pytest.approx(60.0)


def test_gap_attribution_and_top_operations():
    p = plane()
    gaps = dict(trace.idle_gaps(p))
    assert gaps["after_jit_step_before_jit_step"] == pytest.approx(0.005)
    assert gaps["after_jit_step_before_jit_chunk"] == pytest.approx(0.005)
    assert trace.top_ops(p)[0] == ["step", pytest.approx(0.012)]


def test_kernel_roofline_from_shapes_over_kernel_time():
    cfg = lib.load(lib.BENCH, "configs", "gpt2-xl.json")
    from benchmark import flops
    obs = {"trace": [plane()], "config": cfg,
           "peaks": flops.peaks_for("TPU v5 lite"),
           "window": {"span": (0.0, 10.0)},
           "stats": {"open": {"decode_steps": 100},
                     "close": {"decode_steps": 102}},
           "requests": [{"prompt_len": 100, "token_times": [0.5, 1.0, 2.0]}]}
    got = kernel_roofline.read(obs, op=r"^custom-call/step", program="^jit_step$",
                               cost="decode_steps_attention_cost",
                               steps="decode_steps")
    fl, by = flops.paged_attention_cost(cfg["model"], 4, [101, 102])
    least = max(fl / 2 / 197e12, by / 2 / 819e9)
    assert got == pytest.approx(100 * least / 0.006)
    assert obs["roofline_binds"][r"^custom-call/step"] == "memory"
