"""``copy_share_of_decode`` and ``prefill_chunk_device_ms`` (ISSUE 31):
the two metric files read a device plane through the ``op_share`` and
``trace_program_time`` readers the benchmark has; a run with no trace,
or a trace without the program, leaves them out of its line."""
import pytest

from benchmark import run, trace

COPY, CHUNK = "copy_share_of_decode", "prefill_chunk_device_ms"
CELLS = ["gpt2-xl.decode_backlog", "lfm2-8b-a1b.decode_backlog"]


def _plane(step_copy_s=0.004):
    """Three whole steps of 10 ms and two whole chunks of 30 and 50 ms
    between a first and a last step that the trace's edges cut. A step
    holds one ``copy`` (a pool relaid), an asynchronous copy pair that
    is not one, a fusion and the kernel; a chunk holds a longer copy."""
    modules, ops = [("jit_step(1)", 0.0, 0.006)], [
        ("copy/copy.1", 0.001, 0.004)]                 # in the cut run
    t = 0.010
    for i in range(3):
        modules.append(("jit_step", t, 0.010))
        ops += [("copy/copy.%d" % (7 + i), t + 0.001, step_copy_s),
                ("copy-start/copy-start.2", t + 0.006, 0.001),
                ("copy-done/copy-done.2", t + 0.007, 0.0005),
                ("fusion/fusion.3", t + 0.0075, 0.001),
                ("custom-call/paged_attention_decode.1", t + 0.0085, 0.001)]
        t += 0.010
        if i < 2:
            d = (0.030, 0.050)[i]
            modules.append(("jit_chunk", t, d))
            ops.append(("copy/copy.40", t + 0.002, 0.020))
            t += d
    modules.append(("jit_step", t, 0.003))             # cut by the end
    ops.append(("copy/copy.7", t + 0.001, 0.002))
    return trace.DevicePlane("/device:TPU:0", modules, ops)


def test_copy_share_is_the_steps_copy_time_over_the_steps_time():
    obs = {"trace": [_plane()]}
    assert run.read_metric(COPY, obs) == pytest.approx(100 * 0.004 / 0.010)
    # a step that relays nothing reads 0, not nothing
    assert run.read_metric(COPY, {"trace": [_plane(0.0)]}) == 0.0


def test_chunk_time_is_the_mean_of_its_whole_runs():
    assert run.read_metric(CHUNK, {"trace": [_plane()]}) \
        == pytest.approx((30 + 50) / 2)


@pytest.mark.parametrize("name", [COPY, CHUNK])
@pytest.mark.parametrize("obs", [
    {}, {"trace": []},
    {"trace": [trace.DevicePlane("/device:TPU:0", [("jit_other", 0.1, 0.2)],
                                 [("copy/copy.1", 0.0, 0.5)])]}],
    ids=["untraced", "no_plane", "no_such_program"])
def test_nothing_to_read_leaves_the_metric_out(name, obs):
    assert run.read_metric(name, obs) is None


@pytest.mark.parametrize("name,unit", [(COPY, "%"), (CHUNK, "ms")])
def test_benchmark_json_declares_it_for_both_cells(name, unit):
    (m,) = [m for m in run.load_spec()["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": unit, "better": "lower",
                 "source": "device_trace", "layer": "model forwards",
                 "moves": "itl_ms_p95", "workloads": CELLS}
    reported = {e["name"] for e in run.load_spec()["end_to_end"]
                if set(CELLS) <= set(e.get("workloads", CELLS))}
    assert m["moves"] in reported
