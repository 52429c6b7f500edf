"""Share of the chips' peak FLOP/s that the work the algorithm needs
amounts to. ``flops`` names a function of ``benchmark/flops.py`` that
totals the model FLOPs of the work done in a span.

``per="window"``: all the window's work over all the window's time and
all the chips (host clock). ``per="program"``: the window's work
divided by the number of steps the program counted in it (``steps``, a
/stats path), over the mean device time of one run of ``program`` in
the trace: the step's own share, free of host gaps."""
from benchmark import flops as F, trace
from benchmark.readers import stats_counter


def read(obs, flops: str, per: str = "window", program: str = None,
         steps: str = None, **_):
    span = obs["window"]["span"]
    work = getattr(F, flops)(obs, span)
    peak = obs["peaks"]["flops_per_s"]
    if not work:
        return None
    if per == "window":
        return 100.0 * work / (obs["window"]["seconds"] * obs["chips"] * peak)
    if not obs.get("trace"):
        return None
    got = trace.program_seconds(obs["trace"], program)
    n_steps = stats_counter.window_value(obs, steps, "delta")
    if got is None or not n_steps:
        return None
    seconds, runs = got
    return 100.0 * (work / n_steps) / (seconds / runs) / peak
