"""A kernel's share of its roofline: the least time the chip could
take for the FLOPs and bytes the algorithm needs in one step (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s), over the
kernel's device time in one step.

``cost`` names a function of ``benchmark/flops.py`` returning the
(FLOPs, bytes) of the window's work; ``steps`` is the /stats path that
counts the window's steps; ``op`` and ``program`` are regexes on the
trace's operation labels and program names. The side that binds is
written to ``obs["roofline_binds"]``."""
from benchmark import flops as F, trace
from benchmark.readers import stats_counter


def read(obs, op: str, program: str, cost: str, steps: str, **_):
    if not obs.get("trace"):
        return None
    plane = trace.fullest(obs["trace"])
    runs = trace.program_runs(plane, program)
    kernel_s = trace.op_seconds(plane, op, program)
    n_steps = stats_counter.window_value(obs, steps, "delta")
    if not runs or not kernel_s or not n_steps:
        return None
    fl, by = getattr(F, cost)(obs, obs["window"]["span"])
    if not fl:
        return None
    least, side = F.roofline_seconds(fl / n_steps, by / n_steps, obs["peaks"])
    obs.setdefault("roofline_binds", {})[op] = side
    return 100.0 * least / (kernel_s / len(runs))
