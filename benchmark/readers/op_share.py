"""Device time of the operations matching ``op`` inside runs of
``program``, as a share of those runs' device time."""
from benchmark import trace


def read(obs, op: str, program: str, **_):
    if not obs.get("trace"):
        return None
    plane = trace.fullest(obs["trace"])
    runs = trace.program_runs(plane, program)
    if not runs:
        return None
    return 100.0 * trace.op_seconds(plane, op, program) / sum(
        d for _, _, d in runs)
