"""Mean device time of one execution of the programs matching
``program`` (a regex on the jitted function's name), on the device
that spent most in them, in milliseconds."""
from benchmark import trace


def read(obs, program: str, **_):
    if not obs.get("trace"):
        return None
    got = trace.program_seconds(obs["trace"], program)
    if got is None:
        return None
    seconds, runs = got
    return seconds / runs * 1e3
