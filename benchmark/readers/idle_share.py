"""1 - (seconds in which an operation ran on the device, averaged over
the chips) / (traced window)."""
from benchmark import trace


def read(obs, **_):
    if not obs.get("trace"):
        return None
    busy, win = trace.busy_and_window(obs["trace"])
    return 100.0 * (1.0 - busy / win) if win > 0 else None
