"""Percentiles of what the clients stamped.

``itl``: every gap between consecutive tokens of one request whose
later token arrived in the window, over all requests. ``ttft``: send
to first token, over all requests sent in the window (a request whose
first token never came is not a latency: the runner counts it as
failed)."""
import numpy as np


def samples(obs, what: str):
    t0, t1 = obs["window"]["span"]
    out = []
    for r in obs["requests"]:
        ts = r["token_times"]
        if what == "itl":
            out += [b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
        elif what == "ttft":
            if r["t_send"] is not None and t0 <= r["t_send"] <= t1 and ts:
                out.append(ts[0] - r["t_send"])
        else:
            raise ValueError(f"unknown stamp series {what!r}")
    return out


def read(obs, what: str, percentile: float, **_):
    xs = samples(obs, what)
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs), percentile)) * 1e3
