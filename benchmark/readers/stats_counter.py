"""A number from the program's ``/stats`` counters, read when the
window opened and when it closed.

``mode``: ``close`` (the value at the close), ``delta`` (close minus
open), ``hist_mean_delta`` (mean of an exact value->count histogram
over what was recorded inside the window). ``over`` divides by another
path's value at the close (or by a number); ``scale`` multiplies."""


def dig(d, path: str):
    for part in path.split("."):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d


def window_value(obs, path: str, mode: str = "close"):
    st = obs.get("stats") or {}
    if "open" not in st or "close" not in st:
        return None
    a, b = dig(st["open"], path), dig(st["close"], path)
    if b is None:
        return None
    if mode == "close":
        return b
    if a is None:
        return None
    if mode == "delta":
        return b - a
    if mode == "hist_mean_delta":
        d = {k: v - a.get(k, 0) for k, v in b.items()}
        n = sum(d.values())
        return sum(int(k) * v for k, v in d.items()) / n if n else None
    raise ValueError(f"unknown mode {mode!r}")


def read(obs, path: str, mode: str = "close", over=None, scale: float = 1.0,
         **_):
    v = window_value(obs, path, mode)
    if v is None:
        return None
    if over is not None:
        den = over if isinstance(over, (int, float)) else \
            window_value(obs, over, "close")
        if not den:
            return None
        v = v / den
    return v * scale
