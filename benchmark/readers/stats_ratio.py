"""A ratio of the program's ``/stats`` counters over the window: a sum
of deltas (close minus open) over a sum of deltas, times the values
some paths hold at the close.

``num`` and ``den`` are lists of paths whose deltas are added;
``num_less`` and ``den_less`` are lists whose deltas are taken off
(``loop_s`` less ``phase_s.idle``); ``den_times_close`` multiplies the
denominator by each path's value at the close (a pool's size);
``scale`` multiplies the result. A path the program does not serve, or
a denominator of zero, gives None: the metric is left out of the line.

Counters of one block that the program commits together (the
scheduler's time account commits once an iteration) give exact
averages over whole iterations, however the two reads of ``/stats``
fall."""
from benchmark.readers.stats_counter import window_value


def _sum(obs, paths, mode="delta"):
    vals = [window_value(obs, p, mode) for p in paths]
    return None if any(v is None for v in vals) else sum(vals)


def read(obs, num, den, num_less=(), den_less=(), den_times_close=(),
         scale: float = 1.0, **_):
    parts = [_sum(obs, num), _sum(obs, num_less),
             _sum(obs, den), _sum(obs, den_less)]
    at_close = [window_value(obs, p, "close") for p in den_times_close]
    if any(v is None for v in parts + at_close):
        return None
    top, bottom = parts[0] - parts[1], parts[2] - parts[3]
    for v in at_close:
        bottom *= v
    if not bottom:
        return None
    return top / bottom * scale
