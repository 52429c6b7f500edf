"""Shared observability test helpers: a mini Prometheus exposition
parser and a GENERIC snapshot-vs-exposition parity walker.

The walker independently re-derives, from any `/stats`-shaped snapshot
dict, every sample the exposition layer is documented to emit — family
name flattening, the counter `_total` suffix rule, reservoir dicts as
quantile-labelled summaries, int-keyed count histograms as
bucket-labelled series, lists as `_count` gauges — and asserts each one
is present in the parsed `/metrics` text with the right value and
`# TYPE`. One walker covers every family, so a snapshot leaf added
anywhere in the tree is parity-checked for free (the point of ISSUE
13's satellite: no more hand-written per-family asserts that silently
miss new leaves).

Only the POLICY data is imported from the implementation (the counter
leaf-name set and the reservoir key tuple); the flattening mechanism is
re-implemented here so the test fails if the exposition layer's
mechanics drift.
"""
import re

from deeplearning4j_tpu.profiler import RESERVOIR_SNAPSHOT_KEYS
from deeplearning4j_tpu.serving.metrics import _PROM_COUNTERS

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' (-?\d+(?:\.\d+)?(?:[eE][-+]?\d+)?)$')
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|summary)$")

_RESERVOIR_KEYS = frozenset(RESERVOIR_SNAPSHOT_KEYS)

#: leaf names whose VALUE is time-dependent between two successive HTTP
#: reads (sliding-window rates, wall-clock stamps): presence and type
#: are asserted, the value is not.
VOLATILE_LEAVES = frozenset({"tokens_per_sec", "samples_per_sec",
                             "ts", "uptime_s", "iter_seconds",
                             # CPython collects whenever it allocates,
                             # between any two reads
                             "process_gc_collections", "process_gc_pause_s",
                             "process_gc_pause_s_total",
                             "process_gc_longest_s"})


def _volatile(base):
    return any(base.endswith("_" + v) or base == v for v in VOLATILE_LEAVES)


def parse_prometheus(text):
    """Validate the text exposition grammar line by line and return
    ({(name, labels_str): float}, {name: type})."""
    samples, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        mt = _TYPE_RE.match(line)
        if mt:
            types[mt.group(1)] = mt.group(2)
            continue
        assert not line.startswith("#"), f"unexpected comment: {line!r}"
        ms = _SAMPLE_RE.match(line)
        assert ms, f"invalid exposition line: {line!r}"
        samples[(ms.group(1), ms.group(2) or "")] = float(ms.group(3))
    return samples, types


def _name(*parts):
    name = "_".join(p for p in parts if p)
    name = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _esc(v):
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _labels(pairs):
    lab = ",".join(f'{k}="{_esc(v)}"' for k, v in pairs if v is not None)
    return "{" + lab + "}" if lab else ""


def _num(v):
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    return float(v)


def _is_int_key(k):
    try:
        int(k)
        return True
    except (TypeError, ValueError):
        return False


def expected_samples(obj, base, labels=()):
    """Yield (family, labels_str, value_or_None, type) for every sample
    a snapshot subtree must produce. value None means volatile — assert
    presence only."""
    labels = list(labels)
    if isinstance(obj, (bool, int, float)):
        value = None if _volatile(base) else _num(obj)
        if any(base.endswith("_" + c) or base == c
               for c in _PROM_COUNTERS):
            yield base + "_total", _labels(labels), value, "counter"
        else:
            yield base, _labels(labels), value, "gauge"
        return
    if isinstance(obj, dict):
        if obj and set(obj) == _RESERVOIR_KEYS:
            for q, key in (("0.5", "p50"), ("0.9", "p90"),
                           ("0.99", "p99")):
                yield (base, _labels(labels + [("quantile", q)]),
                       _num(obj[key]), "summary")
            yield (base + "_count", _labels(labels), _num(obj["count"]),
                   "summary")
            yield base + "_mean", _labels(labels), _num(obj["mean"]), \
                "gauge"
            yield base + "_max", _labels(labels), _num(obj["max"]), \
                "gauge"
            return
        if obj and all(_is_int_key(k) for k in obj) and \
                all(isinstance(v, (int, float)) for v in obj.values()):
            for k, v in obj.items():
                yield (base, _labels(labels + [("bucket", k)]),
                       None if _volatile(base) else _num(v), "gauge")
            return
        for k, v in obj.items():
            yield from expected_samples(v, _name(base, str(k)), labels)
        return
    if isinstance(obj, (list, tuple)):
        yield base + "_count", _labels(labels), float(len(obj)), "gauge"
        return
    # strings / None produce no samples


def assert_subtree_parity(obj, base, samples, types, labels=()):
    """Assert every expected sample of one subtree is present with the
    right value and type. Returns the number of samples checked."""
    checked = 0
    for fam, lab, value, mtype in expected_samples(obj, base, labels):
        assert (fam, lab) in samples, f"missing sample {fam}{lab}"
        if value is not None:
            got = samples[(fam, lab)]
            assert got == value, \
                f"{fam}{lab}: exposition {got} != snapshot {value}"
        assert types.get(fam) == mtype, \
            f"{fam}: # TYPE {types.get(fam)} != expected {mtype}"
        checked += 1
    return checked


def assert_exposition_parity(stats, samples, types, prefix="dl4j"):
    """Full-snapshot parity: mirrors the exposition layer's top-level
    dispatch (replica-server / fleet / generic snapshots) and walks
    EVERY numeric leaf. Returns the number of samples checked — callers
    assert it is > 0 so an accidentally-empty snapshot can't pass."""
    checked = 0
    if "models" in stats:
        summary = dict(stats.get("summary") or {})
        summary.pop("models", None)
        checked += assert_subtree_parity(
            summary, _name(prefix, "server"), samples, types)
        for mname, snap in (stats.get("models") or {}).items():
            checked += assert_subtree_parity(
                snap, _name(prefix, "model"), samples, types,
                [("model", mname)])
        for section, timing in (stats.get("profiler") or {}).items():
            checked += assert_subtree_parity(
                timing, _name(prefix, "profiler"), samples, types,
                [("section", section)])
    elif "fleet" in stats:
        fl = dict(stats["fleet"])
        replicas = fl.pop("replicas", [])
        checked += assert_subtree_parity(
            fl, _name(prefix, "fleet"), samples, types)
        for rep in replicas:
            rid = rep.get("id") if isinstance(rep, dict) else None
            checked += assert_subtree_parity(
                rep, _name(prefix, "replica"), samples, types,
                [("replica", rid)])
    else:
        checked += assert_subtree_parity(stats, prefix, samples, types)
    assert checked > 0, "snapshot produced no expected samples"
    return checked
