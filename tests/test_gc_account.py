"""CPython's collections as the program's own span and counter
(ISSUE 39): one ``gc.callbacks`` entry however many engines start,
``process.gc`` in every served model's ``/stats`` block and on
``/metrics``, and a ``host.gc`` span in a profile. On the CPU: counts
and identities, never a time."""
import gc
import json
import urllib.request

import jax
import pytest

from deeplearning4j_tpu.serving import GenerationEngine, InferenceServer
from deeplearning4j_tpu.serving.metrics import (GC_ACCOUNT, GC_SPAN,
                                                GcAccount)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM


@pytest.fixture(scope="module")
def lm():
    return CausalTransformerLM(vocab_size=64, d_model=32, n_layers=2,
                               n_heads=4, max_seq_len=32, seed=0,
                               implementation="plain").init()


def _ours():
    return [cb for cb in gc.callbacks
            if isinstance(getattr(cb, "__self__", None), GcAccount)]


@pytest.fixture
def account():
    acct = GcAccount()
    yield acct
    while acct._callback in gc.callbacks:
        gc.callbacks.remove(acct._callback)


def test_a_forced_collection_is_counted_in_its_generation(account):
    account.start()
    before = account.snapshot()
    gc.collect()
    after = account.snapshot()
    assert after["collections"]["2"] == before["collections"]["2"] + 1
    assert after["pause_s_total"] > before["pause_s_total"]
    assert after["pause_s"]["2"] > before["pause_s"]["2"]
    assert after["longest_s"] >= after["pause_s"]["2"] - before["pause_s"]["2"]
    assert sorted(after["collections"]) == ["0", "1", "2"]
    assert after["pause_s_total"] == pytest.approx(sum(after["pause_s"].values()))


def test_a_second_start_leaves_one_callback_installed(account):
    account.start()
    account.start()
    assert gc.callbacks.count(account._callback) == 1


def test_an_account_that_is_not_started_counts_nothing(account):
    gc.collect()
    assert account.snapshot()["collections"] == {"0": 0, "1": 0, "2": 0}


def test_engines_install_the_process_account_once(lm):
    engines = [GenerationEngine(lm, num_slots=2, max_queue=8,
                                min_prompt_bucket=4, cache="paged",
                                block_size=8, prefill_chunk_tokens=8)
               for _ in range(2)]
    for e in engines:
        e.stop()
    assert _ours() == [GC_ACCOUNT._callback]


def test_a_profiled_collection_is_one_host_gc_span(tmp_path):
    from benchmark import host_trace, trace as trace_mod
    GC_ACCOUNT.start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1              # what benchmark/run.py asks
    gc.disable()                            # the forced one and no other
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        gc.enable()
    spans = host_trace.load_host_spans(trace_mod.find_xplane(str(tmp_path)))
    (got,) = [s for s in spans if s["name"] == GC_SPAN]
    assert int(got["generation"]) == 2 and got["end_s"] > got["start_s"]
    assert GC_SPAN == host_trace.GC_SPAN
    assert GC_SPAN in host_trace.HOST_PREFIXES


def test_stats_and_metrics_show_process_gc(lm):
    srv = InferenceServer(port=0)
    srv.register_generator("lm", lm, num_slots=2, max_queue=8,
                           min_prompt_bucket=4, cache="paged",
                           block_size=8, prefill_chunk_tokens=8)
    try:
        base = f"http://{srv.host}:{srv.port}"
        gc.collect()
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            block = json.loads(r.read())["models"]["lm"]["process"]["gc"]
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        srv.stop()
    assert block["collections"]["2"] >= 1 and block["pause_s_total"] > 0
    assert set(block) == {"collections", "pause_s", "pause_s_total",
                          "longest_s"}
    assert 'dl4j_model_process_gc_collections{model="lm",bucket="2"}' in text
    assert 'dl4j_model_process_gc_pause_s{model="lm",bucket="0"}' in text
    assert 'dl4j_model_process_gc_pause_s_total{model="lm"}' in text
    assert 'dl4j_model_process_gc_longest_s{model="lm"}' in text
    assert _ours() == [GC_ACCOUNT._callback]
