"""The generation scheduler's time account (ISSUE 26): phase counters
that partition the loop's wall time, the same phases as
``jax.profiler.TraceAnnotation`` spans, the counters at the scheduler's
and the HTTP tier's boundaries, and what was repaired in the tracing
that was there. All on the CPU with a tiny LM: counts and identities,
never a time."""
import importlib.util
import json
import os
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.profiler import OpProfiler, ProfilingMode
from deeplearning4j_tpu.serving import GenerationEngine, InferenceServer
from deeplearning4j_tpu.serving import generation as generation_mod
from deeplearning4j_tpu.serving.metrics import (HEAD_BLOCKED_CAUSES,
                                                HTTP_WRITE_SPAN,
                                                SCHED_PHASES,
                                                SchedulerAccount)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lm():
    return CausalTransformerLM(vocab_size=64, d_model=32, n_layers=2,
                               n_heads=4, max_seq_len=32, seed=0,
                               implementation="plain").init()


def _engine(lm, **kw):
    args = dict(num_slots=4, max_queue=64, min_prompt_bucket=4,
                cache="paged", block_size=8, prefill_chunk_tokens=8,
                enable_prefix_sharing=False)
    args.update(kw)
    eng = GenerationEngine(lm, **args)
    eng.warmup()
    return eng


def _burst(eng, n=6, max_tokens=6, prompt=lambda i: 5 + 3 * i):
    """n concurrent greedy requests of mixed prompt lengths (one chunk
    to three), so admissions, chunks and steps interleave."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, prompt(i)).tolist() for i in range(n)]
    outs = [None] * n

    def go(i):
        outs[i] = eng.generate(prompts[i], max_tokens=max_tokens,
                               temperature=0.0)
    ths = [threading.Thread(target=go, args=(i,)) for i in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(120)
    return outs


def _settled(eng):
    """/stats once every step dispatched has been collected and its
    iteration committed (the account commits once an iteration, the
    engine's own counters at once)."""
    t_end = time.time() + 20
    while time.time() < t_end:
        s = eng.stats()
        n = s["scheduler"]["phase_n"]
        if n["decode_wait"] == n["decode_dispatch"] == s["decode_steps"] \
                and not s["slots"]["active"] and not s["queue_depth"]:
            return s
        time.sleep(0.02)
    raise AssertionError("the engine did not settle")


# -- the account itself --------------------------------------------------
class _Clock:
    """A clock the test steps by hand: times here are exact."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_phases_nest_by_pausing_the_parent_and_sum_to_the_loop():
    clk = _Clock()
    acct = SchedulerAccount(clock=clk)
    acct.start()
    clk.sleep(0.5)                              # claimed by no phase
    with acct.phase("admit", step=0) as t0:
        assert t0 == 100.5
        clk.sleep(2.0)
        with acct.phase("idle"):
            clk.sleep(4.0)
        clk.sleep(1.0)
    assert acct.t == 107.5                      # the stamp going out
    with acct.phase("decode_dispatch", step=0, slots=2):
        clk.sleep(0.25)
    acct.step_collected(40)
    acct.tick(1)
    with acct.phase("emit", step=0):
        clk.sleep(1.0)
    clk.sleep(0.125)
    acct.tick(1)
    s = acct.snapshot()
    assert set(s["phase_s"]) == set(s["phase_n"]) == set(SCHED_PHASES)
    assert set(s["head_blocked_s"]) == set(HEAD_BLOCKED_CAUSES)
    assert s["phase_s"] == dict.fromkeys(SCHED_PHASES, 0.0) | {
        "admit": 3.0, "idle": 4.0, "decode_dispatch": 0.25, "emit": 1.0,
        "other": 0.625}
    assert s["loop_s"] == sum(s["phase_s"].values()) == 8.875
    assert s["iterations"] == 2 and s["kv_live_token_steps"] == 40
    assert s["phase_n"] == dict.fromkeys(SCHED_PHASES, 0) | {
        "admit": 1, "idle": 1, "decode_dispatch": 1, "emit": 1}
    # the iteration that parked is not among the slowest; the other is
    assert s["slowest"] == [[107.75, 1.125, 1,
                             {"emit": 1.0, "other": 0.125}]]


def test_slowest_keeps_the_eight_longest_non_idle_iterations():
    clk = _Clock()
    acct = SchedulerAccount(clock=clk)
    acct.start()
    for i in (5, 11, 0, 7, 3, 9, 1, 10, 4, 8, 2, 6):
        with acct.phase("emit"):
            clk.sleep(1.0 + i)
        acct.tick(i)
    slow = acct.snapshot()["slowest"]
    assert [e[2] for e in slow] == [11, 10, 9, 8, 7, 6, 5, 4]
    assert [e[1] for e in slow] == [12.0, 11.0, 10.0, 9.0, 8.0, 7.0, 6.0,
                                    5.0]


def test_head_blocked_charges_the_time_until_the_next_pass():
    clk = _Clock()
    acct = SchedulerAccount(clock=clk)
    acct.start()
    acct.head_blocked("blocks")
    clk.sleep(3.0)
    acct.head_blocked("slots")
    clk.sleep(2.0)
    acct.head_blocked(None)
    clk.sleep(7.0)
    acct.head_blocked(None)
    assert acct.snapshot()["head_blocked_s"] == {"blocks": 0.0,
                                                 "slots": 0.0}
    acct.tick(0)                # committed with the iteration
    assert acct.snapshot()["head_blocked_s"] == {"blocks": 3.0,
                                                 "slots": 2.0}


def test_blocks_attended_and_spanned_commit_with_the_iteration():
    acct = SchedulerAccount(clock=_Clock())
    acct.start()
    acct.step_dispatched(7, 64)
    acct.step_dispatched(9, 64)
    s = acct.snapshot()
    assert s["kv_blocks_attended"] == s["kv_blocks_spanned"] == 0
    acct.tick(2)
    s = acct.snapshot()
    assert (s["kv_blocks_attended"], s["kv_blocks_spanned"]) == (16, 128)


# -- the engine's loop -----------------------------------------------------
@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "synchronous"])
def test_blocks_attended_and_spanned_follow_the_dispatched_lengths(
        lm, pipeline):
    """One request at a time, so every step's lengths are known: a
    prompt of P tokens decodes at lengths P + 1, P + 2, ... (the first
    token comes from the prefill). Block 8, 4 slots, a table 4 wide."""
    eng = _engine(lm, decode_pipeline=pipeline)
    S, B, Bs = 4, 32 // 8, 8
    want = steps = 0
    try:
        for P, M in ((5, 6), (8, 9), (15, 2), (1, 12)):
            before = eng.stats()["decode_steps"]
            out = eng.generate(list(range(1, P + 1)), max_tokens=M,
                               temperature=0.0)
            assert len(out["tokens"]) == M
            s = _settled(eng)
            n = s["decode_steps"] - before      # steps this request ran
            assert M - 1 <= n <= M              # the pipeline may run one on
            want += sum(-(-(P + 1 + k) // Bs) for k in range(n))
            steps += n
    finally:
        eng.stop()
    sc = s["scheduler"]
    assert sc["phase_n"]["decode_dispatch"] == steps
    assert sc["kv_blocks_spanned"] == steps * S * B
    assert sc["kv_blocks_attended"] == want


def test_slot_backend_spans_and_attends_no_pool_block(lm):
    eng = GenerationEngine(lm, num_slots=4, max_queue=64,
                           min_prompt_bucket=4, cache="slots")
    eng.warmup()
    try:
        _burst(eng, n=2, max_tokens=4)
        sc = _settled(eng)["scheduler"]
    finally:
        eng.stop()
    assert sc["phase_n"]["decode_dispatch"] > 0
    assert sc["kv_blocks_attended"] == sc["kv_blocks_spanned"] == 0


def test_account_partitions_the_loop_of_a_mixed_run(lm):
    eng = _engine(lm)
    try:
        outs = _burst(eng, n=7, max_tokens=6)
        assert all(len(o["tokens"]) == 6 for o in outs)
        s = _settled(eng)
    finally:
        eng.stop()
    sc = s["scheduler"]
    assert sum(sc["phase_s"].values()) == pytest.approx(sc["loop_s"],
                                                        abs=1e-6)
    assert sc["iterations"] > 0 and sc["loop_s"] > 0
    n = sc["phase_n"]
    assert n["decode_dispatch"] == s["decode_steps"] > 0
    assert n["decode_wait"] == n["decode_dispatch"]
    assert n["chunk_dispatch"] == n["chunk_wait"] \
        == s["paged"]["prefill_chunks"] >= 7 + 3
    assert n["admit"] >= sc["iterations"] and n["fault"] == 0
    assert sc["phase_s"]["fault"] == 0.0
    for phase in ("admit", "chunk_dispatch", "chunk_wait",
                  "decode_dispatch", "decode_wait", "emit", "idle"):
        assert sc["phase_s"][phase] > 0, phase
    # live KV integrated over steps: between one token and the whole
    # pool at every collected step
    pool = s["paged"]["blocks_total"] * s["paged"]["block_size"]
    assert 0 < sc["kv_live_token_steps"] <= n["decode_wait"] * pool
    assert 1 <= len(sc["slowest"]) <= 8
    for t0, secs, step, phases in sc["slowest"]:
        assert sum(phases.values()) == pytest.approx(secs, abs=1e-9)
        assert 0 <= step <= s["decode_steps"] and "idle" not in phases


def test_synchronous_and_slot_backends_keep_the_same_account(lm):
    for kw in (dict(decode_pipeline=False), dict(cache="slots")):
        if kw.get("cache") == "slots":
            eng = GenerationEngine(lm, num_slots=4, max_queue=64,
                                   min_prompt_bucket=4, **kw)
            eng.warmup()
        else:
            eng = _engine(lm, **kw)
        try:
            _burst(eng, n=5, max_tokens=5)
            s = _settled(eng)
        finally:
            eng.stop()
        sc = s["scheduler"]
        assert sum(sc["phase_s"].values()) == pytest.approx(
            sc["loop_s"], abs=1e-6), kw
        assert sc["phase_n"]["decode_dispatch"] == s["decode_steps"] > 0, kw
        assert sc["phase_n"]["chunk_dispatch"] == max(
            s["prefills"], (s["paged"] or {}).get("prefill_chunks", 0)), kw


@pytest.mark.parametrize("num_blocks,blocked", [(5, True), (33, False)])
def test_head_blocked_on_blocks_only_when_the_pool_is_too_small(
        lm, num_blocks, blocked):
    """Each request needs 3 blocks (8 prompt + 16 new tokens, block 8).
    A pool of 4 holds one and keeps the next at the head though three
    slots are free; a pool of 32 holds all four."""
    eng = _engine(lm, num_blocks=num_blocks)
    try:
        outs = _burst(eng, n=4, max_tokens=16, prompt=lambda i: 8)
        assert all(len(o["tokens"]) == 16 for o in outs)
        s = _settled(eng)
    finally:
        eng.stop()
    hb = s["scheduler"]["head_blocked_s"]
    assert hb["slots"] == 0.0
    if blocked:
        assert 0.0 < hb["blocks"] <= s["scheduler"]["loop_s"]
    else:
        assert hb["blocks"] == 0.0


def test_head_blocked_on_slots_when_none_is_free(lm):
    eng = _engine(lm, num_slots=1, num_blocks=33)
    try:
        _burst(eng, n=3, max_tokens=8, prompt=lambda i: 8)
        s = _settled(eng)
    finally:
        eng.stop()
    hb = s["scheduler"]["head_blocked_s"]
    assert hb["slots"] > 0.0 and hb["blocks"] == 0.0


def test_faults_are_charged_to_the_fault_phase(lm):
    from deeplearning4j_tpu.serving import FaultInjector
    eng = _engine(lm, fault_injector=FaultInjector(
        plan={"device_step": [2]}), retry_backoff_ms=5)
    try:
        _burst(eng, n=2, max_tokens=6)
        s = _settled(eng)
    finally:
        eng.stop()
    sc = s["scheduler"]
    assert s["faults"]["retries"] == sc["phase_n"]["fault"] == 1
    assert sc["phase_s"]["fault"] > 0
    assert sum(sc["phase_s"].values()) == pytest.approx(sc["loop_s"],
                                                        abs=1e-6)


# -- the same phases on the profiler's clock -------------------------------
def test_profiler_trace_holds_the_phases_on_the_schedulers_thread(
        lm, tmp_path):
    table = _tool("sched_trace_table")
    eng = _engine(lm)
    try:
        _burst(eng, n=2, max_tokens=3)          # everything has run once
        _settled(eng)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1              # what benchmark/run.py asks
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            steps0 = eng.stats()["decode_steps"]
            _burst(eng, n=3, max_tokens=8)
            _settled(eng)
            steps1 = eng.stats()["decode_steps"]
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    from benchmark import trace as trace_mod
    spans = table.read_spans(trace_mod.find_xplane(str(tmp_path)))
    names = {s["name"] for s in spans}
    assert {"gen.admit", "gen.chunk_dispatch", "gen.chunk_wait",
            "gen.decode_dispatch", "gen.decode_wait", "gen.emit",
            "gen.idle"} <= names
    assert names <= {"gen." + p for p in SCHED_PHASES}
    assert len({s["line"] for s in spans}) == 1      # one thread's line
    disp = [int(s["step"]) for s in spans
            if s["name"] == "gen.decode_dispatch"]
    wait = [int(s["step"]) for s in spans if s["name"] == "gen.decode_wait"]
    assert disp == list(range(steps0, steps1))       # rising by one
    assert wait == disp
    assert all(int(s["slots"]) >= 1 for s in spans
               if s["name"] == "gen.decode_dispatch")
    chunks = [int(s["chunk"]) for s in spans
              if s["name"] == "gen.chunk_dispatch"]
    assert chunks == list(range(chunks[0], chunks[0] + len(chunks)))
    # spans of one thread never overlap: a nested phase pauses its parent
    for a, b in zip(spans, spans[1:]):
        assert b["start_s"] >= a["end_s"] - 1e-9, (a, b)
    # the table matches a step's dispatch, wait and emit by ordinal
    rows = table.step_table(spans, runs=[])
    assert [r["step"] for r in rows] == disp
    assert all(r["dispatch_end"] <= r["wait_end"] <= r["emit_end"]
               for r in rows)


# -- module names the benchmark's metric files match -----------------------
def test_decode_and_chunk_programs_lower_as_jit_step_and_jit_chunk(
        lm, monkeypatch):
    """``benchmark/metrics/*.json`` find the decode and the chunk
    program in a device trace by these names: a rename has to fail
    here, not in a ledger line."""
    names = []
    real = generation_mod.compile_memoized

    def spy(fn, args, donate=()):
        text = jax.jit(fn, donate_argnums=tuple(donate)).lower(
            *args).as_text()
        names.append(re.search(r"module @(\w+)", text).group(1))
        return real(fn, args, donate)
    monkeypatch.setattr(generation_mod, "compile_memoized", spy)
    eng = GenerationEngine(lm, num_slots=2, max_queue=8,
                           min_prompt_bucket=4, cache="paged",
                           block_size=8, prefill_chunk_tokens=8)
    try:
        eng._get_decode_exe()
        eng._get_chunk_exe(8, 4)
    finally:
        eng.stop()
    assert names == ["jit_step", "jit_chunk"]
    wanted = set()
    mdir = os.path.join(ROOT, "benchmark", "metrics")
    for f in os.listdir(mdir):
        with open(os.path.join(mdir, f)) as fh:
            rx = json.load(fh).get("args", {}).get("program")
        if rx:
            wanted.add(rx)
    assert wanted, "no metric file names a program any more"
    for rx in wanted:
        assert any(re.search(rx, n) for n in names), rx


# -- the HTTP tier's boundary ------------------------------------------------
def test_stream_write_is_counted_from_the_emit_stamp(lm):
    srv = InferenceServer(port=0)
    try:
        gen = srv.register_generator(
            "lm", lm, num_slots=2, max_queue=8, min_prompt_bucket=4,
            cache="paged", block_size=8, prefill_chunk_tokens=8)
        gen.warmup()
        body = json.dumps({"prompt": [1, 2, 3, 4, 5], "max_tokens": 7,
                           "temperature": 0.0, "stream": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/models/lm/generate", data=body,
            headers={"Content-Type": "application/json"})
        lines = [json.loads(x) for x in
                 urllib.request.urlopen(req, timeout=60).read().splitlines()]
        assert [x["index"] for x in lines[:-1]] == list(range(7))
        assert lines[-1]["done"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/stats", timeout=10) as r:
            stats = json.loads(r.read())
        st = stats["models"]["lm"]["stream"]
        assert st["chunks"] == 7                 # tokens, not the done line
        assert 0 < st["delay_max_s"] <= st["delay_s"] < 60
        assert "scheduler" in stats["models"]["lm"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("dl4j_model_scheduler_loop_s",
                    "dl4j_model_scheduler_iterations_total",
                    "dl4j_model_scheduler_phase_s_decode_wait",
                    "dl4j_model_scheduler_head_blocked_s_blocks",
                    "dl4j_model_scheduler_kv_live_token_steps_total",
                    "dl4j_model_scheduler_kv_blocks_attended_total",
                    "dl4j_model_scheduler_kv_blocks_spanned_total",
                    "dl4j_model_stream_chunks_total",
                    "dl4j_model_stream_delay_s"):
            assert re.search(rf"^{fam}\{{", text, re.M), fam
    finally:
        srv.stop()
    assert HTTP_WRITE_SPAN == "http.stream_write"


# -- what was repaired in the tracing that was there ----------------------------
def test_step_pipeline_span_reads_the_account(lm):
    from deeplearning4j_tpu.tracing import Tracer
    tracer = Tracer(enabled=True)
    eng = _engine(lm)
    try:
        tr = tracer.begin("r1")
        eng.generate(list(range(1, 9)), max_tokens=10, temperature=0.0,
                     trace=tr)
        tracer.finish(tr)
    finally:
        eng.stop()
    dumps = tracer.dump()
    span = [s for s in dumps[0]["spans"] if s["kind"] == "step_pipeline"]
    assert len(span) == 1
    a = span[0]["attrs"]
    assert set(a) == {"wall_ms", "sync_wait_ms", "overlap_frac"}
    # blocked time is part of the wall, so the overlap is a share
    assert 0 <= a["sync_wait_ms"] <= a["wall_ms"]
    assert a["overlap_frac"] == pytest.approx(
        1.0 - a["sync_wait_ms"] / a["wall_ms"], abs=1e-3)
    rep = _tool("trace_report").step_pipeline(dumps)
    assert rep["requests"] == 1
    assert rep["wall_ms"] == pytest.approx(a["wall_ms"], abs=1e-3)
    assert rep["overlap_frac"] == pytest.approx(a["overlap_frac"], abs=1e-3)


def test_generation_sections_reach_stats(lm):
    from deeplearning4j_tpu.serving.metrics import profiler_sections
    prof = OpProfiler.get_instance()
    mode = prof.mode
    prof.set_mode(ProfilingMode.OPERATIONS)
    try:
        eng = _engine(lm)
        try:
            _burst(eng, n=2, max_tokens=4)
            steps = _settled(eng)["decode_steps"]
        finally:
            eng.stop()
        sec = profiler_sections()
    finally:
        prof.set_mode(mode)
    assert sec["generation.decode_step"]["count"] >= steps
    assert sec["generation.prefill"]["count"] >= 2
    assert "blocks" not in OpProfiler.record.__doc__.lower()
