"""The serve_closed_loop runner end to end through run.py's
``run_cell`` at tiny size on the CPU (Pallas in interpret mode), the
control, and the fault that ``correct`` has to catch."""
import os

import numpy as np
import pytest

import benchmark_testlib as lib
from benchmark import run
from benchmark.kinds import serve_closed_loop as k


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def decode_runs(root):
    return [run.run_cell("tiny-lm.decode", seed, 1.5, False,
                         require_chip=False, root=root)
            for seed in (3, 2**31 + 11)]


def test_last_line_keys_and_metrics(decode_runs):
    out, obs = decode_runs[0]
    assert lib.RESULT_KEYS <= set(out)
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"gen_tokens_per_s", "itl_ms_p95", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    real = lib.load(lib.BENCH, "traffic", "decode_backlog.json")["limits"]
    assert out["compared"][lib.COMPARED]["limit"] == real[lib.COMPARED]
    assert out["compared"][lib.COMPARED]["value"] <= 0.01     # f32 on the CPU
    assert out["compared"]["tokens_checked"]["value"] > 0
    assert all(v == 0 for v in out["failures"].values())
    # a stalled process wakes late; one that runs wakes within the pause
    assert 0 <= out["longest_oversleep_s"] < out["longest_pause_s"] + 0.05


def test_two_seeds_send_the_same_lengths_and_differ_in_ids(decode_runs):
    (_, a), (_, b) = decode_runs
    by_k = lambda obs: {r["k"]: (r["prompt_len"], r["max_tokens"])  # noqa: E731
                        for r in obs["requests"]}
    ka, kb = by_k(a), by_k(b)
    common = sorted(set(ka) & set(kb))
    assert len(common) >= 16
    assert [ka[i] for i in common] == [kb[i] for i in common]
    assert a["traffic"]["clients"] == b["traffic"]["clients"] == 8
    pa = {r["k"]: r["prompt"] for r in a["requests"] if r["prompt"]}
    pb = {r["k"]: r["prompt"] for r in b["requests"] if r["prompt"]}
    assert pa[0] != pb[0]
    for obs in (a, b):      # every finished request ran to max_tokens
        assert obs["finished"] and all(
            len(r["tokens"]) == r["max_tokens"]
            and r["finish_reason"] == "length" for r in obs["finished"])


def test_window_edges_sit_on_token_arrivals(decode_runs):
    _, obs = decode_runs[0]
    t0, t1 = obs["window"]["span"]
    stamps = sorted(t for r in obs["requests"] for t in r["token_times"])
    assert t0 in stamps and t1 in stamps and t1 > t0
    assert obs["window"]["work"]["tokens"] == sum(t0 < t <= t1 for t in stamps)


def test_close_stamp_leaves_out_the_burst_the_deadline_cuts():
    bursts = np.array([0.0, 0.001, 0.5, 0.501, 1.0, 1.001, 1.5, 1.502])
    # deadline inside the last burst: back to the burst end before it
    assert k.close_stamp(bursts, 0.001, 1.501, 0.025, 1.0) == 1.001
    # deadline between bursts: the last arrival before it
    assert k.close_stamp(bursts, 0.001, 1.2, 0.025, 1.0) == 1.001
    # tokens that never pause: any arrival will do
    flow = np.arange(0, 2, 0.01)
    assert k.close_stamp(flow, 0.0, 1.505, 0.025, 1.0) == pytest.approx(1.5)
    assert list(k.burst_ends(bursts, 0.025)) == [0.001, 0.501, 1.001, 1.502]


def test_oversleep_probe_keeps_the_latest_wake_up_inside_the_span():
    import time
    probe = k.Oversleep(period_s=0.002)
    probe.start()
    time.sleep(0.1)
    probe.done.set()
    probe.join(5)
    assert not probe.is_alive() and len(probe.wakes) == len(probe.late) > 5
    assert probe.longest(0.0, float("inf")) == max(probe.late)
    probe.wakes, probe.late = [1.0, 2.0, 3.0, 4.0], [0.001, 2.5, 0.002, 0.7]
    assert probe.longest(2.0, 4.0) == 0.7       # (t0, t1]: 2.0 is outside
    assert probe.longest(0.0, 3.0) == 2.5
    assert probe.longest(10.0, 11.0) == 0.0


def test_scoring_traced_run_reports_per_layer_metrics(root):
    out, obs = run.run_cell("tiny-lm.score", 5, 1.5, True,
                            require_chip=False, root=root)
    assert lib.RESULT_KEYS <= set(out) and out["correct"] is True
    # the CPU has no device plane: trace readers return nothing and
    # their metrics are left out; client and counter readers read
    assert {"ttft_ms_p50", "prefill_mfu"} <= set(out["metrics"])
    assert "prefill_chunk_device_ms" not in out["metrics"]
    assert all(r["max_tokens"] == 1 for r in obs["requests"])
    assert obs["stats"]["close"]["paged"]["prefill_chunks"] > \
        obs["stats"]["open"]["paged"]["prefill_chunks"]


def test_control_run_ends_not_correct_through_the_cells_own_limit(root):
    """``--control 1``: the reference in the nearest lower precision,
    put in the program's place, goes through the same comparison and
    limit as the program and fails it. The cell comes as new files: the
    decode mix with every finished request checked, as the real cell
    checks all of its own (~1,500 served tokens)."""
    here = os.path.join(root, "benchmark")
    t = lib.load(here, "traffic", "tiny_decode.json")
    t["check_requests"] = 400
    lib.dump(t, here, "traffic", "tiny_decode_all.json")
    spec = run.load_spec(root)
    name = "tiny-lm.decode_all"
    spec["workloads"].append({"name": name, "config": "tiny-lm", "chips": 1,
                              "traffic": "tiny_decode_all", "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-lm.decode" in m.get("workloads", []):
            m["workloads"].append(name)
    lib.dump(spec, root, "BENCHMARK.json")
    out, obs = run.run_cell(name, 4, 1.5, False, require_chip=False,
                            control=True, root=root)
    assert out["control"] == "bfloat16" and out["correct"] is False
    c = out["compared"]
    limit = obs["traffic"]["limits"][lib.COMPARED]
    assert c[lib.COMPARED] == {"value": 1.0, "limit": limit} and limit < 1
    assert c["served_logit_gap_mean"]["value"] > 0      # bf16 flips tokens
    # the program's own reading rides beside it, under no limit
    assert c["program_" + lib.COMPARED]["value"] <= limit
    assert c["program_" + lib.COMPARED]["limit"] is None
    assert c["tokens_checked"]["value"] > 1000


def test_a_limit_on_a_number_the_check_does_not_read_is_an_error(root):
    here = os.path.join(root, "benchmark")
    t = lib.load(here, "traffic", "tiny_decode.json")
    t["limits"] = {"served_logit_gap_typo": 1.0}
    lib.dump(t, here, "traffic", "tiny_decode.json")
    try:
        with pytest.raises(KeyError, match="served_logit_gap_typo"):
            run.run_cell("tiny-lm.decode", 3, 1.0, False,
                         require_chip=False, root=root)
    finally:
        t["limits"] = lib.load(lib.BENCH, "traffic",
                               "decode_backlog.json")["limits"]
        lib.dump(t, here, "traffic", "tiny_decode.json")


def test_gaps_are_read_at_the_served_positions_for_program_and_control():
    from benchmark.reference import gpt2
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 211, 24).tolist(), "prompt_len": 24,
               "tokens": rng.integers(0, 211, 40).tolist(), "k": i}
              for i in range(12)]
    # the control need not decode: at each served position of the same
    # prompts and tokens, the gap of the token the lower precision puts
    # first; random "served" tokens lie far below the best
    served, control = k.served_gaps(gpt2, lib.TINY_LM, 9, sample, "bfloat16")
    assert [len(a) for a in served] == [len(a) for a in control] == [40] * 12
    flat, ctl = np.concatenate(served), np.concatenate(control)
    assert (flat >= 0).all() and (ctl >= 0).all() and flat.mean() > 100 * ctl.mean()
    got = k.readings(flat, ctl)
    assert got["tokens_checked"] == 480
    assert got["served_gap_over_control"] == pytest.approx(
        flat.mean() / max(ctl.mean(), 1e-12))
    assert k.readings(ctl, ctl)["served_gap_over_control"] in (0.0, 1.0)
    assert k.readings(np.zeros(5), np.zeros(5))["served_gap_over_control"] == 0.0


@pytest.mark.parametrize("cell,method", [
    ("tiny-lm.decode", "forward_decode_paged"),
    ("tiny-lm.score", "forward_prefill_chunk")])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch, cell, method):
    """The rest of a run with the timed path broken underneath: the
    served model's logits are rolled by one token id."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
    real = getattr(CausalTransformerLM, method)

    def broken(self, *a, **kw):
        logits, ks, vs = real(self, *a, **kw)
        return jnp.roll(logits, 1, axis=-1), ks, vs

    monkeypatch.setattr(CausalTransformerLM, method, broken)
    out, _ = run.run_cell(cell, 4, 1.5, False, require_chip=False, root=root)
    assert out["correct"] is False
    c = out["compared"][lib.COMPARED]
    assert c["value"] > c["limit"]


def test_no_accelerator_is_exit_code_2_and_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.require_chips(1)
    assert e.value.code == 2
    assert capsys.readouterr().out == ""
