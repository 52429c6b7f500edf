"""Runner for traffic of kind ``serve_closed_loop``: a fixed number of
clients over the served LM, each sending its next streamed request when
its last one ends.

The traffic file is data: the client count, a stored list of
(prompt length, max_tokens) pairs that request k takes entry
k mod len of, the lead-in, and how many finished requests the
reference checks. ``--seed`` makes the weights and the prompts' token
ids and nothing else, so every seed sends the same sequence of lengths.

Window edges sit on token arrivals at the client: the window opens at
the last token of a burst (one decode step delivers a token to every
live request within a few milliseconds) once the lead-in is over, and
closes at the last burst end before the deadline. Rates count every
token strictly after the opening stamp over the time between the two
stamps; tails are over all requests of the window.
"""
from __future__ import annotations

import http.client
import importlib
import json
import socket
import threading
import time
from typing import Any, Dict, List

import numpy as np

ROUTE = "/v1/models/lm/generate"


# -- the system under test ------------------------------------------------
def build_server(config: dict, seed: int):
    """The program's own entry points: InferenceServer +
    register_generator + warmup, with the benchmark's weights."""
    import jax
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    m = config["model"]
    lm = CausalTransformerLM(
        vocab_size=m["vocab_size"], d_model=m["d_model"],
        n_layers=m["n_layers"], n_heads=m["n_heads"], d_ff=m["d_ff"],
        max_seq_len=m["max_seq_len"], eos_id=config.get("eos_id"), seed=0)
    emb, blocks = ref.make_params(m, seed)
    lm._params = {
        "tok": emb["tok"], "pos": emb["pos"], "lnf_g": emb["lnf_g"],
        "lnf_b": emb["lnf_b"], "head": emb["head"],
        "blocks": [{"attn_Wq": b["wq"], "attn_Wk": b["wk"],
                    "attn_Wv": b["wv"], "attn_Wo": b["wo"],
                    "attn_b": b["bo"], "ln1_g": b["ln1_g"],
                    "ln1_b": b["ln1_b"], "ln2_g": b["ln2_g"],
                    "ln2_b": b["ln2_b"], "W1": b["w1"], "b1": b["b1"],
                    "W2": b["w2"], "b2": b["b2"]} for b in blocks]}
    jax.block_until_ready(lm._params)
    srv = InferenceServer(port=0)
    gen = srv.register_generator("lm", lm, **config["engine"])
    gen.warmup(**config.get("warmup", {}))
    return srv, gen


def get_stats(port: int) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", "/stats")
        return json.loads(conn.getresponse().read())["models"]["lm"]
    finally:
        conn.close()


# -- traffic ----------------------------------------------------------------
def request_lengths(traffic: dict, k: int):
    pairs = traffic["lengths"]
    p, mt = pairs[k % len(pairs)]
    return int(p), int(mt)


def prompt_ids(seed: int, k: int, n: int, vocab: int) -> List[int]:
    """Uniform over the vocabulary: no accidental shared prefix."""
    return np.random.default_rng([int(seed), int(k)]).integers(
        0, vocab, n).tolist()


class Load:
    """The closed loop. All stamps are ``time.perf_counter()`` at the
    client, taken when a streamed line has been read."""

    def __init__(self, port: int, traffic: dict, seed: int, vocab: int):
        self.port, self.traffic, self.seed, self.vocab = (
            port, traffic, seed, vocab)
        self.lock = threading.Lock()
        self.next_k = 0
        self.requests: List[Dict[str, Any]] = []
        self.n_tokens = 0
        self.n_finished = 0
        self.last_stamp = 0.0
        self.no_new = False
        self.conns: Dict[int, http.client.HTTPConnection] = {}
        self.threads: List[threading.Thread] = []

    def start(self):
        for i in range(int(self.traffic["clients"])):
            t = threading.Thread(target=self._client, args=(i,),
                                 daemon=True, name=f"client-{i}")
            self.threads.append(t)
            t.start()
            time.sleep(self.traffic.get("stagger_ms", 20) / 1e3)

    def _client(self, i: int):
        while True:
            with self.lock:
                if self.no_new:
                    return
                k = self.next_k
                self.next_k += 1
                plen, mt = request_lengths(self.traffic, k)
                rec = {"k": k, "prompt_len": plen, "max_tokens": mt,
                       "prompt": None, "tokens": [], "token_times": [],
                       "t_send": None, "t_done": None, "status": None,
                       "finish_reason": None, "error": None}
                self.requests.append(rec)
            rec["prompt"] = prompt_ids(self.seed, k, plen, self.vocab)
            body = json.dumps({
                "prompt": rec["prompt"], "max_tokens": mt,
                "temperature": 0.0, "stream": True,
                "timeout_ms": self.traffic["timeout_ms"]}).encode()
            conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                              timeout=900)
            self.conns[i] = conn
            try:
                rec["t_send"] = time.perf_counter()
                conn.request("POST", ROUTE, body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                rec["status"] = resp.status
                if resp.status != 200:
                    rec["error"] = resp.read().decode()[:200]
                    continue
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    now = time.perf_counter()
                    item = json.loads(line)
                    if "token" in item:
                        rec["tokens"].append(int(item["token"]))
                        rec["token_times"].append(now)
                        with self.lock:
                            self.n_tokens += 1
                            self.last_stamp = now
                    elif item.get("done"):
                        rec["finish_reason"] = item.get("finish_reason")
                        rec["t_done"] = now
                        with self.lock:
                            self.n_finished += 1
                    elif "error" in item:
                        rec["error"] = str(item)[:200]
            except (OSError, http.client.HTTPException, ValueError) as e:
                if not self.no_new:
                    rec["error"] = repr(e)[:200]
            finally:
                conn.close()
            if rec["error"] and not self.no_new:
                time.sleep(0.05)   # a failing server must not spin us

    def stop(self):
        """No new requests; cut the streams still open."""
        self.no_new = True
        for conn in list(self.conns.values()):
            sock = conn.sock
            if sock is not None:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
        for t in self.threads:
            t.join(30)


class Oversleep(threading.Thread):
    """Sleeps 10 ms at a time and keeps how late each wake-up came. A
    process that the host stalls wakes late here too; an engine that
    waits on the device does not: it tells the two apart when a run
    pauses (``longest_pause_s``)."""

    def __init__(self, period_s: float = 0.01):
        super().__init__(daemon=True, name="oversleep")
        self.period_s = period_s
        self.wakes: List[float] = []
        self.late: List[float] = []
        self.done = threading.Event()

    def run(self):
        while not self.done.is_set():
            t0 = time.perf_counter()
            time.sleep(self.period_s)
            now = time.perf_counter()
            self.wakes.append(now)
            self.late.append(now - t0 - self.period_s)

    def longest(self, t0: float, t1: float) -> float:
        """The latest wake-up among those inside ``(t0, t1]``."""
        inside = [d for t, d in zip(self.wakes, self.late) if t0 < t <= t1]
        return max(inside, default=0.0)


def burst_ends(stamps: np.ndarray, gap_s: float) -> np.ndarray:
    """Stamps after which no token arrived for ``gap_s``: the last
    token of each burst (the final stamp counts as one)."""
    if len(stamps) == 0:
        return stamps
    nxt = np.append(stamps[1:], np.inf)
    return stamps[(nxt - stamps) > gap_s]


def close_stamp(stamps: np.ndarray, t_open: float, deadline: float,
                gap_s: float, backoff_s: float) -> float:
    """The last token arrival before the deadline that ends a burst.
    A burst the deadline cuts through is left out whole, back to the
    burst end before it, unless that lies more than ``backoff_s``
    back (tokens that never pause have no bursts: any arrival will
    do)."""
    inside = stamps[(stamps > t_open) & (stamps <= deadline)]
    if not len(inside):
        return t_open
    last = float(inside[-1])
    after = stamps[stamps > deadline]
    if not len(after) or after[0] - last > gap_s:
        return last
    ends = burst_ends(inside, gap_s)[:-1]    # the final stamp is cut
    if len(ends) and deadline - ends[-1] <= backoff_s:
        return float(ends[-1])
    return last


# -- the run ------------------------------------------------------------------
def run(ctx) -> dict:
    srv, gen = build_server(ctx.config, ctx.seed)
    try:
        obs = _drive(ctx, srv, gen)
    finally:
        srv.stop()
    obs["memory_peak_bytes"] = ctx.memory_peak()
    # free the program's weights and pools before the reference runs
    gen.engine.model._params = None
    del srv, gen
    ctx.free()
    obs["checks"] = check(ctx, obs)
    return obs


def _drive(ctx, srv, gen) -> dict:
    traffic, seed = ctx.traffic, ctx.seed
    port = srv.port
    compiles0 = gen.metrics.compiles
    load = Load(port, traffic, seed, ctx.config["model"]["vocab_size"])
    load.start()
    probe = Oversleep()
    probe.start()
    lead = traffic["lead_in"]
    gap = traffic["burst_gap_ms"] / 1e3
    t_give_up = time.perf_counter() + lead.get("give_up_s", 240)
    t_ready = None
    while True:
        time.sleep(0.004)
        with load.lock:
            ready = (load.n_finished >= lead["finished_requests"]
                     and load.n_tokens >= lead["tokens"])
            last = load.last_stamp
        now = time.perf_counter()
        if ready and t_ready is None:
            t_ready = now
        # a burst end; where tokens never pause (steps shorter than
        # the gap), any token arrival will do
        if ready and (now - last > gap or
                      now - t_ready > traffic.get("open_wait_s", 3)):
            t_open = last
            break
        if now > t_give_up:
            load.stop()
            probe.done.set()
            raise RuntimeError(
                f"lead-in not reached: {load.n_finished} finished, "
                f"{load.n_tokens} tokens")
    ctx.window_opens()
    stats_open = get_stats(port)
    deadline = t_open + ctx.seconds
    if ctx.trace:
        ctx.trace_start()
        time.sleep(traffic.get("trace_seconds", 5))
        ctx.trace_stop()
    time.sleep(max(0.0, deadline + 2 * gap - time.perf_counter()))
    stats_close = get_stats(port)
    if traffic.get("wait_first_tokens"):
        load.no_new = True
        t_end = time.perf_counter() + 60
        while time.perf_counter() < t_end and any(
                r["t_send"] is not None and r["t_send"] <= deadline
                and not r["token_times"] and not r["error"]
                for r in load.requests):
            time.sleep(0.01)
    load.stop()
    probe.done.set()
    probe.join(5)

    stamps = np.sort(np.asarray(
        [t for r in load.requests for t in r["token_times"]]))
    t_close = close_stamp(stamps, t_open, deadline, gap,
                          traffic.get("close_backoff_s", 1.0))
    span = (t_open, t_close)
    in_win = [t for t in stamps if t_open < t <= t_close]
    sent = [r for r in load.requests
            if r["t_send"] is not None and t_open <= r["t_send"] <= t_close]
    finished = [r for r in load.requests
                if r["t_done"] is not None and t_open < r["t_done"] <= t_close]
    failed = [r for r in load.requests if r["error"] or (
        r["t_done"] is not None and (
            len(r["tokens"]) != r["max_tokens"]
            or r["finish_reason"] != "length"))]
    delta = _engine_faults(stats_open, stats_close)
    delta["compiles_after_warmup"] = (
        stats_close["compile_cache"]["compiles"] - compiles0)
    return {
        "requests": load.requests,
        "window": {"span": span, "seconds": t_close - t_open,
                   "work": {"tokens": len(in_win),
                            "requests": len(finished)},
                   # a stall shows here even where a percentile hides it
                   "longest_pause_s": float(np.max(np.diff(
                       [t_open] + in_win))) if in_win else 0.0,
                   "longest_oversleep_s": probe.longest(t_open, t_close)},
        "attempted": len(sent) + len([r for r in failed if r not in sent]),
        "failed": len(failed) + sum(delta.values()),
        "failures": dict(delta, bad_requests=len(failed)),
        "stats": {"open": stats_open, "close": stats_close},
        "finished": finished, "sent": sent,
    }


def _engine_faults(a: dict, b: dict) -> Dict[str, int]:
    """What must stay at zero inside a window, as deltas of /stats."""
    out = {}
    for key in ("shed", "shed_batch", "shed_deadline", "timeouts",
                "server_errors"):
        out[key] = b[key] - a[key]
    for key in ("retries", "recoveries", "quarantined"):
        out[key] = b["faults"][key] - a["faults"][key]
    return out


# -- correct ---------------------------------------------------------------------
def check_sample(traffic: dict, finished: List[dict], seed: int
                 ) -> List[dict]:
    """The finished requests the reference follows: the one with the
    most served tokens (ties: the longest prompt), then a draw from
    the seed."""
    n = int(traffic["check_requests"])
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r["tokens"]),
                                            -r["prompt_len"], r["k"]))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


ROWS = 128      # rows of logits on the device at a time


def _row_chunks(n: int):
    for r0 in range(0, n, ROWS):
        idx = np.arange(r0, r0 + ROWS)
        yield r0, min(n, r0 + ROWS), np.minimum(idx, n - 1)


def served_gaps(ref_module, model_cfg: dict, seed: int,
                sample: List[dict], control_dtype: str):
    """For each request of the sample, two arrays over its served
    positions: how far the served token's logit lies below the
    reference's best there, and the same for the token the control
    puts first there.

    The control is the reference computed in ``control_dtype``, the
    nearest precision below the configuration's, put in the program's
    place. It need not decode: it reads the same prompts and served
    tokens and names its first choice at each served position, so the
    two arrays are the same statistic of the same positions."""
    import jax
    import jax.numpy as jnp
    seqs = [np.asarray(r["prompt"] + r["tokens"], np.int32) for r in sample]
    hid, emb = ref_module.final_hidden(model_cfg, seed, seqs)
    low, _ = ref_module.final_hidden(model_cfg, seed, seqs,
                                     dtype=control_dtype)
    prec = ref_module.precision_for

    @jax.jit
    def gaps(emb, rows, low_rows, toks):
        with jax.default_matmul_precision(prec(None)):
            lg = ref_module.head_logits(emb, rows)
        with jax.default_matmul_precision(prec(control_dtype)):
            first = ref_module.head_logits(emb, low_rows,
                                           control_dtype).argmax(-1)
        below = lambda t: lg.max(-1) - jnp.take_along_axis(  # noqa: E731
            lg, t[:, None], 1)[:, 0]
        return below(toks), below(first)

    served, control = [], []
    for i, r in enumerate(sample):
        first = r["prompt_len"] - 1      # row that predicts token 0
        toks = np.asarray(r["tokens"], np.int32)
        out = np.zeros((2, len(toks)))
        for r0, r1, idx in _row_chunks(len(toks)):
            got = gaps(emb, hid[i][first + idx], low[i][first + idx],
                       jnp.asarray(toks[idx]))
            out[:, r0:r1] = np.asarray(got)[:, :r1 - r0]
        served.append(np.maximum(out[0], 0.0))
        control.append(np.maximum(out[1], 0.0))
    return served, control


def readings(gaps: np.ndarray, control: np.ndarray) -> Dict[str, float]:
    """The numbers of one comparison. ``served_gap_over_control`` is
    the one held to a limit: the mean gap of the served tokens as a
    share of the control's mean gap at the same positions. Both rise
    and fall together with how many near-ties a seed's weights put in
    the way, which an absolute gap does not survive from seed to seed
    (PERF.md section 6)."""
    return {"tokens_checked": len(gaps),
            "greedy_agree": int((gaps == 0).sum()),
            "served_logit_gap": float(gaps.max()),
            "served_logit_gap_mean": float(gaps.mean()),
            "control_logit_gap_mean": float(control.mean()),
            "served_gap_over_control":
                float(gaps.mean() / max(control.mean(), 1e-12))}


def check(ctx, obs: dict) -> List[list]:
    """[name, value, limit] for each number compared.

    In a control run (``run.py --control 1``) the control's gaps take
    the program's place, so the run has to end ``correct: false``; the
    program's own readings are printed beside them as ``program_*``."""
    ref = importlib.import_module(
        "benchmark.reference." + ctx.config["reference"])
    sample = check_sample(ctx.traffic, obs["finished"], ctx.seed)
    limits = ctx.traffic["limits"]
    checks = [["requests_checked", len(sample), None]]
    if not sample:
        return checks + [[k, 1e30, v] for k, v in limits.items()]  # nothing to judge by
    t0 = time.perf_counter()
    served, control = served_gaps(ref, ctx.config["model"], ctx.seed,
                                  sample, ctx.config["control_dtype"])
    flat, ctl = np.concatenate(served), np.concatenate(control)
    got = readings(flat, ctl)
    if ctx.control:
        own = got
        got = readings(ctl, ctl)
        got.update(("program_" + k, own[k]) for k in (
            "served_logit_gap", "served_logit_gap_mean",
            "served_gap_over_control"))
        t_open = obs["window"]["span"][0]
        ctx.write_readings({      # every gap read, for setting limits
            "k": [r["k"] for r in sample],
            "prompt_len": [r["prompt_len"] for r in sample],
            "served": [[round(float(g), 6) for g in a] for a in served],
            "control": [[round(float(g), 6) for g in a] for a in control],
            "timeline": [[r["k"], r["t_send"] - t_open,
                          [t - t_open for t in r["token_times"]]]
                         for r in obs["requests"] if r["t_send"] is not None],
            "stats": obs["stats"]})
    got["check_seconds"] = time.perf_counter() - t0
    if set(limits) - set(got):      # a limit on nothing would never fail
        raise KeyError(f"limits on numbers this check does not read: "
                       f"{sorted(set(limits) - set(got))}")
    return checks + [[k, v, limits.get(k)] for k, v in got.items()]
