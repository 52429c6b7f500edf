"""Chip-independent performance audit (VERDICT r4 directive #2).

Compiles the flagship training steps on the CPU backend, extracts XLA cost analysis (flops / bytes accessed /
arithmetic intensity), predicts v5e step time from the roofline model,
and scans the optimized HLO for the classic TPU performance bugs:

- f32 dot/conv leaks in a bf16-mixed-precision program
- explicit transpose instructions (layout churn the compiler failed to
  fold into the surrounding ops)
- unfused elementwise chains (fusion count vs instruction count)
- all-reduce placement in the sharded program

Outputs PERF_AUDIT.md (committed) + tools/perf_audit.json. Run:

    JAX_PLATFORMS=cpu python tools/perf_audit.py

v5e peak numbers (public spec): 197 TFLOP/s bf16, 819 GB/s HBM.
Roofline: t >= max(flops / peak_flops, bytes / bw); MFU at the measured
step time = flops / (t * peak). The same numbers feed bench.py's
cost_model extras so the eventual on-chip measurement lands on a
pre-staged prediction.
"""
import json
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

V5E_BF16_FLOPS = 197e12
V5E_F32_FLOPS = 49e12   # no native f32 MXU path; ~1/4 bf16
V5E_HBM_BPS = 819e9


def _stablehlo_dtype_scan(txt: str) -> dict:
    """Dtype audit on the backend-INDEPENDENT lowering (StableHLO):
    the program as written, before any backend pass. This is where bf16
    leaks are visible — the CPU backend upcasts all bf16 compute to f32
    during ITS optimization, so the compiled-HLO dtype counts say
    nothing about what the TPU backend would run."""
    dots = re.findall(
        r"stablehlo\.(?:convolution|dot_general)[^\n]*->\s*"
        r"tensor<[^>]*x(\w+)>", txt)
    from collections import Counter
    c = Counter(dots)
    return {"dot_conv_total": sum(c.values()),
            "dot_conv_bf16": c.get("bf16", 0),
            "dot_conv_f32": c.get("f32", 0),
            "by_dtype": dict(c)}


def _stablehlo_dot_operand_scan(txt: str) -> dict:
    """OPERAND-dtype audit of StableHLO dots. The result-dtype scan
    above is the wrong lens for the quantized KV legs: their cache-side
    dots run bf16 OPERANDS with ``preferred_element_type=f32``, so the
    result tensor is f32 by design — what the MXU streams is the
    operand dtype. Counts (lhs, rhs) dtype pairs of every
    ``stablehlo.dot_general``."""
    pairs = re.findall(
        r"stablehlo\.dot(?:_general)?\b[^\n]*:\s*"
        r"\(tensor<[^>]*x(\w+)>,\s*tensor<[^>]*x(\w+)>\)", txt)
    from collections import Counter
    c = Counter(pairs)
    return {"dot_total": sum(c.values()),
            "dot_f32_operands": c.get(("f32", "f32"), 0),
            "dot_bf16_operands": c.get(("bf16", "bf16"), 0),
            "by_operands": {f"{a}x{b}": n for (a, b), n in c.items()}}


def audit_kv_quant():
    """ISSUE 15 satellite: StableHLO dot-dtype scan of the generation
    engine's decode / chunk-prefill / speculative-verify executables
    across kv_dtype legs. On the bf16/int8 legs every CACHE-side
    attention dot (QK and PV, 2 per layer) must run on bf16 operands —
    an f32-operand dot there means a dequantized cache round-tripped
    through HBM. Checked structurally: the quant leg's f32-operand dot
    count must equal the f32 baseline's minus exactly the attention
    dots that moved to bf16, and nothing else may move. Asserts in the
    returned dict (``unintended_f32_dots`` == 0 per executable) so the
    bench/CI caller can gate on it."""
    import jax
    from deeplearning4j_tpu.serving.generation import GenerationEngine
    from deeplearning4j_tpu.serving.paging import NULL_BLOCK
    from deeplearning4j_tpu.serving.speculative import make_verify_slots_fn
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

    NL, NH, C = 2, 4, 8

    def build(kv_dtype, cache="paged"):
        lm = CausalTransformerLM(vocab_size=64, d_model=32, n_layers=NL,
                                 n_heads=NH, max_seq_len=64,
                                 seed=0).init()
        kw = dict(num_slots=4, max_queue=32, prompt_buckets=[16],
                  kv_dtype=kv_dtype)
        if cache == "paged":
            kw.update(cache="paged", block_size=8,
                      prefill_chunk_tokens=16)
        return GenerationEngine(lm, **kw)

    def lower_decode(eng):
        S = eng.num_slots
        args = (eng.model._params, eng._kcs, eng._vcs,
                np.zeros(S, np.int32), np.zeros(S, np.int32),
                np.ones(S, bool), np.zeros(S, np.int32),
                np.full((S, eng._blocks_per_seq), NULL_BLOCK, np.int32),
                np.zeros(S, np.uint32), np.zeros(S, np.int32),
                np.zeros(S, np.float32), np.zeros(S, np.int32),
                np.full(S, -1, np.int32), np.zeros(S, np.int32))
        return jax.jit(eng._decode_fn(),
                       donate_argnums=eng._donate).lower(*args).as_text()

    def lower_chunk(eng, cb=16, tb=8):
        args = (eng.model._params, eng._kcs, eng._vcs,
                np.zeros((1, cb), np.int32), np.int32(0), np.int32(1),
                np.full(tb, NULL_BLOCK, np.int32),
                np.uint32(0), np.float32(0.0), np.int32(0))
        return jax.jit(eng._chunk_fn(),
                       donate_argnums=eng._donate).lower(*args).as_text()

    def lower_verify(eng):
        fn = make_verify_slots_fn(eng.model)
        args = (eng.model._params, eng._kcs, eng._vcs,
                np.zeros((1, C), np.int32), np.int32(0), np.int32(1),
                np.int32(0), np.uint32(0), np.int32(0),
                np.float32(0.0), np.int32(0))
        return jax.jit(fn,
                       donate_argnums=(1, 2)).lower(*args).as_text()

    legs = {}
    for dt in ("f32", "bf16", "int8"):
        eng = build(dt)
        slot_eng = build(dt, cache="slots")
        legs[dt] = {
            "decode": _stablehlo_dot_operand_scan(lower_decode(eng)),
            "prefill_chunk": _stablehlo_dot_operand_scan(
                lower_chunk(eng)),
            "verify": _stablehlo_dot_operand_scan(
                lower_verify(slot_eng)),
        }
        eng.stop()
        slot_eng.stop()

    # QK + PV per layer must move (and ONLY those) on the quant legs
    expect_moved = 2 * NL
    for dt in ("bf16", "int8"):
        for exe, scan in legs[dt].items():
            base = legs["f32"][exe]
            scan["unintended_f32_dots"] = (
                scan["dot_f32_operands"]
                - (base["dot_f32_operands"] - expect_moved))
            scan["attention_dots_bf16_ok"] = (
                scan["dot_bf16_operands"] == expect_moved)
    ok = all(s["unintended_f32_dots"] == 0 and s["attention_dots_bf16_ok"]
             for dt in ("bf16", "int8") for s in legs[dt].values())
    return {"n_layers": NL, "expected_moved_dots": expect_moved,
            "legs": legs, "ok": ok}


def _hlo_scan(txt: str) -> dict:
    """Count the performance-relevant instruction classes in optimized
    HLO text. CPU-backend HLO differs from TPU in fusion/layout detail
    (and upcasts bf16 compute), so these are structural indicators —
    the dtype truth lives in _stablehlo_dtype_scan."""
    lines = txt.splitlines()
    n_instr = sum(1 for l in lines if " = " in l)
    # HLO result types carry an optional layout suffix: `f32[1,2]{1,0}`
    f32_dots = len(re.findall(
        r"= f32\[[^\]]*\]\S* (?:dot|convolution)\(", txt))
    bf16_dots = len(re.findall(
        r"= bf16\[[^\]]*\]\S* (?:dot|convolution)\(", txt))
    all_dots = len(re.findall(
        r"= \w+\[[^\]]*\]\S* (?:dot|convolution)\(", txt))
    # CPU backend may route matmuls to oneDNN custom-calls
    onednn = len(re.findall(r"custom-call.*onednn.*matmul", txt,
                            re.IGNORECASE))
    transposes = len(re.findall(
        r"= \w+\[[^\]]*\]\S* transpose\(", txt))
    fusions = len(re.findall(r"\]\S* fusion\(", txt))
    allreduce = len(re.findall(r"all-reduce", txt))
    copies = len(re.findall(r"= \w+\[[^\]]*\]\S* copy\(", txt))
    return {"instructions": n_instr, "dot_conv_total": all_dots,
            "dot_conv_f32": f32_dots, "dot_conv_bf16": bf16_dots,
            "onednn_matmul_calls": onednn,
            "transposes": transposes, "fusions": fusions,
            "all_reduces": allreduce, "copies": copies}


def _cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    ca = ca or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    intensity = flops / byts if byts else None
    # flops are backend-independent (dot/conv math is the same program);
    # bytes-accessed reflects CPU layouts and CPU fusion decisions, so
    # it is an UPPER bound on TPU HBM traffic — report the compute
    # roofline as the headline prediction and the bytes-inclusive one
    # as the pessimistic bound
    t_compute = flops / V5E_BF16_FLOPS
    t_upper = max(t_compute, byts / V5E_HBM_BPS)
    return {"flops": flops, "bytes_accessed_cpu_upper_bound": byts,
            "arith_intensity_cpu": (round(intensity, 1)
                                    if intensity else None),
            "roofline_ms_v5e_bf16": round(t_compute * 1e3, 3),
            "roofline_ms_with_cpu_bytes": round(t_upper * 1e3, 3)}


def audit_resnet(batch, dtype):
    import jax, jax.numpy as jnp
    from deeplearning4j_tpu.zoo.resnet import ResNet50
    name = f"resnet50_b{batch}_{dtype}"
    model = ResNet50(num_classes=1000, seed=0).init()
    if dtype != "float32":
        model.conf.dtype = dtype  # bf16 compute, f32 master (bench.py)
    x = jnp.zeros((batch, 224, 224, 3), jnp.float32)
    y = jnp.zeros((batch, 1000), jnp.float32).at[:, 0].set(1.0)
    step = model._make_step()
    t0 = time.perf_counter()
    lowered = step.lower(model._params, model._opt_state,
                         model._net_state, jnp.asarray(0),
                         model._as_inputs(x), model._as_labels(y),
                         model._as_masks(None), jax.random.PRNGKey(0))
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    entry = {"model": name, "batch": batch, "dtype": dtype,
             "compile_s_cpu": round(compile_s, 1), **_cost(compiled),
             "stablehlo_dtypes": _stablehlo_dtype_scan(lowered.as_text()),
             "hlo": _hlo_scan(compiled.as_text())}
    entry["pred_throughput_at_40pct_mfu"] = round(
        batch / (entry["roofline_ms_v5e_bf16"] / 1e3 / 0.4), 1)
    return entry


def audit_bert(batch=32, seq=128, dtype="bfloat16"):
    import jax, jax.numpy as jnp
    CACHE = os.path.join(os.path.dirname(__file__), "..", ".bench_cache")
    os.makedirs(CACHE, exist_ok=True)
    pb = os.path.join(CACHE, f"bert_base_s{seq}.pb")
    VOCAB, NCLS = 1000, 2
    if not os.path.exists(pb):
        from deeplearning4j_tpu.interop.tf_bert import build_frozen_bert
        graph_bytes, _ = build_frozen_bert(
            vocab=VOCAB, seq_len=seq, n_classes=NCLS, preset="base",
            seed=0)
        with open(pb, "wb") as f:
            f.write(graph_bytes)
    from deeplearning4j_tpu.modelimport import TFGraphMapper
    from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
    from deeplearning4j_tpu.learning import Adam
    sd = TFGraphMapper.import_graph(pb)
    out = [v.name for v in sd.variables()][-1]
    for v in list(sd.variables()):
        arr = sd._values.get(v.name)
        if arr is not None and hasattr(arr, "ndim") and \
                np.asarray(arr).dtype == np.float32 and \
                np.asarray(arr).size > 2:
            sd.convert_to_variable(v.name)
    labels = sd.placeholder("labels", (None, NCLS))
    probs = sd.get_variable(out)
    lp = probs.clipbyvalue(1e-7, 1.0).log()
    loss = (labels * lp).reduce_sum(axes=(-1,)).reduce_mean().neg()
    sd.set_loss_variables(loss.name)
    sd.set_training_config(TrainingConfig(
        updater=Adam(2e-5), data_set_feature_mapping=["ids", "mask"],
        data_set_label_mapping=["labels"],
        compute_dtype=None if dtype == "float32" else dtype))
    sd.initialize_training()
    step = sd._train_step_fn()
    tnames = tuple(sd._trainable())
    tvars = {n: sd._values[n] for n in tnames}
    needed = sd._loss_fn(tnames).needed
    nondiff = {k: v for k, v in sd._values.items()
               if k not in tnames and k in needed}
    rs = np.random.RandomState(0)
    feed = dict(nondiff)
    feed["ids"] = jnp.asarray(rs.randint(0, VOCAB, (batch, seq)),
                              jnp.int32)
    feed["mask"] = jnp.asarray(np.ones((batch, seq), np.int32))
    feed["labels"] = jnp.asarray(
        np.eye(NCLS, dtype=np.float32)[rs.randint(0, NCLS, batch)])
    rng = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    lowered = step.lower(tvars, sd._updater_state, 0, feed, rng)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    entry = {"model": f"bert_base_s{seq}_b{batch}_{dtype}",
             "batch": batch, "dtype": dtype,
             "compile_s_cpu": round(compile_s, 1), **_cost(compiled),
             "stablehlo_dtypes": _stablehlo_dtype_scan(lowered.as_text()),
             "hlo": _hlo_scan(compiled.as_text())}
    entry["pred_throughput_at_40pct_mfu"] = round(
        batch / (entry["roofline_ms_v5e_bf16"] / 1e3 / 0.4), 1)
    return entry


def donation_audit():
    """Every training-step jit site must donate its carried state
    (params / opt / net state) so XLA reuses the buffers in place —
    without donation a ResNet50-class model holds 2x params + 2x
    moments live across the step boundary."""
    import subprocess
    root = os.path.join(os.path.dirname(__file__), "..",
                        "deeplearning4j_tpu")
    out = subprocess.run(
        ["grep", "-rn", "jax.jit(", root], capture_output=True,
        text=True).stdout.splitlines()
    sites = []
    for line in out:
        path, no, code = line.split(":", 2)
        ctx = open(path).read().splitlines()
        i = int(no) - 1
        # jit call sites span several lines; donate_argnums may sit on
        # any of them
        window = "\n".join(ctx[i:i + 8])
        is_step = ("step" in window or "donate" in window)
        sites.append({"site": f"{os.path.relpath(path, root)}:{no}",
                      "donates": "donate_argnums" in window,
                      "step_like": is_step,
                      "code": code.strip()[:80]})
    return sites


_SHARDED_AUDIT_CODE = r"""
import json, os, re, sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from deeplearning4j_tpu.parallel import make_mesh, jit_sharded_step
from deeplearning4j_tpu.zoo.resnet import ResNet50
n_devices, batch = int(sys.argv[2]), int(sys.argv[3])
model = ResNet50(num_classes=100, seed=0, input_shape=(64, 64, 3)).init()
mesh = make_mesh(jax.devices()[:n_devices])
step = jit_sharded_step(model, mesh)
x = jnp.zeros((batch, 64, 64, 3), jnp.float32)
y = jnp.zeros((batch, 100), jnp.float32).at[:, 0].set(1.0)
with mesh:
    compiled = step.lower(model._params, model._opt_state,
                          model._net_state, jnp.asarray(0),
                          model._as_inputs(x), model._as_labels(y),
                          model._as_masks(None),
                          jax.random.PRNGKey(0)).compile()
txt = compiled.as_text()
# collective DEFINITIONS (results may be tuples: XLA's combiner fuses
# many per-parameter reduces into one tuple-result all-reduce)
defs = re.findall(r"= (\([^=]*?\)|\S+) all-reduce(?:-start)?\(", txt)

# numeric grad-parity spot check IN FLOAT64 (the audit that actually
# matters — the round-5 investigation showed (a) textual collective
# counting on the CPU backend misleads, (b) f32 parity drifts at the
# few-percent level from reassociation amplified through small-batch
# BN statistics, while f64 is decisive: machine-epsilon agreement or a
# real partitioning bug. BN betas directly feeding another
# normalization have true grad ~0 (loss-invariant), so the comparison
# uses a global denominator rather than per-tensor relatives.)
jax.config.update("jax_enable_x64", True)
p64 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float64),
                             model._params)
n64 = jax.tree_util.tree_map(
    lambda a: (jnp.asarray(a, jnp.float64)
               if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
               else a), model._net_state)
rs = np.random.RandomState(1)
xr = jnp.asarray(rs.rand(batch, 64, 64, 3))
yr = jnp.asarray(np.eye(100)[rs.randint(0, 100, batch)])
def loss_fn(p, x, y):
    l, _ = model._loss_fn(p, n64, model._as_inputs(x),
                          model._as_labels(y), None, True,
                          jax.random.PRNGKey(0))
    return l
repl = NamedSharding(mesh, P())
data = NamedSharding(mesh, P("data"))
g_single = jax.jit(jax.grad(loss_fn))(p64, xr, yr)
gs = jax.jit(jax.grad(loss_fn), in_shardings=(repl, data, data),
             out_shardings=repl)
with mesh:
    g_shard = gs(p64, xr, yr)
gmax = max(float(jnp.abs(l).max())
           for l in jax.tree_util.tree_leaves(g_single))
delta = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(jax.tree_util.tree_leaves(g_shard),
                            jax.tree_util.tree_leaves(g_single)))
print(json.dumps({
    "all_reduce_defs": len(defs),
    "tuple_combined_defs": sum(1 for d in defs if d.startswith("(")),
    "param_tensors": len(jax.tree_util.tree_leaves(model._params)),
    "grad_parity_f64_max_abs_delta": delta,
    "grad_parity_f64_rel_to_global_max": delta / gmax}))
"""


def audit_sharded_collectives(n_devices=8, batch=32):
    """All-reduce placement in the SHARDED DP program (verdict r4 #2):
    the gradient all-reduce should appear as a small number of fused
    all-reduce ops (XLA combines per-parameter reduces), not one per
    parameter tensor — per-op collectives would serialize ICI traffic.
    Runs in a subprocess (the device-count flag must precede jax init)
    on the virtual CPU mesh; collective STRUCTURE is backend-portable
    even though CPU wire transport is not."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    try:
        r = subprocess.run(
            [sys.executable, "-c", _SHARDED_AUDIT_CODE, root,
             str(n_devices), str(batch)],
            capture_output=True, text=True, timeout=1800, env=env)
        if r.returncode != 0:
            return {"error": r.stderr[-500:]}
        data = json.loads([l for l in r.stdout.splitlines()
                           if l.startswith("{")][-1])
    except Exception as e:
        # a failed sharded audit must not discard the already-computed
        # per-model audits in main()
        return {"error": f"{type(e).__name__}: {e}"[:500]}
    rel = data["grad_parity_f64_rel_to_global_max"]
    out = {"mesh_devices": n_devices, "batch": batch, **data,
           "note": ("sharded grads match single-device at machine "
                    "epsilon (f64); tuple defs = XLA combined "
                    "per-param reduces"
                    if rel < 1e-9 else
                    "WARNING: sharded gradient parity violated — "
                    "investigate before trusting DP training")}
    return out


def main():
    if "--kv-quant" in sys.argv:
        res = audit_kv_quant()
        print(json.dumps(res, indent=1))
        if not res["ok"]:
            raise AssertionError(
                "unintended f32 dots on a quantized KV leg")
        return
    results = {"spec": {"v5e_bf16_flops": V5E_BF16_FLOPS,
                        "v5e_hbm_bps": V5E_HBM_BPS}}
    models = []
    for batch, dtype in ((32, "bfloat16"), (128, "bfloat16"),
                         (32, "float32")):
        print(f"auditing resnet50 b{batch} {dtype}...", flush=True)
        models.append(audit_resnet(batch, dtype))
    print("auditing bert_base...", flush=True)
    models.append(audit_bert())
    results["models"] = models
    print("auditing quantized KV dot dtypes...", flush=True)
    results["kv_quant"] = audit_kv_quant()
    print("auditing sharded collectives...", flush=True)
    results["sharded_collectives"] = audit_sharded_collectives()
    results["donation_sites"] = donation_audit()
    out = os.path.join(os.path.dirname(__file__), "perf_audit.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results["models"], indent=1))
    print(f"written: {out}")


if __name__ == "__main__":
    main()
