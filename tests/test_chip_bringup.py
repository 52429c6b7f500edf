"""What a CPU can certify about running on the chip (ISSUE 21).

- Every Pallas kernel variant goes through libtpu's real compiler —
  Pallas→TPU lowering and Mosaic — for a deviceless ``v5e`` topology, at
  the served shapes (`chip_smoke.py`) and at the bench's toy shapes. The
  interpret-mode parity tests cannot see a block shape or a vector
  layout Mosaic refuses; this can. It is the cheap pre-check before chip
  time, never a substitute for the run.
- The compile-cache placement rule.
- `chip_smoke.py` refuses to run without a TPU.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.kernels.decode_attention import \
    decode_attention_pallas
from deeplearning4j_tpu.kernels.flash_attention import flash_attention
from deeplearning4j_tpu.kernels.kv_quant import QuantArray
from deeplearning4j_tpu.kernels.paged_attention import (
    KERNEL_NAME, KERNEL_NAME_WINDOW, kv_pool_set, kv_pool_set_span,
    paged_attention_pallas, paged_prefill_attention)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# S slots, H heads, D head_dim, Bs block_size, T cache capacity, N pool
# blocks (a table's worth for every slot and the null block, if not given)
SHAPES = {
    "served": dict(S=8, H=12, D=64, Bs=16, T=1024),   # GPT-2-small
    "toy": dict(S=4, H=4, D=16, Bs=8, T=192),         # the CPU tests' LM
    # the benchmark's cell, gpt2-xl.decode_backlog
    "cell": dict(S=16, H=25, D=64, Bs=16, T=1024, N=321),
}


@pytest.fixture(scope="module")
def v5e():
    """``compile_for(fn, *shape_dtype_structs)`` against one device of a
    v5e:2x2 topology description — no device needed."""
    # libtpu would otherwise ask a metadata server that is not there
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    sharding = SingleDeviceSharding(topo.devices[0])

    def compile_for(fn, *args, donate=(), kernel=True):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), args)
        text = jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().as_text()
        assert ("tpu_custom_call" in text) == kernel, \
            "no Mosaic kernel in the program" if kernel else "a kernel"
        return text
    return compile_for


_DT = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _kv(shape, dt):
    """Abstract K (== V) cache operand of ``shape`` [..., D] at ``dt``."""
    sds = jax.ShapeDtypeStruct
    if dt == "int8":
        return QuantArray(sds(shape, jnp.int8),
                          sds(shape[:-1], jnp.float32))
    return sds(shape, _DT[dt])


def _pool(N, H, Bs, D, dt):
    """Abstract paged pool for K (== V) blocks [N, H, Bs, D] at ``dt``:
    one array, K and V side by side on the lanes."""
    sds = jax.ShapeDtypeStruct
    if dt == "int8":
        return QuantArray(sds((N, H, Bs, 2 * D), jnp.int8),
                          sds((N, 2, H, Bs), jnp.float32))
    return sds((N, H, Bs, 2 * D), _DT[dt])


def _custom_calls(text):
    """The instruction names of a compiled program's Mosaic kernels."""
    return [ln.replace("ROOT ", "").split()[0].lstrip("%").split(".")[0]
            for ln in text.splitlines() if "tpu_custom_call" in ln]


def test_paged_kernels_custom_call_is_named_after_the_kernel(v5e):
    """A device trace names an operation by its HLO instruction: the
    paged decode kernel's has to carry the kernel's own name, not the
    enclosing jit's (the ledger's ``breakdown.device_ops``; the
    benchmark's kernel metrics take any named custom call)."""
    S, H, D, Bs, T = (SHAPES["served"][k] for k in ("S", "H", "D", "Bs", "T"))
    sds = jax.ShapeDtypeStruct
    pool = _pool(S * (T // Bs) + 1, H, Bs, D, "f32")

    def step(q, kv, t, l):
        return paged_attention_pallas(q, kv, t, l, interpret=False)
    text = v5e(step, sds((S, H, D), jnp.float32), pool,
               sds((S, T // Bs), jnp.int32), sds((S,), jnp.int32))
    assert _custom_calls(text) == [KERNEL_NAME]


@pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("size", list(SHAPES))
def test_decode_kernels_compile_for_v5e(v5e, size, dt):
    S, H, D, Bs, T = (SHAPES[size][k] for k in ("S", "H", "D", "Bs", "T"))
    B = T // Bs
    sds = jax.ShapeDtypeStruct
    q, lens = sds((S, H, D), jnp.float32), sds((S,), jnp.int32)
    pool = _pool(SHAPES[size].get("N", S * B + 1), H, Bs, D, dt)
    text = v5e(lambda q, kv, t, l: paged_attention_pallas(
        q, kv, t, l, interpret=False),
        q, pool, sds((S, B), jnp.int32), lens)
    # one kernel for the whole call, under the name the trace reads
    assert _custom_calls(text) == [KERNEL_NAME]
    cache = _kv((S, H, T, D), dt)
    v5e(lambda q, k, v, l: decode_attention_pallas(
        q, k, v, l, interpret=False), q, cache, cache, lens)


#: a cell's decode call: query heads, KV heads, head size, block size,
#: table (or ring) entries, pool blocks, window
DECODE_CALLS = {
    "lfm2-8b-a1b": (32, 8, 64, 16, 64, 1025, None),
    "smallthinker-21b-a3b.global": (28, 4, 128, 64, 256, 3073, None),
    # a ring of blocks_for(window + chunk) + 1 entries a slot
    "smallthinker-21b-a3b.window": (28, 4, 128, 64, 81, 1297, 4096),
    "jamba2-3b": (20, 1, 128, 64, 256, 4097, None)}


@pytest.mark.parametrize("cell", list(DECODE_CALLS))
def test_grouped_query_paged_kernel_compiles_for_v5e_at_the_cells_widths(
        v5e, cell):
    """Each grouped-query cell's decode call, 16 slots of a bf16 pool:
    one kernel, under the name its layer's metrics read, with the grid
    over the live (slot, chunk) pairs sized by a traced scalar."""
    Hq, Hkv, D, Bs, B, N, window = DECODE_CALLS[cell]
    sds = jax.ShapeDtypeStruct
    text = v5e(lambda q, kv, t, l: paged_attention_pallas(
        q, kv, t, l, window=window, interpret=False),
        sds((16, Hq, D), jnp.float32), _pool(N, Hkv, Bs, D, "bf16"),
        sds((16, B), jnp.int32), sds((16,), jnp.int32))
    assert _custom_calls(text) == [KERNEL_NAME_WINDOW if window
                                   else KERNEL_NAME]


# -- the pool's layout: no program relays a pool (ISSUE 31) -------------------
#: the two cells' attention: slots, query heads, KV heads, pool blocks, dtype
CELLS = {"gpt2-xl": (16, 25, 25, 321, "f32"),
         "lfm2-8b-a1b": (16, 32, 8, 1025, "bf16")}


def _decode_layer(pool, q, k, v, tables, pos):
    """What one layer of ``jit_step`` does to its pool: the step's rows
    written, then the kernel over the table."""
    Bs = pool.shape[2]
    blk = jnp.take_along_axis(tables, (pos // Bs)[:, None], axis=1)[:, 0]
    pool = kv_pool_set(pool, (blk[:, None], jnp.arange(k.shape[1])[None],
                              (pos % Bs)[:, None]), k, v)
    return paged_attention_pallas(q, pool, tables, pos + 1,
                                  interpret=False), pool


def _chunk_layer(pool, q, k, v, table, p0):
    """What one layer of ``jit_chunk`` does to its pool: the chunk's
    rows written by blocks, then the sequence's span gathered out and
    attended."""
    pool = kv_pool_set_span(pool, table, p0, k, v)
    return paged_prefill_attention(q, pool, table, p0), pool


@pytest.mark.parametrize("program", ["step", "chunk16", "chunk32",
                                     "chunk64"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_a_donated_pool_goes_through_a_layer_with_no_relayout(
        v5e, cell, program):
    """At both cells' shapes the compiled layer holds no ``copy`` whose
    result has the pool's shape, takes the pool in the default row-major
    tiled layout and aliases its output to it; the step has exactly the
    one kernel. (Two arrays ``[N, H, Bs, 64]`` compiled to four and six
    pool-sized copies here: 39 of ``gpt2-xl``'s 62 ms a step.) A chunk
    layer, at each table bucket the cells' traffic meets, is the write
    by blocks and XLA's span path: no kernel, and of the pool the write
    gathers the ``C / Bs + 1`` blocks the chunk lies in and the
    attention the table's span, nothing else."""
    S, Hq, Hkv, N, dt = CELLS[cell]
    D, Bs, C = 64, 16, 256
    B = 64 if program == "step" else int(program[5:])
    sds = jax.ShapeDtypeStruct
    pool = _pool(N, Hkv, Bs, D, dt)
    rows = lambda n: sds((n, Hkv, D), jnp.float32)        # noqa: E731
    if program == "step":
        text = v5e(_decode_layer, pool, sds((S, Hq, D), jnp.float32),
                   rows(S), rows(S), sds((S, B), jnp.int32),
                   sds((S,), jnp.int32), donate=(0,))
        assert _custom_calls(text) == [KERNEL_NAME]
    else:
        text = v5e(_chunk_layer, pool, sds((C, Hq, D), jnp.float32),
                   rows(C), rows(C), sds((B,), jnp.int32),
                   sds((), jnp.int32), donate=(0,), kernel=False)
        gathered = sorted(int(n) for n in re.findall(
            r"= (?:f32|bf16)\[(\d+),%d,%d,\d+\]\S* gather\(" % (Hkv, Bs),
            text))
        assert gathered == sorted([C // Bs + 1, B])
    shape = "%s[%d,%d,%d,%d]" % (dt, N, Hkv, Bs, 2 * D)
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln)]
    assert copies == []
    (entry,) = re.findall(r"entry_computation_layout=\{\((\S+)", text)
    assert entry.startswith(shape + "{3,2,1,0:T(8,128)")
    assert re.search(r"input_output_alias=\{ \{1\}: \(0, \{\}", text)


@pytest.mark.parametrize("pairs", [64, 1024], ids=["decode", "chunk"])
def test_expert_kernel_compiles_for_v5e_at_the_cells_widths(v5e, pairs):
    """32 experts of 2048 x 1792, bf16; a decode step's 16 x 4 pairs
    and a 256-token chunk's 1,024. Both of its calls carry the name
    the trace reads (``custom-call/moe_experts...``)."""
    from deeplearning4j_tpu.kernels import moe_experts
    sds = jax.ShapeDtypeStruct
    up = sds((32, 2048, 1792), jnp.bfloat16)
    text = v5e(lambda x, w1, w3, w2, g: moe_experts.expert_ffn(
        x, w1, w3, w2, g, impl="pallas", interpret=False),
        sds((pairs, 2048), jnp.bfloat16), up, up,
        sds((32, 1792, 2048), jnp.bfloat16), sds((32,), jnp.int32))
    assert sorted(_custom_calls(text)) == [
        moe_experts.KERNEL_NAME + "_down", moe_experts.KERNEL_NAME + "_up"]


@pytest.mark.parametrize("size", list(SHAPES))
def test_flash_attention_fwd_bwd_compile_for_v5e(v5e, size):
    H, D, T = (SHAPES[size][k] for k in ("H", "D", "T"))
    x = jax.ShapeDtypeStruct((2, T, H, D), jnp.float32)
    km = jax.ShapeDtypeStruct((2, T), jnp.float32)

    def fwd(q, k, v, km):
        return flash_attention(q, k, v, causal=True, key_mask=km,
                               interpret=False)
    v5e(fwd, x, x, x, km)
    v5e(jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2)),
        x, x, x, km)


# -- compile-cache placement --------------------------------------------
_PLACE = ("import jax; "
          "from deeplearning4j_tpu.compile_cache import place_compile_cache; "
          "print(place_compile_cache()); "
          "print(jax.config.jax_compilation_cache_dir)")


def _placed(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    r = subprocess.run([sys.executable, "-c", _PLACE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    assert _placed(str(tmp_path)) == [str(tmp_path), str(tmp_path)]


def test_compile_cache_defaults_to_the_checkout():
    want = os.path.join(ROOT, ".jax_cache")
    assert _placed(None) == [want, want]


def test_no_other_code_sets_a_cache_directory():
    hits = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if d not in (".git", "chiprun_out", ".jax_cache",
                                ".checkouts", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            if f.endswith((".py", ".sh")) and path != __file__:
                with open(path, errors="replace") as fh:
                    if "compilation_cache_dir" in fh.read():
                        hits.append(os.path.relpath(path, ROOT))
    assert hits == ["deeplearning4j_tpu/compile_cache.py"], hits


# -- chip_smoke.py -------------------------------------------------------
def test_chip_smoke_refuses_to_run_without_a_tpu():
    """Importable with no side effects; run without an accelerator it
    exits non-zero at once, says why on stderr and prints no result."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; sys.exit('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=ROOT)
    assert r.returncode == 2
    assert "found no TPU" in r.stderr
    assert r.stdout == ""


# -- the long-context cell's own programs (PR 35) ---------------------------------
def _smallthinker_program(monkeypatch, which):
    """(function, abstract arguments, pool shapes by group) of the
    engine's ``jit_step`` or ``jit_chunk`` for the configuration
    ``benchmark/configs/smallthinker-21b-a3b.json`` as the chip runs it:
    the engine's own program bodies over the served class, shapes in
    place of its weights and pools (an engine of that size is not built
    here)."""
    import importlib
    import json
    from deeplearning4j_tpu.serving.generation import GenerationEngine
    from deeplearning4j_tpu.serving.paging import CacheGroup, blocks_for
    from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerLM
    for mod in ("paged_attention", "moe_experts"):
        # the kernels ask which platform they will run on: the chip's
        monkeypatch.setattr(importlib.import_module(
            "deeplearning4j_tpu.kernels." + mod), "default_platform",
            lambda: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    lm = SmallThinkerLM(**cfg["model"])
    params = jax.eval_shape(lambda: lm.init()._params)
    lm._params = None
    e = cfg["engine"]
    S, Bs, C = e["num_slots"], e["block_size"], e["prefill_chunk_tokens"]
    ring = blocks_for(lm.window + C, Bs) + 1
    blocks = {"global": e["num_blocks"], "window": S * ring + 1}
    eng = object.__new__(GenerationEngine)
    eng.model, eng.decode_impl, eng.cache_backend = lm, "auto", "paged"
    eng._groups = [CacheGroup(g["name"], g["layers"], 2, 1, 1, g["window"])
                   for g in lm.cache_groups()]
    eng._extended = True        # the model takes live / slot
    pools = [None] * lm.n_layers
    for g in lm.cache_groups():
        for i in g["layers"]:
            pools[i] = _pool(blocks[g["name"]], lm.n_kv_heads, Bs,
                             lm.head_dim, "bf16")
    sds = jax.ShapeDtypeStruct
    i32 = lambda *s: sds(s, jnp.int32)                    # noqa: E731
    if which == "step":
        return eng._decode_fn(), (
            params, pools, [], i32(S), i32(S), sds((S,), jnp.bool_), i32(S),
            (i32(S, e["max_seq_len"] // Bs), i32(S, ring)),
            sds((S,), jnp.uint32), i32(S), sds((S,), jnp.float32), i32(S),
            i32(S), i32(S)), blocks
    return eng._chunk_fn(), (
        params, pools, [], i32(1, C), i32(), i32(),
        (i32(e["max_seq_len"] // Bs), i32(ring)), i32(), sds((), jnp.uint32),
        sds((), jnp.float32), i32()), blocks


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_the_long_context_cells_programs_fit_and_relay_no_pool(
        v5e, monkeypatch, which):
    """``jit_step`` and ``jit_chunk`` (table bucket 256, the traffic's
    largest) of ``smallthinker-21b-a3b`` compile for a v5e with both
    groups' pools donated: no ``copy`` with either pool's shape, all 12
    pools aliased, the decode kernels (plain and windowed) or the tiled
    chunk kernels (plain and windowed) beside the expert kernels, and
    arguments plus temporaries under the 15.75 GB a v5e's compiler
    allows (PERF.md section 4)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    fn, args, blocks = _smallthinker_program(monkeypatch, which)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), args)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert mem.alias_size_in_bytes > 2.7e9          # both groups' pools
    shape = r"bf16\[(%d|%d),4,64,256\]" % (blocks["global"],
                                           blocks["window"])
    assert [ln for ln in text.splitlines()
            if re.search(r"= " + shape + r"\S* copy\(", ln)] == []
    (aliases,) = re.findall(r"input_output_alias=\{([^\n]*)", text)
    assert len(re.findall(r"\{\d+\}: \(\d+, \{\}", aliases)) >= 12
    names = set(_custom_calls(text))
    attn = {"step": {"paged_attention_decode",
                     "paged_attention_decode_window"},
            "chunk": {"paged_prefill_attention",
                      "paged_prefill_attention_window"}}[which]
    assert names == attn | {"moe_experts_up", "moe_experts_down"}
    # one attention kernel a layer: 3 global layers, 9 window layers
    calls = _custom_calls(text)
    assert sorted(calls.count(n) for n in attn) == [3, 9]


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_the_long_context_cells_experts_come_back_by_a_gather(
        v5e, monkeypatch, which):
    """PR 38: in ``smallthinker-21b-a3b``'s ``jit_step`` and
    ``jit_chunk`` the experts' sorted rows return to pair order by one
    gather a layer into ``f32[k, T, D]``: no scatter whose result is
    the ``f32[T*k, D]`` rows (one update a row: 11.2 ms of the parent's
    chunk, PERF.md section 5) and no ``f32[T, k, D]`` relayout."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    m, e = cfg["model"], cfg["engine"]
    T = e["num_slots"] if which == "step" else e["prefill_chunk_tokens"]
    k, D = m["moe_num_active_primary_experts"], m["hidden_size"]
    fn, args, _ = _smallthinker_program(monkeypatch, which)
    text = v5e(fn, *args, donate=(1, 2))
    assert not re.search(r"= f32\[%d,%d\]\S* scatter\(" % (T * k, D), text)
    assert "f32[%d,%d,%d]" % (T, k, D) not in text
    assert len(re.findall(r"= f32\[%d,%d,%d\]\S* gather\(" % (k, T, D),
                          text)) == m["num_hidden_layers"]


# -- the fourth configuration's kernels and programs (PR 37) -------------------------
@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
def test_selective_scan_kernel_compiles_for_v5e_at_the_cells_widths(v5e,
                                                                    gated):
    """``jamba2-3b``: a chunk of 1,024 rows over 5,120 channels of 16
    states; the call carries the name the trace reads
    (``custom-call/selective_scan_chunk``)."""
    from deeplearning4j_tpu.kernels import selective_scan as ss
    sds = jax.ShapeDtypeStruct
    f = lambda *s: sds(s, jnp.float32)                      # noqa: E731
    T, Di, N = 1024, 5120, 16
    text = v5e(lambda c, dt, B, C, A, D, h, n, z:
               ss.selective_scan_chunk_pallas(c, dt, B, C, A, D, h, n,
                                              z if gated else None),
               f(T, Di), f(T, Di), f(T, N), f(T, N), f(N, Di), f(Di),
               f(N, Di), sds((), jnp.int32), f(T, Di))
    assert _custom_calls(text) == [ss.CHUNK_KERNEL_NAME]


def test_paged_kernels_take_20_heads_over_one_kv_head_at_the_cells_shape(
        v5e):
    """``jamba2-3b``: 20 query heads over 1 KV head of 128, a bf16 pool
    of 4,097 blocks of 64, a table of 256: the decode kernel's MXU body
    and the tiled chunk kernel over 1,024 rows (its 20 x 128 query rows
    a KV head and their score tiles inside the VMEM limit)."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        PREFILL_KERNEL_NAME, paged_prefill_attention_pallas)
    sds = jax.ShapeDtypeStruct
    pool = _pool(4097, 1, 64, 128, "bf16")
    text = v5e(lambda q, kv, t, l: paged_attention_pallas(
        q, kv, t, l, interpret=False),
        sds((16, 20, 128), jnp.float32), pool, sds((16, 256), jnp.int32),
        sds((16,), jnp.int32))
    assert _custom_calls(text) == [KERNEL_NAME]
    text = v5e(lambda q, kv, t, p0, n: paged_prefill_attention_pallas(
        q, kv, t, p0, n),
        sds((1024, 20, 128), jnp.float32), pool, sds((256,), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32))
    assert _custom_calls(text) == [PREFILL_KERNEL_NAME]


def _jamba_program(monkeypatch, which):
    """(function, abstract arguments) of the engine's ``jit_step`` or
    ``jit_chunk`` for ``benchmark/configs/jamba2-3b.json`` as the chip
    runs it: the engine's own program bodies over the served class,
    shapes in place of its weights, pools and slot state."""
    import importlib
    import json
    from deeplearning4j_tpu.serving.generation import GenerationEngine
    from deeplearning4j_tpu.zoo.jamba import JambaLM
    for mod in ("paged_attention", "selective_scan"):
        monkeypatch.setattr(importlib.import_module(
            "deeplearning4j_tpu.kernels." + mod), "default_platform",
            lambda: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        cfg = json.load(f)
    lm = JambaLM(**cfg["model"])
    params = jax.eval_shape(lambda: lm.init()._params)
    lm._params = None
    e = cfg["engine"]
    S, Bs, C = e["num_slots"], e["block_size"], e["prefill_chunk_tokens"]
    eng = object.__new__(GenerationEngine)
    eng.model, eng.decode_impl, eng.cache_backend = lm, "auto", "paged"
    eng._groups, eng._extended = [], True
    sds = jax.ShapeDtypeStruct
    pools = [_pool(e["num_blocks"], 1, Bs, 128, "bf16")] * 2
    state = [sds(s, d) for s, d in lm.slot_state_shapes(S)]
    i32 = lambda *s: sds(s, jnp.int32)                    # noqa: E731
    B = e["max_seq_len"] // Bs
    if which == "step":
        return eng._decode_fn(), (
            params, pools, state, i32(S), i32(S), sds((S,), jnp.bool_),
            i32(S), i32(S, B), sds((S,), jnp.uint32), i32(S),
            sds((S,), jnp.float32), i32(S), i32(S), i32(S))
    return eng._chunk_fn(), (
        params, pools, state, i32(1, C), i32(), i32(), i32(B), i32(),
        sds((), jnp.uint32), sds((), jnp.float32), i32())


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_the_jamba_cells_programs_fit_and_relay_no_state(
        v5e, monkeypatch, which):
    """``jit_step`` and ``jit_chunk`` (table bucket 256, the traffic's
    largest) of ``jamba2-3b`` compile for a v5e with the pools and the
    52 slot arrays donated: no ``copy`` with a pool's or a state's
    shape, pools and state aliased (0.27 + 0.15 GB), the kernels the
    metrics read by name, and arguments plus temporaries of 6.6 GB."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    fn, args = _jamba_program(monkeypatch, which)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), args)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert 6.4e9 < mem.argument_size_in_bytes < 6.6e9
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.alias_size_in_bytes > 0.41e9         # pools and state
    for shape in (r"bf16\[4097,1,64,256\]", r"f32\[16,16,5120\]",
                  r"bf16\[16,3,5120\]"):
        assert [ln for ln in text.splitlines()
                if re.search(r"= " + shape + r"\S* copy\(", ln)] == []
    names = set(_custom_calls(text))
    if which == "chunk":
        assert names == {"paged_prefill_attention", "selective_scan_chunk"}
    else:       # the step's recurrence is XLA's fusion: no kernel
        assert names == {"paged_attention_decode"}
        assert len(_custom_calls(text)) == 2    # one a attention layer


# -- the first cell's embedding tables ---------------------------------------
@pytest.mark.parametrize("which", ["step", "chunk"])
def test_the_first_cells_programs_fetch_token_rows_where_the_table_lies(
        v5e, monkeypatch, which):
    """One layer of ``CausalTransformerLM`` at ``gpt2-xl``'s widths: its
    decode step over 16 slots and its 256-row chunk (last row only, as
    the engine asks) compile for a v5e with the token and position
    tables taken lane-padded to 1,664 and row-major, and no ``copy`` of
    either table. At 1,600 lanes the device keeps a table with its rows
    on the lanes, and both programs copied the 321 MB token table to
    row-major for the gather: 0.995 ms of each (PERF.md section 7)."""
    import importlib
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
    monkeypatch.setattr(importlib.import_module(
        "deeplearning4j_tpu.kernels.paged_attention"), "default_platform",
        lambda: "tpu")
    lm = CausalTransformerLM(vocab_size=50257, d_model=1600, n_layers=1,
                             n_heads=25, d_ff=6400, max_seq_len=1024)
    params = jax.eval_shape(lambda: lm.init()._params)
    lm._params = None
    sds = jax.ShapeDtypeStruct
    i32 = lambda *s: sds(s, jnp.int32)                    # noqa: E731
    pools = [_pool(321, 25, 16, 64, "f32")]
    if which == "step":
        text = v5e(lm.forward_decode_paged, params, i32(16), i32(16),
                   pools, i32(16, 64), donate=(3,))
    else:
        text = v5e(lambda p, t, p0, n, pools, bt: lm.forward_prefill_chunk(
            p, t, p0, n, pools, bt, last_only=True),
            params, i32(1, 256), i32(), i32(), pools, i32(64),
            donate=(4,), kernel=False)
    (entry,) = re.findall(r"entry_computation_layout=\{\(([^\n]*)", text)
    for rows in (50257, 1024):
        assert "f32[%d,1664]{1,0:T(8,128)}" % rows in entry
        table = r"f32\[%d,16(00|64)\]" % rows
        assert not re.search(r"= %s\S* copy\(" % table, text)
        assert not re.search(r"%s\{0,1" % table, text)


def test_a_table_of_whole_lanes_is_looked_up_by_the_plain_gather():
    """At 2,048 lanes (the other cells' widths are multiples of 128) the
    padding and the row lookup lower to the program ``table[ids]``
    lowers to."""
    from deeplearning4j_tpu.nn.functional import embed_rows, lane_padded
    args = (jax.ShapeDtypeStruct((65536, 2048), jnp.bfloat16),
            jax.ShapeDtypeStruct((16,), jnp.int32))
    assert (jax.jit(lambda t, i: embed_rows(lane_padded(t), i, 2048)).lower(
        *args).as_text() == jax.jit(lambda t, i: t[i]).lower(*args).as_text())
