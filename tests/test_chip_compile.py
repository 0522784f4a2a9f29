"""The main path's kernels, compiled for a described TPU v5e.

Interpret mode proves a kernel's arithmetic; only the chip's compiler
proves that the chip accepts it (block shapes against the (8, 128)
tiling, VMEM, unaligned slices).  The TPU compiler is installed here and
compiles for a chip that is described and not attached, so these cases
guard every later PR at no chip time: each compiles one kernel at the
widths chip_smoke.py runs (gpt3-125m: 12 heads of 64; gpt3-1.3b: 16
heads of 128; seq 2048; 8 slots) and checks that the program carries a
``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at
import), shardings and shapes are built from it in fixtures or tests,
the compiles run in this process with the persistent cache off around
them (a compile for a described chip is written to the cache but cannot
be read back without the chip).  Nothing runs: a compile that passes is
not a chip run.
"""
import contextlib
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

da = importlib.import_module("paddle_tpu.ops.decode_attention")
fa = importlib.import_module("paddle_tpu.ops.flash_attention")
qm = importlib.import_module("paddle_tpu.ops.quantized_matmul")
gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
kk = importlib.import_module("paddle_tpu.ops.kda_chunk_kernel")

SLOTS, SEQ, VOCAB = 8, 2048, 50304
WIDTHS = [(12, 64), (16, 128)]          # (heads, head_dim): 125m, 1.3b


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def persistent_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """compile_for_chip(fn, *(shape, dtype)) -> the compiled program's
    text, with the persistent cache off around the compile."""
    def run(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        with persistent_cache_off():
            # under the suite's 'highest' matmul precision, which the
            # bf16/int8 kernels must not inherit (flash_attention.run_kernel)
            return jax.jit(fn).lower(*args).compile().as_text()
    return run


def assert_kernel(text):
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.mark.parametrize("heads,d", WIDTHS)
@pytest.mark.parametrize("key_mask", [False, True],
                         ids=["no_key_mask", "key_mask"])
def test_flash_forward_and_backward(compile_for_chip, heads, d, key_mask):
    """Both bodies: the one every cell runs (no key mask, so no mask
    operand) and the one a padded batch gets."""
    def loss(q, k, v):
        mask = jnp.ones((q.shape[0], 1, q.shape[1]), f32) if key_mask \
            else None
        return fa._flash(q, k, v, mask, True).astype(f32).sum()

    qkv = ((2, SEQ, heads, d), bf16)
    text = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)),
                            qkv, qkv, qkv)
    # the forward kernel and the backward's one
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_dense_decode(compile_for_chip, quantized):
    heads, d = 16, 128
    cache = ((SLOTS, heads, SEQ, d), i8 if quantized else bf16)
    specs = [((SLOTS, heads, d), bf16), cache, cache, ((SLOTS,), i32)]
    if quantized:
        specs += [((SLOTS, heads, SEQ), f32)] * 2
    assert_kernel(compile_for_chip(da._decode_kernel_path, *specs))


@pytest.mark.parametrize("writes", [False, True], ids=["reads", "writes"])
@pytest.mark.parametrize("slots,heads,kv_heads,d", [
    (24, 16, 16, 128), (SLOTS, 16, 4, 128), (SLOTS, 12, 12, 64)],
    ids=["closed_cell", "gqa_4", "heads_of_64"])
def test_dense_decode_bounded_by_lengths(compile_for_chip, slots, heads,
                                         kv_heads, d, writes):
    """The length-bounded kernel through its entry's dispatch: Mosaic
    takes ``lengths`` as a scalar-prefetch operand and a block of
    several kv heads x 512 keys, at the closed cell's shape (24 slots x
    16 heads of 128 on 16 kv heads, all 16 a program), with a query
    group of 4 and at heads of 64; nothing the size of a mask strip is
    built beside it.  ``writes``: the same kernel as the decode tick
    calls it, with the token's k and v as operands: Mosaic takes the
    16-row tile cut out of a block at a dynamic offset, stored back into
    the block and out through a block aliased to the cache, and the
    program holds one kernel and no scatter."""
    cache = ((slots, kv_heads, SEQ, d), bf16)
    assert da._decode_tiling(kv_heads, SEQ, d, 2) == (kv_heads, 512)
    specs = [((slots, heads, d), bf16), cache, cache, ((slots,), i32)]
    path = da._decode_kernel_path
    if writes:
        path = da._decode_write_kernel_path
        specs[1:1] = [((slots, kv_heads, d), bf16)] * 2
    text = compile_for_chip(path, *specs)
    assert text.count("tpu_custom_call") == 1
    assert f"f32[{slots},{SEQ}]" not in text
    assert f"f32[{slots},1,{SEQ}]" not in text
    assert " scatter(" not in text


def test_dense_window(compile_for_chip):
    heads, d, w = 16, 128, 8
    cache = ((SLOTS, heads, SEQ, d), bf16)
    assert_kernel(compile_for_chip(
        da._window_kernel_path, ((SLOTS, w, heads, d), bf16), cache, cache,
        ((SLOTS,), i32)))


def _paged_specs(heads, d, block, w, quantized):
    max_blocks = SEQ // block
    pool = ((SLOTS * max_blocks + 1, heads, block, d),
            i8 if quantized else bf16)
    specs = [((SLOTS, w, heads, d), bf16), pool, pool,
             ((SLOTS, max_blocks), i32), ((SLOTS,), i32)]
    if quantized:
        specs += [(pool[0][:3], f32)] * 2
    return specs


@pytest.mark.parametrize("heads,d", WIDTHS)
@pytest.mark.parametrize("block", [128, 16])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_decode(compile_for_chip, heads, d, block, quantized):
    """The engine's default kv_block_size (128) and 16, fp and int8 with
    their scale pools: the head-major pool makes each of them a legal
    block shape (ISSUE 21: every one was refused before)."""
    assert_kernel(compile_for_chip(
        da._paged_window_kernel_path,
        *_paged_specs(heads, d, block, 1, quantized)))


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_paged_window(compile_for_chip, quantized):
    """W = 128: the paged chunked-prefill shape (and spec verify's)."""
    assert_kernel(compile_for_chip(
        da._paged_window_kernel_path,
        *_paged_specs(16, 128, 128, 128, quantized)))


def test_flash_gqa_at_8192(compile_for_chip):
    """The hybrid stack's attention: 32 query heads on 2 KV heads of 128
    at sequence 8192 (the kernel holds a whole K and V strip in VMEM)."""
    def loss(q, k, v):
        return fa._flash(q, k, v, None, True).astype(f32).sum()

    text = compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), ((2, 8192, 32, 128), bf16),
        ((2, 8192, 2, 128), bf16), ((2, 8192, 2, 128), bf16))
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)],
                         ids=["up", "down"])
def test_grouped_matmul_forward_and_backward(compile_for_chip, k, n):
    """8 held experts at the published expert width, the dropless worst
    case of 2 x 8192 tokens x 6 pairs in tiles of 512: the product, its
    transpose (dx) and the per-expert weight gradient."""
    tile_m, held = 512, 8
    tiles = 2 * 8192 * 6 // tile_m + held

    def loss(x, w, group, used):
        return jnp.square(
            gm._gmm_vjp(x, w, group, used, tile_m).astype(f32)).sum()

    text = compile_for_chip(
        jax.grad(loss, argnums=(0, 1)), ((tiles * tile_m, k), bf16),
        ((held, k, n), bf16), ((tiles,), i32), ((1,), i32))
    assert text.count("tpu_custom_call") >= 3


def test_int8_matmul(compile_for_chip):
    m, k, n = 2048, 2048, 8192

    def run(qx, qw, sx, sw):
        return qm._qmm_pallas(qx, qw, sx, sw, bf16)

    assert_kernel(compile_for_chip(
        run, ((m, k), i8), ((k, n), i8), ((m, 1), f32), ((1, n), f32)))


# ---------------------------------------------------------------------------
# the whole decode step: the cache is written where it lies
# ---------------------------------------------------------------------------
def _decode_engine(monkeypatch, kv_dtype=None):
    """An engine of 2 layers at gpt3-1.3b widths over 8 slots x 2048,
    whose decode step the tests below compile for the chip."""
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    # the CPU process's dispatch would take the composite: the compile
    # is for the chip, so say so here and not through an option
    monkeypatch.setattr(da, "decode_attention_available", lambda: True)
    heads, d = 16, 128
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=heads * d, num_layers=2,
        num_heads=heads, ffn_hidden_size=4 * heads * d, max_seq_len=SEQ))
    model.eval()
    return InferenceEngine(model, batch_slots=SLOTS, max_seq_len=SEQ,
                           cache_dtype=bf16, kv_dtype=kv_dtype,
                           prefill_buckets=[128])


def _compile_decode_step(eng, params, cache, operand):
    """The engine's jitted decode step over described operands;
    ``operand(array)`` describes a replicated one."""
    slots_i32 = operand(jnp.zeros(SLOTS, i32))
    slots_f32 = operand(jnp.zeros(SLOTS, f32))
    with persistent_cache_off():
        return jax.jit(eng._decode_fn, donate_argnums=(1,)).lower(
            params, cache, slots_i32, slots_i32, operand(eng._key),
            slots_f32, slots_f32).compile()


def _compile_on_one_chip(eng, one_chip):
    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=one_chip)
    return _compile_decode_step(
        eng, {k: struct(v, bf16) for k, v in eng.params.items()},
        jax.tree_util.tree_map(struct, eng.cache), struct)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_decode_step_never_copies_the_cache(one_chip, monkeypatch,
                                            kv_dtype):
    """The engine's jitted decode step, 2 layers at gpt3-1.3b widths over
    8 slots x 2048 with the cache donated: the attention kernel is in the
    program, every cache buffer comes out in the memory it went in by,
    and nothing the size of a layer stands beside them, neither as a
    temporary nor as a ``copy``.  (At the stacked seq-major layout of
    PR 23 this program needed 194.5 MiB of temporaries in bf16 and
    101.8 MiB in int8: a layer sliced out, transposed for the kernel and
    written back.)  Since PR 46 the bf16 step holds NO scatter: the
    attention kernel stores the tick's token itself, through outputs
    aliased to the buffers.  The int8 step keeps its four a layer (codes
    and scale planes of k and v): its scale planes lie position-minor
    and take ``write_kv``."""
    import re
    eng = _decode_engine(monkeypatch, kv_dtype)
    layers = eng.cache.num_layers
    compiled = _compile_on_one_chip(eng, one_chip)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= layers
    scatters = len(re.findall(r" scatter\(", text))
    assert scatters == (4 * layers if kv_dtype else 0)
    # k and v per layer (and their scale planes), and the lengths
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert len(leaves) == layers * (4 if kv_dtype else 2) + 1
    aliased = re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)",
                         text.split("\n", 1)[0])
    assert len(aliased) == len(leaves), text.split("\n", 1)[0][:400]
    layer_k = eng.cache.k[0]
    layer_bytes = layer_k.size * layer_k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    shape = ",".join(str(n) for n in layer_k.shape)
    assert not re.search(r"\[%s\]\S* copy\(" % shape, text), \
        "a whole cache layer is copied"


# ---------------------------------------------------------------------------
# the decode step's sampler: vocabulary-wide work only inside a branch
# ---------------------------------------------------------------------------
def _computations(text):
    """{name: body} of a compiled program's computations, and which of
    them each one calls."""
    import re
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", text, re.M | re.S)}
    calls = {name: set(re.findall(r"%([\w.-]+)", " ".join(re.findall(
        r"(?:to_apply|calls|body|condition|branch_computations)="
        r"(\{[^}]*\}|%[\w.-]+)", body)))) for name, body in bodies.items()}
    return bodies, calls


def _reachable(calls, roots):
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo.extend(calls.get(name, ()))
    return seen


def _outside_the_conditional(text, wanted):
    """Names of the computations that hold a line ``wanted`` matches and
    that the entry reaches without passing through the one
    ``conditional``'s branches."""
    import re
    bodies, calls = _computations(text)
    entry = re.search(r"^ENTRY %(\S+)", text, re.M).group(1)
    conds = [line for body in bodies.values()
             for line in body.splitlines() if " conditional(" in line]
    assert len(conds) == 1, [line[:200] for line in conds]
    branches = set(re.findall(r"%([\w.-]+)", re.search(
        r"branch_computations=\{([^}]*)\}", conds[0]).group(1)))
    assert len(branches) == 2
    holders = {name for name, body in bodies.items()
               if any(wanted(line) for line in body.splitlines())}
    assert holders <= _reachable(calls, branches) | {entry}
    return holders & _reachable(
        {n: c - branches for n, c in calls.items()}, [entry])


def test_decode_step_sorts_only_inside_a_branch(one_chip, monkeypatch):
    """The sampler of the compiled decode step: a ``conditional`` on its
    own ``temps``, the vocabulary's ``sort`` reachable only through a
    branch, and no ``gather`` (alone or fused, flattened or not) with a
    result of slots x vocabulary.  (At PR 26 the sort stood in the entry
    computation and a fused ``gather`` of ``f32[slots, vocab]``
    re-derived the sorted logits: 12.3 ms of a 32.5 ms tick on the
    chip.)"""
    import re
    eng = _decode_engine(monkeypatch)
    text = _compile_on_one_chip(eng, one_chip).as_text()
    assert " sort(" in text, "the sampling branch lost its sort"
    assert not _outside_the_conditional(text, lambda l: " sort(" in l)
    wide = {f"[{SLOTS},{VOCAB}]", f"[{SLOTS * VOCAB}]"}
    for line in text.splitlines():
        if " gather(" in line:
            shape = re.search(r"= \w+(\[[\d,]*\])", line).group(1)
            assert shape not in wide, line[:300]


# ---------------------------------------------------------------------------
# power retention's decode step: one pass over a state updated in place
# ---------------------------------------------------------------------------
def test_retention_step_updates_its_state_where_it_lies(one_chip):
    """The decode kernel at Brumby's published widths (40 query heads on
    8 KV heads of 128) over 4 slots, the state donated: one custom call,
    both halves of the state come out in the memory they went in by, and
    no temporary the size of a slot's state stands beside them."""
    pk = importlib.import_module("paddle_tpu.ops.power_retention_kernel")
    pr = importlib.import_module("paddle_tpu.ops.power_retention")
    slots, heads, kv_heads, d = 4, 40, 8, 128
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    state = pr.RetentionState(f32(slots, kv_heads, pr.state_rows(d), d),
                              f32(slots, kv_heads, d, d))
    assert pk.serves(f32(slots, heads, d), f32(slots, kv_heads, d),
                     f32(slots, kv_heads, d), state)
    with persistent_cache_off():
        compiled = jax.jit(
            lambda q, k, v, g, st: pk.step(q, k, v, g, st, 1e-6),
            donate_argnums=(4,)).lower(
                f32(slots, heads, d), f32(slots, kv_heads, d),
                f32(slots, kv_heads, d), f32(slots, kv_heads),
                state).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    held = (state.s.size + state.z.size) * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < held // slots


# ---------------------------------------------------------------------------
# the state-space decode step, and the step of a stack of two kinds of cache
# ---------------------------------------------------------------------------
def test_ssd_step_updates_its_state_where_it_lies(one_chip):
    """The Mamba-2 decode step (XLA's own form) at Granite 4.0-H's
    published widths (64 heads of 64 on a state of 128, one B/C group)
    over 8 slots, the state donated: the state comes out in the memory it
    went in by, no temporary the size of a slot's state stands beside
    it, and ONE fusion holds the update and the read-out."""
    import re
    ss = importlib.import_module("paddle_tpu.ops.ssd_scan")
    slots, heads, p, n = 8, 64, 64, 128
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    with persistent_cache_off():
        compiled = jax.jit(ss.ssd_step, donate_argnums=(5,)).lower(
            f32(slots, heads, p), f32(slots, heads), f32(heads),
            f32(slots, 1, n), f32(slots, 1, n),
            f32(slots, heads, p, n)).compile()
    held = slots * heads * p * n * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < held // slots
    state = r"f32\[%d,%d,%d,%d\]" % (slots, heads, p, n)
    readers = [line for line in compiled.as_text().splitlines()
               if " fusion(" in line and re.search(state, line)]
    assert len(readers) == 1, [line[:160] for line in readers]


def test_hybrid_decode_step_never_copies_a_state_or_a_row(one_chip,
                                                          monkeypatch):
    """The engine's jitted decode step over a Mamba-2 layer and an
    attention layer at Granite 4.0-H's published widths, 8 slots x 1024,
    the cache donated: the attention kernel is in the program, the step
    holds no scatter, and every leaf of the cache (state, window, k, v, lengths)
    comes out in the memory it went in by, with no copy of a state or of
    a layer's rows beside it."""
    import re
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import (GraniteHybridConfig,
                                   GraniteHybridForCausalLM)
    monkeypatch.setattr(da, "decode_attention_available", lambda: True)
    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        num_hidden_layers=2, layer_types=("mamba", "attention"),
        vocab_size=8192, max_seq_len=1024))
    model.eval()
    eng = InferenceEngine(model, batch_slots=SLOTS, max_seq_len=1024,
                          cache_dtype=bf16, prefill_buckets=[128])
    compiled = _compile_on_one_chip(eng, one_chip)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.findall(r" scatter\(", text)
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert len(leaves) == 2 + 2 + 1
    aliased = re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)",
                         text.split("\n", 1)[0])
    assert len(aliased) == len(leaves), text.split("\n", 1)[0][:400]
    assert eng.cache.layers[1].k.shape == (8, 4, 1024, 128)  # two heads a row
    for shape in ("8,64,64,128", "8,4,1024,128", "8,8,1024,64"):
        assert not re.search(r"\[%s\]\S* copy\(" % shape, text), shape


# ---------------------------------------------------------------------------
# the linear-attention / latent-attention stack's two mixers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,kernels", [("kda", 2), ("mla", 2)])
def test_kimi_mixer_at_published_widths(one_chip, monkeypatch, kind,
                                        kernels):
    """One KDA mixer (32 heads of 128 x 128 state, conv 4, chunks of 64,
    heads in rematerialised groups, the scan through its chunk kernels:
    the forward that keeps what the backward reads and the backward, both
    inside the ``kda_scan`` scope; the sum's gradient needs no first
    forward) and one latent-attention
    mixer (32 heads, 192-wide scores on 128-wide values through the flash
    kernels: forward and backward at least; no composite, no padding to
    256) at 2 x 8192 x 2304, forward and backward under remat: the chip's
    compiler takes them, and the KDA mixer's temporaries stay under 2.5
    GiB (the compile reads 2.19; with XLA's form of the scan 2.92)."""
    from paddle_tpu import ops
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.func import functional_call
    from paddle_tpu.models import kimi_linear as K
    # the CPU process's dispatch would take the composite: the compile
    # is for the chip, so say so here and not through an option
    for mod in (fa, ops):
        monkeypatch.setattr(mod, "flash_attention_available", lambda: True)
    monkeypatch.setattr(kk, "available", lambda: True)
    cfg = K.KimiLinearConfig(vocab_size=20480, num_hidden_layers=5,
                             held_experts=(0, 8))
    layer = {"kda": K.KDAMixer, "mla": K.MLAttention}[kind](cfg)
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)
    params = {n: struct(p.shape, bf16) for n, p in layer.named_parameters()}
    x = struct((2, 8192, 2304), bf16)

    @jax.checkpoint
    def forward(params, x):
        with no_grad():
            return functional_call(layer, params, {}, x)[0]

    loss = lambda params, x: forward(params, x).astype(f32).sum()
    ops.kernel_paths.reset()
    with persistent_cache_off():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile()
    if kind == "mla":
        assert compiled.as_text().count("tpu_custom_call") >= kernels
        assert ops.kernel_paths.counts()["flash_attention"] == \
            {"kernel": 1, "composite": 0}
        assert "256]" not in "".join(
            line for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line)
    else:
        calls = [line for line in compiled.as_text().splitlines()
                 if "tpu_custom_call" in line]
        assert len(calls) == kernels
        assert all("kda_scan" in line for line in calls)
        assert [sum(name in line for line in calls)
                for name in ("kda_chunk_fwd", "kda_chunk_bwd")] == [1, 1]
        assert ops.kernel_paths.counts()["kda_scan"] == \
            {"kernel": 1, "composite": 0}
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2 ** 30


# ---------------------------------------------------------------------------
# the dropless layer: the worst-case buffer only inside a branch
# ---------------------------------------------------------------------------
def test_dropless_layer_runs_its_worst_case_only_inside_a_branch(
        one_chip, monkeypatch):
    """One routed layer at the published widths (2 x 8192 tokens, top 6
    of 128, experts 0-8 held), forward and backward under remat: a
    ``conditional`` each (the remat's second forward is dead code), whose
    one branch runs every grouped-matmul kernel over 32 tiles (16,384
    rows: twice the 6,144 pairs to expect, and a part tile an expert) and
    whose other over the 200 of the worst case; no kernel outside them."""
    import re
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.distributed import moe
    from paddle_tpu.func import functional_call
    # the CPU process's dispatch would take the composite: the compile
    # is for the chip, so say so here and not through an option
    monkeypatch.setattr(gm, "grouped_matmul_available", lambda: True)
    layer = moe.MoELayer(2688, 1856, num_experts=128, top_k=6,
                         capacity_factor=None, routed_scaling=2.5,
                         held_experts=(0, 8), activation="relu2")
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)
    params = {n: struct(p.shape, f32) for n, p in layer.named_parameters()}
    bufs = {n: struct(b.shape, i32) for n, b in layer.named_buffers()}
    x = struct((2, 8192, 2688), bf16)

    @jax.checkpoint
    def forward(params, bufs, x):
        with no_grad():
            return functional_call(layer, params, bufs, x)

    def loss(params, bufs, x):
        y, bufs = forward(params, bufs, x)
        return y.astype(f32).sum(), bufs

    with persistent_cache_off():
        text = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 2), has_aux=True)).lower(
                params, bufs, x).compile().as_text()
    bodies, calls = _computations(text)
    kernel_tiles = lambda names: sorted(
        int(n) for name in names for n in re.findall(
            r'custom_call_target="tpu_custom_call", '
            r'operand_layout_constraints=\{s32\[(\d+)\]', bodies[name]))
    conds = [line for body in bodies.values()
             for line in body.splitlines() if " conditional(" in line]
    assert len(conds) == 2, [line[:200] for line in conds]
    worst = 2 * 8192 * 6 // moe.DROPLESS_TILE + 8
    short = moe.dropless_short_tiles(2 * 8192, 6, 8, 128,
                                     moe.DROPLESS_TILE)
    assert (short, worst) == (32, 200)
    in_branches = set()
    for line, kernels in zip(sorted(conds, key=len), (2, 6)):
        branches = re.findall(r"%([\w.-]+)", re.search(
            r"branch_computations=\{([^}]*)\}", line).group(1))
        reached = [_reachable(calls, [name]) for name in branches]
        # forward: up and down; backward: those again, dx and dw of each
        assert sorted(kernel_tiles(r) for r in reached) == \
            [[short] * kernels, [worst] * kernels]
        in_branches |= reached[0] | reached[1]
    assert not kernel_tiles(set(bodies) - in_branches)


def test_mesh_decode_step_gains_no_collective(topo, monkeypatch):
    """The same step over a described 2x2 mesh (dp x tp, weights and
    cache laid out by the engine's own rules): the conditional's
    predicate is a replicated scalar, so the program carries no more
    collectives than with the sampler of PR 26 (kept in
    tests/test_sampler.py), and none as wide as the tp shard of the
    vocabulary outside the branches.  (The sorted logits come back from
    the sort by an all-to-all where the gather all-gathered them.)"""
    import re
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed.mesh import compile_mesh_guard
    from test_sampler import old_sample_from_logits
    eng = _decode_engine(monkeypatch)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))

    def described(mesh, a, dims):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=eng._spec_for(mesh, a, dims))

    # the engine's own layout rules, handed shapes in place of arrays
    monkeypatch.setattr(eng, "_put", described)
    params = {k: jax.ShapeDtypeStruct(v.shape, bf16, sharding=v.sharding)
              for k, v in eng._shard_params_over(mesh, eng.params,
                                                 eng.model).items()}
    cache = eng._shard_dense_cache_arrays(mesh, eng.cache)

    def replicated(a):
        return described(mesh, a, (None,) * a.ndim)

    collective = re.compile(
        r" (all-reduce|all-gather|all-to-all|collective-permute"
        r"|reduce-scatter)(?:-start)?\(")

    def program():
        with compile_mesh_guard(mesh):
            return _compile_decode_step(eng, params, cache,
                                        replicated).as_text()

    text = program()
    monkeypatch.setattr(
        eng, "_sample_from_logits",
        lambda *a: old_sample_from_logits(eng.top_k, *a))
    assert len(collective.findall(text)) <= \
        len(collective.findall(program()))

    def vocab_wide(line):
        return collective.search(line) and re.search(
            r"= \w+\[[\d,]*\b%d\b" % (VOCAB // 2), line)
    assert any(vocab_wide(line) for line in text.splitlines())
    assert not _outside_the_conditional(text, vocab_wide)


# ---------------------------------------------------------------------------
# chip_smoke.py's rehearsal (a CPU-only child: the parent test process is a
# CPU process too, and JAX_PLATFORMS=cpu is what --rehearse pins)
# ---------------------------------------------------------------------------
def _run_smoke(*args):
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py"), *args],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
        capture_output=True, text=True, timeout=600)


def test_chip_smoke_rehearsal_last_line_names_the_cpu():
    import json
    proc = _run_smoke("--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    # a rehearsal can never be taken for a chip run
    assert verdict == {"ok": True, "rehearsal": True,
                       "device": {"platform": "cpu", "kind": "cpu",
                                  "count": 1}}
    phases = [json.loads(ln)["phase"] for ln in lines[:-1]]
    assert phases == ["start", "train", "serve", "serve", "serve_parity",
                      "done"]
    serve = [json.loads(ln) for ln in lines if '"phase": "serve"' in ln]
    assert [s["layout"] for s in serve] == ["dense", "paged"]
    for s in serve:
        assert s["compiles_after_warmup"] == 0
        assert s["traces_after_warmup"] == 0
        assert s["donate"] is True
        assert s["decode_kernel_paths"]["composite"] == 0
    parity = json.loads(lines[-3])
    # every generated token of every request went through the reference
    assert parity["tokens_checked"] == 3 * 6 == parity["argmax_equal"]
    assert parity["max_logit_deficit"] <= parity["logit_tol"]


def test_chip_smoke_reference_check_catches_a_changed_context():
    """The serving check is against logits of a plain forward over the
    whole sequence, for every generated token: served tokens pass it, and
    the same tokens fail it once a single early prompt token differs —
    what a lost or misplaced KV entry looks like."""
    import chip_smoke as cs
    from paddle_tpu.func import functional_state
    sz = cs.Sizes(True)
    model, prompts = cs.build_serve_model(sz), cs.make_prompts(sz)
    params, _ = functional_state(model)
    fa.set_interpret_mode(True)
    try:
        toks, _, _ = cs.run_serve(sz, model, "paged", prompts)
    finally:
        fa.set_interpret_mode(False)
    ok = cs.check_against_reference("served", params, sz.serve_cfg,
                                    prompts, toks, sz.bucket)
    assert ok["tokens_checked"] == 18 and ok["distinct_tokens"] > 4
    changed = [p.copy() for p in prompts]
    for p in changed:
        p[3] = (p[3] + 1) % sz.serve_cfg.vocab_size
    with pytest.raises(AssertionError, match="the plain forward chooses"):
        cs.check_against_reference("changed", params, sz.serve_cfg,
                                   changed, toks, sz.bucket)


def test_chip_smoke_fails_without_a_chip_and_on_a_failed_phase():
    import json
    # no chip and no rehearsal option: non-zero, and no verdict line
    proc = _run_smoke()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    # a phase that raises: non-zero, and the last line is not a verdict
    proc = _run_smoke("--rehearse", "--fail-phase", "serve")
    assert proc.returncode != 0
    assert "forced failure in phase serve" in proc.stderr
    last = proc.stdout.strip().splitlines()[-1]
    assert "ok" not in json.loads(last)
