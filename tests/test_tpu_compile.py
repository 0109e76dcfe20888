"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is described, not attached. These cases hand it the main
path's kernels at their real widths, so what Mosaic or XLA would refuse
on the chip (a tile that is not aligned, too much VMEM, a kernel GSPMD
cannot partition, a program that does not fit HBM) fails here, at no chip
time. A compile that passes is not a chip run: nothing executes, so these
say nothing about results or speed (chip_smoke.py does).

Everything that touches the topology lives in module-scoped fixtures of
THIS file: only one process at a time may load libtpu, pytest-xdist
workers each import every test file, and a second file's fixture would
find the library taken.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # compile what the program runs: another test file raises the
    # process-wide matmul precision as it is imported, and Mosaic refuses
    # the flash kernels' bf16 operands at fp32 contract precision
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(tree, sharding):
    """A pytree of arrays or shapes -> the same shapes placed on the
    described chip."""
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


# (batch, seq, q heads, kv heads) at head_dim 128
FLASH_SHAPES = {
    "1b-train-4x2048": (4, 2048, 16, 8),
    "8b-prefill-512": (1, 512, 32, 8),
    "400m-8k": (1, 8192, 8, 4),
}


def _qkv(shape, sharding):
    b, s, hq, hkv = shape
    return (_sds((b, s, hq, 128), jnp.bfloat16, sharding),
            _sds((b, s, hkv, 128), jnp.bfloat16, sharding),
            _sds((b, s, hkv, 128), jnp.bfloat16, sharding))


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(), ids=FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, shape):
    from ray_tpu.ops.attention import flash_attention_tpu

    fwd = functools.partial(flash_attention_tpu, causal=True)
    assert _custom_calls(fwd, *_qkv(shape, one_chip)) == 1


@pytest.mark.parametrize("shape, window", [
    ((1, 8192, 28, 4), 4096), ((1, 12288, 28, 4), 4096),
    ((1, 512, 28, 4), 4096), ((2, 2048, 8, 4), 300)],
    ids=["28x4-heads-8k", "28x4-heads-12k", "shorter-than-the-window",
         "edge-inside-a-block"])
def test_flash_window_forward_compiles_for_v5e(one_chip, shape, window):
    """The window kernel (key blocks behind the window neither computed
    nor fetched: the index map holds at the nearest block that is read)
    is one Mosaic kernel under its own name; without a window the causal
    kernel is what it was, unnamed."""
    from ray_tpu.ops.attention import WINDOW_KERNEL, flash_attention_tpu

    args = _qkv(shape, one_chip)
    text = jax.jit(functools.partial(
        flash_attention_tpu, causal=True, window=window)).lower(
            *args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and WINDOW_KERNEL in text
    plain = jax.jit(functools.partial(
        flash_attention_tpu, causal=True)).lower(*args).compile().as_text()
    assert WINDOW_KERNEL not in plain


def _kernel_calls(text):
    """[(instruction name, operand count)] of a compiled program's
    Mosaic kernels."""
    return [(m.group(1), len(m.group(2).split(",")))
            for m in re.finditer(
                r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
                r'custom_call_target="tpu_custom_call"', text)]


_NO_EVENT = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


def _device_op_kinds(text):
    """The kinds (a trace's: an instruction's name without its number)
    of the instructions a compiled program runs as device operations:
    those of its entry computation and of every loop body and branch."""
    run = set(re.findall(
        r"(?:body|condition|true_computation|false_computation)="
        r"%([\w.\-]+)", text))
    for group in re.findall(r"branch_computations=\{([^}]*)\}", text):
        run |= {c.strip().lstrip("%") for c in group.split(",")}
    kinds, inside = set(), False
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = bool(head.group(1)) or head.group(2) in run
            continue
        op = inside and re.match(
            r"^\s+(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(", line)
        if op and op.group(2) not in _NO_EVENT:
            kinds.add(re.sub(r"[.\d]+$", "", op.group(1)) or op.group(1))
    return kinds


@pytest.mark.parametrize("widths, window, name", [
    ((128, 128), None, None), ((128, 128), 4096, "flash_window_fwd"),
    ((192, 128), None, "flash_mla_fwd")],
    ids=["causal", "window", "own-value-width"])
def test_flash_forward_with_lengths_is_the_same_kernel(
        one_chip, widths, window, name):
    """Told the rows' lengths, the forward kernel is still ONE Mosaic
    kernel under the name it had, with the lengths as a fourth (scalar)
    operand; not told, it takes its three operands as it always did."""
    from ray_tpu.ops.attention import flash_attention_tpu

    d, dv = widths
    q = _sds((2, 8192, 8, d), jnp.bfloat16, one_chip)
    k = _sds((2, 8192, 4, d), jnp.bfloat16, one_chip)
    v = _sds((2, 8192, 4, dv), jnp.bfloat16, one_chip)
    fwd = functools.partial(flash_attention_tpu, causal=True, window=window)

    def told(q, k, v, lengths):
        return fwd(q, k, v, lengths=lengths)

    for fn, args, operands in (
            (told, (q, k, v, _sds((2,), jnp.int32, one_chip)), 4),
            (fwd, (q, k, v), 3)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        (called, n), = _kernel_calls(text)
        assert n == operands
        if name is None:
            assert "flash_" not in called
        else:
            assert called.startswith(name)


def _mosaic_calls(text):
    """[(instruction name, grid, VMEM limit asked, VMEM used)] of a
    compiled program's Mosaic kernels (a limit is None where the kernel
    asks for none); the grid is read from the kernel's own module, which
    the custom call carries as bytecode."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r"%([\w.\-]+) = ", line).group(1)
        asked, used = (int(m.group(1)) if m else None for m in (re.search(
            key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
            line) for key in (
                '"scoped_memory_configs', '"used_scoped_memory_configs')))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = str(ir.Module.parse(base64.b64decode(
                re.search(r'"body":"([^"]*)"', line).group(1))))
        grid = tuple(int(n) for n in re.search(
            r"iteration_bounds = array<i64: ([-\d, ]+)>", module).group(1)
            .split(","))
        calls.append((name, grid, asked, used))
    return calls


# (batch, rows, q heads, kv heads, q/k width, v width), window, the
# kernel's name in a trace: the prefill buckets of the four serve cells
CELL_FLASH = {
    "deepseekv2-longdoc-16384": ((1, 16384, 128, 128, 192, 128), None,
                                 "flash_mla_fwd"),
    "smallthinker-window-12288": ((1, 12288, 28, 4, 128, 128), 4096,
                                  "flash_window_fwd"),
    "smallthinker-full-12288": ((1, 12288, 28, 4, 128, 128), None, None),
    "olmoe-docqa-2048": ((1, 2048, 16, 16, 128, 128), None, None),
    "mistral7b-chat-1024": ((1, 1024, 32, 8, 128, 128), None, None),
}


@pytest.mark.parametrize("shape, window, name", CELL_FLASH.values(),
                         ids=CELL_FLASH)
def test_flash_forward_walks_its_key_blocks_at_a_cells_bucket(
        one_chip, shape, window, name):
    """At each serve cell's longest bucket, told the prompt's length,
    the forward kernel is one Mosaic kernel under the name it had, whose
    grid is what ``flash_forward_steps`` says (the whole head's keys a
    grid step: one key chunk) and which compiles inside the VMEM limit
    ``_pick_chunk`` asks for."""
    from ray_tpu.ops.attention import (
        _pick_chunk, flash_attention_tpu, flash_forward_steps)

    b, s, hq, hkv, d, dv = shape

    def told(q, k, v, lengths):
        return flash_attention_tpu(q, k, v, causal=True, window=window,
                                   lengths=lengths)

    text = jax.jit(told).lower(
        _sds((b, s, hq, d), jnp.bfloat16, one_chip),
        _sds((b, s, hkv, d), jnp.bfloat16, one_chip),
        _sds((b, s, hkv, dv), jnp.bfloat16, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile().as_text()
    (called, grid, asked, used), = _mosaic_calls(text)
    if name is None:
        assert "flash_" not in called
    else:
        assert called.startswith(name)
    block = min(s, 512)
    chunk, limit = _pick_chunk(s, block, d, dv, 2)
    assert chunk == s // block              # the whole head is resident
    steps = flash_forward_steps(s, s, block, block, chunk, window=window)
    assert grid == (b * hq, s // block, steps.steps // (s // block))
    assert used <= asked == limit <= 64 * 2**20


def test_flash_forward_with_lse_under_shard_map(topo):
    """The train cell's forward (fsdp=2 x tp=2: 16 of 32 query heads
    and 4 of 8 key heads a chip, 2,048 rows, the LSE kept for the
    backward) under ``shard_map``: one kernel a chip, unnamed, its grid
    ``flash_forward_steps``'s."""
    from jax import shard_map

    from ray_tpu.ops.attention import (
        _pick_chunk, flash_attention_tpu, flash_forward_steps)
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), topo.devices)
    heads = P("fsdp", None, "tp", None)
    fwd = shard_map(
        functools.partial(flash_attention_tpu, causal=True, return_lse=True),
        mesh=mesh, in_specs=(heads,) * 3,
        out_specs=(heads, P("fsdp", "tp", None)), check_vma=False)
    args = [_sds((4, 2048, h, 128), jnp.bfloat16, NamedSharding(mesh, heads))
            for h in (32, 8, 8)]
    (called, grid, asked, used), = _mosaic_calls(
        jax.jit(fwd).lower(*args).compile().as_text())
    assert "flash_" not in called
    chunk, limit = _pick_chunk(2048, 512, 128, 128, 2)
    steps = flash_forward_steps(2048, 2048, 512, 512, chunk)
    assert steps == (4, 10, 4)
    assert grid == (2 * 16, 4, 1) and used <= asked == limit


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(), ids=FLASH_SHAPES)
def test_flash_forward_backward_compiles_for_v5e(one_chip, shape):
    from ray_tpu.ops.attention import attention

    def loss(q, k, v):
        return attention(q, k, v, causal=True,
                         use_pallas=True).astype(jnp.float32).sum()

    # forward, dq, and dk/dv: three kernels
    assert _custom_calls(jax.grad(loss, argnums=(0, 1, 2)),
                         *_qkv(shape, one_chip)) == 3


@pytest.mark.parametrize("layout", ["table-rectangle", "flat-list-64"])
def test_decode_burst_8b_int8_fits_one_v5e(one_chip, layout):
    """The whole decode program chip_smoke.py serves with: Llama-3-8B,
    int8 weights, 8 slots, from shapes alone: over the table's rectangle
    (no list: the call ``benchmarks/aot_fit.py`` makes) and over one flat
    list of 64 live pages with the steps as an operand (what the engine
    runs). Either copies the listed pages ONCE: the compiled text holds
    the uninitialised buffers the loop fills and none of ``jnp.take``'s
    second and third passes (the zero fill was a ``broadcast``, the
    transposition a ``copy_select_fusion``), nor a ``copy`` of the
    loop's result."""
    from ray_tpu.llm.runner import decode_burst
    from ray_tpu.models import LLAMA_CONFIGS
    from ray_tpu.ops import rope_frequencies
    from ray_tpu.ops.quant import init_params_quantized

    cfg = LLAMA_CONFIGS["8b"]
    B, K, page, n_pages, max_seq = 8, 8, 64, 129, 1024

    params = _on_chip(jax.eval_shape(
        lambda: init_params_quantized(jax.random.PRNGKey(0), cfg)), one_chip)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.head_dim, max_seq, cfg.rope_theta)), one_chip)
    cache = _sds((cfg.n_layers, n_pages, page, cfg.n_kv_heads,
                  cfg.head_dim), cfg.dtype, one_chip)
    i32 = _sds((B,), jnp.int32, one_chip)
    f32 = _sds((B,), jnp.float32, one_chip)
    listed = () if layout == "table-rectangle" else (
        _sds((3, 64), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    compiled = decode_burst.lower(
        params, cache, cache, i32, i32,
        _sds((B, 8 if not listed else max_seq // page), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip), cos, sin, i32, f32, i32, f32, None,
        *listed, cfg=cfg, n_steps=K, greedy=True).compile()
    mem = compiled.memory_analysis()
    # the donated cache aliases its output; everything else is live at once
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 8 * 1024**3 < live < V5E_HBM_BYTES, mem
    text = compiled.as_text()
    assert text.count('custom_call_target="AllocateBuffer"') >= 2
    assert "copy_select_fusion" not in text
    assert " copy(%while" not in text
    gathered = f"bf16[{cfg.n_layers},64,{page},"      # the copy, page-major
    assert not [line for line in text.splitlines()
                if " broadcast(" in line and gathered in line]


# the two served configurations of BENCHMARK.json at their published
# widths and depths: LlamaConfig fields, then the engine's prefill bucket
# (its max_seq_len); both pools are 257 pages of 64
SERVED_PREFILLS = {
    "mistral-7b-int8-bucket1024": (dict(
        vocab=32768, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        mlp_dim=14336, max_seq=32768, rope_theta=1e6), 1024),
    "olmoe-1b-7b-int8-bucket2048": (dict(
        vocab=50304, dim=2048, n_layers=16, n_heads=16, n_kv_heads=16,
        mlp_dim=1024, max_seq=4096, rope_theta=1e4, n_experts=64, top_k=8,
        norm_topk_prob=False, qk_norm=True), 2048),
}


@pytest.mark.parametrize("widths, bucket", SERVED_PREFILLS.values(),
                         ids=SERVED_PREFILLS)
def test_prefill_sample_keeps_the_page_pool_out_of_its_layer_scan(
        one_chip, widths, bucket):
    """Whole-prompt prefill writes one prompt's rows: the donated pools
    must alias their outputs, and the program may hold no temporary of a
    pool's size (a pool that rides the layer scan as xs/ys is copied
    whole: 2.36 GB of temporaries at OLMoE's bucket, PERF.md section 4)."""
    from ray_tpu.llm.runner import prefill_sample
    from ray_tpu.models import LlamaConfig
    from ray_tpu.ops import rope_frequencies
    from ray_tpu.ops.quant import init_params_quantized

    cfg = LlamaConfig(**widths)
    page, n_pages = 64, 257

    params = _on_chip(jax.eval_shape(
        lambda: init_params_quantized(jax.random.PRNGKey(0), cfg)), one_chip)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.head_dim, cfg.max_seq, cfg.rope_theta)), one_chip)
    cache = _sds((cfg.n_layers, n_pages, page, cfg.n_kv_heads,
                  cfg.head_dim), cfg.dtype, one_chip)
    pool = cache.size * cache.dtype.itemsize
    assert pool == 257 * 64 * 128 * 1024 // 2      # 128 KiB a token, K or V
    one_i = _sds((1,), jnp.int32, one_chip)
    one_f = _sds((1,), jnp.float32, one_chip)
    mem = prefill_sample.lower(
        params, cache, cache, _sds((1, bucket), jnp.int32, one_chip), one_i,
        _sds((1, bucket // page), jnp.int32, one_chip), cos, sin, 0, one_f,
        one_i, one_f, None, cfg=cfg, greedy=True).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool, mem
    assert mem.temp_size_in_bytes < pool, mem


def _sharded_step(topo, cfg, chips=4, dump=None):
    """``make_train_step`` over ``cfg`` and 4 x 2,048 tokens on fsdp=2 x
    tp=2 of the four described chips (or on one of them), lowered on
    shapes and compiled: ``(compiled, state)``. ``dump``: a directory the
    compiler writes its buffer assignment to (``_hbm_used``)."""
    import optax

    from ray_tpu.models import init_params, lm_loss, param_logical_axes
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import DEFAULT_RULES, shard_pytree
    from ray_tpu.train import make_train_step
    from ray_tpu.train.step import (TrainState, _batch_sharding,
                                    opt_state_shardings)

    mesh = build_mesh(MeshSpec(fsdp=2, tp=2) if chips == 4 else MeshSpec(),
                      topo.devices[:chips])
    optimizer = optax.adamw(3e-4)
    axes = param_logical_axes(cfg)
    _init, step_fn, _place = make_train_step(
        lambda p, b: lm_loss(p, b, cfg, mesh=mesh), optimizer, mesh, axes)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: _sds(a.shape, a.dtype, s), tree, shardings)

    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    param_sh = shard_pytree(params, axes, mesh, DEFAULT_RULES)
    state = TrainState(
        step=_sds((), jnp.int32, NamedSharding(mesh, P())),
        params=placed(params, param_sh),
        opt_state=placed(
            jax.eval_shape(optimizer.init, params),
            opt_state_shardings(optimizer, params, param_sh, mesh)))
    batch = {"tokens": _sds((4, 2048), jnp.int32,
                            _batch_sharding(mesh, DEFAULT_RULES))}
    options = {"xla_dump_to": str(dump),
               "xla_dump_hlo_as_text": True} if dump else None
    return step_fn.lower(state, batch).compile(
        compiler_options=options), state


# a v5e leaves a program 15.75 GiB, of which the compiler keeps this for
# itself ("reserved 258.00M" in its out-of-memory message)
V5E_HBM, V5E_RESERVED = 15.75 * 1024**3, 258 * 1024**2


def _hbm_used(compiled, dump) -> int:
    """What a step compiled with ``dump`` needs of a device by the
    compiler's own account: the buffer assignment's ``preallocated-temp``
    (the ``HLO temp`` of its out-of-memory message, to the byte), the
    arguments (the state, donated: the outputs live in them) and what
    the compiler reserves. ``memory_analysis()``'s temporaries count
    every stacked residual of the layer scan twice and are no measure."""
    temp = max(
        int(size) for path in dump.glob("*buffer-assignment.txt")
        for size in re.findall(r"allocation \d+: size (\d+), "
                               r"preallocated-temp", path.read_text()))
    return (V5E_RESERVED + temp
            + compiled.memory_analysis().argument_size_in_bytes)


# 1b's head layout (16/8 heads of 128) at a fraction of its depth
SHARDED_WIDTHS = dict(vocab=32768, dim=2048, n_layers=2, n_heads=16,
                      n_kv_heads=8, mlp_dim=4096, max_seq=2048)


def test_sharded_train_step_keeps_flash_kernels(topo):
    """fsdp=2 x tp=2 over the four described chips: GSPMD cannot partition
    a Mosaic kernel, so the model runs attention under shard_map there.
    The step must compile, keep the kernels, and spread its state."""
    from ray_tpu.models import LlamaConfig

    compiled, state = _sharded_step(topo, LlamaConfig(**SHARDED_WIDTHS))
    assert compiled.as_text().count("tpu_custom_call") >= 3
    whole = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 0.3 * whole, (per_device, whole)


@pytest.fixture(scope="module")
def sharded_text(topo):
    """The compiled text of the sharded step where a layer's checkpoint
    keeps ``policy`` (None: the default), compiled once a policy."""
    from ray_tpu.models import LlamaConfig

    @functools.cache
    def text(policy):
        asked = {"remat_policy": policy} if policy else {}
        return _sharded_step(
            topo, LlamaConfig(**SHARDED_WIDTHS, **asked))[0].as_text()

    return text


def _all_reduces(text):
    return [line for line in text.splitlines()
            if re.search(r"= \S+ all-reduce(-start)?\(", line)]


@pytest.mark.parametrize("policy, again, kernels", [
    (None, 0, 3), ("attn", 0, 3), ("full", 1, 4)],
    ids=["default", "attn", "full"])
def test_sharded_backward_pass_keeps_what_attention_gave(
        sharded_text, policy, again, kernels):
    """What a layer's checkpoint keeps, read from the compiled step:
    by default and with ``"attn"`` the backward body holds no all-reduce
    of the recomputed output product
    (``rematted_computation/bshk,hkd->bsd``: the product's RESULT is
    kept, after its all-reduce over ``tp``) and the program three flash
    kernels (the forward's output and LSE are kept, so the forward kernel
    is not run again); ``"full"`` holds that all-reduce and the fourth
    kernel."""
    text = sharded_text(policy)
    reduced = [line for line in _all_reduces(text)
               if "rematted_computation/bshk,hkd->bsd" in line]
    assert len(reduced) == again, reduced
    assert ("rematted_computation/bshk,hkd->bsd" in text) == bool(again)
    assert text.count("tpu_custom_call") == kernels


def test_sharded_backward_pass_keeps_the_up_product(sharded_text):
    """The default keeps the up product's result beside what ``"attn"``
    keeps: the backward body recomputes gate alone, so the text mentions
    ``rematted_computation/bsd,dm->bsm`` less often, and the save costs
    no collective: the program's all-reduces (those of the backward body
    among them: the input gradients of ``bsd,dm->bsm`` and
    ``bsd,dhk->bshk``) are ``"attn"``'s, shape for shape and product for
    product."""
    default, attn = sharded_text(None), sharded_text("attn")
    again = "rematted_computation/bsd,dm->bsm"
    assert 0 < default.count(again) < attn.count(again)

    def reduced(text):
        return sorted(
            (re.search(r"= (\S+) all-reduce", line).group(1),
             "".join(re.findall(r'op_name="([^"]*)"', line)))
            for line in _all_reduces(text))

    assert reduced(default) == reduced(attn)
    assert any("checkpoint/bsd,dm->bsm" in op for _, op in reduced(default))


# Mistral-7B-v0.3's widths at the depth the four-chip train cell runs
# (benchmarks/configs/mistral-7b-v0.3-train-24l.json), 4 x 2,048 tokens
TRAIN_CELL_WIDTHS = dict(vocab=32768, dim=4096, n_layers=24, n_heads=32,
                         n_kv_heads=8, mlp_dim=14336, max_seq=32768,
                         rope_theta=1e6, norm_eps=1e-5)


def test_train_cell_step_fits_with_what_its_layers_keep(topo, tmp_path):
    """The four-chip train cell's step with the default saves, by the
    compiler's own buffer assignment, which is what refuses a program
    that does not fit: 14.48 of the 15.75 GiB a v5e leaves a program,
    held here to 15.0. ``memory_analysis()`` counts a stacked residual of
    the layer scan twice (the layer's input too, in every policy) and
    reads 18.4 GB, over the chip, for this step that runs on it. The
    compiler takes the same step TWO LAYERS DEEPER (0.65 GB more state a
    device, 15.54 GiB) and refuses it four layers deeper, which shows
    the refusal is live in a compile for a described chip."""
    from ray_tpu.models import LlamaConfig

    cfg = LlamaConfig(**TRAIN_CELL_WIDTHS)
    compiled = _sharded_step(topo, cfg, dump=tmp_path)[0]
    used = _hbm_used(compiled, tmp_path)
    assert 14.0 * 1024**3 < used < 15.0 * 1024**3, used
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert live > V5E_HBM, live
    assert compiled.as_text().count("tpu_custom_call") == 3

    deeper = _sharded_step(topo, dataclasses.replace(cfg, n_layers=26))[0]
    grown = deeper.memory_analysis().argument_size_in_bytes
    assert grown - mem.argument_size_in_bytes > 0.6e9
    with pytest.raises(Exception, match="Ran out of memory in memory "
                                        "space hbm"):
        _sharded_step(topo, dataclasses.replace(cfg, n_layers=28))


def test_one_chip_1b_step_fits_with_the_input_kept_alone(topo, monkeypatch):
    """Who stands at the memory limit asks for ``remat_policy="full"``:
    chip_smoke.py's one-chip step (``LLAMA_CONFIGS["1b"]``, 4 x 2,048,
    adamw) is taken by the compiler with a layer's input kept alone
    (15.28 of 15.75 GiB; with the default saves it is refused by 0.3)."""
    import sys

    from ray_tpu.models import LLAMA_CONFIGS

    # the one-device path asks the default backend, the CPU here
    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_on_tpu",
                        lambda x: True)
    full = _sharded_step(topo, dataclasses.replace(
        LLAMA_CONFIGS["1b"], remat_policy="full"), chips=1)[0]
    assert full.as_text().count("tpu_custom_call") == 4


# experts, hidden width, expert width, experts a token; a decode step's
# rows (8 slots) and the longest prefill bucket's
ROUTED_WIDTHS = {
    "olmoe": (64, 2048, 1024, 8, (64, 16384)),
    "smallthinker": (64, 2560, 768, 6, (48, 73728)),
}


@pytest.mark.parametrize("regime", [0, 1], ids=["decode", "prefill"])
@pytest.mark.parametrize("widths", ROUTED_WIDTHS.values(),
                         ids=ROUTED_WIDTHS)
def test_routed_expert_layer_reads_int8_experts_without_a_wide_copy(
        one_chip, widths, regime):
    """The dropless layer at the served families' widths (OLMoE: 64
    experts of 1024 on 2048, 8 a token; SmallThinker: 64 of 768 on 2560,
    6 a token, whole-width tiles of 2,560 x 768): three grouped Pallas
    products under the kernel's own name, and the experts stay the int8
    stacks they are stored as: no bf16 or float32 tensor of a layer's
    experts, and no int8 copy of one layer, in the program."""

    from ray_tpu.ops.moe import GROUPED_KERNEL, moe_mlp_routed

    L = 2
    E, d, m, k, rows = widths
    rows = rows[regime]

    def quantized(shape):
        return {"q": _sds(shape, jnp.int8, one_chip),
                "s": _sds(shape[:2] + shape[3:], jnp.float32, one_chip)}

    def layer(x, router, w_gate, w_up, w_down, valid, index):
        return moe_mlp_routed(x, router, w_gate, w_up, w_down, top_k=k,
                              norm_topk_prob=False, valid=valid,
                              layer=index)

    tokens = rows // k
    text = jax.jit(layer).lower(
        _sds((1, tokens, d), jnp.bfloat16, one_chip),
        _sds((d, E), jnp.float32, one_chip), quantized((L, E, d, m)),
        quantized((L, E, d, m)), quantized((L, E, m, d)),
        _sds((1, tokens), jnp.bool_, one_chip),
        _sds((), jnp.int32, one_chip)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert GROUPED_KERNEL in text
    experts = r"\[(?:%d,)?%d,(?:%d,%d|%d,%d)\]" % (L, E, d, m, m, d)
    assert not re.findall(r"(?:bf16|f32)" + experts, text)
    assert set(re.findall(r"s8" + experts, text)) == {
        f"s8[{L},{E},{d},{m}]", f"s8[{L},{E},{m},{d}]"}


# DeepSeek-V2's widths as the benchmark's configuration runs them (one
# chip's share: 40 of 160 experts, a quarter of the vocabulary), at two
# layers: the leading dense one and one expert layer
LATENT_WIDTHS = dict(
    vocab=25600, dim=5120, n_layers=2, n_heads=128, n_kv_heads=128,
    mlp_dim=1536, max_seq=163840, rope_theta=1e4, norm_eps=1e-6,
    n_experts=160, top_k=6, norm_topk_prob=False,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, attn_scale=0.114721, n_dense_layers=1,
    dense_mlp_dim=12288, n_shared_experts=2, n_group=8, topk_group=3,
    routed_scale=16.0, experts_held=(0, 40),
    rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
                  ("mscale", 0.707), ("mscale_all_dim", 0.707),
                  ("original_max_position_embeddings", 4096),
                  ("type", "yarn")))


@pytest.fixture
def latent(one_chip, monkeypatch):
    """The programs' operands on the described chip. ``attention`` asks
    the default backend whether it is a TPU, and here that is the CPU:
    these compiles ARE for a TPU, so the dispatcher is told so."""
    import sys

    import ray_tpu.ops  # noqa: F401  (the package's ``attention`` is the function)
    from ray_tpu.models import LlamaConfig
    from ray_tpu.ops import rope_frequencies
    from ray_tpu.ops.quant import init_params_quantized

    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_on_tpu",
                        lambda x: True)
    cfg = LlamaConfig(**LATENT_WIDTHS)
    params = _on_chip(jax.eval_shape(
        lambda: init_params_quantized(jax.random.PRNGKey(0), cfg)), one_chip)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.rope_dim, 16384, cfg.rope_theta, scaling=cfg.rope_scaling)),
        one_chip)
    pool = _sds((cfg.n_layers, 2049, 64, cfg.latent_row), cfg.dtype,
                one_chip)
    return cfg, params, cos, sin, pool


def test_latent_prefill_is_on_the_flash_kernel_and_writes_one_pool(
        latent, one_chip):
    """The served prefill of the latent family provably runs the flash
    kernel: heads that score 192 wide and return 128 are no multiple of
    128, and ``attention_path`` used to send such a head to plain jax in
    silence. Read from the compiled program's text: a ``flash_mla_fwd``
    custom call a layer scan (the dense layer's and the expert
    layers'), the three grouped expert products, the ONE donated pool
    aliased to its output, and no temporary of the pool's size (its
    rows are written a page at a time, in place)."""
    from ray_tpu.llm.runner import prefill_sample

    cfg, params, cos, sin, pool = latent
    one_i = _sds((1,), jnp.int32, one_chip)
    one_f = _sds((1,), jnp.float32, one_chip)
    compiled = prefill_sample.lower(
        params, pool, None, _sds((1, 2048), jnp.int32, one_chip), one_i,
        _sds((1, 256), jnp.int32, one_chip), cos, sin, 0, one_f, one_i,
        one_f, None, cfg=cfg, greedy=True).compile()
    text = compiled.as_text()
    assert text.count("%flash_mla_fwd") >= 2, "prefill is not on the kernel"
    assert text.count("%rt_moe_gmm") >= 3
    assert text.count("tpu_custom_call") == 5
    mem = compiled.memory_analysis()
    size = pool.size * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= size, mem
    pooled = f"bf16[{cfg.n_layers},2049,64,{cfg.latent_row}]"
    assert not [line for line in text.splitlines()
                if f" = {pooled}" in line and " copy(" in line]


# SmallThinker's widths as its cell runs them, at one period of its
# layer pattern: a full layer without rotary, three window layers
WINDOW_WIDTHS = dict(
    vocab=151936, dim=2560, n_layers=4, n_heads=28, n_kv_heads=4,
    head_size=128, mlp_dim=768, max_seq=16384, rope_theta=1.5e6,
    norm_eps=1e-6, n_experts=64, top_k=6, norm_topk_prob=True,
    layer_pattern=("full_nope", "window", "window", "window"), window=4096,
    router_input="attention", expert_act="relu")


@pytest.mark.parametrize("family", ["latent", "window"])
def test_prefill_sample_hands_its_flash_kernels_the_prompt_length(
        latent, one_chip, monkeypatch, family):
    """The served prefill of the latent family and of the family with
    window layers, told where the prompt ends: every flash kernel of the
    program is still found under the name a trace's reader asks for
    (``flash_mla_fwd``; ``flash_window_fwd`` and the full layer's span)
    and takes the lengths as its fourth operand."""
    import sys

    from ray_tpu.llm.cache import window_group_pages
    from ray_tpu.llm.runner import prefill_sample
    from ray_tpu.models import LlamaConfig
    from ray_tpu.ops import rope_frequencies
    from ray_tpu.ops.quant import init_params_quantized

    one_i = _sds((1,), jnp.int32, one_chip)
    one_f = _sds((1,), jnp.float32, one_chip)
    if family == "latent":
        cfg, params, cos, sin, pool = latent
        bucket, pools, tables = 2048, (pool, None), _sds(
            (1, 256), jnp.int32, one_chip)
        names = {"flash_mla_fwd"}
    else:
        cfg = LlamaConfig(**WINDOW_WIDTHS)   # ``latent`` patched _on_tpu
        params = _on_chip(jax.eval_shape(lambda: init_params_quantized(
            jax.random.PRNGKey(0), cfg)), one_chip)
        cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
            cfg.head_dim, cfg.max_seq, cfg.rope_theta)), one_chip)
        bucket = 8192
        group = tuple(_sds(
            (cfg.group_layers(g), 129 if w is None else window_group_pages(
                1, w, 64, 8), 64, cfg.n_kv_heads, cfg.head_dim), cfg.dtype,
            one_chip) for g, w in enumerate(cfg.kv_groups))
        pools = (group, group)
        tables = tuple(_sds((1, 128), jnp.int32, one_chip) for _ in group)
        names = {"flash_window_fwd", "rt.attn.full"}

    def compiled(program):
        return program.lower(
            params, *pools, _sds((1, bucket), jnp.int32, one_chip), one_i,
            tables, cos, sin, 0, one_f, one_i, one_f, None, cfg=cfg,
            greedy=True).compile().as_text()

    text = compiled(prefill_sample)
    flash = [(name, n) for name, n in _kernel_calls(text)
             if "rt_moe_gmm" not in name]
    assert {re.sub(r"[.\d]+$", "", name) for name, _ in flash} == names
    assert {n for _, n in flash} == {4}
    # the kernel that walks its own key blocks is surrounded by what
    # surrounded the kernel of single blocks (a chunk of ONE block is
    # that kernel's grid): no kind of device operation a trace would
    # count is added round the call, no new copy or transposition of
    # q, k, v
    attention_module = sys.modules["ray_tpu.ops.attention"]
    monkeypatch.setattr(attention_module, "KV_VMEM_BYTES", 1)
    jax.clear_caches()      # the program and its layer scans are traced
    single = compiled(prefill_sample)
    jax.clear_caches()
    assert {grid[2] for name, grid, _, _ in _mosaic_calls(single)
            if "rt_moe_gmm" not in name} == {bucket // 512}
    assert {grid[2] for name, grid, _, _ in _mosaic_calls(text)
            if "rt_moe_gmm" not in name} == {1}
    assert _device_op_kinds(text) == _device_op_kinds(single)


def test_latent_decode_burst_reads_the_pool_where_it_lies(latent, one_chip):
    """A burst copies no page: its attention is the decode kernel over
    each slot's own pages (one a layer scan), the pool is neither copied
    nor turned into another layout (a scatter of single rows did both:
    3 GB of temporaries at nine layers), and the query's bottleneck
    matrix is the only weight the compiler lays out anew."""
    from ray_tpu.llm.runner import decode_burst

    cfg, params, cos, sin, pool = latent
    B = 8
    i32, f32 = _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.float32,
                                                     one_chip)
    compiled = decode_burst.lower(
        params, pool, None, i32, i32, _sds((B, 256), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip), cos, sin, 0, f32, i32, f32, None,
        _sds((B, 128), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        cfg=cfg, n_steps=8, greedy=True).compile()
    text = compiled.as_text()
    assert text.count("%rt_mla_decode") >= 2
    pooled = f"bf16[{cfg.n_layers},2049,64,{cfg.latent_row}]"
    assert not [line for line in text.splitlines()
                if f" = {pooled}" in line and " copy(" in line]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool.size * 2, mem
    assert mem.temp_size_in_bytes < 0.5 * 1024**3, mem


def test_latent_flash_and_decode_kernels_compile_alone(one_chip):
    """Mosaic takes the two kernels at the published widths: the flash
    forward at queries and keys of 192 and values of 128, and the decode
    kernel with 128 heads as the rows of each product over 8 pages of 64
    rows of 640."""
    from ray_tpu.ops import mla
    from ray_tpu.ops.attention import flash_attention_tpu

    q = _sds((1, 2048, 128, 192), jnp.bfloat16, one_chip)
    v = _sds((1, 2048, 128, 128), jnp.bfloat16, one_chip)
    assert _custom_calls(functools.partial(
        flash_attention_tpu, causal=True, scale=0.114721), q, q, v) == 1
    assert _custom_calls(
        functools.partial(mla.decode_attention_tpu, scale=0.114721,
                          rank=512),
        _sds((8, 128, 640), jnp.bfloat16, one_chip),
        _sds((9, 2049, 64, 640), jnp.bfloat16, one_chip),
        _sds((), jnp.int32, one_chip), _sds((8, 256), jnp.int32, one_chip),
        _sds((8,), jnp.int32, one_chip)) == 1


# --- an indexer beside attention (ops/sparse_attention.py): the three
# kernels at the published widths, and a burst that copies no pool ---
def test_sparse_kernels_compile_alone(one_chip):
    """Mosaic takes the scores (16 heads over keys as wide as the pool's
    slot), the choice (32 rows of 8k scores counted over in VMEM a chunk
    of 2,048 at a time, as many as a scalar says, at the two rungs' rows
    too; a decode step's 8 rows with the burst's own behind a gap, over
    a span of one chunk, which is counted whole, and of four) and the
    product over a mask (32 query heads, 4 KV heads of 128)."""
    from ray_tpu.ops import sparse_attention as sparse

    T, S = 512, 8192
    bf16, i32 = jnp.bfloat16, jnp.int32
    assert _custom_calls(
        sparse.index_scores_tpu, _sds((1, T, 16, 128), bf16, one_chip),
        _sds((1, T, 16), jnp.float32, one_chip),
        _sds((1, S, 128), bf16, one_chip), _sds((1, T), i32, one_chip)) == 1
    for rows, keys, start_b in ((T, S, None), (T, 12288, None),
                                (T, 24576, None), (8, 2048 + 128, 2048),
                                (8, 8192 + 128, 8192)):
        assert _custom_calls(
            functools.partial(sparse.choose_tpu, k=2048, start_b=start_b),
            _sds((rows, keys), jnp.float32, one_chip),
            _sds((rows, 2), i32, one_chip)) == 1
    assert _custom_calls(
        functools.partial(sparse.masked_attention_tpu, scale=128 ** -0.5),
        _sds((T, 32, 128), bf16, one_chip), _sds((4, S, 128), bf16, one_chip),
        _sds((4, S, 128), bf16, one_chip), _sds((T, S), jnp.int8, one_chip),
        _sds((T,), i32, one_chip)) == 1


@pytest.mark.parametrize("span", [32, 512])
def test_sparse_decode_product_compiles_alone(one_chip, span):
    """Mosaic takes the decode product at the cell's pools (pages of 64
    positions x 4 KV heads of 128, 8 pages of K and of V a step, copied
    by the kernel itself) at the engine's smallest and largest table
    span, and the pools reach it as they lie: their reshape to (position,
    KV head) rows is a bitcast, nothing of a pool's size is made."""
    from ray_tpu.ops import sparse_attention as sparse

    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = _sds((16, 3585, 64, 4, 128), bf16, one_chip)
    compiled = jax.jit(functools.partial(
        sparse.decode_attention, scale=128 ** -0.5)).lower(
        _sds((8, 32, 128), bf16, one_chip), pool, pool,
        _sds((), i32, one_chip), _sds((8, span), i32, one_chip),
        _sds((8,), i32, one_chip),
        _sds((8, span * 64), jnp.int8, one_chip)).compile()
    text = compiled.as_text()
    assert "%" + sparse.DECODE_KERNEL in text
    assert not [line for line in text.splitlines()
                if " = bf16[16,3585," in line and " copy(" in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 1024**2


def test_sparse_decode_burst_reads_the_pools_where_they_lie(one_chip):
    """A burst of a configuration with an indexer copies no K or V page
    and turns no pool into another layout: the scores, the choice and
    the product over each slot's own pages are the three decode kernels,
    the burst's rows written by slices (the scatter over all 16 layers x
    4 heads x 128 copied each pool layers-inward and back)."""
    import json
    import os

    from benchmarks.harness.families import family_of
    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import decode_burst
    from ray_tpu.ops import rope_frequencies

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-ep4-int8-16l.json")) as f:
        config = dict(json.load(f), vocab_size=4096)
    family = family_of(config)
    cfg = family.program_config(config)
    params = _on_chip(jax.eval_shape(
        lambda: family.served_params(jax.random.PRNGKey(0), config)),
        one_chip)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.rope_dim, 8192, cfg.rope_theta)), one_chip)
    pools = _on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v, c.i))(
        init_kv_cache(cfg, 513, 64))), one_chip)
    B = 8
    i32, f32 = _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.float32,
                                                     one_chip)
    table = _sds((B, 128), jnp.int32, one_chip)
    compiled = decode_burst.lower(
        params, pools[0], pools[1], i32, i32, table,
        _sds((B,), jnp.bool_, one_chip), cos, sin, 0, f32, i32, f32, None,
        table, _sds((), jnp.int32, one_chip), pools[2], cfg=cfg, n_steps=8,
        greedy=True).compile()
    text = compiled.as_text()
    for kernel in ("rt_sparse_index_decode", "rt_sparse_select_decode",
                   "rt_sparse_attend_decode"):
        assert "%" + kernel in text, kernel
    for pool in pools:
        shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
        assert not [line for line in text.splitlines()
                    if f" = {shape}" in line and " copy(" in line], shape
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(p.size * 2 for p in pools), mem
    assert mem.temp_size_in_bytes < 0.6 * 1024**3, mem


# --- state layers (ops/linear_attention.py) and selection by blocks
# (ops/sparse_attention.py), at MiniCPM-SALA's widths
def test_state_layer_kernels_compile_alone(one_chip):
    """Each of the new kernels at the cell's shapes: Mosaic takes their
    tiles, and the decode step updates the state pool in place."""
    from ray_tpu.ops import linear_attention as la
    from ray_tpu.ops import sparse_attention as sparse

    bf16, f32 = jnp.bfloat16, jnp.float32
    slopes = la.slopes_of(32)
    rows = _sds((1, 16384, 32, 128), bf16, one_chip)
    assert _custom_calls(
        lambda q, k, v, n, s: la.prefill_tpu(q, k, v, slopes, n, s,
                                             scale=128 ** -0.5),
        rows, rows, rows, _sds((1,), jnp.int32, one_chip),
        _sds((1, 32, 128, 128), f32, one_chip)) == 1
    step = _sds((16, 32, 128), bf16, one_chip)
    pool = _sds((9, 16, 32, 128, 128), f32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, p, a: la.decode_step_tpu(
            q, k, v, p, jnp.int32(4), a, *la.live_order(a), slopes,
            scale=128 ** -0.5),
        donate_argnums=(3,)).lower(
            step, step, step, pool,
            _sds((16,), jnp.bool_, one_chip)).compile()
    assert "%" + la.DECODE_KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    # the 302 MB pool is aliased, not copied
    assert mem.alias_size_in_bytes >= 9 * 16 * 32 * 128 * 128 * 4, mem
    assert mem.temp_size_in_bytes < 16 * 1024**2, mem
    # the scores of 2,048 compressed keys, a tile of 512 queries
    assert _custom_calls(
        functools.partial(sparse.block_scores_tpu, scale=128 ** -0.5),
        _sds((1, 32, 512, 128), bf16, one_chip),
        _sds((1, 2, 2048, 128), bf16, one_chip),
        _sds((1, 512), jnp.int32, one_chip)) == 1
    # the flash forward under a mask chosen by blocks, a KV head's group
    text = jax.jit(functools.partial(
        sparse.masked_attention_tpu, scale=128 ** -0.5, blocks=True)).lower(
            _sds((512, 16, 128), bf16, one_chip),
            _sds((1, 32768, 128), bf16, one_chip),
            _sds((1, 32768, 128), bf16, one_chip),
            _sds((512, 32768), jnp.int8, one_chip),
            _sds((512,), jnp.int32, one_chip)).compile().as_text()
    assert "%" + sparse.BLOCK_PREFILL_KERNEL in text


def test_block_decode_reads_the_listed_pages_where_they_lie(one_chip):
    """A decode step's attention over the chosen pages: the pools are
    page matrices of (position, KV head) rows and go to the kernel as
    they are: no copy of a pool, whatever the number of KV heads."""
    from ray_tpu.ops import sparse_attention as sparse

    pool = _sds((3, 8193, 128, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(functools.partial(
        sparse.block_decode_attention_tpu, kvh=2, scale=128 ** -0.5)).lower(
            _sds((32, 16, 128), jnp.bfloat16, one_chip), pool, pool,
            _sds((), jnp.int32, one_chip),
            _sds((32, 128), jnp.int32, one_chip),
            _sds((32,), jnp.int32, one_chip),
            _sds((32, 128, 128), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "%" + sparse.DECODE_KERNEL in text
    assert not [line for line in text.splitlines()
                if " = bf16[3,8193,128,128]" in line and " copy(" in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024**2


# Trinity-Large-Preview's widths as the benchmark's configuration runs
# them (one chip's share of eight: 32 of 256 experts, an eighth of the
# vocabulary; two periods, the first layer dense INSIDE the pattern)
TRINITY_WIDTHS = dict(
    vocab=25024, dim=3072, n_layers=8, n_heads=48, n_kv_heads=8,
    head_size=128, mlp_dim=3072, max_seq=262144, rope_theta=1e4,
    norm_eps=1e-5, n_experts=256, top_k=4, norm_topk_prob=True,
    routed_scale=2.448, router_score="sigmoid", router_bias=True,
    n_dense_layers=1, dense_mlp_dim=12288, n_shared_experts=1,
    experts_held=(0, 32), window=4096,
    layer_pattern=("window", "window", "window", "full_nope"),
    qk_norm=True, qk_norm_by_head=True, attn_output_gate=True,
    post_norms=True, embed_scale=3072 ** 0.5)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_dense_layers_inside_a_pattern_compile_with_their_kernels(
        latent, one_chip, program):
    """The served programs of the family whose first layer is dense
    inside a (window, window, window, full NoPE) pattern, at its
    published widths: the leading period runs before the period scan
    (3 + 4 expert layers: 21 grouped products), the window layers are on
    the window flash kernel and the full ones under their own span, and
    the held experts stay the int8 stacks they are stored as."""
    from ray_tpu.llm.cache import window_group_pages
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.models import LlamaConfig
    from ray_tpu.ops import rope_frequencies
    from ray_tpu.ops.moe import GROUPED_KERNEL
    from ray_tpu.ops.quant import init_params_quantized

    cfg = LlamaConfig(**TRINITY_WIDTHS)     # ``latent`` patched _on_tpu
    params = _on_chip(jax.eval_shape(lambda: init_params_quantized(
        jax.random.PRNGKey(0), cfg)), one_chip)
    assert params["layers"]["w_gate"]["q"].shape == (7, 32, 3072, 3072)
    assert params["layers"]["expert_bias"].shape == (7, 256)
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 3072, 12288)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.head_dim, 16384, cfg.rope_theta)), one_chip)
    B = 1 if program == "prefill" else 8
    group = tuple(_sds(
        (cfg.group_layers(g), 129 if w is None else window_group_pages(
            B, w, 64, 8), 64, cfg.n_kv_heads, cfg.head_dim), cfg.dtype,
        one_chip) for g, w in enumerate(cfg.kv_groups))
    assert [p.shape[0] for p in group] == [2, 6]
    tables = tuple(_sds((B, 64), jnp.int32, one_chip) for _ in group)
    i32, f32 = _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.float32,
                                                     one_chip)
    if program == "prefill":
        text = prefill_sample.lower(
            params, group, group, _sds((1, 4096), jnp.int32, one_chip), i32,
            tables, cos, sin, 0, f32, i32, f32, None, cfg=cfg,
            greedy=True).compile().as_text()
        flash = {re.sub(r"[.\d]+$", "", name)
                 for name, _ in _kernel_calls(text)
                 if GROUPED_KERNEL not in name}
        assert flash == {"flash_window_fwd", "rt.attn.full"}
    else:
        lists = tuple(_sds((3, 16), jnp.int32, one_chip) for _ in group)
        text = decode_burst.lower(
            params, group, group, i32, i32, tables,
            _sds((B,), jnp.bool_, one_chip), cos, sin, 0, f32, i32, f32,
            None, lists, _sds((), jnp.int32, one_chip), cfg=cfg, n_steps=8,
            greedy=True).compile().as_text()
    assert sum(GROUPED_KERNEL in name
               for name, _ in _kernel_calls(text)) == 21
    held = r"\[(?:7,)?32,3072,3072\]"
    assert not re.findall(r"(?:bf16|f32)" + held, text)


# Phi-4-mini-flash-reasoning's published widths (benchmarks/configs/
# phi-4-mini-flash-reasoning-bf16.json), the whole depth
SAMBAY_WIDTHS = dict(
    vocab=200064, dim=2560, n_layers=32, n_heads=40, n_kv_heads=20,
    mlp_dim=10240, max_seq=262144, norm_eps=1e-5, window=512,
    layer_pattern=(("scan", "window_diff") * 8 + ("scan", "full_diff")
                   + ("gmu", "cross_diff") * 7),
    scan_state=16, scan_conv=4, scan_expand=2, scan_dt_rank=160)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_hybrid_decoder_compiles_with_its_kernels(latent, one_chip,
                                                    program):
    """The served programs of the decoder-hybrid-decoder stack at its
    published widths and depth, bfloat16: the scan layers are on their
    kernels (three traced bodies: the period scan's, layer 16's), the
    window layers on the window flash kernel with rows of 128, a burst
    reads the full layer's pages where they lie (a slot its own, in the
    full layer and in the cross layers' body), the state pool and the
    full layer's pool are updated where they lie and no stack of weights
    is sliced into a copy."""
    from ray_tpu.llm.cache import init_kv_cache, window_group_pages
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.models import LlamaConfig, init_params
    from ray_tpu.ops import rope_frequencies

    cfg = LlamaConfig(**SAMBAY_WIDTHS)      # ``latent`` patched _on_tpu
    params = _on_chip(jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), cfg)), one_chip)
    assert "lm_head" not in params
    # (``lam0``: a constant a differential layer, no parameter)
    assert sum(a.size for a in jax.tree.leaves(params)) - 16 == \
        cfg.n_params() == 3_852_562_944
    B = 1 if program == "prefill" else 8
    pages = [129 if w is None else window_group_pages(B, w, 64, 8)
             for w in cfg.kv_groups]
    cache_k, cache_v, cache_s = _on_chip(jax.eval_shape(
        lambda: (lambda c: (c.k, c.v, c.s))(
            init_kv_cache(cfg, pages, 64, slots=B))), one_chip)
    assert [p.shape for p in cache_k] == [
        (1, 129, 640, 128), (8, pages[1], 64, 10, 128)]
    assert cache_s.shape == (9, B, 19, 40, 128)
    cos, sin = _on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.rope_dim, 64, cfg.rope_theta)), one_chip)
    tables = tuple(_sds((B, 64), jnp.int32, one_chip) for _ in pages)
    i32, f32 = _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.float32,
                                                     one_chip)
    if program == "prefill":
        text = prefill_sample.lower(
            params, cache_k, cache_v, _sds((1, 4096), jnp.int32, one_chip),
            i32, tables, cos, sin, 0, f32, i32, f32, None, None, None,
            cache_s, i32, cfg=cfg, greedy=True).compile().as_text()
        names = {re.sub(r"[.\d]+$", "", name)
                 for name, _ in _kernel_calls(text)}
        assert names == {"rt_scan_prefill", "flash_window_fwd"}
        assert len(_kernel_calls(text)) == 3
    else:
        # the full group's table span, the window group a row a slot
        lists = (_sds((B, 64), jnp.int32, one_chip),
                 (_sds((B,), jnp.int32, one_chip),
                  _sds((B, 9), jnp.int32, one_chip)))
        text = decode_burst.lower(
            params, cache_k, cache_v, i32, i32, tables,
            _sds((B,), jnp.bool_, one_chip), cos, sin, 0, f32, i32, f32,
            None, lists, _sds((), jnp.int32, one_chip), None, None, cache_s,
            cfg=cfg, n_steps=8, greedy=True).compile().as_text()
        names = {re.sub(r"[.\d]+$", "", name)
                 for name, _ in _kernel_calls(text)}
        assert names == {"rt_scan_decode", "rt_sparse_attend_decode"}
        assert len(_kernel_calls(text)) == 4
    # neither the state pool nor the full layer's is ever copied, and no
    # scan over the first 8 of 9 layers sliced a stack of weights into a
    # buffer of its own
    assert not re.findall(r"f32\[9,%d,19,40,128\][^ ]* copy\(" % B, text)
    assert not re.findall(r"bf16\[1,129,640,128\][^ ]* copy\(", text)
    assert not re.findall(r"bf16\[8,(?:2560,10240|10240,2560)\]", text)
