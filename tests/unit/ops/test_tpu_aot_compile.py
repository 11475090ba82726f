"""The main path's Pallas kernels, compiled for a described (not attached)
TPU v5e at Mistral-7B and OLMoE widths: paged attention, flash forward and
backward at thirteen shapes, the fused backward at the cells' calls (walked in
query ranges past its VMEM cap), rms_norm,
the grouped matmuls, and one layer of the dense and the OLMoE cell's kind
under the program's scopes. ``aot_v5e.py`` has what these files share and
why a topology is described in a fixture; the families with kernels of their
own have files of their own (``_lfm2``, ``_granite``, ``_sdar``, ``_mla``).
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from aot_v5e import (_compile, _custom_call_names, _custom_calls, _sds,  # noqa: F401
                     _grouped_matmuls, _mosaic_lowerings, _steer_the_model_to_the_chip,
                     kernels_keep_their_names_under_the_programs_scopes,
                     no_compile_cache, one_chip, topo)
from deepspeed_tpu.ops.attention import flash_attention
from deepspeed_tpu.ops.normalization import rms_norm
from deepspeed_tpu.ops.paged_attention import paged_attention

# Mistral-7B-v0.1: 32 query / 8 KV heads of 128, hidden 4096, window 4096
H, KV, D, HIDDEN, WINDOW, PAGE = 32, 8, 128, 4096, 4096, 64


@pytest.mark.parametrize("n_new,window,int8", [
    (1, None, False),
    (128, None, False),
    (512, None, False),      # 24 MB of scoped VMEM before the query tile
    (512, WINDOW, False),
    (512, None, True),
])
def test_paged_attention_compiles(one_chip, no_compile_cache, n_new, window,
                                  int8):
    sds = functools.partial(_sds, sharding=one_chip)
    seqs, pages, blocks = 2, 1024, 128
    args = [sds((seqs, n_new, H, D), jnp.bfloat16),
            sds((4, pages * PAGE, KV * D), jnp.int8 if int8 else jnp.bfloat16),
            sds((), jnp.int32), sds((seqs, blocks), jnp.int32),
            sds((seqs, ), jnp.int32), sds((seqs, ), jnp.int32)]
    kern = functools.partial(paged_attention, page_size=PAGE, window=window)
    if int8:
        args.append(sds((4, pages * PAGE, KV), jnp.bfloat16))
        _compile(lambda *a: kern(*a[:-1], cache_scales=a[-1]), *args)
    else:
        _compile(kern, *args)


@pytest.mark.parametrize("seq,window,heads,kv,d", [
    (2048, None, H, KV, D),
    (4096, WINDOW, H, KV, D),
    (8192, WINDOW, H, KV, D),   # dead steps on both sides of the window
    (4096, None, 64, 8, D),     # Llama-3-70B's group of 8
    (4096, WINDOW, 16, 8, 256),  # Gemma-2's head size
    (1024, None, 16, 16, 64),   # the 0.4B preset: head size 64, group 1
    (4096, None, 16, 16, D),    # OLMoE: head size 128, group 1, no window
    (4096, None, 16, 8, D),     # group 2: (512, 512)
    (1536, None, 16, 16, D),    # group 1, a sequence 1024 does not divide
    (8192, None, 32, 8, 64),    # LFM2: head size 64, group 4, 8,192 keys
    (16384, None, 32, 8, 64),   # Granite: 16,384 keys, a 32 MiB dQ accumulator
    (32768, None, 32, 8, 64),   # past the cap whole: two ranges of 16,384
    (4096, WINDOW, H, KV, "fp32"),  # float32 operands: twice the tiles
])
def test_flash_attention_fwd_bwd_compiles(one_chip, no_compile_cache, seq,
                                          window, heads, kv, d):
    """The blocks ``kernel_dispatch.choose_blocks`` picks for each shape fit
    the chip's VMEM and tile, and so does the backward the shape resolves
    to: the fused kernel with its whole-sequence dQ accumulator, or past the
    cap with one query range's; a refusal here costs no chip time."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    dtype, d = (jnp.float32, D) if d == "fp32" else (jnp.bfloat16, d)
    sig = kd.make_sig((1, seq, heads, d), kv, seq, jnp.dtype(dtype).name, True,
                      window, None)
    dec = kd.resolve(sig)[1]
    assert (dec.impl, dec.ranges) == (kd.IMPL_FUSED, 1 if seq < 32768 else 2)

    def sds(n):
        return _sds((1, seq, n, d), dtype, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              force_pallas=True)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(heads), sds(kv), sds(kv))
    names = sorted(n.split(".")[0] for n in _custom_call_names(compiled))
    assert names == ["flash_dkdv_dq", "flash_fwd"], names


def test_flash_kernels_compile_at_the_olmoe_cells_shape(one_chip,
                                                        no_compile_cache):
    """``train-olmoe-1chip-seq4k``'s call, ``[4, 4096, 16/16, 128]``: at
    group 1 a step of 1024 folded rows is a 1024-query block. The forward
    at (1024, 1024) and the fused backward at its (512, 512), whose dQ
    accumulator is 2 MiB here, compile under the compiler's default scoped
    VMEM (no limit on the call), each under its own name, on the cell's own
    operand."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    shape = (4, 4096, 16, D)
    sig = kd.make_sig(shape, 16, 4096, "bfloat16", True, None, None)
    assert kd.choose_blocks(sig, "fwd") == (1024, 1024)
    assert kd.choose_blocks(sig, "bwd") == (1024, 512)
    for leg in ("fwd", "bwd"):
        est = kd.flash_vmem_bytes(leg, 1, D, 2, *kd.choose_blocks(sig, leg))
        assert kd.vmem_limit_bytes(est) is None, (leg, est)
    assert kd.resolve(sig)[1].impl == kd.IMPL_FUSED
    assert kd.choose_blocks(sig, "fused") == (512, 512)
    assert kd.vmem_limit_bytes(kd.fused_vmem_bytes(sig)) is None

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, force_pallas=True)

    x = _sds(shape, jnp.bfloat16, one_chip)
    compiled = _compile(
        jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                 argnums=(0, 1, 2)), x, x, x)
    calls = _custom_calls(compiled)
    names = _custom_call_names(compiled)
    assert sorted(n.split(".")[0] for n in names) == [
        "flash_dkdv_dq", "flash_fwd"], names
    # a call's backend config holds the scoped VMEM it asked for (nothing:
    # the compiler's default) and then what the compiler gave it
    for call in calls:
        asked, given = re.findall(r'scoped_memory_configs":\[([^\]]*)\]', call)
        assert asked == "", call
        assert (int(re.search(r'"size":"(\d+)"', given).group(1))
                <= kd.VMEM_SCOPED_DEFAULT_BYTES)
    fwd, = [c for c in calls if "%flash_fwd" in c.split(" = ")[0]]
    assert f"bf16[64,1,4096,{D}]" in fwd


@pytest.mark.parametrize("cell,batch,seq,window,heads,kv,d", [
    ("train-zero3-seq4k", 1, 4096, WINDOW, H, KV, D),
    ("train-olmoe-1chip-seq4k", 4, 4096, None, 16, 16, D),
    ("train-lfm2moe-1chip-seq8k", 4, 8192, None, 32, 8, 64),
    ("train-granite4hm-1chip-longseq", 1, 16384, None, 32, 8, 64),
    # past the cap whole: eight ranges of 4,096 queries, two of 16,384
    ("train-qwen3next-1chip-gdn-longseq", 1, 32768, None, 16, 2, 256),
    ("32k-tokens-at-group-4-and-head-64", 1, 32768, None, 32, 8, 64),
])
def test_the_fused_backward_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, cell, batch, seq, window, heads, kv, d):
    """Each cell's attention call and its gradient: the backward is one
    ``flash_dkdv_dq`` call that asks for what ``flash_vmem_bytes`` estimates
    (of one query range, where the shape walks in several) and a quarter
    more, within the cap's 80 MiB, and is given no more than it asked for;
    dQ leaves it in the input dtype (no float32 dQ in HBM), first of the
    results where the ranges' float32 partials of dK and dV follow it (what
    ``benchmark/flash_cost.py`` reads the call's shape off), and nothing in
    the program is laid out as the pair's ``f32[.., seq, 1]`` delta column."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    sig = kd.make_sig((batch, seq, heads, d), kv, seq, "bfloat16", True,
                      window, None)
    dec = kd.resolve(sig)[1]
    assert dec.impl == kd.IMPL_FUSED
    assert dec.ranges == {256: 8, 64: 2}[d] if seq == 32768 else dec.ranges == 1
    est = kd.fused_vmem_bytes(sig, dec.ranges)
    assert est <= kd.FUSED_VMEM_CAP_BYTES

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              force_pallas=True)
        return jnp.sum(out.astype(jnp.float32))

    def sds(n):
        return _sds((batch, seq, n, d), jnp.bfloat16, one_chip)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(heads), sds(kv), sds(kv))
    bwd, = [c for c in _custom_calls(compiled)
            if "%flash_dkdv_dq" in c.split(" = ")[0]]
    asked, given = re.findall(r'scoped_memory_configs":\[([^\]]*)\]', bwd)

    def end(config):    # where the region ends: XLA may keep arrays under it
        return sum(int(x) for x in re.search(
            r'"offset":"(\d+)","size":"(\d+)"', config).groups())

    limit = kd.vmem_limit_bytes(est)
    if limit is None:
        assert asked == "" and end(given) <= kd.VMEM_SCOPED_DEFAULT_BYTES
    else:
        assert int(re.search(r'"size":"(\d+)"', asked).group(1)) == limit
        assert limit <= 80 * 2**20 and end(given) <= end(asked) <= 128 * 2**20
    group, bkv = heads // kv, batch * kv
    result = bwd.split(" = ")[1].split(" custom-call(")[0]
    assert f"bf16[{bkv},{group},{seq},{d}]" in result
    if dec.ranges == 1:
        assert "f32[" not in result
    else:
        assert result.startswith(f"(bf16[{bkv},{group},{seq},{d}]")
        assert result.count(f"f32[{bkv},{dec.ranges},{seq},{d}]") == 2
        assert result.count("f32[") == 2
    assert f"f32[{bkv},{group},{seq},1]" not in bwd
    assert sorted(n.split(".")[0] for n in _custom_call_names(compiled)) == [
        "flash_dkdv_dq", "flash_fwd"]
    # the walks are tables of the live tiles (PR 60), lowered through Mosaic
    # with their scalar prefetch: three int32 a forward step, four a backward's
    fwd, = [c for c in _custom_calls(compiled) if "%flash_fwd" in c.split(" = ")[0]]
    for leg, call, tables in (("fwd", fwd, 3), ("bwd", bwd, 4)):
        walk = kd.walked(sig, kd.resolve(sig)[leg == "bwd"], leg)
        assert walk.table and walk.tiles < walk.grid, walk
        assert call.count(f"s32[{walk.tiles}]{{0}}") == tables, (leg, walk)


def test_rms_norm_compiles(one_chip, no_compile_cache):
    x = _sds((1024, HIDDEN), jnp.bfloat16, one_chip)
    w = _sds((HIDDEN, ), jnp.bfloat16, one_chip)
    _compile(functools.partial(rms_norm, force_pallas=True), x, w)


@pytest.mark.parametrize("placed", ["where_no_kernel_runs", "on_one_chip"])
def test_grouped_matmul_compiles_to_the_native_kernel_at_olmoe_widths(
        one_chip, no_compile_cache, monkeypatch, placed):
    """OLMoE's MoE block a step: 16,384 tokens x top-8 = 131,072 rows over 64
    experts of 2048 x 1024, forward and gradient, three grouped matmuls
    forward and nine with the gradient, FLOPs proportional to top-k, named as
    ``benchmark/moe_cost.py`` matches them in a trace, and fitting the chip.
    Where no raw kernel runs (a mesh of several devices, or, here, a process
    that is not on a TPU) ``jax.lax.ragged_dot`` lowers to the chip's own
    (``%ragged-dot*``); on one chip they are the program's ``moe_gmm_*``,
    three of each pass, and the nine call sites take six lowerings through
    Mosaic: one a distinct (pass, shape)."""
    from deepspeed_tpu.ops import grouped_matmul as gm
    kernel = placed == "on_one_chip"
    if kernel:
        _steer_the_model_to_the_chip(monkeypatch)
    T, HID, F, E, K = 16384, 2048, 1024, 64, 8
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((E, HID, F), jnp.bfloat16),
            sds((E, HID, F), jnp.bfloat16), sds((E, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def forward(*a):        # traced anew: jit remembers a function it has seen
        return gm.moe_grouped_mlp(*a)

    def loss(*a):
        return jnp.sum(forward(*a).astype(jnp.float32) ** 2)

    for fn, calls in ((forward, 3), (jax.grad(loss, argnums=(0, 1, 2, 3)), 9)):
        with _mosaic_lowerings() as lowered:
            compiled = _compile(fn, *args)
        if kernel:
            legs = ("rows", ) if calls == 3 else ("rows", "d_rows", "weights")
            assert _grouped_matmuls(compiled) == {leg: 3 for leg in legs}
            # [2048 -> 1024] for w1 and w3, [1024 -> 2048] for w2
            assert lowered == {f"moe_gmm_{leg}": 2 for leg in legs}
        else:
            names = [n for n in _custom_call_names(compiled)
                     if n.startswith("ragged-dot") and "metadata" not in n]
            assert len(names) == calls, names
        assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


@pytest.mark.parametrize("meshed", [False, True], ids=["one_chip", "shard_map_2x2"])
def test_flash_kernels_keep_their_names_in_the_compiled_program(
        topo, one_chip, no_compile_cache, monkeypatch, meshed):
    """The training cell's attention (1 x 4096 x 32/8 heads x 128 a chip,
    window 4096) and its gradient: the forward and the fused backward are
    named for what they are on one chip and inside the shard_map that ``models/llama.py``
    wraps them in (``sequence/layer.py:ulysses_flash``), where they used to
    take the shard_map's name. The benchmark's flash roofline readers and
    ``breakdown.device_ops`` key on these names."""
    seq = 4096
    if meshed:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deepspeed_tpu.comm.mesh import MeshContext
        from deepspeed_tpu.sequence.layer import ulysses_flash
        # code that asks "is this a TPU" sees the CPU here: steer it in the
        # test, as the kernel is what is being compiled
        monkeypatch.setattr("deepspeed_tpu.ops.attention.use_pallas",
                            lambda force=None: True)
        ctx = MeshContext.create({"fsdp": 4}, devices=topo.devices)
        where = NamedSharding(ctx.mesh, P("fsdp", None, None, None))
        rows = 4

        def attend(q, k, v):
            return ulysses_flash(q, k, v, window=WINDOW, mesh_ctx=ctx)
    else:
        where, rows = one_chip, 1

        def attend(q, k, v):
            return flash_attention(q, k, v, causal=True, window=WINDOW,
                                   force_pallas=True)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    def sds(heads):
        return _sds((rows, seq, heads, D), jnp.bfloat16, where)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(H), sds(KV), sds(KV))
    names = _custom_call_names(compiled)
    assert len(names) == 2, names
    for prefix in ("flash_fwd", "flash_dkdv_dq"):
        assert sum(n.startswith(prefix) for n in names) == 1, names
    assert not any(n.startswith("flash_dq") for n in names)
    assert not any(n.startswith("shard_map") for n in names)
    # each chip's call is the cell's shape: [rows * kv, group, seq, d]
    fwd, = [line for line in _custom_calls(compiled)
            if "%flash_fwd" in line.split(" = ")[0]]
    assert f"bf16[{KV},{H // KV},{seq},{D}]" in fwd


@pytest.mark.parametrize("kind", ['attention_moe', 'attention_rope_dense'])
def test_kernels_keep_their_names_under_the_programs_scopes(
        one_chip, no_compile_cache, monkeypatch, kind):
    kernels_keep_their_names_under_the_programs_scopes(one_chip, monkeypatch, kind)
