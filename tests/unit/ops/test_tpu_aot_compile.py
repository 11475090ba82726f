"""The main path's Pallas kernels, compiled for a described (not attached)
TPU v5e at Mistral-7B widths.

The TPU's compiler is installed with jax and compiles for a topology that
is only described, so these tests guard what interpret mode cannot see — a
kernel that asks for more VMEM than the chip has, or a block the tiling
refuses — at no chip time. Nothing runs; numerics are ``chip_smoke.py``'s.

This is the only file that describes a topology, and it does so inside a
fixture: one process at a time may load the TPU's library, so the call must
not happen while any module is imported (every xdist worker imports every
test file), and the compile stays in this process.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.attention import flash_attention
from deepspeed_tpu.ops.normalization import rms_norm
from deepspeed_tpu.ops.paged_attention import paged_attention

# Mistral-7B-v0.1: 32 query / 8 KV heads of 128, hidden 4096, window 4096
H, KV, D, HIDDEN, WINDOW, PAGE = 32, 8, 128, 4096, 4096, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


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
    (32768, None, 32, 8, 64),   # past the fused backward's cap: the pair
    (4096, WINDOW, H, KV, "fp32"),  # float32 operands: twice the tiles
])
def test_flash_attention_fwd_bwd_compiles(one_chip, no_compile_cache, seq,
                                          window, heads, kv, d):
    """The blocks ``kernel_dispatch.choose_blocks`` picks for each shape fit
    the chip's VMEM and tile, and so does the backward the shape resolves
    to: the fused kernel with its whole-sequence dQ accumulator, or past the
    cap the dq + dk/dv pair; a refusal here costs no chip time."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    dtype, d = (jnp.float32, D) if d == "fp32" else (jnp.bfloat16, d)
    sig = kd.make_sig((1, seq, heads, d), kv, seq, jnp.dtype(dtype).name, True,
                      window, None)
    fused = kd.resolve_leg("bwd", sig, "TPU v5 lite").impl == kd.IMPL_FUSED
    assert fused == (seq < 32768)

    def sds(n):
        return _sds((1, seq, n, d), dtype, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              force_pallas=True)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(heads), sds(kv), sds(kv))
    names = sorted(n.split(".")[0] for n in _custom_call_names(compiled))
    assert names == (["flash_dkdv_dq", "flash_fwd"] if fused else
                     ["flash_dkdv", "flash_dq", "flash_fwd"]), names


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
    assert kd.resolve_leg("bwd", sig, "TPU v5 lite").impl == kd.IMPL_FUSED
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
])
def test_the_fused_backward_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, cell, batch, seq, window, heads, kv, d):
    """Each cell's attention call and its gradient: the backward is one
    ``flash_dkdv_dq`` call that asks for what ``flash_vmem_bytes`` estimates
    and a quarter more, within the cap's 80 MiB, and is given no more than it
    asked for; dQ leaves it in the input dtype (no float32 dQ in HBM), and
    nothing in the program is laid out as the pair's ``f32[.., seq, 1]``
    delta column."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    sig = kd.make_sig((batch, seq, heads, d), kv, seq, "bfloat16", True,
                      window, None)
    dec = kd.resolve_leg("bwd", sig, "TPU v5 lite")
    assert dec.impl == kd.IMPL_FUSED
    est = kd.fused_vmem_bytes(sig)
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
    assert f"bf16[{bkv},{group},{seq},{d}]" in result and "f32[" not in result
    assert f"f32[{bkv},{group},{seq},1]" not in bwd


def test_rms_norm_compiles(one_chip, no_compile_cache):
    x = _sds((1024, HIDDEN), jnp.bfloat16, one_chip)
    w = _sds((HIDDEN, ), jnp.bfloat16, one_chip)
    _compile(functools.partial(rms_norm, force_pallas=True), x, w)


def test_grouped_matmul_compiles_to_the_native_kernel_at_olmoe_widths(
        one_chip, no_compile_cache):
    """OLMoE's MoE block a step: 16,384 tokens x top-8 = 131,072 rows over 64
    experts of 2048 x 1024, forward and gradient. ``jax.lax.ragged_dot`` must
    lower to the chip's grouped-matmul kernel (``%ragged-dot*`` custom calls,
    FLOPs proportional to top-k: what ``benchmark/moe_cost.py`` matches in a
    trace), three forward and nine with the gradient, and fit the chip."""
    from deepspeed_tpu.ops.grouped_matmul import moe_grouped_mlp
    T, HID, F, E, K = 16384, 2048, 1024, 64, 8
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((E, HID, F), jnp.bfloat16),
            sds((E, HID, F), jnp.bfloat16), sds((E, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def loss(*a):
        return jnp.sum(moe_grouped_mlp(*a).astype(jnp.float32) ** 2)

    for fn, calls in ((moe_grouped_mlp, 3),
                      (jax.grad(loss, argnums=(0, 1, 2, 3)), 9)):
        compiled = _compile(fn, *args)
        names = [n for n in _custom_call_names(compiled)
                 if n.startswith("ragged-dot") and "metadata" not in n]
        assert len(names) == calls, names
        assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


def test_short_conv_kernels_compile_and_keep_their_names(one_chip, no_compile_cache):
    """LFM2's gated short convolution a sequence of the cell: ``[1, 8192, 3 x
    2048]`` in bf16 with three taps, forward and backward, as Mosaic kernels
    named for what they are (``benchmark/conv_cost.py`` matches
    ``%short_conv_fwd*`` and ``%short_conv_bwd*`` in a trace)."""
    from deepspeed_tpu.ops.short_conv import short_conv
    bcx = _sds((1, 8192, 3 * 2048), jnp.bfloat16, one_chip)
    taps = _sds((3, 2048), jnp.float32, one_chip)

    def loss(x, w):
        return jnp.sum(short_conv(x, w, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(loss, argnums=(0, 1)), bcx, taps))
    assert [n.split(".")[0] for n in sorted(names)] == ["short_conv_bwd"], names
    names = _custom_call_names(_compile(
        lambda x, w: short_conv(x, w, use_kernel=True), bcx, taps))
    assert [n.split(".")[0] for n in names] == ["short_conv_fwd"], names


def test_state_space_kernels_compile_and_keep_their_names(one_chip, no_compile_cache):
    """Granite-4.0-H-Micro's Mamba-2 mixer, the cell's sequence: the scan at
    ``[1, 16384, 64 heads x 64]`` with a state of 128 in chunks of 256, and
    the convolution before it at ``[1, 16384, 4352]`` with four taps and a
    bias, bf16, forward and backward, as Mosaic kernels named for what they
    are (``benchmark/ssd_cost.py`` matches ``%ssd_chunk_fwd*``,
    ``%ssd_chunk_bwd*``, ``%causal_conv_fwd*``, ``%causal_conv_bwd*``)."""
    from deepspeed_tpu.ops.short_conv import causal_conv
    from deepspeed_tpu.ops.ssd import ssd_scan
    scan = [_sds((1, 16384, 64, 64), jnp.bfloat16, one_chip),
            _sds((1, 16384, 64), jnp.float32, one_chip), _sds((64, ), jnp.float32, one_chip),
            _sds((1, 16384, 128), jnp.bfloat16, one_chip),
            _sds((1, 16384, 128), jnp.bfloat16, one_chip), _sds((64, ), jnp.float32, one_chip)]

    def scan_loss(*a):
        return jnp.sum(ssd_scan(*a, 256, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(scan_loss, argnums=tuple(range(6))), *scan))
    assert sorted(n.split(".")[0] for n in names) == ["ssd_chunk_bwd", "ssd_chunk_fwd"], names
    names = _custom_call_names(_compile(
        lambda *a: ssd_scan(*a, 256, use_kernel=True, with_state_absmax=True), *scan))
    assert [n.split(".")[0] for n in names] == ["ssd_chunk_fwd"], names
    conv = [_sds((1, 16384, 4352), jnp.bfloat16, one_chip),
            _sds((4, 4352), jnp.float32, one_chip), _sds((4352, ), jnp.float32, one_chip)]

    def conv_loss(*a):
        return jnp.sum(causal_conv(*a, use_kernel=True).astype(jnp.float32))

    names = _custom_call_names(_compile(jax.grad(conv_loss, argnums=(0, 1, 2)), *conv))
    assert [n.split(".")[0] for n in sorted(names)] == ["causal_conv_bwd"], names
    names = _custom_call_names(_compile(
        lambda *a: causal_conv(*a, use_kernel=True), *conv))
    assert [n.split(".")[0] for n in names] == ["causal_conv_fwd"], names


def test_a_share_of_the_experts_compiles_to_the_native_kernel_at_lfm2_widths(
        one_chip, no_compile_cache):
    """The LFM2 cell's expert layer a step: 32,768 tokens x top-4 over a
    router of 64, 8 experts of 2048 x 1536 held. Both branches of the
    ``cond`` (the static 32,768-row array, and the exact pass over all
    131,072 rows, which since PR 37 walks them a window of 32,768 at a time)
    lower to the chip's grouped-matmul kernel, no array of all the rows at
    an expert's width exists, and the program fits beside the state."""
    from deepspeed_tpu.ops.grouped_matmul import moe_grouped_mlp_share
    T, HID, F, E, HELD, K = 32768, 2048, 1536, 64, 8, 4
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((HELD, HID, F), jnp.bfloat16),
            sds((HELD, HID, F), jnp.bfloat16), sds((HELD, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def loss(*a):
        y, rows, fell = moe_grouped_mlp_share(*a, first_expert=0, num_experts=E)
        return jnp.sum(y.astype(jnp.float32) ** 2), (rows, fell)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 5), has_aux=True), *args)
    names = [n for n in _custom_call_names(compiled)
             if n.startswith("ragged-dot") and "metadata" not in n]
    # in each branch three forward and, in the backward, the recomputed
    # forward's three and the six gradients
    assert len(names) == 2 * (3 + 3 + 6), names
    text = compiled.as_text()
    assert "bf16[32768,1536]" in text and "bf16[131072,1536]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_the_shares_rows_go_back_to_tokens_through_the_kernel_on_one_chip(
        one_chip, no_compile_cache, monkeypatch):
    """As above where the kernel runs (a TPU, one device): the branch over
    the static 32,768 rows sums token-sorted rows in ``moe_rows_to_tokens``
    (the combine's forward, also recomputed, and the dispatch's transpose);
    the exact branch over all 131,072 keeps its gathers, and the grouped
    matmuls are what they were."""
    from deepspeed_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "_kernel_here", lambda: True)
    monkeypatch.setattr(gm, "interpret_kernels", lambda: False)
    T, HID, F, E, HELD, K = 32768, 2048, 1536, 64, 8, 4
    sds = functools.partial(_sds, sharding=one_chip)
    args = [sds((T, HID), jnp.bfloat16), sds((HELD, HID, F), jnp.bfloat16),
            sds((HELD, HID, F), jnp.bfloat16), sds((HELD, F, HID), jnp.bfloat16),
            sds((T, K), jnp.int32), sds((T, K), jnp.bfloat16)]

    def loss(*a):
        y, rows, fell = gm.moe_grouped_mlp_share(*a, first_expert=0, num_experts=E)
        return jnp.sum(y.astype(jnp.float32) ** 2), (rows, fell)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 5), has_aux=True), *args)
    names = _custom_call_names(compiled)
    grouped = [n for n in names if n.startswith("ragged-dot") and "metadata" not in n]
    assert len(grouped) == 2 * (3 + 3 + 6), names
    kernels = [n for n in names if not n.startswith("ragged-dot")]
    assert 2 <= len(kernels) <= 4 and all(
        n.startswith("moe_rows_to_tokens") for n in kernels), names
    # the kernel's blocks: 128 sorted rows of 2,048 in, 128 tokens out
    call, = [line for line in _custom_calls(compiled)
             if "%moe_rows_to_tokens" in line.split(" = ")[0]][:1]
    assert "bf16[32768,2048]" in call
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def _custom_calls(compiled):
    """The compiled program's lines that call a Pallas kernel."""
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _custom_call_names(compiled):
    """Instruction names of the program's Mosaic kernels: what the device
    trace's "XLA Ops" line calls them."""
    return [line.split(" = ")[0].split("%")[-1]
            for line in _custom_calls(compiled)]


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


def _steer_the_model_to_the_chip(monkeypatch):
    """Code that asks "is this a TPU" sees the CPU here, and conftest turns
    interpret mode on: steer both in the test, the kernels are the subject."""
    from deepspeed_tpu.models import llama
    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    monkeypatch.setattr(llama, "interpret_kernels", lambda: False)
    monkeypatch.setattr("deepspeed_tpu.ops.attention.use_pallas",
                        lambda force=None: True)
    monkeypatch.setattr("deepspeed_tpu.ops.kernel_dispatch.device_kind",
                        lambda: "TPU v5 lite")


# a whole layer of each training cell's kind at its widths (hidden 2048, the
# vocabulary cut: the head has no kernel), remat as the LFM2 and Granite cells
# run it: what each kernel's instruction must still be called when the
# program's own scopes (docs/observability.md, "Device scopes") are around it
_SCOPED_LAYERS = {
    "attention_rope_dense": (
        dict(num_attention_heads=16, num_key_value_heads=4, head_dim=128,
             qk_norm="head", intermediate_size=8192), 4096,
        # whole-layer recomputation keeps the kernel's output: one forward
        {"flash_fwd": 1, "flash_dkdv_dq": 1}, 0),
    "conv_moe_share": (
        dict(num_attention_heads=32, num_key_value_heads=8, head_dim=64,
             num_local_experts=64, moe_experts_held=8, num_experts_per_tok=4,
             moe_scoring="sigmoid", moe_selection_bias=True,
             moe_renorm_eps=1e-6, operator="conv", ffn="moe", ffn_width=1536),
        # either branch of the share's cond: the forward's three, a
        # recomputed forward's three, the six gradients
        4096, {"short_conv_fwd": 2, "short_conv_bwd": 1}, 2 * (3 + 3 + 6)),
    "mamba_dense": (
        dict(num_attention_heads=32, num_key_value_heads=8, head_dim=64,
             mamba_n_heads=64, pos_embedding="none", residual_multiplier=0.22,
             operator="mamba", ffn="dense", ffn_width=8192), 4096,
        {"ssd_chunk_fwd": 2, "ssd_chunk_bwd": 1, "causal_conv_fwd": 2,
         "causal_conv_bwd": 1}, 0),
    "attention_moe": (
        dict(num_attention_heads=16, num_key_value_heads=16, head_dim=128,
             qk_norm=True, num_local_experts=64, num_experts_per_tok=8,
             moe_renormalize=False, router_aux_loss_coef=0.01,
             intermediate_size=1024, remat=False), 4096,
        {"flash_fwd": 1, "flash_dkdv_dq": 1}, 9),
}


@pytest.mark.parametrize("kind", sorted(_SCOPED_LAYERS))
def test_kernels_keep_their_names_under_the_programs_scopes(
        one_chip, no_compile_cache, monkeypatch, kind):
    """Hazard (i) of the device scopes: an instruction is named by the
    innermost name scope of the frame that holds it, and twelve admitted
    metrics match kernels by instruction name. One layer of each cell's kind,
    the loss and its gradient under the engine's ``ds.step.loss`` with
    ``ds.rope``, ``ds.moe.*`` and ``ds.head.loss`` inside: every kernel is
    still called what its reader matches, the scopes are on the ops around
    them, and the recomputed forward's kernels are there (count 2; the
    attention kernel's forward once: its output is kept for the backward)."""
    import dataclasses
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.engine import _step_scope
    over, seq, kernels, ragged = _SCOPED_LAYERS[kind]
    over = dict(over)
    spec = {k: over.pop(k) for k in ("operator", "ffn", "ffn_width") if k in over}
    cfg = llama.LlamaConfig(**{**dict(
        vocab_size=2048, hidden_size=2048, num_hidden_layers=1,
        max_position_embeddings=seq, ce_chunk_size=2048, remat=True,
        layer_specs=(llama.LayerSpec(**spec), ) if spec else None), **over})
    _steer_the_model_to_the_chip(monkeypatch)
    model = llama.LlamaForCausalLM(cfg)
    ids = _sds((1, seq), jnp.int32, one_chip)
    shapes = jax.eval_shape(
        lambda: {"params": llama.unbox_params(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))["params"]})
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), shapes)

    def step(params, ids):
        def loss(p):
            out = model.apply(p, ids, ids, mutable=["aux_loss", "moe_stats"])
            return out[0].astype(jnp.float32)
        with _step_scope("loss"):
            return jax.value_and_grad(loss)(params)

    compiled = _compile(step, params, ids)
    names = [n.split(".")[0] for n in _custom_call_names(compiled)
             if not n.startswith("ragged-dot")]
    assert {k: names.count(k) for k in set(names)} == kernels, names
    text = compiled.as_text()
    grouped = re.findall(r"%(ragged-dot-none[.\d]*) = ", text)
    assert len(grouped) == ragged, grouped
    for scope in (["ds.step.loss", "ds.head.loss"]
                  + ["ds.rope"] * (cfg.pos_embedding == "rope" and not spec)
                  + ["ds.moe.route", "ds.moe.dispatch", "ds.moe.combine"]
                  * bool(ragged)):
        assert f"/{scope}/" in text, scope


def test_block_diffusion_kernels_compile_at_the_sdar_cells_shape(one_chip,
                                                                 no_compile_cache):
    """``train-sdar-1chip-bd4-seq8k``'s call, ``[2, 16384, 32/4, 128]`` in
    blocks of 4: the forward and the one backward kernel at the (128, 512)
    tiles ``kernel_dispatch`` picks (2,048 folded rows a step: both copies x
    8 heads x 128 queries), each under its own name and none under a
    ``flash`` name, on operands laid out ``[rows * kv, 2, group, L, d]``;
    the backward asks for the VMEM its clean keys' float32 dK and dV need."""
    from deepspeed_tpu.ops import kernel_dispatch as kd
    from deepspeed_tpu.ops.attention import block_diffusion_attention
    sig = kd.make_sig((2, 16384, 32, D), 4, 16384, "bfloat16", False, None, None,
                      pattern="bd4")
    for leg in ("fwd", "bwd"):
        assert kd.choose_block_diffusion_blocks(sig, leg, 4) == (128, 512)
    need = kd.bdattn_vmem_bytes("bwd", 8, D, 2, 128, 512, 8192)
    assert kd.VMEM_SCOPED_DEFAULT_BYTES < need < kd.FUSED_VMEM_CAP_BYTES

    q = _sds((2, 16384, 32, D), jnp.bfloat16, one_chip)
    k = _sds((2, 16384, 4, D), jnp.bfloat16, one_chip)
    compiled = _compile(
        jax.grad(lambda q, k, v: jnp.sum(block_diffusion_attention(
            q, k, v, 4, force_pallas=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        q, k, k)
    calls, names = _custom_calls(compiled), _custom_call_names(compiled)
    assert sorted(n.split(".")[0] for n in names) == ["bdattn_bwd", "bdattn_fwd"], names
    assert not any("flash" in n for n in names)
    for call in calls:
        assert f"bf16[8,2,8,8192,{D}]" in call.split(" custom-call(")[0], call
    bwd, = [c for c in calls if "%bdattn_bwd" in c.split(" = ")[0]]
    asked = re.findall(r'scoped_memory_configs":\[([^\]]*)\]', bwd)[0]
    assert int(re.search(r'"size":"(\d+)"', asked).group(1)) == kd.vmem_limit_bytes(need)


def test_the_block_diffusion_layer_keeps_its_kernels_names_under_the_scopes(
        one_chip, no_compile_cache, monkeypatch):
    """One SDAR layer at its widths (16 of 128 experts held, the vocabulary
    cut) under the block-diffusion objective, the weighted loss and its
    gradient under the engine's ``ds.step.loss``: the attention is the
    ``bdattn`` pair (the forward ONCE: the recomputed layer takes the kept
    output) and no ``flash`` call, the share's grouped matmuls are XLA's own, and the program's
    scopes are on the ops around them."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.engine import _step_scope
    seq = 4096
    cfg = llama.LlamaConfig(
        vocab_size=2048, hidden_size=2048, num_hidden_layers=1, intermediate_size=768,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128, qk_norm="head",
        num_local_experts=128, moe_experts_held=16, num_experts_per_tok=8,
        rope_theta=1e6, rms_norm_eps=1e-6, max_position_embeddings=seq,
        ce_chunk_size=2048, remat=True, objective="block_diffusion")
    _steer_the_model_to_the_chip(monkeypatch)
    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: {"params": llama.unbox_params(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]})
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    ids = _sds((1, 2 * seq), jnp.int32, one_chip)
    targets = _sds((1, seq), jnp.int32, one_chip)
    weights = _sds((1, seq), jnp.float32, one_chip)

    def step(params, ids, targets, weights):
        def loss(p):
            out = model.apply(p, ids, targets, loss_weights=weights,
                              mutable=["moe_stats", "diffusion_stats"])
            return out[0].astype(jnp.float32)
        with _step_scope("loss"):
            return jax.value_and_grad(loss)(params)

    compiled = _compile(step, params, ids, targets, weights)
    names = [n.split(".")[0] for n in _custom_call_names(compiled)
             if not n.startswith("ragged-dot")]
    assert {k: names.count(k) for k in set(names)} == {"bdattn_fwd": 1, "bdattn_bwd": 1}, names
    text = compiled.as_text()
    assert len(re.findall(r"%(ragged-dot-none[.\d]*) = ", text)) == 2 * (3 + 3 + 6)
    for scope in ("ds.step.loss", "ds.head.loss", "ds.rope", "ds.moe.route",
                  "ds.moe.dispatch", "ds.moe.combine"):
        assert f"/{scope}/" in text, scope


# the three cells that train under whole-layer recomputation: (attention
# kernel, attention layers, bytes of their kernels' outputs and log-sum-exps
# kept a step: tokens x heads x (head_dim x 2 + 4) a layer; what the chip
# reported in use when the step was first traced (my chip runs, PR 41: the
# engine at rest, 12 B a parameter, and the batch); the candidates the rule
# then admits (ops/remat.py), by name the layers that keep it; and of the
# producers left, one whose matmul is still made again)
_T = 32768          # tokens a step of the SDAR and LFM2 cells; Granite half
_RECOMPUTING_CELLS = {
    "train-sdar-1chip-bd4-seq8k": (
        "bdattn", 6, 6 * _T * 32 * (128 * 2 + 4), 7_750_149_632,
        {"ds.mixer.out": range(6), "ds.mixer.in": range(3)}, "layers_3/self_attn/q_proj"),
    "train-lfm2moe-1chip-seq8k": (
        "flash", 1, _T * 32 * (64 * 2 + 4), 5_860_270_592,
        {"ds.moe.route": range(1, 5), "ds.ffn.in": [0], "ds.mixer.in": range(5),
         "ds.mixer.out.narrow": range(5), "ds.mixer.kernel": [0, 2, 3, 4]}, None),
    "train-granite4hm-1chip-longseq": (
        "flash", 1, _T // 2 * 32 * (64 * 2 + 4), 9_267_765_248,
        # the nine Mamba layers' out_proj (4,096 deep; layer 5 is attention)
        {"ds.mixer.out": [0, 1, 2, 3, 4, 6, 7, 8, 9], "ds.ffn.in": range(2)},
        "layers_2/mlp/gate_proj"),
}
V5E_BYTES_LIMIT = 16_909_336_064


def _candidate_bytes(cfg, tokens, name, layer):
    """Bytes of ``name`` in layer ``layer`` of a cell's model at bf16, written
    out from the configuration's widths: what the rule's price list, read
    off the traced shapes, has to agree with."""
    spec = cfg.layer_specs[layer] if cfg.layer_specs else None    # None: attention, MoE
    h = cfg.hidden_size
    if name == "ds.moe.route":      # float32 logits, the top-k and its float32 weights
        assert cfg.moe_selection_bias
        return tokens * (cfg.num_local_experts + 2 * cfg.num_experts_per_tok) * 4
    if name in ("ds.mixer.out", "ds.mixer.out.narrow"):
        return tokens * h * 2
    if name == "ds.ffn.in":         # gate and up
        return tokens * 2 * spec.ffn_width * 2
    if name == "ds.mixer.kernel":   # the gated convolution's y
        assert spec.operator == "conv"
        return tokens * h * 2
    assert name == "ds.mixer.in"
    if spec is not None and spec.operator == "conv":     # B | C | u
        return tokens * 3 * h * 2
    assert spec is None or spec.operator == "attention"
    return tokens * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) \
        * cfg.head_dim_ * 2


def _recomputed_matmuls(text, producer):
    """Matmuls of ``producer`` (``layers_N/<module>/<projection>``) that the
    compiled program makes inside a recomputation."""
    return len(re.findall(
        rf'op_name="[^"]*rematted_computation[^"]*/{producer}/dot_general', text))


@pytest.mark.parametrize("cell", sorted(_RECOMPUTING_CELLS))
def test_a_recomputing_cells_step_runs_each_attention_forward_once_and_fits(
        one_chip, no_compile_cache, monkeypatch, cell):
    """The cell's whole step at its own configuration and batch (the model
    the benchmark's runner builds from ``benchmark/configs``, the engine's
    fused step spelled out: cast, loss and gradient with the sown counters,
    global norm, AdamW over float32 masters), compiled for the described
    v5e that reports the memory in use the chip reported: ONE forward
    attention kernel an attention layer (their outputs are kept for the
    recomputed layers' backward); the rule (``ops/remat.py``) admits the
    candidates listed above, ``kept_residual_bytes`` (what
    ``ds_remat_kept_bytes`` publishes) is their bytes, reckoned from the
    configuration's widths, and the kernels' 1.64 / 0.14 / 0.07 GB; no
    matmul of a kept producer is made inside a recomputation and one left
    out still is; and the program's temporaries beside an engine at rest
    (12 B a parameter: master and two moments, no accumulation buffer) stay
    0.8 GB (5%) under the chip's ``bytes_limit``."""
    import importlib
    import json
    import pathlib
    import optax
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.runtime.engine import _as_apply_fns, _step_scope
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    kernel, layers, residuals, in_use, admitted, left = _RECOMPUTING_CELLS[cell]
    bench = pathlib.Path(__file__).parents[3] / "benchmark"
    workload = json.loads((bench / "workloads" / f"{cell}.json").read_text())
    config = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    cfg = importlib.import_module(
        f"benchmark.runners.{workload['runner']}").model_config(config)
    assert cfg.remat and cfg.remat_policy is None
    rows, seq = workload["traffic"]["global_batch"], workload["traffic"]["seq_len"]
    _steer_the_model_to_the_chip(monkeypatch)
    monkeypatch.setattr("deepspeed_tpu.ops.grouped_matmul.on_tpu", lambda: True)
    monkeypatch.setattr(remat, "device_memory", lambda: (V5E_BYTES_LIMIT, in_use))
    remat.forget_plans()
    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: llama.unbox_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, jnp.float32, one_chip), shapes)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert 0 <= in_use - 12 * n_params < 250e6
    tx, _ = build_optimizer("AdamW", {"lr": 1e-4})
    opt_state = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), jax.eval_shape(tx.init, params))
    ids = lambda n: _sds((rows, n), jnp.int32, one_chip)        # noqa: E731
    if cfg.block_diffusion_:
        args = (ids(2 * seq), ids(seq))
        kwargs = {"loss_weights": _sds((rows, seq), jnp.float32, one_chip)}
    else:
        args, kwargs = (ids(seq), ids(seq)), {}
    _, apply_with_stats = _as_apply_fns(model)

    def train_step(params, opt_state, args, kwargs):
        with _step_scope("cast"):
            compute = jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)

        def loss_of(p):
            out, stats = apply_with_stats(p, *args, **kwargs)
            return out.astype(jnp.float32), stats

        with _step_scope("loss"):
            (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(compute)
        with _step_scope("grad_norm"):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            gnorm = optax.global_norm(grads)
        with _step_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return loss, params, opt_state, gnorm, stats

    traced = jax.jit(train_step, donate_argnums=(0, 1)).trace(
        params, opt_state, args, kwargs)
    remat.forget_plans()
    tokens = rows * seq * (2 if cfg.block_diffusion_ else 1)
    reckoned = sum(_candidate_bytes(cfg, tokens, name, layer)
                   for name, kept_in in admitted.items() for layer in kept_in)
    assert kept_residual_bytes(traced.jaxpr) == residuals + reckoned
    compiled = traced.lower().compile()
    text = compiled.as_text()
    outs = ("self_attn/o_proj", "conv/out_proj", "mamba/out_proj")
    modules = {"ds.mixer.out": outs, "ds.mixer.out.narrow": outs,
               "ds.ffn.in": ("mlp/gate_proj", "mlp/up_proj"),
               "ds.mixer.in": ("self_attn/q_proj", "conv/in_proj", "mamba/in_proj"),
               "ds.moe.route": ("block_sparse_moe/gate", )}
    for name, kept_in in admitted.items():
        for layer in kept_in:
            for module in modules.get(name, ()):
                assert not _recomputed_matmuls(text, f"layers_{layer}/{module}"), (
                    name, layer, module)
    if left:
        assert _recomputed_matmuls(text, left)
    names = [n.split(".")[0] for n in _custom_call_names(compiled)]
    assert names.count(f"{kernel}_fwd") == layers, names
    other = "flash" if kernel == "bdattn" else "bdattn"
    assert not any(n.startswith(other) for n in names), names
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries + 12 * n_params <= V5E_BYTES_LIMIT - 0.8e9, (temporaries, n_params)
