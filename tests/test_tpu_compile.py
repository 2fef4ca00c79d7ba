"""The Pallas kernels of the main path, compiled for a DESCRIBED v5e at
GPT-2 124M widths (the two serving kernels at GPT-2 large's too) — no
chip attached, about two seconds each.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a DMA slice not aligned to the HBM tiling (``ragged_paged_attention`` at
Dh = 64 before the K|V-in-lanes pool layout), a block that does not fit
VMEM (``fused_adamw`` before its row grid). The TPU compiler is installed
here and compiles for a topology that is described and not attached; these
cases keep that guard on every later PR at no chip time. A compile that
passes is not a chip run — ``chip_smoke.py`` is.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402
from paddle_tpu.ops.kv_append import (  # noqa: E402
    APPEND_VMEM_BUDGET, append_ring_blocks, kv_append)
from paddle_tpu.ops.ragged_paged_attention import (  # noqa: E402
    KV_VMEM_BUDGET, kv_group_blocks, q_step_blocks, ragged_layout,
    ragged_paged_attention, ragged_walk_counts)

H, DH = 12, 64          # GPT-2 124M: 12 heads of 64


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip as a sharding; skips where this jax cannot
    describe the topology. The persistent compile cache is off around
    these compiles: an executable for a described device is written to it
    but cannot be read back without a chip, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # code that asks the backend takes its CPU (interpret) branch in this
    # process; steer it here, in the test, not through an option
    patch = pytest.MonkeyPatch()
    patch.setattr(pk, "_on_tpu", lambda: True)
    yield SingleDeviceSharding(topo.devices[0])
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _ragged_case(chip, pool_dtype, block_size, q_lens, heads=H,
                 q_bucket=64, table_len=8):
    """A ragged batch over a 65-block pool: ``q_lens`` rows per sequence
    (1 = a decode row, more = a prefill chunk), 20 tokens of history."""
    S, T, NB = len(q_lens), table_len, 64
    blk_seq, qstart, pos0, _, _ = ragged_layout(
        q_lens, [20] * S, q_bucket=q_bucket)
    tables = np.zeros((S, T), np.int32)
    lo = np.zeros(S, np.int32)
    kv_len = np.asarray([20 + n for n in q_lens], np.int32)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    avals = [sds((heads, q_bucket, DH), jnp.bfloat16),
             sds((2, NB + 1, heads, block_size, 2 * DH), pool_dtype)]
    if pool_dtype == "int8":
        avals.append(sds((2, 2, NB + 1, heads), jnp.float32))

    def fn(q, pool, scales=None):
        return ragged_paged_attention(q, pool, 1, blk_seq, qstart, pos0,
                                      tables, lo, kv_len, scales=scales)
    return fn, avals


@pytest.mark.parametrize("pool_dtype,block_size,q_lens", [
    ("bfloat16", 16, [1] * 8),            # a decode batch
    ("bfloat16", 16, [1, 1, 1, 40]),      # decode rows + a prefill chunk
    ("float32", 16, [1, 1, 1, 40]),
    ("int8", 32, [1, 1, 1, 40]),
], ids=["bf16-decode", "bf16-mixed", "f32-mixed", "int8-bs32-mixed"])
def test_ragged_paged_attention_compiles_at_gpt2_widths(
        v5e, pool_dtype, block_size, q_lens):
    fn, avals = _ragged_case(v5e, pool_dtype, block_size, q_lens)
    assert "ragged_paged_attention" in _compile(fn, *avals)


@pytest.mark.parametrize("pool_dtype,block_size,q_lens,q_bucket", [
    ("bfloat16", 16, [1] * 64, 512),               # the decode program
    ("bfloat16", 16, [1] * 63 + [456], 1024),      # decode rows + a chunk
    ("int8", 32, [1] * 63 + [456], 1024),
], ids=["bf16-512-rows", "bf16-1024-rows", "int8-bs32-1024-rows"])
def test_ragged_paged_attention_compiles_at_gpt2_large_widths(
        v5e, pool_dtype, block_size, q_lens, q_bucket):
    """H = 20 heads of 64: one DMA brings a whole 80 KB block, a group
    of G of them a buffer. What Mosaic refuses (a DMA slice off the
    tiling, too much VMEM) shows here, and the two group buffers the
    call asks for stay inside the budget the module states."""
    heads = 20
    group = kv_group_blocks(heads, block_size, DH, pool_dtype)
    assert group * block_size == 128           # one full-lane score tile
    assert 2 * group * heads * block_size * 2 * DH \
        * jnp.dtype(pool_dtype).itemsize <= KV_VMEM_BUDGET
    fn, avals = _ragged_case(v5e, pool_dtype, block_size, q_lens,
                             heads=heads, q_bucket=q_bucket, table_len=64)
    assert "ragged_paged_attention" in _compile(fn, *avals)


@pytest.mark.parametrize("q_lens,q_bucket", [
    ([4] * 128, 1024),                   # 128 blocks of 4: the plain launch
    ([4] * 64 + [1000], 2048),           # blocks beside a prompt chunk
], ids=["blocks-q1024", "blocks-and-chunk-q2048"])
def test_ragged_paged_attention_compiles_for_grouped_heads_of_128(
        v5e, q_lens, q_bucket):
    """SDAR-30B-A3B's shape: 32 query heads on 4 KV heads of 128, the
    block mask of 4. A KV head's 8 query heads fold into the rows (64 a
    q block), K and V are whole lane tiles taken apart in the kernel, a
    block is 32 KB and the walk still fetches 8 at a time."""
    S, T, NB, hq, hkv, dh, bs = len(q_lens), 192, 64, 32, 4, 128, 16
    assert kv_group_blocks(hkv, bs, dh, "bfloat16") * bs == 128
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, [20] * S,
                                                q_bucket=q_bucket)
    tables, lo = np.zeros((S, T), np.int32), np.zeros(S, np.int32)
    kv_len = np.asarray([20 + n for n in q_lens], np.int32)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    def fn(q, pool):
        return ragged_paged_attention(q, pool, 1, blk_seq, qstart, pos0,
                                      tables, lo, kv_len, mask_block=4)

    text = _compile(fn, sds((hq, q_bucket, dh), jnp.bfloat16),
                    sds((2, NB + 1, hkv, bs, 2 * dh), jnp.bfloat16))
    assert "ragged_paged_attention" in text


def test_kv_append_compiles_for_four_kv_heads_of_128(v5e):
    """The same model's cache write: 4 KV heads, 256 lanes a row."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    text = _compile(lambda pool, wb, off, rows:
                    kv_append(pool, 1, wb, off, rows),
                    sds((2, 65, 4, 16, 256), jnp.bfloat16),
                    sds((1024,), jnp.int32), sds((1024,), jnp.int32),
                    sds((1024, 4, 256), jnp.bfloat16))
    assert "kv_append" in text


@pytest.mark.parametrize("hkv,window,q_lens,q_bucket", [
    (4, 0, [1] * 128, 1024),               # a global layer, decode rows
    (8, 128, [1] * 128, 1024),             # a window layer, decode rows
    (4, 0, [1] * 64 + [1000], 2048),       # beside a prompt chunk
    (8, 128, [1] * 64 + [1000], 2048),
], ids=["global-q1024", "window-q1024", "global-chunk-q2048",
        "window-chunk-q2048"])
def test_ragged_paged_attention_compiles_for_window_and_global_layers(
        v5e, hkv, window, q_lens, q_bucket):
    """MiMo-V2-Flash's two layer kinds at the published widths: 64 query
    heads of 192 on 4 (global) or 8 (window, W 128, a sink logit a head)
    KV heads, V heads of 128, a row stored 384 lanes wide (K, zeros to
    256, V), tables of 576 blocks. The window form carries its own
    kernel name; both fetch 8 blocks a group inside the VMEM budget."""
    S, T, NB, hq, dk, dv, lanes, bs = len(q_lens), 576, 64, 64, 192, 128, \
        384, 16
    assert kv_group_blocks(hkv, bs, 0, "bfloat16", lanes=lanes) * bs == 128
    assert 2 * 8 * hkv * bs * lanes * 2 <= KV_VMEM_BUDGET
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, [4000] * S,
                                                q_bucket=q_bucket)
    tables, lo = np.zeros((S, T), np.int32), np.zeros(S, np.int32)
    kv_len = np.asarray([4000 + n for n in q_lens], np.int32)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    def fn(q, pool, sinks):
        return ragged_paged_attention(
            q, pool, 1, blk_seq, qstart, pos0, tables, lo, kv_len,
            window=window, sinks=sinks if window else None, v_lanes=dv)

    text = _compile(fn, sds((hq, q_bucket, dk), jnp.bfloat16),
                    sds((2, NB + 1, hkv, bs, lanes), jnp.bfloat16),
                    sds((hq,), jnp.float32))
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    want = "%ragged_paged_attention_window" if window \
        else "%ragged_paged_attention"
    assert len(calls) == 1 and calls[0].startswith(want), calls
    assert window or "_window" not in calls[0]


@pytest.mark.parametrize("hq,hkv,dk,dv,lanes,kw", [
    (20, 20, 64, 64, 128, {}),                            # GPT-2 large
    (32, 4, 128, 128, 256, {"mask_block": 4}),            # SDAR-30B-A3B
    (64, 4, 192, 128, 384, {}),                           # MiMo, global
    (64, 8, 192, 128, 384, {"window": 128, "sinks": True}),   # MiMo, window
    # Falcon-H1: g = 5, the first group that is no power of two — a folded
    # q block is 40 rows, two and a half packed bf16 tiles (staged in f32)
    (20, 4, 128, 128, 256, {}),
], ids=["gpt2-large", "sdar", "mimo-global", "mimo-window", "falcon-h1"])
def test_wide_q_step_compiles_at_the_cells_widths(v5e, hq, hkv, dk, dv,
                                                  lanes, kw):
    """A grid step of M = 4 q blocks at the widths of the cells that run
    it, at Q 2,048 with a 1,000-row chunk whose inside is wide steps: 32
    query rows x g folded heads a KV head — at MiMo's global layers the q
    block is 1 MB twice, scores and accumulator 1 MB each. A working set
    Mosaic cannot place fails here, not on the chip."""
    q_lens, q_bucket, T, NB, bs = [1] * 64 + [1000], 2048, 576, 64, 16
    S, g = len(q_lens), hq // hkv
    v_lanes = dv if dv != dk else 0
    m = q_step_blocks(hkv, g, bs, lanes, "bfloat16", v_lanes=v_lanes,
                      q_blocks=q_bucket // 8)
    assert m == 4                   # its working set is inside the budget
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, [4000] * S,
                                                q_bucket=q_bucket)
    tables, lo = np.zeros((S, T), np.int32), np.zeros(S, np.int32)
    kv_len = np.asarray([4000 + n for n in q_lens], np.int32)
    walked = ragged_walk_counts(
        blk_seq, qstart, pos0, lo, kv_len, T, step_blocks=m, block_size=bs,
        group=8, mask_block=kw.get("mask_block", 1),
        window=kw.get("window", 0))
    assert walked["q_blocks_wide"] == 124 and walked["q_blocks"] == 189
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    def fn(q, pool, sinks):
        return ragged_paged_attention(
            q, pool, 1, blk_seq, qstart, pos0, tables, lo, kv_len,
            mask_block=kw.get("mask_block", 1), window=kw.get("window", 0),
            sinks=sinks if kw.get("sinks") else None, v_lanes=v_lanes)

    text = _compile(fn, sds((hq, q_bucket, dk), jnp.bfloat16),
                    sds((2, NB + 1, hkv, bs, lanes), jnp.bfloat16),
                    sds((hq,), jnp.float32))
    assert "ragged_paged_attention" in text


@pytest.mark.parametrize("q_lens,q_bucket,table_len", [
    ([1] * 128, 1024, 192),              # 128 decode rows: the plain launch
    ([1] * 128 + [1024], 2048, 320),     # the same beside a prompt chunk
], ids=["decode-q1024", "decode-and-chunk-q2048"])
def test_mla_paged_attention_compiles_at_the_cells_widths(v5e, q_lens,
                                                          q_bucket,
                                                          table_len):
    """The latent kernel at the widths of the two cells that run it
    (``axk1-ep16``, ``longcat-flash-ep32``): 64 heads folded into the
    rows, stored rows of 640 lanes whose first 512 are the value, blocks
    of 16 tokens over a pool of the cells' order of blocks. A group is 32
    blocks of 20 KB, its copies unrolled (what Mosaic refuses of them —
    a slice off the tiling, a wait whose bytes no copy has, scratch past
    VMEM — shows here), and the two group buffers stay inside the budget
    the per-head kernel states."""
    from paddle_tpu.ops.mla_paged_attention import (latent_group_blocks,
                                                    mla_paged_attention)
    S, heads, lanes, v_lanes, bs, NB = len(q_lens), 64, 640, 512, 16, 33000
    group = latent_group_blocks(bs, lanes, "bfloat16")
    assert group == 32
    assert 2 * group * bs * lanes * 2 <= KV_VMEM_BUDGET
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, [1500] * S,
                                                q_bucket=q_bucket)
    tables, lo = np.zeros((S, table_len), np.int32), np.zeros(S, np.int32)
    kv_len = np.asarray([1500 + n for n in q_lens], np.int32)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    def fn(q, pool):
        return mla_paged_attention(q, pool, 3, blk_seq, qstart, pos0,
                                   tables, lo, kv_len, v_lanes=v_lanes,
                                   scale=0.1)

    text = _compile(fn, sds((q_bucket, heads, lanes), jnp.bfloat16),
                    sds((8, NB + 1, 1, bs, lanes), jnp.bfloat16))
    assert text.count("tpu_custom_call") == 1
    assert "mla_paged_attention" in text


@pytest.mark.parametrize("hkv", [4, 8], ids=["global", "window"])
def test_kv_append_compiles_for_rows_of_384_lanes(v5e, hkv):
    """The same model's cache write: K 192 | V 128 in rows of 384."""
    assert append_ring_blocks(hkv, 16, 192, "bfloat16") * hkv * 16 * 384 \
        * 2 <= APPEND_VMEM_BUDGET
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    text = _compile(lambda pool, wb, off, rows:
                    kv_append(pool, 1, wb, off, rows),
                    sds((2, 65, hkv, 16, 384), jnp.bfloat16),
                    sds((1024,), jnp.int32), sds((1024,), jnp.int32),
                    sds((1024, hkv, 384), jnp.bfloat16))
    assert "kv_append" in text


def _append_avals(chip, heads, q_bucket, pool_dtype="bfloat16"):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return [sds((2, 65, heads, 16, 2 * DH), pool_dtype),
            sds((q_bucket,), jnp.int32), sds((q_bucket,), jnp.int32),
            sds((q_bucket, heads, 2 * DH), jnp.bfloat16)]


@pytest.mark.parametrize("heads,q_bucket,pool_dtype", [
    (20, 512, "bfloat16"),        # gpt2-large, the decode program
    (20, 1024, "bfloat16"),       # decode rows + a chunk
    (12, 512, "bfloat16"),        # 124M
    (5, 512, "float32"),          # a TP shard of 20 heads over 4 devices
], ids=["large-q512", "large-q1024", "124m-q512", "tp-shard-f32"])
def test_kv_append_compiles_at_gpt2_widths(v5e, heads, q_bucket,
                                           pool_dtype):
    """What interpret mode cannot see of the append: the block's DMA
    both ways, the select on packed bf16 rows, the ring inside the
    budget the module states."""
    assert append_ring_blocks(heads, 16, DH, pool_dtype) * heads * 16 \
        * 2 * DH * jnp.dtype(pool_dtype).itemsize <= APPEND_VMEM_BUDGET
    text = _compile(lambda pool, wb, off, rows:
                    kv_append(pool, 1, wb, off, rows),
                    *_append_avals(v5e, heads, q_bucket, pool_dtype))
    assert "kv_append" in text


def test_append_and_attention_tower_updates_the_donated_pool_in_place(v5e):
    """Two layers of append + ragged kernel at GPT-2 large widths, the
    pool donated, as ``_fused_tower`` chains them: the compiled module
    keeps ONE pool (aliased to its output, no temporary of its size, no
    ``copy`` of its shape) and no XLA scatter — the 36 scatters of 10,240
    one-row updates were 61% of a decode launch (PERF.md, PR 30)."""
    heads, q_bucket = 20, 512
    q_lens = [1] * 64
    blk_seq, qstart, pos0, _, _ = ragged_layout(q_lens, [20] * 64,
                                                q_bucket=q_bucket)
    tables = np.zeros((64, 64), np.int32)
    lo = np.zeros(64, np.int32)
    kv_len = np.full(64, 21, np.int32)
    pool, wb, off, rows = _append_avals(v5e, heads, q_bucket)
    q = jax.ShapeDtypeStruct((heads, q_bucket, DH), jnp.bfloat16,
                             sharding=v5e)

    def tower(pool, wb, off, rows, q):
        for li in range(2):
            pool = kv_append(pool, li, wb, off, rows)
            a = ragged_paged_attention(q, pool, li, blk_seq, qstart, pos0,
                                       tables, lo, kv_len)
            q = q + a                   # the next layer waits for this one
        return pool, q

    compiled = jax.jit(tower, donate_argnums=0).lower(
        pool, wb, off, rows, q).compile()
    text = compiled.as_text()
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert sum(c.startswith("%kv_append") for c in calls) == 2, calls
    assert sum(c.startswith("%ragged_paged_attention") for c in calls) == 2
    assert "scatter" not in text
    pool_shape = "bf16[%s]" % ",".join(str(d) for d in pool.shape)
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and pool_shape in ln]
    assert not copies, copies
    pool_bytes = int(np.prod(pool.shape)) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 4


def test_flash_attention_fwd_bwd_compiles_at_gpt2_train_shape(v5e):
    """The train cell's own call ([16, 1024, 12, 64] bf16, causal) with
    the plan's tiles and no autotune cache: the CPU rehearsal of the
    Mosaic lowering — wide tiles, two heads a step, scoped VMEM.  q, k, v
    arrive as the projections leave them, [B, S, H*D], and are split into
    heads by a reshape (``MultiHeadAttention._split_heads``)."""
    b, s = 16, 1024
    x = jax.ShapeDtypeStruct((b, s, H * DH), jnp.bfloat16, sharding=v5e)
    plan = pk.flash_attention_plan(s, s, DH, H, True, jnp.bfloat16)
    assert plan["packed"] and plan["heads_per_step"] == 2
    assert (plan["block_q"], plan["block_k"]) == (512, 512)

    def loss(q, k, v):
        q, k, v = (t.reshape(b, s, H, DH) for t in (q, k, v))
        return pk.flash_attention(q, k, v, is_causal=True).reshape(
            b, s, H * DH).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    # one forward and ONE backward kernel, both found by the roofline
    # reader (it sums every op whose name holds ``flash_attention``)
    assert sorted(c.split(".")[0].lstrip("%") for c in calls) == [
        "jvp_flash_attention_fwd_", "transpose_jvp_flash_attention_bwd__"], \
        calls
    # two heads a step work on [B, S, H*D] as it stands: no copy or
    # transpose of q, k, v, o or a gradient surrounds the kernels
    moved = [ln for ln in text.splitlines()
             if (" transpose(" in ln or " copy(" in ln) and any(
                 shape in ln for shape in ("bf16[16,12,1024,64]",
                                           "bf16[16,1024,12,64]",
                                           "bf16[16,1024,768]"))]
    assert not moved, moved


def test_fused_layer_norm_fwd_bwd_compiles_at_gpt2_train_shape(v5e):
    x = jax.ShapeDtypeStruct((4096, 768), jnp.bfloat16, sharding=v5e)
    w = jax.ShapeDtypeStruct((768,), jnp.float32, sharding=v5e)

    def loss(x, w, b):
        return pk.fused_layer_norm(x, w, b).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, w, w)
    assert "fused_layer_norm_fwd" in text and "fused_layer_norm_bwd" in text


@pytest.mark.parametrize("shape", [(50304, 768), (768, 3072)],
                         ids=["embedding", "mlp"])
def test_fused_adamw_compiles_within_vmem(v5e, shape):
    """Both were refused (RESOURCE_EXHAUSTED in VMEM) when the kernel
    mapped the whole flattened parameter as one block."""
    a = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)
    sc = jax.ShapeDtypeStruct((7,), jnp.float32, sharding=v5e)
    fn = pk._fused_adamw_callable(shape, "float32", False)
    assert "fused_adamw" in _compile(fn, a, a, a, a, sc)


# (held, experts, rows, k, E, I) of the routed-expert cells' launches
ROUTED_CELLS = {
    "lfm2-24b-a2b-pp4": (64, 64, 1152, 4, 2048, 1536),
    "sdar-30b-a3b-pp8": (128, 128, 1024, 8, 2048, 768),
    "axk1-ep16": (12, 192, 128, 8, 7168, 2048),
    "axk1-ep16-chunk": (12, 192, 1152, 8, 7168, 2048),
    "mimo-v2-flash-ep16": (16, 256, 1152, 8, 4096, 2048),
}


def _ragged_dot_tiles(chip, rows, n, K, N):
    """The ``tm,tk,tn`` the compiled grouped product names for itself."""
    import re
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    text = _compile(
        lambda a, b, s: jax.lax.ragged_dot(
            a, b, s, preferred_element_type=jnp.float32),
        sds((rows, K), jnp.bfloat16), sds((n, K, N), jnp.bfloat16),
        sds((n,), jnp.int32))
    tiles = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', text)
    assert tiles, "the grouped product no longer names its tiling"
    return [tuple(int(v) for v in t) for t in tiles]


@pytest.mark.parametrize("cell", list(ROUTED_CELLS))
def test_the_grouped_products_row_tile_is_the_plans(v5e, cell):
    """``routed_experts`` starts every expert's rows on a multiple of
    ``T`` and hands the grouped products ``M = T x odd`` rows a trip
    BECAUSE the compiler then walks them in tiles of ``T`` (PERF.md 44):
    if a compiler chooses its tile otherwise, this is where it shows."""
    from paddle_tpu.models.axk1 import routed_plan
    n, _, _, _, E, I = ROUTED_CELLS[cell]
    T, M, _ = routed_plan(*ROUTED_CELLS[cell])
    for K, N in ((E, I), (I, E)):               # gate | up, and down
        assert {t[0] for t in _ragged_dot_tiles(v5e, M, n, K, N)} == {T}


@pytest.mark.parametrize("rows,tile", [(144, 16), (256, 256), (4608, 512),
                                       (1152, 128)])
def test_the_row_tile_is_the_largest_power_of_two_that_divides_the_rows(
        v5e, rows, tile):
    """What 41.2 (144 rows: tiles of 16, twice the time) and 42.2 (4,608
    rows: tiles of 512 over groups of 72) ran into."""
    assert {t[0] for t in _ragged_dot_tiles(v5e, rows, 16, 2048, 1536)} \
        == {tile}


# ---------------------------------------------------------------------------
# The Mamba-2 decode update (ops/ssm.py:state_step) at the two cells' widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,S,H,P,N,G", [
    (6, 64, 32, 128, 256, 2),       # falcon-h1-34b-pp12.decode
    (5, 128, 128, 64, 128, 8),      # nemotron3-super-ep4.decode
], ids=["falcon-h1", "nemotron3"])
def test_the_state_step_updates_the_donated_state_in_place(v5e, L, S, H, P,
                                                           N, G):
    """Two layers of ``ssm_scan`` — the step kernel, then the chunked
    scan's loop reading the kernel's OUTPUT — over the cell's whole state
    array, donated: the head block the module derives fits its budget,
    the compiled module holds the two kernels, aliases the state to its
    result with no temporary of its size, and has NO ``copy`` of the
    state's shape (1.6 | 2.7 GB: one would not fit beside the weights)."""
    from paddle_tpu.ops import ssm as SSM
    hb = SSM.step_head_block(H, P, N, G)
    assert H % hb == 0 and 4 * hb * P * N * 4 <= SSM.STEP_VMEM_BUDGET
    Q = S + 256                     # decode rows beside a chunk
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=v5e)
    i32 = jnp.int32
    lay = SSM.SeqLayout(sds((Q,), i32), sds((Q,), i32), sds((S,), i32),
                        sds((S,), i32), sds((S,), jnp.bool_))
    state = sds((L, S + 1, H, P, N))

    def mixers(state, lay, x, dt, a, b, c, d):
        for layer in (1, 2):
            y, state = SSM.ssm_scan(x, dt, a, b, c, d, state, layer, lay)
            x = x + y                   # the next layer waits for this one
        return x, state

    compiled = jax.jit(mixers, donate_argnums=0).lower(
        state, lay, sds((Q, H, P)), sds((Q, H)), sds((H,)), sds((Q, G, N)),
        sds((Q, G, N)), sds((H,))).compile()
    text = compiled.as_text()
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert sum(c.startswith("%ssm_step") for c in calls) == 2, calls
    shape = "f32[%s]" % ",".join(str(n) for n in state.shape)
    copies = [ln[:200] for ln in text.splitlines()
              if " copy(" in ln and shape in ln]
    assert not copies, copies
    state_bytes = int(np.prod(state.shape)) * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 4
