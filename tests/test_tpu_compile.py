"""Ahead-of-time compiles for a described TPU v5e chip, no chip needed.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described rather than attached.  These tests compile every kernel of
the continuous serving path at the widths of the models it serves —
qwen3-4b (Hq 32, Hkv 8, head_dim 128), qwen3-30b-a3b (Hq 32, Hkv 4,
head_dim 128, and its grouped expert matmul) and unimo-text (Hq = Hkv =
16, head_dim 64) — with bf16 and int8 page pools, and check that the
compiled program holds the Pallas kernel (``tpu_custom_call``).  They
catch what interpret mode cannot: blocks that break the TPU tiling
rule, unaligned slices, kernels that use too much fast memory.  A
compile that passes says nothing about results or speed.

The engine's two step programs (the fused decode scan and the packed
forward) are compiled whole, at the benchmark cells' geometry with a
smaller pool, to check that they update the KV pool in place: no copy
or slice of the stacked pool, or of one layer of it, and temporaries
smaller than the pool.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as DA
from repro.kernels import expert_matmul as EM
from repro.kernels import flash_attention as FA
from repro.kernels import quant_matmul as QM

# (Hq, Hkv, head_dim)
WIDTHS = {"qwen3-4b": (32, 8, 128), "qwen3-30b-a3b": (32, 4, 128),
          "unimo-text": (16, 16, 64)}
# serving geometry of the smoke: 4 slots, 512-token context, 16-token
# pages, a 256-token packed stream
SLOTS, PAGE, PAGES_PER_SLOT, STREAM = 4, 16, 32, 256
NUM_PAGES = SLOTS * PAGES_PER_SLOT + 1            # + the dump page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _pool_shapes(Hkv, D, pool):
    dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    kv = ((NUM_PAGES, PAGE, Hkv, D), dt)
    scale = ((NUM_PAGES, PAGE, Hkv), jnp.float32)
    ppos = ((NUM_PAGES, PAGE), jnp.int32)
    bt = ((SLOTS, PAGES_PER_SLOT), jnp.int32)
    pools = [kv, scale, kv, scale] if pool == "int8" else [kv, kv]
    return pools, ppos, bt


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_packed_attention_compiles(one_chip, arch, pool):
    Hq, Hkv, D = WIDTHS[arch]
    pools, ppos, bt = _pool_shapes(Hkv, D, pool)
    fn = DA.paged_packed_attention_q8 if pool == "int8" \
        else DA.paged_packed_attention
    n_work = STREAM // DA.PACKED_BLOCK_Q + SLOTS
    compiled = _compile(
        one_chip, lambda *a: fn(*a, window=None, scale=D ** -0.5),
        ((1, STREAM, Hq, D), jnp.bfloat16), *pools, ppos, bt,
        ((1, STREAM), jnp.int32), ((n_work, 4), jnp.int32))
    _assert_kernel(compiled)


# the packed kernel as the benchmark cells run it: (Hq, Hkv, head_dim),
# pool dtype, stream width, slots, pages per slot, pages with the dump
# page, layers; pool rows widened to whole lanes, as on a TPU
PACKED_CELLS = {
    "unimo-text": ((16, 16, 64), "int8", 512, 128, 32, 2049, 24),
    "qwen3-30b-a3b": ((32, 4, 128), "bf16", 256, 64, 128, 4097, 6),
}


def _packed_vmem_bill(q, kpool, vpool, k_scale, npages):
    """VMEM the packed kernel holds: the resident query, output and
    position blocks (double-buffered by the pipeline), both page buffer
    sets, the softmax state and a block's float32 K and V."""
    vm = DA._vmem_bytes
    _, T, Hq, D = q.shape
    page, Hkv, Dv = vpool.shape[-3:]
    bp = DA.packed_block_pages(kpool, vpool, k_scale, npages)
    g = Hq // Hkv
    resident = 2 * (vm((T, Hq, D), q.dtype) + vm((T, Hq, Dv), q.dtype)
                    + vm((T, 1), jnp.int32))
    buffers = 2 * bp * sum(vm(p.shape[2:], p.dtype)
                           for p in (kpool, vpool, k_scale, k_scale)
                           if p is not None)
    buffers += 2 * bp * vm((page, DA.POS_LANES), jnp.int32)
    state = 2 * vm((Hkv, 8 * g), jnp.float32) \
        + vm((Hkv, 8 * g, Dv), jnp.float32)
    block = 2 * vm((bp * page, Hkv, Dv), jnp.float32)
    return bp, resident + buffers + state + block


@pytest.mark.parametrize("cell", sorted(PACKED_CELLS))
def test_paged_packed_attention_compiles_at_the_cells_geometry(one_chip,
                                                               cell):
    """The packed kernel at a benchmark cell's geometry, on the layer
    stack: the shape is supported, it compiles, blocks hold 8 or 16
    pages, and its VMEM bill is within the kernel's scoped limit."""
    (Hq, Hkv, D), pool, T, slots, npages, P, L = PACKED_CELLS[cell]
    dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    kv = ((L, P, PAGE, Hkv, 128), dt)
    scale = ((L, P, PAGE, 128), jnp.float32)
    pools = [kv, scale, kv, scale] if pool == "int8" else [kv, kv]
    shapes = [((1, T, Hq, D), jnp.bfloat16), *pools, ((L, P, PAGE),
                                                      jnp.int32),
              ((slots, npages), jnp.int32), ((1, T), jnp.int32),
              ((T // DA.PACKED_BLOCK_Q + slots, 4), jnp.int32),
              ((), jnp.int32)]
    q, kpool, *_ = structs = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    bt = structs[-4]
    k_scale = structs[2] if pool == "int8" else None
    assert DA.paged_packed_shape_supported(q, kpool, bt)
    bp, bill = _packed_vmem_bill(q, kpool, structs[len(pools) // 2 + 1],
                                 k_scale, npages)
    assert bp in (8, 16)
    assert bill <= DA.PACKED_VMEM_LIMIT_BYTES
    fn = DA.paged_packed_attention_q8 if pool == "int8" \
        else DA.paged_packed_attention
    compiled = _compile(
        one_chip, lambda *a: fn(*a, window=None, scale=D ** -0.5), *shapes)
    _assert_kernel(compiled)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_compiles(one_chip, arch, pool):
    Hq, Hkv, D = WIDTHS[arch]
    pools, ppos, bt = _pool_shapes(Hkv, D, pool)
    fn = DA.paged_decode_attention_q8 if pool == "int8" \
        else DA.paged_decode_attention
    compiled = _compile(
        one_chip, lambda *a: fn(*a, window=None, scale=D ** -0.5),
        ((SLOTS, 1, Hq, D), jnp.bfloat16), *pools, ppos, bt,
        ((SLOTS, 1), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_mixed_attention_compiles(one_chip, arch, pool):
    """Speculative verify windows (k + 1 = 5 queries per slot); the
    per-chunk mixed path uses the same kernel with wider windows."""
    Hq, Hkv, D = WIDTHS[arch]
    pools, ppos, bt = _pool_shapes(Hkv, D, pool)
    fn = DA.paged_mixed_attention_q8 if pool == "int8" \
        else DA.paged_mixed_attention
    compiled = _compile(
        one_chip, lambda *a: fn(*a, window=None, scale=D ** -0.5),
        ((SLOTS, 5, Hq, D), jnp.bfloat16), *pools, ppos, bt,
        ((SLOTS, 5), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_flash_attention_compiles(one_chip, arch):
    Hq, Hkv, D = WIDTHS[arch]
    S = 256
    compiled = _compile(
        one_chip,
        lambda q, k, v, qp, kp: FA.flash_attention(
            q, k, v, qp, kp, window=None, scale=D ** -0.5),
        ((2, S, Hq, D), jnp.bfloat16), ((2, S, Hkv, D), jnp.bfloat16),
        ((2, S, Hkv, D), jnp.bfloat16), ((2, S), jnp.int32),
        ((2, S), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("M", [4, 256])
@pytest.mark.parametrize("K,N", [(2560, 9728), (2560, 151936),
                                 (1024, 4096), (1024, 12800)])
def test_quant_matmul_compiles(one_chip, M, K, N):
    """Decode rows and a packed stream against the FFN and unembedding
    weights of both models."""
    compiled = _compile(one_chip, QM.quant_matmul,
                        ((M, K), jnp.bfloat16), ((K, N), jnp.int8),
                        ((N,), jnp.float32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("rows", [64, 512, 2048])
@pytest.mark.parametrize("K,N", [(2048, 768), (768, 2048)])
def test_expert_matmul_compiles(one_chip, rows, K, N):
    """qwen3-30b-a3b's 128 experts (d 2048, width 768) against the
    token-expert rows of the smallest packed stream (8 tokens x top 8), a
    64-slot decode step and a 256-token packed stream."""
    compiled = _compile(one_chip, EM.expert_matmul,
                        ((rows, K), jnp.bfloat16), ((128, K, N), jnp.bfloat16),
                        ((128,), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("K,N", [(2048, 768), (768, 2048)])
def test_expert_matmul_compiles_on_a_whole_stack(one_chip, K, N):
    """The serving layer scan's form: the stage's 6 layers of 128 experts
    passed whole, one layer's groups read in place, and no copy of a
    layer's stack (128, K, N) in the compiled program."""
    compiled = _compile(one_chip, EM.expert_matmul,
                        ((512, K), jnp.bfloat16),
                        ((6, 128, K, N), jnp.bfloat16),
                        ((128,), jnp.int32), ((), jnp.int32))
    _assert_kernel(compiled)
    assert f"bf16[128,{K},{N}]" not in compiled.as_text()


def test_qwen3_4b_init_holds_only_bf16_weights(one_chip):
    """The jitted init of full-width qwen3-4b under the bf16 policy
    allocates exactly the bf16 parameter tree: no fp32 temporaries, so
    it fits one 16 GB chip."""
    from repro.configs.registry import get_config
    from repro.core.precision import BF16
    from repro.models import transformer as T
    cfg = get_config("qwen3-4b")
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, BF16))
    leaves = jax.tree.leaves(shapes)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    tree_bytes = sum(a.size * 2 for a in leaves)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = T._init_params_jit.lower(key, cfg, BF16).compile() \
        .memory_analysis()
    # the output is the bf16 tree (small leaves pad to the TPU tiling)
    assert tree_bytes <= mem.output_size_in_bytes <= tree_bytes + 2 ** 20
    assert mem.temp_size_in_bytes == 0
    assert tree_bytes < 9e9


# the benchmark cells' engines with fewer pages: (policy, kv, weights),
# slots, max_len, packed stream width, pages.  At 256 pages qwen3's
# pool (50 MB) would be smaller than its expert layers' temporaries.
STEP_CELLS = {"unimo-text": (("bf16", "int8", "int8"), 128, 512, 512, 256),
              "qwen3-30b-a3b-stage6": (("bf16", "auto", "auto"), 64, 2048,
                                       256, 1024)}
POOL_MOVES = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                        r"(copy|dynamic-slice|dynamic-update-slice)\(",
                        re.M)


def _pool_moves(text, shapes):
    """The copies, slices and update-slices of a compiled program whose
    result has one of ``shapes``."""
    return [m.group(0).strip() for m in POOL_MOVES.finditer(text)
            if tuple(int(d) for d in m.group(1).split(",") if d) in shapes]


@pytest.fixture(scope="module")
def step_programs(topo, one_chip):
    """The engine's fused decode (4 steps) and packed programs for each
    cell, compiled for the described chip with kernels on, and the
    pool's shapes (stacked and one layer) and bytes."""
    from repro.configs.registry import get_config
    from repro.core import kv_cache as KV
    from repro.core.engine import InferenceEngine
    from repro.core.precision import compress_weights
    from repro.core.sampling import SamplingParams
    from repro.kernels import ops as KOPS
    from repro.launch.serve import serving_policy
    from repro.models import transformer as T

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    out = {}
    for arch, (pol, slots, max_len, width, pages) in STEP_CELLS.items():
        cfg, policy = get_config(arch), serving_policy(*pol)
        params = place(jax.eval_shape(lambda k: compress_weights(
            policy.cast_params(T._init_params(k, cfg, policy)), policy)[0],
            jax.random.PRNGKey(0)))
        # the pool as the engine makes it on a TPU, rows at whole lanes
        cache = jax.eval_shape(lambda: T.init_paged_cache(
            cfg, num_pages=pages, page_size=16, max_slots=slots,
            max_len=max_len, dtype=policy.compute_dtype,
            kv_dtype=policy.kv_dtype, lanes=KV.pool_lanes(topo.devices[0])))
        shapes, pool_bytes = set(), KV.paged_pool_bytes(cache)
        for c in jax.tree.leaves(cache["layers"], is_leaf=lambda x:
                                 isinstance(x, dict)):
            for n in KV.PAGED_KEYS:
                if n in c:
                    shapes |= {c[n].shape, c[n].shape[1:]}
        cache = place(cache)
        rng = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        # the programs only: no weights are made
        engine = InferenceEngine(cfg, {}, policy=policy, max_batch=slots,
                                 max_len=max_len)
        sp, npg = SamplingParams(), -(-max_len // 16)
        with KOPS.kernel_mode_ctx("tpu"):
            step = engine._continuous_fns(sp, 4)[2].lower(
                params, i32(slots), i32(slots), i32(slots),
                i32(slots, dtype=jnp.bool_), i32(slots, npg), cache, rng)
            packed = engine._packed_fns(sp).lower(
                params, i32(1, width), i32(width), i32(width),
                i32(width // 8 + slots, 4), i32(slots), i32(slots, npg),
                i32(slots, npg), i32(slots), i32(slots), i32(slots), cache,
                rng)
        out[arch] = ({"step": step.compile(), "packed": packed.compile()},
                     shapes, pool_bytes)
    return out


@pytest.mark.parametrize("program", ["step", "packed"])
@pytest.mark.parametrize("arch", sorted(STEP_CELLS))
def test_step_programs_update_the_pool_in_place(step_programs, arch,
                                                program):
    """No copy, dynamic-slice or dynamic-update-slice of the stacked
    pool or of one layer's slice of it, and temporaries smaller than
    the pool: the layer scan carries the pool whole, the kernels read
    their layer from it, and the pool's default layout is the one the
    kernels read."""
    programs, shapes, pool_bytes = step_programs[arch]
    compiled = programs[program]
    _assert_kernel(compiled)
    moves = _pool_moves(compiled.as_text(), shapes)
    assert moves == [], "\n".join(m[:160] for m in moves)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
