"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The Mosaic compiler refuses what interpret mode accepts: block shapes whose
last two dims are neither (8, 128)-aligned nor whole, more VMEM than a
kernel may use, a program larger than the chip's HBM.  These tests compile
the packed-attention kernel in every variant the served path uses, at
Qwen3-4B (and deepseek-v2 MLA) widths, and one whole Qwen3-4B packed step
at the size ``chip_smoke.py`` serves, against the v5e compiler.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import packed_attention as pa
from repro.models import model
from repro.serving import sampling

# v5e HBM the compiler may fill ("Used ... of 15.75G hbm")
V5E_HBM_BYTES = int(15.75 * 2**30)
# the sizes chip_smoke.py serves Qwen3-4B at on one chip
SMOKE_SLOTS, SMOKE_MAX_LEN, SMOKE_T, SMOKE_KV_BUCKET = 8, 2048, 256, 1024
# an HLO instruction's result type and opcode: "= bf16[36,8]{1,0} copy("
_RESULT_OP = re.compile(r"= (\w+\[[\d,]*\])\{[^}]*\} ([\w-]+)\(")
_HLO_DTYPE = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    prev_impl = ops._DEFAULT
    ops.set_default_impl("pallas")   # the CPU backend would pick "ref"
    yield SingleDeviceSharding(topo.devices[0])
    ops.set_default_impl(prev_impl)
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    compiled = lowered.compile()
    # the Pallas kernel is in the program, under its stable name
    assert "tpu_custom_call" in compiled.as_text()
    assert "packed_attention" in lowered.as_text()
    return compiled


# (name, T, H, KV, Dqk, Dv, kv_bucket, int8, block-table block size,
#  layers of a stacked cache read at a traced layer, or None for 4-D)
KERNELS = [
    ("bf16", 256, 32, 8, 128, 128, 1024, False, None, None),
    ("bf16-ragged-bucket", 256, 32, 8, 128, 128, 1000, False, None, None),
    ("int8", 256, 32, 8, 128, 128, 1024, True, None, None),
    ("block-table-bf16", 256, 32, 8, 128, 128, 1024, False, 16, None),
    ("block-table-int8", 256, 32, 8, 128, 128, 1024, True, 16, None),
    # deepseek-v2 absorbed MLA: 128 heads over one latent, d_v != d_qk
    ("mla-absorbed", 256, 128, 1, 576, 512, 1024, False, None, None),
    # the offline cell's mixed launch: query tiles at the widest bucket
    ("bf16-engine-T256-kv2048", 256, 32, 8, 128, 128, 2048, False, None,
     None),
    # the model step's carried Qwen3-4B stack, read in place
    ("bf16-stacked-36-kv2048", 256, 32, 8, 128, 128, 2048, False, None, 36),
    ("int8-stacked-36-kv2048", 256, 32, 8, 128, 128, 2048, True, None, 36),
    # a Qwen3-8B tp=4 shard's two kv heads
    ("bf16-stacked-36-2-kv-heads", 256, 8, 2, 128, 128, 2048, False, None,
     36),
]


@pytest.mark.parametrize("case", KERNELS, ids=[k[0] for k in KERNELS])
def test_packed_attention_compiles_for_v5e(one_chip, case):
    _, t, h, kvh, dqk, dv, bucket, int8, bs, layers = case
    n, s = SMOKE_SLOTS, SMOKE_MAX_LEN
    lead = (layers,) if layers else ()
    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    args = [_sds(one_chip, (t, h, dqk), jnp.bfloat16),
            _sds(one_chip, lead + (n, s, kvh, dqk), kv_dt),
            _sds(one_chip, lead + (n, s, kvh, dv), kv_dt),
            _sds(one_chip, (t,), jnp.int32),
            _sds(one_chip, (t,), jnp.int32)]
    kw_names = []
    if layers:
        args.append(_sds(one_chip, (), jnp.int32))
        kw_names.append("layer")
    if bs is not None:
        args.append(_sds(one_chip, (n, s // bs), jnp.int32))
        kw_names.append("block_tables")
    if int8:
        args += [_sds(one_chip, lead + (n, s, kvh), jnp.float32)] * 2
        kw_names += ["k_scale", "v_scale"]

    def fn(q, k, v, slot, lengths, *extra):
        return pa.packed_attention(q, k, v, slot, lengths, kv_bucket=bucket,
                                   **dict(zip(kw_names, extra)))
    _compile(fn, *args)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_qwen3_4b_packed_step_fits_one_v5e(one_chip, kv_dtype):
    """One whole packed step (forward + greedy sampling, cache donated as
    the engine donates it) at full Qwen3-4B width and the smoke size.  The
    int8 case runs in block-table mode, as chip_smoke.py's second engine
    (int8 + prefix caching) does."""
    cfg = get_config("qwen3-4b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab_size) == (36, 2560, 32, 8, 151936)

    def shaped(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: model.init(cfg, jax.random.PRNGKey(0))))
    cache = shaped(jax.eval_shape(lambda: model.init_cache(
        cfg, 1, SMOKE_SLOTS, SMOKE_MAX_LEN, kv_dtype)))
    t = SMOKE_T
    vec = _sds(one_chip, (t,), jnp.int32)
    tables = None
    if kv_dtype == "int8":
        tables = _sds(one_chip, (SMOKE_SLOTS, SMOKE_MAX_LEN // 16),
                      jnp.int32)

    def step(params, cache, tokens, slot, pos, active, dst, tables):
        logits, new_cache = model.forward_packed(
            cfg, params, tokens, cache, slot, pos, pos, active,
            kv_bucket=SMOKE_KV_BUCKET, token_dst=dst, block_tables=tables)
        return sampling.greedy(logits[0]), new_cache

    compiled = _compile(step, params, cache,
                        _sds(one_chip, (1, t), jnp.int32), vec, vec, vec,
                        vec if tables is not None else None, tables,
                        donate=(1,))
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    # the engine also keeps a one-slot fresh cache beside the step
    slot_init = cache_bytes // SMOKE_SLOTS
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes + slot_init)
    assert used <= V5E_HBM_BYTES, (used / 2**30, mem)

    # the stacked cache is carried through the layer loop and each layer
    # writes its new rows in place (DESIGN.md §8): no copy of a whole
    # stack and no write-back of a whole layer into one (as scanned input
    # and output, 2.28 GiB of temporaries at these sizes) ...
    stack_types = {f"{_HLO_DTYPE[a.dtype.name]}[{','.join(map(str, a.shape))}]"
                   for a in jax.tree.leaves(cache)}
    whole_stack_ops = [
        m.group(0) for m in _RESULT_OP.finditer(compiled.as_text())
        if m.group(1) in stack_types
        and m.group(2) in ("copy", "dynamic-update-slice")]
    assert not whole_stack_ops, whole_stack_ops[:4]
    # ... and the kernel reads the carried stack in place (DESIGN.md §9):
    # no slice, copy or transpose cuts a layer's K/V rows out of it, in
    # slot-major or head-major order (block-table mode: the layer's blocks)
    n, s, kvh, hd = SMOKE_SLOTS, SMOKE_MAX_LEN, cfg.n_kv_heads, \
        cfg.resolved_head_dim
    rows = (((n, s), (n * s // 16, 16)) if tables is not None
            else ((n, SMOKE_KV_BUCKET),))
    kv_dt = _HLO_DTYPE[jax.tree.leaves(cache)[0].dtype.name]
    layer_types = {f"{kv_dt}[{','.join(map(str, lead + shape))}]"
                   for a, b in rows for lead in ((), (1,))
                   for shape in ((a, b, kvh, hd), (a, kvh, b, hd))}
    layer_ops = [
        m.group(0) for m in _RESULT_OP.finditer(compiled.as_text())
        if m.group(1) in layer_types and m.group(2) != "bitcast"]
    assert not layer_ops, layer_ops[:4]
    assert mem.temp_size_in_bytes < 2**29, mem.temp_size_in_bytes / 2**30
    # ... and the donated cache is still updated in place
    assert mem.alias_size_in_bytes == cache_bytes, mem
