"""Compile for TPU v5e, without a chip: every Pallas kernel at paper widths
and the qwen3-4b decode steps the chip smoke serves, each with the
compiler the chip uses. Nothing runs, so these check only that the chip's
compiler accepts the programs and that they fit its memory.

The topology is described inside a fixture (never at import): only the
worker that runs this file loads the TPU library. JAX's persistent
compilation cache is off around these compiles — a program compiled for a
described chip can be written to it but not read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.configs import get_config
from repro.hw import TPU_V5E
from repro.models import abstract_cache, abstract_params
from repro.models.model import init_paged_cache

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# the chip smoke's pool: decode batch 8, max_seq_len 2048, 16-token pages
BATCH, SEQ, PAGE = 8, 2048, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip):
    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return s


def _kernel_case(name, s):
    """(wrapper, args, kwargs) of one kernel at its paper widths."""
    B, L, nb = BATCH, 4096, 4096 // PAGE
    P = B * nb + 1
    if name == "gqa_decode":          # qwen3-4b: H 32, KV 8, hd 128, 36 layers
        return K.gqa_decode_attention, (
            s((B, 1, 32, 128)), s((B, 1, 8, 128)), s((B, 1, 8, 128)),
            s((36, B, L, 8, 128)), s((36, B, L, 8, 128)), s((), I32),
            s((B,), I32), s((B,), jnp.bool_)), {"scale": 128 ** -0.5}
    if name == "gqa_paged_decode":
        return K.gqa_paged_decode_attention, (
            s((B, 1, 32, 128)), s((P, PAGE, 8, 128)), s((P, PAGE, 8, 128)),
            s((B, nb), I32), s((B,), I32)), {"scale": 128 ** -0.5}
    mla_w = (s((512, 24, 128)), s((512, 24, 128)), s((24, 128, 3072)),
             s((B, 24, 128)), s((B, 24, 64)))   # minitron-4b-mla
    if name == "mla_decode":
        return K.mla_fused_decode, mla_w + (
            s((B, L, 512)), s((B, L, 64)), s((B,), I32)), {"scale": 192 ** -0.5}
    if name == "mla_paged_decode":
        return K.mla_paged_fused_decode, mla_w + (
            s((P, PAGE, 512)), s((P, PAGE, 64)), s((B, nb), I32),
            s((B,), I32)), {"scale": 192 ** -0.5}
    if name == "gdn_prefill":         # gdn-4b: H 20, K 128
        x = s((1, SEQ, 20, 128))
        return K.gdn_prefill, (x, x, x, s((1, SEQ, 20)), s((1, SEQ, 20))), {}
    if name == "ssd_prefill":         # mamba2-4b: H 80, P 64, N 128
        return K.ssd_prefill, (
            s((1, SEQ, 80, 64)), s((1, SEQ, 80), F32), s((80,), F32),
            s((1, SEQ, 128)), s((1, SEQ, 128))), {}
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "gqa_decode", "gqa_paged_decode", "mla_decode", "mla_paged_decode",
    "gdn_prefill", "ssd_prefill",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = _kernel_case(name, _shapes(one_chip))
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled, donated_bytes):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes == donated_bytes, "the cache was not donated"
    assert total <= TPU_V5E.hbm_capacity, f"{total} B do not fit one chip"


def _tree_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _writes_cache_in_place(compiled, cache, n_layers):
    """The step holds no second cache: its scratch stays under two layers'
    share of the cache, and no op copies a whole stacked cache leaf."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * _tree_bytes(cache) // n_layers, f"{temp} B of scratch"
    text = compiled.as_text()
    for leaf in jax.tree.leaves(cache):
        dims = ",".join(map(str, leaf.shape))
        copies = re.findall(rf"= \w+\[{dims}\]\{{[^}}]*\}} copy\(", text)
        assert not copies, f"{len(copies)} copies of a cache leaf {leaf.shape}"


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_qwen3_decode_step_fits_one_chip(one_chip, paged):
    """The pool's serial decode program at the smoke's pool shape compiles
    for one v5e chip with its cache donated, fits its memory, and writes
    its new rows into the donated cache in place. The dense one's attention
    is the flash-decode kernel, reading the stacked cache where it lies."""
    from repro.serving.pool import decode_jit_for

    cfg = get_config("qwen3-4b")
    s = _shapes(one_chip)
    on_chip = lambda tree: jax.tree.map(lambda x: s(x.shape, x.dtype), tree)
    if paged:
        nb = SEQ // PAGE
        cache = on_chip(jax.eval_shape(
            lambda: init_paged_cache(cfg, BATCH, BATCH * nb + 1, PAGE)))
        extra = (s((BATCH, nb), I32),)
    else:
        cache = on_chip(abstract_cache(cfg, BATCH, SEQ))
        extra = ()
    args = (on_chip(abstract_params(cfg)), s((BATCH,), I32), cache,
            s((BATCH,), I32), s((BATCH,), jnp.bool_)) + extra + (
            s((2,), jnp.uint32), s((BATCH,), F32))
    compiled = decode_jit_for(cfg, paged=paged).lower(*args).compile()
    _fits(compiled, _tree_bytes(cache))
    _writes_cache_in_place(compiled, cache, cfg.n_blocks)
    assert ("tpu_custom_call" in compiled.as_text()) == (not paged)


def test_granite_decode_step_fits_one_chip(one_chip):
    """granite-4.0-h-micro's decode program at the benchmark cell's pool
    (batch 32, max_seq_len 2048): its 2.42 GB of stacked Mamba2 state and
    its KV cache are donated and written in place, with no second copy:
    scratch under one layer's state at batch 32 (76 MB x 32 / 36). Its
    64-wide heads keep the XLA attention: no kernel in the program."""
    from repro.serving.pool import decode_jit_for

    cfg = get_config("granite-4.0-h-micro")
    batch = 32
    s = _shapes(one_chip)
    on_chip = lambda tree: jax.tree.map(lambda x: s(x.shape, x.dtype), tree)
    cache = on_chip(abstract_cache(cfg, batch, SEQ))
    args = (on_chip(abstract_params(cfg)), s((batch,), I32), cache,
            s((batch,), I32), s((batch,), jnp.bool_), s((2,), jnp.uint32),
            s((batch,), F32))
    compiled = decode_jit_for(cfg).lower(*args).compile()
    _fits(compiled, _tree_bytes(cache))
    state = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
                if x.dtype == F32)
    n_ssm = sum(k == "ssm_mlp" for k in cfg.block_kinds_flat())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < state // n_ssm, f"{temp} B of scratch"
    _writes_cache_in_place(compiled, cache, cfg.n_blocks)
    assert "tpu_custom_call" not in compiled.as_text()


def test_gemma_mqa_decode_step_keeps_the_xla_attention(one_chip):
    """gemma-2b's single KV head (256 lanes wide) does not fill the
    kernel's sublane tile, and Mosaic refuses the kernel for it: its dense
    decode program compiles for one v5e with the XLA attention, in place."""
    from repro.serving.pool import decode_jit_for

    cfg = get_config("gemma-2b")
    s = _shapes(one_chip)
    on_chip = lambda tree: jax.tree.map(lambda x: s(x.shape, x.dtype), tree)
    cache = on_chip(abstract_cache(cfg, BATCH, SEQ))
    args = (on_chip(abstract_params(cfg)), s((BATCH,), I32), cache, s((BATCH,), I32),
            s((BATCH,), jnp.bool_), s((2,), jnp.uint32), s((BATCH,), F32))
    compiled = decode_jit_for(cfg).lower(*args).compile()
    _fits(compiled, _tree_bytes(cache))
    _writes_cache_in_place(compiled, cache, cfg.n_blocks)
    assert "tpu_custom_call" not in compiled.as_text()


def test_qwen3_vmap_decode_reads_each_replicas_cache_in_place(one_chip):
    """The event engine's fused decode on one chip (plain vmap over two
    replicas' banks, its default layout): the kernel runs over both
    replicas' slots and reads each replica's stacked cache where it lies,
    so no replica's cache is sliced out or copied."""
    from repro.serving.events import _batched_core
    from repro.serving.pool import decode_impl_for

    cfg = get_config("qwen3-4b")
    k = 2
    s = _shapes(one_chip)
    bank = jax.tree.map(lambda x: s((k,) + x.shape, x.dtype), abstract_cache(cfg, BATCH, SEQ))
    impl = decode_impl_for(cfg)

    def fused(params, cache, toks, lengths, active, keys, temps):
        return _batched_core(impl, None)(params, toks, cache, lengths, active, keys, temps)

    params = jax.tree.map(lambda x: s(x.shape, x.dtype), abstract_params(cfg))
    compiled = jax.jit(fused, donate_argnums=(1,)).lower(
        params, bank, s((k, BATCH), I32), s((k, BATCH), I32), s((k, BATCH), jnp.bool_),
        s((k, 2), jnp.uint32), s((k, BATCH), F32)).compile()
    _fits(compiled, _tree_bytes(bank))
    _writes_cache_in_place(compiled, bank, cfg.n_blocks)
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_mesh_decode_fits_four_chips(topo):
    """The four-chip phase's fused decode: four replicas' banks sharded one
    row per chip under shard_map, weights replicated — compiles for a 2x2
    v5e host and fits each chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.serving.events import _batched_core
    from repro.serving.pool import decode_impl_for

    cfg = get_config("qwen3-4b")
    devs = tuple(topo.devices[:4])
    mesh = Mesh(np.asarray(devs), ("replica",))
    rep = NamedSharding(mesh, PartitionSpec())
    row = NamedSharding(mesh, PartitionSpec("replica"))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
                          abstract_params(cfg))
    bank = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((4,) + x.shape, x.dtype, sharding=row),
        abstract_cache(cfg, BATCH, SEQ))
    impl = decode_impl_for(cfg)

    def fused(params, cache, toks, lengths, active, keys, temps):
        core = _batched_core(impl, devs)
        return core(params, toks, cache, lengths, active, keys, temps)

    per = lambda dtype: jax.ShapeDtypeStruct((4, BATCH), dtype, sharding=row)
    compiled = jax.jit(fused, donate_argnums=(1,)).lower(
        params, bank, per(I32), per(I32), per(jnp.bool_),
        jax.ShapeDtypeStruct((4, 2), jnp.uint32, sharding=row), per(F32)).compile()
    _fits(compiled, _tree_bytes(bank) // 4)
