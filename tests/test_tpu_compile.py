"""Compile the main-path Pallas kernels for a described TPU v5e at
MoE-GPT2 widths (d 768, expert d_ff 3072, 8192 tokens per chip), with
no chip attached: the TPU compiler refuses here what interpret mode
accepts (unaligned blocks, int indexing of refs, too much VMEM).

It also checks that the flat and hierarchical expert exchanges compile
to the same arithmetic around the collectives, which the CPU compiler
cannot show.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

D, F, E_LOCAL, ROWS, TOKENS, G = 768, 3072, 16, 1024, 8192, 128
HEADS, HEAD_DIM, SEQ, BATCH = 12, 64, 1024, 8


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, *structs):
    """Compile ``fn`` for the chip and check the kernel is in it."""
    text = jax.jit(fn).lower(*structs).compile().as_text()
    assert "tpu_custom_call" in text


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_expert_ffn_compiles(one_chip, dtype):
    from repro.kernels.expert_ffn import expert_ffn
    _compile(functools.partial(expert_ffn, act_name="gelu",
                               interpret=False),
             _s(one_chip, (E_LOCAL, ROWS, D), dtype),
             _s(one_chip, (E_LOCAL, D, F), dtype),
             _s(one_chip, (E_LOCAL, D, F), dtype),
             _s(one_chip, (E_LOCAL, F, D), dtype))


def test_masked_similarity_compiles(one_chip):
    from repro.kernels.similarity import masked_similarity
    _compile(functools.partial(masked_similarity, interpret=False),
             _s(one_chip, (G, D), jnp.bfloat16),
             _s(one_chip, (G, G), jnp.bool_))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gather_rows_compiles(one_chip, dtype):
    from repro.kernels.condense import gather_rows
    _compile(functools.partial(gather_rows, interpret=False),
             _s(one_chip, (TOKENS, D), dtype),
             _s(one_chip, (TOKENS,), jnp.int32))


@pytest.mark.parametrize("wire_dtype", ["bf16", "f8e4m3"])
def test_pack_quantize_compiles(one_chip, wire_dtype):
    from repro.kernels.pack import pack_quantize
    _compile(functools.partial(pack_quantize, wire_dtype=wire_dtype,
                               interpret=False),
             _s(one_chip, (TOKENS, D), jnp.bfloat16),
             _s(one_chip, (TOKENS,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attn import flash_attention
    qkv = [_s(one_chip, (BATCH, SEQ, HEADS, HEAD_DIM), jnp.bfloat16)] * 3
    _compile(functools.partial(flash_attention, interpret=False), *qkv)


def test_mamba_scan_compiles(one_chip):
    from repro.kernels.mamba_scan import mamba_scan
    B, S, di, N = 2, 1024, 2048, 16
    f32 = jnp.float32
    _compile(functools.partial(mamba_scan, interpret=False),
             _s(one_chip, (B, S, di), f32), _s(one_chip, (B, S, di), f32),
             _s(one_chip, (B, S, N), f32), _s(one_chip, (B, S, N), f32),
             _s(one_chip, (di, N), f32))


def _reduce_ops(hlo: str) -> collections.Counter:
    """The reduce and fusion ops of a compiled module, names dropped."""
    ops = collections.Counter()
    for line in hlo.splitlines():
        if re.search(r"= \S+ (reduce|fusion)\(", line):
            op = re.sub(r"%[\w.\-]+", "%", line.split("=", 1)[1])
            ops[re.sub(r"(metadata|backend_config)=.*", "", op)] += 1
    return ops


def test_flat_and_hier_exchange_compile_alike(v5e_2x2):
    """Dispatch, expert matmul, combine and a sum over the returned
    chunks, differentiated, on a (node=2, local=2) mesh: the two comm
    modes must differ only in their collectives. Unless the exchange is
    fenced, the TPU compiler folds hier's (node, local) reshape into the
    chunk sum and adds in another order, and training runs diverge."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm import CommContext
    from repro.comm.compat import make_mesh
    mesh = make_mesh((2, 2), ("node", "local"), devices=v5e_2x2.devices)
    M, C = 4, 1024
    rows = P(("node", "local"))

    def compiled(mode):
        comm = CommContext(mode, ("node", "local"))

        def body(x, w):
            h = jnp.tanh(comm.all_to_all(x) @ w)
            back = comm.combine(h).reshape(M, C, D)
            return jnp.sum(back.astype(jnp.float32), 0)

        def loss(x, w):
            y = jax.shard_map(body, mesh=mesh, in_specs=(rows, P()),
                              out_specs=rows)(x, w)
            return jnp.sum(y ** 2)

        x = jax.ShapeDtypeStruct((M * M * C, D), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, rows))
        w = jax.ShapeDtypeStruct((D, D), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P()))
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w) \
            .compile().as_text()

    assert _reduce_ops(compiled("flat")) == _reduce_ops(compiled("hier"))
