"""Compile both attention kernels for a described TPU v5e at published widths.

No chip is needed: the TPU compiler builds for a topology that is described,
not attached, and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, primitives Mosaic cannot lower, scoped VMEM overruns).
Interpret mode on the CPU checks none of that.  Shapes are internlm2-1.8b's
attention widths (H 16, Hkv 8, head_dim 128) with the serving defaults:
block_k 256 dense, pages of 16, verify rows of 5 queries.  The paged
decode cases cover both of its walks: blocks of 8-bit pages fetched by
async copies, and 4-bit pages (64 bytes wide) one per grid step.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pim_attention import pim_attention_pallas
from repro.kernels.pim_decode import pim_decode_pallas

B, H, HKV, DH = 2, 16, 8, 128
SK, PS = 1024, 16
N_PAGES = B * SK // PS + 1
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry written here could not be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, or it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    cc.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = saved_log_dir


def _operands(chip, sq, kv_bits, paged):
    """Shapes of one launch: q rows (B*H, sq), KV dense (B*Hkv, SK) or a
    page pool (Hkv, N_PAGES, PS), per-row offsets, lengths and q_len.  A
    whole serve step compiled for a described chip does not surface Mosaic
    errors the kernel alone does, so the kernels get the serving dtypes
    here."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    dhk = DH * kv_bits // 8
    kv_rows = (HKV, N_PAGES, PS) if paged else (B * HKV, SK)
    kv = [s(kv_rows + (dhk,), jnp.int8), s(kv_rows, jnp.float32)]
    # q scales in the serving path's compute dtype, bf16
    args = ([s((B * H, sq, DH), jnp.int8), s((B * H, sq), jnp.bfloat16)]
            + kv + kv + [s((B,), jnp.int32), s((B,), jnp.int32)])
    kwargs = {"q_len": s((B,), jnp.int32)}
    if paged:
        kwargs["page_table"] = s((B, SK // PS), jnp.int32)
    return args, kwargs


@pytest.mark.parametrize("kind,sq,kv_bits,paged", [
    ("prefill", 256, 8, False),
    ("prefill", 256, 8, True),
    ("prefill", 256, 4, True),
    ("decode", 1, 8, False),
    ("decode", 1, 8, True),
    ("decode", 1, 4, False),
    ("verify", 5, 8, False),
    ("verify", 5, 8, True),
    ("verify", 5, 4, False),
    ("decode", 1, 4, True),
    ("verify", 5, 4, True),
])
def test_kernel_compiles_for_v5e(one_chip, kind, sq, kv_bits, paged):
    fn = pim_attention_pallas if kind == "prefill" else pim_decode_pallas
    args, kwargs = _operands(one_chip, sq, kv_bits, paged)
    compiled = fn.lower(*args, interpret=False, return_iters=True,
                        **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    # no f32 array whose minor dim is a singleton in the (8, 128) tiled
    # layout: it pads every entry to 128 lanes (a paged V-scale plane laid
    # out so is twice the int8 K/V bytes of the call)
    padded = [(dims, order) for dims, order in re.findall(
        r"f32\[([0-9,]+)\]\{([0-9,]+):T\(8,128\)", compiled.as_text())
        if dims.split(",")[-1] == "1"
        and order.split(",")[0] == str(dims.count(","))]
    assert not padded, padded


@pytest.mark.parametrize("kind,sq,kv_bits", [
    ("decode", 1, 8), ("verify", 5, 8), ("decode", 1, 4), ("verify", 5, 4)])
def test_paged_decode_serving_call_writes_no_partials(one_chip, kind, sq,
                                                      kv_bits):
    """The serving call (no iteration map) writes only the attention
    output: its output bytes are B*H*Sq*Dh f32, and neither they nor the
    call's temporaries grow with the page table's width."""
    sizes = []
    for pages in (SK // PS, 2 * SK // PS):
        args, kwargs = _operands(one_chip, sq, kv_bits, paged=True)
        kwargs["page_table"] = jax.ShapeDtypeStruct(
            (B, pages), jnp.int32, sharding=one_chip)
        mem = pim_decode_pallas.lower(
            *args, interpret=False, **kwargs).compile().memory_analysis()
        assert mem.output_size_in_bytes == B * H * sq * DH * 4
        sizes.append(mem.temp_size_in_bytes)
    assert sizes[0] == sizes[1], sizes
