"""Every Pallas kernel of the serving path compiles for a v5e chip.

Interpret mode (what the CPU runs) accepts kernels Mosaic refuses: blocks
that break the (8, 128) tiling rule, dynamic slices of loaded tiles. So
each kernel is compiled here with `interpret=False` at published widths
for a *described* v5e:2x2 topology — the TPU compiler runs, nothing
executes — and the compiled text must hold the Mosaic call.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _decode(B, H, KV, dh, S_c, block_k=None):
    from repro.kernels.decode_attention.ops import decode_attention
    args = [((B, 1, H, dh), bf16), ((B, S_c, KV, dh), bf16),
            ((B, S_c, KV, dh), bf16), ((S_c,), i32), ((), i32)]
    return decode_attention, args, dict(block_k=block_k)


def _flash(B, S, H, KV, dh, window):
    from repro.kernels.flash_attention.ops import flash_attention
    args = [((B, S, H, dh), bf16), ((B, S, KV, dh), bf16),
            ((B, S, KV, dh), bf16)]
    return flash_attention, args, dict(causal=True, window=window)


def _mq_decode(B, Q, H, KV, dh, S_c):
    from repro.kernels.decode_attention.multiquery import mq_decode_attention
    args = [((B, Q, H, dh), bf16), ((B, S_c, KV, dh), bf16),
            ((B, S_c, KV, dh), bf16), ((S_c,), i32), ((), i32)]
    return mq_decode_attention, args, {}


def _paged(B, H, KV, dh, n_pages, page, max_pages):
    from repro.kernels.decode_attention.paged import paged_decode_attention
    args = [((B, 1, H, dh), bf16), ((n_pages, page, KV, dh), bf16),
            ((n_pages, page, KV, dh), bf16), ((B, max_pages), i32),
            ((B,), i32)]
    return paged_decode_attention, args, {}


def _ssm(B, S, H, dh, N):
    from repro.kernels.ssm_scan.ops import ssm_scan
    args = [((B, S, H, dh), f32), ((B, S, H), f32), ((B, S, N), f32),
            ((B, S, N), f32), ((H,), f32), ((B, H, N, dh), f32)]
    return ssm_scan, args, {}


def _wkv(B, S, H, dh):
    from repro.kernels.rwkv6_scan.ops import wkv
    args = [((B, S, H, dh), f32)] * 4 + [((H, dh), f32),
                                         ((B, H, dh, dh), f32)]
    return wkv, args, {}


# (builder, widths): gemma3-1b (H 4, KV 1, dh 256, window 1024),
# internlm2-1.8b (H 16, KV 8, dh 128), hymba-1.5b SSM heads (25 x 64,
# state 16), rwkv6-3b (40 heads x 64)
CASES = {
    "decode_gemma3_1b": (_decode, dict(B=1, H=4, KV=1, dh=256, S_c=1024)),
    "decode_internlm2": (_decode, dict(B=4, H=16, KV=8, dh=128, S_c=2048)),
    "decode_block_k_2048": (_decode, dict(B=1, H=16, KV=8, dh=128,
                                          S_c=4096, block_k=2048)),
    "flash_gemma3_1b": (_flash, dict(B=1, S=1024, H=4, KV=1, dh=256,
                                     window=1024)),
    "flash_internlm2": (_flash, dict(B=1, S=512, H=16, KV=8, dh=128,
                                     window=None)),
    "mq_decode_q5": (_mq_decode, dict(B=2, Q=5, H=16, KV=8, dh=128,
                                      S_c=2048)),
    "paged_decode_page64": (_paged, dict(B=4, H=16, KV=8, dh=128,
                                         n_pages=64, page=64, max_pages=16)),
    "ssm_scan_hymba": (_ssm, dict(B=1, S=256, H=25, dh=64, N=16)),
    "wkv_rwkv6_3b": (_wkv, dict(B=1, S=256, H=40, dh=64)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    build, widths = CASES[case]
    fn, shapes, static = build(**widths)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if static.get("window") is not None:
        static["window"] = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
    static = {k: v for k, v in static.items() if v is not None}
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text(), case
