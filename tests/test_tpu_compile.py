"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler runs on the host against the
``v5e:2x2`` topology description and refuses what the chip would refuse
(VMEM overflow, unaligned blocks, primitives Mosaic cannot lower).  Shapes
only, at phi4-mini-3.8b's published widths.  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.base import MoESpec
from repro.core.families import get_family
from repro.core.runtime import KernelRuntime
from repro.kernels.attention import DEFAULT_ATTN_CONFIG, flash_attention_pallas
from repro.kernels.gmm import DEFAULT_GMM_CONFIG, GmmConfig, gmm_config_space, gmm_pallas
from repro.kernels.matmul import DEFAULT_CONFIG, config_space, matmul_pallas
from repro.kernels.ssm import DEFAULT_SSM_CONFIG, ssm_scan_pallas
from repro.kernels.wkv import DEFAULT_WKV_CONFIG, wkv_pallas
from repro.models import moe as MOE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the persistent
    # cache without one: keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# phi4-mini-3.8b GEMMs (k, n): q/o, k/v, gate/up, down, vocab head.
_PHI4_GEMMS = {
    "qo": (3072, 3072),
    "kv": (3072, 1024),
    "ffn_up": (3072, 8192),
    "ffn_down": (8192, 3072),
    "vocab": (3072, 200064),
}
_LARGEST = max(config_space(), key=lambda c: c.vmem_bytes())
_CASES = [
    ("default-bf16", DEFAULT_CONFIG, jnp.bfloat16),
    ("largest-bf16", _LARGEST, jnp.bfloat16),
    ("largest-f32", _LARGEST, jnp.float32),
]


@pytest.mark.parametrize("m", [4, 512], ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("gemm", sorted(_PHI4_GEMMS))
@pytest.mark.parametrize("case", _CASES, ids=[c[0] for c in _CASES])
def test_matmul_compiles_for_v5e(one_chip, case, gemm, m):
    _, cfg, dtype = case
    k, n = _PHI4_GEMMS[gemm]
    text = _compiled_text(
        lambda a, b: matmul_pallas(a, b, cfg), one_chip, ((m, k), dtype), ((k, n), dtype)
    )
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, DEFAULT_ATTN_CONFIG),
        one_chip,
        ((512, 128), jnp.bfloat16), ((1024, 128), jnp.bfloat16), ((1024, 128), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text


# qwen3-moe-235b-a22b's expert GEMMs (k, n) over the 16 experts one chip of
# eight holds; rows: a 32-lane decode's and a 512-token chunk's assignments.
_QWEN3_EXPERT_GEMMS = {"gate_up": (4096, 1536), "down": (1536, 4096)}
_GMM_CASES = [DEFAULT_GMM_CONFIG, GmmConfig(16, 128, 128),
              max(gmm_config_space(), key=lambda c: c.vmem_bytes())]


@pytest.mark.parametrize("m", [256, 4096], ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("gemm", sorted(_QWEN3_EXPERT_GEMMS))
@pytest.mark.parametrize("cfg", _GMM_CASES, ids=lambda c: c.name())
def test_grouped_expert_gemm_compiles_for_v5e(one_chip, cfg, gemm, m):
    k, n = _QWEN3_EXPERT_GEMMS[gemm]
    text = _compiled_text(
        lambda a, b, g: gmm_pallas(a, b, g, cfg), one_chip,
        ((m, k), jnp.bfloat16), ((16, k, n), jnp.bfloat16), ((16,), jnp.int32),
    )
    assert "tpu_custom_call" in text and cfg.name() in text


def test_expert_layer_reads_the_expert_stacks_in_place_on_v5e(one_chip):
    """Two layers of the serving expert layer at qwen3's widths (d 4096,
    expert width 1536, 16 held, top-8 of 128), 32 tokens, scanned as the
    serving programs scan them: the grouped GEMMs take each whole layer stack
    and the scan's layer index, and no op copies a layer's experts out."""
    cfg = dataclasses.replace(registry.get("qwen3-moe-235b-a22b"), n_layers=2,
                              moe=MoESpec(128, 8, first_expert=0, n_held=16))
    d, f, held, bf16 = cfg.d_model, cfg.d_ff, cfg.moe.held, jnp.bfloat16
    assert (d, f, cfg.moe.top_k) == (4096, 1536, 8)

    def two_layers(x, router, w_gate, w_up, w_down):
        stacks = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}

        def body(x, inp):
            r, layer = inp
            out, _ = MOE.moe_serve(dict(stacks, router=r), x, cfg, layer)
            return x + out, None

        return jax.lax.scan(body, x, (router, jnp.arange(2)))[0]

    rt = KernelRuntime()
    rt.set_pallas_enabled(True)
    with rt.activate():
        text = _compiled_text(
            two_layers, one_chip, ((32, 1, d), bf16), ((2, d, 128), bf16),
            ((2, held, d, f), bf16), ((2, held, d, f), bf16), ((2, held, f, d), bf16))
    gemms = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "%gmm_" in line]
    assert len(gemms) == 3
    for line in gemms:  # operands are printed with their shapes
        assert re.search(r"custom-call\(.*bf16\[2,16,(4096,1536|1536,4096)\]", line)
    # expert bytes sliced per program: 0 (no op yields one layer's experts,
    # and none copies a whole stack)
    assert not re.search(r"= bf16\[16,(4096,1536|1536,4096)\]", text)
    assert not re.search(r"= bf16\[2,16,(4096,1536|1536,4096)\]\S* copy", text)


def _wkv(one_chip):
    f32 = jnp.float32
    return _compiled_text(
        lambda r, k, v, w, u, s: wkv_pallas(r, k, v, w, u, s, DEFAULT_WKV_CONFIG),
        one_chip,
        ((128, 64), f32), ((128, 64), f32), ((128, 64), f32), ((128, 64), f32),
        ((64,), f32), ((64, 64), f32),
    )


def _ssm(one_chip):
    f32 = jnp.float32
    return _compiled_text(
        lambda x, a, b, c, s: ssm_scan_pallas(x, a, b, c, s, DEFAULT_SSM_CONFIG),
        one_chip,
        ((128, 1600), f32), ((128, 1600, 16), f32), ((128, 16), f32), ((128, 16), f32),
        ((1600, 16), f32),
    )


@pytest.mark.parametrize(
    "family, compile_fn",
    [
        pytest.param(name, fn, marks=pytest.mark.xfail(
            strict=True, raises=(NotImplementedError, ValueError),
            reason=get_family(name).tpu_refusal))
        for name, fn in (("wkv", _wkv), ("ssm_scan", _ssm))
    ],
)
def test_refused_kernel_compiles_for_v5e(one_chip, family, compile_fn):
    """A kernel fix flips this to XPASS, which fails: then clear the family's
    ``tpu_refusal`` so the TPU dispatches to it."""
    assert "tpu_custom_call" in compile_fn(one_chip)


# The chat cell's pool: 4 lanes x 128 blocks of 16 tokens over 32 layers of
# 8 kv heads of 128, as bf16 k/v and as kv_quant's int8 k/v with float32 scales.
_POOL_CACHES = {
    "bf16": {"k": ((32, 8, 128), jnp.bfloat16), "v": ((32, 8, 128), jnp.bfloat16)},
    "kv_quant": {"k": ((32, 8, 128), jnp.int8), "v": ((32, 8, 128), jnp.int8),
                 "k_scale": ((32, 8), jnp.float32), "v_scale": ((32, 8), jnp.float32)},
}


@pytest.mark.parametrize("layout", sorted(_POOL_CACHES))
def test_pool_programs_compile_for_v5e(one_chip, layout):
    """The pool's gather and scatter at full width, as the chip compiles
    them: the scatter updates the pool in place."""
    from repro.serve.kvpool import KVPool

    class Described:  # shapes on the described chip, (layers, batch, length, ...)
        def init_cache(self, b, n):
            return {name: jax.ShapeDtypeStruct((s[0], b, n) + s[1:], d, sharding=one_chip)
                    for name, (s, d) in _POOL_CACHES[layout].items()}

    pool = KVPool(Described(), lanes=4, cache_len=2048, block_size=16)
    view = [jax.ShapeDtypeStruct(a.shape[:1] + (4, 2048) + a.shape[3:], a.dtype,
                                 sharding=one_chip) for a in pool._leaves("paged")]
    ids = [jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip) for n in (512, 4)]
    gather = pool._program("kvpool_gather_w4", pool._gather_body)
    gather.lower(pool._leaves("paged"), (), *ids).compile()
    scatter = pool._program("kvpool_scatter_w4", pool._scatter_body, donate=(0, 1))
    compiled = scatter.lower(pool._leaves("paged"), (), tuple(view), (), *ids).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in pool._leaves("paged"))
