"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler runs on the host against the
``v5e:2x2`` topology description and refuses what the chip would refuse
(VMEM overflow, unaligned blocks, primitives Mosaic cannot lower).  Shapes
only, at phi4-mini-3.8b's published widths.  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.families import get_family
from repro.kernels.attention import DEFAULT_ATTN_CONFIG, flash_attention_pallas
from repro.kernels.matmul import DEFAULT_CONFIG, config_space, matmul_pallas
from repro.kernels.ssm import DEFAULT_SSM_CONFIG, ssm_scan_pallas
from repro.kernels.wkv import DEFAULT_WKV_CONFIG, wkv_pallas


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
