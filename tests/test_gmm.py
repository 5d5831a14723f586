"""The grouped expert GEMM family: the Pallas kernel (interpreted on the CPU)
against its XLA reference over ragged group sizes, empty groups included,
and its place in the tuning pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.families import get_family
from repro.core.runtime import KernelRuntime
from repro.kernels import ops
from repro.kernels.gmm import DEFAULT_GMM_CONFIG, GmmConfig, gmm_config_space, gmm_pallas
from repro.kernels.ref import gmm_ref

# (m, k, n, group sizes): uneven groups, empty groups first, last and between,
# rows no group holds, a k and an n that the tiles do not divide, no rows held.
_CASES = [
    (40, 256, 384, [3, 0, 10, 5]),
    (64, 200, 256, [0, 16, 16, 0, 16, 16]),
    (37, 128, 200, [1, 0, 0, 2, 30]),
    (8, 384, 128, [0, 0, 0, 0, 0, 0, 0, 0]),
]
_CONFIGS = [GmmConfig(16, 128, 128), GmmConfig(32, 256, 128), DEFAULT_GMM_CONFIG]


@pytest.mark.parametrize("cfg", _CONFIGS, ids=lambda c: c.name())
@pytest.mark.parametrize("case", _CASES, ids=lambda c: f"m{c[0]}_k{c[1]}_n{c[2]}")
def test_kernel_matches_ragged_dot(case, cfg):
    m, k, n, sizes = case
    rng = np.random.default_rng(m + k)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gmm_pallas(lhs, rhs, gs, cfg, interpret=True))
    want = np.asarray(gmm_ref(lhs, rhs, gs))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert np.all(got[sum(sizes):] == 0)  # rows no group holds


def test_ops_dispatch_runs_the_tuned_kernel_or_its_reference():
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.normal(size=(24, 128)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(3, 128, 256)), jnp.bfloat16)
    gs = jnp.asarray([5, 0, 11], jnp.int32)
    outs = []
    for pallas in (False, True):
        rt = KernelRuntime()
        rt.set_pallas_enabled(pallas, interpret=pallas)
        rt.set_selection_logging(True)
        with rt.activate():
            outs.append(np.asarray(ops.expert_gemm(lhs, rhs, gs, rows=16), np.float32))
        assert rt.kernel_path("expert_gemm") == ("pallas" if pallas else "xla")
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-2, atol=2e-2)


def test_family_tunes_and_selects_by_rows_per_expert():
    fam = get_family("expert_gemm")
    assert fam.default_config in gmm_config_space()
    probs = fam.harvest(["qwen3-moe-235b-a22b"])
    assert (8, 4096, 1536, 128) in probs  # one decode lane: 8 assignments
    feats = fam.features([(32, 4096, 1536, 16)])
    np.testing.assert_allclose(feats, [[1.0, 12.0, np.log2(1536), 4.0]])
    perf = fam.perf_matrix(probs, fam.config_space(), "tpu_v5e")
    assert perf.shape == (len(probs), len(fam.config_space())) and np.all(perf >= 0)
    assert len(set(perf.argmax(1).tolist())) >= 3  # no one config wins everywhere


# (m, k, n, group sizes, config) of a layer stack: an empty group, rows no
# group holds, a k that the tiles do not divide (200 over tiles of 128), and
# qwen3's expert width under a k tile wider than it (the tile shrinks to k).
_STACK_CASES = [
    (24, 200, 256, [5, 0, 11], GmmConfig(16, 128, 128)),
    (40, 1536, 128, [0, 9, 7, 20], GmmConfig(16, 2048, 128)),
]


@pytest.mark.parametrize("path", ["gmm_pallas", "ops_xla", "ops_pallas"])
@pytest.mark.parametrize("case", _STACK_CASES, ids=lambda c: f"k{c[1]}_{c[4].name()}")
def test_a_layer_stack_is_read_at_the_scanned_layer(case, path):
    """Three layers' experts in one stack; the layer index is traced, as the
    serving scan carries it; each layer's product is that layer's."""
    m, k, n, sizes, cfg = case
    rng = np.random.default_rng(k)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    stack = jnp.asarray(rng.normal(size=(3, len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    rt = KernelRuntime()
    rt.set_pallas_enabled(path == "ops_pallas", interpret=True)
    if path == "gmm_pallas":
        gemm = lambda layer: gmm_pallas(lhs, stack, gs, cfg, layer=layer, interpret=True)
    else:
        gemm = lambda layer: ops.expert_gemm(lhs, stack, gs, layer=layer, config=cfg)
    with rt.activate():
        _, got = jax.lax.scan(lambda c, layer: (c, gemm(layer)), 0, jnp.arange(3))
    for layer in range(3):
        want = np.asarray(gmm_ref(lhs, stack[layer], gs))
        np.testing.assert_allclose(np.asarray(got[layer]), want, rtol=1e-4, atol=1e-3)
    assert np.all(np.asarray(got)[:, sum(sizes):] == 0)  # rows no group holds


def test_one_layer_is_a_stack_of_one():
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(40, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 256, 384)), jnp.float32)
    gs = jnp.asarray([3, 0, 10, 5], jnp.int32)
    cfg = GmmConfig(16, 128, 128)
    one = np.asarray(gmm_pallas(lhs, rhs, gs, cfg, interpret=True))
    np.testing.assert_array_equal(
        one, np.asarray(gmm_pallas(lhs, rhs[None], gs, cfg, layer=0, interpret=True)))
    np.testing.assert_allclose(one, np.asarray(gmm_ref(lhs, rhs, gs)), rtol=1e-4, atol=1e-3)
    with pytest.raises(ValueError, match="layer"):
        gmm_pallas(lhs, rhs, gs, cfg, layer=0, interpret=True)
    with pytest.raises(ValueError, match="layer"):
        gmm_pallas(lhs, rhs[None], gs, cfg, interpret=True)
