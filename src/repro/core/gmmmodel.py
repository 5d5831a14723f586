"""Analytic performance model for the grouped expert GEMM family.

Fifth kernel family through the paper's pipeline: the grouped matmul of a
mixture-of-experts layer (``repro.kernels.gmm``).  Same physics as
``core.perfmodel``: an overlapped compute/memory roofline over the kernel's
tile-streaming pattern, per-grid-step pipeline overhead, VMEM-overflow
configs fail, and a deterministic texture (``recmodel._texture``).

Problem: ``(rows, k, n, groups)`` — the assignments the groups hold (what a
router sends to this chip's experts, expected under uniform routing), the
contraction and output widths, and the number of groups (experts held).
The kernel visits, per group with rows, its ``tm``-row tiles, plus a tile
shared with the previous group when the group starts inside one; each
visit streams the group's whole ``k x n`` weight through VMEM.  So a small
``tm`` re-reads nothing when groups are small (decode: a few rows an
expert) and wastes MXU rows, while a large ``tm`` fills the MXU when groups
are large (prefill chunks) — the selection problem.
"""
from __future__ import annotations

import math

import numpy as np

from repro.kernels.gmm import GmmConfig, gmm_config_space

from .perfmodel import TPU_V5E, DeviceModel
from .recmodel import _ceil, _device, _round_up, _texture

GmmProblem = tuple[int, int, int, int]  # (rows, k, n, groups)

GMM_FEATURE_NAMES = ("log2_rows_per_group", "log2_k", "log2_n", "log2_groups")


def gmm_problem_features(problems: list[GmmProblem]) -> np.ndarray:
    p = np.maximum(np.asarray(problems, dtype=np.float64).reshape(-1, 4), 1.0)
    rows, k, n, g = p.T
    return np.column_stack([np.log2(rows / g), np.log2(k), np.log2(n), np.log2(g)])


def _visits(rows: int, groups: int, tm: int) -> float:
    """Expected m-tile visits: groups with rows (Poisson occupancy), each
    over its own tiles, plus a shared tile where a group starts inside one."""
    lam = rows / groups
    busy = groups * (1.0 - math.exp(-lam))
    if busy <= 0:
        return 0.0
    per = rows / busy
    return busy * _ceil(max(int(round(per)), 1), tm) + (busy - 1) * (1.0 - min(per, tm) / tm)


def predict_gmm_time(problem: GmmProblem, cfg: GmmConfig, device: DeviceModel = TPU_V5E, *,
                     dtype_bytes: int = 2, texture: bool = True) -> float:
    """Predicted seconds for one grouped matmul; inf if the config is invalid."""
    rows, k, n, groups = problem
    if cfg.vmem_bytes(dtype_bytes) > device.vmem_bytes:
        return float("inf")
    tk, tn = min(cfg.tk, _round_up(k, 128)), min(cfg.tn, _round_up(n, 128))
    tiles_k, tiles_n = _ceil(k, tk), _ceil(n, tn)
    visits = _visits(rows, groups, cfg.tm)
    flops = 2.0 * visits * cfg.tm * (tiles_k * tk) * (tiles_n * tn)
    util = min(cfg.tm, device.mxu_dim) / device.mxu_dim
    t_compute = flops / (device.peak_flops * util)
    # Each visit streams its group's weight, and its lhs tile once per n tile.
    traffic = visits * (k * n + tiles_n * cfg.tm * k + cfg.tm * n) * dtype_bytes
    t_mem = traffic / device.hbm_bw
    steps = visits * tiles_n * tiles_k
    t = max(t_compute, t_mem) + steps * device.grid_step_overhead + device.launch_overhead
    if not texture:
        return t
    return t / _texture(device, "gmm", (cfg.tm, cfg.tk, cfg.tn), problem)


def predict_gmm_gflops(problem: GmmProblem, cfg: GmmConfig, device: DeviceModel = TPU_V5E,
                       **kw) -> float:
    t = predict_gmm_time(problem, cfg, device, **kw)
    if not np.isfinite(t) or t <= 0:
        return 0.0
    rows, k, n, _ = problem
    return 2.0 * rows * k * n / t / 1e9


def build_gmm_matrix(problems: list[GmmProblem], configs=None,
                     device: DeviceModel | str | None = TPU_V5E, *,
                     texture: bool = True) -> np.ndarray:
    if not isinstance(device, DeviceModel):
        device = _device(device)
    configs = list(configs if configs is not None else gmm_config_space())
    perf = np.zeros((len(problems), len(configs)))
    for i, p in enumerate(problems):
        for j, c in enumerate(configs):
            perf[i, j] = predict_gmm_gflops(p, c, device, texture=texture)
    return perf


def harvest_gmm_problems(arch_ids: list[str] | None = None) -> list[GmmProblem]:
    """Expert GEMMs the mixture-of-experts architectures launch when served."""
    from repro.configs import registry

    out: set[GmmProblem] = set()
    for arch in arch_ids or list(registry.ARCHS):
        for shape in registry.shapes_for(arch):
            out.update(registry.expert_gemm_problems(arch, shape))
    return sorted(out)
