"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed, a sample of the finished requests is drawn from
the seed, with the request that served the most tokens always in it, until
the sample holds ``min_compared_tokens`` served tokens or ``max_requests``
requests.  The reference runs once over each sampled prompt followed by its
served tokens.  At the position that produced served token ``i`` (the prompt's
last position for the first token, which the prefill's final chunk produced;
earlier served tokens for the rest, which decode produced), the gap is the
reference's largest logit minus its logit of the served token.  Greedy
serving makes that gap 0 up to rounding; a token altered anywhere on the
served path opens it.  The number compared is the widest gap of the sample.

The control puts the fp8 reference in the program's place: at the same
positions of the same prompts and served tokens, the token that fp8 puts
first stands in for the served token, and the same comparison against the
same limit decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

from .traffic import round_up

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Served:
    prompt: np.ndarray
    output: list[int]


def load_reference(name: str):
    """The reference module a configuration names, from ``references/``."""
    path = HERE / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: list[Served], seed: int, min_tokens: int, max_requests: int) -> list[Served]:
    """The longest finished request, then others in a seeded order."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -len(finished[i].output))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    picked = [first] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for i in picked:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        n += len(finished[i].output)
    return out


def gaps(ref, params, config: dict, served: list[Served], length: int, *, control: bool = False):
    """Per sampled request, the gap of each served token (and, with
    ``control``, the gap of the fp8 control's first choice at the same
    positions), as float arrays."""
    rows = ref.ROWS
    t_pad = round_up(length, rows)
    prog, ctrl = [], []
    for s in served:
        seq = np.concatenate([np.asarray(s.prompt, np.int32), np.asarray(s.output[:-1], np.int32)])
        n_p, n_o = len(s.prompt), len(s.output)
        tokens = np.zeros(t_pad, np.int32)
        tokens[: len(seq)] = seq
        at = np.arange(n_p - 1, n_p - 1 + n_o)  # positions that produced the served tokens
        targets = np.zeros(t_pad, np.int32)
        targets[at] = s.output
        alt = np.zeros(t_pad, np.int32)
        if control:
            alt = np.asarray(ref.control_argmax(params, config, tokens), np.int32)
        best, got, got_alt = (np.asarray(a, np.float64) for a in
                              ref.reference_stats(params, config, tokens, targets, alt))
        prog.append(best[at] - got[at])
        ctrl.append(best[at] - got_alt[at])
    return prog, (ctrl if control else None)


def widest(per_request) -> float:
    return float(max((g.max() for g in per_request if len(g)), default=float("nan")))
