"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest
one, is run through the configuration's plain reference: once over each
prompt followed by its served tokens. At every served position the
reference's best logit is compared with the reference's logit of the token
the program served there; the widest such gap over the sample is the
number compared. It is valid for greedy decoding, which every cell uses.

The control puts the reference, computed with float8 weights, in the
program's place: at the same positions, the token that the float8 model
ranks first is read against the float32 reference in the same way.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import spec

SAMPLE_MIN_TOKENS = 400
SAMPLE_MAX_REQUESTS = 8


def load_reference(bench_dir: Path, architecture: str):
    return spec.load_module(bench_dir / "references" / f"{architecture}.py",
                            f"perfbench_ref_{architecture}")


def sample(finished: Sequence, seed: int) -> List:
    """The longest finished request, then others in an order drawn from
    the seed, until the sample holds SAMPLE_MIN_TOKENS served tokens or
    SAMPLE_MAX_REQUESTS requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                            r.rid))
    picked = [order[-1]]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    for i in rng.permutation(len(order) - 1):
        if (sum(len(r.tokens) for r in picked) >= SAMPLE_MIN_TOKENS
                or len(picked) >= SAMPLE_MAX_REQUESTS):
            break
        picked.append(order[int(i)])
    return picked


def gaps(ref, cfg: Dict, weights, prompt: Sequence[int],
         served: Sequence[int], control: bool = False) -> np.ndarray:
    """Per served position: reference best logit minus the reference logit
    of the token served there (``control``: of the token the float8
    reference ranks first)."""
    seq = list(prompt) + list(served)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    lg = ref.logits(cfg, weights, seq[:-1] if len(served) else seq, at)
    best = lg.max(axis=1)
    if control:
        pick = ref.logits(cfg, weights, seq[:-1], at, fp8=True).argmax(1)
    else:
        pick = np.asarray(served, np.int64)
    return best - lg[np.arange(len(at)), pick]


def widest_gap(ref, cfg: Dict, weights, requests: Sequence,
               control: bool = False) -> Optional[float]:
    out = [gaps(ref, cfg, weights, r.prompt, r.tokens, control).max()
           for r in requests if r.tokens]
    return float(max(out)) if out else None
