"""Whether what the window served is right, by the plain reference.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the
family's float32 reference: each prompt followed by the tokens served
for it.  Two numbers are compared:

* ``logit_rmse``: the root mean square difference between the program's
  logits and the reference's, at a fixed set of vocabulary ids drawn
  from the seed (``probe_ids``), at every position whose logits came
  from a cached decode step.  It measures the precision of the served
  path at every position, near tie or not.
* ``max_logit_gap``: over every served token, the reference's best logit
  minus its logit of the token served.  Every served token was the
  program's greedy pick, so a sound run reads a rounding gap; a token
  altered where it is produced reads the gap of a random token.

The readings that set the limits (``--control``): the control is the
reference in the next precision down (float8 operands), read by the same
two numbers at the same positions, with the token it ranks first in
place of the served one; the fault is the served tokens with every
fifth one altered, read on the reference.  Random weights decode
greedily into long repeats with wide margins, so a lower precision flips
few tokens and the gap alone does not set the control apart; the
logits do.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: vocabulary ids at which the program's logits are kept and compared
PROBE = 512


def probe_ids(vocab: int, seed: int) -> np.ndarray:
    """``PROBE`` distinct vocabulary ids drawn from the seed, ascending."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 11])
    return np.sort(rng.choice(vocab, size=min(PROBE, vocab), replace=False))


def sample(finished: list, k: int, seed: int) -> list:
    """The longest finished request, and ``k - 1`` others by the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda tr: (-tr.n_out, tr.rid))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


@dataclasses.dataclass
class Readings:
    gap: np.ndarray            # per served token
    err: np.ndarray            # program - reference, decode positions x probe
    control_gap: np.ndarray = None
    control_err: np.ndarray = None
    fault_gap: np.ndarray = None

    @staticmethod
    def rmse(err: np.ndarray) -> float:
        return float(np.sqrt(np.mean(np.square(err)))) if err.size else np.inf

    @staticmethod
    def widest(gap: np.ndarray) -> float:
        return float(gap.max()) if gap.size else np.inf


def read(family, conf: dict, params, reqs: list, rows_pad: int,
         probe: np.ndarray, control: bool = False) -> Readings:
    """The reference's readings of the sampled requests (and, with
    ``control``, the control's and the fault's)."""
    acc = {k: [] for k in ("gap", "err", "control_gap", "control_err",
                           "fault_gap")}
    for tr in reqs:
        p, n = tr.prompt.shape[-1], tr.tokens.size
        seq = np.concatenate([tr.prompt.reshape(-1), tr.tokens[:-1]])
        tokens = np.zeros(_bucket(seq.size, 256), np.int32)
        tokens[: seq.size] = seq
        rows = np.full(max(rows_pad, n), p + n - 2, np.int32)
        rows[:n] = np.arange(p - 1, p + n - 1)
        ref = family.reference_logits(conf, params, tokens, rows)[:n]
        at = np.arange(n)
        acc["gap"].append(ref.max(-1) - ref[at, tr.tokens])
        prog = np.asarray(tr.probe, np.float32).reshape(-1, probe.size)
        acc["err"].append((prog - ref[1:, probe]).ravel())
        if control:
            low = family.reference_logits(conf, params, tokens, rows,
                                          low=True)[:n]
            acc["control_gap"].append(ref.max(-1) - ref[at, low.argmax(-1)])
            acc["control_err"].append((low[1:, probe] - ref[1:, probe]).ravel())
            altered = tr.tokens.copy()
            altered[::5] = (altered[::5] + 1) % ref.shape[-1]
            acc["fault_gap"].append((ref.max(-1) - ref[at, altered])[::5])
    out = {k: np.concatenate(v) if v else None for k, v in acc.items()}
    if not reqs:
        out["gap"] = out["err"] = np.zeros(0)
    return Readings(**out)
