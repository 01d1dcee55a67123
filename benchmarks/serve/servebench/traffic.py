"""Closed-loop traffic from a mix file and ``--seed``.

A mix file (``traffic/<name>.json``) gives the number of clients and two
length distributions, prompt and output.  Each distribution becomes a
fixed table of ``cycle`` lengths: exact shares for a list of values, or
evenly spaced quantiles of a clipped lognormal.  Each round deals the
whole table out to the clients, ``cycle / clients`` requests each: the
sorted table is cut into strata of ``clients`` neighbouring lengths and
every client gets one length of each stratum, in an order of its own.
So every seed offers the same sizes, and every client about the same
work per round, in another order; a window of about a round does about
the same work whatever the seed.  Prompt token ids come from the seed
too.  The same seed gives the same requests.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


def length_table(spec: dict, cycle: int) -> np.ndarray:
    """``cycle`` lengths that follow ``spec``, in ascending order.

    ``{"values": [...], "weights": [...]}`` gives each value its share
    of the table (largest remainders settle the rounding);
    ``{"lognormal_median": m, "sigma": s, "min": a, "max": b}`` gives the
    quantiles ``(i + 0.5) / cycle`` of a lognormal clipped to [a, b].
    """
    if "values" in spec:
        vals = [int(v) for v in spec["values"]]
        w = np.asarray(spec.get("weights", [1] * len(vals)), float)
        share = w / w.sum() * cycle
        counts = np.floor(share).astype(int)
        for i in np.argsort(-(share - counts), kind="stable")[: cycle - counts.sum()]:
            counts[i] += 1
        return np.sort(np.repeat(vals, counts))
    nd = statistics.NormalDist()
    med, sig = float(spec["lognormal_median"]), float(spec["sigma"])
    q = [med * math.exp(sig * nd.inv_cdf((i + 0.5) / cycle)) for i in range(cycle)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(int)


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int            # the client's n-th request; -1 for its pre-roll
    prompt: np.ndarray    # (1, prompt_len) int32
    n_out: int


class Traffic:
    """The requests of ``clients`` callers that each send the next one
    when the last one finished (no think time)."""

    def __init__(self, mix: dict, vocab: int, seed: int, page_tokens: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = int(seed) % (1 << 64)
        self.page_tokens = page_tokens
        self.clients = int(mix["clients"])
        cycle = int(mix.get("cycle", 40))
        if cycle % self.clients:
            raise ValueError(f"cycle {cycle} is not a multiple of "
                             f"{self.clients} clients")
        self.prompts = length_table(mix["prompt_tokens"], cycle)
        self.outputs = length_table(mix["output_tokens"], cycle)
        self._rounds = {}
        self._sent = [0] * self.clients

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def _tokens(self, client: int, index: int, n: int) -> np.ndarray:
        rng = self._rng(0, client, index + 1)
        return rng.integers(0, self.vocab, (1, n), dtype=np.int32)

    def _deal(self, table: np.ndarray, stream: int, rnd: int) -> np.ndarray:
        """(clients, per-client) lengths of one round: client ``c`` gets
        one length of each stratum, in an order of its own."""
        strata = table.reshape(-1, self.clients)          # ascending table
        rng = self._rng(stream, rnd)
        dealt = np.stack([rng.permutation(row) for row in strata], axis=1)
        return np.stack([rng.permutation(row) for row in dealt])

    def _pick(self, client: int, index: int):
        k = self.prompts.size // self.clients
        rnd = index // k
        if rnd not in self._rounds:
            self._rounds[rnd] = (self._deal(self.prompts, 1, rnd),
                                 self._deal(self.outputs, 2, rnd))
        prompts, outputs = self._rounds[rnd]
        return int(prompts[client, index % k]), int(outputs[client, index % k])

    def next(self, client: int) -> Request:
        """The client's next request."""
        i = self._sent[client]
        self._sent[client] += 1
        p, o = self._pick(client, i)
        return Request(client, i, self._tokens(client, i, p), o)

    def preroll(self, client: int) -> Request:
        """A warm-up request: it runs every prompt length of the mix
        across the clients, crosses a page boundary, and ends two ticks
        after the previous client's, so the loop starts staggered."""
        lens = sorted(set(int(v) for v in self.prompts))
        p = lens[client % len(lens)]
        return Request(client, -1, self._tokens(client, -1, p),
                       self.page_tokens + 1 + 2 * client)

    @property
    def max_prompt(self) -> int:
        return int(self.prompts.max())

    @property
    def max_output(self) -> int:
        return int(max(self.outputs.max(), self.page_tokens + 2 * self.clients))
