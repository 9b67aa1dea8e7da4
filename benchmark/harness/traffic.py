"""The one request generator. A traffic mix is a JSON file of its
parameters; a new mix is a new file, never new code here.

    {"runner": "serve",
     "arrival": {"kind": "closed", "clients": 48}        # or
                {"kind": "poisson", "rate_per_s": 8.0},
     "prompt_tokens": [256, 1792], "output_tokens": [128, 384],
     "shared_prefix_tokens": 0, "block": 128}

Every seed sends the SAME work in the SAME order: lengths (and, for an
open loop, the gaps between arrivals) are a stratified block of `block`
values, shuffled block by block by a generator that does not depend on
the seed; the seed draws only the token ids. On the chip, two runs of one
seed finished exactly the same requests while six seeds that merely
reordered each block spread by 1.7% in tokens/s and 7% in a tail
(PERF.md, PR 25): the order of sizes is work, so it belongs to the mix.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Planned:
    index: int
    prompt: np.ndarray        # int64 token ids
    max_new_tokens: int
    gap_s: float              # open loop: seconds after the previous arrival


def _ladder(lo: int, hi: int, n: int) -> np.ndarray:
    """n integers evenly spaced over [lo, hi], both ends included."""
    return np.rint(np.linspace(lo, hi, n)).astype(np.int64)


class Generator:
    """An endless, seeded stream of planned requests."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.block = int(traffic.get("block", 128))
        p_lo, p_hi = traffic["prompt_tokens"]
        o_lo, o_hi = traffic["output_tokens"]
        fixed = np.random.default_rng(0)          # the same for every seed
        self._fixed = fixed
        self._prompt_lens = _ladder(p_lo, p_hi, self.block)
        # pair each prompt length with an output length through one fixed
        # shuffle, so long prompts do not all get long answers
        self._out_lens = fixed.permutation(_ladder(o_lo, o_hi, self.block))
        arrival = traffic["arrival"]
        self.kind = arrival["kind"]
        if self.kind == "closed":
            self.clients = int(arrival["clients"])
            self._gaps = np.zeros(self.block)
        elif self.kind == "poisson":
            self.clients = None
            self._gaps = fixed.exponential(1.0 / float(arrival["rate_per_s"]),
                                           self.block)
        else:
            raise ValueError(f"arrival kind {self.kind!r}: closed or poisson")
        self._rng = np.random.default_rng(int(seed))
        self._vocab = int(vocab_size)
        n_shared = int(traffic.get("shared_prefix_tokens", 0))
        if n_shared >= p_lo:
            raise ValueError("shared_prefix_tokens must be shorter than the "
                             "shortest prompt")
        self._prefix = self._rng.integers(0, self._vocab, n_shared)
        self._order = np.empty(0, np.int64)
        self._next = 0

    def draw(self) -> Planned:
        at = self._next % self.block
        if at == 0:
            self._order = self._fixed.permutation(self.block)
        i = int(self._order[at])
        n = int(self._prompt_lens[i])
        tail = self._rng.integers(0, self._vocab, n - len(self._prefix))
        plan = Planned(index=self._next,
                       prompt=np.concatenate([self._prefix, tail])
                       .astype(np.int64),
                       max_new_tokens=int(self._out_lens[i]),
                       gap_s=float(self._gaps[i]))
        self._next += 1
        return plan

    def padded_prompt_lengths(self, bucket: int) -> list:
        """Every prompt length of the mix rounded up to `bucket`: the
        prefill shapes a warm-up has to touch."""
        return sorted({int(-(-n // bucket) * bucket)
                       for n in self._prompt_lens})
