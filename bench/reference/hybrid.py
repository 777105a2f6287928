"""Plain reference for the hybrid method's token ids.

Written from the method's description (byte-level BPE as in GPT-2, paper
§3.3) and imports nothing of the program.  It trains its own vocabulary
from the configuration's stated training corpus, so no table the program
made enters the comparison:

* pre-tokenization splits text into words (contractions, letter, digit
  and punctuation runs, whitespace), and merges never cross words;
* training merges, greedily, the adjacent pair of symbols with the
  highest count over all words (ties to the smallest pair), counting
  every adjacent position, until no pair occurs twice or the vocabulary
  is full;
* encoding maps each special marker to its id (100000 + its position in
  the list) and, within a word, merges the pair of lowest rank first,
  every non-overlapping occurrence from the left, until none applies.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterable, List, Sequence, Tuple

_WORD_RE = re.compile(
    r"'s|'t|'re|'ve|'m|'ll|'d"
    r"| ?[A-Za-z_]+"
    r"| ?[0-9]+"
    r"| ?[^\sA-Za-z_0-9]+"
    r"|\s+(?!\S)|\s+"
)
SPECIAL_BASE = 100_000


def _words(text: str) -> List[bytes]:
    return [w.encode("utf-8") for w in _WORD_RE.findall(text)]


def _replace(syms: Tuple[int, ...], pair: Tuple[int, int],
             new: int) -> Tuple[int, ...]:
    out: List[int] = []
    i = 0
    while i < len(syms):
        if i + 1 < len(syms) and (syms[i], syms[i + 1]) == pair:
            out.append(new)
            i += 2
        else:
            out.append(syms[i])
            i += 1
    return tuple(out)


def _pairs(syms: Tuple[int, ...]) -> Counter:
    return Counter(zip(syms, syms[1:]))


def train(docs: Iterable[str], vocab_size: int) -> List[Tuple[int, int]]:
    freq: Counter = Counter()
    for doc in docs:
        freq.update(_words(doc))
    words = {w: tuple(w) for w in freq}
    counts: Counter = Counter()
    holders: Dict[Tuple[int, int], set] = {}
    for w, syms in words.items():
        for p, n in _pairs(syms).items():
            counts[p] += n * freq[w]
            holders.setdefault(p, set()).add(w)
    merges: List[Tuple[int, int]] = []
    while len(merges) < vocab_size - 256 and counts:
        best = min(counts, key=lambda p: (-counts[p], p))
        if counts[best] < 2:
            break
        new = 256 + len(merges)
        merges.append(best)
        for w in holders.pop(best):
            old = words[w]
            for p, n in _pairs(old).items():
                counts[p] -= n * freq[w]
                if counts[p] <= 0:
                    del counts[p]
                if p != best:
                    holders[p].discard(w)
            words[w] = _replace(old, best, new)
            for p, n in _pairs(words[w]).items():
                counts[p] += n * freq[w]
                holders.setdefault(p, set()).add(w)
    return merges


class Encoder:
    def __init__(self, merges: Sequence[Tuple[int, int]],
                 specials: Sequence[str]) -> None:
        self.rank = {tuple(p): i for i, p in enumerate(merges)}
        self.merge = [tuple(p) for p in merges]
        self.special = {s: SPECIAL_BASE + i for i, s in enumerate(specials)}
        self.split = re.compile(
            "(" + "|".join(re.escape(s) for s in specials) + ")")
        self.memo: Dict[bytes, Tuple[int, ...]] = {}

    def _word(self, w: bytes) -> Tuple[int, ...]:
        syms = tuple(w)
        while len(syms) > 1:
            ranked = [self.rank[p] for p in zip(syms, syms[1:])
                      if p in self.rank]
            if not ranked:
                break
            r = min(ranked)
            syms = _replace(syms, self.merge[r], 256 + r)
        return syms

    def encode(self, text: str) -> List[int]:
        out: List[int] = []
        for chunk in self.split.split(text):
            if not chunk:
                continue
            if chunk in self.special:
                out.append(self.special[chunk])
                continue
            for w in _words(chunk):
                syms = self.memo.get(w)
                if syms is None:
                    syms = self.memo[w] = self._word(w)
                out.extend(syms)
        return out
