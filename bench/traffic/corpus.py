"""Synthetic evaluation corpus matching the paper's dataset statistics.

The benchmark's own copy of the program's corpus law (``repro.data.corpus``),
so that no later change to the program moves the traffic.  Below the copied
generator, ``law_sizes`` and ``prompt`` make traffic with a fixed set of
sizes and content addressed by index, for the generators in ``kinds/``.

The paper evaluates on 386 prompts from a markdown-docs dataset
(philschmid/markdown-docs-transformers, unavailable offline) with:

* content mix: code 82.6 %, markdown 16.8 %, plain text 0.5 %  (§4.1)
* log-normal size distribution: min 129, median 20 803, mean 30 982,
  max 213 379 characters (§4.1, Fig. 3/4)

We regenerate a corpus with the same mix and the same log-normal law
(mu = ln 20803, sigma derived from mean/median ratio), clipped to the
paper's min/max.  Content is template-based technical material (python
code with API/doc patterns, markdown documentation, prose) so redundancy
structure — the thing compression ratios actually measure — resembles the
paper's code-heavy documentation corpus.  Fully deterministic per seed.
"""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

# Log-normal parameters derived from the paper's summary statistics.
_MU = math.log(20_803.0)                       # median
_SIGMA = math.sqrt(2.0 * math.log(30_982.0 / 20_803.0))  # mean/median ratio
_MIN_CHARS, _MAX_CHARS = 129, 213_379

_CONTENT_MIX = (("code", 0.826), ("markdown", 0.168), ("text", 0.006))


@dataclass(frozen=True)
class Prompt:
    pid: int
    kind: str  # code | markdown | text
    text: str

    @property
    def n_chars(self) -> int:
        return len(self.text)


# ---------------------------------------------------------------------------
# Vocabulary pools for template generation
# ---------------------------------------------------------------------------

_IDENTIFIERS = [
    "model", "config", "tokenizer", "batch", "sequence", "attention", "hidden",
    "layer", "output", "input_ids", "logits", "embedding", "cache", "state",
    "params", "gradients", "optimizer", "learning_rate", "checkpoint", "dataset",
    "pipeline", "request", "response", "prompt", "context", "window", "mask",
    "head", "query", "key", "value", "projection", "norm", "residual", "buffer",
]

_TYPES = ["int", "float", "str", "bool", "Tensor", "Array", "Optional[int]",
          "List[str]", "Dict[str, Any]", "np.ndarray"]

_VERBS = ["compute", "apply", "build", "load", "save", "encode", "decode",
          "compress", "validate", "initialize", "update", "merge", "split",
          "shard", "gather", "scatter", "prefetch", "tokenize"]

_NOUNS = ["compression ratio", "space savings", "throughput", "memory footprint",
          "token sequence", "binary payload", "format byte", "vocabulary",
          "sliding window", "entropy coder", "checkpoint shard", "device mesh",
          "attention head", "expert router", "KV cache", "prompt store"]

_SENTS = [
    "The {n} is computed from the compressed representation before storage.",
    "Large language model applications must {v} the {n} without loss.",
    "We {v} the {n} and verify bit-perfect reconstruction via SHA-256.",
    "This configuration controls how the system will {v} each {n}.",
    "Higher levels favor ratio over speed when we {v} the {n}.",
    "Production deployments should {v} the {n} before each release.",
    "The {n} scales sub-linearly with input size across the evaluated range.",
    "Decompression of the {n} consistently outperforms compression.",
]


def _rng_choice(rng: np.random.Generator, pool: List[str]) -> str:
    return pool[int(rng.integers(0, len(pool)))]


def _gen_sentence(rng: np.random.Generator) -> str:
    t = _rng_choice(rng, _SENTS)
    return t.replace("{v}", _rng_choice(rng, _VERBS)).replace("{n}", _rng_choice(rng, _NOUNS))


def _gen_function(rng: np.random.Generator) -> str:
    name = f"{_rng_choice(rng, _VERBS)}_{_rng_choice(rng, _IDENTIFIERS)}"
    args = ", ".join(
        f"{_rng_choice(rng, _IDENTIFIERS)}: {_rng_choice(rng, _TYPES)}"
        for _ in range(int(rng.integers(1, 4)))
    )
    ret = _rng_choice(rng, _TYPES)
    body_var = _rng_choice(rng, _IDENTIFIERS)
    lines = [
        f"def {name}({args}) -> {ret}:",
        f'    """{_gen_sentence(rng)}"""',
    ]
    for _ in range(int(rng.integers(2, 7))):
        lhs = _rng_choice(rng, _IDENTIFIERS)
        rhs = _rng_choice(rng, _IDENTIFIERS)
        op = _rng_choice(rng, ["+", "*", "//", "-"])
        lines.append(f"    {lhs} = {rhs} {op} {int(rng.integers(1, 128))}")
    lines.append(f"    if {body_var} is None:")
    lines.append(f"        raise ValueError(\"{body_var} must be provided\")")
    lines.append(f"    return {body_var}")
    return "\n".join(lines)


def _gen_class(rng: np.random.Generator) -> str:
    cname = "".join(w.capitalize() for w in
                    [_rng_choice(rng, _VERBS), _rng_choice(rng, _IDENTIFIERS)])
    lines = [f"class {cname}:", f'    """{_gen_sentence(rng)}"""', ""]
    for _ in range(int(rng.integers(1, 4))):
        lines.append(_indent(_gen_function(rng), 4))
        lines.append("")
    return "\n".join(lines)


def _indent(block: str, n: int) -> str:
    pad = " " * n
    return "\n".join(pad + ln if ln else ln for ln in block.split("\n"))


def _gen_code(rng: np.random.Generator, target_chars: int) -> str:
    parts = [
        "import numpy as np",
        "from typing import Any, Dict, List, Optional",
        "",
    ]
    size = sum(len(p) + 1 for p in parts)
    while size < target_chars:
        block = _gen_class(rng) if rng.random() < 0.3 else _gen_function(rng)
        parts.append(block)
        parts.append("")
        size += len(block) + 2
    return "\n".join(parts)[:max(target_chars, _MIN_CHARS)]


def _gen_markdown(rng: np.random.Generator, target_chars: int) -> str:
    parts = [f"# {_rng_choice(rng, _NOUNS).title()} Guide", ""]
    size = sum(len(p) + 1 for p in parts)
    section = 0
    while size < target_chars:
        section += 1
        parts.append(f"## {section}. {_rng_choice(rng, _VERBS).title()} the "
                     f"{_rng_choice(rng, _NOUNS).title()}")
        parts.append("")
        for _ in range(int(rng.integers(2, 5))):
            parts.append(_gen_sentence(rng))
        parts.append("")
        if rng.random() < 0.5:
            parts.append("```python")
            parts.append(_gen_function(rng))
            parts.append("```")
            parts.append("")
        if rng.random() < 0.4:
            for _ in range(int(rng.integers(2, 6))):
                parts.append(f"- **{_rng_choice(rng, _NOUNS)}**: {_gen_sentence(rng)}")
            parts.append("")
        if rng.random() < 0.25:
            parts.append(f"See [the {_rng_choice(rng, _NOUNS)} docs]"
                         f"(https://docs.example.com/{_rng_choice(rng, _IDENTIFIERS)}).")
            parts.append("")
        size = sum(len(p) + 1 for p in parts)
    return "\n".join(parts)[:max(target_chars, _MIN_CHARS)]


def _gen_text(rng: np.random.Generator, target_chars: int) -> str:
    parts: List[str] = []
    size = 0
    while size < target_chars:
        para = " ".join(_gen_sentence(rng) for _ in range(int(rng.integers(3, 8))))
        parts.append(para)
        size += len(para) + 2
    return "\n\n".join(parts)[:max(target_chars, _MIN_CHARS)]


_GENERATORS = {"code": _gen_code, "markdown": _gen_markdown, "text": _gen_text}


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def generate_corpus(n_prompts: int = 386, seed: int = 0) -> List[Prompt]:
    """Deterministic synthetic corpus with the paper's size/type statistics."""
    rng = np.random.default_rng(seed)
    kinds: List[str] = []
    for kind, frac in _CONTENT_MIX:
        kinds.extend([kind] * max(1, round(frac * n_prompts)))
    kinds = kinds[:n_prompts]
    while len(kinds) < n_prompts:
        kinds.append("code")
    rng.shuffle(kinds)  # type: ignore[arg-type]

    sizes = np.clip(
        rng.lognormal(mean=_MU, sigma=_SIGMA, size=n_prompts),
        _MIN_CHARS, _MAX_CHARS,
    ).astype(int)
    # pin the extremes so the evaluated range matches the paper exactly
    if n_prompts >= 2:
        sizes[int(np.argmin(sizes))] = _MIN_CHARS
        sizes[int(np.argmax(sizes))] = _MAX_CHARS

    prompts = []
    for pid, (kind, target) in enumerate(zip(kinds, sizes)):
        text = _GENERATORS[kind](rng, int(target))
        # sprinkle special-token markers on a subset (exercises uint32 path)
        if pid % 9 == 0:
            text = "<|system|>\n" + text + "\n<|endofprompt|>"
        prompts.append(Prompt(pid=pid, kind=kind, text=text))
    return prompts


def corpus_stats(prompts: List[Prompt]) -> dict:
    """Summary statistics in the shape of the paper's §4.1 EDA table."""
    sizes = np.array([p.n_chars for p in prompts])
    kinds = {}
    for p in prompts:
        kinds[p.kind] = kinds.get(p.kind, 0) + 1
    pct = {f"P{q}": float(np.percentile(sizes, q)) for q in (10, 25, 50, 75, 90, 95, 99)}
    return {
        "n_prompts": len(prompts),
        "min": int(sizes.min()),
        "max": int(sizes.max()),
        "mean": float(sizes.mean()),
        "median": float(np.median(sizes)),
        "std": float(sizes.std()),
        "percentiles": pct,
        "content_mix": {k: v / len(prompts) for k, v in sorted(kinds.items())},
    }


# ---------------------------------------------------------------------------
# Traffic: the same law, with a fixed set of sizes and addressable content
# ---------------------------------------------------------------------------

#: every n-th prompt carries the special-token markers (as above)
SPECIAL_EVERY = 9


def law_sizes(n: int) -> List[int]:
    """The law's n quantiles at (i + 0.5) / n, ascending and clipped: the
    same set of sizes for every seed, so a seed changes only order and
    content, never the amount of work."""
    inv = statistics.NormalDist().inv_cdf
    return [int(min(max(math.exp(_MU + _SIGMA * inv((i + 0.5) / n)),
                        _MIN_CHARS), _MAX_CHARS)) for i in range(n)]


def law_kinds(n: int) -> List[str]:
    """The content mix over n prompts, in a fixed order."""
    kinds: List[str] = []
    for kind, frac in _CONTENT_MIX:
        kinds.extend([kind] * max(1, round(frac * n)))
    kinds = kinds[:n]
    while len(kinds) < n:
        kinds.append("code")
    return kinds


def prompt(seed: int, stream: int, index: int, kind: str, target: int,
           special: bool) -> str:
    """Prompt ``index`` of ``stream`` under ``seed``: generated from its
    own RNG, so any process can make it again from these arguments."""
    rng = np.random.default_rng([seed, stream, index])
    text = _GENERATORS[kind](rng, int(target))
    if special:
        text = "<|system|>\n" + text + "\n<|endofprompt|>"
    return text
