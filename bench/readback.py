"""Reads acknowledged prompts back from a store as text, in its own process.

    python3 bench/readback.py --config <config> --root <store> --keys <file>
                              --part I --parts N

Started by the harness after the window, several at once, each on its
share of the keys (every N-th from the I-th); JAX runs on the CPU here,
the chip stays with the harness.  Opens the store read-only, as
``deploy.py`` builds it from the configuration, and prints
one JSON line, ``{"read": n, "bad": m}``: ``m`` counts the keys whose
text fails to read or does not hash to the key (a content key is the
SHA-256 of its text).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import wire  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--root", required=True)
    ap.add_argument("--keys", required=True)
    ap.add_argument("--part", type=int, required=True)
    ap.add_argument("--parts", type=int, required=True)
    args = ap.parse_args()
    cfg = json.loads(args.config)

    import deploy
    from repro.tokenizer.vocab import default_tokenizer

    keys = Path(args.keys).read_text().split()[args.part::args.parts]
    store = deploy.store(cfg, args.root,
                         deploy.compressor(cfg, default_tokenizer()),
                         readonly=True)

    def text_of(key: str):
        try:
            return store.get(key, verify=False)
        except Exception:               # a read that fails is a wrong answer
            return None

    bad = 0
    for i in range(0, len(keys), 64):
        part = keys[i:i + 64]
        try:
            texts = store.get_many(part, verify=False)
        except Exception:               # find which read failed
            texts = [text_of(k) for k in part]
        bad += sum(t is None or wire.content_key(t) != k
                   for k, t in zip(part, texts))
    store.close()
    print(json.dumps({"read": len(keys), "bad": bad}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
