"""A merges file in which one generated token is one visible SSE event.

The program's default tokenizer knows ids 0-261, and the OpenAI server sends
an event only when a token decodes to text. A model with seeded weights and
a vocabulary of 32,768 would stream nothing. This makes, from the vocabulary
size alone, a file `ByteBPETokenizer.load` accepts in which every merged id
(256 .. vocab-7) decodes to 2-4 lowercase ASCII letters. What stays silent
is fixed by the tokenizer class itself: bytes 128-255 alone are partial
UTF-8 (held back until text follows), and the six specials decode to
nothing; `is_silent` names them so that a count can allow for them."""

from __future__ import annotations

import itertools
import json
import os
from typing import List, Tuple

LETTERS = "abcdefghijklmnopqrstuvwxyz"
SPECIALS = ("<|begin_of_text|>", "<|end_of_text|>", "<|start_header_id|>",
            "<|end_header_id|>", "<|eot_id|>", "<|pad|>")


def merges_for(vocab_size: int) -> List[Tuple[str, str]]:
    """`vocab_size - 256 - 6` merges, each making one new string: all
    two-letter strings, then three letters (a pair plus a letter), then
    four, in lexicographic order, as many as are needed."""
    need = vocab_size - 256 - len(SPECIALS)
    if need < 0:
        raise ValueError(f"vocabulary {vocab_size} is below 262")
    out: List[Tuple[str, str]] = []
    prev = list(LETTERS)
    while len(out) < need:
        level = []
        for left, c in itertools.product(prev, LETTERS):
            if len(out) == need:
                break
            out.append((left, c))
            level.append(left + c)
        prev = level
    return out


def is_silent(token: int, vocab_size: int) -> bool:
    """Ids that reach a client as no event of their own (see above)."""
    return 128 <= token < 256 or token >= vocab_size - len(SPECIALS)


def write(path: str, vocab_size: int) -> str:
    """Write the file (atomically; the same bytes every time)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump({"merges": merges_for(vocab_size),
                   "specials": list(SPECIALS)}, f)
    os.replace(tmp, path)
    return path
