"""A tokenizer whose tokens are the model's ids themselves.

Id ``i`` is the one code point ``BASE + i`` of Unicode's supplementary
private-use area A, so a prompt drawn as ids passes through the engine's
chat template and encoder unchanged and at its exact length, sampling
runs over the model's whole vocabulary, and every served token streams
back as exactly one character.  No id ends a request (``eos_id`` is -1),
so an answer always runs to its ``max_tokens``: every seed does the same
amount of work.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence

BASE = 0xF0000
MAX_VOCAB = 0xFFFFE - BASE        # code points of the area (U+F0000..FFFFD)


class IdTokenizer:
    n_special = 0
    eos_id = -1

    def __init__(self, vocab_size: int):
        if not 0 < vocab_size <= MAX_VOCAB:
            raise ValueError(f"vocab {vocab_size} outside 1..{MAX_VOCAB}")
        self._v = vocab_size

    @property
    def vocab_size(self) -> int:
        return self._v

    def encode(self, text: str, **_) -> List[int]:
        ids = [ord(c) - BASE for c in text]
        bad = [i for i in ids if not 0 <= i < self._v]
        if bad:
            raise ValueError(f"{len(bad)} characters are not token ids")
        return ids

    def decode(self, ids: Iterable[int]) -> str:
        return "".join(chr(BASE + int(i)) for i in ids)

    def token_bytes(self, token_id: int) -> bytes:
        return chr(BASE + int(token_id)).encode("utf-8")

    def apply_chat_template(self, messages: Sequence[dict]) -> str:
        """Turns concatenate with no markers: a later turn's ids start
        with every earlier turn's ids, answers included."""
        return "".join(m.get("content") or "" for m in messages)
