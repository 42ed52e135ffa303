"""A tokenizer in which every id is visible text.

The published tokenizers cannot be fetched (no network), and the program's
fallback ``ByteTokenizer`` maps ids to bytes: random-weight models then emit
mostly invalid UTF-8, which ``DecodeStream`` withholds, so a client cannot
tell when a token reached it. Here every id of the configuration's published
``vocab_size`` is a distinct printable ASCII word (WordLevel model, split on
whitespace): ``decode`` joins words with one space, so every SSE content
frame carries ``len(text.split())`` tokens, and a prompt of ``n`` vocabulary
words is exactly ``n`` tokens. The file is loaded through the program's own
``load_tokenizer`` -> ``HFTokenizer``.
"""

from __future__ import annotations

import json
import os

# The default chat template renders "<|user|>\n{content}\n<|assistant|>\n": its
# two role markers are whole words here, so a one-message chat of n words is
# n + CHAT_OVERHEAD_TOKENS tokens.
SPECIAL_WORDS = ("<unk>", "<|user|>", "<|assistant|>", "<|system|>")
CHAT_OVERHEAD_TOKENS = 2


def word(i: int) -> str:
    """The text of token id ``i``."""
    return SPECIAL_WORDS[i] if i < len(SPECIAL_WORDS) else f"t{i}"


def first_plain_id() -> int:
    return len(SPECIAL_WORDS)


def tokenizer_json(vocab_size: int) -> dict:
    vocab = {word(i): i for i in range(vocab_size)}
    return {
        "version": "1.0",
        "truncation": None,
        "padding": None,
        "added_tokens": [],
        "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None,
        "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": SPECIAL_WORDS[0]},
    }


def write_tokenizer(vocab_size: int, directory: str) -> str:
    """Write ``tokenizer.json`` for ``vocab_size`` ids into ``directory`` (a
    fixed path inside the checkout) and return the file's path."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "tokenizer.json")
    tmp = f"{path}.{os.getpid()}.tmp"  # two runs in one checkout write the same bytes, each through a file of its own
    with open(tmp, "w") as f:
        json.dump(tokenizer_json(vocab_size), f)
    os.replace(tmp, path)
    return path


class WordTokenizer:
    """The same mapping without the ``tokenizers`` wheel (used only where it
    is missing): implements the program's ``Tokenizer`` protocol."""

    chat_template = None

    def __init__(self, vocab_size: int):
        self._n = vocab_size
        self._ids = {word(i): i for i in range(vocab_size)}

    def encode(self, text: str):
        return [self._ids.get(w, 0) for w in text.split()]

    def decode(self, ids):
        return " ".join(word(int(i)) for i in ids)

    @property
    def eos_token_ids(self):
        return []

    @property
    def vocab_size(self) -> int:
        return self._n


def load(vocab_size: int, directory: str):
    """The synthetic tokenizer through the program's loader; the benchmark's
    own class only if the ``tokenizers`` wheel is absent."""
    try:
        import tokenizers  # noqa: F401
    except ImportError:
        return WordTokenizer(vocab_size)
    from dynamo_tpu.llm.tokenizer import load_tokenizer

    return load_tokenizer(write_tokenizer(vocab_size, directory))
