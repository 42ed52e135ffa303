"""Tokenizer abstraction + incremental detokenization.

Ref: lib/llm/src/tokenizers.rs (HF tokenizers wrapper + ``DecodeStream``).
Backends:
- :class:`HFTokenizer` — a local ``tokenizer.json`` via the ``tokenizers``
  wheel (no network; the reference downloads from the hub, we resolve local
  paths only).
- :class:`ByteTokenizer` — UTF-8 byte-level fallback (vocab 256) so the full
  serving stack runs hermetically in tests and demos (pairs with the ``tiny``
  model config).

:class:`DecodeStream` implements incremental detokenization with the
prefix-diff technique: hold back output while the decoded tail ends in an
incomplete UTF-8/byte-fallback sequence (U+FFFD).
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Protocol, Sequence


class Tokenizer(Protocol):
    def encode(self, text: str) -> List[int]: ...

    def decode(self, ids: Sequence[int]) -> str: ...

    @property
    def eos_token_ids(self) -> List[int]: ...

    @property
    def vocab_size(self) -> int: ...


class ByteTokenizer:
    """UTF-8 bytes as tokens; id 0 reserved as EOS/pad. With ``offset`` the
    ids below it are special and byte ``b`` is id ``offset + b`` (a byte-level
    model with 64 special ids: ``ByteTokenizer(64)``, vocabulary 320)."""

    EOS = 0

    def __init__(self, offset: int = 0):
        if offset < 0:
            raise ValueError(f"offset must be >= 0, got {offset}")
        self.offset = offset

    def encode(self, text: str) -> List[int]:
        if self.offset:
            return [self.offset + b for b in text.encode("utf-8")]
        return [b if b != 0 else 1 for b in text.encode("utf-8")]

    def decode(self, ids: Sequence[int]) -> str:
        if self.offset:  # special ids carry no text
            return bytes(i - self.offset for i in ids if self.offset <= i < self.offset + 256).decode(
                "utf-8", errors="replace")
        return bytes(i & 0xFF for i in ids if i != self.EOS).decode("utf-8", errors="replace")

    @property
    def eos_token_ids(self) -> List[int]:
        return [self.EOS]

    @property
    def vocab_size(self) -> int:
        return 256 + self.offset


class HFTokenizer:
    def __init__(self, path: str):
        from tokenizers import Tokenizer as _Tok

        tokenizer_file = path if path.endswith(".json") else os.path.join(path, "tokenizer.json")
        self._tok = _Tok.from_file(tokenizer_file)
        self._eos_ids: List[int] = []
        self.chat_template: Optional[str] = None
        self.bos_token: Optional[str] = None
        self.eos_token: Optional[str] = None
        cfg_path = os.path.join(os.path.dirname(tokenizer_file), "tokenizer_config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                cfg = json.load(f)
            self.chat_template = cfg.get("chat_template")
            for key in ("eos_token", "bos_token"):
                tok = cfg.get(key)
                if isinstance(tok, dict):
                    tok = tok.get("content")
                setattr(self, key.replace("_token", "_token"), tok)
                if key == "eos_token" and tok:
                    tid = self._tok.token_to_id(tok)
                    if tid is not None:
                        self._eos_ids.append(tid)

    def encode(self, text: str) -> List[int]:
        return self._tok.encode(text, add_special_tokens=False).ids

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(list(ids), skip_special_tokens=True)

    @property
    def eos_token_ids(self) -> List[int]:
        return self._eos_ids

    @property
    def vocab_size(self) -> int:
        return self._tok.get_vocab_size()


class DecodeStream:
    """Incremental detokenizer: feed token ids, get text deltas
    (ref: tokenizers.rs DecodeStream)."""

    def __init__(self, tokenizer: Tokenizer, skip_token_ids: Optional[Sequence[int]] = None):
        self.tokenizer = tokenizer
        self.ids: List[int] = []
        self._emitted = 0  # chars already emitted
        self._skip = set(skip_token_ids or [])

    def step(self, token_ids: Sequence[int]) -> str:
        self.ids.extend(t for t in token_ids if t not in self._skip)
        text = self.tokenizer.decode(self.ids)
        # Hold back while the tail is an incomplete sequence.
        while text.endswith("�") and len(text) > self._emitted:
            text = text[:-1]
        delta = text[self._emitted :]
        self._emitted += len(delta)
        return delta

    def flush(self) -> str:
        text = self.tokenizer.decode(self.ids)
        delta = text[self._emitted :]
        self._emitted = len(text)
        return delta


def load_tokenizer(path_or_name: Optional[str]) -> Tokenizer:
    """Local tokenizer.json dir/file → HFTokenizer; ``bytes:<n>`` → the byte
    tokenizer with ``n`` special ids below the bytes; otherwise ByteTokenizer."""
    if path_or_name and path_or_name.startswith("bytes:"):
        return ByteTokenizer(offset=int(path_or_name.split(":", 1)[1]))
    if path_or_name:
        candidate = path_or_name if path_or_name.endswith(".json") else os.path.join(path_or_name, "tokenizer.json")
        if os.path.exists(candidate):
            return HFTokenizer(path_or_name)
    return ByteTokenizer()
