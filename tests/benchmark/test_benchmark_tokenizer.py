"""The synthetic tokenizer: every id is visible text and nothing is withheld."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import tokenizer as toktext  # noqa: E402
from dynamo_tpu.llm.tokenizer import DecodeStream, HFTokenizer  # noqa: E402

VOCAB = 2048


@pytest.fixture(scope="module", params=["hf", "own"])
def tok(request, tmp_path_factory):
    if request.param == "own":
        return toktext.WordTokenizer(VOCAB)
    pytest.importorskip("tokenizers")
    t = toktext.load(VOCAB, str(tmp_path_factory.mktemp("tok")))
    assert isinstance(t, HFTokenizer)  # through the program's own loader
    return t


def test_every_id_round_trips_to_distinct_non_empty_printable_text(tok):
    assert tok.vocab_size == VOCAB
    words = [tok.decode([i]) for i in range(VOCAB)]
    assert all(w and w.isprintable() and not any(c.isspace() for c in w) and w.isascii() for w in words)
    assert len(set(words)) == VOCAB
    assert [tok.encode(w) for w in words] == [[i] for i in range(VOCAB)]


def test_decode_stream_withholds_nothing(tok):
    ids = [(i * 37) % VOCAB for i in range(200)]
    stream = DecodeStream(tok)
    seen = 0
    for start in range(0, len(ids), 7):
        chunk = ids[start:start + 7]
        delta = stream.step(chunk)
        assert len(delta.split()) == len(chunk)  # a frame's tokens are countable
        seen += len(delta.split())
    assert seen == len(ids) and stream.flush() == ""


def test_the_default_chat_template_costs_two_tokens(tok):
    body = " ".join(toktext.word(i) for i in range(10, 30))
    assert len(tok.encode(f"<|user|>\n{body}\n<|assistant|>\n")) == 20 + toktext.CHAT_OVERHEAD_TOKENS
