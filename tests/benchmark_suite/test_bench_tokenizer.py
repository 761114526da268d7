"""The generated merges file: one generated token is one visible event."""

import pytest

from benchmark import tokenizer_gen
from ray_tpu.llm._internal.openai import _IncrementalDecoder
from ray_tpu.llm._internal.tokenizer import ByteBPETokenizer

VOCAB = 32768


@pytest.fixture(scope="module")
def tok(tmp_path_factory):
    path = tokenizer_gen.write(
        str(tmp_path_factory.mktemp("tok") / "t.json"), VOCAB)
    return ByteBPETokenizer.load(path)


def test_vocabulary_size_and_stop_id(tok):
    assert tok.vocab_size == VOCAB
    assert tok.eot_id == VOCAB - 2
    assert [i for i in range(256, VOCAB)
            if tokenizer_gen.is_silent(i, VOCAB)] == list(
        range(VOCAB - 6, VOCAB))


def test_every_id_decodes_to_nonempty_ascii(tok):
    """All but the ids the tokenizer CLASS keeps silent (bytes 128-255
    alone are partial UTF-8, specials decode to nothing): 32,634 of 32,768."""
    silent = 0
    for i in range(VOCAB):
        text = tok.decode([i])
        if tokenizer_gen.is_silent(i, VOCAB):
            silent += 1
            assert text in ("", "�")
        else:
            assert text and text.isascii(), (i, text)
    assert silent == 128 + 6


def test_merged_ids_round_trip(tok):
    ids = [256, 300, 931, 5000, 20000, VOCAB - 7]
    words = [tok.decode([i]) for i in ids]
    assert len(set(words)) == len(ids)
    assert all(2 <= len(w) <= 4 and w.islower() for w in words)
    # Text survives encode -> decode; a two-letter token is its own
    # encoding (longer ones may split elsewhere: merges apply by rank).
    for i, w in zip(ids, words):
        assert tok.decode(tok.encode(w)) == w
        if len(w) == 2:
            assert tok.encode(w) == [i]


def test_one_token_is_one_delta_in_the_servers_decoder(tok):
    """What `_stream_deltas` does with each token: a non-empty delta per
    visible id; a lone high byte is held back and rides on the next."""
    dec = _IncrementalDecoder(tok)
    assert [bool(dec.push(i)) for i in (256, 40, 7000, 65)] == [True] * 4
    assert dec.push(200) == ""
    assert dec.push(300) == "�" + tok.decode([300])


def test_file_is_the_same_every_time(tmp_path):
    a = tokenizer_gen.write(str(tmp_path / "a.json"), 1000)
    b = tokenizer_gen.write(str(tmp_path / "b.json"), 1000)
    assert open(a).read() == open(b).read()
    assert len(tokenizer_gen.merges_for(1000)) == 1000 - 262
    with pytest.raises(ValueError):
        tokenizer_gen.merges_for(100)
