"""The benchmark's tokenizer: ids round-trip at a model's full vocabulary."""
import pytest

from bench.tokenizer import MAX_VOCAB, IdTokenizer


@pytest.mark.parametrize("vocab", [32064, 64000])
def test_round_trip_full_vocab(vocab):
    tok = IdTokenizer(vocab)
    ids = list(range(vocab))
    text = tok.decode(ids)
    assert len(text) == vocab
    assert tok.encode(text) == ids
    assert b"".join(tok.token_bytes(i) for i in ids).decode() == text


def test_chat_template_concatenates_turns():
    tok = IdTokenizer(100)
    turns = [[5, 6], [7], [8, 9, 10]]
    msgs = [{"role": r, "content": tok.decode(t)}
            for r, t in zip(("system", "user", "assistant"), turns)]
    assert tok.encode(tok.apply_chat_template(msgs)) == [5, 6, 7, 8, 9, 10]


def test_rejects_text_that_is_not_ids():
    tok = IdTokenizer(100)
    with pytest.raises(ValueError):
        tok.encode("hello")
    with pytest.raises(ValueError):
        tok.encode(tok.decode([100]))        # past the vocabulary
    with pytest.raises(ValueError):
        IdTokenizer(MAX_VOCAB + 1)


def test_no_token_ends_a_request():
    tok = IdTokenizer(64000)
    assert tok.eos_id not in range(tok.vocab_size)
    assert tok.n_special == 0
