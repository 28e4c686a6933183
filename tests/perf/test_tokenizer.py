"""The benchmark's tokenizer: unique, non-empty, reversible for every id,
loaded by the program's own loader, and the silent byte fallback detected."""

import pytest

from perf import tokenizer
from production_stack_tpu.engine.tokenizer import ByteTokenizer, get_tokenizer
from production_stack_tpu.engine.server import _fmt_completion_logprobs

VOCAB = 1000


@pytest.fixture(scope="module")
def tok(tmp_path_factory):
    path = tokenizer.write_tokenizer_dir(
        str(tmp_path_factory.mktemp("tok")), VOCAB)
    t = get_tokenizer(path, VOCAB)
    assert type(t).__name__ == "HFTokenizer" and t.vocab_size == VOCAB
    return t


def test_every_id_decodes_to_a_unique_word_and_back(tok):
    words = [tok.decode([i]) for i in range(VOCAB)]
    assert all(words) and len(set(words)) == VOCAB
    assert [tokenizer.ids_of(w) for w in words] == [[i] for i in range(VOCAB)]


def test_a_streamed_delta_counts_its_tokens(tok):
    ids = [999, 0, 257, 31]
    assert tokenizer.ids_of(tok.decode(ids)) == ids
    # the engine's incremental detokeniser emits the suffix of a longer decode
    delta = tok.decode(ids)[len(tok.decode(ids[:2])):]
    assert tokenizer.count(delta) == 2


def _completion(tok_, top):
    entries = [{"token_id": top[0][0], "logprob": top[0][1], "top": top}]
    return {"choices": [{"logprobs": _fmt_completion_logprobs(tok_, entries)}]}


def test_proof_accepts_the_benchmarks_tokenizer(tok):
    top = [(700, -1.0), (3, -2.0), (999, -2.5), (256, -3.0), (12, -4.0)]
    assert tokenizer.prove_in_use(_completion(tok, top), VOCAB) == [t for t, _ in top]


def test_proof_detects_the_byte_fallback():
    top = [(700, -1.0), (300, -2.0), (999, -2.5), (256, -3.0), (512, -4.0)]
    with pytest.raises(tokenizer.TokenizerNotInUse):
        tokenizer.prove_in_use(_completion(ByteTokenizer(VOCAB), top), VOCAB)


def test_a_failed_load_falls_back_silently_and_is_detected(tmp_path):
    fallback = get_tokenizer(str(tmp_path / "no-such-dir"), VOCAB)
    assert isinstance(fallback, ByteTokenizer)  # only a warning in the log
    top = [(70, -1.0), (71, -2.0), (72, -2.5), (73, -3.0), (74, -4.0)]
    with pytest.raises(tokenizer.TokenizerNotInUse):
        tokenizer.prove_in_use(_completion(fallback, top), VOCAB)
