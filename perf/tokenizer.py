"""The benchmark's own tokenizer: one word per id, reversible.

A preset has no tokenizer directory, and the program then falls back to a
byte tokenizer whose ``decode`` drops every id above 256: generated tokens
come back as empty strings, ``top_logprobs`` (keyed by decoded text)
collapses to one key, and a client cannot count the tokens of a streamed
event. The API returns no token ids. So the harness writes a local HF
tokenizer directory (WordLevel, one entry per id of the configuration's
vocabulary, each decoding to the unique word ``t<id>``, none special),
hands it to the engine with ``--tokenizer``, and proves before any traffic
that it is the one in use.
"""

from __future__ import annotations

import json
import os
import re

_WORD = re.compile(r"t(\d+)")


class TokenizerNotInUse(RuntimeError):
    """The engine did not decode with the benchmark's tokenizer."""


def word(token_id: int) -> str:
    return f"t{token_id}"


def write_tokenizer_dir(path: str, vocab_size: int) -> str:
    """Write ``tokenizer.json`` + ``tokenizer_config.json`` for
    ``transformers.AutoTokenizer.from_pretrained(path)``."""
    os.makedirs(path, exist_ok=True)
    spec = {
        "version": "1.0",
        "truncation": None,
        "padding": None,
        "added_tokens": [],
        "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None,
        "decoder": None,
        "model": {
            "type": "WordLevel",
            "vocab": {word(i): i for i in range(vocab_size)},
            "unk_token": word(0),
        },
    }
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "clean_up_tokenization_spaces": False,
            "model_max_length": 1 << 30,
        }, f)
    return path


def ids_of(text: str) -> list:
    """Token ids of a piece of decoded text (an SSE event's ``text``, a
    ``tokens`` entry, a ``top_logprobs`` key)."""
    return [int(m) for m in _WORD.findall(text or "")]


def count(text: str) -> int:
    return len(_WORD.findall(text or ""))


def prove_in_use(completion: dict, vocab_size: int, top_n: int = 5) -> list:
    """``completion`` is the answer to a ``max_tokens=1, logprobs=top_n``
    request. Returns the ``top_n`` ids or raises :class:`TokenizerNotInUse`
    (the byte fallback gives empty or colliding keys)."""
    try:
        lp = completion["choices"][0]["logprobs"]
        keys = list(lp["top_logprobs"][0].keys())
        tokens = lp["tokens"]
    except (KeyError, IndexError, TypeError) as e:
        raise TokenizerNotInUse(f"no logprobs in {completion!r}") from e
    ids = []
    for k in keys:
        m = _WORD.fullmatch(k.strip())
        if not m or int(m.group(1)) >= vocab_size:
            raise TokenizerNotInUse(
                f"top_logprobs key {k!r} is not a word of the benchmark's "
                f"tokenizer (keys: {keys!r}): the engine fell back to "
                "another tokenizer")
        ids.append(int(m.group(1)))
    if len(set(ids)) != top_n:
        raise TokenizerNotInUse(
            f"asked for {top_n} top logprobs, got {len(set(ids))} distinct "
            f"ids from keys {keys!r}")
    if len(tokens) != 1 or count(tokens[0]) != 1:
        raise TokenizerNotInUse(f"generated token decodes to {tokens!r}")
    return ids
