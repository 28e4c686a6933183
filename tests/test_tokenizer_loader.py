"""The loader of a ``tokenizer.json`` directory (``tokenizers`` alone) is held
to ``transformers.AutoTokenizer`` on directories built here, and a directory's
files choose the loader.

One module, so that ``--dist loadfile`` pays the ``transformers`` import once.
The reference is :class:`TransformersTokenizer`, the path a directory without
a ``tokenizer.json`` still takes: ``AutoTokenizer`` behind the same protocol.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest
from tokenizers import Tokenizer, decoders, models, normalizers, pre_tokenizers
from tokenizers import processors, trainers

from perf import tokenizer as bench_tokenizer
from production_stack_tpu.engine.tokenizer import (
    HFTokenizer,
    TransformersTokenizer,
    _fallback_chat_template,
    get_tokenizer,
)
from production_stack_tpu.protocols import ChatMessage

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello , world ! do n't you know it 's what we 've said ?",
    "a tokenizer reads its file , and the engine starts .",
    "def load(path): return open(path).read()",
] * 4

CHATML = (
    "{{ bos_token }}{% for m in messages %}<|im_start|>{{ m['role'] }}\n"
    "{{ m['content'] }}{{ eos_token }}\n{% endfor %}"
    "{% if add_generation_prompt %}<|im_start|>assistant\n{% endif %}"
)
# everything a template may ask of the environment: raise_exception,
# strftime_now, tojson, a generation block, loop controls, trimmed blocks
RICH = """\
{% if messages[0]['role'] == 'tool' %}
    {{ raise_exception('a conversation cannot start with a tool') }}
{% endif %}
{{ bos_token }}[year {{ strftime_now('%Y') | length }}] {{ {'n': messages | length, 'h': '<b>'} | tojson }}
{% for m in messages %}
    {% if m['role'] == 'system' %}{% continue %}{% endif %}
    {% if m['role'] == 'assistant' %}
<|im_start|>assistant:{% generation %} {{ m['content'] | trim }} {% endgeneration %}{{ eos_token }}
    {% else %}
<|im_start|>{{ m['role'] }}: {{ m['content'] }}{{ additional_special_tokens[0] }}
    {% endif %}
{% endfor %}
{% if add_generation_prompt %}<|im_start|>assistant:{% endif %}"""


def _write(path, name, obj):
    with open(os.path.join(path, name), "w", encoding="utf-8") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def _bpe(specials):
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(CORPUS, trainers.BpeTrainer(
        vocab_size=400, special_tokens=specials, show_progress=False,
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet()))
    bos = specials[0]
    tok.post_processor = processors.TemplateProcessing(
        single=f"{bos} $A", pair=f"{bos} $A {bos} $B:1",
        special_tokens=[(bos, tok.token_to_id(bos))])
    return tok


def _added(content, **kw):
    return {"__type": "AddedToken", "content": content, "lstrip": False,
            "normalized": False, "rstrip": False, "single_word": False, **kw}


def build_wordlevel(path):
    bench_tokenizer.write_tokenizer_dir(path, 1000)


def build_bpe_eos_string(path):
    """Byte-level BPE, ``eos_token`` a string, the template a string,
    clean-up on, a truncation and a padding in the file that no call asked
    for."""
    tok = _bpe(["<|bos|>", "<|eot|>", "<|im_start|>"])
    tok.enable_truncation(6)
    tok.enable_padding(length=12, pad_token="<|eot|>", pad_id=tok.token_to_id("<|eot|>"))
    tok.save(os.path.join(path, "tokenizer.json"))
    _write(path, "tokenizer_config.json", {
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<|bos|>", "eos_token": "<|eot|>",
        "clean_up_tokenization_spaces": True, "chat_template": CHATML,
    })


def build_bpe_eos_object(path):
    """``eos_token`` an added-token object that ``tokenizer.json`` lacks (the
    vocabulary grows at load), ``unk_token`` a word of the base vocabulary
    (it turns special at load), ``added_tokens_decoder`` with one more token
    the file lacks, named templates, clean-up off, truncation on the left."""
    _bpe(["<|bos|>", "<|im_start|>"]).save(os.path.join(path, "tokenizer.json"))
    _write(path, "tokenizer_config.json", {
        "tokenizer_class": "PreTrainedTokenizerFast",
        "added_tokens_decoder": {
            "0": _added("<|bos|>", special=True),
            "1": _added("<|im_start|>", special=True),
            "400": _added("<|tool|>", special=False),
        },
        "bos_token": "<|bos|>", "eos_token": _added("<|end|>", special=True),
        "unk_token": "the", "additional_special_tokens": ["<|im_start|>"],
        "clean_up_tokenization_spaces": False, "truncation_side": "left",
        "chat_template": [{"name": "default", "template": CHATML},
                          {"name": "tool_use", "template": "never taken"}],
    })


def build_bpe_legacy(path):
    """The older layout: no ``added_tokens_decoder``, the special tokens in
    ``special_tokens_map.json`` (an object without ``__type``, and winning
    over the configuration), the template in ``chat_template.jinja`` and
    winning over the configuration's, clean-up absent."""
    _bpe(["<|bos|>", "<|eot|>", "<|im_start|>"]).save(os.path.join(path, "tokenizer.json"))
    _write(path, "tokenizer_config.json", {
        "tokenizer_class": "PreTrainedTokenizerFast", "eos_token": "<|bos|>",
        "additional_special_tokens": ["<|im_start|>"], "chat_template": "not this one",
    })
    _write(path, "special_tokens_map.json", {
        "bos_token": "<|bos|>",
        "eos_token": {"content": "<|eot|>", "lstrip": False, "normalized": False,
                      "rstrip": False, "single_word": False},
        "additional_special_tokens": ["<|sep|>", "<|im_start|>"],
    })
    _write(path, "chat_template.jinja", RICH)


def build_wordpiece(path):
    """BERT's layout: WordPiece, ``[CLS] a [SEP] b [SEP]`` with segment ids,
    loaded by ``BertTokenizerFast``; no template, no ``eos_token``."""
    words = sorted({w for line in CORPUS for w in line.lower().split()})
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + words + ["##s", "##ing", "##ed"]
    tok = Tokenizer(models.WordPiece({w: i for i, w in enumerate(vocab)}, unk_token="[UNK]"))
    tok.normalizer = normalizers.BertNormalizer(lowercase=True)
    tok.pre_tokenizer = pre_tokenizers.BertPreTokenizer()
    tok.decoder = decoders.WordPiece(prefix="##", cleanup=True)
    tok.post_processor = processors.TemplateProcessing(
        single="[CLS] $A [SEP]", pair="[CLS] $A [SEP] $B:1 [SEP]:1",
        special_tokens=[("[CLS]", 2), ("[SEP]", 3)])
    tok.add_special_tokens(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"])
    tok.save(os.path.join(path, "tokenizer.json"))
    _write(path, "tokenizer_config.json", {
        "tokenizer_class": "BertTokenizerFast", "do_lower_case": True,
        "unk_token": "[UNK]", "sep_token": "[SEP]", "pad_token": "[PAD]",
        "cls_token": "[CLS]", "mask_token": "[MASK]",
        "clean_up_tokenization_spaces": True,
    })


BUILDERS = {
    "wordlevel": build_wordlevel,
    "bpe_eos_string": build_bpe_eos_string,
    "bpe_eos_object": build_bpe_eos_object,
    "bpe_legacy": build_bpe_legacy,
    "wordpiece": build_wordpiece,
}
TEMPLATED = ("bpe_eos_string", "bpe_eos_object", "bpe_legacy")
TEXTS = (
    "", "the quick brown fox", "hello , world ! do n't you know it 's what we 've said ?",
    "t5 t77 t999 t1000 zebra", "<|bos|>the fox<|eot|> and<|end|> <|im_start|>user<|tool|><|sep|>",
    "[CLS] the fox [SEP] jumps [MASK] unknownword", "  two  spaces\nand a line ",
)
PAIRS = (
    ("the quick brown fox", "jumps over the lazy dog"),
    ("a", "the engine starts , and the engine starts , and the engine starts ."),
    ("hello , world ! hello , world ! hello , world !", "t1 t2"),
    ("", ""),
)


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """name -> (path, the new loader's tokenizer, transformers' tokenizer)"""
    out = {}
    for name, build in BUILDERS.items():
        path = str(tmp_path_factory.mktemp(name))
        build(path)
        new = get_tokenizer(path)
        assert isinstance(new, HFTokenizer) and new.loader == "tokenizers"
        out[name] = (path, new, TransformersTokenizer(path))
    return out


def _messages(*turns):
    return [ChatMessage(role=r, content=c) for r, c in turns]


@pytest.mark.parametrize("name", BUILDERS)
def test_vocabulary_and_eos(loaded, name):
    _, new, ref = loaded[name]
    assert new.vocab_size == ref.vocab_size == len(ref._tok)
    assert new.eos_token_ids == ref.eos_token_ids


def test_what_the_directories_are_meant_to_exercise(loaded):
    """The cases above compare two loaders; this says they compared
    something: a grown vocabulary, an eos of each kind, none at all."""
    sizes = {n: t.vocab_size for n, (_, t, _) in loaded.items()}
    assert sizes["wordlevel"] == 1000 and loaded["wordlevel"][1].eos_token_ids == ()
    path = loaded["bpe_eos_object"][0]
    base = Tokenizer.from_file(os.path.join(path, "tokenizer.json")).get_vocab_size()
    assert sizes["bpe_eos_object"] == base + 2  # <|tool|> and <|end|> added at load
    assert loaded["bpe_eos_object"][1].eos_token_ids == (base + 1,)
    assert loaded["bpe_eos_string"][1].eos_token_ids == (1,)
    assert loaded["bpe_legacy"][1].eos_token_ids == (1,)  # the map's, not the config's
    assert loaded["wordpiece"][1].eos_token_ids == ()


@pytest.mark.parametrize("special", [True, False], ids=["special", "plain"])
@pytest.mark.parametrize("name", BUILDERS)
def test_encode(loaded, name, special):
    _, new, ref = loaded[name]
    for text in TEXTS:
        assert new.encode(text, add_special_tokens=special) == ref.encode(
            text, add_special_tokens=special), text


@pytest.mark.parametrize("name", BUILDERS)
def test_decode_skips_special_tokens_and_cleans_up_as_configured(loaded, name):
    _, new, ref = loaded[name]
    for text in TEXTS:
        ids = ref.encode(text)
        assert new.decode(ids) == ref.decode(ids), text
        assert new.decode(tuple(ids[1:])) == ref.decode(ids[1:])
    every = list(range(min(new.vocab_size, 420)))
    assert new.decode(every) == ref.decode(every)
    assert [new.decode([i]) for i in every] == [ref.decode([i]) for i in every]


@pytest.mark.parametrize("setting", [True, False, None], ids=["on", "off", "absent"])
def test_clean_up_tokenization_spaces(tmp_path, setting):
    build_bpe_eos_string(str(tmp_path))
    cfg_path = tmp_path / "tokenizer_config.json"
    cfg = json.loads(cfg_path.read_text())
    del cfg["clean_up_tokenization_spaces"]
    if setting is not None:
        cfg["clean_up_tokenization_spaces"] = setting
    cfg_path.write_text(json.dumps(cfg))
    new, ref = get_tokenizer(str(tmp_path)), TransformersTokenizer(str(tmp_path))
    text = "hello , world ! do n't you know it 's what we 've said ? ' a ' you 're i 'm ."
    ids = ref.encode(text)
    assert new.decode(ids) == ref.decode(ids)
    assert (new.decode(ids) == text) == (not setting)


@pytest.mark.parametrize("name", BUILDERS)
def test_encode_pair(loaded, name):
    _, new, ref = loaded[name]
    for a, b in PAIRS:
        assert new.encode_pair(a, b) == ref.encode_pair(a, b)
        full = len(ref.encode_pair(a, b)[0])
        for max_len in (64, 12, 9, 8, 5):
            got = new.encode_pair(a, b, max_len=max_len)
            assert got == ref.encode_pair(a, b, max_len=max_len), (a, b, max_len)
            assert len(got[0]) == min(full, max_len)
            # a truncated call leaves nothing behind it
            assert new.encode_pair(a, b) == ref.encode_pair(a, b)
            assert new.encode(a + " " + b) == ref.encode(a + " " + b)


def test_the_pair_template_and_the_segment_ids_are_the_files(loaded):
    _, new, _ = loaded["wordpiece"]
    ids, types = new.encode_pair("the fox", "the dog")
    assert (ids[0], ids[3], ids[-1]) == (2, 3, 3)  # [CLS] a a [SEP] b b [SEP]
    assert types == [0, 0, 0, 0, 1, 1, 1]
    ids, types = new.encode_pair("the quick brown fox", "jumps over the lazy dog", max_len=7)
    assert (ids[0], ids[-1], len(ids)) == (2, 3, 7) and types[-1] == 1


def test_truncation_does_not_leak_between_threads(loaded):
    """Truncation is state on a ``tokenizers.Tokenizer``; calls of several
    limits and of none, from more threads than cores, each get their own."""
    _, new, ref = loaded["wordpiece"]
    a, b = PAIRS[0]
    limits = [None, 5, 7, 9, None, 6, 8, 64] * 4
    want = {m: ref.encode_pair(a, b, max_len=m) for m in set(limits)}
    plain = ref.encode(a)
    wrong, old = [], sys.getswitchinterval()

    def work(limit):
        for _ in range(150):
            if new.encode_pair(a, b, max_len=limit) != want[limit] or new.encode(a) != plain:
                wrong.append(limit)

    threads = [threading.Thread(target=work, args=(m,)) for m in limits]
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


CHATS = {
    "one_turn": _messages(("user", "hello there")),
    "system_and_turns": _messages(
        ("system", "be brief"), ("user", "hi"), ("assistant", " hello "), ("user", "and now ?")),
    "a_skipped_role": _messages(("user", "hi"), ("system", "not shown"), ("user", "again")),
}


@pytest.mark.parametrize("chat", CHATS)
@pytest.mark.parametrize("name", TEMPLATED)
def test_chat_template_with_a_generation_prompt(loaded, name, chat):
    _, new, ref = loaded[name]
    for prompt in (True, False):
        got = new.apply_chat_template(CHATS[chat], add_generation_prompt=prompt)
        assert got == ref.apply_chat_template(CHATS[chat], add_generation_prompt=prompt)
        assert "<|im_start|>" in got and got.endswith("assistant" + got[-1]) == prompt
        assert got != _fallback_chat_template(CHATS[chat], prompt)


@pytest.mark.parametrize("final", [
    "the answer is", "trailing space ", "  both sides  ", "twice twice", "line\n"])
@pytest.mark.parametrize("name", TEMPLATED)
def test_chat_template_continues_the_final_message(loaded, name, final):
    _, new, ref = loaded[name]
    chat = _messages(("user", "question"), ("assistant", final))
    got = new.apply_chat_template(
        chat, add_generation_prompt=False, continue_final_message=True)
    assert got == ref.apply_chat_template(
        chat, add_generation_prompt=False, continue_final_message=True)
    # the turn is open: the text ends inside the final message
    assert "<|im_start|>" in got and got.rstrip().endswith(final.strip())
    assert "<|eot|>" not in got[got.rindex(final.strip()):]
    assert "<|end|>" not in got[got.rindex(final.strip()):]


@pytest.mark.parametrize("name", TEMPLATED)
def test_continuing_with_a_generation_prompt_falls_back_as_before(loaded, name):
    _, new, ref = loaded[name]
    chat = _messages(("user", "q"), ("assistant", "so far"))
    got = new.apply_chat_template(chat, add_generation_prompt=True, continue_final_message=True)
    assert got == ref.apply_chat_template(
        chat, add_generation_prompt=True, continue_final_message=True)
    assert got == _fallback_chat_template(chat, True, True)


def test_a_template_that_raises_gives_the_fallback_template(loaded):
    _, new, ref = loaded["bpe_legacy"]
    chat = _messages(("tool", "42"), ("user", "what was that ?"))
    for prompt in (True, False):
        got = new.apply_chat_template(chat, add_generation_prompt=prompt)
        assert got == ref.apply_chat_template(chat, add_generation_prompt=prompt)
        assert got == _fallback_chat_template(chat, prompt)


@pytest.mark.parametrize("source", ["{% for m in messages %}", "{{ messages | nofilter }}"],
                         ids=["unclosed", "unknown_filter"])
def test_a_template_that_does_not_compile_gives_the_fallback_template(tmp_path, source):
    build_bpe_eos_string(str(tmp_path))
    cfg = json.loads((tmp_path / "tokenizer_config.json").read_text())
    (tmp_path / "tokenizer_config.json").write_text(json.dumps({**cfg, "chat_template": source}))
    new, ref = get_tokenizer(str(tmp_path)), TransformersTokenizer(str(tmp_path))
    assert new.loader == "tokenizers"
    chat = CHATS["one_turn"]
    assert new.apply_chat_template(chat) == ref.apply_chat_template(chat)
    assert new.apply_chat_template(chat) == _fallback_chat_template(chat, True)


@pytest.mark.parametrize("name", ["wordlevel", "wordpiece"])
def test_no_template_gives_the_fallback_template(loaded, name):
    _, new, ref = loaded[name]
    chat = CHATS["system_and_turns"]
    for prompt, cont in ((True, False), (False, False), (False, True)):
        got = new.apply_chat_template(
            chat, add_generation_prompt=prompt, continue_final_message=cont)
        assert got == ref.apply_chat_template(
            chat, add_generation_prompt=prompt, continue_final_message=cont)
        assert got == _fallback_chat_template(chat, prompt, cont)


def test_the_sandbox_refuses_what_transformers_refuses(tmp_path):
    build_bpe_eos_string(str(tmp_path))
    cfg = json.loads((tmp_path / "tokenizer_config.json").read_text())
    cfg["chat_template"] = "{{ messages.append(1) }}{{ ''.__class__.__mro__ }}"
    (tmp_path / "tokenizer_config.json").write_text(json.dumps(cfg))
    chat = CHATS["one_turn"]
    assert get_tokenizer(str(tmp_path)).apply_chat_template(chat) == _fallback_chat_template(
        chat, True)


def _gpt2_dir_without_tokenizer_json(path):
    tok = _bpe(["<|endoftext|>"])
    tok.model.save(path)  # vocab.json + merges.txt
    _write(path, "tokenizer_config.json", {
        "tokenizer_class": "GPT2Tokenizer", "eos_token": "<|endoftext|>",
        "bos_token": "<|endoftext|>", "unk_token": "<|endoftext|>"})


def test_the_files_choose_the_loader_and_an_engine_start_imports_no_transformers(tmp_path):
    """The guard on the 19 s: an engine started on a ``tokenizer.json``
    directory, and its tokenizer used, with neither ``transformers`` nor
    ``torch`` in the process, and the loader named in the log and in what
    ``GET /version`` returns; a directory without one still goes through
    ``transformers``."""
    with_json, without = str(tmp_path / "with"), str(tmp_path / "without")
    os.makedirs(with_json)
    os.makedirs(without)
    build_bpe_eos_string(with_json)
    _gpt2_dir_without_tokenizer_json(without)
    script = textwrap.dedent("""
        import json, sys
        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.engine import LLMEngine
        from production_stack_tpu.engine.tokenizer import get_tokenizer
        from production_stack_tpu.protocols import ChatMessage
        engine = LLMEngine(EngineConfig(model="tiny-llama-debug", tokenizer=sys.argv[1]))
        t = engine.tokenizer
        ids = t.encode("the quick brown fox")
        t.decode(ids); t.encode_pair("a", "b", max_len=4)
        t.apply_chat_template([ChatMessage(role="user", content="hi")])
        out = {"loader": engine.runner.device_info["tokenizer_loader"],
               "cls": type(t).__name__, "ids": ids,
               "heavy": sorted(m for m in ("transformers", "torch") if m in sys.modules)}
        u = get_tokenizer(sys.argv[2])
        out.update(loader2=u.loader, cls2=type(u).__name__, eos2=list(u.eos_token_ids),
                   text2=u.decode(u.encode("the quick brown fox")),
                   transformers2="transformers" in sys.modules)
        print(json.dumps(out))
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c", script, with_json, without], cwd=repo, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["heavy"] == [], "an engine start imported " + str(out["heavy"])
    assert (out["loader"], out["cls"]) == ("tokenizers", "HFTokenizer")
    assert f"tokenizer: loader tokenizers, path {with_json}" in p.stdout + p.stderr
    assert out["ids"] == get_tokenizer(with_json).encode("the quick brown fox")
    assert (out["loader2"], out["cls2"]) == ("transformers", "TransformersTokenizer")
    assert out["transformers2"] and out["eos2"] == [0]
    assert out["text2"] == "the quick brown fox"


def test_a_load_that_fails_still_falls_back_to_bytes(tmp_path):
    (tmp_path / "tokenizer.json").write_text("{not json")
    assert get_tokenizer(str(tmp_path), 300).loader == "byte"
    assert get_tokenizer(None).loader == get_tokenizer("byte").loader == "byte"
