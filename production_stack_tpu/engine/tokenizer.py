"""Tokenizer abstraction: local HF tokenizers + a byte-level fallback.

The environment is zero-egress, so tokenizers load only from local
directories; tests, benchmarks, and the fake fleet use :class:`ByteTokenizer`
(utf-8 bytes as ids — reversible, vocab-compatible with the tiny debug
models). Mirrors the tokenize/chat-template duties vLLM's OpenAI server
performs behind the reference stack (`/tokenize`, chat templating).

One protocol, two loaders behind it, chosen by the files of the directory:
one that holds a ``tokenizer.json`` is read with the ``tokenizers`` package
(:class:`HFTokenizer`); one without (a SentencePiece ``tokenizer.model``, a
``vocab.json`` + ``merges.txt`` pair) goes through ``transformers``
(:class:`TransformersTokenizer`), whose import takes 19 s of an engine
start and brings ``torch`` with it.
"""

from __future__ import annotations

import copy
import json
import os
import threading
from datetime import datetime
from typing import Dict, List, Optional, Protocol, Sequence, Tuple

from ..logging_utils import init_logger
from ..protocols import ChatMessage

logger = init_logger(__name__)


class Tokenizer(Protocol):
    vocab_size: int
    eos_token_ids: Tuple[int, ...]
    loader: str  # "tokenizers", "transformers" or "byte"

    def encode(self, text: str, add_special_tokens: bool = True) -> List[int]: ...

    def decode(self, ids: Sequence[int]) -> str: ...

    def apply_chat_template(
        self,
        messages: List[ChatMessage],
        add_generation_prompt: bool = True,
        continue_final_message: bool = False,
    ) -> str: ...


def _fallback_chat_template(
    messages: List[ChatMessage],
    add_generation_prompt: bool,
    continue_final_message: bool = False,
) -> str:
    parts = [f"<|{m.role}|>\n{m.text()}\n" for m in messages]
    if continue_final_message:
        # Leave the final message's turn OPEN (no terminator, no new
        # generation prompt) so the model continues it mid-sentence —
        # the contract stream resumption relies on: the continuation is
        # the suffix of the final assistant message, not a fresh turn.
        if parts:
            parts[-1] = parts[-1][:-1]
        return "".join(parts)
    if add_generation_prompt:
        parts.append("<|assistant|>\n")
    return "".join(parts)


class ByteTokenizer:
    """utf-8 bytes as token ids 1..256; id 0 is EOS/pad."""

    loader = "byte"

    def __init__(self, vocab_size: int = 512):
        self.vocab_size = vocab_size
        self.eos_token_ids: Tuple[int, ...] = (0,)

    def encode(self, text: str, add_special_tokens: bool = True) -> List[int]:
        return [b + 1 for b in text.encode("utf-8")]

    def decode(self, ids: Sequence[int]) -> str:
        return bytes(i - 1 for i in ids if 1 <= i <= 256).decode(
            "utf-8", errors="replace"
        )

    def encode_pair(
        self, a: str, b: str, max_len: Optional[int] = None
    ) -> Tuple[List[int], List[int]]:
        # 258 = synthetic separator (outside the byte id range 1..256).
        # Segment ids: 0 for the first text (+sep), 1 for the second.
        # longest-first truncation keeps the pair template intact (ADVICE
        # r3: tail-slicing dropped the final separator on long documents).
        ia, ib = self.encode(a), self.encode(b)
        if max_len is not None:
            budget = max_len - 1  # separator
            while len(ia) + len(ib) > budget:
                if len(ia) >= len(ib):
                    ia.pop()
                else:
                    ib.pop()
        return ia + [258] + ib, [0] * (len(ia) + 1) + [1] * len(ib)

    def apply_chat_template(
        self,
        messages: List[ChatMessage],
        add_generation_prompt: bool = True,
        continue_final_message: bool = False,
    ) -> str:
        return _fallback_chat_template(
            messages, add_generation_prompt, continue_final_message
        )


# transformers' ``clean_up_tokenization``: English tokenisation artefacts
_CLEAN_UP = (
    (" .", "."), (" ?", "?"), (" !", "!"), (" ,", ","), (" ' ", "'"),
    (" n't", "n't"), (" 'm", "'m"), (" 's", "'s"), (" 've", "'ve"), (" 're", "'re"),
)

# What transformers appends to the final message and cuts the rendered
# text at, to leave that message's turn open.
_CONTINUE_TAG = "CONTINUE_FINAL_MESSAGE_TAG "

_SPECIAL_TOKEN_KEYS = (
    "bos_token", "eos_token", "unk_token", "sep_token", "pad_token",
    "cls_token", "mask_token",
)


def _read_json(path: str) -> dict:
    """One of a tokenizer directory's optional files; ``{}`` when absent."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def _compile_chat_template(source: str):
    """``source`` compiled as transformers compiles a chat template: the
    immutable sandbox, ``trim_blocks`` and ``lstrip_blocks``, loop controls,
    ``raise_exception``, ``strftime_now``, a ``tojson`` that escapes no HTML,
    and ``{% generation %}`` blocks passed through."""
    import jinja2
    from jinja2.ext import Extension, loopcontrols
    from jinja2.sandbox import ImmutableSandboxedEnvironment

    class Generation(Extension):
        tags = {"generation"}

        def parse(self, parser):
            lineno = next(parser.stream).lineno
            body = parser.parse_statements(["name:endgeneration"], drop_needle=True)
            call = self.call_method("_body")
            return jinja2.nodes.CallBlock(call, [], [], body).set_lineno(lineno)

        def _body(self, caller):
            return caller()

    def raise_exception(message):
        raise jinja2.exceptions.TemplateError(message)

    def tojson(x, ensure_ascii=False, indent=None, separators=None, sort_keys=False):
        return json.dumps(x, ensure_ascii=ensure_ascii, indent=indent,
                          separators=separators, sort_keys=sort_keys)

    env = ImmutableSandboxedEnvironment(
        trim_blocks=True, lstrip_blocks=True, extensions=[Generation, loopcontrols]
    )
    env.filters["tojson"] = tojson
    env.globals["raise_exception"] = raise_exception
    env.globals["strftime_now"] = lambda format: datetime.now().strftime(format)
    return env.from_string(source)


class HFTokenizer:
    """A directory's ``tokenizer.json`` through ``tokenizers.Tokenizer``:
    what ``transformers.AutoTokenizer`` makes of the same directory
    (tests/test_tokenizer_loader.py holds it to that) without the import.
    What is not read: a tokenizer class's own rewrite of the file's
    post-processor (``add_bos_token`` / ``add_eos_token``, warned about
    where they differ from the classes' defaults)."""

    loader = "tokenizers"

    def __init__(self, path: str):
        from tokenizers import Tokenizer as Backend

        self._tok = Backend.from_file(os.path.join(path, "tokenizer.json"))
        # transformers truncates and pads only where a call asks for it,
        # whatever the file says
        self._tok.no_truncation()
        self._tok.no_padding()
        cfg = _read_json(os.path.join(path, "tokenizer_config.json"))
        if "added_tokens_decoder" not in cfg:
            # the older layout, where the map's entries win
            legacy = _read_json(os.path.join(path, "special_tokens_map.json"))
            listed = list(cfg.get("additional_special_tokens") or [])
            listed += [
                t for t in legacy.get("additional_special_tokens") or [] if t not in listed
            ]
            cfg = {**cfg, **legacy, "additional_special_tokens": listed}
        if cfg.get("add_bos_token") is False or cfg.get("add_eos_token") is True:
            logger.warning(
                "%s: add_bos_token / add_eos_token are not read; the "
                "post-processor of tokenizer.json decides what encode adds", path
            )
        self._special = self._add_special_tokens(cfg)
        self.vocab_size = self._tok.get_vocab_size(with_added_tokens=True)
        eos = self._special.get("eos_token")
        eos_id = None if eos is None else self._tok.token_to_id(eos)
        self.eos_token_ids: Tuple[int, ...] = () if eos_id is None else (eos_id,)
        self._clean_up = bool(cfg.get("clean_up_tokenization_spaces", False))
        self._truncation_side = cfg.get("truncation_side", "right")
        # Truncation is state on a tokenizers.Tokenizer: a truncated pair
        # is encoded on a copy of its own, under a lock, so that no other
        # call or thread ever meets it.
        self._pair_tok = None
        self._pair_lock = threading.Lock()
        self._template = self._load_chat_template(path, cfg)

    def _add_special_tokens(self, cfg: dict) -> Dict[str, object]:
        """Adds to the backend what transformers adds at load (the
        configuration's ``added_tokens_decoder`` entries the file lacks,
        then every named special token that is not an added token yet,
        marked special so that ``decode`` skips it) and returns the
        special tokens by name, as strings, for the chat template."""
        from tokenizers import AddedToken

        def as_token(value):  # a string, or an added-token object
            if isinstance(value, dict):
                return AddedToken(**{k: v for k, v in value.items() if k != "__type"})
            return value

        named = {k: as_token(cfg[k]) for k in _SPECIAL_TOKEN_KEYS if cfg.get(k)}
        listed = [as_token(t) for t in cfg.get("additional_special_tokens") or []]
        special = {str(t) for t in listed} | {str(t) for t in named.values()}
        have = self._tok.get_added_tokens_decoder().values()
        have_repr = {repr(t) for t in have}
        known = {t.content for t in have}
        to_add = []
        for _, fields in sorted(
            (int(i), f) for i, f in (cfg.get("added_tokens_decoder") or {}).items()
        ):
            token = as_token(fields)
            if repr(token) not in have_repr:
                token.special = token.special or token.content in special
                to_add.append(token)
                known.add(token.content)
        for token in list(named.values()) + listed:
            if str(token) not in known:
                if isinstance(token, str):
                    token = AddedToken(token, special=True)
                else:
                    token.special = True
                to_add.append(token)
                known.add(token.content)
        if to_add:
            self._tok.add_tokens(to_add)
        out: Dict[str, object] = {k: str(t) for k, t in named.items()}
        if listed:
            out["additional_special_tokens"] = [str(t) for t in listed]
        return out

    @staticmethod
    def _load_chat_template(path: str, cfg: dict):
        """The compiled template of ``chat_template.jinja``, else of the
        configuration's ``chat_template`` (a string, or named templates of
        which ``default`` is taken); None where there is none or it does
        not compile."""
        source = cfg.get("chat_template")
        if isinstance(source, list):
            source = {t["name"]: t["template"] for t in source}
        if isinstance(source, dict):
            source = source.get("default")
        try:
            with open(os.path.join(path, "chat_template.jinja"), encoding="utf-8") as f:
                source = f.read()
        except FileNotFoundError:
            pass
        if not source:
            return None
        try:
            return _compile_chat_template(source)
        except Exception as e:  # noqa: BLE001 - jinja2 is imported in the call
            logger.warning(
                "chat template of %s does not compile (%s); using the fallback template",
                path, e,
            )
            return None

    def encode(self, text: str, add_special_tokens: bool = True) -> List[int]:
        return self._tok.encode(text, add_special_tokens=add_special_tokens).ids

    def decode(self, ids: Sequence[int]) -> str:
        text = self._tok.decode(list(ids), skip_special_tokens=True)
        if self._clean_up:
            for spaced, joined in _CLEAN_UP:
                text = text.replace(spaced, joined)
        return text

    def encode_pair(
        self, a: str, b: str, max_len: Optional[int] = None
    ) -> Tuple[List[int], List[int]]:
        """Sentence-pair encoding with the file's own pair template
        (RoBERTa: <s> a </s></s> b </s>; BERT: [CLS] a [SEP] b [SEP] with
        segment ids) — what cross-encoders were trained on. ``longest_first``
        truncation keeps the final special tokens (ADVICE r3: tail-slicing
        silently degraded long-document scores)."""
        if max_len is None:
            enc = self._tok.encode(a, b or None)
        else:
            with self._pair_lock:
                if self._pair_tok is None:
                    self._pair_tok = copy.deepcopy(self._tok)
                self._pair_tok.enable_truncation(
                    max_len, strategy="longest_first", direction=self._truncation_side
                )
                enc = self._pair_tok.encode(a, b or None)
        return enc.ids, enc.type_ids

    def apply_chat_template(
        self,
        messages: List[ChatMessage],
        add_generation_prompt: bool = True,
        continue_final_message: bool = False,
    ) -> str:
        if self._template is not None:
            try:
                return self._render(messages, add_generation_prompt, continue_final_message)
            except Exception as e:  # noqa: BLE001 - a template may raise anything
                logger.warning("chat template failed (%s); using the fallback template", e)
        return _fallback_chat_template(
            messages, add_generation_prompt, continue_final_message
        )

    def _render(self, messages, add_generation_prompt: bool, continue_final_message: bool) -> str:
        chat = [{"role": m.role, "content": m.text()} for m in messages]
        if continue_final_message:
            if add_generation_prompt:
                raise ValueError(
                    "continue_final_message and add_generation_prompt are not compatible"
                )
            final = chat[-1]["content"]
            chat[-1]["content"] = final + _CONTINUE_TAG
        text = self._template.render(
            messages=chat, tools=None, documents=None,
            add_generation_prompt=add_generation_prompt, **self._special,
        )
        if continue_final_message:
            # as transformers does it: render, then cut after the final
            # message's content
            tag = _CONTINUE_TAG.strip()
            if final.strip() not in text or tag not in text:
                raise ValueError("the chat template drops the final message")
            at = text.rindex(tag)
            if text[at:at + len(_CONTINUE_TAG)] == _CONTINUE_TAG:
                text = text[:at]
            else:  # the template trimmed the message's trailing space
                text = text[:at].rstrip()
        return text


class TransformersTokenizer:
    """transformers.AutoTokenizer over a local directory that has no
    ``tokenizer.json``."""

    loader = "transformers"

    def __init__(self, path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(path, local_files_only=True)
        self.vocab_size = len(self._tok)
        eos = self._tok.eos_token_id
        self.eos_token_ids: Tuple[int, ...] = tuple(
            eos if isinstance(eos, (list, tuple)) else [eos] if eos is not None else []
        )

    def encode(self, text: str, add_special_tokens: bool = True) -> List[int]:
        return self._tok.encode(text, add_special_tokens=add_special_tokens)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(list(ids), skip_special_tokens=True)

    def encode_pair(
        self, a: str, b: str, max_len: Optional[int] = None
    ) -> Tuple[List[int], List[int]]:
        """Sentence-pair encoding with the model's own pair template
        (RoBERTa: <s> a </s></s> b </s>; BERT: [CLS] a [SEP] b [SEP] with
        segment ids) — what cross-encoders were trained on. Tokenizer-side
        ``longest_first`` truncation preserves the final special tokens
        (ADVICE r3: tail-slicing silently degraded long-document scores)."""
        kwargs = {}
        if max_len is not None:
            kwargs = {"truncation": "longest_first", "max_length": max_len}
        enc = self._tok(a, b, **kwargs)
        ids = enc["input_ids"]
        types = enc.get("token_type_ids") or [0] * len(ids)
        return ids, types

    def apply_chat_template(
        self,
        messages: List[ChatMessage],
        add_generation_prompt: bool = True,
        continue_final_message: bool = False,
    ) -> str:
        dicts = [{"role": m.role, "content": m.text()} for m in messages]
        kwargs = {"tokenize": False,
                  "add_generation_prompt": add_generation_prompt}
        if continue_final_message:
            # Older transformers silently swallow unknown kwargs into
            # **tokenizer_kwargs — which would render the final turn
            # CLOSED with no error. Verify real support; degrade loudly
            # to the manual template (open turn guaranteed) otherwise.
            import inspect

            params = inspect.signature(
                self._tok.apply_chat_template
            ).parameters
            if "continue_final_message" not in params:
                logger.warning(
                    "tokenizer lacks continue_final_message; rendering "
                    "the continuation with the fallback chat template"
                )
                return _fallback_chat_template(
                    messages, add_generation_prompt, continue_final_message
                )
            kwargs["continue_final_message"] = True
        try:
            return self._tok.apply_chat_template(dicts, **kwargs)
        except Exception:
            return _fallback_chat_template(
                messages, add_generation_prompt, continue_final_message
            )


def get_tokenizer(spec: Optional[str], vocab_size: int = 512) -> Tokenizer:
    """``spec``: local HF dir, or None/"byte" for the byte fallback. A
    directory's files choose its loader: with a ``tokenizer.json`` the
    ``tokenizers`` package alone, without one ``transformers``."""
    if spec and spec != "byte":
        try:
            if os.path.isfile(os.path.join(spec, "tokenizer.json")):
                return HFTokenizer(spec)
            return TransformersTokenizer(spec)
        except Exception as e:
            logger.warning("HF tokenizer load failed (%s); using byte tokenizer", e)
    return ByteTokenizer(vocab_size)
